#!/usr/bin/env python3
"""Chip benchmark: one run of one cell of BENCHMARK.json.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Checks for the chips the cell asks for (no TPU: exit non-zero, no
result), keeps JAX's compile cache in bench/.cache/jax, builds the
cell's configuration with weights from the seed on the device, warms
the cell's own shapes, measures for --seconds, checks what the timed
path produced against the plain reference, and prints one JSON line.
With --trace 1 a profiler trace of the window gives the cell's
per-layer metrics in place of its end-to-end ones.

Everything a cell needs is found by name: the configuration file
(BENCHMARK.json "configs"), bench/traffic/<traffic>.json (its "kind"
picks bench/jobs/<kind>.py), bench/models/<reference>.py and
bench/metrics/<metric>.py.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import common  # noqa: E402


def per_layer_metrics(bench, cell_name: str) -> list:
    """The per-layer metrics whose `workloads` list this cell."""
    return [m for m in bench["per_layer"] if cell_name in m["workloads"]]


def cell_e2e(bench, cell_name: str) -> list:
    return [m["name"] for m in bench["end_to_end"]
            if m.get("workloads") is None or cell_name in m["workloads"]]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = common.benchmark()
    cell = common.find(bench["workloads"], args.workload, "workload")
    cfg_doc = common.config_doc(cell["config"])
    traffic = common.traffic_doc(cell["traffic"])
    device = common.require_chips(cell["chips"])
    common.enable_compile_cache()
    common.add_program_to_path()
    job = common.load_module(common.BENCH / "jobs" / f"{traffic['kind']}.py")
    trace_dir = str(common.BENCH / ".cache" / "trace" / args.workload)
    res = job.run(args, cell, cfg_doc, traffic, T_START, trace_dir)
    units = {m["name"]: m["unit"] for m in bench["end_to_end"]}
    metrics = {}
    breakdown = None
    if args.trace:
        from bench import trace as T
        ctx = res["ctx"]
        ctx.update(chips=cell["chips"], cfg=cfg_doc["model"],
                   cfg_doc=cfg_doc, traffic=traffic,
                   peaks=common.peaks(device["kind"]),
                   reference=common.reference_module(cfg_doc))
        for m in per_layer_metrics(bench, args.workload):
            reader = common.load_module(
                common.BENCH / "metrics" / f"{m['name']}.py")
            value = reader.read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        summary = T.summary(ctx["trace"], cell["chips"])
        device = dict(device, busy_s=summary["busy_s"],
                      window_s=summary["window_s"])
        breakdown = {"device_ops": summary["device_ops"],
                     "idle_gaps": summary["idle_gaps"]}
    else:
        for name in cell_e2e(bench, args.workload):
            metrics[name] = {"value": res["end_to_end"][name],
                             "unit": units[name]}
    device["memory_peak_bytes"] = res["memory_peak_bytes"]
    common.emit(res["correct"], res["attempted"], res["failed"], metrics,
                device, res["checks"], breakdown)


if __name__ == "__main__":
    main()
