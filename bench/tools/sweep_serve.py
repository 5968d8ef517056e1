"""Find a serving cell's knee once: the highest offered rate at which 90%
of the requests due in a window meet both latency limits, with no
backlog growing through it. One engine, one process, rates in order.

    python3 bench/tools/sweep_serve.py <cell> --rates 1,2,3 \
        --seconds 20 --ttft-limit-s 2 --tpot-limit-s 0.1 [--seed n]

Each rate runs the cell's mix at that rate for --seconds, then drains
what was sent. One line of JSON per rate. Runs on the chip.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import common  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--ttft-limit-s", type=float, required=True)
    ap.add_argument("--tpot-limit-s", type=float, required=True)
    ap.add_argument("--seed", type=int, default=20261018)
    args = ap.parse_args()
    bench = common.benchmark()
    cell = common.find(bench["workloads"], args.cell, "workload")
    cfg_doc = common.config_doc(cell["config"])
    traffic = common.traffic_doc(cell["traffic"])
    common.require_chips(cell["chips"])
    common.enable_compile_cache()
    common.add_program_to_path()
    import jax
    job = common.load_module(common.BENCH / "jobs" / "serve.py")
    t0 = time.perf_counter()
    prog = job.Program(cfg_doc, traffic, args.seed)
    prog.warm(traffic)
    print(json.dumps({"setup_s": time.perf_counter() - t0,
                      "memory": jax.devices()[0].memory_stats()}),
          flush=True)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        mix = dict(traffic, arrival=dict(traffic["arrival"],
                                         rate_per_s=rate))
        reqs = job.schedule(mix, args.seed + i, args.seconds)
        clock0 = time.perf_counter()
        res = job.drive(prog, reqs, args.seconds, clock0)
        backlog = res["unsent"] + len(prog.engine.scheduler.queue)
        mid = [r for r in reqs if r.due < args.seconds / 2]
        backlog_mid = sum(1 for r in mid if r.handle is None)
        prog.engine.drain()
        sent = [r for r in reqs if r.handle is not None]
        met = 0
        ttft, tpot = [], []
        for r in reqs:
            if r.handle is None or not r.times:
                continue
            a = r.times[0] - (clock0 + r.due)
            b = ((r.times[-1] - r.times[0]) / (len(r.times) - 1)
                 if len(r.times) > 1 else 0.0)
            ttft.append(a)
            tpot.append(b)
            met += a <= args.ttft_limit_s and b <= args.tpot_limit_s
        tokens = sum(len([t for t in r.times if t <= res["end"]])
                     for r in reqs)
        print(json.dumps({
            "rate": rate, "due": len(reqs), "sent": len(sent),
            "attainment": met / max(1, len(reqs)),
            "backlog_end": backlog, "backlog_mid": backlog_mid,
            "tokens_per_s": tokens / (res["end"] - clock0),
            "ttft_p50": common.percentile(ttft, 50),
            "ttft_p95": common.percentile(ttft, 95),
            "tpot_p50": common.percentile(tpot, 50),
            "tpot_p95": common.percentile(tpot, 95),
            "peak_bytes": jax.devices()[0].memory_stats().get(
                "peak_bytes_in_use")}), flush=True)
    prog.close()


if __name__ == "__main__":
    main()
