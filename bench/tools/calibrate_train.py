"""Readings that set a training cell's limits, for many seeds in one
process: the program's sound runs, the control (the reference in the
program's place, computed with float8 matmul operands) and the
half-batch fault planted in the reference put in the program's place.

    python3 bench/tools/calibrate_train.py <cell> <out.jsonl> \
        --seeds 1,2,... [--control-seeds 1,2,3]

Each seed's readings go to <out.jsonl> as one line as soon as they are
known. Runs on the chip, like the benchmark.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import common, weights  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("out")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args()
    bench = common.benchmark()
    cell = common.find(bench["workloads"], args.cell, "workload")
    cfg_doc = common.config_doc(cell["config"])
    traffic = common.traffic_doc(cell["traffic"])
    common.require_chips(cell["chips"])
    common.enable_compile_cache()
    common.add_program_to_path()
    job = common.load_module(common.BENCH / "jobs" / "train.py")
    seeds = [int(s) for s in args.seeds.split(",")]
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    prog = job.Program(cell, cfg_doc, traffic, seeds[0])
    specs = prog.specs
    A = traffic["accum_steps"]
    with open(args.out, "a") as f:
        for seed in seeds:
            prog.seed_pair = weights.split_seed(seed)
            prog.reset()
            warm = job.warm_readings(prog)
            prog.session.state = None
            t0 = time.perf_counter()
            ref = job.reference_readings(cfg_doc, traffic, seed, specs)
            t_ref = time.perf_counter() - t0
            row = {"seed": seed, "program": job.readings(warm, ref),
                   "losses": warm["losses"], "ref_losses": ref["losses"],
                   "reference_s": t_ref,
                   "grad_gap_by_leaf": _by_leaf(specs, warm, ref)}
            if seed in controls:
                ctl = job.reference_readings(cfg_doc, traffic, seed, specs,
                                             precision="fp8")
                half = job.reference_readings(
                    cfg_doc, traffic, seed, specs,
                    micro_keep=range(A // 2))
                row["control"] = job.readings(ctl, ref)
                row["half_batch"] = job.readings(half, ref)
            f.write(json.dumps(row) + "\n")
            f.flush()
            print(json.dumps({k: v for k, v in row.items()
                              if k != "grad_gap_by_leaf"}), flush=True)
    prog.close()


def _by_leaf(specs, warm, ref):
    import numpy as np
    from bench import common
    job = common.load_module(common.BENCH / "jobs" / "train.py")
    g = np.asarray(ref["grad"], float).reshape(-1, len(specs))
    gap = job.leaf_gaps(np.asarray(warm["grad"], float).reshape(g.shape), g)
    return {path: [float(gap[:, i].max()), float(g[:, i].max())]
            for i, (path, _, _) in enumerate(specs)}


if __name__ == "__main__":
    main()
