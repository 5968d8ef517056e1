"""Readings that set a serving cell's limit, seed after seed in one
process: the program's sound runs (a short window at the cell's own load,
whose own sample is checked, after the requests in flight have had --drain-seconds more)
and, on the same prompts and served tokens, the control (the
reference computed with float8 matmul operands, choosing each token).

    python3 bench/tools/calibrate_serve.py <cell> <out.jsonl> \
        --seeds 1,2,... [--seconds 15]

One JSON line per seed. Runs on the chip, like the benchmark.
"""
from __future__ import annotations

import argparse
import gc
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
    ap.add_argument("out")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--drain-seconds", type=float, default=20.0)
    args = ap.parse_args()
    bench = common.benchmark()
    cell = common.find(bench["workloads"], args.cell, "workload")
    cfg_doc = common.config_doc(cell["config"])
    traffic = common.traffic_doc(cell["traffic"])
    common.require_chips(cell["chips"])
    common.enable_compile_cache()
    common.add_program_to_path()
    import numpy as np
    job = common.load_module(common.BENCH / "jobs" / "serve.py")
    with open(args.out, "a") as f:
        for seed in (int(s) for s in args.seeds.split(",")):
            prog = job.Program(cfg_doc, traffic, seed)
            prog.warm(traffic)
            reqs = job.schedule(traffic, seed, args.seconds)
            job.drive(prog, reqs, args.seconds, time.perf_counter())
            stop = time.perf_counter() + args.drain_seconds
            while prog.engine.step() and time.perf_counter() < stop:
                pass
            picked = job.sample(reqs, seed, **traffic["sample"])
            specs = prog.specs
            prog.close()
            del prog
            gc.collect()
            seqs = job.sequences(picked)
            ref = job.reference_logits(cfg_doc, specs, seed, seqs)
            ctl = job.reference_logits(cfg_doc, specs, seed, seqs,
                                       precision="fp8")
            served = job.gaps_of(ref, [r.out for r in picked])
            control = job.gaps_of(ref, [np.asarray(c).argmax(-1)
                                        for c in ctl])
            row = {"seed": seed, "requests": len(picked),
                   "tokens": len(served), "program": max(served),
                   "control": max(control),
                   "program_p99": float(np.percentile(served, 99)),
                   "control_p50": float(np.percentile(control, 50))}
            f.write(json.dumps(row) + "\n")
            f.flush()
            print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
