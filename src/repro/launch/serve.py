"""Request-level serving driver — a thin CLI over `ServeEngine`.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-32b --reduced \
        --requests 3 --prompt-len 32 --gen 16 --max-slots 2 --stagger 2

Submits `--requests` synthetic prompts (lengths jittered around
--prompt-len, arrivals staggered by --stagger decode ticks), drives the
continuous-batching engine to completion, and prints the throughput
fields (`completed=`, `tok_s=`, ...). With --ckpt-dir it serves the
trained weights from the latest checkpoint; add --hot-reload to pick up
new checkpoints mid-stream. `--legacy` runs the old batch-synchronous
`ServeSession.generate` stepped loop instead (same workload) for
comparison.
"""
from __future__ import annotations

import argparse
import sys
import time

import numpy as np
import jax.numpy as jnp

from repro.engine import (EngineConfig, GenerationRequest, ServeEngine,
                          ServeSession)
from repro.launch.compile_cache import enable_compile_cache


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--max-slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=0,
                    help="slot capacity (0 => prompt+gen+1)")
    ap.add_argument("--stagger", type=int, default=1,
                    help="decode ticks between request arrivals")
    ap.add_argument("--prefill-mode", default="auto",
                    choices=["auto", "parallel", "scan"])
    ap.add_argument("--kv-layout", default="paged",
                    choices=["paged", "dense"],
                    help="paged KV arena (default) or dense per-slot "
                    "buffers")
    ap.add_argument("--page-size", type=int, default=16, dest="page_size",
                    help="token rows per KV page")
    ap.add_argument("--kv-pages", type=int, default=0, dest="kv_pages",
                    help="physical pages in the KV arena (0 = enough for "
                    "every slot at full capacity)")
    ap.add_argument("--no-prefix-sharing", action="store_true",
                    help="disable shared-prefix page reuse")
    ap.add_argument("--system-prompt", type=int, default=0,
                    dest="system_prompt",
                    help="prepend this many shared system-prompt tokens "
                    "to every request (exercises prefix sharing)")
    ap.add_argument("--speculation-k", type=int, default=0,
                    dest="speculation_k",
                    help="draft tokens per speculation tick (0 = off); "
                    "greedy requests only")
    ap.add_argument("--draft-preset", default="", dest="draft_preset",
                    help="registry arch for the draft model (default: "
                    "auto-shrunk target)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy argmax (default); >0 samples")
    ap.add_argument("--top-k", type=int, default=0, dest="top_k",
                    help="top-k truncation (0 = off)")
    ap.add_argument("--top-p", type=float, default=1.0, dest="top_p",
                    help="nucleus truncation (1.0 = off)")
    ap.add_argument("--sample-seed", type=int, default=None,
                    dest="sample_seed",
                    help="base sampling seed (default: per request_id)")
    ap.add_argument("--deadline-s", type=float, default=0.0,
                    dest="deadline_s",
                    help="per-request wall-clock deadline in seconds "
                    "(0 = none); past it the request fails terminally")
    ap.add_argument("--max-retries", type=int, default=None,
                    dest="max_retries",
                    help="preemption retry budget per request (default: "
                    "unlimited)")
    ap.add_argument("--pressure-ladder", action="store_true",
                    dest="pressure_ladder",
                    help="graceful degradation under kv/queue pressure: "
                    "shed speculation, pause admissions, preempt")
    ap.add_argument("--ckpt-dir", default="", dest="ckpt_dir")
    ap.add_argument("--hot-reload", action="store_true", dest="hot_reload")
    ap.add_argument("--legacy", action="store_true",
                    help="old ServeSession.generate stepped loop")
    ap.add_argument("--data-mesh", type=int, default=0)
    ap.add_argument("--model-mesh", type=int, default=1)
    args = ap.parse_args(argv)
    enable_compile_cache()

    max_len = args.max_len or (args.system_prompt + args.prompt_len
                               + args.gen + 1)
    if max_len <= args.gen + args.system_prompt:
        ap.error(f"--max-len {max_len} leaves no room for a prompt "
                 f"beyond --system-prompt {args.system_prompt} + --gen "
                 f"{args.gen} tokens")
    draft_config = None
    if args.draft_preset:
        draft_config = {"arch": args.draft_preset, "reduced": args.reduced}
    cfg = EngineConfig(arch=args.arch, reduced=args.reduced,
                       data_mesh=args.data_mesh, model_mesh=args.model_mesh,
                       max_slots=args.max_slots, max_len=max_len,
                       prefill_mode=args.prefill_mode,
                       kv_layout=args.kv_layout, page_size=args.page_size,
                       kv_pages=args.kv_pages,
                       prefix_sharing=not args.no_prefix_sharing,
                       speculation_k=args.speculation_k,
                       draft_config=draft_config,
                       pressure_ladder=args.pressure_ladder,
                       ckpt_dir=args.ckpt_dir,
                       hot_reload=args.hot_reload).validate()
    rng = np.random.RandomState(1)

    from repro.configs.base import get_config, get_reduced
    mcfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    stepped_only = mcfg.is_encoder_decoder or mcfg.frontend != "none"
    if args.legacy or stepped_only:
        if stepped_only and not args.legacy:
            print(f"[serve] {mcfg.name}: frontend/enc-dec archs serve "
                  f"through the stepped batch path")
        session = ServeSession.from_config(cfg)
        mcfg = session.model.cfg
        V = mcfg.vocab_size
        prompts = rng.randint(0, V, (args.requests, args.prompt_len))
        fe = None
        if mcfg.frontend != "none":
            ft = mcfg.frontend_tokens or args.prompt_len
            fe = jnp.zeros((args.requests, ft, mcfg.frontend_dim),
                           jnp.float32)
        t0 = time.perf_counter()
        out = session.generate(jnp.asarray(prompts), args.gen,
                               max_len=max_len, frontend_embeds=fe,
                               stepped_prefill=True)
        wall = time.perf_counter() - t0
        toks = args.requests * args.gen
        print(f"[serve] legacy completed={args.requests} "
              f"generated_tokens={toks} wall_s={wall:.2f} "
              f"tok_s={toks / wall:.1f}")
        print(np.asarray(out)[:, args.prompt_len:])
        return out

    engine = ServeEngine.from_config(cfg)
    V = engine.model.cfg.vocab_size
    if engine.loaded_step is not None:
        print(f"[serve] serving checkpoint step {engine.loaded_step} "
              f"from {cfg.ckpt_dir}")

    def stream(handle, token):
        if len(handle.tokens) == 1:
            dt = handle.first_token_at - handle.submitted_at
            print(f"[serve] req {handle.request.request_id} first token "
                  f"after {dt * 1e3:.0f}ms (slot {handle.slot})")

    system = rng.randint(0, V, args.system_prompt)
    handles = []
    for i in range(args.requests):
        # staggered arrivals at jittered prompt lengths: the continuous-
        # batching case (admit into a running batch, retire independently)
        plen = max(1, min(args.prompt_len + int(rng.randint(-4, 5)),
                          max_len - args.gen - args.system_prompt))
        prompt = np.concatenate([system, rng.randint(0, V, plen)])
        seed = None if args.sample_seed is None else args.sample_seed + i
        handles.append(engine.submit(GenerationRequest(
            prompt=prompt, max_new_tokens=args.gen,
            temperature=args.temperature, top_k=args.top_k,
            top_p=args.top_p, seed=seed, stream=stream,
            deadline_s=args.deadline_s or None,
            max_retries=args.max_retries)))
        for _ in range(args.stagger):
            engine.step()
    engine.drain()

    tp = engine.throughput()
    phases = tp.pop("tick_phase_s")
    lat = {k: tp.pop(k) for k in list(tp)
           if k.startswith(("ttft_", "tpot_"))}
    res = {k: tp.pop(k) for k in
           ("failed", "deadline_kills", "retries", "drained",
            "restore_fallbacks", "degradation_level",
            "degradation_changes", "ladder_preempts") if k in tp}
    fields = " ".join(
        f"{k}={v:.2f}" if isinstance(v, float) else f"{k}={v}"
        for k, v in tp.items())
    print(f"[serve] {fields}")
    print("[serve] resilience " + " ".join(
        f"{k}={v}" for k, v in res.items()))
    if lat:
        print("[serve] latency " + " ".join(
            f"{k[:-2]}_ms={v * 1e3:.1f}" for k, v in lat.items()))
    print("[serve] tick phases " + " ".join(
        f"{k}_s={v:.3f}" for k, v in phases.items()))
    slowest = engine.stats["slowest_tick"]
    if slowest:
        print(f"[serve] slowest tick total_s={slowest['total_s']:.3f} "
              + " ".join(f"{k}_s={v:.3f}"
                         for k, v in slowest["phases"].items()))
    if args.speculation_k:
        kv = engine.kv_stats()
        print(f"[serve] spec k={args.speculation_k} "
              f"ticks={tp.get('spec_ticks', 0)} "
              f"proposed={tp.get('spec_tokens_proposed', 0)} "
              f"accepted={tp.get('spec_tokens_accepted', 0)} "
              f"acceptance={kv.get('spec_acceptance_rate', 0.0):.3f} "
              f"dispatches_per_token="
              f"{tp.get('dispatches_per_token', 0.0):.3f}")
    kv = engine.kv_stats()
    print(f"[serve] kv layout={kv['kv_layout']} "
          f"in_use={kv['kv_bytes_in_use']} peak={kv['peak_kv_bytes_in_use']} "
          f"capacity={kv['kv_capacity_bytes']} "
          f"pages={kv['kv_pages_used']}/{kv['kv_pages_used'] + kv['kv_pages_free']} "
          f"prefix_hits={kv['prefix_hits']} "
          f"prefix_tokens_reused={kv['prefix_tokens_reused']} "
          f"cow={kv['cow_copies']} preemptions={kv['preemptions']}")
    for h in handles:
        print(f"[serve] req {h.request.request_id} "
              f"({h.finish_reason}): {h.tokens}")
    # every submitted request must be terminal (completed or, with
    # deadlines/retry budgets in force, failed) — never hung
    terminal = tp["completed"] + res.get("failed", 0)
    if terminal != args.requests:
        print(f"[serve] ERROR: {terminal}/{args.requests} terminal",
              file=sys.stderr)
        sys.exit(1)
    if tp["completed"] != args.requests and not (
            args.deadline_s or args.max_retries is not None):
        print(f"[serve] ERROR: {tp['completed']}/{args.requests} completed",
              file=sys.stderr)
        sys.exit(1)
    return handles


if __name__ == "__main__":
    main()
