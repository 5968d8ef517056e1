"""Serving cells: open-loop arrivals into `ServeEngine.submit` / `step`,
greedy decoding, timed from each request's due time; then the served
tokens of a sample of finished requests are checked against the plain
reference.

The schedule (bench/traffic/<mix>.json, read by `schedule`) gives every
seed the same prompt lengths, output lengths and arrival times, drawn at
fixed quantiles of their distributions in an order that the traffic
file fixes; the seed sets the prompts' token ids, and so the tokens
served.

The engine compiles its prefill once per (prompt bucket, number of
prompts admitted together). So that nothing compiles inside the window,
set-up warms every bucket the mix can reach for one prompt, and the
client hands the engine at most one new request per tick (it waits in
the client's queue, and its time-to-first-token counts the wait).
"""
from __future__ import annotations

import collections
import dataclasses
import gc
import math
import statistics
import time
from typing import Any, Dict, List

from bench import common, trace as T, weights

@dataclasses.dataclass
class Req:
    rid: int
    due: float              # seconds after the window opens
    prompt_len: int
    out_len: int
    tokens: Any = None      # prompt ids
    handle: Any = None
    times: List[float] = dataclasses.field(default_factory=list)
    out: List[int] = dataclasses.field(default_factory=list)


# ----------------------------------------------------------- schedule
def quantile_sizes(spec: Dict[str, Any], n: int) -> List[int]:
    """n lengths at the quantiles (i + 1/2) / n of a lognormal with the
    spec's median and sigma, rounded and clipped to [min, max]."""
    nd = statistics.NormalDist()
    mu = math.log(spec["median"])
    out = []
    for i in range(n):
        z = nd.inv_cdf((i + 0.5) / n)
        out.append(int(min(spec["max"], max(spec["min"],
                                            round(math.exp(mu + spec["sigma"] * z))))))
    return out


def schedule(traffic: Dict[str, Any], seed: int, seconds: float
             ) -> List[Req]:
    """The requests of one run, due at Poisson-like times: n = rate x
    seconds gaps at the quantiles of an exponential, and as many prompt
    and output lengths at the quantiles of theirs, each list shuffled
    once by the traffic file's `order_seed`. Every seed gets the same
    sizes at the same times; the seed sets only the prompts' token ids.
    A pure function of the traffic file, the seed and the window's
    length."""
    import numpy as np
    rate = traffic["arrival"]["rate_per_s"]
    n = max(1, int(round(rate * seconds)))
    order = np.random.Generator(np.random.PCG64(
        traffic["arrival"]["order_seed"]))
    gaps = [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]
    gaps = [gaps[i] for i in order.permutation(n)]
    prompts = quantile_sizes(traffic["prompt"], n)
    outs = quantile_sizes(traffic["output"], n)
    prompts = [prompts[i] for i in order.permutation(n)]
    outs = [outs[i] for i in order.permutation(n)]
    rng = np.random.Generator(np.random.PCG64(int(seed) % (1 << 64)))
    vocab = traffic["vocab"]
    reqs, t = [], 0.0
    for i in range(n):
        t += gaps[i]
        if t >= seconds:
            break
        ids = rng.integers(0, vocab, prompts[i], dtype=np.int32)
        reqs.append(Req(i, t, prompts[i], outs[i], ids))
    return reqs


def buckets(traffic) -> List[int]:
    """Every prompt bucket the engine can use for this mix (powers of
    two from 8, clipped to max_len, as its prefill pads)."""
    out, b = [], 8
    lo, hi = traffic["prompt"]["min"], traffic["prompt"]["max"]
    while True:
        if b >= lo:
            out.append(min(b, traffic["max_len"]))
        if b >= hi:
            break
        b *= 2
    return sorted(set(out))


# ------------------------------------------------------------ program
class Program:
    def __init__(self, cfg_doc, traffic, seed: int):
        import jax
        import jax.numpy as jnp
        from repro.configs.base import ModelConfig
        from repro.engine import EngineConfig, ServeEngine
        from repro.models import build_model
        self.seed_pair = weights.split_seed(seed)
        mcfg = ModelConfig(**cfg_doc["model"])
        dtype = cfg_doc["precision"]["params"]
        ecfg = EngineConfig.preset(
            traffic["preset"], param_dtype=dtype,
            max_slots=traffic["max_slots"], max_len=traffic["max_len"],
            page_size=traffic["page_size"], kv_pages=traffic["kv_pages"])
        model = build_model(mcfg, attn_chunk=traffic["attn_chunk"],
                            param_dtype=jnp.dtype(dtype))
        shapes = jax.eval_shape(model.init, jax.random.key(0))
        self.specs = weights.leaf_specs(shapes)
        params = jax.jit(weights.make_generator(shapes))(self.seed_pair)
        self.engine = ServeEngine.from_config(ecfg, model=model,
                                              params=params)
        self.mcfg = mcfg

    def request(self, ids, out_len: int, stream):
        from repro.engine import GenerationRequest
        return GenerationRequest(prompt=ids, max_new_tokens=out_len,
                                 stream=stream)

    def warm(self, traffic):
        """Each bucket once as a one-token request, then decode ticks
        that cross into a fresh page, then the engine runs dry. Prompts
        are distinct random ids: a shared page would send a prompt down
        the prefix-extend path instead of the plain prefill."""
        import numpy as np
        rng = np.random.Generator(np.random.PCG64(0x3A7))
        ids = lambda n: rng.integers(0, traffic["vocab"], n, dtype=np.int32)
        for b in buckets(traffic):
            self.engine.submit(self.request(ids(b), 1, None))
            self.engine.drain()
        ps = traffic["page_size"]
        self.engine.submit(self.request(ids(ps - 2), 4, None))
        self.engine.drain()

    def close(self):
        self.engine.close()
        self.engine = None


def drive(prog: Program, reqs: List[Req], seconds: float, clock0: float,
          tracer=None, stalls=None) -> Dict[str, Any]:
    """The open loop: submit each request when due (one new request per
    tick at most), tick the engine while it has work, sleep otherwise.
    With a `tracer`, the last part of the window is traced, and each
    tick there records the tokens it decoded (not a request's first
    token, which its prefill made) and the cache rows their contexts
    held. With `stalls`, each tick's time is recorded. Returns the
    window's end and the client's lateness."""
    engine = prog.engine
    pending = collections.deque(sorted(reqs, key=lambda r: r.due))
    end = clock0 + seconds
    late, live = [], []
    ticks = None

    def stream_for(r):
        def on_token(handle, token):
            r.times.append(time.perf_counter())
            r.out.append(int(token))
        return on_token

    while True:
        now = time.perf_counter()
        if now >= end:
            break
        if tracer is not None and ticks is None and now >= tracer.start:
            tracer.open()
            ticks = tracer.ticks = []
        if pending and pending[0].due + clock0 <= now \
                and not engine.scheduler.queue:
            r = pending.popleft()
            late.append(now - (r.due + clock0))
            r.handle = engine.submit(prog.request(r.tokens, r.out_len,
                                                  stream_for(r)))
            live.append(r)
        if engine.scheduler.has_work:
            before = [len(r.out) for r in live]
            if stalls is not None:
                stalls.begin()
            with T.span("serve_tick"):
                engine.step()
            if stalls is not None:
                stalls.mark()
            if ticks is not None:
                decoded = rows = 0
                for r, n0 in zip(live, before):
                    for j in range(max(n0, 1), len(r.out)):
                        decoded += 1
                        rows += r.prompt_len + j
                ticks.append((decoded, rows))
            live = [r for r in live if not r.handle.done]
        else:
            nxt = pending[0].due + clock0 if pending else end
            with T.span("wait_for_arrival"):
                time.sleep(max(0.0, min(nxt, end) - time.perf_counter()))
    out = {"end": time.perf_counter(), "late": late,
           "unsent": len(pending)}
    if ticks is not None:
        tracer.close()
    return out


# ------------------------------------------------------------ metrics
def latencies(reqs: List[Req], clock0: float, end: float
              ) -> Dict[str, Any]:
    due = [r for r in reqs if clock0 + r.due < end]
    ttft, tpot, tokens, failed = [], [], 0, 0
    for r in due:
        if r.handle is not None and r.handle.failed:
            failed += 1
            ttft.append(math.inf)
            continue
        t_in = [t for t in r.times if t <= end]
        tokens += len(t_in)
        d = clock0 + r.due
        ttft.append((t_in[0] if t_in else end) - d)
        if len(t_in) >= 2:
            tpot.append((t_in[-1] - t_in[0]) / (len(t_in) - 1))
    return {"due": len(due), "failed": failed, "ttft": ttft, "tpot": tpot,
            "tokens": tokens}


def sample(reqs: List[Req], seed: int, min_tokens: int, max_requests: int,
           min_requests: int = 3) -> List[Req]:
    """Finished requests to check: the one that served most tokens, then
    others in an order the seed sets, until there are min_requests of
    them and min_tokens served tokens, or max_requests requests."""
    import numpy as np
    done = [r for r in reqs if r.handle is not None and r.handle.done
            and not r.handle.failed and r.out]
    if not done:
        return []
    first = max(done, key=lambda r: len(r.out))
    rest = [r for r in done if r is not first]
    rng = np.random.Generator(np.random.PCG64((int(seed) + 1) % (1 << 64)))
    picked, n = [first], len(first.out)
    for i in rng.permutation(len(rest)):
        if (n >= min_tokens and len(picked) >= min_requests) \
                or len(picked) >= max_requests:
            break
        picked.append(rest[i])
        n += len(rest[i].out)
    return picked


def reference_logits(cfg_doc, specs, seed: int, seqs, precision="f32",
                     block: int = 1024):
    """For each (token ids, first row, n rows): the reference's logits at
    those rows, layer by layer over each sequence (right-padded to a
    multiple of `block`), with weights made again from the seed."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    ref = common.reference_module(cfg_doc)
    cfg = cfg_doc["model"]
    seed_pair = weights.split_seed(seed)
    n_layers = cfg["n_layers"]
    layer_w = jax.jit(lambda s, l: weights.layer_weights(
        s, specs, l, jnp.float32))
    top = jax.jit(lambda s: weights.top_weights(s, specs, jnp.float32))(
        seed_pair)
    layer_fn = jax.jit(lambda p, x: ref.layer(p, x, cfg, precision))
    # weights go in as arguments: a closed-over array would be compiled
    # into the program as a constant
    embed = jax.jit(lambda top, t: top["embed/table"][t])
    head = jax.jit(lambda top, x, rows: ref.logits(top, x, rows, cfg,
                                                   precision))
    xs = []
    for ids, _, _ in seqs:
        T = -(-len(ids) // block) * block
        padded = np.zeros(T, np.int32)
        padded[:len(ids)] = ids
        xs.append(embed(top, jnp.asarray(padded)))
    with jax.default_matmul_precision("highest"):
        for l in range(n_layers):
            p = layer_w(seed_pair, l)
            xs = [layer_fn(p, x) for x in xs]
        out = [head(top, x, jnp.arange(r0, r0 + n))
               for x, (_, r0, n) in zip(xs, seqs)]
    return out


def gaps_of(ref_logits, chosen) -> List[float]:
    """Per position: how far below the reference's best logit the chosen
    token's logit lies (0 when it is the reference's own greedy pick)."""
    import numpy as np
    out = []
    for lg, toks in zip(ref_logits, chosen):
        lg = np.asarray(lg, np.float64)
        out.extend((lg.max(-1) - lg[np.arange(len(toks)), toks]).tolist())
    return out


def sequences(picked: List[Req]):
    """(prompt + served tokens but the last, first logit row, rows): row
    prompt_len - 1 + j predicts served token j."""
    import numpy as np
    return [(np.concatenate([r.tokens, np.asarray(r.out[:-1], np.int32)]),
             r.prompt_len - 1, len(r.out)) for r in picked]


# ---------------------------------------------------------------- run
def run(args, cell, cfg_doc, traffic, t0: float, trace_dir: str):
    import jax
    counter = common.CompileCounter()
    prog = Program(cfg_doc, traffic, args.seed)
    prog.warm(traffic)
    reqs = schedule(traffic, args.seed, args.seconds)
    c0 = counter.snapshot()
    ctx: Dict[str, Any] = {
        "phases": common.load_json(common.BENCH / "phases.json")["phases"]}
    clock0 = time.perf_counter()
    tracer = None
    if args.trace:
        tracer = T.Tracer(trace_dir, clock0 + max(
            0.0, args.seconds - traffic["trace_seconds"]))
    stalls = common.Stalls()
    res = drive(prog, reqs, args.seconds, clock0, tracer, stalls)
    if tracer is not None:
        ctx["trace"] = T.load(tracer.path)
        ctx["ticks"] = tracer.ticks
    setup_s = clock0 - t0
    counter.report(c0)
    stalls.report("tick")
    end = res["end"]
    lat = latencies(reqs, clock0, end)
    peak = common.memory_peak_bytes(jax.devices()[:cell["chips"]])
    sent = sum(1 for r in reqs if r.handle is not None)
    done = sum(1 for r in reqs if r.handle is not None and r.handle.done
               and not r.handle.failed)
    late = sorted(res["late"]) or [0.0]
    common.log(f"serve: due={lat['due']} sent={sent} succeeded={done} "
               f"failed={lat['failed']} unsent={res['unsent']} "
               f"tokens={lat['tokens']} window_s={end - clock0!r}")
    common.log(f"serve: client lateness p50={common.percentile(late, 50)!r}"
               f" p99={common.percentile(late, 99)!r} max={late[-1]!r} s")
    picked = sample(reqs, args.seed, **traffic["sample"])
    specs = prog.specs
    prog.close()
    del prog
    gc.collect()
    seqs = sequences(picked)
    ctx["checked"] = (seqs, [r.out for r in picked], specs)
    got = {"logit_gap": math.inf}
    if seqs:
        t_ref = time.perf_counter()
        lg = reference_logits(cfg_doc, specs, args.seed, seqs)
        common.log(f"serve: reference took {time.perf_counter() - t_ref!r} s")
        got["logit_gap"] = max(gaps_of(lg, [r.out for r in picked]))
        common.log(f"serve: checked {len(picked)} requests, "
                   f"{sum(len(r.out) for r in picked)} served tokens")
    ok, checks = common.judge(got, traffic["limits"])
    window = end - clock0
    e2e = {"ttft_p95_ms": 1e3 * common.percentile(lat["ttft"], 95),
           "tpot_p95_ms": 1e3 * common.percentile(lat["tpot"], 95),
           "serve_tokens_per_s": lat["tokens"] / window,
           "setup_s": setup_s}
    return {"correct": ok and lat["failed"] == 0, "attempted": lat["due"],
            "failed": lat["failed"], "end_to_end": e2e, "ctx": ctx,
            "checks": checks, "memory_peak_bytes": peak}
