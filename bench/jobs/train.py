"""Training cells: `TrainSession.step` on seeded rows, timed for the
window, then checked against the plain reference.

Set-up builds one session (the compiled step and its state), puts the
benchmark's weights from the seed into it, and drives it through steps
1-3 with the window's own call and feed. Those three steps compile the
step and give the readings the reference is held to: each step's loss,
the first gradient as Adam received it (its first moment after one step
over 1 - b1) and the parameters' change after three steps, read before
step 4 takes the state. The window then runs steps 4, 5, ... on the same
session. After the window the session is freed and the reference redoes
the three steps in float32.
"""
from __future__ import annotations

import functools
import gc
import math
import statistics
import time
from typing import Any, Dict, List

from bench import common, trace as T, weights

BATCH_SALT = 0xBA7C


def engine_config(cell, traffic):
    from repro.engine import EngineConfig
    opt = traffic["optimizer"]
    return EngineConfig.preset(
        traffic["preset"], seq_len=traffic["seq_len"],
        global_batch=traffic["global_batch"], optimizer=opt["name"],
        lr=opt["lr"], accum_steps=traffic["accum_steps"],
        data_mesh=cell["chips"], model_mesh=1)


def make_batch_fn(vocab: int, rows: int, seq: int):
    """(seed_pair, step) -> {"tokens", "labels"}: uniform token ids,
    labels the next token. A pure function of the seed and the step."""
    import jax
    import jax.numpy as jnp

    def batch(seed_pair, step):
        key = jax.random.fold_in(weights.seed_key(seed_pair), BATCH_SALT)
        key = jax.random.fold_in(key, step)
        x = jax.random.randint(key, (rows, seq + 1), 0, vocab, jnp.int32)
        return {"tokens": x[:, :-1], "labels": x[:, 1:]}

    return batch


def leaf_norms(tree):
    import jax
    import jax.numpy as jnp
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


# ------------------------------------------------------------ program
class Program:
    """The system under test: one TrainSession and the benchmark's feed."""

    def __init__(self, cell, cfg_doc, traffic, seed: int):
        import jax
        import jax.numpy as jnp
        from repro.configs.base import ModelConfig
        from repro.engine import TrainSession
        from repro.models import build_model
        self.seed_pair = weights.split_seed(seed)
        self.mcfg = ModelConfig(**cfg_doc["model"])
        ecfg = engine_config(cell, traffic)
        model = build_model(self.mcfg,
                            attn_chunk=min(ecfg.attn_chunk, ecfg.seq_len),
                            param_dtype=jnp.dtype(ecfg.param_dtype))
        self.session = TrainSession.from_config(ecfg, model=model,
                                                callbacks=[])
        st = self.session.state
        shapes = jax.eval_shape(lambda: st["params"])
        self.specs = weights.leaf_specs(shapes)
        shard = jax.tree.map(lambda x: x.sharding, st["params"])
        self.gen = jax.jit(weights.make_generator(shapes),
                           out_shardings=shard)
        self.session.state = st = None
        rows = traffic["global_batch"]
        self.batch = jax.jit(make_batch_fn(self.mcfg.vocab_size, rows,
                                           traffic["seq_len"]))
        self.tokens_per_step = rows * traffic["seq_len"]
        self.b1 = traffic["optimizer"]["b1"]
        self._norms = jax.jit(leaf_norms)
        self._delta_norms = jax.jit(
            lambda p, s: leaf_norms(jax.tree.map(lambda a, b: a - b, p,
                                                 self.gen(s))))
        self.reset()

    def reset(self):
        """Fresh state: the program's own initial state (zero optimizer
        state, step 0) with the seed's weights in place of its own."""
        import jax
        state = self.session.runtime.init_state(jax.random.key(0))
        state["params"] = self.gen(self.seed_pair)
        self.session.state = state

    def feed(self, step: int):
        return self.batch(self.seed_pair, step)

    def step(self, step: int) -> float:
        with T.span("train_step"):
            return self.session.step(self.feed(step))["loss"]

    def first_grad_norms(self):
        """Per-leaf norms of the gradient Adam received at step 1, from
        its first moment: m_1 = (1 - b1) g_1."""
        import numpy as np
        m = self.session.state["opt"]["inner"]["m"]
        return np.asarray(self._norms(m)) / (1.0 - self.b1)

    def delta_norms(self):
        import numpy as np
        return np.asarray(self._delta_norms(self.session.state["params"],
                                            self.seed_pair))

    def close(self):
        self.session.close()
        self.session.state = None


def warm_readings(prog: Program) -> Dict[str, Any]:
    """Steps 1-3 through the window's call; the readings to compare."""
    losses = [prog.step(1)]
    grad = prog.first_grad_norms()
    losses += [prog.step(2), prog.step(3)]
    return {"losses": losses, "grad": grad, "delta": prog.delta_norms()}


# ----------------------------------------------------------- reference
def reference_readings(cfg_doc, traffic, seed: int, specs,
                       precision: str = "f32", micro_keep=None
                       ) -> Dict[str, Any]:
    """The plain reference put through the same three steps: float32
    weights from the seed, the same rows, each microbatch's gradient of
    its mean loss summed as the program's accumulation does (taken a row
    at a time, each row weighted 1 / rows per microbatch, to keep the
    activations of one row on the chip), then Adam as the traffic file
    states it. `precision="fp8"` is the control; `micro_keep` plants the
    half-batch fault (only those microbatches, scaled to the full sum)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    ref = common.reference_module(cfg_doc)
    cfg = cfg_doc["model"]
    opt = traffic["optimizer"]
    A = traffic["accum_steps"]
    seed_pair = weights.split_seed(seed)
    paths = [p for p, _, _ in specs]
    batch = jax.jit(make_batch_fn(cfg["vocab_size"], traffic["global_batch"],
                                  traffic["seq_len"]))

    def gen_params(seed_pair):
        key = weights.seed_key(seed_pair)
        out = weights.top_weights(seed_pair, specs, jnp.float32)
        for p, shape, dt in specs:
            if weights.is_stacked(p):
                out[p] = jax.lax.map(
                    lambda l, p=p, shape=shape, dt=dt: weights.layer_leaf(
                        key, p, shape[1:], dt, l).astype(jnp.float32),
                    jnp.arange(shape[0]))
        return out

    tree = jax.tree.map
    grad_fn = jax.jit(jax.value_and_grad(
        lambda p, t, l: ref.loss(p, t[None], l[None], cfg, precision)))
    scaled = jax.jit(lambda g, w: tree(lambda b: w * b, g))
    acc = jax.jit(lambda g, gi, w: tree(lambda a, b: a + w * b, g, gi),
                  donate_argnums=0)
    b1, b2, eps, lr = opt["b1"], opt["b2"], opt["eps"], opt["lr"]

    @functools.partial(jax.jit, donate_argnums=(0, 2, 3))
    def adam(p, g, m, v, t):
        m = tree(lambda m, g: b1 * m + (1 - b1) * g, m, g)
        v = tree(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
        c1, c2 = 1 - b1 ** t, 1 - b2 ** t
        p = tree(lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) + eps),
                 p, m, v)
        return p, m, v

    norms = jax.jit(lambda t: jnp.stack([jnp.sqrt(jnp.sum(t[k] * t[k]))
                                         for k in paths]))
    change = jax.jit(lambda p, s: norms(tree(jnp.subtract, p,
                                             gen_params(s))))
    keep = list(range(A)) if micro_keep is None else list(micro_keep)
    mb = traffic["global_batch"] // A
    w = (A / len(keep)) / mb

    with jax.default_matmul_precision("highest"):
        params = jax.jit(gen_params)(seed_pair)
        zeros = jax.jit(lambda p: tree(jnp.zeros_like, p))
        m, v = zeros(params), zeros(params)
        losses, grad = [], None
        for step in (1, 2, 3):
            b = batch(seed_pair, step)
            g, ls = None, []
            for i in keep:
                for r in range(i * mb, (i + 1) * mb):
                    l, gi = grad_fn(params, b["tokens"][r], b["labels"][r])
                    ls.append(float(l))
                    g = scaled(gi, w) if g is None else acc(g, gi, w)
                    del gi
            if step == 1:
                grad = np.asarray(norms(g))
            params, m, v = adam(params, g, m, v, float(step))
            del g
            losses.append(statistics.fmean(ls))
        del m, v
        delta = np.asarray(change(params, seed_pair))
    return {"losses": losses, "grad": grad, "delta": delta}


def _in_use(device):
    return (device.memory_stats() or {}).get("bytes_in_use")


# ---------------------------------------------------------- comparison
def leaf_gaps(prog, ref):
    """Per leaf: |prog norm - ref norm| over the larger of the ref norm
    of that leaf and the median leaf's (some gradients are all but 0)."""
    import numpy as np
    prog, ref = np.asarray(prog, float), np.asarray(ref, float)
    med = np.median(ref, axis=-1, keepdims=True)
    return np.abs(prog - ref) / np.maximum(np.maximum(ref, med), 1e-30)


def counted_leaves(ref_grad):
    """Leaves whose reference gradient is above a thousandth of the
    median leaf's; the rest move by round-off alone under Adam."""
    import numpy as np
    g = np.asarray(ref_grad, float)
    return g >= 1e-3 * float(np.median(g))


def readings(prog: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, float]:
    """The numbers compared: the relative gap of each step's loss (the
    first alone, and the worst of the three), and the per-leaf gaps of
    the first gradient's norm and of the change's norm after three steps,
    by the worst leaf and by the median leaf, over the counted leaves."""
    import numpy as np
    counted = counted_leaves(ref["grad"])
    g = leaf_gaps(prog["grad"], ref["grad"])[counted]
    d = leaf_gaps(prog["delta"], ref["delta"])[counted]
    loss = [abs(a - b) / abs(b)
            for a, b in zip(prog["losses"], ref["losses"])]
    return {"loss1_gap": float(loss[0]), "loss_gap": float(max(loss)),
            "grad_gap": float(g.max()), "grad_gap_median": float(np.median(g)),
            "update_gap": float(d.max()),
            "update_gap_median": float(np.median(d))}


# ---------------------------------------------------------------- run
def run(args, cell, cfg_doc, traffic, t0: float, trace_dir: str):
    import jax
    counter = common.CompileCounter()
    prog = Program(cell, cfg_doc, traffic, args.seed)
    warm = warm_readings(prog)
    c0 = counter.snapshot()
    tokens = prog.tokens_per_step
    losses: List[float] = []
    step = 4
    ctx: Dict[str, Any] = {}
    tw0 = time.perf_counter()
    tracer = None
    if args.trace:
        tracer = T.Tracer(trace_dir, tw0 + max(
            0.0, args.seconds - traffic["trace_seconds"]))
    traced = 0
    stalls = common.Stalls()
    while True:
        if tracer is not None and not tracer.active and tracer.path is None \
                and time.perf_counter() >= tracer.start:
            tracer.open()
        losses.append(prog.step(step))
        stalls.mark()
        step += 1
        traced += tracer is not None and tracer.active
        if time.perf_counter() - tw0 >= args.seconds:
            break
    tw1 = time.perf_counter()
    if tracer is not None:
        tracer.close()
        ctx["trace"] = T.load(tracer.path)
    setup_s = tw0 - t0
    counter.report(c0)
    stalls.report("step")
    n = len(losses)
    peak = common.memory_peak_bytes(jax.devices()[:cell["chips"]])
    specs = prog.specs
    prog.close()
    del prog
    gc.collect()
    common.log(f"train: {_in_use(jax.devices()[0])} bytes in use on the "
               f"chip before the reference")
    t_ref = time.perf_counter()
    ref = reference_readings(cfg_doc, traffic, args.seed, specs)
    common.log(f"train: reference took {time.perf_counter() - t_ref!r} s")
    got = readings(warm, ref)
    ok, checks = common.judge(got, traffic["limits"])
    failed = sum(1 for l in losses if not math.isfinite(l))
    common.log(f"train: steps={n} window_s={tw1 - tw0!r} "
               f"losses={losses[:3]}... warm_losses={warm['losses']} "
               f"ref_losses={ref['losses']}")
    ctx.update(tokens_traced=traced * tokens, steps_traced=traced)
    end_to_end = {"train_tokens_per_s": n * tokens / (tw1 - tw0),
                  "setup_s": setup_s}
    return {"correct": ok and failed == 0, "attempted": n,
            "failed": failed, "end_to_end": end_to_end, "ctx": ctx,
            "checks": checks, "memory_peak_bytes": peak}
