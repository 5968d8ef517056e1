"""Runtime construction: model + mesh + policy -> jit-able step functions.

Moved here from `repro.parallel.steps` (which remains as a deprecated
compat shim); `repro.engine.TrainSession` is the public entry point and
`build_runtime` the low-level builder for callers that manage their own
training loop (dry-runs, benchmarks).

train_step anatomy (paper Fig. 3 + §4):
  1. reshape the global batch to `span` lanes; one lane = one Adasum leaf;
  2. vmap(value_and_grad) over lanes — per-lane gradients, TP handled by
     GSPMD from the parameter shardings; when span < dp the per-lane
     gradients are plain sums over the lane's DP group (the paper's
     hierarchical intra-node reduce, emitted as reduce-scatter overlapped
     with backward when `scatter_grads`);
  3. combine lanes with Sum (baseline) or Adasum (pre- or post-optimizer
     per the optimizer kind), RVH backend when span == dp;
  4. apply the combined delta; optimizer state is ZeRO-1-sharded.

Named scopes put the step's parts into every XLA op's `op_name`, so a
device trace can be summed by them: `accumulate` is the microbatch
gradient sum, `optimizer` every optimizer update and application of a
delta (at span > 1 the synchronous path's combine runs inside
`dopt.update`, so inside it too). The model's own layers carry
`attention` / `mamba` / `mlp` (models/transformer.py).

`local_steps > 1` reproduces §5.2 (TensorFlow ResNet-50 on slow TCP):
each lane performs k *local* optimizer steps and the combined quantity is
the model delta since the last sync.

`combine_delay = 1` (DaSGD-style, Zhou et al.) turns that sync from a
barrier into a background stream: round i launches the Adasum exchange
for round i-1's deltas (no data dependency on round i's batch, so XLA
overlaps the per-bucket psum chains with forward/backward), applies the
lane-mean delta immediately, and folds the combined remote correction —
`Adasum(deltas) - lane_mean(deltas)` — into the params at the end of the
round. The in-flight carry lives in `state["pending"]`, so checkpoints
capture it and an (elastic) restart replays the pending exchange instead
of dropping or double-applying it.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.control.noise import summarize_stats
from repro.core.combine import CombineConfig
from repro.core.dist_opt import DistributedOptimizer
from repro.models.api import Model
from repro.optim.optimizers import Optimizer, get_optimizer
from repro.parallel.policy import RunPolicy
from repro.parallel.sharding import ShardingPolicy, param_specs

from .registry import make_combiner

PyTree = Any


class EngineWarning(UserWarning):
    """Non-fatal engine degradations (e.g. backend fallback)."""


@dataclasses.dataclass
class Runtime:
    """Everything the launcher needs for one (arch, mesh) training setup."""
    model: Model
    mesh: jax.sharding.Mesh
    spol: ShardingPolicy
    rpol: RunPolicy
    dp_axes: Tuple[str, ...]
    dp_total: int
    span: int
    pspecs: PyTree
    state_shapes: PyTree
    state_specs: PyTree
    train_step: Callable
    init_state: Callable
    lane_specs: PyTree = None    # payload sharding of one lane's tensors
    gspecs: PyTree = None        # stacked [span, ...] gradient specs
    combine_path: str = ""       # the combiner implementation that will
                                 # actually run (e.g. 'gspmd-fused' vs
                                 # 'gspmd-reference' after a fallback)
    combine_stats: bool = False  # per-step CombineStats metrics emitted
                                 # (grad-noise / orthogonality / gain)
    # delayed-combine split pieces (combine_delay > 0 only): train_step
    # == fold(local_fn, correction_fn(pending)); DelayedCombineStream
    # runs correction_fn on a host thread for observable overlap
    correction_fn: Optional[Callable] = None
    local_fn: Optional[Callable] = None
    fold_fn: Optional[Callable] = None


def _dp_axes(mesh: jax.sharding.Mesh, tp_axis: str) -> Tuple[str, ...]:
    return tuple(ax for ax in mesh.axis_names if ax != tp_axis)


def _prepend(spec: P, entry) -> P:
    return P(entry, *tuple(spec))


def _drop_axes(spec: P, axes) -> P:
    def ent(e):
        if e is None:
            return None
        if isinstance(e, tuple):
            kept = tuple(a for a in e if a not in axes)
            return kept or None
        return None if e in axes else e

    return P(*[ent(e) for e in tuple(spec)])


def _resolve_combine_cfg(rpol: RunPolicy, span: int, dp_total: int,
                         explicit: Optional[CombineConfig],
                         strict: bool) -> CombineConfig:
    """Build the CombineConfig, plumbing every policy knob through, and
    replace the old *silent* rvh -> gspmd_tree fallback with an explicit
    warning (or a hard error under strict mode)."""
    if explicit is not None:
        ccfg = explicit
        requested = explicit.backend
    else:
        # "" = auto: paper-faithful RVH when one lane per DP rank,
        # GSPMD tree for sub-dp spans (hierarchical mode, no warning —
        # the user never asked for rvh)
        requested = rpol.backend or ("rvh" if span == dp_total
                                     else "gspmd_tree")
        ccfg = CombineConfig(
            op=rpol.combine_op, point=rpol.combine_point,
            backend=requested, span=span, per_layer=rpol.per_layer,
            acc_dtype=rpol.acc_dtype, use_pallas=rpol.use_pallas,
            compress=rpol.compress, fused=rpol.fused_combine,
            fusion_threshold_mb=rpol.fusion_threshold_mb)
    if ccfg.op in ("sum", "mean"):
        return ccfg
    if requested == "rvh" and span != dp_total:
        msg = (f"backend='rvh' needs one lane per DP rank "
               f"(span={span}, dp={dp_total}); falling back to "
               f"'gspmd_tree'. Set span=0 (or span={dp_total}) for the "
               f"paper-faithful RVH reduction.")
        if strict:
            raise ValueError(msg)
        warnings.warn(msg, EngineWarning, stacklevel=3)
        ccfg = dataclasses.replace(ccfg, backend="gspmd_tree")
    return ccfg


def plan_lane_specs(cfg, pshapes: PyTree, spol: ShardingPolicy,
                    rpol: RunPolicy, span: int, dp_total: int,
                    dp_axes: Tuple[str, ...]) -> Tuple[PyTree, PyTree]:
    """Lane-gradient/delta sharding plan: (lane_specs, gspecs).

    When span==dp each lane's tensors live on their DP rank (RVH input
    layout: the lane axis carries the DP axes); when span<dp lanes are
    replicated and the tensors are ZeRO-2-scattered over `data` (zpol2).
    Without these pins GSPMD can replicate full-model per-lane deltas,
    which is catastrophic at MoE scale (found via memory_analysis).

    Pure host logic — no mesh or devices needed, which is what lets the
    sharding linter (`repro.analysis.shardlint`) validate the plan over
    the whole (arch x span) space statically."""
    pspecs = param_specs(cfg, pshapes, spol)
    if span == dp_total:
        lane_axes = tuple(dp_axes)        # pod-major lane index (RVH layout)
        # One lane per DP rank: the lane index IS the dp coordinate, so
        # the payload cannot also be FSDP-sharded over dp — keep only the
        # TP axes (the rvh combiner's leaf_specs contract, and a
        # NamedSharding requirement: one mesh axis, one dim). Found by
        # repro.analysis.shardlint: the unstripped spec is rejected by
        # NamedSharding whenever fsdp engages in the RVH regime.
        lane_specs = jax.tree.map(lambda s: _drop_axes(s, set(lane_axes)),
                                  pspecs)
        gspecs = jax.tree.map(lambda s: _prepend(s, lane_axes), lane_specs)
    else:
        zpol2 = dataclasses.replace(
            spol, fsdp_axis="data" if rpol.scatter_grads else spol.fsdp_axis)
        lane_specs = param_specs(cfg, pshapes, zpol2)
        gspecs = jax.tree.map(lambda s: _prepend(s, None), lane_specs)
    return lane_specs, gspecs


def build_runtime(model: Model, mesh: jax.sharding.Mesh, rpol: RunPolicy,
                  *, tp_axis: str = "model", lr=1e-3,
                  combine: Optional[CombineConfig] = None,
                  optimizer: Optional[Optimizer] = None,
                  strict: bool = False) -> Runtime:
    cfg = model.cfg
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    dp_axes = _dp_axes(mesh, tp_axis)
    dp_total = int(np.prod([sizes[a] for a in dp_axes]))
    span = rpol.span or dp_total
    assert dp_total % span == 0, (span, dp_total)
    spol = ShardingPolicy(tp_axis=tp_axis,
                          fsdp_axis="data" if rpol.fsdp else None,
                          tp_size=sizes.get(tp_axis, 1),
                          fsdp_size=sizes.get("data", 1))

    pshapes = jax.eval_shape(model.init, jax.random.key(0))
    pspecs = param_specs(cfg, pshapes, spol)

    ccfg = _resolve_combine_cfg(rpol, span, dp_total, combine, strict)
    # RVH lane order: innermost mesh axis first (adjacent ranks pair first)
    rvh_axes = tuple(reversed(dp_axes))
    delayed = rpol.combine_delay > 0
    assert not (delayed and rpol.accum_steps > 1), (
        "combine_delay needs accum_steps == 1 (the delayed path combines "
        "per-lane optimizer-step deltas; EngineConfig.validate enforces "
        "this at the config layer)")

    lane_specs, gspecs = plan_lane_specs(cfg, pshapes, spol, rpol,
                                         span, dp_total, dp_axes)

    # The combiner sees the stacked lane tensors, so it gets their true
    # payload sharding (lane_specs == pspecs in the RVH regime; the
    # ZeRO-2-scattered specs in the hierarchical span<dp regime) — the
    # fused bucketed path packs local shards along exactly these specs.
    combiner = make_combiner(ccfg, mesh=mesh, dp_axes=rvh_axes,
                             leaf_specs=lane_specs)
    # CombineStats: the combiner's own dot triples, surfaced as per-step
    # metrics (noise scale / lane orthogonality / adascale gain). The
    # stats-enabled combiner runs the SAME combine program — on the
    # fused path the triples ride the per-bucket psums it already
    # issues — so enabling stats never perturbs the update. Scoped to
    # the synchronous paths: the delayed carry's dots describe the
    # previous round's deltas, not this step's gradients.
    scombiner = None
    if rpol.combine_stats and span > 1 and not delayed:
        scombiner = make_combiner(ccfg, mesh=mesh, dp_axes=rvh_axes,
                                  leaf_specs=lane_specs, with_stats=True)
    opt_kwargs = {}
    if rpol.optimizer in ("adam", "lamb"):
        opt_kwargs["state_dtype"] = jnp.dtype(rpol.opt_state_dtype)
    opt = optimizer or get_optimizer(rpol.optimizer, lr, **opt_kwargs)

    to_shardings = lambda specs: jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P))

    dopt = DistributedOptimizer(
        opt, ccfg, combiner, span,
        lane_constraint=lambda d: jax.lax.with_sharding_constraint(
            d, to_shardings(gspecs)),
        delta_constraint=lambda d: jax.lax.with_sharding_constraint(
            d, to_shardings(pspecs)))

    # ---- state shapes + shardings ----
    def init_state_fn(key):
        params = model.init(key)
        state = {"params": params, "opt": dopt.init(params),
                 "step": jnp.zeros((), jnp.int32)}
        if delayed:
            # the in-flight exchange carry: the previous round's stacked
            # lane deltas. Zeros before the first round — Adasum and the
            # lane mean of zeros are both zero (EPS regularization), so
            # the step-0 correction is exactly zero with no cold-start
            # branch in the trace.
            state["pending"] = jax.tree.map(
                lambda p: jnp.zeros((span,) + p.shape, jnp.float32),
                params)
        return state

    state_shapes = jax.eval_shape(init_state_fn, jax.random.key(0))
    # ZeRO-1: optimizer state always (further) scattered over data
    zpol = dataclasses.replace(spol, fsdp_axis="data")
    inner_shapes = state_shapes["opt"]["inner"]
    if dopt.point == "post" and span > 1:
        drop_lane = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype), inner_shapes)
        if span == dp_total:
            # one state per DP rank, living with its lane (paper: per-node
            # optimizer state) — the lane axis IS the distribution, so the
            # payload must not also be FSDP-sharded over dp (same
            # NamedSharding one-axis-one-dim rule plan_lane_specs pins
            # for the lane tensors; with fsdp on, the unstripped spec
            # would name `data` twice).
            ospecs = jax.tree.map(
                lambda s: _drop_axes(s, set(dp_axes)),
                param_specs(cfg, drop_lane, spol))
            lane_entry = tuple(dp_axes)   # pod-major lane index (RVH layout)
        else:
            # lanes replicated; ZeRO-1-scatter the state over `data`.
            ospecs = param_specs(cfg, drop_lane, zpol)
            lane_entry = None
        ospecs = jax.tree.map(lambda s: _prepend(s, lane_entry), ospecs)
    else:
        ospecs = param_specs(cfg, inner_shapes, zpol)
    state_specs = {"params": pspecs,
                   "opt": {"inner": ospecs, "step": P()},
                   "step": P()}
    if delayed:
        # pending deltas are lane-stacked like gradients; checkpoints
        # save/restore them with the rest of the state, and the restore
        # path's reshard_lanes handles a span change across an elastic
        # restart (the pending exchange is replayed, never dropped)
        state_specs["pending"] = gspecs

    init_state = jax.jit(init_state_fn,
                         out_shardings=to_shardings(state_specs))

    # ---- the train step ----
    def split_lanes(batch):
        return jax.tree.map(
            lambda x: x.reshape((span, x.shape[0] // span) + x.shape[1:]),
            batch)

    def lane_loss(p, lb):
        return model.loss(p, lb)

    grad_fn = jax.value_and_grad(lane_loss, has_aux=True)

    def lane_grads(params, lanes):
        """Per-lane gradients, with optional microbatch accumulation
        (paper §2.2 'gradient accumulation'): the lane batch is processed
        in `accum_steps` chunks inside a scan, bounding saved-activation
        memory by 1/A while the gradient sum is carried in fp32."""
        A = rpol.accum_steps
        if A <= 1:
            return jax.vmap(grad_fn, in_axes=(None, 0))(params, lanes)

        acc_dt = jnp.dtype(rpol.accum_dtype)

        def one_lane(lane_batch):
            micro = jax.tree.map(
                lambda x: x.reshape((A, x.shape[0] // A) + x.shape[1:]),
                lane_batch)

            def body(acc, mb):
                (l, m), g = grad_fn(params, mb)
                with jax.named_scope("accumulate"):
                    acc = jax.tree.map(
                        lambda a, gg: (a.astype(jnp.float32)
                                       + gg.astype(jnp.float32)
                                       ).astype(acc_dt),
                        acc, g)
                return acc, (l, m)

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, acc_dt), params)
            gsum, (ls, ms) = jax.lax.scan(body, zeros, micro)
            return (jnp.mean(ls), jax.tree.map(jnp.mean, ms)), gsum

        return jax.vmap(one_lane)(lanes)

    def stat_metrics(stats, batch):
        """CombineStats -> scalar metric dict (lane_rows is static from
        the batch shape, so this traces into the jitted step)."""
        rows = jax.tree.leaves(batch)[0].shape[0]
        return summarize_stats(stats, span, rows // span)

    def sync_step(state, batch):
        params = state["params"]
        lanes = split_lanes(batch)
        (losses, mets), G = lane_grads(params, lanes)
        G = jax.lax.with_sharding_constraint(G, to_shardings(gspecs))
        with jax.named_scope("optimizer"):
            if scombiner is not None:
                delta, opt_state, stats = dopt.update_stats(
                    G, state["opt"], params, scombiner)
            else:
                delta, opt_state = dopt.update(G, state["opt"], params)
                stats = None
            new_params = dopt.apply(params, delta)
        metrics = {k: jnp.mean(v) for k, v in mets.items()}
        metrics["grad_lanes"] = jnp.asarray(span, jnp.int32)
        if stats is not None:
            metrics.update(stat_metrics(stats, batch))
        new_state = {"params": new_params, "opt": opt_state,
                     "step": state["step"] + 1}
        return new_state, metrics

    def local_deltas(params, opt_state, batch):
        """Per-lane local optimizer deltas (paper §5.2): each lane takes
        k = local_steps optimizer steps on its own microbatches. Returns
        (fp32 deltas [span, ...], new inner state, metrics) — the
        metrics carry the mean of the FULL loss dict out of the scan, so
        local-step runs log the same keys sync_step does (the old path
        reported aux as a constant zero)."""
        k = max(rpol.local_steps, 1)
        lanes = split_lanes(batch)   # [span, B/span, ...]
        rows = jax.tree.leaves(lanes)[0].shape[1]
        assert rows % k == 0 and rows >= k, (
            f"local_steps={k} needs global_batch >= span*k "
            f"(got {rows} rows/lane)")
        micro = jax.tree.map(
            lambda x: x.reshape((x.shape[0], k, x.shape[1] // k)
                                + x.shape[2:]), lanes)

        def one_lane(lane_batch, opt_inner):
            def body(carry, mb):
                p, oi, step = carry
                (_, mets), g = grad_fn(p, mb)
                with jax.named_scope("optimizer"):
                    d, oi = dopt.opt.update(g, oi, p, step)
                    p = jax.tree.map(lambda a, b: (a.astype(jnp.float32)
                                                   + b).astype(a.dtype), p, d)
                return (p, oi, step + 1), mets
            (p_end, oi, _), mets = jax.lax.scan(
                body, (params, opt_inner, opt_state["step"]), lane_batch)
            delta = jax.tree.map(
                lambda e, s: e.astype(jnp.float32) - s.astype(jnp.float32),
                p_end, params)
            return delta, oi, jax.tree.map(jnp.mean, mets)

        # micro is [span, k, micro_b, ...]: vmap span, scan k
        if span > 1 and dopt.point == "post":
            deltas, inner, mets = jax.vmap(one_lane)(
                micro, opt_state["inner"])
        else:
            inner_b = jax.tree.map(
                lambda x: jnp.broadcast_to(x, (span,) + x.shape),
                opt_state["inner"])
            deltas, inner, mets = jax.vmap(one_lane)(micro, inner_b)
            inner = jax.tree.map(lambda x: x[0], inner)
        metrics = {name: jnp.mean(v) for name, v in mets.items()}
        metrics["grad_lanes"] = jnp.asarray(span, jnp.int32)
        return deltas, inner, metrics

    def local_sgd_step(state, batch):
        """Paper §5.2: k local optimizer steps, then Adasum of the deltas."""
        deltas, inner, metrics = local_deltas(
            state["params"], state["opt"], batch)
        if scombiner is not None:
            delta, stats = scombiner(deltas)
            metrics.update(stat_metrics(stats, batch))
        else:
            delta = combiner(deltas)
        with jax.named_scope("optimizer"):
            new_params = dopt.apply(state["params"], delta)
        new_state = {"params": new_params,
                     "opt": {"inner": inner,
                             "step": state["opt"]["step"] + rpol.local_steps},
                     "step": state["step"] + 1}
        return new_state, metrics

    # ---- delayed combine (combine_delay = 1, DaSGD-style) ----
    correction_fn = local_only_step = delayed_local_step = None
    if delayed:
        from repro.core.combine import build_delayed_correction, lane_mean
        correction_fn = build_delayed_correction(
            ccfg, mesh=mesh, dp_axes=rvh_axes, leaf_specs=lane_specs)

        def local_only_step(state, batch):
            """The compute half of a delayed round: k local optimizer
            steps per lane, the lane-mean delta applied immediately, and
            the stacked deltas parked as the next round's pending carry.
            The pending correction is NOT consumed here — pair with
            `correction_fn` + `fold_fn` (what both `delayed_local_step`
            and DelayedCombineStream do)."""
            deltas, inner, metrics = local_deltas(
                state["params"], state["opt"], batch)
            local = lane_mean(deltas, ccfg.acc)
            with jax.named_scope("optimizer"):
                new_params = dopt.apply(state["params"], local)
            new_state = {"params": new_params,
                         "opt": {"inner": inner,
                                 "step": state["opt"]["step"]
                                 + max(rpol.local_steps, 1)},
                         "step": state["step"] + 1,
                         "pending": deltas}
            return new_state, metrics

        def delayed_local_step(state, batch):
            """One-round-delayed Adasum: the exchange of the PREVIOUS
            round's deltas (state['pending']) is traced before this
            round's forward/backward and has no data dependency on the
            batch, so XLA schedules its per-bucket psum chains
            concurrently with compute. The lane-mean delta applies
            immediately; the remote correction (combined minus that
            mean) folds into the params at the end of the round, i.e.
            the round AFTER its deltas were produced. Step 0 cold-starts
            on a zero carry — the correction is exactly zero with the
            same trace signature, no cond (the retrace pass pins this)."""
            corr = correction_fn(state["pending"])
            new_state, metrics = local_only_step(state, batch)
            with jax.named_scope("optimizer"):
                new_state["params"] = dopt.apply(new_state["params"], corr)
            return new_state, metrics

    if delayed:
        step_fn = delayed_local_step
    elif rpol.local_steps > 1:
        step_fn = local_sgd_step
    else:
        step_fn = sync_step

    return Runtime(model, mesh, spol, rpol, dp_axes, dp_total, span, pspecs,
                   state_shapes, state_specs, step_fn, init_state,
                   lane_specs=lane_specs, gspecs=gspecs,
                   combine_path=getattr(combiner, "combine_path", ""),
                   combine_stats=scombiner is not None,
                   correction_fn=correction_fn, local_fn=local_only_step,
                   fold_fn=dopt.apply if delayed else None)


def make_serve_step(model: Model, greedy: bool = True):
    """One decode step: tokens [B,1] -> (next token [B,1], cache)."""
    def serve_step(params, tokens, cache):
        logits, cache = model.decode_step(params, tokens, cache)
        nxt = jnp.argmax(logits[:, -1:, :], axis=-1).astype(tokens.dtype)
        return nxt, cache
    return serve_step


def sample_logits(logits: jnp.ndarray, keys: jnp.ndarray, pos: jnp.ndarray,
                  temperature: jnp.ndarray, top_k: jnp.ndarray,
                  top_p: jnp.ndarray) -> jnp.ndarray:
    """Per-row token sampling: [B, V] logits -> [B] int32 tokens.

    Rows with temperature <= 0 take the greedy argmax — bitwise the
    pre-sampling decode path. Sampled rows apply temperature, then top-k
    (k == 0 disables) and nucleus top-p (p >= 1 disables) truncation,
    then a Gumbel-max draw keyed by fold_in(request key, pos): token t of
    a request is a pure function of (seed, t), so decode stays
    reproducible across batch compositions and admission timings.

    keys: [B, 2] uint32 raw PRNG keys (jax.random.PRNGKey rows);
    pos: [B] int32 per-request token positions (generated so far).
    """
    V = logits.shape[-1]
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def row(lg, key, p, t, k, tp):
        lg = lg.astype(jnp.float32) / jnp.maximum(t, 1e-6)
        order = jnp.argsort(-lg)                    # descending
        xs = lg[order]
        ranks = jnp.arange(V)
        xs = jnp.where((k > 0) & (ranks >= k), -jnp.inf, xs)
        probs = jax.nn.softmax(xs)
        cum = jnp.cumsum(probs) - probs             # exclusive prefix mass
        xs = jnp.where((cum < tp) | (tp >= 1.0), xs, -jnp.inf)
        g = jax.random.gumbel(jax.random.fold_in(key, p), (V,))
        return order[jnp.argmax(xs + g)].astype(jnp.int32)

    sampled = jax.vmap(row)(logits, keys, pos, temperature, top_k, top_p)
    return jnp.where(temperature > 0, sampled, greedy)


def make_batched_decode_step(model: Model):
    """Slotted decode step for continuous batching: the cache carries a
    per-slot position vector ([B], from `init_cache(per_slot=True)`), so
    one jitted call advances B requests sitting at *different* sequence
    lengths — each row writes its KV at its own position and masks its
    own length. Rows whose slot is free compute garbage that the next
    admission's prefill insert fully overwrites; shapes never depend on
    the active set, so the scheduler's churn never recompiles."""
    def decode_step(params, tokens, cache):
        logits, cache = model.decode_step(params, tokens, cache)
        nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        return nxt[:, None], cache
    return decode_step


def make_sampling_decode_step(model: Model):
    """`make_batched_decode_step` with per-slot sampling policies: extra
    [B]-shaped key/pos/temperature/top_k/top_p rows select each slot's
    policy (greedy rows stay bitwise-argmax via `sample_logits`). Shapes
    are fixed at [max_slots], so policy churn never recompiles."""
    def decode_step(params, tokens, cache, keys, pos, temperature,
                    top_k, top_p):
        logits, cache = model.decode_step(params, tokens, cache)
        nxt = sample_logits(logits[:, -1, :], keys, pos, temperature,
                            top_k, top_p)
        return nxt[:, None], cache
    return decode_step


def make_verify_step(model: Model):
    """Greedy speculative verification, ONE target dispatch per tick.

    tokens [B, k+1] = [last committed token, draft_1..draft_k]; the
    model's verify_step scores all k+1 positions in one fused forward
    (writing their K/V rows as it goes), then in the SAME jit: per-slot
    greedy argmax g [B, k+1], acceptance = longest prefix of drafts
    matching g, cache pos advanced to pos + accepted + 1 — which both
    commits the accepted rows and rolls back the rejected ones (they
    become masked garbage the next writes overwrite). Shapes are fixed
    at [max_slots, k+1], so slot churn, rollback depth, and hot-reload
    never retrace.

    Returns (next feed token [B,1], greedy tokens [B,k+1], accepted [B],
    cache). Row b commits g[b, :accepted[b]+1]; the next tick feeds
    g[b, accepted[b]] — the last committed token, exactly like plain
    decode."""
    from .serving.slots import set_positions, slot_positions

    def verify(params, tokens, cache):
        pos = slot_positions(cache)
        logits, cache = model.verify_step(params, tokens, cache)
        g = jnp.argmax(logits, axis=-1).astype(jnp.int32)        # [B,k+1]
        match = (g[:, :-1] == tokens[:, 1:]).astype(jnp.int32)
        acc = jnp.sum(jnp.cumprod(match, axis=1), axis=1)        # [B] 0..k
        cache = set_positions(cache, pos + acc + 1)
        nxt = jnp.take_along_axis(g, acc[:, None], axis=1)       # [B,1]
        return nxt, g, acc, cache
    return verify


def make_draft_propose(draft_model: Model, k: int):
    """k autoregressive greedy draft steps in ONE dispatch: a lax.scan
    of batched decode steps over the draft's dense per-slot cache,
    chaining each argmax into the next feed. `pos` [B] (the host's
    committed position per slot) is written into the draft cache first —
    that single rewrite heals last tick's draft overrun (its rejected
    rows become masked garbage this scan overwrites), so draft rollback
    costs nothing and adds no extra dispatch.

    tokens [B,1] = last committed token; returns (drafts [B,k], cache at
    pos + k + 1).

    The scan runs k+1 steps, not k: step t writes the K/V row for its
    INPUT token, so k steps would leave the last draft d_k proposed but
    never fed — a hole at row pos+k. On full acceptance the target
    commits through d_k and the next propose would attend across that
    hole, collapsing acceptance to zero from then on. The extra step
    feeds d_k (its output d_{k+1} is discarded), keeping the draft cache
    contiguous through every accept depth."""
    from .serving.slots import set_positions

    def propose(params, tokens, cache, pos):
        cache = set_positions(cache, pos)

        def body(carry, _):
            tok, cache = carry
            logits, cache = draft_model.decode_step(params, tok, cache)
            nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(
                jnp.int32)[:, None]
            return (nxt, cache), nxt

        (_, cache), drafts = jax.lax.scan(body, (tokens, cache), None,
                                          length=k + 1)
        return jnp.moveaxis(drafts[:k, ..., 0], 0, 1), cache     # [B,k]
    return propose
