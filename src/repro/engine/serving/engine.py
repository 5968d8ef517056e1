"""ServeEngine — request-level serving over the EngineConfig surface.

    engine = ServeEngine.from_config(
        EngineConfig(arch="qwen3-32b", reduced=True, max_slots=8,
                     max_len=128))
    h = engine.submit(GenerationRequest(prompt, max_new_tokens=32))
    engine.drain()                       # or: while engine.step(): ...
    h.tokens                             # generated ids (streamed too)

Compared to the legacy `ServeSession.generate(prompts, gen_len)` batch
loop this is a different shape of API — requests, not batches:

  * **continuous batching** — a fixed pool of `max_slots` decode slots
    over ONE slotted KV cache (per-slot write positions / length masks);
    requests are admitted the moment a slot frees and retired on
    EOS/budget, with no recompilation as the active set churns;
  * **fused prefill** — the whole prompt runs through one
    `model.prefill_cache` forward (flash-attention path on TPU) instead
    of T sequential jitted `decode_step` dispatches; recurrent-state
    families (mamba/RWKV) use a fused `lax.scan` of decode steps —
    still one dispatch, bitwise-faithful to stepped decode;
  * **checkpoint hot-reload** — params are versioned; a `HotReloader`
    watching a (possibly shared, barrier-protected) CheckpointManager
    swaps in new weights for NEW admissions while in-flight slots keep
    decoding on the version they started with.

The engine is deliberately single-threaded and tick-driven (`step()` =
admit + one batched decode + retire): callers own the concurrency story,
and tests get determinism for free.

KV layout (PR 5): the default is **paged** — attention K/V lives in a
global page arena sized in `page_size`-token pages, slots address it
through int32 page tables, and a host-side `PagePool` allocates on
admission/growth, frees on retirement, copies-on-write shared pages and
evicts cold prefix pages under pressure. Short requests hold only the
pages their tokens occupy (not `max_len` capacity), page-aligned shared
prompt prefixes are mapped read-only onto the same physical pages with
prefill computing only the unshared tail, and when the arena is
undersized (`kv_pages`) the engine preempts the youngest request
vLLM-style (recompute on re-admission — bitwise-identical continuation,
though a preempted request restarts on the CURRENT param version).
Greedy tokens are bitwise-identical to `kv_layout='dense'`; page churn
never changes a device shape, so the no-retrace contract holds.

Tick phases: `step()` runs inside a `repro:serve.step` host span
(`repro.runtime.spans`), and its phases inside `serve.admit` (queue
admission, prefill, first tokens), `serve.grow` (page growth and table
sync), `serve.decode` (the decode dispatch), `serve.pull` (the next
tokens to the host) and `serve.commit` (streaming and retirement). A
profiler trace shows them on the device's clock; `stats["tick_phase_s"]`
sums each phase's seconds over all ticks, and `stats["slowest_tick"]`
keeps the longest tick's total and split.
"""
from __future__ import annotations

import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.runtime.spans import span

from .reload import HotReloader
from .scheduler import (ContinuousBatchingScheduler, GenerationRequest,
                        PrefixIndex, PressureLadder, RequestHandle)
from .slots import (PagePool, cast_paged_like as _cast_paged, copy_pages,
                    dense_fallback_stats, dense_kv_bytes, gather_prefix,
                    insert_rows_at, paged_insert_rows, paged_kv_page_bytes,
                    select_rows, select_rows_paged, set_page_tables)

PyTree = Any

_PREFILL_MODES = ("auto", "parallel", "scan")


def effective_kv_layout(config, model_cfg):
    """The cache layout ServeEngine actually builds for (config, model):
    ('paged' | 'dense', fallback_reason). Recurrent-only families (rwkv)
    have no attention K/V to page, so `kv_layout='paged'` falls back to
    the dense slotted layout — this is THE place that decision lives;
    `__init__` warns on a non-empty reason and the retrace checker
    (`repro.analysis.retrace`) keys its transition enumeration off it."""
    if config.kv_layout != "paged":
        return "dense", ""
    if model_cfg.family == "ssm":
        return "dense", (f"{model_cfg.name} (family=ssm) has no attention "
                         f"K/V to page; serving the dense slotted layout")
    return "paged", ""


def resolve_prefill_mode(config, model) -> str:
    """'parallel' or 'scan' for (config, model), validating the request
    the same way ServeEngine does (shared with the retrace checker)."""
    mode = config.prefill_mode
    if mode not in _PREFILL_MODES:
        raise ValueError(f"prefill_mode={mode!r}; one of {_PREFILL_MODES}")
    if mode == "auto":
        mode = "parallel" if model.prefill_cache is not None else "scan"
    if mode == "parallel" and model.prefill_cache is None:
        raise ValueError(
            f"{model.cfg.name} ({model.cfg.family}) has no parallel "
            f"prefill (recurrent state); use prefill_mode='scan'")
    return mode


def _bucket(n: int, max_len: int) -> int:
    """Prompt padding bucket: next power of two (min 8), clipped to the
    cache capacity — bounds prefill recompilation at log2(max_len)."""
    b = 8
    while b < n:
        b *= 2
    return min(b, max_len)


def resolve_serve_parts(config, *, model=None, mesh=None, params=None,
                        checkpoint=None, attn_chunk: int = 64):
    """Shared ServeEngine/ServeSession bootstrap: local mesh, arch ->
    model (preset head padding), checkpoint manager from ckpt_dir, and
    params — freshly initialized, or the params-only restore of the
    latest checkpoint when one exists. Returns
    (model, mesh, params, checkpoint, loaded_step)."""
    from repro.checkpoint import CheckpointManager
    from repro.configs.base import get_config, get_reduced, pad_heads_for_tp
    from repro.launch.mesh import make_local_mesh
    from repro.models import build_model

    config.validate()
    if mesh is None:
        mesh = make_local_mesh(config.data_mesh or 1, config.model_mesh)
    if model is None:
        if not config.arch:
            raise ValueError("EngineConfig.arch is empty — pass a built "
                             "Model via from_config(model=...)")
        mcfg = (get_reduced(config.arch) if config.reduced
                else get_config(config.arch))
        if config.pad_heads:
            sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
            mcfg = pad_heads_for_tp(mcfg, sizes.get("model", 1))
        model = build_model(mcfg, attn_chunk=attn_chunk,
                            param_dtype=jnp.dtype(config.param_dtype))
    if checkpoint is None and config.ckpt_dir:
        checkpoint = CheckpointManager(config.ckpt_dir)
    loaded_step = None
    if params is None:
        if checkpoint is not None and checkpoint.latest_step() is not None:
            template = jax.eval_shape(model.init, jax.random.key(0))
            loaded_step = checkpoint.latest_step()
            params = checkpoint.restore_params(template, loaded_step)
        else:
            params = model.init(jax.random.key(0))
    return model, mesh, params, checkpoint, loaded_step


def derive_draft_config(target_cfg, spec: Optional[Dict[str, Any]] = None):
    """The draft ModelConfig a speculation-enabled engine builds.

    `spec` (EngineConfig.draft_config) forms:
      * None — auto-derived shrink of the target: quarter depth, MoE
        routing dropped (a draft exists to be cheap), same widths/vocab;
      * {'arch': preset-name[, 'reduced': bool, field overrides]} — a
        registry preset (the --draft-preset CLI path);
      * {field overrides} — dataclasses.replace over the target config.

    Invariants enforced for every form: the draft shares the target's
    vocab (proposals must live in the target's token space), is an
    attention-family decoder (its per-slot cache rolls back by a pos
    rewrite), and runs full attention (sliding_window forced to 0 so the
    dense draft cache masks by pos alone — no rolling wrap to heal)."""
    import dataclasses as _dc
    from repro.configs.base import get_config, get_reduced
    if spec and "arch" in spec:
        extra = {k: v for k, v in spec.items() if k not in ("arch",
                                                            "reduced")}
        dcfg = (get_reduced(spec["arch"]) if spec.get("reduced")
                else get_config(spec["arch"]))
        if extra:
            dcfg = _dc.replace(dcfg, **extra)
    elif spec:
        dcfg = _dc.replace(target_cfg, **spec)
    else:
        dcfg = _dc.replace(target_cfg,
                           name=f"{target_cfg.name}-draft",
                           n_layers=max(1, target_cfg.n_layers // 4),
                           n_experts=0, n_experts_per_tok=0,
                           n_shared_experts=0, first_dense_layers=0)
    if dcfg.sliding_window:
        dcfg = _dc.replace(dcfg, sliding_window=0)
    if dcfg.family in ("ssm", "hybrid") or dcfg.is_encoder_decoder:
        raise ValueError(
            f"draft model {dcfg.name} (family={dcfg.family}) cannot "
            f"draft for speculation: recurrent/enc-dec state has no "
            f"pos-rewrite rollback — pick an attention-family draft")
    if dcfg.vocab_size != target_cfg.vocab_size:
        raise ValueError(
            f"draft vocab_size={dcfg.vocab_size} != target vocab_size="
            f"{target_cfg.vocab_size} ({target_cfg.name}): draft "
            f"proposals must be target token ids")
    return dcfg


def _make_parallel_prefill(model, cap: int):
    """Returns the last-position logits [B, V] (not an argmax'd token):
    the engine applies the per-request sampling policy — greedy argmax
    by default, bitwise the old fused path."""
    def prefill(params, tokens, lengths):
        logits, cache = model.prefill_cache(params, tokens, lengths, cap)
        return logits[:, -1, :], cache
    return prefill


def _steady_cache_dtypes(model, params, batch: int, cap: int):
    """Fixed-point of decode_step's output dtypes: recurrent families
    (mamba conv history, RWKV token shifts) re-emit state in the compute
    dtype, so a freshly-initialized cache can change leaf dtypes after
    the first step. Serving needs the steady layout up front — the decode
    tick must never retrace and the prefill scan carry must be stable —
    and starting there is exact: the initial zeros are representable in
    either dtype. Runs entirely under eval_shape: nothing is allocated
    on device (a paged engine must not spike to the dense footprint it
    exists to avoid)."""
    cache = jax.eval_shape(
        lambda p: model.init_cache(p, batch, cap, per_slot=True), params)
    tok = jax.ShapeDtypeStruct((batch, 1), jnp.int32)
    for _ in range(3):
        new = jax.eval_shape(model.decode_step, params, tok, cache)[1]
        drift = jax.tree.leaves(jax.tree.map(
            lambda c, n: c.dtype != n.dtype, cache, new))
        if not any(drift):
            break
        cache = jax.tree.map(
            lambda c, n: jax.ShapeDtypeStruct(c.shape, n.dtype), cache, new)
    else:
        raise ValueError(f"{model.cfg.name}: decode cache dtypes do not "
                         f"reach a fixed point")
    return jax.tree.map(lambda c: c.dtype, cache)


def _make_scan_prefill(model, cap: int, dtypes):
    """Fused stepped prefill: a lax.scan of decode steps — ONE dispatch
    per prompt (vs T), bitwise-identical math to sequential decode. The
    fused path for recurrent-state families whose chunked training
    forward cannot surrender its state mid-sequence."""
    def prefill(params, tokens, lengths):
        B, P = tokens.shape
        cache0 = jax.tree.map(
            lambda c, dt: c.astype(dt),
            model.init_cache(params, B, cap, per_slot=True), dtypes)
        V = model.cfg.vocab_size
        last0 = jnp.zeros((B, V), jnp.float32)

        def body(carry, t):
            cache, last = carry
            tok = jax.lax.dynamic_slice_in_dim(tokens, t, 1, axis=1)
            logits, new_cache = model.decode_step(params, tok, cache)
            cache = select_rows(t < lengths, new_cache, cache)
            last = jnp.where((t == lengths - 1)[:, None],
                             logits[:, -1, :].astype(jnp.float32), last)
            return (cache, last), None

        (cache, last), _ = jax.lax.scan(body, (cache0, last0),
                                        jnp.arange(P))
        return last, cache
    return prefill


def abstract_serve_state(config, model) -> Dict[str, Any]:
    """Shape-level model of the engine's device state — every field is a
    ShapeDtypeStruct tree obtained under `jax.eval_shape` (nothing ever
    touches a device, not even PRNG key creation).

    Mirrors `ServeEngine.__init__`'s cache construction exactly: steady
    dtypes, paged-vs-dense layout (via `effective_kv_layout`), paged
    arena sizing, and the prefill row signatures the admission path
    scatters in. The retrace checker (`repro.analysis.retrace`) proves
    every slot-churn / page-table / hot-reload transition maps the cache
    signature onto itself, which is what makes the decode tick's
    no-retrace contract a static guarantee."""
    config.validate()
    cfg = model.cfg
    cap = config.serve_max_len()
    B = config.max_slots
    kshape = jax.eval_shape(lambda: jax.random.key(0))
    params = jax.eval_shape(model.init, kshape)
    dtypes = _steady_cache_dtypes(model, params, B, cap)
    layout, fallback_reason = effective_kv_layout(config, cfg)
    pages = None
    if layout == "paged":
        from repro.models.attention import paged_capacity
        ps = config.page_size
        pcap = paged_capacity(cfg, cap)
        if pcap % ps:
            raise ValueError(f"{cfg.name}: paged capacity {pcap} not a "
                             f"multiple of page_size={ps}")
        pages_per_slot = pcap // ps
        num_pages = config.kv_pages or (B * pages_per_slot + 1)
        pages = {"page_size": ps, "pages_per_slot": pages_per_slot,
                 "num_pages": num_pages}
        cache = jax.eval_shape(
            lambda p: _cast_paged(
                model.init_cache(p, B, cap, per_slot=True,
                                 paged=(ps, num_pages)), dtypes), params)
    else:
        cache = jax.eval_shape(
            lambda p: jax.tree.map(lambda c, dt: c.astype(dt),
                                   model.init_cache(p, B, cap,
                                                    per_slot=True), dtypes),
            params)
    mode = resolve_prefill_mode(config, model)
    prefill = (_make_parallel_prefill(model, cap) if mode == "parallel"
               else _make_scan_prefill(model, cap, dtypes))
    P = min(8, cap)
    rows = {}
    for n in sorted({1, B}):
        rows[n] = jax.eval_shape(
            prefill, params, jax.ShapeDtypeStruct((n, P), jnp.int32),
            jax.ShapeDtypeStruct((n,), jnp.int32))[1]
    fallback = (dense_fallback_stats(cache)
                if config.kv_layout == "paged" else (0, 0))
    speculation = None
    if config.speculation_k and model.verify_step is not None:
        from repro.models import build_model as _build_model
        dcfg = derive_draft_config(cfg, config.draft_config)
        dmodel = _build_model(dcfg,
                              param_dtype=jnp.dtype(config.param_dtype))
        dparams = jax.eval_shape(dmodel.init, kshape)
        ddtypes = _steady_cache_dtypes(dmodel, dparams, B, cap)
        dcache = jax.eval_shape(
            lambda p: jax.tree.map(lambda c, dt: c.astype(dt),
                                   dmodel.init_cache(p, B, cap,
                                                     per_slot=True),
                                   ddtypes), dparams)
        drows = {}
        dprefill = _make_parallel_prefill(dmodel, cap)
        for n in sorted({1, B}):
            drows[n] = jax.eval_shape(
                dprefill, dparams,
                jax.ShapeDtypeStruct((n, P), jnp.int32),
                jax.ShapeDtypeStruct((n,), jnp.int32))[1]
        speculation = {"k": config.speculation_k, "draft_model": dmodel,
                       "draft_params": dparams, "draft_cache": dcache,
                       "draft_rows": drows}
    return {"params": params, "cache": cache, "rows": rows,
            "layout": layout, "fallback_reason": fallback_reason,
            "dense_fallback": fallback, "prefill_mode": mode,
            "pages": pages, "max_slots": B, "capacity": cap,
            "speculation": speculation}


class ServeEngine:
    """Continuous-batching serving engine for one (model, mesh, config)."""

    def __init__(self, config, model, mesh, params: PyTree, *,
                 checkpoint=None, loaded_step: Optional[int] = None,
                 draft_params: Optional[PyTree] = None):
        cfg = model.cfg
        if cfg.is_encoder_decoder or cfg.frontend != "none":
            raise ValueError(
                f"ServeEngine serves decoder-only text models; "
                f"{cfg.name} (frontend={cfg.frontend}, "
                f"enc-dec={cfg.is_encoder_decoder}) still goes through "
                f"ServeSession.generate(stepped_prefill=True)")
        self.config = config
        self.model = model
        self.mesh = mesh
        self.max_slots = config.max_slots
        # max_len=0 => seq_len, rounded up to a page multiple when paged
        # (the old bare `max_len or seq_len` default now composes with
        # page_size instead of tripping the tiling assert)
        self.max_len = config.serve_max_len()
        self.scheduler = ContinuousBatchingScheduler(self.max_slots,
                                                     self.max_len)
        mode = self.prefill_mode = resolve_prefill_mode(config, model)

        # versioned params: in-flight slots pin the version they were
        # admitted with; hot-reload bumps _version for new admissions
        self._params: Dict[int, PyTree] = {0: params}
        self._version = 0
        self._loaded_step = loaded_step
        self.checkpoint = checkpoint
        self._reloader: Optional[HotReloader] = None
        if checkpoint is not None and config.hot_reload:
            template = jax.eval_shape(model.init, jax.random.key(0))
            self._reloader = HotReloader(checkpoint, template,
                                         loaded_step=loaded_step)

        # steady-state leaf dtypes: the decode tick never retraces and
        # the prefill paths land rows in exactly this layout (the DENSE
        # per-slot layout — also what every prefill path emits; the
        # paged arena borrows its dtypes leaf-for-leaf)
        self._cache_dtypes = _steady_cache_dtypes(model, params,
                                                  self.max_slots,
                                                  self.max_len)
        # paged KV arena (the default): recurrent-only families (rwkv)
        # have no KV to page and keep the dense slotted layout — loudly
        layout, fallback_reason = effective_kv_layout(config, cfg)
        self.paged = layout == "paged"
        if fallback_reason:
            import warnings
            from ..build import EngineWarning
            warnings.warn(fallback_reason, EngineWarning, stacklevel=3)
        if self.paged:
            from repro.models.attention import paged_capacity
            ps = config.page_size
            cap = paged_capacity(cfg, self.max_len)
            if cap % ps:
                raise ValueError(
                    f"{cfg.name}: paged cache capacity {cap} (sliding "
                    f"window {cfg.sliding_window}) is not a multiple of "
                    f"page_size={ps}; pick a page size dividing the "
                    f"window so paged rows tile pages exactly "
                    f"(kv_layout='dense' always works)")
            self._page_size = ps
            self._pages_per_slot = cap // ps
            # full provisioning: every slot at capacity + the trash page.
            # kv_pages can size the arena down (backpressure + preemption
            # kick in) or up (a larger warm prefix cache).
            full = self.max_slots * self._pages_per_slot + 1
            self._num_pages = config.kv_pages or full
            if self._num_pages < self._pages_per_slot + 1:
                raise ValueError(
                    f"kv_pages={self._num_pages} cannot hold even one "
                    f"full slot: capacity {cap} needs "
                    f"{self._pages_per_slot} pages of {ps} tokens plus "
                    f"the reserved trash page "
                    f"(>= {self._pages_per_slot + 1})")
            self._pool = PagePool(self._num_pages, ps)
            share = (config.prefix_sharing and self.prefill_mode == "parallel"
                     and not cfg.sliding_window)
            self._prefix = PrefixIndex(ps) if share else None
            self._tables = np.zeros((self.max_slots, self._pages_per_slot),
                                    np.int32)
            self._owned = np.zeros_like(self._tables, bool)
            self._shared = np.zeros_like(self._tables, bool)
            self._tables_dirty = False
            self._host_pos = np.zeros((self.max_slots,), np.int64)
            self._admit_seq = np.zeros((self.max_slots,), np.int64)
            self._seq = 0
            self.cache = _cast_paged(
                model.init_cache(params, self.max_slots, self.max_len,
                                 per_slot=True,
                                 paged=(ps, self._num_pages)),
                self._cache_dtypes)
            self._page_bytes = paged_kv_page_bytes(self.cache)
            self._kv_capacity_bytes = (self._num_pages - 1) * self._page_bytes
            self._paged_insert = jax.jit(paged_insert_rows)
            self._set_tables = jax.jit(set_page_tables)
            self._copy_pages = jax.jit(copy_pages)
            self._select_paged = jax.jit(select_rows_paged)
            self._gather_prefix = jax.jit(gather_prefix)
        else:
            self.cache = jax.tree.map(
                lambda c, dt: c.astype(dt),
                model.init_cache(params, self.max_slots, self.max_len,
                                 per_slot=True), self._cache_dtypes)
            self._page_bytes = 0
            self._kv_capacity_bytes = dense_kv_bytes(self.cache)
            self._pool = None
            self._prefix = None
        # paged-accounting honesty: per-slot state that stays dense even
        # though paging was requested (mamba recurrent state in hybrids;
        # the whole cache under the ssm fallback). Surfaced in kv_stats.
        self._dense_fallback_leaves = 0
        self._dense_fallback_bytes = 0
        if config.kv_layout == "paged":
            self._dense_fallback_leaves, self._dense_fallback_bytes = \
                dense_fallback_stats(self.cache)
            if self.paged and self._dense_fallback_leaves:
                import warnings
                from ..build import EngineWarning
                warnings.warn(
                    f"{cfg.name}: {self._dense_fallback_leaves} cache "
                    f"leaves ({self._dense_fallback_bytes} bytes) stay "
                    f"dense per-slot under kv_layout='paged' (recurrent "
                    f"state has no K/V rows to page); paged byte "
                    f"accounting excludes them — see "
                    f"kv_stats()['dense_fallback_leaves']",
                    EngineWarning, stacklevel=3)
        self._tokens = np.zeros((self.max_slots, 1), np.int32)
        # per-slot sampling policy rows (fixed [max_slots] shapes: policy
        # churn never retraces). Greedy slots (temperature 0) take the
        # bitwise argmax path; the all-greedy tick skips sampling math
        # entirely via the plain decode step.
        self._temp = np.zeros((self.max_slots,), np.float32)
        self._topk = np.zeros((self.max_slots,), np.int32)
        self._topp = np.ones((self.max_slots,), np.float32)
        self._keys = np.zeros((self.max_slots, 2), np.uint32)
        self._pos = np.zeros((self.max_slots,), np.int32)
        # NOTE: no buffer donation — hot-reload may decode the same cache
        # under two param versions in one tick
        from ..build import (make_batched_decode_step,
                             make_sampling_decode_step, sample_logits)
        self._decode = jax.jit(make_batched_decode_step(model))
        self._decode_sampled = jax.jit(make_sampling_decode_step(model))
        self._sample = jax.jit(sample_logits)
        self._argmax = jax.jit(
            lambda lg: jnp.argmax(lg, axis=-1).astype(jnp.int32))
        self._insert = jax.jit(insert_rows_at)
        self._select = jax.jit(select_rows)
        self._prefill = jax.jit(
            _make_parallel_prefill(model, self.max_len) if mode == "parallel"
            else _make_scan_prefill(model, self.max_len,
                                    self._cache_dtypes))
        if self.paged and self._prefix is not None:
            # shared-prefix extend: one forward over the UNSHARED TAIL
            # only, attending to the gathered prefix pages. Compiles per
            # (tail bucket, prefix page count) pair — prefixes are few
            # (system prompts); the decode tick itself never retraces.
            def _ext(params, toks, lengths, pfx, prefix_len):
                logits, rows = model.prefill_cache(
                    params, toks, lengths, self.max_len,
                    prefix_kv=pfx, prefix_len=prefix_len)
                return logits[:, -1, :], rows
            self._prefill_ext = jax.jit(_ext,
                                        static_argnames=("prefix_len",))
        # ---- speculative decoding: draft propose -> one-forward verify
        self.spec_k = int(config.speculation_k or 0)
        self._draft_model = None
        if self.spec_k and model.verify_step is None:
            import warnings
            from ..build import EngineWarning
            warnings.warn(
                f"{cfg.name} (family={cfg.family}): recurrent state has "
                f"no pos-rewrite rollback — speculation disabled, every "
                f"tick runs plain decode", EngineWarning, stacklevel=3)
            self.spec_k = 0
        if self.spec_k:
            from repro.models import build_model as _build_model
            from repro.models.attention import paged_capacity
            from ..build import make_draft_propose, make_verify_step
            dcfg = derive_draft_config(cfg, config.draft_config)
            self._draft_model = _build_model(
                dcfg, attn_chunk=64,
                param_dtype=jnp.dtype(config.param_dtype))
            self._draft_params = (draft_params if draft_params is not None
                                  else self._draft_model.init(
                                      jax.random.key(1)))
            ddtypes = _steady_cache_dtypes(self._draft_model,
                                           self._draft_params,
                                           self.max_slots, self.max_len)
            # the draft cache is DENSE per-slot by design: drafts are
            # small, their rows are transient (rolled back by the next
            # propose's pos rewrite), and paging them would double the
            # host bookkeeping for no memory story
            self._draft_cache = jax.tree.map(
                lambda c, dt: c.astype(dt),
                self._draft_model.init_cache(self._draft_params,
                                             self.max_slots, self.max_len,
                                             per_slot=True), ddtypes)
            self._draft_prefill = jax.jit(
                _make_parallel_prefill(self._draft_model, self.max_len))
            self._propose = jax.jit(
                make_draft_propose(self._draft_model, self.spec_k))
            self._verify = jax.jit(make_verify_step(model))
            # spec-tick feasibility ceiling: pos + k must stay BELOW the
            # rolling capacity for every active slot, so verify writes
            # land at rows pos+t exactly (no wrap/clamp) and rollback is
            # a pure pos rewrite. SWA targets stop speculating once the
            # window fills; everyone stops within k of max_len.
            self._spec_cap = paged_capacity(cfg, self.max_len)
        self._ttft: List[float] = []
        self._tpot: List[float] = []
        # graceful degradation (opt-in): the pressure ladder watches
        # page-pool and queue pressure each tick and sheds load in
        # stages instead of thrashing on preemptions
        self._ladder = PressureLadder() if config.pressure_ladder else None
        self._draining = False
        self.stats = {"submitted": 0, "completed": 0, "generated_tokens": 0,
                      "prefill_calls": 0, "decode_steps": 0, "reloads": 0,
                      "kv_bytes_in_use": 0, "peak_kv_bytes_in_use": 0,
                      "kv_pages_used": 0, "kv_pages_free": (
                          self._pool.pages_free if self._pool else 0),
                      "prefix_hits": 0, "prefix_tokens_reused": 0,
                      "cow_copies": 0, "preemptions": 0,
                      "spec_ticks": 0, "spec_tokens_proposed": 0,
                      "spec_tokens_accepted": 0, "draft_prefills": 0,
                      "failed": 0, "deadline_kills": 0, "retries": 0,
                      "drained": 0, "restore_fallbacks": 0,
                      "degradation_level": 0, "degradation_changes": 0,
                      "ladder_preempts": 0,
                      "tick_phase_s": {}, "slowest_tick": None,
                      "started_at": None}
        if not self.paged:
            # dense slots pay full capacity up front — that constant IS
            # the footprint (what paging exists to beat)
            self.stats["kv_bytes_in_use"] = self._kv_capacity_bytes
            self.stats["peak_kv_bytes_in_use"] = self._kv_capacity_bytes

    # ------------------------------------------------------- construction
    @classmethod
    def from_config(cls, config, *, model=None, mesh=None, params=None,
                    checkpoint=None, attn_chunk: int = 64,
                    draft_params=None) -> "ServeEngine":
        """Build model/mesh/params from the same EngineConfig surface as
        TrainSession; with `ckpt_dir` set, serves the *trained* weights
        via the params-only restore (and hot-reloads later saves when
        `hot_reload=True`). `draft_params`: trained weights for the
        speculation draft model (default: fresh init — correct but low
        acceptance; speculation pays off with a draft that agrees with
        the target)."""
        model, mesh, params, checkpoint, loaded_step = resolve_serve_parts(
            config, model=model, mesh=mesh, params=params,
            checkpoint=checkpoint, attn_chunk=attn_chunk)
        return cls(config, model, mesh, params, checkpoint=checkpoint,
                   loaded_step=loaded_step, draft_params=draft_params)

    # ------------------------------------------------------------- submit
    def submit(self, request: GenerationRequest) -> RequestHandle:
        """Enqueue a request; it is admitted to a slot by a later
        `step()`. Raises immediately if it can never fit a slot."""
        handle = RequestHandle(request)
        self.scheduler.submit(handle)
        self.stats["submitted"] += 1
        if self.stats["started_at"] is None:
            self.stats["started_at"] = time.perf_counter()
        return handle

    # ------------------------------------------------------------- params
    def swap_params(self, params: PyTree, step: Optional[int] = None):
        """Hot-swap: new admissions decode with `params`; slots already
        in flight finish on their admitted version."""
        self._version += 1
        self._params[self._version] = params
        self._loaded_step = step
        self.stats["reloads"] += 1
        # registered prefix pages hold K/V computed under the OLD
        # weights — flush them so new admissions re-prefill under the
        # new version (pages still referenced by in-flight old-version
        # slots survive until those slots retire)
        self.flush_prefix()

    def flush_prefix(self) -> int:
        """Release every prefix-index page reference; returns the number
        of pages flushed. Hot-reload calls this; the chaos soak uses it
        before asserting the zero-leaked-pages invariant."""
        n = 0
        if self._prefix is not None:
            while True:
                pid = self._prefix.evict_lru()
                if pid is None:
                    break
                self._pool.release([pid])
                n += 1
        return n

    def _gc_versions(self):
        live = {h.version for h in self.scheduler.active.values()}
        live.add(self._version)
        for v in [v for v in self._params if v not in live]:
            del self._params[v]

    @property
    def params(self) -> PyTree:
        """The params new admissions will see."""
        return self._params[self._version]

    @property
    def loaded_step(self) -> Optional[int]:
        return self._loaded_step

    # --------------------------------------------------------------- tick
    def step(self) -> bool:
        """One scheduler tick: deadline enforcement -> hot-reload poll
        -> pressure-ladder update -> admit (fused prefill; paged
        admission reserves pages, shared prefixes prefill only the
        unshared tail) -> one batched decode over the active slots (paged
        growth/COW first) -> retire finished. Returns True while queued
        or in-flight work remains."""
        tick: Dict[str, float] = {}
        with span("serve.step", tick):
            more = self._tick(tick)
        self._record_tick(tick)
        return more

    def _record_tick(self, tick: Dict[str, float]):
        total = tick.pop("serve.step")
        phases = self.stats["tick_phase_s"]
        for name, seconds in tick.items():
            phases[name] = phases.get(name, 0.0) + seconds
        slowest = self.stats["slowest_tick"]
        if slowest is None or total > slowest["total_s"]:
            self.stats["slowest_tick"] = {"total_s": total, "phases": tick}

    def _tick(self, tick: Dict[str, float]) -> bool:
        self._enforce_deadlines()
        if self._reloader is not None:
            got = self._reloader.poll()
            if got is not None:
                self.swap_params(got[1], step=got[0])
            self.stats["restore_fallbacks"] = self._reloader.fallbacks
        level = 0
        if self._ladder is not None:
            free_frac = 1.0
            if self.paged:
                free_frac = (self._pool.pages_free
                             / max(1, self._num_pages - 1))
            level = self._ladder.update(
                free_frac=free_frac, queue_len=len(self.scheduler.queue),
                max_slots=self.max_slots)
            self.stats["degradation_level"] = level
            self.stats["degradation_changes"] = self._ladder.changes
        # admissions stop while draining, and at ladder level >= 2 while
        # anything is in flight (an empty active set must still admit —
        # pausing then would deadlock the queue against a full pool)
        blocked = self._draining or (level >= 2 and self.scheduler.active)
        if not blocked:
            with span("serve.admit", tick):
                admitted = self.scheduler.admit(
                    self._reserve_pages if self.paged else None)
                if admitted:
                    self._admit_batch(admitted)
        if (level >= 3 and self.paged and self._pool.pages_free == 0
                and len(self.scheduler.active) > 1):
            # preempt-by-recompute rung: free the youngest slot's pages
            # proactively so the older slots can keep growing
            if self._preempt_youngest(None):
                self.stats["ladder_preempts"] += 1
        if self.scheduler.active:
            self._decode_tick(tick)
        if self._draining and not self.scheduler.active:
            # active set drained: queued requests end terminally (never
            # hung) with finish_reason 'drained'
            for h in list(self.scheduler.queue):
                self.scheduler.fail(h, "drained")
                self.stats["failed"] += 1
                self.stats["drained"] += 1
        self._gc_versions()
        if self.paged:
            used = self._pool.pages_used
            b = used * self._page_bytes
            self.stats["kv_bytes_in_use"] = b
            self.stats["peak_kv_bytes_in_use"] = max(
                self.stats["peak_kv_bytes_in_use"], b)
            self.stats["kv_pages_used"] = used
            self.stats["kv_pages_free"] = self._pool.pages_free
        return self.scheduler.has_work

    def drain(self) -> None:
        """Run ticks until every submitted request is terminal."""
        while self.step():
            pass

    # --------------------------------------------------------- resilience
    def _enforce_deadlines(self):
        """Fail every queued/running request past its deadline_s budget
        (terminal finish_reason 'deadline'; a running slot's pages are
        released first). Requests without a deadline are untouched."""
        now = time.perf_counter()
        for h in self.scheduler.expired(now):
            if h.slot is not None and self.paged:
                self._release_slot_pages(h.slot)
            self.scheduler.fail(h, "deadline")
            self.stats["deadline_kills"] += 1
            self.stats["failed"] += 1

    def request_drain(self):
        """Graceful-drain mode (SIGTERM): no new admissions; in-flight
        slots decode to completion; once the active set empties, queued
        requests fail terminally with finish_reason 'drained'. `drain()`
        then falls through — no request is ever left hanging."""
        self._draining = True

    @property
    def draining(self) -> bool:
        return self._draining

    def install_drain_handler(self):
        """SIGTERM => request_drain(): the serve-side analogue of the
        checkpoint preemption handler (train already exits through one).
        The process keeps running until the caller's drain loop ends."""
        import signal

        def handler(signum, frame):
            print("[serve] SIGTERM: draining (no new admissions; "
                  "in-flight requests finish)")
            self.request_drain()
        signal.signal(signal.SIGTERM, handler)

    def leaked_pages(self) -> int:
        """Pages the pool holds that no active slot and no prefix-index
        entry accounts for. After a drain (empty active set) and a
        `flush_prefix()`, this must be exactly `pages_used` == 0 — the
        zero-leak invariant the chaos soak asserts."""
        if not self.paged:
            return 0
        pids = set()
        for slot in self.scheduler.active:
            mask = self._owned[slot] | self._shared[slot]
            pids.update(int(p) for p in self._tables[slot][mask])
        if self._prefix is not None:
            pids.update(self._prefix.pages())
        return self._pool.pages_used - len(pids)

    # ------------------------------------------------------ paged plumbing
    def _full_prompt(self, handle) -> np.ndarray:
        """Prompt plus any already-generated tokens: preempted requests
        re-prefill their whole trajectory (recompute preemption), which
        continues decode bitwise-identically."""
        if not handle.tokens:
            return handle.request.prompt
        return np.concatenate([handle.request.prompt,
                               np.asarray(handle.tokens, np.int32)])

    def _prompt_pages(self, n_tokens: int) -> int:
        """Pages the prefill of an n-token prompt touches (rolling SWA
        prompts longer than the window only ever occupy the window)."""
        return min(-(-n_tokens // self._page_size), self._pages_per_slot)

    def _evict_until(self, n_free: int) -> bool:
        """Drop cold prefix-index entries (LRU, leaf pages first) until
        `n_free` pages are available. Only pages nothing else references
        are candidates — evicting an entry whose page an active (or
        reserving) slot still holds frees nothing and would just cold
        the cache."""
        while self._pool.pages_free < n_free:
            if self._prefix is None:
                return False
            pid = self._prefix.evict_lru(
                lambda p: self._pool.refcount(p) == 1)
            if pid is None:
                return False
            self._pool.release([pid])
        return True

    def _reserve_pages(self, handle) -> bool:
        """Admission gate + reservation: match the prompt against the
        prefix index (read-only reuse), then allocate pages for the
        unshared tail — evicting cold prefix pages if needed. Declines
        (request stays queued, FIFO) when the pool cannot cover it."""
        prompt = self._full_prompt(handle)
        shared: List[int] = []
        if self._prefix is not None:
            shared = self._prefix.match(prompt)[:self._pages_per_slot]
        # pin the matched pages FIRST: with this reference held, evicting
        # their index entries can never free them, so the allocation below
        # cannot hand a matched page back as this slot's own page
        # (aliasing a shared table entry with an owned one)
        self._pool.ref(shared)
        n_own = self._prompt_pages(len(prompt)) - len(shared)
        own = self._pool.alloc(n_own) if self._evict_until(n_own) else None
        if own is None:
            self._pool.release(shared)
            return False
        if self._prefix is not None:
            # register this prompt's own full pages NOW — at reservation,
            # not after prefill — so a SAME-TICK co-arrival with the same
            # page-aligned prefix matches them above and joins the
            # extend-prefill path (first-contact grouping: the leader
            # prefills the full prompt once, followers prefill only their
            # tails against the leader's pages). The index holds one pool
            # ref per newly registered page; admission-group ordering
            # guarantees the leader's prefill lands before any follower
            # gathers the prefix.
            newly = self._prefix.register(prompt, own, start=len(shared))
            self._pool.ref(newly)
        handle._admit_plan = (prompt, shared, own)
        return True

    def _release_slot_pages(self, slot: int):
        """Drop this slot's page references (owned AND shared); pages
        the prefix index still holds survive for future reuse."""
        mask = self._owned[slot] | self._shared[slot]
        if mask.any():
            self._pool.release(self._tables[slot][mask].tolist())
        self._tables[slot] = 0
        self._owned[slot] = False
        self._shared[slot] = False
        self._tables_dirty = True

    def _preempt_youngest(self, keep_slot: Optional[int]) -> bool:
        """Pool pressure: push the most recently admitted request (other
        than `keep_slot`; None keeps nothing) back to the queue front,
        freeing its pages. It re-prefills prompt+generated on
        re-admission — same tokens, but on the CURRENT param version. A
        request over its `max_retries` budget fails terminally instead
        of requeueing (finish_reason 'retries')."""
        others = [s for s in self.scheduler.active if s != keep_slot]
        if not others:
            return False
        victim = max(others, key=lambda s: self._admit_seq[s])
        handle = self.scheduler.active[victim]
        self._release_slot_pages(victim)
        self.scheduler.preempt(victim)
        self.stats["preemptions"] += 1
        if handle.failed:
            self.stats["failed"] += 1
        else:
            self.stats["retries"] += 1
        return True

    def _claim_page(self, slot: int, lp: int):
        """Make logical page `lp` of `slot` writable: allocate a fresh
        page (growth) or copy-on-write a shared one, evicting/preempting
        under pressure."""
        while not (self._evict_until(1) and self._pool.pages_free >= 1):
            if not self._preempt_youngest(slot):
                raise RuntimeError(
                    f"page pool exhausted growing slot {slot} "
                    f"(kv_pages={self._num_pages}): no evictable prefix "
                    f"pages and no other request to preempt")
        if self._shared[slot, lp]:
            old = int(self._tables[slot, lp])
            new = self._pool.cow(old)     # cannot fail: a page is free
            self.cache = self._copy_pages(self.cache,
                                          jnp.asarray([old]),
                                          jnp.asarray([new]))
            self._shared[slot, lp] = False
            self.stats["cow_copies"] += 1
        else:
            (new,) = self._pool.alloc(1)
        self._tables[slot, lp] = new
        self._owned[slot, lp] = True
        self._tables_dirty = True

    def _grow_active(self):
        """Before a decode tick: every active slot must own the page its
        next token writes into. Fresh pages for linear growth; COW when
        a (forced-)shared page would be written; preemption as the last
        resort. May shrink the active set."""
        cap = self._pages_per_slot * self._page_size
        for slot in sorted(self.scheduler.active):
            if slot not in self.scheduler.active:   # preempted meanwhile
                continue
            p = int(self._host_pos[slot])
            rolling = self.model.cfg.sliding_window > 0
            row = p % cap if rolling else min(p, cap - 1)
            lp = row // self._page_size
            if not self._owned[slot, lp]:
                self._claim_page(slot, lp)

    def _sync_tables(self):
        if self._tables_dirty:
            self.cache = self._set_tables(self.cache,
                                          jnp.asarray(self._tables))
            self._tables_dirty = False

    # ----------------------------------------------------------- internals
    def _admit_batch(self, admitted):
        """Fused prefill for this tick's admissions, grouped by prompt
        bucket (and, when paged, by shared-prefix chain): one prefill
        dispatch + one cache scatter per group (not per request) — the
        batched-arrival fast path. Shared-prefix groups gather the
        prefix K/V from its pages once and prefill ONLY the unshared
        tail."""
        groups: Dict[Any, list] = {}
        plans: Dict[int, Any] = {}
        for slot, handle in admitted:
            handle.version = self._version
            req = handle.request
            self._temp[slot] = req.temperature
            self._topk[slot] = req.top_k
            self._topp[slot] = req.top_p
            self._keys[slot] = np.asarray(
                jax.random.PRNGKey(req.sampling_seed), np.uint32)
            # sampling position continues across preemption: token t is
            # a pure function of (seed, t)
            self._pos[slot] = len(handle.tokens)
            if self.paged:
                prompt, shared, own = handle._admit_plan
                del handle._admit_plan
                plans[slot] = (prompt, shared, own)
                n_sh = len(shared)
                table = np.zeros((self._pages_per_slot,), np.int32)
                table[:n_sh] = shared
                table[n_sh:n_sh + len(own)] = own
                self._tables[slot] = table
                self._owned[slot] = False
                self._owned[slot, n_sh:n_sh + len(own)] = True
                self._shared[slot] = False
                self._shared[slot, :n_sh] = True
                # no dirty mark: paged_insert writes this slot's device
                # table row itself
                self._host_pos[slot] = len(prompt)
                self._admit_seq[slot] = self._seq = self._seq + 1
                if n_sh:
                    self.stats["prefix_hits"] += 1
                    self.stats["prefix_tokens_reused"] += (
                        n_sh * self._page_size)
                # (prefix registration happened in _reserve_pages, so
                # same-tick co-arrivals could already match these pages)
                tail = prompt[n_sh * self._page_size:]
                # bucket within the capacity left after the prefix: the
                # cache rows land at offset prefix_len
                key = (_bucket(len(tail),
                               self.max_len - n_sh * self._page_size),
                       tuple(shared))
            else:
                prompt = handle.request.prompt
                key = (_bucket(len(prompt), self.max_len), ())
            groups.setdefault(key, []).append((slot, handle))
        if self._draft_model is not None:
            # draft-cache lifecycle, admit: the draft prefills the FULL
            # prompt (plus generated tokens for preempted re-admissions
            # — i.e. prompt+accepted only, rejected drafts were never
            # committed) into its dense per-slot cache. No prefix
            # sharing: the draft has no page arena to share through.
            # Runs BEFORE the target groups commit their first token so
            # the draft lands at the same position the target is at.
            dgroups: Dict[int, list] = {}
            for slot, handle in admitted:
                fp = self._full_prompt(handle)
                dgroups.setdefault(_bucket(len(fp), self.max_len),
                                   []).append((slot, fp))
            for P, dgroup in dgroups.items():
                toks = np.zeros((len(dgroup), P), np.int32)
                lengths = np.zeros((len(dgroup),), np.int32)
                for i, (_, fp) in enumerate(dgroup):
                    toks[i, :len(fp)] = fp
                    lengths[i] = len(fp)
                _, rows = self._draft_prefill(self._draft_params,
                                              jnp.asarray(toks),
                                              jnp.asarray(lengths))
                self._draft_cache = self._insert(
                    self._draft_cache, rows,
                    jnp.asarray([s for s, _ in dgroup]))
                self.stats["draft_prefills"] += 1
        params = self._params[self._version]
        for (P, shared), group in groups.items():
            n = len(group)
            prefix_len = len(shared) * self._page_size if self.paged else 0
            toks = np.zeros((n, P), np.int32)
            lengths = np.zeros((n,), np.int32)
            for i, (slot, handle) in enumerate(group):
                prompt = (plans[slot][0][prefix_len:] if self.paged
                          else handle.request.prompt)
                toks[i, :len(prompt)] = prompt
                lengths[i] = len(prompt)
            if prefix_len:
                pfx = self._gather_prefix(self.cache,
                                          jnp.asarray(shared, jnp.int32))
                logits, rows = self._prefill_ext(params, jnp.asarray(toks),
                                                 jnp.asarray(lengths), pfx,
                                                 prefix_len)
            else:
                logits, rows = self._prefill(params, jnp.asarray(toks),
                                             jnp.asarray(lengths))
            slots = [slot for slot, _ in group]
            if self.paged:
                tables = self._tables[slots]
                write_tables = np.where(self._owned[slots], tables, 0)
                self.cache = self._paged_insert(
                    self.cache, rows, jnp.asarray(slots),
                    jnp.asarray(write_tables), jnp.asarray(tables))
            else:
                self.cache = self._insert(self.cache, rows,
                                          jnp.asarray(slots))
            self.stats["prefill_calls"] += 1
            # first generated token: the group's sampling policies (all-
            # greedy groups stay on the bitwise argmax path)
            if all(h.request.temperature <= 0 for _, h in group):
                nxt = np.asarray(self._argmax(logits))
            else:
                nxt = np.asarray(self._sample(
                    logits, jnp.asarray(self._keys[slots]),
                    jnp.asarray(self._pos[slots]),
                    jnp.asarray(self._temp[slots]),
                    jnp.asarray(self._topk[slots]),
                    jnp.asarray(self._topp[slots])))
            for i, (_, handle) in enumerate(group):
                self._commit(handle, int(nxt[i]))

    # ------------------------------------------------- speculative decoding
    def _can_speculate(self) -> bool:
        """Host-side spec-tick preconditions; any miss makes THIS tick
        run plain decode (never an error — speculation is opportunistic):
        single live param version (hot-reload transition ticks verify
        under one set of weights or not at all), all-greedy (sampled
        requests bypass speculation), and pos + k < capacity for every
        active slot — the no-wrap/no-clamp contract that makes verify
        rows exactly pos+t and rollback a pure pos rewrite. The first
        pressure-ladder rung also lands here: degraded mode sheds the
        draft's extra dispatches before touching admissions."""
        if self._ladder is not None and self._ladder.level >= 1:
            return False
        active = self.scheduler.active
        if not active:
            return False
        if len({h.version for h in active.values()}) != 1:
            return False
        k = self.spec_k
        for h in active.values():
            if h.request.temperature > 0:
                return False
            # rows in cache = prompt + generated - 1 (the last committed
            # token's K/V lands when it is fed); verify writes k+1 more
            rows = len(h.request.prompt) + len(h.tokens) - 1
            if rows + k >= self._spec_cap:
                return False
        return True

    def _grow_spec(self, k: int) -> Dict[int, list]:
        """Claim every page the verify forward may write (rows
        pos..pos+k per active slot; fresh page, or COW of a shared one,
        preempting under pool pressure like plain growth). Each claim
        records (logical page, previous table entry, was-shared) so
        `_rollback_spec` can return pages that ended up holding only
        rejected rows. Claims for a slot preempted by a LATER claim are
        already released with its other pages; its undo entries are
        simply never applied."""
        ps = self._page_size
        undo: Dict[int, list] = {}
        for slot in sorted(self.scheduler.active):
            if slot not in self.scheduler.active:   # preempted meanwhile
                continue
            p = int(self._host_pos[slot])
            for lp in range(p // ps, (p + k) // ps + 1):
                if not self._owned[slot, lp]:
                    undo.setdefault(slot, []).append(
                        (lp, int(self._tables[slot, lp]),
                         bool(self._shared[slot, lp])))
                    self._claim_page(slot, lp)
        return undo

    def _rollback_spec(self, entries, slot: int, last_row: int):
        """Undo this tick's page claims that hold ONLY rejected rows
        (logical pages strictly beyond `last_row`, the K/V row of the
        last committed token): release the page and restore the
        pre-claim table entry — trash for plain growth, the
        re-referenced read-only original for a COW'd shared page (the
        original was never written; the copy holds only rejected rows).
        Pages up to `last_row` keep their claims: they hold committed
        K/V. Device tables re-sync values-only on the next tick."""
        ps = self._page_size
        for lp, old_pid, old_shared in entries:
            if lp * ps > last_row:
                self._pool.release([int(self._tables[slot, lp])])
                self._tables[slot, lp] = old_pid
                self._owned[slot, lp] = False
                self._shared[slot, lp] = old_shared
                if old_shared:
                    self._pool.ref([old_pid])
                self._tables_dirty = True

    def _spec_tick(self, tick: Dict[str, float]) -> bool:
        """One speculation tick: draft proposes k tokens per slot (one
        scanned dispatch over its dense cache, healing last tick's
        overrun via the pos rewrite), the target scores all k+1
        positions in ONE verify dispatch, and each slot commits its
        longest draft prefix matching the target's greedy argmax plus
        the corrected token — 1..k+1 tokens for one target dispatch,
        bitwise what k+1 plain ticks would have produced. Returns False
        when preconditions fail (caller runs the plain tick)."""
        if not self._can_speculate():
            return False
        k = self.spec_k
        undo: Dict[int, list] = {}
        if self.paged:
            with span("serve.grow", tick):
                undo = self._grow_spec(k)   # may preempt under pressure
                self._sync_tables()
        active = dict(self.scheduler.active)
        if not active:                      # growth preempted everything
            return True
        p_vec = np.zeros((self.max_slots,), np.int32)
        for slot, h in active.items():
            # K/V rows currently in cache == the device pos (see
            # _can_speculate); equals _host_pos for the paged layout
            p_vec[slot] = len(h.request.prompt) + len(h.tokens) - 1
        version = next(iter({h.version for h in active.values()}))
        with span("serve.decode", tick):
            toks = jnp.asarray(self._tokens)
            drafts, self._draft_cache = self._propose(
                self._draft_params, toks, self._draft_cache,
                jnp.asarray(p_vec))
            spec_toks = jnp.concatenate([toks, drafts], axis=1)  # [B, k+1]
            nxt, g, acc, self.cache = self._verify(
                self._params[version], spec_toks, self.cache)
        del nxt   # == g[b, acc[b]]; _commit feeds _tokens from g anyway
        with span("serve.pull", tick):
            g = np.asarray(g)
            acc_np = np.asarray(acc)
        self.stats["decode_steps"] += 1     # ONE target dispatch
        self.stats["spec_ticks"] += 1
        with span("serve.commit", tick):
            for slot, handle in active.items():
                a = int(acc_np[slot])
                self.stats["spec_tokens_proposed"] += k
                self.stats["spec_tokens_accepted"] += a
                handle.spec_proposed += k
                handle.spec_accepted += a
                if self.paged:
                    self._host_pos[slot] += a + 1
                for t in range(a + 1):
                    self._commit(handle, int(g[slot, t]))
                    if handle.done:
                        # EOS/budget inside the accepted run: later
                        # tokens are discarded; the slot's pages are
                        # already released wholesale, no rollback needed
                        break
                if self.paged and not handle.done:
                    self._rollback_spec(undo.get(slot, ()), slot,
                                        int(p_vec[slot]) + a)
        return True

    def _decode_tick(self, tick: Dict[str, float]):
        if self.spec_k and self._spec_tick(tick):
            return
        if self.paged:
            # every active slot must own its write page before the batch
            # advances (growth / COW; may preempt under pool pressure)
            with span("serve.grow", tick):
                self._grow_active()
                self._sync_tables()
        active = dict(self.scheduler.active)       # slot -> handle
        if not active:                             # all preempted
            return
        versions = sorted({h.version for h in active.values()})
        with span("serve.decode", tick):
            toks = jnp.asarray(self._tokens)
            # all-greedy ticks take the plain argmax decode (bitwise the
            # pre-sampling path, no wasted sort/gumbel work); any sampled
            # slot switches the tick to the sampling step, where greedy
            # rows still resolve to the identical argmax
            if any(h.request.temperature > 0 for h in active.values()):
                policy = (jnp.asarray(self._keys), jnp.asarray(self._pos),
                          jnp.asarray(self._temp), jnp.asarray(self._topk),
                          jnp.asarray(self._topp))
                decode = lambda params: self._decode_sampled(
                    params, toks, self.cache, *policy)
            else:
                decode = lambda params: self._decode(params, toks,
                                                     self.cache)
            # a transition tick decodes once per live param version
            outs = {v: decode(self._params[v]) for v in versions}
        with span("serve.pull", tick):
            nxt = self._pull_tokens(outs, versions, active)
        self.stats["decode_steps"] += 1
        with span("serve.commit", tick):
            if self.paged:
                for slot in active:
                    self._host_pos[slot] += 1
            for slot, handle in active.items():
                self._commit(handle, int(nxt[slot, 0]))

    def _pull_tokens(self, outs, versions, active):
        """Sets the tick's new cache; returns its tokens on the host."""
        if len(versions) == 1:
            nxt, self.cache = outs[versions[0]]
            nxt = np.asarray(nxt)
        else:
            # transition tick(s): keep each slot's row from the version
            # it is pinned to. Paged: arena leaves merge by PHYSICAL page
            # ownership (each slot writes only its own pages; shared
            # prefix pages are read-only and identical under every
            # version)
            merged = outs[versions[0]][1]
            nxt = np.asarray(outs[versions[0]][0]).copy()
            for v in versions[1:]:
                mask = np.zeros((self.max_slots,), bool)
                for slot, h in active.items():
                    if h.version == v:
                        mask[slot] = True
                if self.paged:
                    pmask = np.zeros((self._num_pages,), bool)
                    pmask[self._tables[mask][self._owned[mask]]] = True
                    pmask[PagePool.TRASH] = False
                    merged = self._select_paged(jnp.asarray(mask),
                                                jnp.asarray(pmask),
                                                outs[v][1], merged)
                else:
                    merged = self._select(jnp.asarray(mask), outs[v][1],
                                          merged)
                nxt[mask] = np.asarray(outs[v][0])[mask]
            self.cache = merged
        return nxt

    def _commit(self, handle: RequestHandle, token: int):
        """Record one generated token; stream it; retire if finished."""
        handle.tokens.append(token)
        slot = handle.slot
        self._tokens[slot, 0] = token
        # next sample position = #tokens generated so far: token t is a
        # pure function of (seed, t) regardless of batch composition
        self._pos[slot] = len(handle.tokens)
        self.stats["generated_tokens"] += 1
        if handle.first_token_at is None:
            handle.first_token_at = time.perf_counter()
        if handle.request.stream is not None:
            handle.request.stream(handle, token)
        reason = self.scheduler.should_retire(handle, token)
        if reason is not None:
            self.scheduler.retire(slot, reason)
            self.stats["completed"] += 1
            if handle.ttft is not None:
                self._ttft.append(handle.ttft)
            if handle.tpot is not None:
                self._tpot.append(handle.tpot)
            if self.paged:
                self._release_slot_pages(slot)

    # ---------------------------------------------------------- reporting
    def kv_stats(self) -> Dict[str, Any]:
        """KV-memory view of the engine: live/peak bytes, page counts,
        prefix-reuse and pressure counters. Dense layout reports its
        constant full-capacity footprint."""
        return {"kv_layout": "paged" if self.paged else "dense",
                "dense_fallback_leaves": self._dense_fallback_leaves,
                "dense_fallback_bytes": self._dense_fallback_bytes,
                "kv_bytes_in_use": self.stats["kv_bytes_in_use"],
                "peak_kv_bytes_in_use": self.stats["peak_kv_bytes_in_use"],
                "kv_capacity_bytes": self._kv_capacity_bytes,
                "kv_page_bytes": self._page_bytes,
                "kv_pages_used": self.stats["kv_pages_used"],
                "kv_pages_free": self.stats["kv_pages_free"],
                "prefix_hits": self.stats["prefix_hits"],
                "prefix_tokens_reused": self.stats["prefix_tokens_reused"],
                "cow_copies": self.stats["cow_copies"],
                "preemptions": self.stats["preemptions"],
                "spec_ticks": self.stats["spec_ticks"],
                "spec_tokens_proposed": self.stats["spec_tokens_proposed"],
                "spec_tokens_accepted": self.stats["spec_tokens_accepted"],
                "spec_acceptance_rate": (
                    self.stats["spec_tokens_accepted"]
                    / self.stats["spec_tokens_proposed"]
                    if self.stats["spec_tokens_proposed"] else 0.0)}

    def throughput(self) -> Dict[str, float]:
        """Completion/throughput fields (the serve CLI prints these):
        tok/s plus per-request latency — TTFT (submit -> first token)
        and TPOT (per-token cadence after the first), each mean/p50/p99
        over completed requests — the seconds spent in each tick phase,
        and, under speculation, acceptance accounting and target
        dispatches per generated token."""
        started = self.stats["started_at"]
        wall = (time.perf_counter() - started) if started else 0.0
        toks = self.stats["generated_tokens"]
        out = {"completed": self.stats["completed"],
               "submitted": self.stats["submitted"],
               "generated_tokens": toks,
               "decode_steps": self.stats["decode_steps"],
               "prefill_calls": self.stats["prefill_calls"],
               "reloads": self.stats["reloads"],
               "wall_s": wall,
               "tok_s": toks / wall if wall > 0 else 0.0,
               "kv_bytes_in_use": self.stats["kv_bytes_in_use"],
               "peak_kv_bytes": self.stats["peak_kv_bytes_in_use"],
               "prefix_hits": self.stats["prefix_hits"],
               "prefix_tokens_reused": self.stats["prefix_tokens_reused"],
               # resilience counters: every submitted request ends in
               # completed or failed; failed splits into deadline kills,
               # retry-budget exhaustion, and drain-time shedding
               "failed": self.stats["failed"],
               "deadline_kills": self.stats["deadline_kills"],
               "retries": self.stats["retries"],
               "drained": self.stats["drained"],
               "restore_fallbacks": self.stats["restore_fallbacks"],
               # host seconds per tick phase, summed over every tick
               "tick_phase_s": dict(self.stats["tick_phase_s"])}
        if self._ladder is not None:
            out["degradation_level"] = self.stats["degradation_level"]
            out["degradation_changes"] = self.stats["degradation_changes"]
            out["ladder_preempts"] = self.stats["ladder_preempts"]
        for name, samples in (("ttft", self._ttft), ("tpot", self._tpot)):
            if samples:
                # host wall-clock stats, not device pulls: `samples` are
                # time.perf_counter deltas recorded at retirement
                arr = np.asarray(samples, np.float64)
                out[f"{name}_mean_s"] = float(arr.mean())  # lint: allow(host-pull)
                out[f"{name}_p50_s"] = float(np.percentile(arr, 50))  # lint: allow(host-pull)
                out[f"{name}_p99_s"] = float(np.percentile(arr, 99))  # lint: allow(host-pull)
        if self.paged:
            out["kv_pages_used"] = self.stats["kv_pages_used"]
            out["kv_pages_free"] = self.stats["kv_pages_free"]
            out["preemptions"] = self.stats["preemptions"]
        if self.spec_k:
            proposed = self.stats["spec_tokens_proposed"]
            out["spec_ticks"] = self.stats["spec_ticks"]
            out["spec_tokens_proposed"] = proposed
            out["spec_tokens_accepted"] = self.stats["spec_tokens_accepted"]
            out["spec_acceptance_rate"] = (
                self.stats["spec_tokens_accepted"] / proposed
                if proposed else 0.0)
            out["draft_prefills"] = self.stats["draft_prefills"]
            # dispatches_per_token: target-model decode+verify dispatches
            # per generated token — the quantity speculation exists to
            # shrink (1.0 for plain decode; 1/(1 + acceptance*k) under
            # speculation)
            out["dispatches_per_token"] = (
                self.stats["decode_steps"] / toks if toks else 0.0)
        return out

    def close(self):
        if self.checkpoint is not None:
            close = getattr(self.checkpoint, "close", None)
            if close is not None:
                close()
