"""Decoder-only LM assembly for every family in the pool.

Block families:
  dense/moe : x += attn(norm(x));  x += mlp|moe(norm(x))
  hybrid    : x += attn(norm(x)) + mamba(norm(x))   (hymba parallel heads)
              x += mlp(norm(x))
  ssm(rwkv) : x += time_mix(norm(x)); x += channel_mix(norm(x))

Layers are *stacked* [L, ...] and driven by lax.scan (compile time stays
O(1 layer) even for 64-layer configs) with jax.checkpoint around the block
body (activation remat). MoE configs may have a dense prefix
(first_dense_layers) which scans separately.

Inside a block, the attention, Mamba and MLP calls run under
`jax.named_scope("attention" | "mamba" | "mlp")`, forward and decode
alike: the names reach every XLA op's `op_name`, backward and
rematerialised ops included, so a device trace can be summed by layer.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from . import layers as L
from . import attention as ATT
from . import moe as MOE
from . import ssm as SSM
from repro.configs.base import ModelConfig

PyTree = Any


# ------------------------------------------------------------------- blocks
def block_init(key, cfg: ModelConfig, dtype, moe_block: bool) -> PyTree:
    ks = L.split_keys(key, 4)
    p: Dict[str, PyTree] = {}
    if cfg.family == "ssm":                       # rwkv
        p["norm1"] = L.rmsnorm_init(cfg.d_model, dtype)
        p["time"] = SSM.rwkv_time_init(ks[0], cfg, dtype)
        p["norm2"] = L.rmsnorm_init(cfg.d_model, dtype)
        p["chan"] = SSM.rwkv_chan_init(ks[1], cfg, dtype)
        return p
    p["norm1"] = L.rmsnorm_init(cfg.d_model, dtype)
    if cfg.attn_type == "mla":
        p["attn"] = ATT.mla_init(ks[0], cfg, dtype)
    else:
        p["attn"] = ATT.gqa_init(ks[0], cfg, dtype)
    if cfg.family == "hybrid":
        p["mamba"] = SSM.mamba_init(ks[2], cfg, dtype)
    p["norm2"] = L.rmsnorm_init(cfg.d_model, dtype)
    if moe_block:
        p["moe"] = MOE.moe_init(ks[1], cfg, dtype)
    else:
        p["mlp"] = L.mlp_init(ks[1], cfg.d_model, cfg.d_ff, dtype)
    return p


def block_apply(params: PyTree, cfg: ModelConfig, x: jnp.ndarray,
                positions: jnp.ndarray, moe_block: bool,
                compute_dtype=jnp.bfloat16, attn_chunk: int = 512,
                moe_shards: int = 1, use_flash: bool = False,
                return_kv: bool = False, prefix_kv=None):
    """[B,T,D] -> ([B,T,D], aux_loss[, kv]).

    return_kv (attention families only): also return this block's
    decode-cache contribution — (k, v) for GQA, (c_kv, k_rope) for MLA —
    so a fused prefill can populate a cache in one forward pass.
    prefix_kv: this block's cached shared-prefix contribution (same pair
    shapes, [B, S0, ...]) for the extend-prefill — `positions` then
    starts at S0 and only the tail is computed/returned."""
    aux = jnp.zeros((), jnp.float32)
    if cfg.family == "ssm":
        assert not return_kv, "fused kv capture needs an attention family"
        h = L.rmsnorm(params["norm1"], x, cfg.norm_eps)
        x = x + SSM.rwkv_time_forward(params["time"], cfg, h, compute_dtype)
        h = L.rmsnorm(params["norm2"], x, cfg.norm_eps)
        prev = jnp.zeros((x.shape[0], 1, x.shape[-1]), h.dtype)
        x = x + SSM.rwkv_chan_forward(params["chan"], cfg, h, prev,
                                      compute_dtype)
        return x, aux
    h = L.rmsnorm(params["norm1"], x, cfg.norm_eps)
    kv = None
    with jax.named_scope("attention"):
        if cfg.attn_type == "mla":
            a = ATT.mla_forward(params["attn"], cfg, h, positions,
                                compute_dtype, attn_chunk,
                                return_kv=return_kv, prefix_kv=prefix_kv)
        else:
            a = ATT.gqa_forward(params["attn"], cfg, h, positions,
                                compute_dtype, attn_chunk, use_flash,
                                return_kv=return_kv, prefix_kv=prefix_kv)
    if return_kv:
        a, kv = a
    if cfg.family == "hybrid":
        assert not return_kv, "fused kv capture needs an attention family"
        with jax.named_scope("mamba"):
            m = SSM.mamba_forward(params["mamba"], cfg, h, compute_dtype)
        a = (a + m) * 0.5
    x = x + a
    h = L.rmsnorm(params["norm2"], x, cfg.norm_eps)
    if moe_block:
        m, aux = MOE.moe_apply(params["moe"], cfg, h, compute_dtype,
                               moe_shards)
    else:
        with jax.named_scope("mlp"):
            m = L.mlp_apply(params["mlp"], h, cfg.mlp_type, compute_dtype)
    if return_kv:
        return x + m, aux, kv
    return x + m, aux


# ------------------------------------------------------------------- params
def _stack_init(key, n: int, one_init):
    """Initialise n blocks with different keys, stacked on axis 0."""
    keys = jnp.stack(L.split_keys(key, n))
    return jax.vmap(one_init)(keys)


def init_params(cfg: ModelConfig, key, param_dtype=jnp.float32) -> PyTree:
    ks = L.split_keys(key, 6)
    params: Dict[str, PyTree] = {
        "embed": L.embedding_init(ks[0], cfg.vocab_size, cfg.d_model,
                                  param_dtype),
        "final_norm": L.rmsnorm_init(cfg.d_model, param_dtype),
    }
    n_moe = 0
    if cfg.n_experts:
        n_dense = cfg.first_dense_layers
        n_moe = cfg.n_layers - n_dense
        if n_dense:
            params["dense_blocks"] = _stack_init(
                ks[1], n_dense,
                lambda k: block_init(k, cfg, param_dtype, moe_block=False))
        params["blocks"] = _stack_init(
            ks[2], n_moe,
            lambda k: block_init(k, cfg, param_dtype, moe_block=True))
    else:
        params["blocks"] = _stack_init(
            ks[2], cfg.n_layers,
            lambda k: block_init(k, cfg, param_dtype, moe_block=False))
    if not cfg.tie_embeddings:
        params["lm_head"] = L.lm_head_init(ks[3], cfg.d_model, cfg.vocab_size,
                                           param_dtype)
    if cfg.frontend == "vision":
        params["projector"] = {
            "w1": L.dense_init(ks[4], (cfg.frontend_dim, cfg.d_model),
                               param_dtype),
            "w2": L.dense_init(ks[5], (cfg.d_model, cfg.d_model), param_dtype),
        }
    elif cfg.frontend == "audio":
        params["projector"] = {
            "w1": L.dense_init(ks[4], (cfg.frontend_dim, cfg.d_model),
                               param_dtype),
        }
    return params


def project_frontend(params: PyTree, cfg: ModelConfig, embeds: jnp.ndarray,
                     compute_dtype=jnp.bfloat16) -> jnp.ndarray:
    """Modality stub -> model space. embeds: [B, S, frontend_dim]."""
    x = embeds.astype(compute_dtype) @ params["projector"]["w1"].astype(
        compute_dtype)
    if "w2" in params.get("projector", {}):
        x = jax.nn.gelu(x) @ params["projector"]["w2"].astype(compute_dtype)
    return x


# ------------------------------------------------------------------ forward
def _scan_blocks(blocks: PyTree, cfg: ModelConfig, x, positions, moe_block,
                 compute_dtype, attn_chunk, remat: bool = True,
                 moe_shards: int = 1, use_flash: bool = False,
                 collect_kv: bool = False, prefix_kv=None):
    body = functools.partial(block_apply, cfg=cfg, positions=positions,
                             moe_block=moe_block, compute_dtype=compute_dtype,
                             attn_chunk=attn_chunk, moe_shards=moe_shards,
                             use_flash=use_flash, return_kv=collect_kv)

    def step(carry, inp):
        x, aux = carry
        bparams, pkv = inp
        fn = (jax.checkpoint(lambda p, y: body(p, x=y, prefix_kv=pkv))
              if remat else (lambda p, y: body(p, x=y, prefix_kv=pkv)))
        if collect_kv:
            x, a, kv = fn(bparams, x)
            return (x, aux + a), kv
        x, a = fn(bparams, x)
        return (x, aux + a), None

    # collect_kv: the scan's ys stack per-layer kv on axis 0 — exactly the
    # [L, ...] layout of DecodeCache.layers; prefix_kv rides along as a
    # per-layer xs pair ([L, B, S0, ...] stacked, sliced by the scan)
    (x, aux), kvs = jax.lax.scan(step, (x, jnp.zeros((), jnp.float32)),
                                 (blocks, prefix_kv))
    if collect_kv:
        return x, aux, kvs
    return x, aux


def forward(params: PyTree, cfg: ModelConfig, tokens: jnp.ndarray,
            frontend_embeds: Optional[jnp.ndarray] = None,
            compute_dtype=jnp.bfloat16, attn_chunk: int = 512,
            remat: bool = True, last_only: bool = False,
            moe_shards: int = 1, use_flash: bool = False
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """tokens [B,T_text] (+ optional frontend embeds prepended) -> logits
    [B,T,V], aux. last_only: unembed only the final position (prefill)."""
    x = L.embed(params["embed"], tokens, compute_dtype)
    if frontend_embeds is not None:
        fe = project_frontend(params, cfg, frontend_embeds, compute_dtype)
        x = jnp.concatenate([fe, x], axis=1)
    T = x.shape[1]
    positions = jnp.arange(T, dtype=jnp.float32)
    aux = jnp.zeros((), jnp.float32)
    if "dense_blocks" in params:
        x, a = _scan_blocks(params["dense_blocks"], cfg, x, positions, False,
                            compute_dtype, attn_chunk, remat)
        aux += a
    x, a = _scan_blocks(params["blocks"], cfg, x, positions,
                        bool(cfg.n_experts), compute_dtype, attn_chunk, remat,
                        moe_shards, use_flash)
    aux += a
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    if last_only:
        x = x[:, -1:, :]
    if cfg.tie_embeddings:
        logits = L.unembed(params["embed"], x, compute_dtype)
    else:
        logits = L.lm_head(params["lm_head"], x, compute_dtype)
    return logits, aux


def lm_loss(params: PyTree, cfg: ModelConfig, batch: Dict[str, jnp.ndarray],
            compute_dtype=jnp.bfloat16, attn_chunk: int = 512,
            aux_weight: float = 0.01, remat: bool = True,
            moe_shards: int = 1
            ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Next-token cross entropy. batch: tokens [B,T], labels [B,T]
    (-100 = masked), optional frontend_embeds."""
    logits, aux = forward(params, cfg, batch["tokens"],
                          batch.get("frontend_embeds"), compute_dtype,
                          attn_chunk, remat, moe_shards=moe_shards)
    labels = batch["labels"]
    if logits.shape[1] != labels.shape[1]:      # frontend positions prepended
        pad = logits.shape[1] - labels.shape[1]
        labels = jnp.concatenate(
            [jnp.full((labels.shape[0], pad), -100, labels.dtype), labels],
            axis=1)
    mask = (labels >= 0).astype(jnp.float32)
    safe = jnp.maximum(labels, 0)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
    loss = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    total = loss + aux_weight * aux
    return total, {"loss": loss, "aux": aux}


# ------------------------------------------------------------------- decode
class DecodeCache(NamedTuple):
    """Per-layer caches stacked on a leading L axis."""
    layers: PyTree
    dense_layers: Optional[PyTree] = None


def _one_layer_cache(cfg: ModelConfig, batch: int, max_len: int, dtype,
                     per_slot: bool = False, paged=None):
    pos0 = jnp.zeros((batch,) if per_slot else (), jnp.int32)
    if cfg.family == "ssm":
        return SSM.RWKVState(
            jnp.zeros((batch, cfg.d_model // cfg.rwkv_head_dim,
                       cfg.rwkv_head_dim, cfg.rwkv_head_dim), jnp.float32),
            jnp.zeros((batch, cfg.d_model), dtype),
            jnp.zeros((batch, cfg.d_model), dtype),
            pos0)
    if paged is not None:
        ps, num_pages = paged
        if cfg.attn_type == "mla":
            att = ATT.init_paged_mla_cache(cfg, batch, max_len, ps,
                                           num_pages, dtype)
        else:
            att = ATT.init_paged_kv_cache(cfg, batch, max_len, ps,
                                          num_pages, dtype)
    elif cfg.attn_type == "mla":
        att = ATT.init_mla_cache(cfg, batch, max_len, dtype, per_slot)
    else:
        att = ATT.init_kv_cache(cfg, batch, max_len, dtype, per_slot)
    if cfg.family == "hybrid":
        return {"attn": att,
                "mamba": SSM.mamba_init_state(cfg, batch, dtype, per_slot)}
    return {"attn": att}


def init_decode_cache(cfg: ModelConfig, batch: int, max_len: int,
                      dtype=jnp.bfloat16, per_slot: bool = False,
                      paged=None) -> DecodeCache:
    """per_slot=True: every leaf (including the pos counters, then [B])
    carries the batch axis at position 1 after layer stacking — the layout
    engine/serving's slotted-cache ops (row insert/select) rely on.

    paged=(page_size, num_pages): attention K/V lives in per-layer page
    arenas `[L, num_pages, page_size, ...]` addressed via int32 page
    tables [L, B, pages_per_slot] (recurrent state — mamba/rwkv — stays
    per-slot dense; it is O(1) per slot). Implies per-slot positions."""
    stack = lambda n: jax.tree.map(
        lambda x: jnp.broadcast_to(x, (n,) + x.shape),
        _one_layer_cache(cfg, batch, max_len, dtype, per_slot, paged))
    dense = None
    n_moe = cfg.n_layers
    if cfg.n_experts and cfg.first_dense_layers:
        dense = stack(cfg.first_dense_layers)
        n_moe = cfg.n_layers - cfg.first_dense_layers
    return DecodeCache(stack(n_moe), dense)


def _cache_rows(t: jnp.ndarray, lengths: jnp.ndarray, cap: int,
                rolling: bool, cache_dtype, offset: int = 0) -> jnp.ndarray:
    """Place captured per-position tensors [L,B,P,...] into fixed-capacity
    cache rows [L,B,cap,...].

    Linear layout (full attention, or a rolling buffer that fits the whole
    prompt): row p holds position p; rows >= length are dead weight the
    per-slot pos mask excludes. Rolling layout (SWA, prompt longer than
    the window): row r holds the most recent prompt position p with
    p % cap == r — exactly what cap sequential decode writes would leave.

    offset: absolute position of t[..., 0] (extend-prefill: the tail
    starts after a cached shared prefix; linear layout only)."""
    Lyr, B, P = t.shape[:3]
    tail = t.shape[3:]
    if not rolling or cap >= offset + P:
        assert cap >= offset + P, \
            f"cache capacity {cap} < prompt bucket {offset}+{P}"
        out = jnp.zeros((Lyr, B, cap) + tail, cache_dtype)
        return out.at[:, :, offset:offset + P].set(t.astype(cache_dtype))
    assert offset == 0, "rolling prefill cannot extend a shared prefix"
    last = (lengths - 1)[:, None]                       # [B,1]
    idx = jnp.arange(cap)[None, :]                      # [1,cap]
    p_r = last - ((last - idx) % cap)                   # [B,cap] winner per row
    valid = p_r >= 0
    take = jnp.clip(p_r, 0, P - 1).reshape((1, B, cap) + (1,) * len(tail))
    rows = jnp.take_along_axis(t, take, axis=2)
    mask = valid.reshape((1, B, cap) + (1,) * len(tail))
    return jnp.where(mask, rows, 0).astype(cache_dtype)


def prefill_decode_cache(params: PyTree, cfg: ModelConfig,
                         tokens: jnp.ndarray, lengths: jnp.ndarray,
                         max_len: int, compute_dtype=jnp.bfloat16,
                         attn_chunk: int = 512, use_flash: bool = False,
                         cache_dtype=jnp.bfloat16, prefix_kv=None,
                         prefix_len: int = 0
                         ) -> Tuple[jnp.ndarray, DecodeCache]:
    """Fused serving prefill: ONE full-sequence forward that both computes
    the last-prompt-position logits and writes every layer's K/V into a
    fresh slotted DecodeCache — replacing T sequential decode_step
    dispatches. Attention-only families (the recurrent-state ssm/hybrid
    families prefill via a fused decode scan in engine/serving).

    tokens: [B,P] prompts right-padded to a common bucket length (causal
    attention makes the padding inert); lengths: [B] true prompt lengths.
    Returns (logits [B,1,V] at position lengths-1, cache with per-slot
    pos = lengths).

    Shared-prefix extend: with `prefix_kv` (a DecodeCache-shaped pytree
    of per-layer cached prefix pairs, [L, B, prefix_len, ...]) the tokens
    are the UNSHARED TAIL only — positions start at `prefix_len`, the
    forward computes O(tail) work attending to prefix+tail, the returned
    cache rows hold the tail at its absolute positions (the caller
    already owns the prefix rows/pages) and pos = prefix_len + lengths."""
    assert cfg.family not in ("ssm", "hybrid") and not cfg.is_encoder_decoder
    assert prefix_kv is None or (prefix_len > 0 and not cfg.sliding_window)
    B, P = tokens.shape
    x = L.embed(params["embed"], tokens, compute_dtype)
    positions = jnp.arange(prefix_len, prefix_len + P, dtype=jnp.float32)
    pfx = prefix_kv or DecodeCache(None, None)
    # accept both the bare per-layer pair and the {"attn": pair} segment
    # shape that engine/serving's gather_prefix produces
    seg = lambda s: s["attn"] if isinstance(s, dict) else s
    pfx = DecodeCache(seg(pfx.layers), seg(pfx.dense_layers)
                      if pfx.dense_layers is not None else None)
    dense_kv = None
    if "dense_blocks" in params:
        x, _, dense_kv = _scan_blocks(params["dense_blocks"], cfg, x,
                                      positions, False, compute_dtype,
                                      attn_chunk, remat=False,
                                      collect_kv=True,
                                      prefix_kv=pfx.dense_layers)
    x, _, kv = _scan_blocks(params["blocks"], cfg, x, positions,
                            bool(cfg.n_experts), compute_dtype, attn_chunk,
                            remat=False,
                            use_flash=use_flash and prefix_kv is None,
                            collect_kv=True, prefix_kv=pfx.layers)
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    last = jnp.take_along_axis(x, (lengths - 1)[:, None, None], axis=1)
    if cfg.tie_embeddings:
        logits = L.unembed(params["embed"], last, compute_dtype)
    else:
        logits = L.lm_head(params["lm_head"], last, compute_dtype)

    def seg_cache(pair):
        Lyr = jax.tree.leaves(pair)[0].shape[0]
        pos = jnp.broadcast_to(prefix_len + lengths[None, :], (Lyr, B))
        if cfg.attn_type == "mla":
            c_kv, k_rope = pair
            att = ATT.MLACache(
                _cache_rows(c_kv, lengths, max_len, False, cache_dtype,
                            prefix_len),
                _cache_rows(k_rope, lengths, max_len, False, cache_dtype,
                            prefix_len),
                pos)
        else:
            k, v = pair
            cap = (min(max_len, cfg.sliding_window) if cfg.sliding_window
                   else max_len)
            rolling = bool(cfg.sliding_window)
            att = ATT.KVCache(
                _cache_rows(k, lengths, cap, rolling, cache_dtype,
                            prefix_len),
                _cache_rows(v, lengths, cap, rolling, cache_dtype,
                            prefix_len), pos)
        return {"attn": att}

    dense = seg_cache(dense_kv) if dense_kv is not None else None
    return logits, DecodeCache(seg_cache(kv), dense)


def _block_decode(params: PyTree, cfg: ModelConfig, x, cache, moe_block,
                  compute_dtype):
    """One token through one block. x: [B,1,D]."""
    if cfg.family == "ssm":
        h = L.rmsnorm(params["norm1"], x, cfg.norm_eps)
        t_out, cache = SSM.rwkv_decode_step(params["time"], params["chan"],
                                            cfg, h, cache, compute_dtype)
        x = x + t_out
        h = L.rmsnorm(params["norm2"], x, cfg.norm_eps)
        c_out = SSM.rwkv_chan_forward(params["chan"], cfg, h,
                                      cache.x_chan[:, None], compute_dtype)
        cache = cache._replace(x_chan=h[:, 0])
        return x + c_out, cache, jnp.zeros((), jnp.float32)
    h = L.rmsnorm(params["norm1"], x, cfg.norm_eps)
    with jax.named_scope("attention"):
        if isinstance(cache["attn"], ATT.PagedKVCache):
            a, att = ATT.gqa_paged_decode_step(params["attn"], cfg, h,
                                               cache["attn"], compute_dtype)
        elif isinstance(cache["attn"], ATT.PagedMLACache):
            a, att = ATT.mla_paged_decode_step(params["attn"], cfg, h,
                                               cache["attn"], compute_dtype)
        elif cfg.attn_type == "mla":
            a, att = ATT.mla_decode_step(params["attn"], cfg, h,
                                         cache["attn"], compute_dtype)
        else:
            a, att = ATT.gqa_decode_step(params["attn"], cfg, h,
                                         cache["attn"], compute_dtype)
    cache = dict(cache, attn=att)
    if cfg.family == "hybrid":
        with jax.named_scope("mamba"):
            m, ms = SSM.mamba_decode_step(params["mamba"], cfg, h,
                                          cache["mamba"], compute_dtype)
        a = (a + m) * 0.5
        cache["mamba"] = ms
    x = x + a
    h = L.rmsnorm(params["norm2"], x, cfg.norm_eps)
    if moe_block:
        m, aux = MOE.moe_apply(params["moe"], cfg, h, compute_dtype)
    else:
        with jax.named_scope("mlp"):
            m = L.mlp_apply(params["mlp"], h, cfg.mlp_type, compute_dtype)
        aux = jnp.zeros((), jnp.float32)
    return x + m, cache, aux


def decode_step(params: PyTree, cfg: ModelConfig, tokens: jnp.ndarray,
                cache: DecodeCache, compute_dtype=jnp.bfloat16
                ) -> Tuple[jnp.ndarray, DecodeCache]:
    """tokens [B,1] -> (logits [B,1,V], cache)."""
    x = L.embed(params["embed"], tokens, compute_dtype)

    def scan_seg(x, blocks, caches, moe_block):
        def step(h, inp):
            bp, c = inp
            h, c, _ = _block_decode(bp, cfg, h, c, moe_block, compute_dtype)
            return h, c
        return jax.lax.scan(step, x, (blocks, caches))

    dense_caches = cache.dense_layers
    if "dense_blocks" in params:
        x, dense_caches = scan_seg(x, params["dense_blocks"],
                                   cache.dense_layers, False)
    x, layer_caches = scan_seg(x, params["blocks"], cache.layers,
                               bool(cfg.n_experts))
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = L.unembed(params["embed"], x, compute_dtype)
    else:
        logits = L.lm_head(params["lm_head"], x, compute_dtype)
    return logits, DecodeCache(layer_caches, dense_caches)


def _block_verify(params: PyTree, cfg: ModelConfig, x, cache, moe_block,
                  compute_dtype):
    """T speculative tokens through one block. x: [B,T,D]. Attention
    families only — recurrent state cannot be rolled back by rewriting
    `pos`, so ssm/hybrid never reach this path."""
    h = L.rmsnorm(params["norm1"], x, cfg.norm_eps)
    if isinstance(cache["attn"], ATT.PagedKVCache):
        a, att = ATT.gqa_paged_verify_step(params["attn"], cfg, h,
                                           cache["attn"], compute_dtype)
    elif isinstance(cache["attn"], ATT.PagedMLACache):
        a, att = ATT.mla_paged_verify_step(params["attn"], cfg, h,
                                           cache["attn"], compute_dtype)
    elif cfg.attn_type == "mla":
        a, att = ATT.mla_verify_step(params["attn"], cfg, h, cache["attn"],
                                     compute_dtype)
    else:
        a, att = ATT.gqa_verify_step(params["attn"], cfg, h, cache["attn"],
                                     compute_dtype)
    cache = dict(cache, attn=att)
    x = x + a
    h = L.rmsnorm(params["norm2"], x, cfg.norm_eps)
    if moe_block:
        m, _ = MOE.moe_apply(params["moe"], cfg, h, compute_dtype)
    else:
        m = L.mlp_apply(params["mlp"], h, cfg.mlp_type, compute_dtype)
    return x + m, cache


def verify_step(params: PyTree, cfg: ModelConfig, tokens: jnp.ndarray,
                cache: DecodeCache, compute_dtype=jnp.bfloat16
                ) -> Tuple[jnp.ndarray, DecodeCache]:
    """Speculative verification: tokens [B,T] = [last committed token,
    draft_1..draft_{T-1}] -> (logits [B,T,V], cache). ONE forward scores
    all T positions; the returned cache has K/V rows written for
    positions pos..pos+T-1 but `pos` UNCHANGED — the caller advances pos
    by accepted+1 (engine/build's make_verify_step), which is both the
    accept and the rollback. Per-position greedy argmax is bitwise-equal
    to T sequential decode_step calls (see models/attention.py)."""
    assert cfg.family not in ("ssm", "hybrid") and not cfg.is_encoder_decoder
    x = L.embed(params["embed"], tokens, compute_dtype)

    def scan_seg(x, blocks, caches, moe_block):
        def step(h, inp):
            bp, c = inp
            h, c = _block_verify(bp, cfg, h, c, moe_block, compute_dtype)
            return h, c
        return jax.lax.scan(step, x, (blocks, caches))

    dense_caches = cache.dense_layers
    if "dense_blocks" in params:
        x, dense_caches = scan_seg(x, params["dense_blocks"],
                                   cache.dense_layers, False)
    x, layer_caches = scan_seg(x, params["blocks"], cache.layers,
                               bool(cfg.n_experts))
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = L.unembed(params["embed"], x, compute_dtype)
    else:
        logits = L.lm_head(params["lm_head"], x, compute_dtype)
    return logits, DecodeCache(layer_caches, dense_caches)
