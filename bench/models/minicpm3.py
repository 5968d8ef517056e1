"""Plain float32 reference of the MLA decoder (MiniCPM3-style), and its
operation and byte counts for decoding.

It imports nothing of the program. Per layer, as the configuration file
states it:

    h  = rmsnorm(x) * norm1
    cq = rmsnorm(h Wq_down) * q_norm;  q = cq Wq_up  -> (q_nope, q_rope)
    c  = h Wkv_down -> (c_kv, k_rope);  c_kv = rmsnorm(c_kv) * kv_norm
    k  = (c_kv Wkv_up -> k_nope) ++ rope(k_rope)  (one rope key, all heads)
    v  = c_kv Wkv_up -> v
    x += softmax(q k / sqrt(qk_nope + qk_rope), causal) v Wo
    x += swiglu_mlp(rmsnorm(x) * norm2)

then rmsnorm * final_norm and logits against the tied embedding. The
latent keys are expanded per head and attention is a plain masked
softmax, taken over blocks of queries so that one sequence of a few
thousand tokens fits. Every matmul runs at `highest` precision. The
control computes in float8 (e4m3) where the program computes in
bfloat16: matmul operands and results, norm outputs and the residual
stream are rounded to its precision.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST


def _mantissa3(t):
    """Round float32 to the 3 mantissa bits of float8 e4m3 (to nearest,
    ties to even), keeping float32's exponent range: e4m3's precision
    with ideal scaling, so nothing underflows that scaling would keep."""
    b = jax.lax.bitcast_convert_type(t.astype(F32), jnp.uint32)
    b = (b + jnp.uint32(0x7FFFF) + ((b >> 20) & 1)) & jnp.uint32(0xFFF00000)
    return jax.lax.bitcast_convert_type(b, F32)


@jax.custom_vjp
def _fp8(t):
    return _mantissa3(t)


# the control rounds values forward and gradients backward alike
_fp8.defvjp(lambda t: (_mantissa3(t), None), lambda _, g: (_mantissa3(g),))


def _round(t, precision):
    return _fp8(t) if precision == "fp8" else t


def mm(a, b, precision="f32"):
    return _round(jnp.matmul(_round(a, precision), _round(b, precision),
                             precision=HI), precision)


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def rope(x, pos, theta):
    """x [T, H, d]; the two halves of each head form the rotated pairs."""
    d = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = pos[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def layer(p, x, cfg, precision="f32", q_block: int = 512):
    """One decoder layer over one sequence x [T, D] (T a multiple of
    q_block; positions past the real tokens only see earlier ones, so
    right padding changes nothing before it)."""
    T, _ = x.shape
    H = cfg["n_heads"]
    qn, qr, vh, r = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"], cfg["kv_lora_rank"])
    eps = cfg["norm_eps"]
    pos = jnp.arange(T, dtype=F32)
    h = _round(rmsnorm(x, p["norm1/scale"], eps), precision)
    cq = rmsnorm(mm(h, p["attn/q_down"], precision), p["attn/q_norm/scale"],
                 eps)
    q = mm(cq, p["attn/q_up"], precision).reshape(T, H, qn + qr)
    q = jnp.concatenate([q[..., :qn], rope(q[..., qn:], pos,
                                           cfg["rope_theta"])], -1)
    c = mm(h, p["attn/kv_down"], precision)
    c_kv = rmsnorm(c[:, :r], p["attn/kv_norm/scale"], eps)
    k_rope = rope(c[:, None, r:], pos, cfg["rope_theta"])     # [T, 1, qr]
    kv = mm(c_kv, p["attn/kv_up"], precision).reshape(T, H, qn + vh)
    k = jnp.concatenate([kv[..., :qn],
                         jnp.broadcast_to(k_rope, (T, H, qr))], -1)
    v = kv[..., qn:]
    scale = 1.0 / math.sqrt(qn + qr)
    kq, vq = _round(k, precision), _round(v, precision)

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * q_block, q_block)
        s = jnp.einsum("thd,shd->hts", _round(qi, precision), kq,
                       precision=HI) * scale
        rows = i * q_block + jnp.arange(q_block)
        s = jnp.where(jnp.arange(T)[None, None, :] <= rows[None, :, None],
                      s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hts,shd->thd", _round(w, precision), vq,
                          precision=HI)

    o = jax.lax.map(block, jnp.arange(T // q_block)).reshape(T, H * vh)
    x = _round(x + mm(o, p["attn/wo"], precision), precision)
    h = _round(rmsnorm(x, p["norm2/scale"], eps), precision)
    g = mm(h, p["mlp/w_gate"], precision)
    u = mm(h, p["mlp/w_up"], precision)
    return _round(x + mm(jax.nn.silu(g) * u, p["mlp/w_down"], precision),
                  precision)


def logits(top, x, rows, cfg, precision="f32"):
    """Logits at rows of x [T, D] after the final norm (tied head)."""
    h = rmsnorm(x[rows], top["final_norm/scale"], cfg["norm_eps"])
    return mm(h, top["embed/table"].T, precision)


# ------------------------------------------------------------ counts
def matmul_params(cfg) -> int:
    """Parameters in a matrix product once per decoded token, the tied
    head included (the embedding lookup is a gather)."""
    D, H = cfg["d_model"], cfg["n_heads"]
    qn, qr, vh = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    ql, r = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    attn = (D * ql + ql * H * (qn + qr) + D * (r + qr) + r * H * (qn + vh)
            + H * vh * D)
    mlp = 3 * D * cfg["d_ff"]
    return cfg["n_layers"] * (attn + mlp) + D * cfg["vocab_size"]


def all_params(cfg) -> int:
    """Every parameter: the matmul weights, the norms (the tied
    embedding is counted once, as the head)."""
    D, ql, r = cfg["d_model"], cfg["q_lora_rank"], cfg["kv_lora_rank"]
    return matmul_params(cfg) + cfg["n_layers"] * (2 * D + ql + r) + D


def decode_flops(cfg, live_rows: float, tokens: float) -> float:
    """FLOPs of decoding `tokens` tokens whose contexts hold `live_rows`
    rows in all: the matmuls (2 per parameter) and, per layer and row,
    the scores against the latent and rope keys (2 (r + qr) per head)
    and the weighted sum of latents (2 r per head)."""
    H, r, qr = cfg["n_heads"], cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    return (2.0 * matmul_params(cfg) * tokens
            + cfg["n_layers"] * H * (4 * r + 2 * qr) * live_rows)


def decode_bytes(cfg, live_rows: float, param_bytes: int = 2,
                 cache_bytes: int = 2) -> float:
    """Least bytes one decode tick moves: every weight once, and the
    latent and rope rows live in the slots it decodes."""
    row = cfg["n_layers"] * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
    return param_bytes * all_params(cfg) + cache_bytes * row * live_rows
