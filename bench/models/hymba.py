"""Plain float32 reference of the hybrid (Hymba-style) decoder, and its
operation counts.

It imports nothing of the program. Per layer, as the configuration file
states it:

    h  = rmsnorm(x) * norm1
    x += (swa_attention(h) + mamba(h)) / 2
    x += swiglu_mlp(rmsnorm(x) * norm2)

then rmsnorm * final_norm and an untied head. Attention is grouped-query
with rotary positions (the two halves of each head rotated together),
causal and limited to the last `sliding_window` positions, written as a
full masked softmax. The Mamba mixer is the selective-state recurrence
S_t = exp(dt_t * a) S_{t-1} + B_t (x_t dt_t), y_t = C_t . S_t + D x_t,
gated by silu(z) and normed, written as a sequential scan over time
(the program evaluates it in chunks). Every matmul runs at `highest`
precision. The control computes in float8 (e4m3) where the program
computes in bfloat16: matmul operands and results, norm outputs and the
residual stream are rounded to its precision, and so is every gradient that
flows back through them.
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

F32 = jnp.float32


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


def mm(a, b, precision: str = "f32"):
    return _round(jnp.matmul(_round(a, precision), _round(b, precision),
                             precision=jax.lax.Precision.HIGHEST), precision)


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def rope(x, pos, theta):
    """x [B, T, H, Dh]; the first and second halves of each head are the
    two coordinates of each rotated pair."""
    dh = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=F32) / dh)
    ang = pos[:, None] * freqs                       # [T, Dh/2]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(p, h, cfg, prec):
    B, T, _ = h.shape
    H, KV, Dh = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    pos = jnp.arange(T, dtype=F32)
    q = rope(mm(h, p["attn/wq"], prec).reshape(B, T, H, Dh), pos,
             cfg["rope_theta"])
    k = rope(mm(h, p["attn/wk"], prec).reshape(B, T, KV, Dh), pos,
             cfg["rope_theta"])
    v = mm(h, p["attn/wv"], prec).reshape(B, T, KV, Dh)
    k = jnp.repeat(k, H // KV, axis=2)               # query head i reads
    v = jnp.repeat(v, H // KV, axis=2)               # kv head i // (H/KV)
    s = jnp.einsum("bthd,bshd->bhts", _round(q, prec), _round(k, prec),
                   precision=jax.lax.Precision.HIGHEST) / math.sqrt(Dh)
    i = jnp.arange(T)
    mask = i[None, :] <= i[:, None]
    if cfg["sliding_window"]:
        mask &= i[None, :] > i[:, None] - cfg["sliding_window"]
    s = jnp.where(mask, s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhts,bshd->bthd", _round(w, prec), _round(v, prec),
                   precision=jax.lax.Precision.HIGHEST)
    return mm(o.reshape(B, T, H * Dh), p["attn/wo"], prec)


def mamba(p, h, cfg, prec):
    B, T, D = h.shape
    H = cfg["ssm_heads"] or cfg["n_heads"]
    P = D // H
    xs = mm(h, p["mamba/w_x"], prec)                 # [B, T, H*P]
    z = mm(h, p["mamba/w_z"], prec)
    Bm = mm(h, p["mamba/w_B"], prec)                 # [B, T, N]
    Cm = mm(h, p["mamba/w_C"], prec)
    dt = jax.nn.softplus(mm(h, p["mamba/w_dt"], prec) + p["mamba/dt_bias"])
    a = -jnp.exp(p["mamba/A_log"])                   # [H]
    w = p["mamba/conv_w"]                            # [K, H*P]
    K = w.shape[0]
    conv = sum(jnp.pad(xs, ((0, 0), (j, 0), (0, 0)))[:, :T] * w[K - 1 - j]
               for j in range(K))                    # causal, depthwise
    xh = jax.nn.silu(conv).reshape(B, T, H, P)
    u = xh * dt[..., None]
    decay = jnp.exp(dt * a)                          # [B, T, H]

    def step(S, inp):                                # S [B, H, N, P]
        d_t, b_t, c_t, u_t = inp
        S = d_t[:, :, None, None] * S + jnp.einsum("bn,bhp->bhnp", b_t, u_t)
        return S, jnp.einsum("bn,bhnp->bhp", c_t, S,
                             precision=jax.lax.Precision.HIGHEST)

    N = Bm.shape[-1]
    t_major = lambda t: jnp.moveaxis(t, 1, 0)
    _, y = jax.lax.scan(step, jnp.zeros((B, H, N, P), F32),
                        (t_major(decay), t_major(Bm), t_major(Cm),
                         t_major(u)))
    y = jnp.moveaxis(y, 0, 1) + p["mamba/D"][None, None, :, None] * xh
    y = y.reshape(B, T, H * P) * jax.nn.silu(z)
    y = rmsnorm(y, p["mamba/norm/scale"], cfg["norm_eps"])
    return mm(y, p["mamba/w_out"], prec)


def block(p, x, cfg, prec):
    eps = cfg["norm_eps"]
    h = _round(rmsnorm(x, p["norm1/scale"], eps), prec)
    x = _round(x + 0.5 * (attention(p, h, cfg, prec)
                          + mamba(p, h, cfg, prec)), prec)
    h = _round(rmsnorm(x, p["norm2/scale"], eps), prec)
    g = mm(h, p["mlp/w_gate"], prec)
    u = mm(h, p["mlp/w_up"], prec)
    return _round(x + mm(jax.nn.silu(g) * u, p["mlp/w_down"], prec), prec)


def loss(params: Dict[str, Any], tokens, labels, cfg, prec: str = "f32"):
    """Mean next-token cross entropy over labels >= 0. `params` maps each
    path to its value; "blocks/..." leaves are stacked over layers."""
    x = _round(params["embed/table"][tokens], prec)
    blocks = {k.split("/", 1)[1]: v for k, v in params.items()
              if k.startswith("blocks/")}
    body = jax.checkpoint(lambda x, p: block(p, x, cfg, prec))
    x, _ = jax.lax.scan(lambda x, p: (body(x, p), None), x, blocks)
    x = rmsnorm(x, params["final_norm/scale"], cfg["norm_eps"])
    logits = mm(x, params["lm_head/w"], prec)
    logp = jax.nn.log_softmax(logits, axis=-1)
    mask = (labels >= 0).astype(F32)
    nll = -jnp.take_along_axis(logp, jnp.maximum(labels, 0)[..., None],
                               -1)[..., 0]
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


# ------------------------------------------------------------ counts
def matmul_params(cfg) -> int:
    """Parameters that enter a matrix product once per token (the
    embedding lookup is a gather and does not)."""
    D, H, KV, Dh = (cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"],
                    cfg["head_dim"])
    Hs = cfg["ssm_heads"] or H
    HP = (D // Hs) * Hs
    attn = D * H * Dh * 2 + D * KV * Dh * 2
    ssm = D * HP * 2 + D * cfg["ssm_state"] * 2 + D * Hs + HP * D
    mlp = 3 * D * cfg["d_ff"]
    return cfg["n_layers"] * (attn + ssm + mlp) + D * cfg["vocab_size"]


def mean_keys(seq_len: int, window: int) -> float:
    """Mean number of keys a causal query attends to in a sequence."""
    w = window or seq_len
    return sum(min(p + 1, w) for p in range(seq_len)) / seq_len


def train_flops_per_token(cfg, seq_len: int) -> float:
    """Model FLOPs per trained token, forward and backward (3x forward),
    no recomputation: the matmuls, the attention scores and weighted
    values over the keys in the window, and the state update and read
    of the recurrence."""
    H, Dh = cfg["n_heads"], cfg["head_dim"]
    Hs = cfg["ssm_heads"] or H
    P = cfg["d_model"] // Hs
    keys = mean_keys(seq_len, cfg["sliding_window"])
    fwd = (2 * matmul_params(cfg)
           + cfg["n_layers"] * (4 * H * Dh * keys
                                + 4 * Hs * cfg["ssm_state"] * P))
    return 3.0 * fwd
