"""Weights from the seed, made by the benchmark and not by the program.

Every leaf of the program's parameter tree is named by its path
("blocks/attn/wq"). Its values are a pure function of (seed, path,
layer): a uniform draw scaled by a rule on the leaf's name. The program
gets them in one jitted call on the device; the plain reference makes
the same values again, one layer at a time, through the same function,
so it never reads what the program holds.

Uniform bits become floats by exact operations only (a uniform in
[0, 1), then 2u - 1, then one multiply), so the same leaf comes out
bit for bit the same inside any program that makes it.
"""
from __future__ import annotations

import math
import zlib
from typing import Any, Callable, Dict, List, Tuple

STACKED = ("blocks", "dense_blocks")


def path_str(path) -> str:
    parts = []
    for k in path:
        parts.append(str(getattr(k, "key", getattr(k, "name",
                                                   getattr(k, "idx", k)))))
    return "/".join(parts)


def leaf_specs(shapes) -> List[Tuple[str, Tuple[int, ...], Any]]:
    """(path, shape, dtype) of each leaf of a ShapeDtypeStruct tree."""
    import jax
    flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
    return [(path_str(p), tuple(s.shape), s.dtype) for p, s in flat]


def _rule(path: str, shape: Tuple[int, ...]) -> Tuple[float, float]:
    """(centre, half-width) of the uniform draw for one layer's slice.
    Matrices get the fan-in scale of a truncated-normal init (std
    1/sqrt(fan_in)); vectors sit near the program's own init values, off
    them by a little so that every parameter carries a gradient. Where
    the centre is not 0 the half-width is a power of two: the multiply
    is then exact and the add is the only rounding, fused or not."""
    name = path.rsplit("/", 1)[-1]
    root3 = math.sqrt(3.0)
    if name == "table":
        return 0.0, 0.02 * root3
    if name in ("scale", "q_norm", "k_norm", "ln_x", "D"):
        return 1.0, 0.125
    if name == "A_log":
        return 0.0, 0.0625
    if name == "dt_bias":
        # dt = softplus(dt_bias + ...) near 0.127: above Mamba's published
        # init range (0.001 to 0.1), below the program's own 0 (dt near
        # 0.69). From that init a log-decay summed over one of the
        # program's 64-step scan chunks passes ~88 within tens of steps,
        # exp() overflows in the masked half of the chunk and the gradient
        # turns to NaN (PERF.md, Open questions; the configuration file's
        # departures)
        return -2.0, 0.0625
    if name == "conv_w":
        return 0.25, 0.125
    if name.endswith("bias"):
        return 0.0, 0.0
    fan_in = shape[0] if len(shape) > 1 else shape[-1]
    return 0.0, root3 / math.sqrt(max(fan_in, 1))


def seed_key(seed):
    """A key from a seed of any size up to 64 bits: traced seeds arrive
    as a uint32 pair (lo, hi)."""
    import jax
    import jax.numpy as jnp
    lo, hi = seed
    return jax.random.wrap_key_data(
        jnp.stack([jnp.asarray(hi, jnp.uint32), jnp.asarray(lo, jnp.uint32)]),
        impl="threefry2x32")


def split_seed(seed: int):
    import numpy as np
    seed = int(seed) % (1 << 64)
    return (np.uint32(seed & 0xFFFFFFFF), np.uint32(seed >> 32))


def layer_leaf(key, path: str, layer_shape, dtype, layer=None):
    """One layer's slice of a leaf (or the whole of an unstacked leaf)."""
    import jax
    import jax.numpy as jnp
    k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
    if layer is not None:
        k = jax.random.fold_in(k, layer)
    centre, half = _rule(path, layer_shape)
    u = jax.random.uniform(k, layer_shape, jnp.float32)
    w = (2.0 * u - 1.0) * half
    if centre:
        w = w + centre
    return w.astype(dtype)


def is_stacked(path: str) -> bool:
    return path.split("/", 1)[0] in STACKED


def make_generator(shapes) -> Callable:
    """jit-able fn(seed_pair) -> the whole tree, each leaf in its own
    dtype. Stacked leaves are made a layer at a time (lax.map), so no
    temporary holds more than one layer in float32."""
    import jax
    import jax.numpy as jnp
    specs = leaf_specs(shapes)
    treedef = jax.tree_util.tree_structure(shapes)

    def gen(seed_pair):
        key = seed_key(seed_pair)
        leaves = []
        for path, shape, dt in specs:
            if is_stacked(path):
                leaves.append(jax.lax.map(
                    lambda l, path=path, shape=shape, dt=dt: layer_leaf(
                        key, path, shape[1:], dt, l),
                    jnp.arange(shape[0])))
            else:
                leaves.append(layer_leaf(key, path, shape, dt))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return gen


def layer_weights(seed_pair, specs, layer: int, dtype,
                  group: str = "blocks") -> Dict[str, Any]:
    """The stacked leaves of one layer, by their path under `group`
    ("attn/wq" ...), as the program holds them, then cast to `dtype`."""
    key = seed_key(seed_pair)
    out = {}
    for path, shape, dt in specs:
        if path.split("/", 1)[0] == group:
            out[path.split("/", 1)[1]] = layer_leaf(
                key, path, shape[1:], dt, layer).astype(dtype)
    return out


def top_weights(seed_pair, specs, dtype) -> Dict[str, Any]:
    """The unstacked leaves (embedding, final norm, head) by path."""
    key = seed_key(seed_pair)
    return {path: layer_leaf(key, path, shape, dt).astype(dtype)
            for path, shape, dt in specs if not is_stacked(path)}
