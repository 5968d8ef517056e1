"""Weights from the seed: the program's copy (the whole tree in one call)
and the reference's (one layer at a time) are the same bits, seeds of
any size work, and different seeds differ."""
import pathlib
import sys

import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from bench import weights  # noqa: E402


def shapes():
    import jax
    import jax.numpy as jnp
    f = jax.ShapeDtypeStruct
    return {"embed": {"table": f((50, 8), jnp.float32)},
            "final_norm": {"scale": f((8,), jnp.float32)},
            "blocks": {"attn": {"wq": f((3, 8, 16), jnp.bfloat16)},
                       "mamba": {"A_log": f((3, 4), jnp.float32),
                                 "conv_w": f((3, 4, 16), jnp.float32)},
                       "norm1": {"scale": f((3, 8), jnp.float32)}}}


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 12345, 2 ** 63 + 5])
def test_the_reference_remakes_the_programs_weights_bit_for_bit(seed):
    import jax
    import jax.numpy as jnp
    s = shapes()
    specs = weights.leaf_specs(s)
    pair = weights.split_seed(seed)
    tree = jax.jit(weights.make_generator(s))(pair)
    flat = {weights.path_str(p): np.asarray(v.astype(jnp.float32))
            for p, v in jax.tree_util.tree_flatten_with_path(tree)[0]}
    top = jax.jit(lambda q: weights.top_weights(q, specs, jnp.float32))(pair)
    for k, v in top.items():
        np.testing.assert_array_equal(np.asarray(v), flat[k])
    for layer in range(3):
        lw = jax.jit(lambda q, l: weights.layer_weights(
            q, specs, l, jnp.float32))(pair, layer)
        for k, v in lw.items():
            np.testing.assert_array_equal(np.asarray(v),
                                          flat["blocks/" + k][layer])


def test_seeds_and_layers_differ_and_rules_hold():
    import jax
    s = shapes()
    a = jax.jit(weights.make_generator(s))(weights.split_seed(1))
    b = jax.jit(weights.make_generator(s))(weights.split_seed(2))
    wq = np.asarray(a["blocks"]["attn"]["wq"], np.float32)
    assert not np.array_equal(wq, np.asarray(b["blocks"]["attn"]["wq"],
                                             np.float32))
    assert not np.array_equal(wq[0], wq[1])
    assert np.abs(wq).max() <= np.sqrt(3.0) / np.sqrt(8) * 1.01
    scale = np.asarray(a["final_norm"]["scale"])
    assert np.all((scale >= 0.875) & (scale <= 1.125))
    conv = np.asarray(a["blocks"]["mamba"]["conv_w"])
    assert np.all((conv >= 0.125) & (conv <= 0.375))
