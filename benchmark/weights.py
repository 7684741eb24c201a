"""The benchmark's own seeded weights for a BLOOM-shaped model.

One flat dict of named arrays, made on the device in one jitted call in
the dtype the model is served in. A driver maps the names onto the
program's tree; the reference takes the same dict (and nothing the
program made). Every leaf is random — biases and layer-norm parameters
too — so a path that drops one of them changes the result.

One departure from a plain N(0, initializer_range) init: the embedding
layer norm's scale is centred on ``EMBED_LN_GAIN`` (0.1), not 1. With
the head tied to the embedding and a scale of 1, the final hidden state
is mostly the input token's own embedding, so every greedy token merely
echoes its predecessor by a margin of many logits — whatever the
attention, the cache or the precision did. At 0.1 the blocks' outputs
decide the next token, which is what the comparison has to see.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_SEED_MOD = 2 ** 31 - 1
EMBED_LN_GAIN = 0.1


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number (the driver's seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % _SEED_MOD),
                              seed // _SEED_MOD)


def leaf_shapes(sizes: dict) -> dict:
    """name -> (shape, centre): a leaf is N(0, std) where centre is 0
    (matrices, biases) and centre * (1 + N(0, std)) otherwise (layer-norm
    scales). Per-layer leaves are stacked on a leading L."""
    h, v, L = sizes["hidden_size"], sizes["vocab_size"], sizes["n_layer"]
    return {
        "embed": ((v, h), 0),
        "embed_ln_scale": ((h,), EMBED_LN_GAIN), "embed_ln_bias": ((h,), 0),
        "ln1_scale": ((L, h), 1), "ln1_bias": ((L, h), 0),
        # HF BLOOM's fused query_key_value: columns ordered (head, 3, hd)
        "qkv_w": ((L, h, 3 * h), 0), "qkv_b": ((L, 3 * h), 0),
        "out_w": ((L, h, h), 0), "out_b": ((L, h), 0),
        "ln2_scale": ((L, h), 1), "ln2_bias": ((L, h), 0),
        "up_w": ((L, h, 4 * h), 0), "up_b": ((L, 4 * h), 0),
        "down_w": ((L, 4 * h, h), 0), "down_b": ((L, h), 0),
        "lnf_scale": ((h,), 1), "lnf_bias": ((h,), 0),
    }


def make(key: jax.Array, sizes: dict, dtype=jnp.bfloat16) -> dict:
    """All leaves from ``key`` (see ``leaf_shapes``), std the
    configuration's initializer_range. Call under ``jax.jit`` (with
    ``out_shardings`` on a mesh)."""
    std = sizes.get("initializer_range", 0.02)
    shapes = leaf_shapes(sizes)
    out = {}
    for i, (name, (shape, centre)) in enumerate(sorted(shapes.items())):
        x = jax.random.normal(jax.random.fold_in(key, i), shape,
                              jnp.float32) * std
        if centre:
            x = (x + 1.0) * centre
        # round HERE, by an operation XLA may not drop: a caller that
        # widens the result again inside the same jit would otherwise
        # get the unrounded float32 (astype().astype() is elided on TPU)
        info = jnp.finfo(dtype)
        out[name] = jax.lax.reduce_precision(
            x, info.nexp, info.nmant).astype(dtype)
    return out
