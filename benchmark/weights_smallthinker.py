"""The benchmark's own seeded weights for a SmallThinker-shaped model.

One flat dict of named arrays in the dtype the model is served in, as
``weights_laguna.py`` makes Laguna's (its jitted ``_leaf`` is used
here): the adapter maps the names onto the program's tree; the reference
takes the same dict (and nothing the program made). Every leaf is
random, the router's kernel and the norms' scales too (the scales
centred on 1), so a path that drops one of them changes the result. A
layer's leaves carry its number (``l3_q``); its experts are stacked on a
leading axis.

Made LEAF BY LEAF, one jitted call a distinct shape: a layer's expert
leaf is 0.25 GB in bfloat16 and 0.5 GB as the float32 normals it is
rounded from, and one call making all 11 GB could hold every leaf's
float32 at once.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.weights import seed_key  # noqa: F401  (drivers take it here)
from benchmark.weights_laguna import _leaf


def leaf_shapes(sizes: dict) -> dict:
    """name -> (shape, centre): a leaf is N(0, std) where centre is 0 and
    centre * (1 + N(0, std)) otherwise (norm scales)."""
    h, v, hd = sizes["hidden_size"], sizes["vocab_size"], sizes["head_dim"]
    nh, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    f, e = sizes["moe_ffn_hidden_size"], sizes["moe_num_primary_experts"]
    held = sizes["experts_held"][1]
    out = {"embed": ((v, h), 0), "head": ((v, h), 0), "lnf": ((h,), 1)}
    layer = {
        "ln1": ((h,), 1), "router": ((h, e), 0),
        "q": ((h, nh * hd), 0), "k": ((h, kv * hd), 0),
        "v": ((h, kv * hd), 0), "o": ((nh * hd, h), 0), "ln2": ((h,), 1),
        "ex_gate": ((held, h, f), 0), "ex_up": ((held, h, f), 0),
        "ex_down": ((held, f, h), 0)}
    for i in range(sizes["num_hidden_layers"]):
        out.update({f"l{i}_{k}": x for k, x in layer.items()})
    return out


def n_params(sizes: dict) -> int:
    return sum(math.prod(shape) for shape, _ in leaf_shapes(sizes).values())


def make(key: jax.Array, sizes: dict, dtype=jnp.bfloat16) -> dict:
    """All leaves from ``key`` (see ``leaf_shapes``), std the
    configuration's initializer_range; a jitted call a leaf."""
    std = float(sizes.get("initializer_range", 0.02))
    dtype = jnp.dtype(dtype)
    return {name: _leaf(jax.random.fold_in(key, i), shape, centre, std, dtype)
            for i, (name, (shape, centre)) in enumerate(
                sorted(leaf_shapes(sizes).items()))}
