"""The benchmark's own seeded weights for a Laguna-shaped model.

One flat dict of named arrays in the dtype the model is served in, as
``weights.py`` makes BLOOM's: the adapter maps the names onto the
program's tree; the reference takes the same dict (and nothing the
program made). Every leaf is random, the norms' scales too (centred on
1), so a path that drops one of them changes the result. Layers differ
in shape (48 or 72 query heads, dense or sparse), so a layer's leaves
carry its number (``l3_q``) and nothing is stacked over layers; a sparse
layer's held experts are stacked on a leading axis.

Made LEAF BY LEAF, one jitted call a distinct shape: a sparse layer's
expert leaf is 0.8 GB in bfloat16 and 1.6 GB as the float32 normals it
is rounded from, and one call making all 11 GB could hold every leaf's
float32 at once.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from benchmark.weights import seed_key  # noqa: F401  (drivers take it here)


def leaf_shapes(sizes: dict) -> dict:
    """name -> (shape, centre): a leaf is N(0, std) where centre is 0 and
    centre * (1 + N(0, std)) otherwise (norm scales)."""
    h, v, hd = sizes["hidden_size"], sizes["vocab_size"], sizes["head_dim"]
    kv = sizes["num_key_value_heads"]
    f, fe = sizes["intermediate_size"], sizes["moe_intermediate_size"]
    fs = sizes["shared_expert_intermediate_size"]
    e, held = sizes["router_experts"], sizes["experts_held"][1]
    out = {"embed": ((v, h), 0), "head": ((v, h), 0), "lnf": ((h,), 1)}
    for i in range(sizes["num_hidden_layers"]):
        nh = sizes["num_attention_heads_per_layer"][i]
        layer = {
            "ln1": ((h,), 1), "q": ((h, nh * hd), 0), "k": ((h, kv * hd), 0),
            "v": ((h, kv * hd), 0), "g": ((h, nh), 0), "o": ((nh * hd, h), 0),
            "ln2": ((h,), 1),
        }
        if i in sizes["mlp_only_layers"]:
            layer.update({"gate": ((h, f), 0), "up": ((h, f), 0),
                          "down": ((f, h), 0)})
        else:
            layer.update({
                "router": ((h, e), 0),
                "sh_gate": ((h, fs), 0), "sh_up": ((h, fs), 0),
                "sh_down": ((fs, h), 0),
                "ex_gate": ((held, h, fe), 0), "ex_up": ((held, h, fe), 0),
                "ex_down": ((held, fe, h), 0)})
        out.update({f"l{i}_{k}": x for k, x in layer.items()})
    return out


def n_params(sizes: dict) -> int:
    total = 0
    for shape, _ in leaf_shapes(sizes).values():
        n = 1
        for d in shape:
            n *= d
        total += n
    return total


@partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _leaf(key, shape, centre, std, dtype):
    x = jax.random.normal(key, shape, jnp.float32) * std
    if centre:
        x = (x + 1.0) * centre
    # round by an operation XLA may not drop (see weights.py)
    info = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, info.nexp, info.nmant).astype(dtype)


def make(key: jax.Array, sizes: dict, dtype=jnp.bfloat16) -> dict:
    """All leaves from ``key`` (see ``leaf_shapes``), std the
    configuration's initializer_range; a jitted call a leaf."""
    std = float(sizes.get("initializer_range", 0.02))
    dtype = jnp.dtype(dtype)
    return {name: _leaf(jax.random.fold_in(key, i), shape, centre, std, dtype)
            for i, (name, (shape, centre)) in enumerate(
                sorted(leaf_shapes(sizes).items()))}
