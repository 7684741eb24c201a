"""The benchmark's own seeded weights for an EvaByte-shaped model.

One flat dict of named arrays in the dtype the model is served in, as
``weights.py`` makes BLOOM's: the adapter maps the names onto the
program's tree; the reference takes the same dict (and nothing the
program made). Every layer is alike, so a layer's leaves are STACKED on a
leading axis (``q`` is (layers, hidden, hidden)), as the program keeps
them.

Matrices are N(0, ``init_std``); the norms' offsets ``g``
(``norm_add_unit_offset``: the scale is ``1 + g``) N(0, 0.02), so a path
that drops one changes the result. EVA's two vectors a head, ``phi``
(the pooling's query) and ``mu`` (added to a pooled key), are N(0, 1)
clipped to +-1, times ``phi_std`` and ``mu_std``: wide enough that
uniform pooling, a dropped ``mu`` or a summary handed over wrongly each
change what a query past its first window reads (the configuration
file's ``assumed`` gives the readings: how far each moves a summary's
score, and the share of a query's probability on summaries).

Made LEAF BY LEAF, one jitted call a distinct shape: a stacked
feed-forward matrix is 1.1 GB in bfloat16 and twice that as the float32
normals it is rounded from.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from benchmark.weights import seed_key  # noqa: F401  (drivers take it here)

NORM_STD = 0.02
NORMS, VECTORS = ("lnf", "ln1", "ln2"), ("phi", "mu")


def leaf_shapes(sizes: dict) -> dict:
    """name -> shape."""
    h, f, n = sizes["hidden_size"], sizes["intermediate_size"], \
        sizes["num_hidden_layers"]
    nh, v = sizes["num_attention_heads"], sizes["vocab_size"]
    return {
        "embed": (v, h), "head": (h, sizes["num_pred_heads"] * v),
        "lnf": (h,), "ln1": (n, h), "ln2": (n, h),
        "q": (n, h, h), "k": (n, h, h), "v": (n, h, h), "o": (n, h, h),
        "phi": (n, nh, h // nh), "mu": (n, nh, h // nh),
        "gate": (n, h, f), "up": (n, h, f), "down": (n, f, h),
    }


def n_params(sizes: dict) -> int:
    total = 0
    for shape in leaf_shapes(sizes).values():
        n = 1
        for d in shape:
            n *= d
        total += n
    return total


@partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _leaf(key, shape, std, clip, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    if clip:
        x = jnp.clip(x, -1.0, 1.0)
    x = x * std
    # round by an operation XLA may not drop (see weights.py)
    info = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, info.nexp, info.nmant).astype(dtype)


def make(key: jax.Array, sizes: dict, dtype=jnp.bfloat16) -> dict:
    """All leaves from ``key`` (see ``leaf_shapes``); a jitted call a
    leaf."""
    std = float(sizes["init_std"])
    spread = {**dict.fromkeys(NORMS, NORM_STD),
              "phi": float(sizes["phi_std"]), "mu": float(sizes["mu_std"])}
    dtype = jnp.dtype(dtype)
    return {name: _leaf(jax.random.fold_in(key, i), shape,
                        spread.get(name, std), name in VECTORS, dtype)
            for i, (name, shape) in enumerate(sorted(
                leaf_shapes(sizes).items()))}
