"""The benchmark's own seeded weights for a LongCat-Flash-shaped model.

One flat dict of named arrays in the dtype the model is served in, as
``weights.py`` makes BLOOM's: the adapter maps the names onto the
program's tree; the reference takes the same dict (and nothing the
program made). A block's leaves carry its number (``l2_h0_qa``) and
nothing is stacked over blocks (the program keeps a tree a block:
``models/longcat_flash.py:param_shapes`` says why); ``h0_`` and ``h1_``
are a block's two halves (an attention, its two norms, its dense
feed-forward each), ``router``, ``bias`` and ``ex_`` the one expert layer
between them, its held experts stacked on a leading axis.

Every matrix is N(0, ``initializer_range``); the norms' scales are
random too (centred on 1), so a path that drops one changes the result.
The router's selection bias (``e_score_correction_bias``, float32) is
N(0, ``router_bias_std``): wide enough against softmax scores that about
one pick in twelve differs from the unbiased choice (the configuration
file's ``assumed`` gives the reading), so that weights taken from the
biased scores, or a selection without the bias, change the result.

Made LEAF BY LEAF, one jitted call a distinct shape: a block's held
experts' one matrix is 0.4 GB in bfloat16 and twice that as the float32
normals it is rounded from, and one call making all 10 GB could hold
every leaf's float32 at once.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from benchmark.weights import seed_key  # noqa: F401  (drivers take it here)

BIAS = "bias"


def half_shapes(sizes: dict) -> dict:
    """One half of a block, a layer: name -> (shape, centre)."""
    h, nh = sizes["hidden_size"], sizes["num_attention_heads"]
    rq, rkv = sizes["q_lora_rank"], sizes["kv_lora_rank"]
    dn, dr, dv = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"], \
        sizes["v_head_dim"]
    f = sizes["ffn_hidden_size"]
    return {
        "ln_in": ((h,), 1), "qa": ((h, rq), 0), "qa_norm": ((rq,), 1),
        "qb": ((rq, nh * (dn + dr)), 0), "kva": ((h, rkv + dr), 0),
        "kva_norm": ((rkv,), 1), "kvb": ((rkv, nh * (dn + dv)), 0),
        "o": ((nh * dv, h), 0), "ln_post": ((h,), 1),
        "gate": ((h, f), 0), "up": ((h, f), 0), "down": ((f, h), 0),
    }


def leaf_shapes(sizes: dict) -> dict:
    """name -> (shape, centre): a leaf is N(0, std) where centre is 0 and
    centre * (1 + N(0, std)) otherwise (norm scales)."""
    h, v = sizes["hidden_size"], sizes["vocab_size"]
    fe, held = sizes["expert_ffn_hidden_size"], sizes["experts_held"][1]
    e = sizes["router_experts"] + sizes["zero_expert_num"]
    block = {f"h{j}_{k}": x for j in (0, 1)
             for k, x in half_shapes(sizes).items()}
    block.update({
        "router": ((h, e), 0), BIAS: ((e,), 0),
        "ex_gate": ((held, h, fe), 0), "ex_up": ((held, h, fe), 0),
        "ex_down": ((held, fe, h), 0)})
    out = {"embed": ((v, h), 0), "head": ((v, h), 0), "lnf": ((h,), 1)}
    for i in range(sizes["num_layers"]):
        out.update({f"l{i}_{k}": x for k, x in block.items()})
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
    """All leaves from ``key`` (see ``leaf_shapes``); a jitted call a
    leaf. The selection bias stays float32 at its own spread."""
    std = float(sizes.get("initializer_range", 0.02))
    dtype, f32 = jnp.dtype(dtype), jnp.dtype(jnp.float32)
    out = {}
    for i, (name, (shape, centre)) in enumerate(
            sorted(leaf_shapes(sizes).items())):
        bias = name.endswith("_" + BIAS)
        out[name] = _leaf(jax.random.fold_in(key, i), shape, centre,
                          float(sizes["router_bias_std"]) if bias else std,
                          f32 if bias else dtype)
    return out
