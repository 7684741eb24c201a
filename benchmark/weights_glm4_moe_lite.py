"""The benchmark's own seeded weights for a GLM-4.7-Flash-shaped model.

One flat dict of named arrays, made on the device in one jitted call in
the dtype the model is trained in, as ``weights.py`` makes BLOOM's. The
adapter maps the names onto the program's tree; the reference takes the
same dict (and nothing the program made). Every leaf is random: the
norms' scales (centred on 1) and the routers' selection bias too, so a
path that drops one of them changes the result. Leaves of the expert
layers are stacked on a leading L, a layer's held experts on a second
axis.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.weights import seed_key  # noqa: F401  (drivers take it here)


def _attn(s: dict) -> dict:
    h, nh = s["hidden_size"], s["num_attention_heads"]
    rq, r = s["q_lora_rank"], s["kv_lora_rank"]
    dn, dr, dv = s["qk_nope_head_dim"], s["qk_rope_head_dim"], s["v_head_dim"]
    return {
        "ln1": ((h,), 1), "qa": ((h, rq), 0), "qa_norm": ((rq,), 1),
        "qb": ((rq, nh * (dn + dr)), 0), "kva": ((h, r + dr), 0),
        "kva_norm": ((r,), 1), "kvb": ((r, nh * (dn + dv)), 0),
        "o": ((nh * dv, h), 0), "ln2": ((h,), 1),
    }


def _moe(s: dict) -> dict:
    h, f = s["hidden_size"], s["moe_intermediate_size"]
    fs = f * s["n_shared_experts"]
    e, held = s["router_experts"], s["experts_held"][1]
    return {
        "router_w": ((h, e), 0), "router_b": ((e,), 0),
        "sh_gate": ((h, fs), 0), "sh_up": ((h, fs), 0),
        "sh_down": ((fs, h), 0),
        "ex_gate": ((held, h, f), 0), "ex_up": ((held, h, f), 0),
        "ex_down": ((held, f, h), 0),
    }


def leaf_shapes(sizes: dict) -> dict:
    """name -> (shape, centre): a leaf is N(0, std) where centre is 0 and
    centre * (1 + N(0, std)) otherwise (norm scales)."""
    h, v, f = (sizes["hidden_size"], sizes["vocab_size"],
               sizes["intermediate_size"])
    n_moe = sizes["num_hidden_layers"] - sizes["first_k_dense_replace"]
    block = {**_attn(sizes), **_moe(sizes)}
    out = {"embed": ((v, h), 0), "head": ((v, h), 0), "lnf": ((h,), 1)}
    out.update({"l0_" + k: x for k, x in _attn(sizes).items()})
    out.update({"l0_gate": ((h, f), 0), "l0_up": ((h, f), 0),
                "l0_down": ((f, h), 0)})
    out.update({"moe_" + k: ((n_moe,) + shape, c)
                for k, (shape, c) in block.items()})
    if sizes["num_nextn_predict_layers"]:
        out.update({"mtp_enorm": ((h,), 1), "mtp_hnorm": ((h,), 1),
                    "mtp_eh": ((2 * h, h), 0), "mtp_norm": ((h,), 1)})
        out.update({"mtp_" + k: x for k, x in block.items()})
    return out


def n_params(sizes: dict) -> int:
    total = 0
    for shape, _ in leaf_shapes(sizes).values():
        n = 1
        for d in shape:
            n *= d
        total += n
    return total


def make(key: jax.Array, sizes: dict, dtype=jnp.bfloat16) -> dict:
    """All leaves from ``key`` (see ``leaf_shapes``), std the
    configuration's initializer_range. Call under ``jax.jit``."""
    std = sizes.get("initializer_range", 0.02)
    out = {}
    for i, (name, (shape, centre)) in enumerate(
            sorted(leaf_shapes(sizes).items())):
        x = jax.random.normal(jax.random.fold_in(key, i), shape,
                              jnp.float32) * std
        if centre:
            x = (x + 1.0) * centre
        # round by an operation XLA may not drop (see weights.py)
        info = jnp.finfo(dtype)
        out[name] = jax.lax.reduce_precision(
            x, info.nexp, info.nmant).astype(dtype)
    return out
