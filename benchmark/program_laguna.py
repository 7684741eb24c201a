"""The one place where the benchmark names the program's Laguna
(``pipegoose_tpu/models/laguna.py``): its configuration class and the
layout of its parameter tree. Driver ``serve_model`` finds this file by
the name the configuration file gives under ``program.adapter``;
another architecture brings another adapter and no driver.

What ``serve_model`` asks of an adapter: ``sizes(config)`` (plain sizes
for the weights, the reference and the rooflines), ``make_config`` (what
``ServingEngine`` is given) and ``to_tree`` (the benchmark's flat leaves
-> the program's tree).
"""
from __future__ import annotations

ATTN = {"ln1": ("ln_1", "scale"), "q": ("attn", "q", "kernel"),
        "k": ("attn", "k", "kernel"), "v": ("attn", "v", "kernel"),
        "g": ("attn", "gate", "kernel"), "o": ("attn", "o", "kernel"),
        "ln2": ("ln_2", "scale")}
MLP = {k: ("mlp", k, "kernel") for k in ("gate", "up", "down")}
MOE = {
    "router": ("router", "gate", "kernel"),
    **{"sh_" + k: ("shared", k, "kernel") for k in ("gate", "up", "down")},
    **{"ex_" + k: ("experts", k, "kernel") for k in ("gate", "up", "down")},
}
TOP = {"embed": ("embed", "weight"), "head": ("lm_head", "weight"),
       "lnf": ("ln_f", "scale")}


def sizes(config: dict) -> dict:
    """The configuration as plain sizes: the published keys as the file
    has them (the three reduced ones as held here), the router's width
    and the experts held."""
    keys = ("vocab_size", "hidden_size", "intermediate_size",
            "moe_intermediate_size", "shared_expert_intermediate_size",
            "num_hidden_layers", "num_key_value_heads", "head_dim",
            "num_experts_per_tok", "moe_routed_scaling_factor",
            "norm_topk_prob", "rms_norm_eps", "sliding_window",
            "mlp_only_layers", "layer_types", "rope_parameters",
            "num_attention_heads_per_layer", "router_experts",
            "experts_held", "initializer_range")
    out = {k: config[k] for k in keys}
    if config["num_experts"] != config["experts_held"][1]:
        raise SystemExit("benchmark: num_experts is the count held here "
                         "and has to agree with experts_held")
    # the per-layer lists stay whole in the file, as published: the
    # layers held are the first num_hidden_layers of them
    n = config["num_hidden_layers"]
    for k in ("layer_types", "num_attention_heads_per_layer"):
        if len(config[k]) < n:
            raise SystemExit(f"benchmark: {k} needs {n} entries")
        out[k] = list(config[k][:n])
    out["mlp_only_layers"] = [i for i in config["mlp_only_layers"] if i < n]
    return out


def _model():
    try:
        from pipegoose_tpu.models import laguna
    except ImportError as e:
        raise SystemExit(f"benchmark: this program has no models/laguna "
                         f"({e}); nothing was run")
    return laguna


def make_config(config: dict, options: dict = None):
    """The program's ``LagunaConfig`` at the configuration's sizes and
    dtype, with the options the class still has."""
    import dataclasses

    import jax.numpy as jnp

    model = _model()
    fields = {f.name for f in dataclasses.fields(model.LagunaConfig)}
    options = dict(config.get("model_options") or {}, **(options or {}))
    dropped = sorted(k for k in options if k not in fields)
    if dropped:
        print(f"benchmark: LagunaConfig has no field {dropped}; dropped "
              f"(now the program's default)", flush=True)
    kept = {k: v for k, v in options.items() if k in fields}
    s = sizes(config)
    published = {k: s[k] for k in (
        "vocab_size", "hidden_size", "intermediate_size",
        "moe_intermediate_size", "shared_expert_intermediate_size",
        "num_hidden_layers", "num_key_value_heads", "head_dim",
        "num_experts_per_tok", "moe_routed_scaling_factor", "norm_topk_prob",
        "rms_norm_eps", "sliding_window", "initializer_range")}
    return model.LagunaConfig(
        num_experts=s["router_experts"],
        experts_held=tuple(s["experts_held"]),
        mlp_only_layers=tuple(s["mlp_only_layers"]),
        layer_types=tuple(s["layer_types"]),
        num_attention_heads_per_layer=tuple(
            s["num_attention_heads_per_layer"]),
        rope_parameters=model.freeze_rope(s["rope_parameters"]),
        dtype=jnp.dtype(config["dtype"]), **published, **kept)


def to_tree(flat: dict, config: dict) -> dict:
    """The benchmark's flat leaves as the program's parameter tree."""
    def put(tree, path, x):
        for key in path[:-1]:
            tree = tree.setdefault(key, {})
        tree[path[-1]] = x

    tree = {"layers": []}
    for name, path in TOP.items():
        put(tree, path, flat[name])
    for i in range(config["num_hidden_layers"]):
        layer = {}
        names = {**ATTN, **(MLP if i in config["mlp_only_layers"] else MOE)}
        for name, path in names.items():
            put(layer, path, flat[f"l{i}_{name}"])
        tree["layers"].append(layer)
    return tree
