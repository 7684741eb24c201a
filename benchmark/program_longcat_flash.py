"""The one place where the benchmark names the program's LongCat-Flash
(``pipegoose_tpu/models/longcat_flash.py``): its configuration class and
the layout of its parameter tree. Driver ``serve_model`` finds this file
by the name the configuration file gives under ``program.adapter``.

What ``serve_model`` asks of an adapter: ``sizes(config)`` (plain sizes
for the weights, the reference and the rooflines), ``make_config`` (what
``ServingEngine`` is given) and ``to_tree`` (the benchmark's flat leaves
-> the program's tree).
"""
from __future__ import annotations

HALF = {"ln_in": ("ln_in", "scale"), "qa": ("attn", "q_a", "kernel"),
        "qa_norm": ("attn", "q_a_norm", "scale"),
        "qb": ("attn", "q_b", "kernel"), "kva": ("attn", "kv_a", "kernel"),
        "kva_norm": ("attn", "kv_a_norm", "scale"),
        "kvb": ("attn", "kv_b", "kernel"), "o": ("attn", "o", "kernel"),
        "ln_post": ("ln_post", "scale"),
        **{k: ("mlp", k, "kernel") for k in ("gate", "up", "down")}}
BLOCK = {
    **{f"h{j}_{k}": (f"half{j}",) + path
       for j in (0, 1) for k, path in HALF.items()},
    "router": ("router", "gate", "kernel"), "bias": ("router", "bias"),
    **{"ex_" + k: ("experts", k, "kernel") for k in ("gate", "up", "down")},
}
TOP = {"embed": ("embed", "weight"), "head": ("lm_head", "weight"),
       "lnf": ("ln_f", "scale")}

PUBLISHED = (
    "vocab_size", "hidden_size", "ffn_hidden_size", "expert_ffn_hidden_size",
    "num_layers", "num_attention_heads", "kv_lora_rank", "q_lora_rank",
    "qk_rope_head_dim", "v_head_dim", "qk_nope_head_dim", "mla_scale_q_lora",
    "mla_scale_kv_lora", "routed_scaling_factor", "rms_norm_eps",
    "rope_theta", "zero_expert_num", "zero_expert_type", "moe_topk")


def sizes(config: dict) -> dict:
    """The configuration as plain sizes: the published keys as the file
    has them (the three reduced ones as held here), the router's width
    over real experts, the experts held, and what the file assumes."""
    out = {k: config[k] for k in PUBLISHED + (
        "router_experts", "experts_held", "initializer_range",
        "router_bias_std", "norm_topk_prob")}
    if config["n_routed_experts"] != config["experts_held"][1]:
        raise SystemExit("benchmark: n_routed_experts is the count held "
                         "here and has to agree with experts_held")
    return out


def _model():
    try:
        from pipegoose_tpu.models import longcat_flash
    except ImportError as e:
        raise SystemExit(f"benchmark: this program has no "
                         f"models/longcat_flash ({e}); nothing was run")
    return longcat_flash


def make_config(config: dict, options: dict = None):
    """The program's ``LongcatFlashConfig`` at the configuration's sizes
    and dtype, with the options the class still has."""
    import dataclasses

    import jax.numpy as jnp

    model = _model()
    fields = {f.name for f in dataclasses.fields(model.LongcatFlashConfig)}
    options = dict(config.get("model_options") or {}, **(options or {}))
    dropped = sorted(k for k in options if k not in fields)
    if dropped:
        print(f"benchmark: LongcatFlashConfig has no field {dropped}; "
              f"dropped (now the program's default)", flush=True)
    kept = {k: v for k, v in options.items() if k in fields}
    s = sizes(config)
    return model.LongcatFlashConfig(
        n_routed_experts=s["router_experts"],
        experts_held=tuple(s["experts_held"]),
        norm_topk_prob=s["norm_topk_prob"],
        initializer_range=s["initializer_range"],
        dtype=jnp.dtype(config["dtype"]),
        **{k: s[k] for k in PUBLISHED}, **kept)


def to_tree(flat: dict, config: dict) -> dict:
    """The benchmark's flat leaves as the program's parameter tree."""
    def put(tree, path, x):
        for key in path[:-1]:
            tree = tree.setdefault(key, {})
        tree[path[-1]] = x

    tree = {"layers": []}
    for name, path in TOP.items():
        put(tree, path, flat[name])
    for i in range(config["num_layers"]):
        layer = {}
        for name, path in BLOCK.items():
            put(layer, path, flat[f"l{i}_{name}"])
        tree["layers"].append(layer)
    return tree
