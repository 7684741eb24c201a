"""The one place where the benchmark names the program's GLM-4.7-Flash
(``pipegoose_tpu/models/glm4_moe_lite.py``): its configuration class,
the layout of its parameter tree and what its ``Trainer`` is given.
Driver ``train_model`` finds this file by the name the configuration
file gives under ``program.adapter``; another architecture brings
another adapter and no driver.

What ``train_model`` asks of an adapter: ``sizes(config)`` (plain sizes
for the weights, the reference and the rooflines), ``make_config``,
``to_tree`` / ``from_tree`` (the benchmark's flat leaves <-> the
program's tree; ``from_tree`` skips what a tree lacks), ``specs``,
``trainer_kwargs`` (loss, counters, leaves without gradient) and
``counter_metrics``.
"""
from __future__ import annotations

import dataclasses

ATTN = {
    "ln1": ("ln_1", "scale"),
    "qa": ("attn", "q_a", "kernel"),
    "qa_norm": ("attn", "q_a_norm", "scale"),
    "qb": ("attn", "q_b", "kernel"),
    "kva": ("attn", "kv_a", "kernel"),
    "kva_norm": ("attn", "kv_a_norm", "scale"),
    "kvb": ("attn", "kv_b", "kernel"),
    "o": ("attn", "o", "kernel"),
    "ln2": ("ln_2", "scale"),
}
MLP = {k: ("mlp", k, "kernel") for k in ("gate", "up", "down")}
MOE = {
    "router_w": ("router", "gate", "kernel"),
    "router_b": ("router", "bias"),
    **{"sh_" + k: ("shared", k, "kernel") for k in ("gate", "up", "down")},
    **{"ex_" + k: ("experts", k, "kernel") for k in ("gate", "up", "down")},
}

# benchmark leaf name -> path in the program's tree
TREE = {
    "embed": ("embed", "weight"),
    "head": ("lm_head", "weight"),
    "lnf": ("ln_f", "scale"),
    **{"l0_" + k: ("dense",) + p for k, p in {**ATTN, **MLP}.items()},
    **{"moe_" + k: ("blocks",) + p for k, p in {**ATTN, **MOE}.items()},
    "mtp_enorm": ("mtp", "enorm", "scale"),
    "mtp_hnorm": ("mtp", "hnorm", "scale"),
    "mtp_eh": ("mtp", "eh_proj", "kernel"),
    "mtp_norm": ("mtp", "norm", "scale"),
    **{"mtp_" + k: ("mtp", "block") + p for k, p in {**ATTN, **MOE}.items()},
}
VOCAB_LEAVES = ("embed", "head")    # rows padded in the program's tree


def sizes(config: dict) -> dict:
    """The configuration as plain sizes: the published keys as the file
    has them (the three reduced ones as held here), the router's width,
    the experts held and the assumed values."""
    keys = ("vocab_size", "hidden_size", "intermediate_size",
            "moe_intermediate_size", "num_hidden_layers",
            "first_k_dense_replace", "num_attention_heads",
            "n_shared_experts", "num_experts_per_tok",
            "routed_scaling_factor", "norm_topk_prob", "q_lora_rank",
            "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "rope_theta", "rms_norm_eps",
            "num_nextn_predict_layers", "router_experts", "experts_held",
            "mtp_loss_weight", "initializer_range")
    out = {k: config[k] for k in keys}
    if config["n_routed_experts"] != config["experts_held"][1]:
        raise SystemExit("benchmark: n_routed_experts is the count held "
                         "here and has to agree with experts_held")
    return out


def padded_vocab(config: dict) -> int:
    to = config.get("vocab_pad_to", 1)
    return -(-config["vocab_size"] // to) * to


def to_tree(flat: dict, config: dict) -> dict:
    import jax.numpy as jnp

    pad = padded_vocab(config) - config["vocab_size"]
    tree = {}
    for name, path in TREE.items():
        x = flat[name]
        if name in VOCAB_LEAVES and pad:
            x = jnp.pad(x, ((0, pad), (0, 0)))
        if name.endswith("router_b"):
            x = x.astype(jnp.float32)
        node = tree
        for key in path[:-1]:
            node = node.setdefault(key, {})
        node[path[-1]] = x
    return tree


def from_tree(tree: dict, config: dict) -> dict:
    """The flat leaves of a tree shaped like the program's parameters;
    a leaf the tree lacks (no gradient, no optimizer state) is left out."""
    flat = {}
    for name, path in TREE.items():
        node = tree
        for key in path:
            node = None if node is None else node.get(key)
        if node is None:
            continue
        flat[name] = (node[:config["vocab_size"]]
                      if name in VOCAB_LEAVES else node)
    return flat


def _model():
    try:
        from pipegoose_tpu.models import glm4_moe_lite
    except ImportError as e:
        raise SystemExit(f"benchmark: this program has no "
                         f"models/glm4_moe_lite ({e}); nothing was run")
    return glm4_moe_lite


def make_config(config: dict, options: dict = None):
    """The program's ``Glm4MoeLiteConfig`` at the configuration's sizes
    and dtype. ``options`` are passed while the class still has the
    field; the ones it no longer has are printed and dropped."""
    import jax.numpy as jnp

    model = _model()
    fields = {f.name for f in dataclasses.fields(model.Glm4MoeLiteConfig)}
    options = dict(options or {})
    dropped = sorted(k for k in options if k not in fields)
    if dropped:
        print(f"benchmark: Glm4MoeLiteConfig has no field {dropped}; "
              f"dropped (now the program's default)", flush=True)
    kept = {k: v for k, v in options.items() if k in fields}
    published = {k: config[k] for k in (
        "hidden_size", "intermediate_size", "moe_intermediate_size",
        "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
        "n_shared_experts", "num_experts_per_tok", "routed_scaling_factor",
        "norm_topk_prob", "n_group", "topk_group", "first_k_dense_replace",
        "num_nextn_predict_layers", "q_lora_rank", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
        "rms_norm_eps", "tie_word_embeddings", "initializer_range")}
    return model.Glm4MoeLiteConfig(
        vocab_size=padded_vocab(config),
        valid_vocab_size=config["vocab_size"],
        n_routed_experts=config["router_experts"],
        experts_held=tuple(config["experts_held"]),
        rope_theta=float(config["rope_theta"]),
        mtp_loss_weight=config["mtp_loss_weight"],
        dtype=jnp.dtype(config["dtype"]), **published, **kept)


def specs(shapes: dict):
    return _model().tp_specs(shapes)


def counter_metrics() -> dict:
    """The step's counters by registry name (``telemetry.AuxRecorder``)."""
    return _model().COUNTER_METRICS


def trainer_kwargs(cfg, shapes: dict) -> dict:
    """What ``Trainer`` is given beside parameters, specs, optimizer and
    mesh: the loss on the normal path (counters beside it) and the
    leaves that take no gradient."""
    model = _model()
    return {
        "loss_fn": lambda p, ids: model.loss_and_counters(
            p, ids, None, ids, cfg, tp_axis="tensor"),
        "has_aux": True,
        "frozen": model.frozen_leaves(shapes),
    }
