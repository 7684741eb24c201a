"""The one place where the benchmark names the program's SmallThinker
(``pipegoose_tpu/models/smallthinker.py``): its configuration class and
the layout of its parameter tree. Driver ``serve_model`` finds this file
by the name the configuration file gives under ``program.adapter``.

What ``serve_model`` asks of an adapter: ``sizes(config)`` (plain sizes
for the weights, the reference and the rooflines), ``make_config`` (what
``ServingEngine`` is given) and ``to_tree`` (the benchmark's flat leaves
-> the program's tree).
"""
from __future__ import annotations

LAYER = {
    "ln1": ("ln_1", "scale"), "router": ("router", "gate", "kernel"),
    "q": ("attn", "q", "kernel"), "k": ("attn", "k", "kernel"),
    "v": ("attn", "v", "kernel"), "o": ("attn", "o", "kernel"),
    "ln2": ("ln_2", "scale"),
    **{"ex_" + k: ("experts", k, "kernel") for k in ("gate", "up", "down")},
}
TOP = {"embed": ("embed", "weight"), "head": ("lm_head", "weight"),
       "lnf": ("ln_f", "scale")}
# the published keys the program's configuration class takes as they are
PUBLISHED = (
    "vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "head_dim", "rms_norm_eps", "moe_ffn_hidden_size",
    "moe_num_primary_experts", "moe_num_active_primary_experts",
    "moe_primary_router_apply_softmax", "norm_topk_prob",
    "sliding_window_size", "rope_theta", "rope_scaling",
    "max_position_embeddings", "tie_word_embeddings", "initializer_range")
LAYOUTS = ("rope_layout", "sliding_window_layout")


def sizes(config: dict) -> dict:
    """The configuration as plain sizes: the published keys as the file
    has them (the depth as held here), the experts held, and
    ``sliding_window``: the window under the name driver ``serve_model``
    reads it by."""
    out = {k: config[k] for k in PUBLISHED + ("experts_held",)}
    if list(config["experts_held"]) != [0, config["moe_num_primary_experts"]]:
        raise SystemExit("benchmark: this configuration holds every expert "
                         "(experts_held = [0, moe_num_primary_experts])")
    # the per-layer lists stay whole in the file, as published: the
    # layers held are the first num_hidden_layers of them
    n = config["num_hidden_layers"]
    for k in LAYOUTS:
        if len(config[k]) < n:
            raise SystemExit(f"benchmark: {k} needs {n} entries")
        out[k] = list(config[k][:n])
    out["sliding_window"] = config["sliding_window_size"]
    return out


def _model():
    try:
        from pipegoose_tpu.models import smallthinker
    except ImportError as e:
        raise SystemExit(f"benchmark: this program has no "
                         f"models/smallthinker ({e}); nothing was run")
    return smallthinker


def make_config(config: dict, options: dict = None):
    """The program's ``SmallThinkerConfig`` at the configuration's sizes
    and dtype, with the options the class still has."""
    import dataclasses

    import jax.numpy as jnp

    model = _model()
    fields = {f.name for f in dataclasses.fields(model.SmallThinkerConfig)}
    options = dict(config.get("model_options") or {}, **(options or {}))
    dropped = sorted(k for k in options if k not in fields)
    if dropped:
        print(f"benchmark: SmallThinkerConfig has no field {dropped}; "
              f"dropped (now the program's default)", flush=True)
    kept = {k: v for k, v in options.items() if k in fields}
    s = sizes(config)
    return model.SmallThinkerConfig(
        experts_held=tuple(s["experts_held"]),
        **{k: tuple(s[k]) for k in LAYOUTS},
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
    for i in range(config["num_hidden_layers"]):
        layer = {}
        for name, path in LAYER.items():
            put(layer, path, flat[f"l{i}_{name}"])
        tree["layers"].append(layer)
    return tree
