"""The one place where the benchmark names the program's Falcon-H1
(``pipegoose_tpu/models/falcon_h1.py``): its configuration class and the
layout of its parameter tree. Driver ``serve_model`` finds this file by
the name the configuration file gives under ``program.adapter``.

What ``serve_model`` asks of an adapter: ``sizes(config)`` (plain sizes
for the weights, the reference and the rooflines), ``make_config`` (what
``ServingEngine`` is given) and ``to_tree`` (the benchmark's flat leaves
-> the program's tree).
"""
from __future__ import annotations

BLOCK = {
    "ln1": ("ln_1", "scale"), "ln2": ("ln_2", "scale"),
    "q": ("attn", "q", "kernel"), "k": ("attn", "k", "kernel"),
    "v": ("attn", "v", "kernel"), "o": ("attn", "o", "kernel"),
    "in_proj": ("ssm", "in_proj", "kernel"),
    "conv_w": ("ssm", "conv", "weight"), "conv_b": ("ssm", "conv", "bias"),
    "dt_bias": ("ssm", "dt_bias"), "A_log": ("ssm", "A_log"),
    "D": ("ssm", "D"), "ssm_norm": ("ssm", "norm", "scale"),
    "out_proj": ("ssm", "out_proj", "kernel"),
    "gate": ("mlp", "gate", "kernel"), "up": ("mlp", "up", "kernel"),
    "down": ("mlp", "down", "kernel"),
}
TOP = {"embed": ("embed", "weight"), "head": ("lm_head", "weight"),
       "lnf": ("ln_f", "scale")}

# the published keys the program's configuration class takes as they are
PUBLISHED = (
    "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
    "num_attention_heads", "num_key_value_heads", "head_dim", "rms_norm_eps",
    "rope_theta", "mamba_d_ssm", "mamba_n_heads", "mamba_d_head",
    "mamba_n_groups", "mamba_d_state", "mamba_d_conv", "mamba_chunk_size",
    "embedding_multiplier", "lm_head_multiplier", "attention_in_multiplier",
    "attention_out_multiplier", "key_multiplier", "ssm_in_multiplier",
    "ssm_out_multiplier", "ssm_multipliers", "mlp_multipliers")
# what the program builds no variant for: the values it is built for
FIXED = {"attention_bias": False, "mamba_conv_bias": True,
         "mamba_proj_bias": False, "mamba_rms_norm": True,
         "mamba_norm_before_gate": False, "mlp_bias": False,
         "projectors_bias": False, "rope_scaling": None,
         "tie_word_embeddings": False, "hidden_act": "silu"}


def sizes(config: dict) -> dict:
    """The configuration as plain sizes: the published keys as the file
    has them (``num_hidden_layers`` as held here), the weights' spreads
    and the state's dtype."""
    for key, built in FIXED.items():
        if config.get(key, built) != built:
            raise SystemExit(f"benchmark: {key}={config[key]!r} is a variant "
                             f"neither the program nor the reference builds")
    out = {k: config[k] for k in PUBLISHED}
    out.update({k: config[k] for k in (
        "initializer_range", "in_proj_std", "conv_std", "state_dtype")})
    return out


def _model():
    try:
        from pipegoose_tpu.models import falcon_h1
    except ImportError as e:
        raise SystemExit(f"benchmark: this program has no models/falcon_h1 "
                         f"({e}); nothing was run")
    return falcon_h1


def make_config(config: dict, options: dict = None):
    """The program's ``FalconH1Config`` at the configuration's sizes and
    dtypes, with the options the class still has."""
    import dataclasses

    import jax.numpy as jnp

    model = _model()
    fields = {f.name for f in dataclasses.fields(model.FalconH1Config)}
    options = dict(config.get("model_options") or {}, **(options or {}))
    dropped = sorted(k for k in options if k not in fields)
    if dropped:
        print(f"benchmark: FalconH1Config has no field {dropped}; dropped "
              f"(now the program's default)", flush=True)
    kept = {k: v for k, v in options.items() if k in fields}
    s = sizes(config)
    published = {k: tuple(s[k]) if isinstance(s[k], list) else s[k]
                 for k in PUBLISHED}
    return model.FalconH1Config(
        initializer_range=s["initializer_range"],
        state_dtype=jnp.dtype(s["state_dtype"]),
        dtype=jnp.dtype(config["dtype"]), **published, **kept)


def to_tree(flat: dict, config: dict) -> dict:
    """The benchmark's flat leaves as the program's parameter tree (the
    block leaves are stacked over the layers in both)."""
    def put(tree, path, x):
        for key in path[:-1]:
            tree = tree.setdefault(key, {})
        tree[path[-1]] = x

    tree = {"blocks": {}}
    for name, path in TOP.items():
        put(tree, path, flat[name])
    for name, path in BLOCK.items():
        put(tree["blocks"], path, flat[name])
    return tree
