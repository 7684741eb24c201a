"""The one place where the benchmark names the program's EvaByte
(``pipegoose_tpu/models/evabyte.py``): its configuration class and the
layout of its parameter tree. Driver ``serve_model`` finds this file by
the name the configuration file gives under ``program.adapter``.

What ``serve_model`` asks of an adapter: ``sizes(config)`` (plain sizes
for the weights, the reference and the rooflines), ``make_config`` (what
``ServingEngine`` is given) and ``to_tree`` (the benchmark's flat leaves
-> the program's tree).
"""
from __future__ import annotations

BLOCK = {"ln1": ("ln_1", "scale"), "ln2": ("ln_2", "scale"),
         "phi": ("attn", "phi"), "mu": ("attn", "mu"),
         **{k: ("attn", k, "kernel") for k in "qkvo"},
         **{k: ("mlp", k, "kernel") for k in ("gate", "up", "down")}}
TOP = {"embed": ("embed", "weight"), "head": ("lm_head", "weight"),
       "lnf": ("ln_f", "scale")}

# what the program's config class takes of the catalog row's keys
PUBLISHED = (
    "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
    "num_attention_heads", "num_key_value_heads", "num_pred_heads",
    "window_size", "chunk_size", "num_chunks", "rope_theta", "rope_scaling",
    "rms_norm_eps", "norm_add_unit_offset", "fp32_skip_add", "fp32_logits",
    "fp32_ln", "mixedp_attn", "attention_bias", "attention_class",
    "hidden_act", "tie_word_embeddings", "init_std", "init_fn",
    "init_cutoff_factor", "lazy_init", "max_position_embeddings",
    "max_seq_length", "model_type")


def sizes(config: dict) -> dict:
    """The configuration as plain sizes: the published keys as the file
    has them (the layers as held here) and what the file assumes of the
    weights."""
    return {k: config[k] for k in PUBLISHED + ("phi_std", "mu_std")}


def _model():
    try:
        from pipegoose_tpu.models import evabyte
    except ImportError as e:
        raise SystemExit(f"benchmark: this program has no models/evabyte "
                         f"({e}); nothing was run")
    return evabyte


def make_config(config: dict, options: dict = None):
    """The program's ``EvaByteConfig`` at the configuration's sizes and
    dtype, with the options the class still has."""
    import dataclasses

    import jax.numpy as jnp

    model = _model()
    fields = {f.name for f in dataclasses.fields(model.EvaByteConfig)}
    options = dict(config.get("model_options") or {}, **(options or {}))
    dropped = sorted(k for k in options if k not in fields)
    if dropped:
        print(f"benchmark: EvaByteConfig has no field {dropped}; dropped "
              f"(now the program's default)", flush=True)
    kept = {k: v for k, v in options.items() if k in fields}
    return model.EvaByteConfig(
        dtype=jnp.dtype(config["dtype"]),
        **{k: config[k] for k in PUBLISHED}, **kept)


def to_tree(flat: dict, config: dict) -> dict:
    """The benchmark's flat leaves as the program's parameter tree: the
    layers' leaves are stacked in both."""
    tree = {}
    for name, path in {**TOP, **{k: ("blocks",) + p
                                 for k, p in BLOCK.items()}}.items():
        at = tree
        for key in path[:-1]:
            at = at.setdefault(key, {})
        at[path[-1]] = flat[name]
    return tree
