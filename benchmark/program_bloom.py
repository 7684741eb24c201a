"""The one place where the benchmark names the program's BLOOM: its
configuration class and the layout of its parameter tree. Drivers go
through here, so a later change to either needs one new adapter file
and no edit to a driver."""
from __future__ import annotations

import dataclasses

# benchmark leaf name -> path in the program's tree
TREE = {
    "embed": ("embed", "weight"),
    "embed_ln_scale": ("embed_ln", "scale"),
    "embed_ln_bias": ("embed_ln", "bias"),
    "ln1_scale": ("blocks", "ln_1", "scale"),
    "ln1_bias": ("blocks", "ln_1", "bias"),
    "qkv_w": ("blocks", "attn", "qkv", "kernel"),
    "qkv_b": ("blocks", "attn", "qkv", "bias"),
    "out_w": ("blocks", "attn", "out", "kernel"),
    "out_b": ("blocks", "attn", "out", "bias"),
    "ln2_scale": ("blocks", "ln_2", "scale"),
    "ln2_bias": ("blocks", "ln_2", "bias"),
    "up_w": ("blocks", "mlp", "up", "kernel"),
    "up_b": ("blocks", "mlp", "up", "bias"),
    "down_w": ("blocks", "mlp", "down", "kernel"),
    "down_b": ("blocks", "mlp", "down", "bias"),
    "lnf_scale": ("ln_f", "scale"),
    "lnf_bias": ("ln_f", "bias"),
}


def to_tree(flat: dict) -> dict:
    tree = {}
    for name, path in TREE.items():
        node = tree
        for key in path[:-1]:
            node = node.setdefault(key, {})
        node[path[-1]] = flat[name]
    return tree


def from_tree(tree: dict) -> dict:
    flat = {}
    for name, path in TREE.items():
        node = tree
        for key in path:
            node = node[key]
        flat[name] = node
    return flat


def make_config(config: dict, options: dict = None):
    """The program's ``BloomConfig`` at the configuration's sizes and
    dtype. ``options`` (training only) are passed while the class still
    has the field; the ones it no longer has are printed and dropped —
    they are then the program's default."""
    import jax.numpy as jnp

    from pipegoose_tpu.models import bloom

    sizes = config["sizes"]
    fields = {f.name for f in dataclasses.fields(bloom.BloomConfig)}
    options = dict(options or {})
    dropped = sorted(k for k in options if k not in fields)
    if dropped:
        print(f"benchmark: BloomConfig has no field {dropped}; dropped "
              f"(now the program's default)", flush=True)
    kept = {k: v for k, v in options.items() if k in fields}
    return bloom.BloomConfig(
        vocab_size=sizes["vocab_size"], hidden_size=sizes["hidden_size"],
        n_layer=sizes["n_layer"], n_head=sizes["n_head"],
        layer_norm_epsilon=sizes["layer_norm_epsilon"],
        initializer_range=sizes["initializer_range"],
        dtype=jnp.dtype(config["dtype"]), **kept)
