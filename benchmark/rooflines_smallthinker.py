"""Operations and bytes of SmallThinker's served path, from shapes: what
a decode step has to read, what a grouped product over the touched
experts reads, what a flash-attention forward under a causal or window
mask has to compute. Every function takes the adapter's plain ``sizes``
(``program_smallthinker.sizes``). Kept with the benchmark so that no PR
that claims a gain can change how a utilisation is computed. The pairs a
mask keeps and a flash forward's cost are ``rooflines_laguna``'s.
"""
from __future__ import annotations

from benchmark.rooflines_laguna import flash_fwd_cost, kept_pairs  # noqa: F401


def attention_params(sizes: dict) -> int:
    """One layer's q, k, v and o."""
    h, hd = sizes["hidden_size"], sizes["head_dim"]
    return 2 * h * hd * (sizes["num_attention_heads"]
                         + sizes["num_key_value_heads"])


def expert_params(sizes: dict) -> int:
    """One expert: gate, up and down."""
    return 3 * sizes["hidden_size"] * sizes["moe_ffn_hidden_size"]


def expert_bytes(sizes: dict, dtype_bytes: int = 2) -> int:
    return expert_params(sizes) * dtype_bytes


def layer_params(sizes: dict, experts: int = None) -> int:
    """One layer with ``experts`` of its experts (default: those the
    router counts): attention, two norms, the router, the experts."""
    h = sizes["hidden_size"]
    if experts is None:
        experts = sizes["moe_num_primary_experts"]
    return (attention_params(sizes) + 2 * h
            + h * sizes["moe_num_primary_experts"]
            + experts * expert_params(sizes))


def model_params(sizes: dict, layers: int = None) -> int:
    """``layers`` whole layers (default: those held), the embedding, the
    head and the final norm."""
    h = sizes["hidden_size"]
    if layers is None:
        layers = sizes["num_hidden_layers"]
    return layers * layer_params(sizes) + 2 * sizes["vocab_size"] * h + h


def params_outside_experts(sizes: dict) -> int:
    """Parameters a decode step multiplies every row by: attention, the
    norms and the router of every layer, the final norm and the head.
    Not the embedding (a step gathers one row a slot), not the experts."""
    h = sizes["hidden_size"]
    return (sizes["num_hidden_layers"] * layer_params(sizes, experts=0)
            + h + sizes["vocab_size"] * h)


def kv_bytes_per_key(sizes: dict, dtype_bytes: int = 2) -> int:
    """K and V of one position in one layer."""
    return 2 * sizes["num_key_value_heads"] * sizes["head_dim"] * dtype_bytes


def layers_by_kind(sizes: dict) -> tuple:
    """(global layers, window layers)."""
    window = sum(1 for w in sizes["sliding_window_layout"] if w)
    return sizes["num_hidden_layers"] - window, window


def decode_step_bytes(sizes: dict, experts_touched: int, keys_global: int,
                      keys_window: int, dtype_bytes: int = 2) -> float:
    """Bytes one decode step has to read: every weight outside the
    experts once (the head with them), the experts the step touched
    (summed over its layers), and the K and V of the keys live in that
    step, by cache kind: ``keys_global`` every cached position of the
    rows alive, ``keys_window`` those inside the window (a row's capped
    at ``sliding_window``)."""
    n_global, n_window = layers_by_kind(sizes)
    kv = kv_bytes_per_key(sizes, dtype_bytes)
    return float(params_outside_experts(sizes) * dtype_bytes
                 + experts_touched * expert_bytes(sizes, dtype_bytes)
                 + kv * (n_global * keys_global + n_window * keys_window))


def prefill_flash_costs(seq: int, sizes: dict, dtype_bytes: int = 2) -> list:
    """``[(share of a prefill's calls, flops, bytes), ..]`` of the flash
    forward calls of a prefill over ``seq`` positions, by layer kind:
    every layer has one head count, so a call's kind cannot be told off
    its shapes; a prefill makes a call a layer, the global layers' over
    the causal pairs and the window layers' over the pairs the window
    keeps."""
    n_global, n_window = layers_by_kind(sizes)
    args = (seq, sizes["num_attention_heads"], sizes["num_key_value_heads"],
            sizes["head_dim"])
    n = n_global + n_window
    return [(n_global / n, *flash_fwd_cost(*args, None, dtype_bytes)),
            (n_window / n, *flash_fwd_cost(*args, sizes["sliding_window"],
                                           dtype_bytes))]
