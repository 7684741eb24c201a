"""Operations and bytes of Laguna's served path, from shapes: what a
decode step has to read, what a grouped product over the touched experts
reads, what a flash-attention forward under a causal or window mask has
to compute. Every function takes the adapter's plain ``sizes``
(``program_laguna.sizes``). Kept with the benchmark so that no PR that
claims a gain can change how a utilisation is computed.
"""
from __future__ import annotations

SLIDING = "sliding_attention"


def expert_matrix_bytes(sizes: dict, dtype_bytes: int = 2) -> int:
    """One routed expert's one matrix (hidden x expert width)."""
    return sizes["hidden_size"] * sizes["moe_intermediate_size"] * dtype_bytes


def expert_bytes(sizes: dict, dtype_bytes: int = 2) -> int:
    """One routed expert: gate, up and down."""
    return 3 * expert_matrix_bytes(sizes, dtype_bytes)


def sparse_layers(sizes: dict) -> int:
    return sizes["num_hidden_layers"] - len(sizes["mlp_only_layers"])


def params_outside_experts(sizes: dict) -> int:
    """Parameters a decode step multiplies every row by: attention (q,
    k, v, gate, o) and the norms of every layer, the dense MLP, routers
    and shared experts, the final norm and the head. Not the embedding
    (a step gathers one row a slot), not the routed experts."""
    h, hd, kv = sizes["hidden_size"], sizes["head_dim"], \
        sizes["num_key_value_heads"]
    total = h + sizes["vocab_size"] * h                 # final norm, head
    for i in range(sizes["num_hidden_layers"]):
        nh = sizes["num_attention_heads_per_layer"][i]
        total += 2 * h + 2 * h * nh * hd + 2 * h * kv * hd + h * nh
        if i in sizes["mlp_only_layers"]:
            total += 3 * h * sizes["intermediate_size"]
        else:
            total += h * sizes["router_experts"] \
                + 3 * h * sizes["shared_expert_intermediate_size"]
    return total


def kv_bytes_per_key(sizes: dict, dtype_bytes: int = 2) -> int:
    """K and V of one position in one layer."""
    return 2 * sizes["num_key_value_heads"] * sizes["head_dim"] * dtype_bytes


def layers_by_kind(sizes: dict) -> tuple:
    """(global layers, window layers)."""
    window = sum(1 for t in sizes["layer_types"] if t == SLIDING)
    return sizes["num_hidden_layers"] - window, window


def decode_step_bytes(sizes: dict, experts_touched: int, keys_global: int,
                      keys_window: int, dtype_bytes: int = 2) -> float:
    """Bytes one decode step has to read: every weight outside the
    routed experts once, the experts the step touched (summed over its
    sparse layers), and the K and V of the keys live in that step, by
    cache kind: ``keys_global`` every cached position of the rows alive,
    ``keys_window`` those inside the window."""
    n_global, n_window = layers_by_kind(sizes)
    kv = kv_bytes_per_key(sizes, dtype_bytes)
    return float(params_outside_experts(sizes) * dtype_bytes
                 + experts_touched * expert_bytes(sizes, dtype_bytes)
                 + kv * (n_global * keys_global + n_window * keys_window))


def kept_pairs(seq: int, window: int = None) -> int:
    """(query, key) pairs a causal mask keeps over ``seq`` positions,
    or a causal window of ``window`` keys: what the algorithm has to
    compute, whatever blocks a kernel visits to do so."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def flash_fwd_cost(seq: int, n_head: int, n_kv_head: int, head_dim: int,
                   window: int = None, dtype_bytes: int = 2) -> tuple:
    """(flops, bytes) of ONE forward call over one sequence: S = QK^T
    and O = PV, 2 * head_dim flops a kept pair a head each; q and o at
    ``n_head`` heads, k and v at ``n_kv_head`` (read once: a KV head's
    block is shared by its group), the float32 row statistic."""
    flops = 2 * 2.0 * n_head * kept_pairs(seq, window) * head_dim
    tensor = seq * head_dim * dtype_bytes
    return flops, float(2 * n_head * tensor + 2 * n_kv_head * tensor
                        + n_head * seq * 4)
