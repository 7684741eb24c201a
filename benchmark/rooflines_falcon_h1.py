"""Bytes of Falcon-H1's served path, from shapes: what a decode step has
to read and write. Every function takes the adapter's plain ``sizes``
(``program_falcon_h1.sizes``). Kept with the benchmark so that no PR
that claims a gain can change how a utilisation is computed. The path
has no kernel of its own: its prefill's flash attention is
``rooflines_laguna.flash_fwd_cost``'s.
"""
from __future__ import annotations


def conv_dim(sizes: dict) -> int:
    """Channels the convolution runs over: x | B | C."""
    return sizes["mamba_d_ssm"] \
        + 2 * sizes["mamba_n_groups"] * sizes["mamba_d_state"]


def block_params(sizes: dict) -> int:
    """Parameters of one block: attention (q, k, v, o), the state-space
    mixer (input projection, convolution and its bias, dt_bias, A_log, D,
    the gated norm, output projection), the MLP, two norms."""
    h, hd = sizes["hidden_size"], sizes["head_dim"]
    q, kv = sizes["num_attention_heads"] * hd, \
        sizes["num_key_value_heads"] * hd
    d, nh, c = sizes["mamba_d_ssm"], sizes["mamba_n_heads"], conv_dim(sizes)
    attention = 2 * h * q + 2 * h * kv
    mixer = h * (d + c + nh) + c * sizes["mamba_d_conv"] + c + 3 * nh + d \
        + d * h
    return attention + mixer + 3 * h * sizes["intermediate_size"] + 2 * h


def decode_weight_params(sizes: dict) -> int:
    """Parameters a decode step multiplies every row by: every block,
    the final norm and the head. Not the embedding (a step gathers one
    row a slot)."""
    h = sizes["hidden_size"]
    return sizes["num_hidden_layers"] * block_params(sizes) + h \
        + sizes["vocab_size"] * h


def kv_bytes_per_key(sizes: dict, dtype_bytes: int = 2) -> int:
    """K and V of one position, over every block."""
    return sizes["num_hidden_layers"] * 2 * sizes["num_key_value_heads"] \
        * sizes["head_dim"] * dtype_bytes


def state_bytes_per_slot(sizes: dict, dtype_bytes: int = 2,
                         state_bytes: int = 4) -> int:
    """What one sequence leaves behind, over every block: the
    recurrence's H (heads x d_head x d_state, ``state_dtype``) and the
    convolution's last ``mamba_d_conv - 1`` inputs (the model's dtype)."""
    h = sizes["mamba_n_heads"] * sizes["mamba_d_head"] * sizes["mamba_d_state"]
    conv = (sizes["mamba_d_conv"] - 1) * conv_dim(sizes)
    return sizes["num_hidden_layers"] * (h * state_bytes + conv * dtype_bytes)


def decode_step_bytes(sizes: dict, keys_live: float, rows_live: float,
                      dtype_bytes: int = 2, state_bytes: int = 4) -> float:
    """Bytes one decode step owes the memory: every weight it multiplies
    once, the K and V of every cached position of the rows alive, and
    the state of the rows alive read and written (twice its size)."""
    return float(decode_weight_params(sizes) * dtype_bytes
                 + kv_bytes_per_key(sizes, dtype_bytes) * keys_live
                 + 2 * state_bytes_per_slot(sizes, dtype_bytes, state_bytes)
                 * rows_live)
