"""Operations and bytes of LongCat-Flash's served path, from shapes: what
a decode step has to read, what a grouped product over the touched
experts reads, what a flash-attention forward at unequal key and value
widths has to compute. Every function takes the adapter's plain
``sizes`` (``program_longcat_flash.sizes``). Kept with the benchmark so
that no PR that claims a gain can change how a utilisation is computed.
"""
from __future__ import annotations

from benchmark.rooflines_laguna import kept_pairs


def mla_params(sizes: dict) -> int:
    """One latent attention's matrices: ``q_a``, ``q_b``, ``kv_a``,
    ``kv_b``, ``o``."""
    h, nh = sizes["hidden_size"], sizes["num_attention_heads"]
    rq, rkv = sizes["q_lora_rank"], sizes["kv_lora_rank"]
    dn, dr, dv = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"], \
        sizes["v_head_dim"]
    return (h * rq + rq * nh * (dn + dr) + h * (rkv + dr)
            + rkv * nh * (dn + dv) + nh * dv * h)


def block_params_outside_experts(sizes: dict) -> int:
    """A block outside its routed experts: two latent attentions, two
    dense SwiGLUs, the router over real and zero-compute experts (its
    selection bias with it) and the norms (two of the hidden width and
    the two low-rank ones an attention)."""
    h = sizes["hidden_size"]
    e = sizes["router_experts"] + sizes["zero_expert_num"]
    norms = 2 * (2 * h + sizes["q_lora_rank"] + sizes["kv_lora_rank"])
    return (2 * mla_params(sizes) + 2 * 3 * h * sizes["ffn_hidden_size"]
            + h * e + e + norms)


def expert_params(sizes: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * sizes["hidden_size"] * sizes["expert_ffn_hidden_size"]


def expert_bytes(sizes: dict, dtype_bytes: int = 2) -> int:
    return expert_params(sizes) * dtype_bytes


def held_params(sizes: dict) -> int:
    """Everything this share holds: its blocks with the experts held,
    embedding, head and the final norm."""
    h = sizes["hidden_size"]
    return (sizes["num_layers"] * (
        block_params_outside_experts(sizes)
        + sizes["experts_held"][1] * expert_params(sizes))
        + 2 * sizes["vocab_size"] * h + h)


def params_outside_experts(sizes: dict) -> int:
    """Parameters a decode step multiplies every row by: the blocks
    outside their routed experts, the final norm and the head's held
    rows. Not the embedding (a step gathers one row a slot)."""
    h = sizes["hidden_size"]
    return (sizes["num_layers"] * block_params_outside_experts(sizes)
            + h + sizes["vocab_size"] * h)


def latent_bytes_per_token(sizes: dict, dtype_bytes: int = 2) -> int:
    """A cached token: one row of ``kv_lora_rank + rope`` lanes an
    attention, two attentions a block, at the width it is STORED."""
    lanes = sizes["kv_lora_rank"] + sizes["qk_rope_head_dim"]
    return 2 * sizes["num_layers"] * lanes * dtype_bytes


def decode_step_bytes(sizes: dict, experts_touched: int, latent_keys: int,
                      dtype_bytes: int = 2) -> float:
    """Bytes one decode step has to read: every weight outside the
    routed experts once (the head's held rows with them), the three
    matrices of the experts the step touched (summed over its blocks),
    and the latent rows of every cached position of the rows alive
    (``latent_keys``), read ONCE an attention: keys and values are one
    row."""
    return float(params_outside_experts(sizes) * dtype_bytes
                 + experts_touched * expert_bytes(sizes, dtype_bytes)
                 + latent_keys * latent_bytes_per_token(sizes, dtype_bytes))


def flash_fwd_cost(seq: int, sizes: dict, dtype_bytes: int = 2) -> tuple:
    """(flops, bytes) of ONE expanded-form forward call over one
    sequence at the TRUE widths: S = QK^T over ``nope + rope`` lanes, O =
    PV over ``v_head_dim``, 2 flops a kept pair a lane a head; q and k
    at the keys' width, v and o at the values', the float32 row
    statistic. Lanes a kernel pads to are no work: they read as lost
    share."""
    nh = sizes["num_attention_heads"]
    dk = sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]
    dv = sizes["v_head_dim"]
    flops = 2.0 * nh * kept_pairs(seq) * (dk + dv)
    return flops, float(nh * seq * (2 * (dk + dv) * dtype_bytes + 4))
