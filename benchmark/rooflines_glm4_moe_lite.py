"""Operations and bytes of a GLM-4.7-Flash-shaped model from shapes,
kept with the benchmark like ``rooflines.py``. ``sizes`` is what the
adapter's ``sizes(config)`` gives: published widths, the layers, experts
and vocabulary rows HELD HERE.

Counted as a matmul: the five latent-attention projections, causal
attention at the q/k width and the v width, every SwiGLU, the router,
the MTP module's projection and the two head passes over the valid
rows of the vocabulary (padding is no work). The embedding lookup,
norms, RoPE and the routing's sort and gathers are not. The routed
experts are counted by the rows actually routed to the experts held
(``picks_per_token``: picks on held experts per token and expert layer,
from the step's counters); 4 * held / router width if balanced.
"""
from __future__ import annotations


def attention_proj_params(s: dict) -> int:
    h, nh = s["hidden_size"], s["num_attention_heads"]
    rq, r = s["q_lora_rank"], s["kv_lora_rank"]
    dn, dr, dv = s["qk_nope_head_dim"], s["qk_rope_head_dim"], s["v_head_dim"]
    return (h * rq + rq * nh * (dn + dr) + h * (r + dr)
            + r * nh * (dn + dv) + nh * dv * h)


def attention_flops_per_token(s: dict, seq: int) -> float:
    """QK^T at the q/k width and PV at the v width, 2*seq*width a head
    a token each, halved by the causal mask."""
    nh = s["num_attention_heads"]
    width = s["qk_nope_head_dim"] + s["qk_rope_head_dim"] + s["v_head_dim"]
    return 2.0 * seq * nh * width / 2.0


def swiglu_params(hidden: int, inner: int) -> int:
    return 3 * hidden * inner


def n_moe_blocks(s: dict) -> int:
    """Expert layers a token passes: the stacked ones and the MTP
    module's."""
    return (s["num_hidden_layers"] - s["first_k_dense_replace"]
            + s["num_nextn_predict_layers"])


def balanced_picks_per_token(s: dict) -> float:
    return (s["num_experts_per_tok"] * s["experts_held"][1]
            / s["router_experts"])


def forward_flops_per_token(s: dict, seq: int,
                            picks_per_token: float = None) -> float:
    h, f = s["hidden_size"], s["moe_intermediate_size"]
    picks = (balanced_picks_per_token(s) if picks_per_token is None
             else picks_per_token)
    per_attn = 2.0 * attention_proj_params(s) + attention_flops_per_token(
        s, seq)
    dense = s["first_k_dense_replace"] * (
        per_attn + 2.0 * swiglu_params(h, s["intermediate_size"]))
    moe = n_moe_blocks(s) * (
        per_attn + 2.0 * h * s["router_experts"]
        + 2.0 * swiglu_params(h, f * s["n_shared_experts"])
        + 2.0 * swiglu_params(h, f) * picks)
    mtp = s["num_nextn_predict_layers"] * 2.0 * (2 * h) * h
    heads = (1 + s["num_nextn_predict_layers"]) * 2.0 * h * s["vocab_size"]
    return dense + moe + mtp + heads


def train_flops_per_token(s: dict, seq: int,
                          picks_per_token: float = None) -> float:
    """Forward + backward (twice the forward), recomputation not
    counted."""
    return 3.0 * forward_flops_per_token(s, seq, picks_per_token)


def grouped_mm_call_cost(rows: float, s: dict, dtype_bytes: int = 2) -> tuple:
    """(flops, bytes) of ONE grouped product of an expert layer (gate,
    up or down; forward, dx or dw alike): ``rows`` rows routed to the
    held experts against their (hidden x inner) matrices. Bytes: the
    held experts' matrices once, the routed rows in and out."""
    h, f = s["hidden_size"], s["moe_intermediate_size"]
    held = s["experts_held"][1]
    flops = 2.0 * rows * h * f
    nbytes = (held * h * f + rows * (h + f)) * dtype_bytes
    return flops, float(nbytes)
