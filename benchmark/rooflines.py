"""Operations and bytes from shapes, and the table of peaks.

Kept with the benchmark so that no PR that claims a gain can change how
a utilisation is computed. Every function takes plain sizes.
"""
from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks_for(device_kind: str) -> dict:
    """The published peaks of one chip; an unknown kind is an error."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(
            f"device kind {device_kind!r} is not in benchmark/peaks.json "
            f"(known: {sorted(table)}); add its published peaks with their "
            f"source before reporting a utilisation on it")
    return table[device_kind]


def matmul_params(sizes: dict) -> int:
    """Parameters that a token is multiplied by: the four block matrices
    of every layer and the tied head. The embedding LOOKUP is no matmul;
    layer norms and biases are left out (under 0.1%)."""
    h, L, v = sizes["hidden_size"], sizes["n_layer"], sizes["vocab_size"]
    return 12 * L * h * h + v * h


def all_params(sizes: dict) -> int:
    h, L, v = sizes["hidden_size"], sizes["n_layer"], sizes["vocab_size"]
    per_layer = 12 * h * h + 13 * h          # matrices + biases + 2 LNs
    return v * h + L * per_layer + 4 * h


def train_flops_per_token(sizes: dict, seq: int) -> float:
    """Forward + backward, recomputation not counted: 6 per matmul
    parameter, plus causal attention (QK^T and PV: 2*2*S*h a token a
    layer forward, halved by the causal mask, times 3)."""
    h, L = sizes["hidden_size"], sizes["n_layer"]
    return 6.0 * matmul_params(sizes) + 6.0 * seq * h * L


def flash_call_cost(kind: str, batch: int, seq: int, n_head: int,
                    head_dim: int, dtype_bytes: int = 2) -> tuple:
    """(flops, bytes) the algorithm needs for ONE call of a causal flash
    attention kernel over (batch, seq, n_head, head_dim). ``kind``:
    "fwd" (S = QK^T, O = PV: 2 matmuls), "dq" (S, dP = dO V^T, dQ = dS K:
    3), "dkv" (S, dP, dV = P^T dO, dK = dS^T Q: 4) — each matmul is
    2*seq*seq*head_dim flops a head, halved by the causal mask. Bytes:
    every operand read once and every result written once."""
    matmuls = {"fwd": 2, "dq": 3, "dkv": 4}[kind]
    flops = matmuls * 2.0 * batch * n_head * seq * seq * head_dim / 2.0
    tensor = batch * seq * n_head * head_dim * dtype_bytes
    rows = batch * seq * n_head * 4                    # lse / delta, f32
    n_tensors = {"fwd": 4, "dq": 5, "dkv": 6}[kind]    # q k v o | +do, dq | +do, dk dv
    n_rows = {"fwd": 1, "dq": 2, "dkv": 2}[kind]
    return flops, float(n_tensors * tensor + n_rows * rows)


def least_time_s(flops: float, nbytes: float, peaks: dict,
                 dtype: str = "bfloat16") -> tuple:
    """(the least seconds the chip could take, which bound sets it)."""
    t_c = flops / peaks["flops_per_s"][dtype]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


def decode_step_bytes(sizes: dict, live_tokens: int,
                      dtype_bytes: int = 2) -> float:
    """Bytes one decode step has to read: every weight once, and the K
    and V of every token live in that step."""
    kv = 2 * sizes["n_layer"] * sizes["hidden_size"] * dtype_bytes
    return float(all_params(sizes) * dtype_bytes + live_tokens * kv)
