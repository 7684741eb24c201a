"""The one-kernel flash-attention BACKWARD's share of its roofline: the
least time the chip could take for the ``flash_bwd`` calls the trace
holds, over the summed device time of those calls, on the busiest
device. One call an attention layer a step; a program whose backward
runs under other names (``flash_dq`` + ``flash_dkv``: before PR 46, and
since then at shapes whose whole-sequence dQ accumulator does not fit
VMEM) has nothing to read here.

The kernel is found by the name its ``pallas_call`` carries
(``ops/flash_attention.py``), searched for in the instruction's own
name, left of `` = `` (jax wraps it: ``%transpose_jvp_flash_bwd__.1``).

Operations, over (batch, seq, n_head, head_dim): FIVE matmuls of
2*seq*seq*head_dim a head, halved by the causal mask. Each (query, key)
tile of the scores is formed once from the saved lse (S = Q K^T: 1) and
so is dP = dO V^T (2); P feeds dV = P^T dO (3), dS feeds dK = dS^T Q (4)
and dQ = dS K (5). The pair ran seven: S and dP in each kernel. The
recomputed scores count as the kernel's own work, as they do in
``rooflines.flash_call_cost``.
"""
from benchmark import rooflines

KERNEL = "flash_bwd"
MATMULS = 5


def call_cost(batch: int, seq: int, n_head: int, head_dim: int,
              dtype_bytes: int = 2) -> tuple:
    """(flops, bytes) of ONE call. Bytes: q, k, v and dO read once, dQ,
    dK and dV written once, and the float32 rows lse and delta read
    once. Compute bounds a call at every trained shape (3x the bytes'
    time at width 64 x 2,048 positions, 6x at 128 and at 256 x 4,096)."""
    flops = MATMULS * 2.0 * batch * n_head * seq * seq * head_dim / 2.0
    tensor = batch * seq * n_head * head_dim * dtype_bytes
    rows = batch * seq * n_head * 4
    return flops, float(7 * tensor + 2 * rows)


def is_call(event_name: str) -> bool:
    return KERNEL in event_name.split(" = ")[0]


def share(run, n_head: int, head_dim: int):
    """100 x least time / device time of the ``flash_bwd`` calls on the
    busiest device, for ``n_head`` heads of ``head_dim`` on one device;
    ``None`` where the trace holds no such call."""
    f = run.facts
    if run.trace is None:
        return None
    flops, nbytes = call_cost(f["rows_per_replica"], f["seq"], n_head,
                              head_dim)
    one = rooflines.least_time_s(flops, nbytes, f["peaks"])[0]
    dev = max(run.trace["devices"], key=lambda d: d["busy_ns"])
    calls = [(e - s) / 1e9 for name, s, e in dev["ops"] if is_call(name)]
    if not calls or not sum(calls):
        return None
    return 100.0 * one * len(calls) / sum(calls)


def read(run):
    f = run.facts
    sizes = f["sizes"]
    # the facts ``flash_attn_roofline.train`` takes: a device's share of
    # the heads, ``hidden_size / n_head`` wide
    return share(run, sizes["n_head"] // f["tensor"],
                 sizes["hidden_size"] // sizes["n_head"])
