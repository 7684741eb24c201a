"""The flash-attention FORWARD's share of its roofline: the least time
the chip could take for the ``flash_fwd`` calls the trace holds, over
the summed device time of those calls, on the busiest device. One call
an attention layer a step (the trained blocks keep the kernel's result
and lse over the backward, so it is not run again).

The kernel is found by the name its ``pallas_call`` carries
(``ops/flash_attention.py``), searched for in the instruction's own
name, left of `` = `` (jax wraps it: ``%jvp_flash_fwd_.1``), and its
work is reckoned from the run's facts, so the share reads whatever
shapes the kernel takes and returns: ``(rows*heads, seq, width)`` a
head a tile, or since PR 49 at head width 64 the model's own ``(rows,
seq, heads*width)``, two heads a 128-lane tile, where
``flash_attn_roofline.train`` (which tells the kernels by a
``bf16[rows*heads, seq, width]`` result) finds nothing.

Operations and bytes are ``rooflines.flash_call_cost("fwd", ..)``: two
matmuls of 2*seq*seq*width a head (S = Q K^T, O = P V), halved by the
causal mask; q, k, v read and the result written once, and the float32
lse row. Compute bounds a call at every trained shape.
"""
from benchmark import rooflines

KERNEL = "flash_fwd"


def is_call(event_name: str) -> bool:
    return KERNEL in event_name.split(" = ")[0]


def share(run, n_head: int, head_dim: int):
    """100 x least time / device time of the ``flash_fwd`` calls on the
    busiest device, for ``n_head`` heads of ``head_dim`` on one device;
    ``None`` where the trace holds no such call."""
    f = run.facts
    if run.trace is None:
        return None
    flops, nbytes = rooflines.flash_call_cost(
        "fwd", f["rows_per_replica"], f["seq"], n_head, head_dim)
    one = rooflines.least_time_s(flops, nbytes, f["peaks"])[0]
    dev = max(run.trace["devices"], key=lambda d: d["busy_ns"])
    calls = [(e - s) / 1e9 for name, s, e in dev["ops"] if is_call(name)]
    if not calls or not sum(calls):
        return None
    return 100.0 * one * len(calls) / sum(calls)


def read(run):
    f = run.facts
    sizes = f["sizes"]
    # the facts ``flash_bwd_roofline.train`` takes: a device's share of
    # the heads, ``hidden_size / n_head`` wide
    return share(run, sizes["n_head"] // f["tensor"],
                 sizes["hidden_size"] // sizes["n_head"])
