"""The fused cross-entropy BACKWARD kernel's share of its roofline: the
least time the chip could take for the ``fused_ce_bwd`` calls the trace
holds, over the summed device time of those calls, on the busiest
device. One call a head pass; a program whose backward runs under other
names (``fused_ce_dh`` / ``fused_ce_dw``, before PR 41) has nothing to
read here.

The kernel is found by the name its ``pallas_call`` carries
(``ops/fused_ce.py``), searched for in the instruction's own name, left
of `` = `` (jax wraps it: ``%transpose_jvp_fused_ce_bwd__.1``).

Operations, for T tokens, hidden H and the V rows of the head that one
device holds (the vocabulary over ``tensor``): THREE matmuls of 2*T*V*H
a call. Each (token, vocabulary) tile of ``dlogits`` is formed once from
the saved lse (the logits again: 1) and feeds both ``dlogits @ W`` into
``dh`` and ``dlogits^T @ h`` into ``dw`` (2). With the forward's one
that is four a step where forward and backward of a dense head owe
three; the fourth is the recomputation that spares the (T, V) buffer
and counts as the kernel's own work, as the recomputed scores do for
the flash kernels.
"""
from benchmark import rooflines

KERNEL = "fused_ce_bwd"
MATMULS = 3


def call_cost(tokens: int, hidden: int, vocab_rows: int,
              dtype_bytes: int = 2) -> tuple:
    """(flops, bytes) of ONE call. Bytes: every operand read once (h, W,
    and the float32 rows targets, lse and g) and both results written
    once, in float32 as the kernel leaves them. What the kernel moves
    besides (the weight once more a token super-block, ``dw`` carried
    between them) is the algorithm's cost, not the call's need; compute
    bounds a call at every trained shape by 10x and more, so the bytes
    decide nothing."""
    flops = MATMULS * 2.0 * tokens * vocab_rows * hidden
    h, w = tokens * hidden, vocab_rows * hidden
    nbytes = (h + w) * dtype_bytes + 3 * tokens * 4 + (h + w) * 4
    return flops, float(nbytes)


def is_call(event_name: str) -> bool:
    return KERNEL in event_name.split(" = ")[0]


def read(run):
    f = run.facts
    if run.trace is None:
        return None
    sizes = f["sizes"]
    tokens = f["rows_per_replica"] * (f["seq"] - 1)   # shifted targets
    rows = sizes["vocab_size"] // f["tensor"]
    flops, nbytes = call_cost(tokens, sizes["hidden_size"], rows)
    one = rooflines.least_time_s(flops, nbytes, f["peaks"])[0]
    dev = max(run.trace["devices"], key=lambda d: d["busy_ns"])
    calls = [(e - s) / 1e9 for name, s, e in dev["ops"] if is_call(name)]
    if not calls or not sum(calls):
        return None
    return 100.0 * one * len(calls) / sum(calls)
