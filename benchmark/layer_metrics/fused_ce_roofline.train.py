"""The fused cross-entropy kernels' share of their roofline: the least
time the chip could take for the calls the trace holds, over the summed
device time of those calls, on the busiest device. Compute bounds every
one of them at these shapes (a 250,880-row head against 16k tokens).

The kernels are found by the names their ``pallas_call`` carries
(``ops/fused_ce.py``: ``fused_ce_fwd``, ``fused_ce_dh``,
``fused_ce_dw``). jax wraps a name in the transforms the call went
through (``%transpose_jvp_fused_ce_dw__.1``), so the name is searched
for in the instruction's own name, left of `` = ``. A program whose
kernels carry no name has nothing to read here.

Operations, from ``ops/fused_ce.py``, for T tokens, hidden H and the V
rows of the head that one device holds (the vocabulary over ``tensor``);
each matmul is 2*T*V*H:

* forward: the logits tile by tile (1 matmul), never stored;
* dh: the logits again from the saved lse, then dlogits @ W (2);
* dw: the logits a third time, then dlogits^T @ h (2).

Five matmuls run where forward and backward of a dense head need three
(the 6 per parameter of ``mfu_pct.train``); the other two are the
recomputation that spares the (T, V) buffer, and count here as the
kernels' own work, as the recomputed scores do for the flash kernels.
"""
from benchmark import rooflines

MATMULS = {"fused_ce_fwd": 1, "fused_ce_dh": 2, "fused_ce_dw": 2}


def call_cost(kind: str, tokens: int, hidden: int, vocab_rows: int,
              dtype_bytes: int = 2) -> tuple:
    """(flops, bytes) of ONE call of a fused-CE kernel. Bytes: every
    operand read once (h, W, targets, and for the backward lse and g in
    float32) and every result written once (forward: lse and the target
    logit; dh: (T, H); dw: (V, H))."""
    flops = MATMULS[kind] * 2.0 * tokens * vocab_rows * hidden
    h, w = tokens * hidden * dtype_bytes, vocab_rows * hidden * dtype_bytes
    rows = tokens * 4
    nbytes = h + w + rows + {"fused_ce_fwd": 2 * rows,
                             "fused_ce_dh": 2 * rows + h,
                             "fused_ce_dw": 2 * rows + w}[kind]
    return flops, float(nbytes)


def kernel_of(event_name: str):
    """Which fused-CE kernel a trace event is, or None."""
    head = event_name.split(" = ")[0]
    return next((k for k in MATMULS if k in head), None)


def read(run):
    f = run.facts
    if run.trace is None:
        return None
    sizes = f["sizes"]
    tokens = f["rows_per_replica"] * (f["seq"] - 1)   # shifted targets
    rows = sizes["vocab_size"] // f["tensor"]
    dev = max(run.trace["devices"], key=lambda d: d["busy_ns"])
    least = actual = 0.0
    for name, s, e in dev["ops"]:
        kind = kernel_of(name)
        if kind is None:
            continue
        flops, nbytes = call_cost(kind, tokens, sizes["hidden_size"], rows)
        least += rooflines.least_time_s(flops, nbytes, f["peaks"])[0]
        actual += (e - s) / 1e9
    if not actual:
        return None
    return 100.0 * least / actual
