"""The flash-attention kernels' share of their roofline: the least time
the chip could take for the calls the trace holds (forward, dQ and dK/dV
each by its own operations and bytes, from ``rooflines.flash_call_cost``)
over the summed device time of those calls, on the busiest device.
Compute bounds every one of them at these shapes.

The kernels carry no name of their own yet (the trace shows them as
custom calls named after whichever jax transform wrapped them), so they
are told apart by what they return: a custom call whose first result is
``bf16[rows*heads, seq, head_dim]`` is a flash kernel — with a float32
row statistic beside it the forward, with a second tensor dK/dV, alone
dQ. A later ``tracing`` PR gives them names; this reader then matches
those.
"""
import re

from benchmark import rooflines

SHAPE = re.compile(r"\b(bf16|f16|f32)\[([\d,]*)\]")


def classify(name: str, tensor: tuple):
    """"fwd", "dq", "dkv" or None for one trace event's name, which is
    the HLO instruction: ``%x = <results> custom-call(<operands>...``."""
    head, call, _ = name.partition(" custom-call(")
    if not call:
        return None
    shapes = [(d, tuple(int(x) for x in dims.split(",") if x))
              for d, dims in SHAPE.findall(head.partition(" = ")[2])]
    if not shapes or shapes[0][1] != tensor or shapes[0][0] == "f32":
        return None
    if len(shapes) == 1:
        return "dq"
    if shapes[1][1] == tensor:
        return "dkv"
    return "fwd" if shapes[1][0] == "f32" else None


def read(run):
    f = run.facts
    if run.trace is None:
        return None
    sizes = f["sizes"]
    heads = sizes["n_head"] // f["tensor"]
    hd = sizes["hidden_size"] // sizes["n_head"]
    rows, seq = f["rows_per_replica"], f["seq"]
    dev = max(run.trace["devices"], key=lambda d: d["busy_ns"])
    least = actual = 0.0
    for name, s, e in dev["ops"]:
        kind = classify(name, (rows * heads, seq, hd))
        if kind is None:
            continue
        flops, nbytes = rooflines.flash_call_cost(kind, rows, seq, heads, hd)
        least += rooflines.least_time_s(flops, nbytes, f["peaks"])[0]
        actual += (e - s) / 1e9
    if not actual:
        return None
    return 100.0 * least / actual
