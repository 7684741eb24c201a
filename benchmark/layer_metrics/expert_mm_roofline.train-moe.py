"""The grouped matrix products' share of their roofline: the least time
the chip could take for the calls the trace holds over their summed
device time, on the busiest device.

The expert layer's products are ``jax.lax.ragged_dot`` (forward, dx and
dw alike), which the chip's compiler lowers to its own grouped kernel:
the trace shows each as ``%ragged-dot...`` with a small
``%ragged-dot-metadata...`` call before it that lays out the groups'
tiles. The metadata calls' time counts (it is part of the product's
cost) and brings no work. Work of one product: the rows routed to the
experts held (mean over the window's steps and expert layers, from the
step's counters) against one hidden x inner matrix an expert; bytes:
the held experts' matrices once, the rows in and out
(``rooflines_glm4_moe_lite.grouped_mm_call_cost``)."""
from benchmark import rooflines
from benchmark import rooflines_glm4_moe_lite as moe

NAME = "ragged-dot"


def read(run):
    f = run.facts
    counters = [c for c in f.get("counters") or [] if "rows_per_expert" in c]
    if run.trace is None or not counters:
        return None
    per_layer = [sum(layer) for c in counters for layer in c["rows_per_expert"]]
    # the counters are a data replica's; the tensor axis cuts the inner
    # width, and so a call's work and bytes
    flops, nbytes = moe.grouped_mm_call_cost(
        sum(per_layer) / len(per_layer), f["sizes"])
    one = rooflines.least_time_s(flops / f["tensor"], nbytes / f["tensor"],
                                 f["peaks"])[0]
    dev = max(run.trace["devices"], key=lambda d: d["busy_ns"])
    least = actual = 0.0
    for name, start, end in dev["ops"]:
        head = name.split(" = ")[0]
        if NAME not in head:
            continue
        actual += (end - start) / 1e9
        if "metadata" not in head:
            least += one
    if not actual:
        return None
    return 100.0 * least / actual
