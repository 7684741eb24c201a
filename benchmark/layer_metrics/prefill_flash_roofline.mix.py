"""The flash-attention forward's share of its roofline inside the
prefill programs: the least time the chip could take for the
``flash_fwd`` calls the trace holds (``rooflines_laguna.flash_fwd_cost``:
the (query, key) pairs the causal or the window rule keeps, whatever
blocks the kernel visits) over their summed device time.

The kernel is found by the name its ``pallas_call`` carries
(``ops/flash_attention.py``: ``flash_fwd``), searched for in the
instruction's own name, left of `` = ``; a call's heads and length are
read off its first result, ``bf16[heads, seq, head_dim]``; a layer's kind
off its head count (the configuration gives sliding layers another
count than full ones). A trace without such calls reads as ``None``."""
import re

from benchmark import rooflines, rooflines_laguna

SHAPE = re.compile(r"\b(?:bf16|f16|f32)\[(\d+),(\d+),(\d+)\]")


def read(run):
    f = run.facts
    if run.trace is None:
        return None
    s = f["sizes"]
    kinds = dict(zip(s["num_attention_heads_per_layer"], s["layer_types"]))
    least = actual = 0.0
    for name, start, end in run.trace["devices"][0]["ops"]:
        head, _, rest = name.partition(" = ")
        if "flash_fwd" not in head:
            continue
        shape = SHAPE.search(rest)
        if shape is None:
            continue
        heads, seq, hd = (int(x) for x in shape.groups())
        if heads not in kinds or hd != s["head_dim"]:
            continue
        window = (s["sliding_window"]
                  if kinds[heads] == rooflines_laguna.SLIDING else None)
        flops, nbytes = rooflines_laguna.flash_fwd_cost(
            seq, heads, s["num_key_value_heads"], hd, window)
        least += rooflines.least_time_s(flops, nbytes, f["peaks"])[0]
        actual += (end - start) / 1e9
    if not actual:
        return None
    return 100.0 * least / actual
