"""The flash-attention forward's share of its roofline inside the
prefill programs: the least time the chip could take for the
``flash_fwd`` calls the trace holds
(``rooflines_smallthinker.prefill_flash_costs``: the (query, key) pairs
the causal rule keeps on a global layer and the window rule on a window
layer, at the group of 7 query heads a KV head, whatever blocks the
kernel visits) over their summed device time.

The kernel is found by the name its ``pallas_call`` carries
(``ops/flash_attention.py``: ``flash_fwd``), searched for in the
instruction's own name, left of `` = ``; a call's heads and length are
read off its first result, ``bf16[heads, seq, head_dim]``. Every layer
has one head count, so a call's kind is not told off its shape: a
prefill makes a call a layer, and each call is charged the mean over the
layers' kinds, which sums to the prefill's own pairs. A trace without
such calls reads as ``None``."""
import re

from benchmark import rooflines, rooflines_smallthinker

SHAPE = re.compile(r"\b(?:bf16|f16|f32)\[(\d+),(\d+),(\d+)\]")


def read(run):
    f = run.facts
    if run.trace is None:
        return None
    s = f["sizes"]
    least = actual = 0.0
    for name, start, end in run.trace["devices"][0]["ops"]:
        head, _, rest = name.partition(" = ")
        if "flash_fwd" not in head:
            continue
        shape = SHAPE.search(rest)
        if shape is None:
            continue
        heads, seq, hd = (int(x) for x in shape.groups())
        if heads != s["num_attention_heads"] or hd != s["head_dim"]:
            continue
        least += sum(
            share * rooflines.least_time_s(flops, nbytes, f["peaks"])[0]
            for share, flops, nbytes
            in rooflines_smallthinker.prefill_flash_costs(seq, s))
        actual += (end - start) / 1e9
    if not actual:
        return None
    return 100.0 * least / actual
