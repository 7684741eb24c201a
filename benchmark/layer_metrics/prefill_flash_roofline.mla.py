"""The flash-attention forward's share of its roofline inside the
prefill programs of a model with latent attention in its EXPANDED form:
the least time the chip could take for the ``flash_fwd`` calls the trace
holds (``rooflines_longcat_flash.flash_fwd_cost``: the causal (query,
key) pairs at the TRUE widths, keys ``nope + rope``, values
``v_head_dim``) over their summed device time. The kernels take one head
width, so the values (or all three operands) are padded: the padded
lanes are no work and read as lost share.

The kernel is found by the name its ``pallas_call`` carries
(``ops/flash_attention.py``: ``flash_fwd``), searched for in the
instruction's own name, left of `` = ``; a call's heads and length are
read off its first result, ``bf16[heads, seq, width]``. A trace without
such calls, or sizes without a latent row, reads as ``None``."""
import re

from benchmark import rooflines, rooflines_longcat_flash

SHAPE = re.compile(r"\b(?:bf16|f16|f32)\[(\d+),(\d+),(\d+)\]")


def read(run):
    f = run.facts
    s = f["sizes"]
    if run.trace is None or "kv_lora_rank" not in s:
        return None
    least = actual = 0.0
    for name, start, end in run.trace["devices"][0]["ops"]:
        head, _, rest = name.partition(" = ")
        if "flash_fwd" not in head:
            continue
        shape = SHAPE.search(rest)
        if shape is None:
            continue
        heads, seq, _ = (int(x) for x in shape.groups())
        if heads != s["num_attention_heads"]:
            continue
        flops, nbytes = rooflines_longcat_flash.flash_fwd_cost(seq, s)
        least += rooflines.least_time_s(flops, nbytes, f["peaks"])[0]
        actual += (end - start) / 1e9
    if not actual:
        return None
    return 100.0 * least / actual
