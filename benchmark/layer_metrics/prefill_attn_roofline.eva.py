"""The prefill's attention kernels' share of their roofline where
attention is EVA: the least time the chip could take for the (query, key)
pairs the MATHEMATICS has (``rooflines_evabyte.eva_prefill_cost``: a
query's own window up to itself, and a summary a chunk of every closed
window) over the summed device time of the kernels that compute them,
found by the names their ``pallas_call`` s carry
(``ops/flash_attention.py``): ``flash_fwd``, the window part, a call
over (head, window) pairs as rows, rows and the length of one read off
the first result ``bf16[heads x windows, window, 128]``;
``flash_ring_fwd``, the summary part, one more chunk of keys under the
window part's carry, its heads and positions off the float32 accumulator
``f32[heads, positions, 128]``. A call carries as many heads as the
program gives it (a layer's heads in groups, or all at once): its work
is the whole layer's at its length, times its rows over the model's
heads. Masked blocks a kernel computes, operands it widens and the
padding of a bucket to whole windows are no work: they read as lost
share. A trace without such calls, or sizes without a chunk, reads as
``None``."""
import re

from benchmark import rooflines, rooflines_evabyte

SHAPE = re.compile(r"\b(bf16|f16|f32)\[(\d+),(\d+),(\d+)\]")
# the longer name first: ``flash_ring_fwd`` holds no ``flash_fwd``
PARTS = (("flash_ring_fwd", "summary"), ("flash_fwd", "window"))


def read(run):
    f = run.facts
    s = f["sizes"]
    if run.trace is None or "chunk_size" not in s:
        return None
    heads = s["num_attention_heads"]
    width = s["hidden_size"] // heads
    least = actual = 0.0
    for name, start, end in run.trace["devices"][0]["ops"]:
        head, _, rest = name.partition(" = ")
        part = next((p for key, p in PARTS if key in head), None)
        if part is None:
            continue
        # the result a head wide: the window part's output, the summary
        # part's accumulator (its two float32 rows are (.., 1, length))
        shape = next((m for m in SHAPE.finditer(rest)
                      if int(m.group(4)) == width
                      and (m.group(1) == "f32") == (part == "summary")), None)
        if shape is None:
            continue
        rows, length = int(shape.group(2)), int(shape.group(3))
        # a row of the window part is one head's window (or its one
        # partial window), of the summary part one head's whole sequence:
        # all heads' work at that length, a head's share a row
        flops, nbytes = rooflines_evabyte.eva_prefill_cost(s, length, (part,))
        least += rows / heads * rooflines.least_time_s(
            flops, nbytes, f["peaks"])[0]
        actual += (end - start) / 1e9
    if not actual:
        return None
    return 100.0 * least / actual
