"""The decode program's share of its memory roofline where ONE attention
reads a ring of its window's exact keys and a summary a chunk of every
closed window: the bytes a decode step of the window has to read
(``rooflines_evabyte.decode_step_bytes``: the weights a step multiplies,
the head's served columns with them, one embedding row a live row; the
rows the softmax NEEDS, both banks, every layer, at the mean over the
window's plain decode steps by the engine's counters: ``pos % W + 1``
exact keys and ``(pos // W) * (W // C)`` summaries a live row) over the
peak bytes/s, divided by the mean device time of the decode program's
executions in the trace. Rows a walk gathers beyond those (a ring walked
whole, summaries walked as far as the longest row) are no work: they read
as lost share. A program without the counters, or sizes without a chunk,
reads as ``None``."""
import re

import jax.numpy as jnp

from benchmark import rooflines_evabyte

DECODE_MODULE = re.compile(r"^jit__step\b")


def read(run):
    f = run.facts
    eva = f["run_metrics"].get("eva")
    n = f["run_metrics"].get("decode_steps")
    if run.trace is None or not eva or not n or "chunk_size" not in f["sizes"]:
        return None
    steps = [e - s for name, s, e in run.trace["devices"][0]["modules"]
             if DECODE_MODULE.search(name)]
    if not steps:
        return None
    nbytes = rooflines_evabyte.decode_step_bytes(
        f["sizes"], eva["window_rows_needed"] / n,
        eva["summary_rows_needed"] / n, eva["rows_live"] / n,
        jnp.dtype(f["dtype"]).itemsize)
    least = nbytes / f["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (sum(steps) / len(steps) / 1e9)
