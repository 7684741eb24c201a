"""The decode program's share of its memory roofline: the bytes every
decode step of the traced window has to read (all weights once, and the
K and V of the tokens live in that step: ``rooflines.decode_step_bytes``)
over the peak bytes/s, divided by the device time of the decode
program's executions in the trace. Memory bounds a decode step at these
batch sizes (16 tokens a step against 819 GB/s)."""
import re

import jax.numpy as jnp

from benchmark import rooflines

DECODE_MODULE = re.compile(r"^jit__step\b")


def read(run):
    f = run.facts
    if run.trace is None:
        return None
    steps = [(s, e) for n, s, e in run.trace["devices"][0]["modules"]
             if DECODE_MODULE.search(n)]
    live = [n for _, n in f["ticks"] if n > 0]
    n = min(len(live), len(steps))
    if not n:
        return None
    itemsize = jnp.dtype(f["dtype"]).itemsize
    nbytes = sum(rooflines.decode_step_bytes(f["sizes"], tokens, itemsize)
                 for tokens in live[:n])
    least = nbytes / f["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (sum(e - s for s, e in steps[:n]) / 1e9)
