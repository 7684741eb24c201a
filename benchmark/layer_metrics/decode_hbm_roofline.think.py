"""The decode program's share of its memory roofline where a router
picks before attention and both ring states share a step: the bytes
every decode step of the traced window has to read
(``rooflines_smallthinker.decode_step_bytes``: the weights outside the
experts once, the head with them, the experts THAT step touched over its
layers, by the engine's counters, and the K and V of the keys live in
it, a global layer's every cached position and a window layer's last
``sliding_window``) over the peak bytes/s, divided by the device time of
the decode program's executions in the trace. Memory bounds a decode
step at these batch sizes. A program without the counters reads as
``None``."""
import re

import jax.numpy as jnp

from benchmark import rooflines_smallthinker

DECODE_MODULE = re.compile(r"^jit__step\b")


def read(run):
    f = run.facts
    touched = (f["run_metrics"].get("experts") or {}).get("touched_by_step")
    if run.trace is None or not touched or "live_window" not in f:
        return None
    steps = [(s, e) for n, s, e in run.trace["devices"][0]["modules"]
             if DECODE_MODULE.search(n)]
    live = [(g, w) for (_, g), w in zip(f["ticks"], f["live_window"])
            if g > 0]
    n = min(len(live), len(steps), len(touched))
    if not n:
        return None
    itemsize = jnp.dtype(f["dtype"]).itemsize
    nbytes = sum(rooflines_smallthinker.decode_step_bytes(
        f["sizes"], touched[i], live[i][0], live[i][1], itemsize)
        for i in range(n))
    least = nbytes / f["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (sum(e - s for s, e in steps[:n]) / 1e9)
