"""The grouped products' share of their memory roofline at decode: the
bytes of the experts the traced window's decode steps touched (one
matrix a touched expert a call: three calls a layer, by the engine's
counters) over the peak bytes/s, divided by the device time of the
``ragged-dot`` calls inside the decode program's executions (their
``ragged-dot-metadata`` calls' time counted, no work). A few rows an
expert: the read of the matrices bounds the call, not its arithmetic. A
program without the counters, or a trace without the calls, reads as
``None``."""
import bisect
import re

import jax.numpy as jnp

from benchmark import rooflines_smallthinker

NAME = "ragged-dot"
DECODE_MODULE = re.compile(r"^jit__step\b")


def read(run):
    f = run.facts
    touched = (f["run_metrics"].get("experts") or {}).get("touched_by_step")
    if run.trace is None or not touched:
        return None
    dev = run.trace["devices"][0]
    steps = sorted((s, e) for n, s, e in dev["modules"]
                   if DECODE_MODULE.search(n))
    starts = [s for s, _ in steps]
    actual = 0.0
    for name, start, end in dev["ops"]:
        if NAME not in name.split(" = ")[0]:
            continue
        i = bisect.bisect_right(starts, start) - 1
        if i >= 0 and end <= steps[i][1]:
            actual += (end - start) / 1e9
    n = min(len(steps), len(touched))
    if not actual or not n:
        return None
    itemsize = jnp.dtype(f["dtype"]).itemsize
    nbytes = sum(touched[:n]) * rooflines_smallthinker.expert_bytes(
        f["sizes"], itemsize)
    return 100.0 * (nbytes / f["peaks"]["hbm_bytes_per_s"]) / actual
