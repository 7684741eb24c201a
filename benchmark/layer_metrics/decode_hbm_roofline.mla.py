"""The decode program's share of its memory roofline where the cache is
latent rows and experts are read at decode: the bytes every decode step
of the traced window has to read
(``rooflines_longcat_flash.decode_step_bytes``: the weights outside the
routed experts once, the head's held rows with them; the three matrices
of the experts THAT step touched, by the engine's counters; the latent
rows of the keys live in it, at the width the bank stores them, once an
attention) over the peak bytes/s, divided by the device time of the
decode program's executions in the trace. Memory bounds a decode step at
these batch sizes. A program without the counters, or sizes without a
latent row, reads as ``None``."""
import re

import jax.numpy as jnp

from benchmark import rooflines_longcat_flash

DECODE_MODULE = re.compile(r"^jit__step\b")


def read(run):
    f = run.facts
    touched = (f["run_metrics"].get("experts") or {}).get("touched_by_step")
    if run.trace is None or not touched or "kv_lora_rank" not in f["sizes"]:
        return None
    steps = [(s, e) for n, s, e in run.trace["devices"][0]["modules"]
             if DECODE_MODULE.search(n)]
    live = [keys for _, keys in f["ticks"] if keys > 0]
    n = min(len(live), len(steps), len(touched))
    if not n:
        return None
    itemsize = jnp.dtype(f["dtype"]).itemsize
    nbytes = sum(rooflines_longcat_flash.decode_step_bytes(
        f["sizes"], touched[i], live[i], itemsize) for i in range(n))
    least = nbytes / f["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (sum(e - s for s, e in steps[:n]) / 1e9)
