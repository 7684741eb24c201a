"""The decode program's share of its memory roofline where every block
keeps a state a slot beside its keys and values: the bytes the decode
steps of the traced window owe (``rooflines_falcon_h1.decode_step_bytes``:
the weights a step multiplies once, the K and V of the keys live in it,
and the state of the rows alive read and written: twice its size, by the
engine's ``finish_run()["state"]["rows_live"]``) over the peak bytes/s,
divided by the device time of the decode program's executions in the
trace. Memory bounds a decode step at these batch sizes. A program
without the state's counters reads as ``None``."""
import re

import jax.numpy as jnp

from benchmark import rooflines_falcon_h1

DECODE_MODULE = re.compile(r"^jit__step\b")


def read(run):
    f = run.facts
    state = f["run_metrics"].get("state")
    decode_steps = f["run_metrics"].get("decode_steps")
    if run.trace is None or not state or not decode_steps \
            or "state_dtype" not in f["sizes"]:
        return None
    steps = [(s, e) for n, s, e in run.trace["devices"][0]["modules"]
             if DECODE_MODULE.search(n)]
    keys = [g for _, g in f["ticks"] if g > 0]
    n = min(len(keys), len(steps))
    if not n:
        return None
    # rows alive in the steps counted: the run's total, or its share
    rows = state["rows_live"] * n / decode_steps
    sizes, itemsize = f["sizes"], jnp.dtype(f["dtype"]).itemsize
    nbytes = n * rooflines_falcon_h1.decode_step_bytes(
        sizes, sum(keys[:n]) / n, rows / n, itemsize,
        jnp.dtype(sizes["state_dtype"]).itemsize)
    least = nbytes / f["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (sum(e - s for s, e in steps[:n]) / 1e9)
