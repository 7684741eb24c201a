"""Whether a prefill's stall is the device computing or the host walking:
over the traced window's ticks that prefilled, the nanoseconds of their
``prefill`` intervals (the engine's tick timeline on the profiler's
clock: ``benchmark/tick_join.py``) in which some program ran on the
device (the union of the ``XLA Modules`` events), over those intervals'
length, x 100. High: chunking and overlap are the remedies; low: fewer
round trips (upload, dispatch, first-token fetch, page write). The
device's line carries ``tick_join``'s ``shift``: a short prefill's share
moves with it (half of ``shift_bounds_us``' width against the stall)."""
from benchmark import tick_join


def read(run):
    return tick_join.prefill_device_busy_pct(tick_join.of(run))
