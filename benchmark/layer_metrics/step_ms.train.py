"""Median optimizer step, the device drained at both ends. Host clock."""
from statistics import median


def read(run):
    steps = run.facts.get("step_s")
    if not steps:
        return None
    return 1e3 * median(steps)
