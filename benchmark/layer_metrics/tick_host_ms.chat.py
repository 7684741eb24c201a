"""Host work of one decode tick outside the wait for the device: the
engine's ``tick_phase_s`` (``finish_run()``) for admit + prepare +
dispatch + record, over ``decode_steps``. What a loop that kept the
device fed without a host round trip a token would hide."""

HOST_PHASES = ("admit", "prepare", "dispatch", "record")


def read(run):
    m = run.facts["run_metrics"]
    phases = m.get("tick_phase_s")
    if not phases or not m.get("decode_steps"):
        return None
    return 1e3 * sum(phases[p] for p in HOST_PHASES) / m["decode_steps"]
