"""Host wall of one admission's prefill, to the fetch of its first
token: the engine's ``tick_phase_s["prefill"]`` over ``prefills``
(``finish_run()``). Every decoding request waits this long, once per
admission. The page write is dispatched inside this phase and not
fetched there: its device time is ``page_write_device_ms.chat``'s, and
the stall of a decoding request per admission is the two summed."""


def read(run):
    m = run.facts["run_metrics"]
    phases = m.get("tick_phase_s")
    if not phases or not m.get("prefills"):
        return None
    return 1e3 * phases["prefill"] / m["prefills"]
