"""The device's idle stretch between two decode steps: p50, over
successive plain ticks of the traced window, from one ``jit__step``
execution's end to the next one's start on ``XLA Modules``. All of what a
loop that kept a step in flight could hide, and the check on the other
``.serve`` metrics: return + record + the driver's time between ticks +
admit + prepare + launch make it up pair by pair. This reader prints the
``tick_join`` line: how the timeline's placement went (origin, what is
left at p50 and p95, the reason where the join refused), the constant
added to the device's times and what causality left it, the ticks past
the device line's end, the gap term by term and the idle seconds by the
part of a tick they fall in (``benchmark/tick_join.py:facts``). The gap
itself lies between two events of the device's line and needs no such
constant."""
import json

from benchmark import tick_join


def read(run):
    j = tick_join.of(run)
    if j is not None:
        print("tick_join " + json.dumps(tick_join.facts(j)), flush=True)
    return tick_join.device_gap_ms(j)
