"""How long the device waits for a decode step the host has begun to
send: p50, over the traced window's plain ticks (a decode step and no
prefill before it), of the step's start on the device (its ``jit__step``
execution on ``XLA Modules``) less the host's entry into ``upload``
(the engine's tick timeline, put on the profiler's clock and checked
against the ``bench.serve.tick`` annotations: ``benchmark/tick_join.py``).
Upload, the jitted call's own work and the way to the device; a loop with
one step in flight hides it, token feedback on the device shortens it.
The device's line is held to causality, not trusted (``tick_join``'s
``shift``): the number may be off by half of what that leaves, which the
``tick_join`` line states (``shift_bounds_us``); launch + return is not."""
from benchmark import tick_join


def read(run):
    return tick_join.step_launch_ms(tick_join.of(run))
