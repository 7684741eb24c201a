"""A decode step's way back: p50, over the traced window's plain ticks,
of the host leaving ``fetch`` (the engine's tick timeline on the
profiler's clock: ``benchmark/tick_join.py``) less the end of the tick's
``jit__step`` execution on ``XLA Modules``: the tokens' transfer and the
host's waking, during which the device has nothing to do. As uncertain as
``step_launch_ms.serve`` (the device's line is held to causality, not
trusted: ``shift_bounds_us`` on the ``tick_join`` line), and off the
other way: their sum is exact."""
from benchmark import tick_join


def read(run):
    return tick_join.step_return_ms(tick_join.of(run))
