"""The part of ``dispatch`` that hands the step's host arrays (tokens,
page table, lengths) to the device before the jitted call: the mean of
the tick timeline's ``upload`` column (span ``serving.decode_step.upload``)
over the ticks that decoded. Token feedback kept on the device removes
it; one step in flight only hides it. Read through the join
(``benchmark/tick_join.py``) like the other ``.serve`` metrics, so that
the five are of one run or of none."""
from benchmark import tick_join


def read(run):
    return tick_join.step_upload_ms(tick_join.of(run))
