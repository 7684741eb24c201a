"""Key columns the live rows' windows hold (``min(pos + 1, window)`` a
row) over those the window layers' walks gathered for them (every row
of a step walks its ring as far as the FURTHEST row stands: 17 chunks of
256 once any row is past the window): the engine's
``window["rows_useful_share"]`` (``finish_run()``), counted on the host
from the ``seq_lens`` each plain decode step is sent with, by the
device's own arithmetic. 100 is a walk that gathers what the softmax
needs and no more. A program without the counter reads as ``None``."""


def read(run):
    window = run.facts["run_metrics"].get("window")
    if not window or not run.facts["run_metrics"].get("decode_steps"):
        return None
    return 100.0 * window["rows_useful_share"]
