"""Rows the softmax needed over rows its two walks gathered, over the
window's plain decode steps, live rows only: the engine's
``eva["rows_useful_share"]`` (``finish_run()``), from the counters the
decode step brings out (``window_rows_needed`` + ``summary_rows_needed``
over ``window_rows_gathered`` + ``summary_rows_gathered``, a layer a
step). A ring walked whole for a row 100 bytes into its window, or
summaries walked as far as the longest row of the step, read as lost
share. A program without the counters reads as ``None``."""


def read(run):
    eva = run.facts["run_metrics"].get("eva") or {}
    if eva.get("rows_useful_share") is None:
        return None
    return 100.0 * eva["rows_useful_share"]
