"""Live rows at or past the window (their ring has wrapped and is taken
over in place) over all live rows, summed over the plain decode steps:
the engine's ``window["wrapped_row_share"]`` (``finish_run()``), counted
on the host from the ``seq_lens`` each step is sent with. Between 0 and
100 both ring states share steps: rings still filling beside rings that
have wrapped. A program without the counter reads as ``None``."""


def read(run):
    window = run.facts["run_metrics"].get("window")
    if not window or not run.facts["run_metrics"].get("decode_steps"):
        return None
    return 100.0 * window["wrapped_row_share"]
