"""Share of a decode step's router picks that fell on zero-compute
experts, mean over the window's plain decode steps: the engine's
``experts["zero_pick_share"]`` (``finish_run()``), from the counters the
decode step brings out (``zero_picks`` over ``picks``, the live rows', a
block). What such a pick costs is nothing, so a token's compute varies
with it; a third at random weights (256 of 768 outputs). A program
without the counter reads as ``None``."""


def read(run):
    experts = run.facts["run_metrics"].get("experts") or {}
    if experts.get("zero_pick_share") is None:
        return None
    return 100.0 * experts["zero_pick_share"]
