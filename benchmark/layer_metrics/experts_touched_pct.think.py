"""Share of the experts that a decode step touched, over its layers,
mean over the window's plain decode steps: the engine's
``experts["touched_share"]`` (``finish_run()``), from the counters the
decode step brings out (rows on each expert, a layer). The bytes a step
reads follow it: 100 is a step that reads every expert. A program
without the counters reads as ``None``."""


def read(run):
    experts = run.facts["run_metrics"].get("experts")
    if not experts:
        return None
    return 100.0 * experts["touched_share"]
