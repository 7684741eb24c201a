"""Share of the window's wall that is not inside a fenced step: the
trainer's loop, its callbacks and the feed. Host clocks."""


def read(run):
    steps = run.facts.get("step_s")
    if not steps:
        return None
    return 100.0 * (1.0 - sum(steps) / run.facts["window_wall_s"])
