"""Mean decode step by the engine's own clock
(``finish_run()``'s ``decode_step_time_s / decode_steps``): host clock
around the step and its token fetch."""


def read(run):
    m = run.facts["run_metrics"]
    if not m["decode_steps"]:
        return None
    return 1e3 * m["decode_step_time_s"] / m["decode_steps"]
