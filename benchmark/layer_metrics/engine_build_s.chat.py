"""Wall of ``ServingEngine.__init__``, first line to last: the engine's
``setup["build_s"]`` (``finish_run()``). Part of ``setup_s``."""


def read(run):
    setup = run.facts["run_metrics"].get("setup")
    if not setup:
        return None
    return setup["build_s"]
