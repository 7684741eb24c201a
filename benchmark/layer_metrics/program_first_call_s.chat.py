"""Summed host wall of the first call of every jitted program the
engine has run (decode step, one prefill and one page-write program per
prompt bucket): the engine's ``setup["first_call_s"]``
(``finish_run()``). The first call compiles the program or loads it
from the cache; all of them fall in warm-up, so in ``setup_s``."""


def read(run):
    setup = run.facts["run_metrics"].get("setup")
    if not setup or not setup.get("first_call_s"):
        return None
    return sum(setup["first_call_s"].values())
