"""Mean device time of the program that puts a prefill's state into its
slot, in the traced window: ``jit__write`` on the ``XLA Modules`` line,
which for a model with a state bank writes the prompt's K and V into
their pages AND the state after the last real token into the slot's row,
once an admission, before the decode step that follows. A program
without a state bank reads as ``None``."""
import re

WRITE_MODULE = re.compile(r"^jit__write\b")


def read(run):
    if run.trace is None or not run.facts["run_metrics"].get("state"):
        return None
    writes = [e - s for n, s, e in run.trace["devices"][0]["modules"]
              if WRITE_MODULE.search(n)]
    if not writes:
        return None
    return sum(writes) / len(writes) / 1e6
