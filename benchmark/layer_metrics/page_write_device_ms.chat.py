"""Mean device time of the page-write program's executions in the
traced window (``jit__write`` on the ``XLA Modules`` line): the copy of
a prompt's K and V into its pages, which runs after every prefill and
before the decode step that follows."""
import re

WRITE_MODULE = re.compile(r"^jit__write\b")


def read(run):
    if run.trace is None:
        return None
    writes = [e - s for n, s, e in run.trace["devices"][0]["modules"]
              if WRITE_MODULE.search(n)]
    if not writes:
        return None
    return sum(writes) / len(writes) / 1e6
