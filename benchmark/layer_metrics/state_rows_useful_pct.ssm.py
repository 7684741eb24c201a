"""Rows of the state bank that were alive over the rows the decode steps
read and wrote: ``finish_run()["state"]``'s ``rows_live / rows_updated``
x 100. A step's walk over the bank takes a few slots a trip as far as
the highest live one, so a dead slot under it is read and written for
nothing; counted on the host from the slots each step is sent, with the
device's own arithmetic. A program without a state bank reads as
``None``."""


def read(run):
    state = run.facts["run_metrics"].get("state")
    if not state or not state.get("rows_updated"):
        return None
    return 100.0 * state["rows_live"] / state["rows_updated"]
