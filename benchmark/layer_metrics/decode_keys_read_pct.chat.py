"""Share of the page tables' key columns the decode steps walked: the
engine's ``decode_key_share`` (``finish_run()``), key columns visited by
the plain decode steps over ``steps x table width x page size``. The
rate at which the read's walk (``kv_pool._attend_rows``: only as far as
the longest live sequence) engages; 100 is a step that reads every page
its tables reach. A program without the counter reads as ``None``."""


def read(run):
    share = run.facts["run_metrics"].get("decode_key_share")
    if share is None or not run.facts["run_metrics"].get("decode_steps"):
        return None
    return 100.0 * share
