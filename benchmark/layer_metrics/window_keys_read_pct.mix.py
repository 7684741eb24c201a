"""Key columns a window layer's decode reads walked (its ring: three
chunks at most) over those a global layer's read of the same steps
walked: the engine's ``window_key_share`` (``finish_run()``), counted on
the host from the ``seq_lens`` each step is sent, with the device's own
arithmetic. 100 is a window layer that reads as far as a global one. A
program without a window kind reads as ``None``."""


def read(run):
    share = run.facts["run_metrics"].get("window_key_share")
    if share is None or not run.facts["run_metrics"].get("decode_steps"):
        return None
    return 100.0 * share
