"""``peak_bytes_in_use`` of the fullest device, read after the window
and before the reference runs."""


def read(run):
    return run.facts["memory_peak_bytes"] / 1e9
