"""Device time of collective operations during which no compute
operation runs on that device, over the traced window, worst device."""


def read(run):
    if run.trace is None:
        return None
    worst = max(d["exposed_collective_ns"] for d in run.trace["devices"])
    return 100.0 * worst / run.trace["window_ns"]
