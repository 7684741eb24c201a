"""The reader PR 32 added, on hand-built facts: the engine's
``decode_key_share`` as a percentage; ``None`` from a program without
the counter (the parent commit) and from a run without a decode step."""
import os

import pytest

from benchmark import harness

READER = os.path.join(harness.HERE, "layer_metrics",
                      "decode_keys_read_pct.chat.py")


def serve_run(run_metrics):
    return harness.Result(end_to_end={}, attempted=1, failed=0,
                          t_window_start=0.0, memory_peak_bytes=0,
                          facts={"run_metrics": run_metrics}, trace=None)


@pytest.mark.parametrize("metrics, want", [
    ({"decode_key_share": 0.1875, "decode_steps": 640}, 18.75),
    ({"decode_key_share": 1.0, "decode_steps": 3}, 100.0),
    ({"decode_steps": 640}, None),                  # the parent: no counter
    ({"decode_key_share": 0.0, "decode_steps": 0}, None),
])
def test_decode_keys_reader(metrics, want):
    got = harness.load_module(READER).read(serve_run(metrics))
    assert got == (want if want is None else pytest.approx(want))
