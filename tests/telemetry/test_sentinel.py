"""Perf-regression sentinel (telemetry/sentinel.py, ISSUE 14):
rolling-baseline math, component naming, flight-recorder black boxes
and baseline hygiene. Host-only — no compiles (the engine-integration
e2e lives in tests/serving/test_engine.py)."""
import json
import os

import pytest

from pipegoose_tpu.telemetry.flightrec import FlightRecorder
from pipegoose_tpu.telemetry.registry import MetricsRegistry
from pipegoose_tpu.telemetry.sentinel import PerfSentinel


def _base_run(**over):
    run = {"tokens_per_s": 100.0, "compute_s": 0.01,
           "comm[tensor]_s": 0.004, "idle_s": 0.002}
    run.update(over)
    return run


def test_constructor_validation():
    with pytest.raises(ValueError, match="window"):
        PerfSentinel(window=0)
    with pytest.raises(ValueError, match="min_baseline"):
        PerfSentinel(min_baseline=0)
    with pytest.raises(ValueError, match="ratio_threshold"):
        PerfSentinel(ratio_threshold=1.0)
    with pytest.raises(ValueError, match="drop_threshold"):
        PerfSentinel(drop_threshold=1.5)


def test_no_verdict_below_min_baseline():
    s = PerfSentinel(min_baseline=3)
    # the third observation has 2 baseline runs — still below min
    assert s.observe(_base_run()) is None
    assert s.observe(_base_run(idle_s=1.0)) is None
    assert s.observe(_base_run(idle_s=5.0)) is None
    assert s.regressions == 0 and s.baseline_size == 3


def test_component_regression_names_the_component():
    s = PerfSentinel(min_baseline=2, ratio_threshold=1.5)
    for _ in range(3):
        assert s.observe(_base_run()) is None
    v = s.observe(_base_run(**{"comm[tensor]_s": 0.0084}))
    assert v is not None and s.regressions == 1
    assert "tensor-axis collective time 2.1x baseline" in v["reason"]
    # the regressed run must NOT enter the baseline it was judged by
    assert s.baseline_size == 3
    assert s.baseline()["comm[tensor]_s"] == pytest.approx(0.004)
    # a healthy follow-up is judged against the unpoisoned median
    assert s.observe(_base_run()) is None


def test_tokens_per_s_drop_direction():
    s = PerfSentinel(min_baseline=2, drop_threshold=0.7)
    for _ in range(2):
        s.observe(_base_run())
    # faster is never a regression
    assert s.observe(_base_run(tokens_per_s=500.0)) is None
    v = s.observe(_base_run(tokens_per_s=60.0))
    assert v is not None and "tokens/s 0.60x baseline" in v["reason"]


def test_worst_component_leads_the_reason():
    s = PerfSentinel(min_baseline=2, ratio_threshold=1.5)
    for _ in range(2):
        s.observe(_base_run())
    v = s.observe(_base_run(idle_s=0.02, **{"comm[tensor]_s": 0.007}))
    assert v["reason"].startswith("idle time 10.0x")
    assert {r["component"] for r in v["regressions"]} == {
        "idle_s", "comm[tensor]_s"}


def test_recorder_black_box_fired(tmp_path):
    rec = FlightRecorder(str(tmp_path), capacity=4)
    s = PerfSentinel(recorder=rec, min_baseline=2)
    for _ in range(2):
        s.observe(_base_run())
    trig = s.observe(_base_run(idle_s=0.02), step=7)
    assert trig is not None and trig.name == "perf_regression"
    assert trig.step == 7 and "idle time" in trig.reason
    assert trig.dump_path and os.path.exists(trig.dump_path)
    with open(trig.dump_path) as f:
        box = json.load(f)
    assert box["trigger"]["details"]["regressions"][0]["component"] == "idle_s"
    # healthz-style consumers see it pending until taken
    assert rec.take_trigger() is trig


def test_gauges_exported_on_enabled_registry():
    reg = MetricsRegistry(enabled=True)
    s = PerfSentinel(registry=reg, min_baseline=2)
    s.observe({"tokens_per_s": 50.0,
               "profile": {"wall_step_s": 0.01, "compute_s": 0.005,
                           "comm_s": 0.002, "idle_s": 0.003,
                           "comm_by_axes": {"tensor": 0.002}}})
    snap = reg.snapshot()["gauges"]
    assert snap["perf.compute_fraction"] == pytest.approx(0.5)
    assert snap["perf.comm_fraction"] == pytest.approx(0.2)
    assert snap["perf.idle_fraction"] == pytest.approx(0.3)
    assert snap["perf.tokens_per_s"] == pytest.approx(50.0)


def test_profile_subdict_components_flatten():
    s = PerfSentinel(min_baseline=2, ratio_threshold=1.5)
    row = {"tokens_per_s": 100.0,
           "profile": {"wall_step_s": 0.01, "compute_s": 0.005,
                       "comm_s": 0.002, "idle_s": 0.003,
                       "comm_by_axes": {"tensor": 0.002}}}
    s.observe(dict(row))
    s.observe(dict(row))
    slow = json.loads(json.dumps(row))
    slow["profile"]["comm_by_axes"]["tensor"] = 0.008
    v = s.observe(slow)
    assert v is not None and "tensor-axis collective" in v["reason"]
