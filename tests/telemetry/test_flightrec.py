"""Flight recorder (telemetry/flightrec.py): ring bounds, structured
triggers, atomic black-box dumps, and the recovery handshake — all
host-side (no device work)."""
import json
import os
import types

import pytest

from pipegoose_tpu.telemetry import MetricsRegistry
from pipegoose_tpu.telemetry.flightrec import FlightRecorder, TriggerEvent


def _trainer_stub(health=None, tokens=128):
    """Minimal duck-typed trainer for the callback interface."""
    state = types.SimpleNamespace(last_health=health, step=0)
    return types.SimpleNamespace(
        state=state, tokens_per_step=tokens, parallel_context=None,
        logger=None,
    )


def _healthy(gn=1.0):
    return {
        "grad_norm": gn,
        "grad_norm_per_module": {"embed": gn * 0.9, "blocks": gn * 0.1},
        "nonfinite_grad_leaves": 0.0,
        "nonfinite_update_leaves": 0.0,
        "update_max_abs": 1e-3,
        "update_norm": 0.1,
        "param_norm": 10.0,
        "update_ratio": 0.01,
    }


def _run_steps(rec, trainer, losses, healths=None):
    for i, loss in enumerate(losses, start=1):
        trainer.state.last_health = (
            healths[i - 1] if healths is not None else _healthy()
        )
        rec.on_step_start(trainer, i)
        rec.on_step_end(trainer, i, loss)


def test_ring_is_bounded(tmp_path):
    rec = FlightRecorder(str(tmp_path), capacity=4)
    for i in range(10):
        rec.record("x", step=i)
    assert len(rec.records) == 4
    assert [r["step"] for r in rec.records] == [6, 7, 8, 9]


def test_nonfinite_trigger_names_module_and_dumps(tmp_path):
    rec = FlightRecorder(str(tmp_path), capacity=8)
    trainer = _trainer_stub()
    bad = _healthy()
    bad["nonfinite_grad_leaves"] = 2.0
    bad["grad_norm"] = float("inf")
    bad["grad_norm_per_module"] = {"embed": float("inf"), "blocks": 0.1}
    _run_steps(rec, trainer, [4.0, 4.0, float("inf")],
               [_healthy(), _healthy(), bad])
    trig = rec.take_trigger()
    assert trig is not None and trig.name == "nonfinite"
    assert "'embed'" in trig.reason          # names the module group
    assert "non-finite loss" in trig.reason
    assert trig.dump_path and os.path.exists(trig.dump_path)
    # consuming clears it
    assert rec.take_trigger() is None

    # STRICT JSON: the nonfinite dump is exactly where inf/nan live;
    # bare Infinity/NaN tokens would make the black box unreadable by
    # jq/JS/log pipelines right when it matters (RFC 8259 has no such
    # literals — python's json.load merely tolerates them)
    text = open(trig.dump_path).read()
    assert "Infinity" not in text and "NaN" not in text
    data = json.loads(
        text, parse_constant=lambda c: pytest.fail(f"non-JSON token {c}")
    )
    assert data["records"][-1]["health"]["grad_norm"] == "inf"
    assert data["trigger"]["name"] == "nonfinite"
    assert data["trigger"]["step"] == 3
    assert data["trigger"]["details"]["bad_modules"] == ["embed"]
    kinds = [r["kind"] for r in data["records"]]
    assert kinds.count("train.step") == 3
    assert data["records"][-1]["health"]["nonfinite_grad_leaves"] == 2.0
    assert data["records"][-1]["step_time_s"] is not None
    assert "jax" in data["environment"]
    # atomic write: no temp litter
    assert not [f for f in os.listdir(tmp_path) if f.endswith(".tmp")]


def test_update_overflow_triggers_without_bad_loss(tmp_path):
    """Overflowed optimizer updates under a still-finite loss (the
    CheckpointCallback blind spot) must fire on their own."""
    rec = FlightRecorder(str(tmp_path))
    bad = _healthy()
    bad["nonfinite_update_leaves"] = 1.0
    _run_steps(rec, _trainer_stub(), [4.0], [bad])
    trig = rec.take_trigger()
    assert trig is not None and trig.name == "nonfinite"
    assert "optimizer updates" in trig.reason


def test_loss_spike_zscore_arms_after_warmup(tmp_path):
    # below the arming threshold a spike-looking value must not fire
    # (startup loss cliffs would trip a day-one z-score)
    rec0 = FlightRecorder(str(tmp_path / "a"), loss_spike_z=4.0, window=8,
                          grad_explosion_factor=None)
    _run_steps(rec0, _trainer_stub(), [4.0, 50.0])
    assert rec0.take_trigger() is None

    rec = FlightRecorder(str(tmp_path / "b"), loss_spike_z=4.0, window=8,
                         grad_explosion_factor=None)
    trainer = _trainer_stub()
    _run_steps(rec, trainer, [4.0, 4.1, 3.9, 4.0])   # >= window//2: armed
    assert rec.take_trigger() is None
    _run_steps(rec, trainer, [50.0])
    trig = rec.take_trigger()
    assert trig is not None and trig.name == "loss_spike"
    assert "sigma" in trig.reason
    assert trig.details["z"] > 4.0


def test_grad_explosion_trigger_names_largest_module(tmp_path):
    rec = FlightRecorder(str(tmp_path), grad_explosion_factor=10.0,
                         window=4, loss_spike_z=None)
    trainer = _trainer_stub()
    _run_steps(rec, trainer, [4.0, 4.0], [_healthy(1.0), _healthy(1.1)])
    assert rec.take_trigger() is None
    _run_steps(rec, trainer, [4.0], [_healthy(100.0)])
    trig = rec.take_trigger()
    assert trig is not None and trig.name == "grad_explosion"
    assert "'embed'" in trig.reason          # largest per-module norm
    assert trig.details["grad_norm"] == pytest.approx(100.0)


def test_spike_does_not_poison_its_own_baseline(tmp_path):
    """A triggering step's loss must NOT enter the trailing window —
    otherwise one spike shifts the mean and masks the next one."""
    rec = FlightRecorder(str(tmp_path), loss_spike_z=4.0, window=6,
                         grad_explosion_factor=None)
    trainer = _trainer_stub()
    _run_steps(rec, trainer, [4.0, 4.1, 3.9, 4.0])
    _run_steps(rec, trainer, [60.0])
    assert rec.take_trigger().name == "loss_spike"
    assert 60.0 not in rec._loss_hist
    _run_steps(rec, trainer, [55.0])         # second spike still fires
    assert rec.take_trigger().name == "loss_spike"


def test_check_every_skips_off_steps(tmp_path):
    rec = FlightRecorder(str(tmp_path), check_every=2)
    trainer = _trainer_stub()
    bad = _healthy()
    bad["nonfinite_grad_leaves"] = 1.0
    # step 1 is an off step (1 % 2 != 0): not recorded, no trigger
    trainer.state.last_health = bad
    rec.on_step_start(trainer, 1)
    rec.on_step_end(trainer, 1, float("nan"))
    assert len(rec.records) == 0 and rec.take_trigger() is None
    rec.on_step_start(trainer, 2)
    rec.on_step_end(trainer, 2, float("nan"))
    assert len(rec.records) == 1 and rec.take_trigger() is not None


def test_reset_after_restore_clears_baselines_and_marks_ring(tmp_path):
    rec = FlightRecorder(str(tmp_path))
    _run_steps(rec, _trainer_stub(), [4.0, 4.0, 4.0])
    assert len(rec._loss_hist) == 3
    rec.last_trigger = TriggerEvent("nonfinite", "x", 3)
    rec.reset_after_restore(2)
    assert not rec._loss_hist and not rec._grad_hist
    assert rec.take_trigger() is None
    assert rec.records[-1]["kind"] == "restore"
    assert rec.records[-1]["step"] == 2


def test_max_dumps_bounds_disk(tmp_path):
    rec = FlightRecorder(str(tmp_path), max_dumps=2)
    for i in range(4):
        path = rec.dump(TriggerEvent("nonfinite", "r", i))
        assert (path is not None) == (i < 2)
    assert len([f for f in os.listdir(tmp_path) if f.endswith(".json")]) == 2


def test_span_summaries_drain_from_enabled_registry(tmp_path):
    from pipegoose_tpu.telemetry.spans import span

    reg = MetricsRegistry(enabled=True)
    rec = FlightRecorder(str(tmp_path), registry=reg)
    trainer = _trainer_stub()
    rec.on_fit_start(trainer)
    with span("train.step", registry=reg):
        pass
    with span("train.step", registry=reg):
        pass
    rec.on_step_start(trainer, 1)
    rec.on_step_end(trainer, 1, 4.0)
    spans = rec.records[-1]["spans"]
    assert spans["train.step"]["n"] == 2
    assert spans["train.step"]["total_s"] >= 0
    rec.on_fit_end(trainer)
    assert rec._sink not in reg._sinks


def test_disabled_registry_is_never_implicitly_enabled(tmp_path):
    reg = MetricsRegistry(enabled=False)
    rec = FlightRecorder(str(tmp_path), registry=reg)
    rec.on_fit_start(_trainer_stub())
    assert not reg.enabled and not rec._attached


def test_serving_stall_trigger_dumps(tmp_path):
    rec = FlightRecorder(str(tmp_path))
    rec.observe_serving_step(1, active=2, queue_depth=3, dur_s=0.01, tokens=2)
    trig = rec.trigger_decode_stall(
        5, "no decode progress", context={"queued": 3}
    )
    assert trig.name == "decode_stall"
    data = json.load(open(trig.dump_path))
    assert data["context"]["queued"] == 3
    assert data["records"][0]["kind"] == "serving.step"


def test_validation():
    with pytest.raises(ValueError, match="capacity"):
        FlightRecorder("/tmp/x", capacity=0)
    with pytest.raises(ValueError, match="check_every"):
        FlightRecorder("/tmp/x", check_every=0)
    with pytest.raises(ValueError, match="window"):
        FlightRecorder("/tmp/x", window=1)
