"""Fleet goodput ledger e2e (ISSUE 19): the control plane's tick loop
drives wall-clock attribution that conserves to 1e-6 through a seeded
crash + rejoin, mints ONE incident per failure episode joined to the
``chaos.injection`` ring record (latency == ring distance) for every
fleet chaos kind, prices MTTR and the capacity-gap integral, embeds the
incident in the ``replica_failure`` black box, surfaces through
``fleet_status``/``/debug/goodput``/``/debug/fleet``, stays
token-identical to an unledgered run, and costs < 5 µs per tick when
off (the default)."""
import json
import time
from types import SimpleNamespace
from urllib.request import urlopen

import numpy as np
import pytest

from pipegoose_tpu.serving import Request
from pipegoose_tpu.serving.control_plane import ControlPlane
from pipegoose_tpu.serving.control_plane.plane import ControlPlane as _CP
from pipegoose_tpu.telemetry.flightrec import FlightRecorder
from pipegoose_tpu.testing.chaos import (
    ChaosMonkey,
    ChaosSchedule,
    Injection,
)


@pytest.fixture(scope="module")
def tiny():
    import jax

    from pipegoose_tpu.models import bloom

    cfg = bloom.BloomConfig(vocab_size=64, hidden_size=32, n_layer=2,
                            n_head=2)
    params = bloom.init_params(cfg, jax.random.PRNGKey(0))
    return params, cfg


def _factory(params, cfg, host_tier_bytes=0):
    def make(name, registry):
        from pipegoose_tpu.serving import ServingEngine

        kw = {}
        if host_tier_bytes:
            from pipegoose_tpu.serving.kv_tier import HostTier

            kw["host_tier"] = HostTier(host_tier_bytes)
        return ServingEngine(params, cfg, num_slots=1, num_pages=33,
                             page_size=8, max_context=96,
                             prefix_cache=True, registry=registry, **kw)
    return make


def _requests(n=10, seed=0, vocab=64):
    from pipegoose_tpu.serving import make_skewed_replay

    replay = make_skewed_replay(
        n_requests=n, n_prefixes=3, prefix_len=32, suffix_lens=(2, 4),
        max_new=3, vocab=vocab, seed=seed, n_tenants=2,
    )
    return lambda: [Request(prompt=p, max_new_tokens=m, tenant=t)
                    for p, m, t in replay]


def _assert_token_identical(clean, got):
    assert len(got) == len(clean)
    for a, b in zip(clean, got):
        np.testing.assert_array_equal(a.generated, b.generated)


# -- the acceptance pin: crash + rejoin, conservation + incident ------------


def test_crash_rejoin_conservation_and_incident(tiny, tmp_path):
    """Seeded replica_crash at tick 4, rejoin, run again: per-replica
    class-seconds == alive wall within 1e-6 through the whole lifecycle;
    EXACTLY one incident — joined to the injection at ring distance 0,
    MTTR and capacity-gap integral > 0, resolved by the rejoin, the
    salvage manifest attached — embedded in the replica_failure black
    box and served by /debug/goodput and /debug/fleet."""
    from pipegoose_tpu.telemetry.opsserver import OpsServer

    params, cfg = tiny
    reqs = _requests()
    recorder = FlightRecorder(str(tmp_path), capacity=64)
    plane = ControlPlane(_factory(params, cfg), n_replicas=2,
                         recorder=recorder, goodput=True)
    assert plane.goodput is not None
    clean, _ = plane.run(reqs())
    schedule = ChaosSchedule(
        [Injection(4, "replica_crash", (("replica", 1),))])
    monkey = ChaosMonkey(schedule, recorder=recorder)
    crashed, metrics = plane.run(reqs(), tick_hook=monkey.fleet_hook)
    _assert_token_identical(clean, crashed)

    led = plane.goodput
    # one incident: kind, ring join, pricing
    assert len(led.incidents) == 1
    inc = led.incidents[0]
    assert inc.kind == "crash" and inc.replica == "replica1"
    assert inc.open and inc.reason.startswith("tick_once raised")
    # the fault arms and fires in the SAME tick: ring distance 0
    assert inc.detection_latency_ticks == 0
    assert inc.injection_step == 4 and inc.tick_detected == 4
    assert inc.capacity_gap_at_open == 1
    assert inc.capacity_gap_integral_s > 0
    assert inc.salvaged_uids and inc.lost_uids == []
    # quarantine wall accrued while failed; conservation held anyway
    assert led.replicas["replica1"].classes["failed_quarantine"] > 0
    cons = led.conservation()
    assert cons["ok"] and cons["max_error_s"] <= 1e-6, cons
    # run metrics + fleet_status carry the summary and per-replica dwell
    assert metrics["goodput"]["incidents"] == 1
    assert metrics["goodput"]["conservation_ok"]
    status = plane.fleet_status()
    assert 0 < status["goodput"]["goodput_fraction"] <= 1
    rows = {r["name"]: r for r in status["replicas"]}
    assert rows["replica1"]["state_seconds"]["failed"] > 0
    assert ["failed", 4] in [list(h) for h in
                             rows["replica1"]["state_history"]]
    json.dumps(status)
    # the black box embeds the incident next to the salvage manifest
    box = [p for p in recorder.dumps if "replica_failure" in p][0]
    with open(box) as f:
        det = json.load(f)["trigger"]["details"]
    assert det["incident"]["kind"] == "crash"
    assert det["incident"]["detection_latency_ticks"] == 0

    # rejoin closes the incident: MTTR = detection -> rejoin
    plane.rejoin("replica1")
    assert not inc.open and inc.resolved_by == "rejoin"
    assert inc.mttr_s > 0 and inc.mttr_ticks >= 0
    assert inc.slo_burn["wall_s"] > 0
    assert led.open_incidents == []
    # a post-rejoin run keeps conserving and serves the ops endpoint
    again, _ = plane.run(reqs())
    _assert_token_identical(clean, again)
    cons = led.conservation()
    assert cons["ok"] and cons["max_error_s"] <= 1e-6, cons
    with OpsServer(recorder=recorder, port=0,
                   goodput=lambda: led.report()) as srv:
        body = json.loads(
            urlopen(srv.url + "/debug/goodput", timeout=5).read())
    assert body["incidents"] == 1
    assert body["incident_log"][0]["resolved_by"] == "rejoin"
    assert body["replicas"]["replica1"]["conservation"]["ok"]


def test_goodput_run_token_identical_to_unledgered(tiny, tmp_path):
    """The observer-effect pin: the ledgered fleet emits byte-identical
    tokens to the unledgered one through the same seeded crash."""
    params, cfg = tiny
    reqs = _requests(seed=1)
    outs = []
    for goodput in (False, True):
        recorder = FlightRecorder(str(tmp_path / f"g{goodput}"),
                                  capacity=64)
        plane = ControlPlane(_factory(params, cfg), n_replicas=2,
                             recorder=recorder, goodput=goodput)
        plane.run(reqs())                                  # warm
        monkey = ChaosMonkey(ChaosSchedule(
            [Injection(4, "replica_crash", (("replica", 1),))]),
            recorder=recorder)
        got, _ = plane.run(reqs(), tick_hook=monkey.fleet_hook)
        outs.append(got)
    assert outs[0] and len(outs[0]) == len(outs[1])
    _assert_token_identical(outs[0], outs[1])


# -- chaos-kind -> incident joins (the other two fleet kinds) ---------------


def test_wedge_incident_latency_is_ring_distance(tiny, tmp_path):
    """A replica_wedge walks the SUSPECT -> FAILED ladder before
    detection: the incident's latency is EXACTLY tick_detected minus
    the injection's ring step — never 0, never re-zeroed to the
    detection tick — and scale-up (capacity replacement) closes it."""
    params, cfg = tiny
    reqs = _requests(seed=2)
    recorder = FlightRecorder(str(tmp_path), capacity=64)
    plane = ControlPlane(_factory(params, cfg), n_replicas=2,
                         recorder=recorder, goodput=True,
                         suspect_after_ticks=2, failed_after_ticks=6)
    clean, _ = plane.run(reqs())
    monkey = ChaosMonkey(ChaosSchedule(
        [Injection(3, "replica_wedge", (("replica", 0),))]),
        recorder=recorder)
    wedged, _ = plane.run(reqs(), tick_hook=monkey.fleet_hook)
    _assert_token_identical(clean, wedged)
    led = plane.goodput
    assert len(led.incidents) == 1
    inc = led.incidents[0]
    assert inc.kind == "wedge" and "wedged" in inc.reason
    assert inc.injection_step == 3
    assert inc.detection_latency_ticks == inc.tick_detected - 3
    # the first missed heartbeat lands the same tick the wedge arms,
    # so the ladder detects after failed_after_ticks - 1 further ticks
    assert inc.detection_latency_ticks >= plane.failed_after_ticks - 1
    # the ladder left suspect wall on the books before the failure
    wedge_rep = led.replicas[inc.replica]
    assert wedge_rep.classes["suspect_probing"] > 0
    assert led.conservation()["ok"]
    # replacement capacity closes the episode
    plane.scale_up()
    assert not inc.open and inc.resolved_by == "scale_up"
    assert inc.mttr_s > 0


def test_transfer_flap_incident_joins_injection_at_ring_distance(
        tiny, tmp_path):
    """The third fleet kind, fully real: the seeded transfer fault
    makes a cross-replica KV pull fail mid-run, the restore path falls
    back to recompute, and the plane's fallback-delta watch mints ONE
    zero-MTTR incident (the fallback IS the recovery) joined to the
    transfer_flap ring record at exact ring distance — and nothing
    fails or quarantines."""
    params, cfg = tiny
    reqs = _requests(seed=3)
    recorder = FlightRecorder(str(tmp_path), capacity=64)
    plane = ControlPlane(_factory(params, cfg, host_tier_bytes=1 << 20),
                         n_replicas=2, recorder=recorder, goodput=True)
    assert all(r.engine.kv_tier is not None for r in plane.replicas)
    monkey = ChaosMonkey(ChaosSchedule(
        [Injection(5, "transfer_flap", (("fail_times", 2),))]),
        recorder=recorder)
    try:
        plane.run(reqs(), tick_hook=monkey.fleet_hook)
    finally:
        monkey.disarm()
    led = plane.goodput
    assert len(led.incidents) == 1
    inc = led.incidents[0]
    assert inc.kind == "transfer_flap"
    assert "KV transfer fallback" in inc.reason
    assert inc.injection_step == 5
    assert inc.detection_latency_ticks == inc.tick_detected - 5
    assert inc.detection_latency_ticks >= 0
    # closed at detection: recompute IS the recovery
    assert not inc.open and inc.resolved_by == "fallback"
    assert inc.mttr_s == 0.0 and inc.capacity_gap_at_open == 0
    assert not plane.failed_replicas()
    assert led.conservation()["ok"]


# -- the <5µs off-switch guard ----------------------------------------------


def test_goodput_flush_disabled_under_5us(empty_iterations):
    """The established branch-guard contract: with no ledger attached
    (the default) the per-tick flush is one attribute read + branch —
    under 300 iterations of an empty loop
    (``conftest.empty_iterations``), like the tracer/sentinel/memledger
    guards."""
    fake = SimpleNamespace(goodput=None)
    clock = time.perf_counter
    assert empty_iterations(
        lambda: _CP._goodput_flush(fake, None, 0, clock)) < 300
