"""Live memory ledger integration (ISSUE 18 acceptance): conservation
holds EXACTLY on every engine tick across the replay matrix (fp/int8 x
{plain, chunked+cached cold/warm, speculative} x disagg handoff x
kv-tier round trip), served tokens are byte-identical with the ledger
attached, the ledger-off tick costs one attribute read + branch
(< 5 µs, the established guard convention), the seeded ``page_leak``
chaos kind fires exactly one ``memory_leak`` black box naming the
owner trail, ``stranded_reservation`` is caught by the reservation
cross-check, and the exhaustion forecast walks monotonically to zero
BEFORE the first admission deferral on an overflow replay."""
import math
import time
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from pipegoose_tpu.models import bloom
from pipegoose_tpu.serving import DisaggEngine, Request, ServingEngine
from pipegoose_tpu.serving.engine import make_skewed_replay
from pipegoose_tpu.serving.kv_tier import HostTier
from pipegoose_tpu.serving.kv_tier.restore import wire_page_bytes
from pipegoose_tpu.telemetry import FlightRecorder, MemoryLedger
from pipegoose_tpu.telemetry.registry import MetricsRegistry
from pipegoose_tpu.testing.chaos import ChaosMonkey, ChaosSchedule, Injection

PS = 4


@pytest.fixture(scope="module")
def setup():
    cfg = bloom.BloomConfig(vocab_size=64, hidden_size=64, n_layer=2,
                            n_head=4)
    params = bloom.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(7)
    shared = rng.randint(1, 64, (12,))       # 3 full pages @ ps=4
    reqs = [
        (np.concatenate([shared, rng.randint(1, 64, (k,))]), n)
        for k, n in [(3, 6), (5, 4)]
    ] + [
        (shared[:10], 5),                    # strict prefix: COW mid-page
        (rng.randint(1, 64, (7,)), 6),       # unrelated: pure miss
    ]
    return cfg, params, reqs


def _requests(reqs):
    return [Request(prompt=p, max_new_tokens=n) for p, n in reqs]


def _conservation_hook(failures):
    """Per-tick conservation assertion, collected (not raised) so one
    broken tick reports with full context after the run."""
    def hook(engine, tick):
        ml = engine.memledger
        if ml is None:
            return
        cons = ml.conservation()
        if not cons["ok"]:
            failures.append((tick, cons))
    return hook


def _assert_identical(ref_outs, outs, label):
    assert len(ref_outs) == len(outs)
    for a, b in zip(ref_outs, outs):
        np.testing.assert_array_equal(
            b.generated, a.generated,
            err_msg=f"{label}: request {a.uid} diverged",
        )


# --- the conservation x token-identity matrix ------------------------------

MATRIX = [
    ("fp-plain", {}),
    ("fp-chunked-cache", dict(prefix_cache=True, prefill_chunk=PS)),
    ("int8-chunked-cache", dict(kv_dtype="int8", prefix_cache=True,
                                prefill_chunk=PS)),
    ("fp-spec", dict(speculative=(1, 3))),
]


@pytest.mark.parametrize("label,kw", MATRIX, ids=[m[0] for m in MATRIX])
def test_conservation_exact_and_tokens_identical(setup, label, kw):
    """Every tick of every matrix arm: classes sum to pool capacity
    EXACTLY (integer pages), the per-tick audit finds nothing, and the
    served streams match a ledger-less reference byte for byte. Warm
    second pass included for the cached arms."""
    cfg, params, reqs = setup

    def _engine(**extra):
        return ServingEngine(params, cfg, num_slots=2, num_pages=32,
                             page_size=PS, max_context=32,
                             registry=MetricsRegistry(), **kw, **extra)

    ref = _engine()
    ref_runs = [ref.run(_requests(reqs))[0]]
    if "prefix_cache" in kw:
        ref_runs.append(ref.run(_requests(reqs))[0])

    eng = _engine(memledger=MemoryLedger(audit_every=1))
    failures = []
    hook = _conservation_hook(failures)
    for i, ref_outs in enumerate(ref_runs):
        outs, metrics = eng.run(_requests(reqs), tick_hook=hook)
        _assert_identical(ref_outs, outs,
                          f"{label} run {i} (ledger attached)")
        assert metrics["memory"]["conservation_failures"] == 0
        assert metrics["memory"]["leaks"] == 0
    assert failures == [], f"{label}: conservation broke: {failures[:3]}"
    ml = eng.memledger
    assert ml.ticks > 0 and ml.audits_run > 0
    assert ml.last_audit["ok"], ml.last_audit
    # full reclamation: at rest everything is cached-or-free
    c = ml.counts()
    assert c["request"] == c["staged"] == c["cow"] == 0
    assert c["cached"] == eng.pool.used_count


def test_attach_knob_and_post_hoc_resync(setup):
    """``memledger=True`` builds and binds a ledger; attaching to a
    WARM engine adopts the live pool via resync and conserves from the
    first tick after."""
    cfg, params, reqs = setup
    eng = ServingEngine(params, cfg, num_slots=2, num_pages=32,
                        page_size=PS, max_context=32, prefix_cache=True,
                        prefill_chunk=PS, memledger=True,
                        registry=MetricsRegistry())
    assert isinstance(eng.memledger, MemoryLedger)
    assert eng.memledger.bytes_per_page > 0
    eng.run(_requests(reqs))
    # detach, run (cache stays warm), re-attach post-hoc: resync
    eng.attach_memledger(None)
    assert eng.memledger is None and eng.pool.ledger is None
    eng.run(_requests(reqs))
    assert eng.pool.used_count > 0          # warm cache holds pages
    eng.attach_memledger(MemoryLedger())
    assert eng.memledger.conservation()["ok"]
    failures = []
    eng.run(_requests(reqs), tick_hook=_conservation_hook(failures))
    assert failures == []


# --- disagg handoff --------------------------------------------------------

def test_disagg_handoff_conservation_and_tokens(setup):
    """Both pools' ledgers conserve on every disagg tick — transfer
    staging pages classify as ``staged`` on the decode pool until
    ``admit_with_pages`` retags them to request KV — and the streams
    match the single-engine reference."""
    cfg, params, reqs = setup
    single = ServingEngine(params, cfg, num_slots=2, num_pages=32,
                           page_size=PS, max_context=32,
                           prefix_cache=True, prefill_chunk=2 * PS,
                           registry=MetricsRegistry())
    ref_outs, _ = single.run(_requests(reqs))
    pe = ServingEngine(params, cfg, num_slots=2, num_pages=32,
                       page_size=PS, max_context=32, prefix_cache=True,
                       prefill_chunk=2 * PS, prefill_only=True,
                       memledger=True, registry=MetricsRegistry())
    de = ServingEngine(params, cfg, num_slots=2, num_pages=32,
                       page_size=PS, max_context=32, prefix_cache=True,
                       prefill_chunk=2 * PS, memledger=True,
                       registry=MetricsRegistry(), stall_patience=10_000)
    dis = DisaggEngine(pe, de, max_inflight=4,
                       registry=MetricsRegistry(enabled=True))
    failures = []
    staged_seen = []

    def hook(_dis, tick):
        for name, eng in (("prefill", pe), ("decode", de)):
            cons = eng.memledger.conservation()
            if not cons["ok"]:
                failures.append((name, tick, cons))
        staged_seen.append(de.memledger.counts()["staged"])

    outs, _ = dis.run(_requests(reqs), tick_hook=hook)
    _assert_identical(ref_outs, outs, "disagg handoff")
    assert failures == [], failures[:3]
    assert max(staged_seen) > 0, \
        "the decode ledger never saw a staged transfer page"
    assert pe.memledger.audit()["ok"]
    assert de.memledger.audit()["ok"]


# --- kv-tier round trip (satellite: host-tier byte census) -----------------

@pytest.mark.parametrize("kv_dtype", [None, "int8"], ids=["fp", "int8kv"])
def test_kv_tier_flapping_census_pinned_to_wire_bytes(setup, kv_dtype):
    """Eviction/restore flapping across N round trips: the host-tier
    byte census stays pinned to EXACTLY resident_pages x the int8 wire
    size (q + scale planes; fp: pool dtype), the HBM ledger conserves
    on every tick, and the audit stays clean."""
    cfg, params, _ = setup
    rng = np.random.RandomState(11)
    prefixes = [rng.randint(1, 64, (12,)) for _ in range(2)]
    suffixes = [rng.randint(1, 64, (2,)) for _ in range(2)]
    tier = HostTier(1 << 20)
    eng = ServingEngine(params, cfg, num_slots=2, num_pages=9,
                        page_size=PS, max_context=32, prefill_chunk=PS,
                        prefix_cache=True, kv_dtype=kv_dtype,
                        host_tier=tier, memledger=True,
                        registry=MetricsRegistry())
    wire = wire_page_bytes(eng)
    failures = []
    hook = _conservation_hook(failures)
    for round_trip in range(3):          # A evicts B evicts A, 3x
        for pfx in (prefixes[0], prefixes[1]):
            eng.run([Request(prompt=np.concatenate([pfx, s]),
                             max_new_tokens=4) for s in suffixes],
                    tick_hook=hook)
            assert tier.resident_bytes == tier.resident_pages * wire, (
                f"round {round_trip}: census drifted off the wire size")
    assert tier.spills > 0 and tier.restores > 0, \
        "the flapping replay never exercised the tier"
    assert failures == [], failures[:3]
    assert eng.memledger.audit()["ok"]
    ml_report = eng.memledger.report()
    assert ml_report["host_tier"]["resident_bytes"] == tier.resident_bytes


# --- the <5µs off-switch guard ---------------------------------------------

def test_ledger_tick_disabled_under_5us(setup, empty_iterations):
    """The established branch-guard contract: with no ledger attached
    (the default) the per-tick hook costs one attribute read + branch
    — under 300 iterations of an empty loop
    (``conftest.empty_iterations``), like the tracer/sentinel guards."""
    cfg, params, _ = setup
    eng = ServingEngine(params, cfg, num_slots=2, num_pages=8,
                        page_size=PS, max_context=32,
                        registry=MetricsRegistry())
    assert eng.memledger is None
    rs = SimpleNamespace(tick=3, now=lambda: 0.0)
    assert empty_iterations(lambda: eng._ledger_tick(rs)) < 300


# --- chaos: seeded leak + stranded reservation -----------------------------

def test_seeded_page_leak_fires_one_memory_leak_box(setup, tmp_path):
    """The detection path end-to-end: the chaos ``page_leak`` kind
    takes an unowned extra reference mid-run; the per-tick audit fires
    EXACTLY one ``memory_leak`` black box naming the page, the chaos
    owner tag, and the ownership trail — ringed right next to the
    ``chaos.injection`` record that caused it."""
    cfg, params, reqs = setup
    rec = FlightRecorder(str(tmp_path), capacity=64)
    monkey = ChaosMonkey(
        ChaosSchedule([Injection(3, "page_leak", (("page_index", 0),))]),
        recorder=rec,
    )
    eng = ServingEngine(params, cfg, num_slots=2, num_pages=32,
                        page_size=PS, max_context=32, recorder=rec,
                        memledger=MemoryLedger(audit_every=1),
                        registry=MetricsRegistry())
    outs, _ = eng.run(_requests(reqs), tick_hook=monkey.tick_hook)
    assert len(outs) == len(reqs)
    ml = eng.memledger
    assert ml.conservation()["ok"]       # a leak is NOT a ledger bug
    report = ml.last_audit
    assert not report["ok"] and len(report["leaks"]) == 1
    leak = report["leaks"][0]
    assert ["chaos", "page_leak"] in leak["owners"]
    assert leak["trail"], "the box must name the ownership trail"
    trig = rec.take_trigger()
    assert trig is not None and trig.name == "memory_leak"
    assert trig.details["page"] == leak["page"]
    assert rec.take_trigger() is None    # exactly ONE box, audits_run > 1
    assert ml.audits_run > 1
    injected = [r for r in rec.records if r["kind"] == "chaos.injection"]
    assert len(injected) == 1 and injected[0]["injection"] == "page_leak"
    # the leaked page survives full reclamation — that IS the leak
    assert eng.pool.used_count == 1


def test_seeded_stranded_reservation_detected(setup, tmp_path):
    cfg, params, reqs = setup
    rec = FlightRecorder(str(tmp_path), capacity=64)
    monkey = ChaosMonkey(
        ChaosSchedule([Injection(2, "stranded_reservation",
                                 (("pages", 2),))]),
        recorder=rec,
    )
    eng = ServingEngine(params, cfg, num_slots=2, num_pages=32,
                        page_size=PS, max_context=32, recorder=rec,
                        memledger=MemoryLedger(audit_every=1),
                        registry=MetricsRegistry())
    eng.run(_requests(reqs), tick_hook=monkey.tick_hook)
    ml = eng.memledger
    assert ml.conservation()["ok"]       # strand shrinks headroom, not sums
    assert ml.last_audit["stranded_reserved_pages"] == 2
    trig = rec.take_trigger()
    assert trig is not None and trig.name == "stranded_reservation"
    assert trig.details["stranded_pages"] == 2


def test_seeded_schedule_with_ledger_kinds_is_reproducible():
    from pipegoose_tpu.testing.chaos import schedule_fingerprint

    a = ChaosSchedule.seeded(5, 40, page_leak=2, stranded_reservation=1)
    b = ChaosSchedule.seeded(5, 40, page_leak=2, stranded_reservation=1)
    assert schedule_fingerprint(a) == schedule_fingerprint(b)
    assert len(a) == 3
    kinds = {i.kind for i in a.injections}
    assert kinds == {"page_leak", "stranded_reservation"}


# --- exhaustion forecast on the overflow replay ----------------------------

def test_forecast_monotone_to_zero_before_first_admission_block(setup):
    """The forecaster acceptance: on a skewed overflow replay fed one
    request per tick, steps-to-exhaustion becomes finite, walks down
    MONOTONICALLY, and reaches zero on a tick at or before the first
    admission deferral the scheduler actually records."""
    cfg, params, _ = setup
    specs = make_skewed_replay(
        n_requests=12, n_prefixes=1, prefix_len=4, suffix_lens=(2,),
        max_new=24, vocab=64, seed=3, working_set_factor=2.0,
        num_pages=32, page_size=PS)
    eng = ServingEngine(params, cfg, num_slots=8, num_pages=32,
                        page_size=PS, max_context=64, prefill_chunk=PS,
                        memledger=True, registry=MetricsRegistry())
    eng.start_run((), now=time.perf_counter)
    trend = []
    ml = eng.memledger
    for i in range(60):
        if i < len(specs):
            prompt, max_new = specs[i]
            eng.submit_request(Request(prompt=prompt,
                                       max_new_tokens=max_new))
        eng.tick_once()
        trend.append(ml.steps_to_exhaustion)
        if ml.first_admission_block_tick is not None:
            break
    try:
        assert ml.first_admission_block_tick is not None, \
            "the overflow replay never exhausted admission"
        finite = [s for s in trend if not math.isinf(s)]
        assert finite, "no finite forecast before exhaustion"
        assert finite == sorted(finite, reverse=True), \
            f"forecast bounced: {finite}"
        assert finite[-1] == 0.0 or 0.0 in finite, \
            f"forecast never reached zero: {finite}"
        first_zero_tick = trend.index(0.0) + 1
        assert first_zero_tick <= ml.first_admission_block_tick, (
            f"forecast zeroed at tick {first_zero_tick}, AFTER the "
            f"first deferral at {ml.first_admission_block_tick}")
        assert ml.min_steps_to_exhaustion == 0.0
    finally:
        # drain so the module-scoped params see a clean engine
        while not eng.sched.all_done():
            eng.tick_once()
        eng.finish_run()


# --- run metrics + capacity snapshot plumbing ------------------------------

def test_capacity_snapshot_carries_forecast(setup):
    cfg, params, reqs = setup
    eng = ServingEngine(params, cfg, num_slots=2, num_pages=32,
                        page_size=PS, max_context=32, memledger=True,
                        registry=MetricsRegistry())
    snap = eng.sched.capacity_snapshot()
    assert snap["steps_to_exhaustion"] is None   # inf renders as None
    eng.run(_requests(reqs))
    snap = eng.sched.capacity_snapshot()
    assert "steps_to_exhaustion" in snap
    # without a ledger the key is absent — callers feature-detect
    eng.attach_memledger(None)
    assert "steps_to_exhaustion" not in eng.sched.capacity_snapshot()


def test_run_metrics_memory_block(setup):
    cfg, params, reqs = setup
    eng = ServingEngine(params, cfg, num_slots=2, num_pages=32,
                        page_size=PS, max_context=32, memledger=True,
                        registry=MetricsRegistry())
    _, metrics = eng.run(_requests(reqs))
    mem = metrics["memory"]
    assert mem["peak_pages"]["request"] > 0
    assert mem["conservation_failures"] == 0
    assert set(mem["peak_bytes"]) == set(mem["peak_pages"])
    # ledger-less runs carry no memory block (default-off contract)
    bare = ServingEngine(params, cfg, num_slots=2, num_pages=32,
                         page_size=PS, max_context=32,
                         registry=MetricsRegistry())
    _, bare_metrics = bare.run(_requests(reqs))
    assert "memory" not in bare_metrics
