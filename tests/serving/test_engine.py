"""Serving correctness oracle: continuous-batching greedy decode must be
token-identical to per-request ``generate()`` — paging, slot reuse, and
mid-stream admission are pure memory-management, invisible in the
tokens. Plus pool reclamation after a full run, metrics sanity, the
continuous-vs-static step-count win, and the tp=2 sharded smoke."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pipegoose_tpu.distributed import ParallelContext
from pipegoose_tpu.models import bloom, generate as gen
from pipegoose_tpu.serving import Request, ServingEngine

MIXED = [(3, 5), (9, 12), (17, 4), (5, 9), (12, 7), (2, 15)]


@pytest.fixture(scope="module")
def setup():
    cfg = bloom.BloomConfig(vocab_size=64, hidden_size=64, n_layer=2, n_head=4)
    params = bloom.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(7)
    prompts = [rng.randint(1, 64, (s,)) for s, _ in MIXED]
    return cfg, params, prompts


def _reference(params, cfg, prompt, max_new, eos=None):
    out = gen.generate(
        params, jnp.asarray(prompt)[None], cfg, max_new_tokens=max_new,
        eos_token_id=eos,
    )
    return np.asarray(out)[0, len(prompt):]


def test_mixed_lengths_token_identical_to_generate(setup):
    """Six mixed-length requests through 3 slots: every emitted token
    equals the per-request contiguous-cache decode."""
    cfg, params, prompts = setup
    eng = ServingEngine(params, cfg, num_slots=3, num_pages=32,
                        page_size=4, max_context=64)
    outs, metrics = eng.run([
        Request(prompt=p, max_new_tokens=n)
        for p, (_, n) in zip(prompts, MIXED)
    ])
    assert [o.uid for o in outs] == list(range(len(MIXED)))
    for o, p, (_, n) in zip(outs, prompts, MIXED):
        np.testing.assert_array_equal(
            o.generated, _reference(params, cfg, p, n),
            err_msg=f"request {o.uid} diverged from generate()",
        )
        assert o.finish_reason == "length"
    # all pages reclaimed, metrics account for every token
    assert eng.pool.used_count == 0
    assert metrics["generated_tokens"] == sum(n for _, n in MIXED)
    assert 0.0 < metrics["slot_occupancy"] <= 1.0
    assert 0.0 < metrics["page_occupancy"] <= 1.0
    assert metrics["prefills"] == len(MIXED)


def test_eos_stops_request_and_frees_capacity(setup):
    cfg, params, prompts = setup
    p = prompts[0]
    ref = _reference(params, cfg, p, 6)
    eos = int(ref[1])  # the token the model emits 2nd becomes "eos"
    ref_eos = _reference(params, cfg, p, 6, eos=eos)
    stop = list(ref_eos).index(eos) + 1 if eos in ref_eos else len(ref_eos)

    eng = ServingEngine(params, cfg, num_slots=2, num_pages=32,
                        page_size=4, max_context=64)
    outs, _ = eng.run([Request(prompt=p, max_new_tokens=6, eos_token_id=eos)])
    # engine stops AT eos (generate pads the tail with eos afterwards)
    assert list(outs[0].generated) == list(ref_eos[:stop])
    assert outs[0].finish_reason == "eos"
    assert eng.pool.used_count == 0


def test_more_requests_than_pool_waves(setup):
    """A pool too small for all requests at once forces queueing waves;
    tokens still match and reclamation still completes."""
    cfg, params, prompts = setup
    eng = ServingEngine(params, cfg, num_slots=2, num_pages=12,
                        page_size=4, max_context=44)
    outs, _ = eng.run([
        Request(prompt=p, max_new_tokens=n)
        for p, (_, n) in zip(prompts, MIXED)
    ])
    for o, p, (_, n) in zip(outs, prompts, MIXED):
        np.testing.assert_array_equal(o.generated, _reference(params, cfg, p, n))
    assert eng.pool.used_count == 0


def test_continuous_beats_static_on_decode_steps(setup):
    """The continuous scheduler's whole point: mixed lengths through the
    same slots take FEWER synchronized decode steps than drain-then-
    refill batching (steps, not wall time — deterministic on CPU)."""
    cfg, params, prompts = setup
    requests = [(p, n) for p, (_, n) in zip(prompts, MIXED)]
    num_slots = 3
    eng = ServingEngine(params, cfg, num_slots=num_slots, num_pages=64,
                        page_size=4, max_context=64)
    outs, metrics = eng.run(
        [Request(prompt=p, max_new_tokens=n) for p, n in requests]
    )
    for o, (p, n) in zip(outs, requests):
        np.testing.assert_array_equal(
            o.generated, _reference(params, cfg, p, n)
        )
    # drain-then-refill: submit order in waves of num_slots, every slot
    # held until the wave's longest member ends; prefill emits each
    # request's first token, so a wave costs longest - 1 decode steps
    news = [n for _, n in requests]
    static_steps = sum(max(news[i:i + num_slots]) - 1
                       for i in range(0, len(news), num_slots))
    assert metrics["decode_steps"] < static_steps
    # same slot-steps of work in fewer steps: fuller slots
    work = sum(n - 1 for n in news)
    assert metrics["slot_occupancy"] > work / (num_slots * static_steps)


def test_engine_rejects_bad_geometry(setup):
    cfg, params, _ = setup
    with pytest.raises(ValueError, match="multiple of page_size"):
        ServingEngine(params, cfg, page_size=16, max_context=40)


@pytest.mark.parametrize("tp", [2])
def test_tp_sharded_serving_matches_generate(setup, devices, tp):
    """tp=2 shard_map serving (head-sharded pages, global_greedy_pick)
    emits the same tokens as single-device per-request generate."""
    cfg, params, prompts = setup
    ctx = ParallelContext(tensor_parallel_size=tp, data_parallel_size=4)
    try:
        eng = ServingEngine(
            params, cfg, num_slots=2, num_pages=32, page_size=4,
            max_context=64, mesh=ctx.mesh, param_specs=bloom.tp_specs(params),
        )
        sub = list(zip(prompts, MIXED))[:3]
        outs, _ = eng.run([
            Request(prompt=p, max_new_tokens=n) for p, (_, n) in sub
        ])
        for o, (p, (_, n)) in zip(outs, sub):
            np.testing.assert_array_equal(
                o.generated, _reference(params, cfg, p, n),
                err_msg=f"tp={tp} request {o.uid} diverged",
            )
        assert eng.pool.used_count == 0
    finally:
        ctx.destroy()


def test_engine_telemetry_agrees_with_legacy_metrics(setup):
    """ISSUE 2 acceptance: the per-step telemetry instrumentation and
    the legacy end-of-run aggregate dict describe the SAME run — token
    counters match exactly, derived tokens/s within 1% — and the new
    per-request latency fields are consistent."""
    from pipegoose_tpu.telemetry import MetricsRegistry

    cfg, params, prompts = setup
    reg = MetricsRegistry(enabled=True)
    eng = ServingEngine(params, cfg, num_slots=3, num_pages=32,
                        page_size=4, max_context=64, registry=reg)
    outs, metrics = eng.run([
        Request(prompt=p, max_new_tokens=n)
        for p, (_, n) in zip(prompts, MIXED)
    ])
    snap = reg.snapshot()
    # counters vs aggregates: exact
    assert snap["counters"]["serving.tokens_total"] == metrics["generated_tokens"]
    assert snap["counters"]["serving.prefills_total"] == metrics["prefills"]
    assert snap["counters"]["serving.decode_steps_total"] == metrics["decode_steps"]
    # derived throughput: within 1% of the legacy dict
    tel_tps = snap["gauges"]["serving.tokens_per_s"]
    assert tel_tps == pytest.approx(metrics["decode_tokens_per_s"], rel=0.01)
    # latency histograms: one TTFT per request, one decode observation
    # per step, e2e recorded for every finished request
    assert snap["histograms"]["serving.ttft_seconds"]["count"] == len(MIXED)
    assert (snap["histograms"]["serving.decode_token_seconds"]["count"]
            == metrics["decode_steps"])
    assert snap["histograms"]["serving.e2e_latency_seconds"]["count"] == len(MIXED)
    # per-request outputs carry the new submit->done latency, consistent
    # with TTFT and the dict
    for o, pr in zip(outs, metrics["requests"]):
        assert o.e2e_latency_s >= o.ttft_s > 0
        assert pr["e2e_latency_s"] == pytest.approx(o.e2e_latency_s, abs=1e-5)


def test_engine_telemetry_step_events_time_series(setup):
    """The engine emits a live occupancy time series (events), not just
    the end-of-run averages."""
    from pipegoose_tpu.telemetry import MetricsRegistry

    cfg, params, prompts = setup
    reg = MetricsRegistry(enabled=True)
    events = []
    reg.attach(events.append)
    eng = ServingEngine(params, cfg, num_slots=2, num_pages=32,
                        page_size=4, max_context=64, registry=reg)
    _, metrics = eng.run([
        Request(prompt=p, max_new_tokens=n)
        for p, (_, n) in zip(prompts[:4], MIXED[:4])
    ])
    steps = [e for e in events if e["kind"] == "serving.step"]
    assert len(steps) == metrics["decode_steps"]
    assert all(0 < e["slot_occupancy"] <= 1 for e in steps)
    assert all(e["dur_s"] > 0 for e in steps)
    # the mean of the time series equals the dict's aggregate
    mean_occ = sum(e["slot_occupancy"] for e in steps) / len(steps)
    assert mean_occ == pytest.approx(metrics["slot_occupancy"], abs=1e-3)
    spans = [e for e in events if e["kind"] == "span"]
    assert {"serving.prefill", "serving.decode_step"} <= {
        e["span"] for e in spans
    }


def test_engine_default_registry_disabled_records_nothing(setup):
    """Without opt-in the engine's instrumentation must leave the global
    registry untouched (the near-zero-overhead contract)."""
    from pipegoose_tpu.telemetry import get_registry

    cfg, params, prompts = setup
    reg = get_registry()
    assert not reg.enabled  # tests never enable the global registry
    eng = ServingEngine(params, cfg, num_slots=2, num_pages=32,
                        page_size=4, max_context=64)
    eng.run([Request(prompt=prompts[0], max_new_tokens=3)])
    snap = reg.snapshot()
    assert snap["counters"].get("serving.tokens_total", 0.0) == 0.0


def test_run_metrics_report_rate_and_occupancy(setup):
    """``run``'s metrics on a warm engine: a positive decode rate, slot
    occupancy a proper fraction, tokens still the reference's."""
    cfg, params, _ = setup
    specs = [(3, 4), (9, 8), (5, 2), (2, 6)]
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, cfg.vocab_size, (s,)) for s, _ in specs]

    def requests():
        return [Request(prompt=p, max_new_tokens=n)
                for p, (_, n) in zip(prompts, specs)]

    eng = ServingEngine(params, cfg, num_slots=2, num_pages=32,
                        page_size=4, max_context=32)
    eng.run(requests())   # compiles every bucket
    outs, metrics = eng.run(requests())
    assert metrics["decode_tokens_per_s"] > 0
    assert 0 < metrics["slot_occupancy"] <= 1.0
    for o, p, (_, n) in zip(outs, prompts, specs):
        np.testing.assert_array_equal(
            o.generated, _reference(params, cfg, p, n))


def test_stall_watchdog_dumps_and_raises(setup, tmp_path):
    """The no-decode-progress watchdog: a queue whose head can never be
    admitted (pool pages exhausted behind the scheduler's back stands in
    for a reservation-accounting bug) must raise a decode-stall error
    with a flight-recorder black box, not livelock the run loop."""
    from pipegoose_tpu.telemetry import FlightRecorder

    cfg, params, prompts = setup
    rec = FlightRecorder(str(tmp_path), capacity=8)
    eng = ServingEngine(params, cfg, num_slots=2, num_pages=8,
                        page_size=4, max_context=32, recorder=rec,
                        stall_patience=5)
    eng.pool.alloc(eng.pool.free_count - 1)   # strand the pool
    with pytest.raises(RuntimeError, match="decode stall"):
        eng.run([Request(prompt=prompts[0], max_new_tokens=4)])
    trig = rec.take_trigger()
    assert trig is not None and trig.name == "decode_stall"
    assert "queued" in trig.reason and "pages free" in trig.reason
    import json
    import os

    assert trig.dump_path and os.path.exists(trig.dump_path)
    data = json.load(open(trig.dump_path))
    assert data["trigger"]["name"] == "decode_stall"
    assert data["context"]["queued"] == 1


def test_recorder_rings_decode_steps(setup, tmp_path):
    from pipegoose_tpu.telemetry import FlightRecorder

    cfg, params, prompts = setup
    rec = FlightRecorder(str(tmp_path), capacity=64)
    eng = ServingEngine(params, cfg, num_slots=2, num_pages=32,
                        page_size=4, max_context=64, recorder=rec)
    _, metrics = eng.run([
        Request(prompt=p, max_new_tokens=n)
        for p, (_, n) in zip(prompts[:3], MIXED[:3])
    ])
    steps = [r for r in rec.records if r["kind"] == "serving.step"]
    assert len(steps) == metrics["decode_steps"]
    assert all(r["dur_s"] > 0 and r["active"] >= 1 for r in steps)


# -- perf sentinel integration (ISSUE 14) ----------------------------------


def test_sentinel_observe_disabled_under_5us(setup, empty_iterations):
    """The established branch-guard contract: with no sentinel attached
    (the default) the finish_run hook costs one attribute read + branch
    — under 300 iterations of an empty loop
    (``conftest.empty_iterations``), like the registry guard."""
    from types import SimpleNamespace

    cfg, params, _ = setup
    eng = ServingEngine(params, cfg, num_slots=2, num_pages=8,
                        page_size=4, max_context=32)
    assert eng.sentinel is None
    rs = SimpleNamespace(steps=3, step_time=0.01, generated_total=6)
    assert empty_iterations(lambda: eng._sentinel_observe(rs, 1.0)) < 300


def test_sentinel_attached_outputs_token_identical(setup):
    """The sentinel only reads host-side run aggregates: attaching one
    must leave the served token streams byte-identical."""
    from pipegoose_tpu.telemetry import PerfSentinel

    cfg, params, prompts = setup
    def reqs():
        return [Request(prompt=p, max_new_tokens=4) for p in prompts[:2]]

    ref_eng = ServingEngine(params, cfg, num_slots=2, num_pages=16,
                            page_size=4, max_context=32)
    ref, _ = ref_eng.run(reqs())
    eng = ServingEngine(params, cfg, num_slots=2, num_pages=16,
                        page_size=4, max_context=32,
                        sentinel=PerfSentinel(min_baseline=1))
    got, _ = eng.run(reqs())
    for a, b in zip(ref, got):
        np.testing.assert_array_equal(a.generated, b.generated)
    assert eng.sentinel.baseline_size == 1


def test_sentinel_names_regressed_component_on_host_stall(setup, tmp_path):
    """Sentinel e2e (ISSUE 14 acceptance): healthy baseline runs, then
    an injected slowdown through the chaos ``host_stall`` seam — the
    perf_regression black box must fire and NAME the regressed
    component (the stall lands in the per-step idle time).

    The engine reads a clock that advances one fixed quantum a reading,
    so identical runs time identically however busy the machine is (on
    the wall clock a millisecond run three times slower than the one
    before it read as a tokens/s regression); only the chaos hook is
    timed for real, and whatever a loaded machine adds there is more
    idle time."""
    import json
    import os
    import time

    from pipegoose_tpu.telemetry import FlightRecorder, PerfSentinel
    from pipegoose_tpu.testing.chaos import (
        ChaosMonkey,
        ChaosSchedule,
        Injection,
    )

    cfg, params, prompts = setup
    rec = FlightRecorder(str(tmp_path), capacity=8)
    sent = PerfSentinel(recorder=rec, window=4, min_baseline=2,
                        ratio_threshold=1.5)
    eng = ServingEngine(params, cfg, num_slots=2, num_pages=16,
                        page_size=4, max_context=32,
                        sentinel=sent, recorder=rec)

    def reqs():
        return [Request(prompt=p, max_new_tokens=4) for p in prompts[:2]]

    virtual = [0.0]

    def clock():
        virtual[0] += 1e-4
        return virtual[0]

    for _ in range(3):
        eng.run(reqs(), now=clock)
    assert sent.regressions == 0, sent.last_verdict

    monkey = ChaosMonkey(
        ChaosSchedule([Injection(2, "host_stall", (("stall_s", 0.3),))]),
        recorder=rec,
    )

    def stalling_hook(engine, tick):
        before = time.perf_counter()
        monkey.tick_hook(engine, tick)
        virtual[0] += time.perf_counter() - before

    eng.run(reqs(), now=clock, tick_hook=stalling_hook)
    assert sent.regressions == 1
    trig = rec.take_trigger()
    assert trig is not None and trig.name == "perf_regression"
    assert "idle time" in trig.reason and "baseline" in trig.reason
    assert trig.dump_path and os.path.exists(trig.dump_path)
    box = json.load(open(trig.dump_path))
    ratios = {r["component"]: r["ratio"]
              for r in box["trigger"]["details"]["regressions"]}
    # 0.3 s over three steps against a few quanta: far past any
    # threshold one would set, not just the 1.5 configured
    assert ratios["idle_s"] >= 10
    # the chaos injection is ringed next to the detection
    kinds = [r.get("kind") for r in box["records"]]
    assert "chaos.injection" in kinds


def test_engine_profile_attributes_decode_step(setup):
    """ServingEngine.profile(): measured attribution of the compiled
    decode step over the null page — components sum to the fenced wall,
    the engine adopts the donated page buffers, and serving afterwards
    stays token-identical."""
    cfg, params, prompts = setup
    eng = ServingEngine(params, cfg, num_slots=2, num_pages=16,
                        page_size=4, max_context=32)
    prof = eng.profile(steps=2)
    assert prof.source == "device_trace"
    total = prof.compute_s + prof.comm_s + prof.idle_s
    assert abs(total - prof.wall_step_s) <= 0.05 * prof.wall_step_s
    assert prof.compute_s > 0
    assert eng.last_step_profile is prof
    ref_eng = ServingEngine(params, cfg, num_slots=2, num_pages=16,
                            page_size=4, max_context=32)
    ref, _ = ref_eng.run([Request(prompt=prompts[0], max_new_tokens=4)])
    got, _ = eng.run([Request(prompt=prompts[0], max_new_tokens=4)])
    np.testing.assert_array_equal(ref[0].generated, got[0].generated)
    with pytest.raises(RuntimeError, match="profile"):
        eng.start_run([])
        try:
            eng.profile(steps=1)
        finally:
            eng.abort_run()


def test_sentinel_skips_runs_with_no_decode_steps(setup):
    """A run that decoded nothing — everything deadline-shed, or a
    prefill-only handoff run — is the degraded-but-healthy mode, not a
    perf sample: it must neither fire a spurious regression
    (tokens/s=0) nor enter the baseline."""
    from types import SimpleNamespace

    from pipegoose_tpu.telemetry import PerfSentinel

    cfg, params, _ = setup
    sent = PerfSentinel(min_baseline=1)
    eng = ServingEngine(params, cfg, num_slots=2, num_pages=8,
                        page_size=4, max_context=32, sentinel=sent)
    sent._hist.append({"tokens_per_s": 100.0, "decode_step_s": 0.01,
                       "idle_s": 0.001})
    eng._sentinel_observe(
        SimpleNamespace(steps=0, step_time=0.0, generated_total=0), 2.0)
    assert sent.regressions == 0 and sent.baseline_size == 1


# -- the tick's phase clocks, the set-up clocks and the spans (PR 26) ---------


def _requests(prompts, rows):
    return [Request(prompt=p, max_new_tokens=n)
            for p, (_, n) in zip(prompts, rows)]


def _timed_run(eng, requests):
    """Drive the steppable run, timing every ``tick_once`` from outside."""
    import time

    eng.start_run(requests)
    wall = 0.0
    while not eng.sched.all_done():
        t0 = time.perf_counter()
        eng.tick_once()
        wall += time.perf_counter() - t0
    return wall, eng.finish_run()[1]


def test_tick_phase_clocks_sum_to_the_tick_wall(setup):
    from pipegoose_tpu.serving.engine import TICK_PHASES

    cfg, params, prompts = setup
    eng = ServingEngine(params, cfg, num_slots=3, num_pages=32,
                        page_size=4, max_context=64)
    eng.run(_requests(prompts, MIXED))     # compiles land in this run
    wall, m = _timed_run(eng, _requests(prompts, MIXED))
    phases = m["tick_phase_s"]
    assert tuple(phases) == TICK_PHASES
    assert all(v >= 0.0 for v in phases.values())
    assert m["ticks"] >= m["decode_steps"] > 0
    assert sum(phases.values()) == pytest.approx(wall, rel=0.10)
    # an existing metric keeps its meaning: the decode step is dispatch
    # plus fetch (each of the three is rounded to a microsecond)
    assert phases["dispatch"] + phases["fetch"] == pytest.approx(
        m["decode_step_time_s"], abs=3e-6)
    assert m["prefills"] == len(MIXED) and phases["prefill"] > 0.0


def test_prefill_phase_is_zero_exactly_when_nothing_prefilled(setup):
    cfg, params, prompts = setup
    eng = ServingEngine(params, cfg, num_slots=2, num_pages=32,
                        page_size=4, max_context=64)
    eng.start_run([])
    assert eng.tick_once() is False        # an idle tick: admit + record
    _, m = eng.finish_run()
    assert m["prefills"] == 0 and m["ticks"] == 1
    assert m["tick_phase_s"]["prefill"] == 0.0
    assert m["tick_phase_s"]["dispatch"] == m["tick_phase_s"]["fetch"] == 0.0
    _, m = eng.run(_requests(prompts[:1], MIXED[:1]))
    assert m["prefills"] == 1 and m["tick_phase_s"]["prefill"] > 0.0


@pytest.mark.parametrize("kwargs, families", [
    ({}, {"prefill", "write", "step"}),
    ({"prefill_chunk": 8}, {"chunk", "step"}),
    # every cycle of these requests is speculative: no plain step runs
    ({"speculative": (1, 2)}, {"prefill", "write", "spec"}),
])
def test_setup_keeps_one_first_call_per_program(setup, kwargs, families):
    cfg, params, prompts = setup
    eng = ServingEngine(params, cfg, num_slots=2, num_pages=32,
                        page_size=4, max_context=64, **kwargs)
    _, m = eng.run(_requests(prompts[:4], MIXED[:4]))
    first = m["setup"]
    assert first["build_s"] > 0.0
    calls = first["first_call_s"]
    assert len(calls) == eng.programs_run
    assert {k.split("/")[0] for k in calls} == families
    assert all(v > 0.0 for v in calls.values())
    if not kwargs:
        # one prefill and one page-write program per page count
        buckets = {-(-len(p) // 4) * 4 for p in prompts[:4]}
        assert set(calls) == ({f"prefill/{b}" for b in buckets}
                              | {f"write/{b}" for b in buckets}
                              | {"step/0"})
    # engine-lifetime facts: a second run adds nothing, changes nothing
    _, again = eng.run(_requests(prompts[:4], MIXED[:4]))
    assert again["setup"] == first
    assert again["setup"] is not first


def test_tick_spans_in_order_under_their_documented_paths(setup):
    """The tick is the run of its phases: eight span paths, no parent
    that would rename ``serving.prefill`` and ``serving.decode_step``."""
    from pipegoose_tpu.telemetry import MetricsRegistry

    cfg, params, prompts = setup
    reg = MetricsRegistry(enabled=True)
    events = []
    reg.attach(events.append)
    eng = ServingEngine(params, cfg, num_slots=2, num_pages=32,
                        page_size=4, max_context=64, registry=reg)
    eng.start_run(_requests(prompts[:1], [(3, 4)]))
    eng.tick_once()                        # admits, prefills and decodes
    spans = [e["span"] for e in events if e["kind"] == "span"]
    assert spans == [
        "serving.admit", "serving.prefill", "serving.prepare",
        "serving.decode_step.upload", "serving.decode_step.dispatch",
        "serving.decode_step.fetch", "serving.decode_step",
        "serving.record"]
    del events[:]
    eng.tick_once()                        # a pure decode tick
    assert [e["span"] for e in events if e["kind"] == "span"] == [
        s for s in spans if s != "serving.prefill"]
    while not eng.sched.all_done():
        eng.tick_once()
    eng.finish_run()


# -- the tick timeline: the phase clock's boundaries, a row a tick (PR 42) ----


def _stepped_run(eng, requests):
    """Drive the steppable run, keeping ``last_tick()`` after every tick."""
    eng.start_run(requests)
    assert eng.last_tick() is None         # a run, no tick yet
    seen = []
    while not eng.sched.all_done():
        eng.tick_once()
        seen.append(eng.last_tick())
    metrics = eng.finish_run()[1]
    assert eng.last_tick() is None         # no run in progress
    return seen, metrics


def _column(timeline, name):
    i = timeline["columns"].index(name)
    return [row[i] for row in timeline["rows"]]


TIMELINE_ENGINES = [
    pytest.param({}, id="plain"),
    pytest.param({"prefill_chunk": 8}, id="chunked-prefill"),
    pytest.param({"speculative": (1, 2)}, id="speculative"),
]


@pytest.mark.parametrize("kwargs", TIMELINE_ENGINES)
def test_timeline_has_a_row_a_tick_and_sums_to_the_phase_clock(setup, kwargs):
    from pipegoose_tpu.serving.engine import TIMELINE_COLUMNS

    cfg, params, prompts = setup
    eng = ServingEngine(params, cfg, num_slots=3, num_pages=32,
                        page_size=4, max_context=64, **kwargs)
    seen, m = _stepped_run(eng, _requests(prompts, MIXED))
    tl = m["tick_timeline"]
    assert tuple(tl["columns"]) == TIMELINE_COLUMNS
    assert tl["dropped"] == 0 and len(tl["rows"]) == m["ticks"] == len(seen)
    # ``last_tick()`` is the tick's row by column, and whether its step
    # took its inputs from the host: exactly the ticks whose ``upload``
    # is not 0.0
    assert [dict(zip(tl["columns"], row), step_uploads=int(up > 0.0))
            for row, up in zip(tl["rows"], _column(tl, "upload"))] == seen
    assert sum(s["step_uploads"] for s in seen) == m["step_uploads"]
    # every column sums to its phase; upload + call is dispatch
    phases = m["tick_phase_s"]
    for name in ("admit", "prefill", "prepare", "fetch", "record"):
        assert sum(_column(tl, name)) == pytest.approx(phases[name], abs=1e-6)
    assert sum(_column(tl, "upload")) + sum(_column(tl, "call")) == \
        pytest.approx(phases["dispatch"], abs=1e-6)
    for name in TIMELINE_COLUMNS[2:]:
        assert all(v >= 0 for v in _column(tl, name)), name
    # ticks follow one another on both clocks, and do not overlap
    starts, walls = _column(tl, "t_start"), _column(tl, "t_wall_ns")
    ends = [t + sum(row[2:9]) for t, row in zip(starts, tl["rows"])]
    assert all(e <= s + 1e-9 for e, s in zip(ends, starts[1:]))
    assert walls == sorted(walls) and isinstance(walls[0], int)
    # a prefill's seconds exactly where a prefill (or a chunk) ran
    assert all((p > 0.0) == (n > 0) for p, n in
               zip(_column(tl, "prefill"), _column(tl, "prefills")))
    assert sum(_column(tl, "prefills")) == (
        m["prefill_chunks"] if "prefill_chunk" in kwargs else m["prefills"])
    # a tick with a decode step has its rows and its wait, and no other
    assert all((r > 0) == (f > 0.0) for r, f in
               zip(_column(tl, "rows"), _column(tl, "fetch")))
    assert sum(1 for r in _column(tl, "rows") if r) == m["decode_steps"]
    if "speculative" in kwargs:
        # a speculative cycle books its wall under fetch
        assert sum(_column(tl, "upload")) == sum(_column(tl, "call")) == 0.0


def test_timeline_rows_and_prefills_on_a_scripted_run(setup):
    """Three requests of 3, 2 and 4 new tokens through two slots: the
    third is admitted when the second leaves."""
    cfg, params, prompts = setup
    eng = ServingEngine(params, cfg, num_slots=2, num_pages=32,
                        page_size=4, max_context=64)
    _, m = _stepped_run(eng, [
        Request(prompt=p, max_new_tokens=n)
        for p, n in zip(prompts[:3], (3, 2, 4))])
    tl = m["tick_timeline"]
    # a prefill gives the first token; every decode step one more a row
    assert _column(tl, "prefills") == [2, 1, 0, 0]
    assert _column(tl, "rows") == [2, 2, 1, 1]
    assert _column(tl, "prefill")[2:] == [0.0, 0.0]
    assert m["generated_tokens"] == 9


def test_an_idle_tick_is_admit_and_record(setup):
    cfg, params, _ = setup
    eng = ServingEngine(params, cfg, num_slots=2, num_pages=32,
                        page_size=4, max_context=64)
    eng.start_run([])
    eng.tick_once()
    row = eng.last_tick()
    _, m = eng.finish_run()
    assert [row[k] for k in ("prefill", "prepare", "upload", "call", "fetch",
                             "rows", "prefills")] == [0.0] * 5 + [0, 0]
    assert row["admit"] > 0.0 and row["record"] > 0.0
    assert row.pop("step_uploads") == 0
    assert m["tick_timeline"]["rows"] == [list(row.values())]


def test_timeline_ring_keeps_the_newest_and_counts_the_rest(setup,
                                                            monkeypatch):
    import json

    from pipegoose_tpu.serving import engine as engine_mod

    cfg, params, prompts = setup
    eng = ServingEngine(params, cfg, num_slots=3, num_pages=32,
                        page_size=4, max_context=64)
    seen, whole = _stepped_run(eng, _requests(prompts, MIXED))
    assert len(seen) > 5
    monkeypatch.setattr(engine_mod, "TIMELINE_CAPACITY", 5)
    seen, m = _stepped_run(eng, _requests(prompts, MIXED))
    tl = m["tick_timeline"]
    assert len(tl["rows"]) == 5 and tl["dropped"] == m["ticks"] - 5 > 0
    assert tl["rows"] == [list(row.values())[:len(tl["columns"])]
                          for row in seen[-5:]]
    # the sums are kept apart from the ring: nothing of them is dropped
    assert m["decode_steps"] == whole["decode_steps"]
    assert sum(_column(tl, "fetch")) < m["tick_phase_s"]["fetch"]
    # plain lists and numbers: the metrics are serialised whole in places
    back = json.loads(json.dumps(m))["tick_timeline"]
    assert back == tl


def test_timeline_costs_a_tick_little_beside_an_empty_loop(setup):
    """What a tick pays for its row: the wall clock, the row and the
    sums, in iterations of an empty loop (~40 on the machine the bound
    was set on; two microseconds more a tick would be ~150): each batch
    timed back to back with its batch of empty iterations, so whatever
    slows the machine meets both."""
    import time

    from pipegoose_tpu.serving.engine import _RunState

    cfg, params, _ = setup
    eng = ServingEngine(params, cfg, num_slots=2, num_pages=32,
                        page_size=4, max_context=64)
    rs = _RunState(eng, time.perf_counter, None)
    n, ratios = 5_000, []
    for _ in range(15):
        t0 = time.perf_counter()
        for _ in range(n):
            rs.close_tick(time.time_ns(), time.perf_counter(), 1e-5, 0.0,
                          1e-5, 1e-5, 1e-5, 1e-3, 1e-5, 4, 0)
        t1 = time.perf_counter()
        for _ in range(n):
            pass
        ratios.append((t1 - t0) / (time.perf_counter() - t1))
    assert sorted(ratios)[len(ratios) // 2] < 120
    assert len(rs.timeline) == rs.timeline.maxlen   # the ring went round


# -- the decode step's inputs stay on the device (PR 44) ----------------------
#
# The step leaves its own next inputs on the device (a live row's pick and
# its length plus one, the page tables as they were); a tick sends the one
# packed buffer only where what ``prepare`` built differs from the host's
# copy of what the device holds. The wrong outcome is a STALE step, so the
# oracle is the tokens: those of the same engine made to send every tick,
# and those of the model's own greedy forward.

CARRY_PS, CARRY_CONTEXT = 8, 64
# (prompt length, new tokens): more requests than the three slots, so rows
# are admitted and end while others decode; a page of 8 leaves most ticks
# of three rows with nothing new
CARRY_MIX = [(25, 20), (9, 12), (17, 9), (2, 10), (12, 25), (5, 6)]


def _carry_bloom():
    cfg = bloom.BloomConfig(vocab_size=96, hidden_size=64, n_layer=2,
                            n_head=4)
    params = bloom.init_params(cfg, jax.random.PRNGKey(0))

    def check(prompt, generated):
        np.testing.assert_array_equal(
            generated, _reference(params, cfg, prompt, len(generated)))

    return cfg, params, check


def _forward_check(forward, params):
    """``generated`` is what the model's own full forward puts first
    after every prefix (the sequence right-padded, which a causal model
    does not see)."""
    best = jax.jit(lambda p, t: forward(p, t).argmax(-1))

    def check(prompt, generated):
        tokens = np.zeros((1, CARRY_CONTEXT), np.int32)
        n = len(prompt) + len(generated)
        tokens[0, :n] = np.concatenate([prompt, generated])
        got = np.asarray(best(params, jnp.asarray(tokens)))[0]
        np.testing.assert_array_equal(generated, got[len(prompt) - 1:n - 1])

    return check


def _carry_laguna():
    from pipegoose_tpu.models import laguna

    cfg = laguna.LagunaConfig(
        vocab_size=96, hidden_size=64, intermediate_size=128,
        num_hidden_layers=5, num_key_value_heads=2, head_dim=16,
        num_experts=16, num_experts_per_tok=4, moe_intermediate_size=32,
        shared_expert_intermediate_size=32, sliding_window=8,
        layer_types=(laguna.FULL,) + (laguna.SLIDING,) * 3 + (laguna.FULL,),
        num_attention_heads_per_layer=(4, 6, 6, 6, 4), experts_held=(0, 8),
        initializer_range=0.1)
    params = laguna.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params, _forward_check(
        lambda p, t: laguna.forward(p, t, cfg), params)


def _carry_falcon():
    from pipegoose_tpu.models import falcon_h1

    cfg = falcon_h1.FalconH1Config(
        vocab_size=96, hidden_size=64, intermediate_size=128,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, mamba_d_ssm=64, mamba_n_heads=4, mamba_d_head=16,
        mamba_n_groups=2, mamba_d_state=8, mamba_chunk_size=8,
        lm_head_multiplier=0.5, embedding_multiplier=3.0,
        initializer_range=0.3)
    params = falcon_h1.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params, _forward_check(
        lambda p, t: falcon_h1.forward(p, t, cfg), params)


CARRY_FAMILIES = {"one-table": _carry_bloom, "two-cache-kinds": _carry_laguna,
                  "state-bank": _carry_falcon}


@pytest.fixture(scope="module", params=sorted(CARRY_FAMILIES))
def carry_family(request):
    return CARRY_FAMILIES[request.param]()


@pytest.fixture(scope="module")
def carry_bloom():
    return _carry_bloom()


def _carry_requests():
    rng = np.random.RandomState(7)
    return [Request(prompt=rng.randint(1, 96, (s,)), max_new_tokens=n)
            for s, n in CARRY_MIX]


def _carry_engine(cfg, params, **kw):
    kw = {"num_slots": 3, "num_pages": 48, "page_size": CARRY_PS,
          "max_context": CARRY_CONTEXT, **kw}
    return ServingEngine(params, cfg, **kw)


class _StepSpy:
    """Of every ``_step`` call of a run: the rows it ran on (slot -> the
    request and its pages by kind) and whether its packed inputs came
    from the host or were the very array the step before returned."""

    def __init__(self, eng):
        from pipegoose_tpu.serving.scheduler import Status

        self.steps = []            # (rows, handed)
        self._carry = None
        step = eng._step

        def spy(params, carry, *rest):
            rows = {r.slot: (r.uid, tuple(r.pages), tuple(r.window_pages))
                    for r in eng.sched.active() if r.status is Status.DECODE}
            self.steps.append((rows, carry is not self._carry))
            out = step(params, carry, *rest)
            self._carry = out[-1]
            return out

        eng._step = spy

    @property
    def handed(self):
        return sum(h for _, h in self.steps)


def _carry_script(eng, requests, *, always_send=False, submit_at=None,
                  late=(), retract_at=None):
    """Drive the steppable run by a script: ``late`` requests submitted
    at tick ``submit_at`` (an admission mid-run); at tick ``retract_at``
    a neighbour's lazy growth retracts a decoding row inside ``prepare``
    (what ``Scheduler._alloc`` does where eviction cannot cover a
    reserved page: ``preempt`` of another active request), which is
    re-admitted and re-prefilled. ``always_send`` forgets the device's
    copy before every tick, so every step takes its inputs from the
    host. Returns (outputs in submit order, metrics, ``last_tick()`` of
    the ticks that decoded)."""
    from pipegoose_tpu.serving.scheduler import Status

    def hook(e, tick):
        if always_send:
            e._run.carry = None
        if tick == submit_at:
            for r in late:
                e.submit_request(r)

    ensure, retracted = eng.sched.ensure_page, []

    def ensure_page(req):
        if eng._run.tick == retract_at and not retracted:
            victim = next(r for r in eng.sched.active()
                          if r.status is Status.DECODE and r is not req)
            retracted.append(victim)
            eng.sched.preempt(victim)
        ensure(req)

    eng.sched.ensure_page = ensure_page
    try:
        eng.start_run(requests, tick_hook=hook)
        ticks = []
        while not eng.sched.all_done():
            eng.tick_once()
            tick = eng.last_tick()
            if tick["rows"]:
                ticks.append(tick)
        outs, metrics = eng.finish_run()
    finally:
        del eng.sched.ensure_page
    assert (retract_at is None) == (not retracted)
    return sorted(outs, key=lambda o: o.uid), metrics, ticks


def _assert_sent_where_something_changed(spy, metrics, ticks):
    """A step takes its inputs from the host exactly where its rows are
    not those of the step before (nobody admitted, ended or retracted,
    no page taken: it runs on what that step left on the device);
    ``step_uploads`` and the timeline's ``upload`` say the same."""
    assert len(spy.steps) == len(ticks) > 0
    before = None
    for (rows, handed), tick in zip(spy.steps, ticks):
        assert handed == (rows != before)
        assert tick["step_uploads"] == handed
        assert (tick["upload"] > 0.0) == handed
        before = rows
    assert metrics["step_uploads"] == spy.handed


def test_the_carry_serves_what_sending_every_tick_serves(carry_family):
    """Admitted mid-run, ended, grown over a page boundary, retracted by
    a neighbour's lazy growth and re-admitted, aborted and started
    again: token for token the always-send engine's, and the model's own
    greedy forward's."""
    from pipegoose_tpu.serving.scheduler import Status

    cfg, params, check = carry_family
    script = {"submit_at": 4, "retract_at": 9}

    def run(eng, **kw):
        reqs = _carry_requests()
        return _carry_script(eng, reqs[:2], late=reqs[2:], **script, **kw)

    eng = _carry_engine(cfg, params)
    spy = _StepSpy(eng)
    outs, metrics, ticks = run(eng)
    assert len(outs) == len(CARRY_MIX)
    for out in outs:
        check(out.prompt, out.generated)
    _assert_sent_where_something_changed(spy, metrics, ticks)
    # what the script implies: the run's first step is sent; the next
    # two are not (no admission, and 25 + 3 and 9 + 3 tokens lie in the
    # pages of 8 that 25 + 1 and 9 + 1 do); tick 4 admits a late request
    # into the free slot and its step is sent; the one after is not
    assert [t["step_uploads"] for t in ticks[:5]] == [1, 0, 0, 1, 0]
    assert [t["prefills"] for t in ticks[:5]] == [2, 0, 0, 1, 0]
    assert all(t["step_uploads"] for t in ticks if t["prefills"])
    assert metrics["prefills"] == len(CARRY_MIX) + 1    # the re-admission
    # the mechanism engaged: most ticks of three rows take no page
    plain = metrics["decode_steps"]
    assert len(CARRY_MIX) < metrics["step_uploads"] < plain // 2
    # every column still sums to its phase; upload is 0.0 where nothing
    # was sent
    tl = metrics["tick_timeline"]
    for name in ("admit", "prefill", "prepare", "fetch", "record"):
        assert sum(_column(tl, name)) == pytest.approx(
            metrics["tick_phase_s"][name], abs=1e-6)
    assert sum(_column(tl, "upload")) + sum(_column(tl, "call")) == \
        pytest.approx(metrics["tick_phase_s"]["dispatch"], abs=1e-6)
    assert sum(1 for u in _column(tl, "upload") if u > 0.0) == \
        metrics["step_uploads"]

    # the same script, every step sent
    every = _carry_engine(cfg, params)
    want, sent, _ = run(every, always_send=True)
    assert sent["step_uploads"] == sent["decode_steps"] == plain
    assert [list(o.generated) for o in outs] == \
        [list(o.generated) for o in want]

    # aborted and started again: a fresh run holds nothing, its first
    # step is sent, and the tokens are the first run's
    eng.start_run(_carry_requests()[:3])
    for _ in range(4):
        eng.tick_once()
    assert eng._run.carry is not None
    for req in list(eng.sched.active()):
        eng.sched.preempt(req)
        eng.sched.withdraw(req)
    eng.abort_run()
    assert eng.pool.used_count == 0 and not eng.run_in_progress
    spy.steps.clear()
    again, metrics2, ticks2 = run(eng)
    _assert_sent_where_something_changed(spy, metrics2, ticks2)
    assert ticks2[0]["step_uploads"] == 1
    assert metrics2["step_uploads"] == metrics["step_uploads"]
    assert [list(o.generated) for o in again] == \
        [list(o.generated) for o in outs]


def test_the_host_copy_is_the_engines_own(carry_bloom):
    """On the CPU a device array may share the host buffer it was made
    from: what is sent and the host's copy of what the device holds are
    copies of ``prepare``'s buffer, never views of it; the copy is what
    the device holds after a step."""
    cfg, params, _ = carry_bloom
    eng = _carry_engine(cfg, params)
    eng.start_run(_carry_requests()[:2])
    eng.tick_once()
    rs = eng._run
    assert rs.tokens.base is rs.packed and rs.table.base is not None
    assert not np.shares_memory(rs.held, rs.packed)
    held = rs.held.copy()
    rs.packed.fill(-1)                      # what the next prepare does
    np.testing.assert_array_equal(rs.held, held)
    np.testing.assert_array_equal(np.asarray(rs.carry), rs.held)
    eng.tick_once()                         # a step on the device's own
    assert eng.last_tick()["step_uploads"] == 0
    np.testing.assert_array_equal(np.asarray(rs.carry), rs.held)
    eng.abort_run()


def test_step_uploads_is_counted_on_the_registry(carry_bloom):
    from pipegoose_tpu.telemetry import MetricsRegistry

    cfg, params, _ = carry_bloom
    reg = MetricsRegistry(enabled=True)
    eng = _carry_engine(cfg, params, registry=reg)
    _, metrics = eng.run(_carry_requests())
    counted = reg.snapshot()["counters"]
    assert counted["serving.step_uploads"] == metrics["step_uploads"] > 0
    assert counted["serving.decode_steps_total"] == metrics["decode_steps"]


CARRY_MODES = [
    pytest.param({"speculative": (1, 2)}, id="speculative"),
    pytest.param({"prefill_chunk": 8}, id="prefill_chunk"),
    pytest.param({"prefix_cache": True}, id="prefix_cache"),
]


def _mode_requests(kwargs):
    requests = _carry_requests()
    if "prefix_cache" in kwargs:
        # a shared prefix of three pages: admissions that share pages
        for r in requests[1:]:
            r.prompt = np.concatenate([requests[0].prompt[:24], r.prompt])
            r.max_new_tokens = min(r.max_new_tokens, 10)
    return requests


@pytest.mark.parametrize("kwargs", CARRY_MODES)
def test_a_mode_that_moves_rows_outside_the_step_is_never_stale(
        carry_bloom, kwargs):
    """A speculative cycle, a chunk, a shared prefix: whatever moved a
    row between two plain steps shows as a difference between what the
    host prepared and what the device holds, and is sent."""
    cfg, params, check = carry_bloom
    eng = _carry_engine(cfg, params, **kwargs)
    spy = _StepSpy(eng)
    outs, metrics, ticks = _carry_script(eng, _mode_requests(kwargs))
    for out in outs:
        check(out.prompt, out.generated)
    want, _, _ = _carry_script(_carry_engine(cfg, params, **kwargs),
                               _mode_requests(kwargs), always_send=True)
    assert [list(o.generated) for o in outs] == \
        [list(o.generated) for o in want]
    assert metrics["step_uploads"] == spy.handed
    before = None
    for rows, handed in spy.steps:
        # never a step on stale inputs: what differs from the step
        # before is sent
        assert handed or rows == before
        before = rows
    if "speculative" in kwargs:
        # every cycle advanced its rows behind the device's copy: no
        # plain step that follows one may run on it
        assert metrics["speculative"]["draft_tokens"] > 0
        assert spy.handed == len(spy.steps)
    else:
        assert 0 < spy.handed < len(spy.steps)


class _Lowerings:
    """Counts what JAX lowers, by function (``benchmark/compile_watch.py``
    counts the same event)."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        self.names = []

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == self.EVENT:
            self.names.append(kw.get("fun_name"))

    def count(self, fn):
        return sum(1 for n in self.names if n == f"jit({fn})")


def _warm_up_and_window(eng, check):
    """The benchmark's shape: a warm-up of one request a prompt bucket,
    then a window under a script."""
    rng = np.random.RandomState(3)
    eng.run([Request(prompt=rng.randint(1, 96, (b,)), max_new_tokens=2)
             for b in (8, 16, 24, 32)])
    reqs = _carry_requests()
    outs, metrics, _ = _carry_script(eng, reqs[:4], late=reqs[4:],
                                     submit_at=4)
    for out in outs:
        check(out.prompt, out.generated)
    # both kinds of step ran: on inputs from the host, and on the
    # device's own
    assert 0 < metrics["step_uploads"] < metrics["decode_steps"]


def test_the_step_is_lowered_once_whoever_hands_it_its_inputs(carry_bloom):
    """The plain path's program count is the parent's: the step that
    takes its inputs from the host and the step that takes the step
    before's are ONE program."""
    cfg, params, check = carry_bloom
    eng = _carry_engine(cfg, params)
    with _Lowerings() as seen:
        _warm_up_and_window(eng, check)
    assert seen.count("_step") == 1
    assert seen.count("_prefill") == seen.count("_write") == 4


def test_the_step_is_lowered_once_under_a_mesh(carry_bloom, devices):
    """tp=2: the tokens are generate()'s, a quiet tick sends nothing,
    and the buffer placed on every device meets the program that the
    step's own next inputs meet."""
    cfg, params, check = carry_bloom
    ctx = ParallelContext(tensor_parallel_size=2, data_parallel_size=4)
    try:
        eng = _carry_engine(cfg, params, mesh=ctx.mesh,
                            param_specs=bloom.tp_specs(params))
        with _Lowerings() as seen:
            _warm_up_and_window(eng, check)
        assert seen.count("_step") == 1
        spy = _StepSpy(eng)
        outs, metrics, ticks = _carry_script(eng, _carry_requests())
        _assert_sent_where_something_changed(spy, metrics, ticks)
        for out in outs:
            check(out.prompt, out.generated)
        eng.doctor()
        eng.profile(steps=1, warmup=1)
        assert eng.pool.used_count == 0
    finally:
        ctx.destroy()
