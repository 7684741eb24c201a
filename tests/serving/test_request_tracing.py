"""Engine-level request tracing (ISSUE 8): the attribution contract
(components sum to measured e2e), TTFT observed EXACTLY once per
request across preempt→re-admit, the scheduler timestamp contract the
attribution trusts, stall black boxes naming the stuck request, and the
attribution summary of a traced replay with and without the cache."""
import json
import os

import jax
import numpy as np
import pytest

from pipegoose_tpu.models import bloom
from pipegoose_tpu.serving import (
    Request,
    ServingEngine,
    Status,
    make_skewed_replay,
)
from pipegoose_tpu.telemetry import MetricsRegistry, RequestTracer

MIXED = [(3, 5), (9, 12), (17, 4), (5, 9)]


@pytest.fixture(scope="module")
def setup():
    cfg = bloom.BloomConfig(vocab_size=64, hidden_size=64, n_layer=2, n_head=4)
    params = bloom.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(7)
    prompts = [rng.randint(1, 64, (s,)) for s, _ in MIXED]
    return cfg, params, prompts


def test_components_sum_to_e2e_and_match_request_outputs(setup):
    """ISSUE 8 acceptance: for every request the exported latency
    components sum to its measured e2e within 1%, and the tracer's
    ttft/e2e agree with RequestOutput's own fields."""
    cfg, params, prompts = setup
    reg = MetricsRegistry(enabled=True)
    tracer = RequestTracer(registry=reg)
    eng = ServingEngine(params, cfg, num_slots=3, num_pages=32,
                        page_size=4, max_context=64, registry=reg,
                        tracer=tracer)
    outs, _ = eng.run([
        Request(prompt=p, max_new_tokens=n)
        for p, (_, n) in zip(prompts, MIXED)
    ])
    summary = tracer.attribution_summary()
    assert summary["n"] == len(MIXED)
    by_uid = {r["uid"]: r for r in summary["requests"]}
    for o in outs:
        row = by_uid[o.uid]
        total = sum(row["components"].values())
        assert total == pytest.approx(row["e2e_s"], rel=0.01)
        assert row["e2e_s"] == pytest.approx(o.e2e_latency_s, rel=0.01)
        assert row["ttft_s"] == pytest.approx(o.ttft_s, rel=0.01)
        assert row["components"]["queue_s"] == pytest.approx(
            o.queue_latency_s, abs=1e-6)
        # TTFT decomposes into the pre-first-token components
        ttft_sum = sum(row["ttft_components"].values())
        assert ttft_sum == pytest.approx(row["ttft_s"], rel=0.01)
    snap = reg.snapshot()
    attrib = snap["histograms"]
    for c in ("queue", "prefill", "decode", "stall"):
        assert attrib[f"serving.attrib.{c}_seconds"]["count"] == len(MIXED)
    assert snap["counters"]["serving.attrib.requests_total"] == len(MIXED)


def test_ttft_observed_exactly_once_across_preempt_and_readmit(setup):
    """ISSUE 8 satellite: a request that is preempted mid-decode and
    re-admitted re-enters the prefill path with its t_first_token
    already set — the TTFT histogram must still see EXACTLY one
    observation per request, and its value must use the ORIGINAL
    submit→first-token wait (t_admit/t_first_token preservation)."""
    cfg, params, prompts = setup
    shared = np.arange(1, 14)
    reg = MetricsRegistry(enabled=True)
    tracer = RequestTracer(registry=reg)
    eng = ServingEngine(params, cfg, num_slots=2, num_pages=32,
                        page_size=4, max_context=64, prefix_cache=True,
                        prefill_chunk=8, registry=reg, tracer=tracer)
    warm_outs, _ = eng.run([Request(prompt=shared, max_new_tokens=4)])
    n_warm = reg.snapshot()["histograms"]["serving.ttft_seconds"]["count"]
    assert n_warm == 1

    state = {"preempts": 0}

    def preempt_once(engine, tick):
        if state["preempts"]:
            return
        for r in engine.sched.active():
            if r.status is Status.DECODE and len(r.generated) >= 2:
                engine.sched.preempt(r)
                state["preempts"] += 1
                return

    outs, _ = eng.run([Request(prompt=shared, max_new_tokens=8)],
                      tick_hook=preempt_once)
    assert state["preempts"] == 1, "request was never preempted"
    h = reg.snapshot()["histograms"]["serving.ttft_seconds"]
    assert h["count"] == n_warm + 1          # exactly once, not twice
    # the two observations are exactly the two requests' own
    # (original-submit) TTFTs — preservation, not a requeue artifact
    expect = sorted([warm_outs[0].ttft_s, outs[0].ttft_s])
    assert h["min"] == pytest.approx(expect[0], rel=1e-6)
    assert h["max"] == pytest.approx(expect[1], rel=1e-6)
    (row,) = [r for r in tracer.attribution_summary()["requests"]
              if r["uid"] == outs[0].uid]
    assert row["preemptions"] == 1
    assert row["components"]["stall_s"] > 0.0
    assert sum(row["components"].values()) == pytest.approx(
        row["e2e_s"], rel=0.01)
    # queue_latency_s still measures the FIRST wait (t_admit preserved):
    # it must equal the tracer's pre-preemption queue component, not
    # include the requeue wait booked under stall_s
    assert row["components"]["queue_s"] == pytest.approx(
        outs[0].queue_latency_s, abs=1e-6)


def test_preempt_during_prefill_still_observes_ttft_once(setup):
    """Preemption BEFORE the first token: the re-admission re-prefills
    from scratch and the single TTFT lands at the eventual first token
    (ttft_s spans the preemption — the user-visible wait)."""
    cfg, params, prompts = setup
    long_prompt = np.arange(1, 25)
    reg = MetricsRegistry(enabled=True)
    eng = ServingEngine(params, cfg, num_slots=2, num_pages=32,
                        page_size=4, max_context=64, prefix_cache=True,
                        prefill_chunk=8, registry=reg)

    state = {"preempts": 0}

    def preempt_in_prefill(engine, tick):
        if state["preempts"]:
            return
        for r in engine.sched.active():
            if r.status is Status.PREFILL and r.prefilled_len >= 8:
                engine.sched.preempt(r)
                state["preempts"] += 1
                return

    outs, _ = eng.run([Request(prompt=long_prompt, max_new_tokens=4)],
                      tick_hook=preempt_in_prefill)
    assert state["preempts"] == 1, "request was never preempted in prefill"
    h = reg.snapshot()["histograms"]["serving.ttft_seconds"]
    assert h["count"] == 1
    assert h["max"] == pytest.approx(outs[0].ttft_s, rel=0.01)


def test_stall_blackbox_names_the_stuck_request(setup, tmp_path):
    """The flight-recorder integration: a decode_stall dump embeds the
    tracer's timelines, so the post-mortem names WHICH request is stuck
    and in which phase."""
    from pipegoose_tpu.telemetry import FlightRecorder

    cfg, params, prompts = setup
    rec = FlightRecorder(str(tmp_path), capacity=8)
    tracer = RequestTracer(registry=MetricsRegistry(enabled=True))
    eng = ServingEngine(params, cfg, num_slots=2, num_pages=8,
                        page_size=4, max_context=32, recorder=rec,
                        stall_patience=5, tracer=tracer)
    eng.pool.alloc(eng.pool.free_count - 1)   # strand the pool
    with pytest.raises(RuntimeError, match="decode stall"):
        eng.run([Request(prompt=prompts[0], max_new_tokens=4)])
    trig = rec.take_trigger()
    assert trig is not None and trig.dump_path
    data = json.load(open(trig.dump_path))
    timelines = data["request_timelines"]
    (stuck,) = timelines["in_flight"]
    assert stuck["uid"] == 0
    assert stuck["phase"] == "queue"          # never admitted: queued
    assert stuck["events"][0]["kind"] == "submit"


def test_traced_replay_attribution_explains_cache_win(setup):
    """ISSUE 8 acceptance on one skewed replay through an engine without
    and one with the prefix cache, each traced on its second, warm run —
    per request, components sum to e2e within 1%; the cached engine's
    cache-savings share ≈ the measured prefill-token reduction (both
    count the same hit tokens), which is what accounts for its TTFT win
    on prefill-bound workloads."""
    cfg, params, _ = setup
    replay = make_skewed_replay(
        n_requests=6, n_prefixes=2, prefix_len=16, suffix_lens=(2, 4),
        max_new=3, vocab=cfg.vocab_size,
    )

    def traced_run(prefix_cache):
        eng = ServingEngine(params, cfg, num_slots=2, num_pages=33,
                            page_size=8, max_context=64, prefill_chunk=16,
                            prefix_cache=prefix_cache)
        reqs = lambda: [Request(prompt=p, max_new_tokens=n)  # noqa: E731
                        for p, n in replay]
        eng.run(reqs())   # compiles, and seeds the cache where there is one
        tracer = RequestTracer(registry=eng.registry, keep_completed=6)
        eng.attach_tracer(tracer)
        _, metrics = eng.run(reqs())
        return tracer.attribution_summary(), metrics

    (plain, plain_m), (cached, cached_m) = traced_run(False), traced_run(True)
    for label, arm in (("plain", plain), ("cached", cached)):
        assert arm["n"] == 6, label
        for row in arm["requests"]:
            total = sum(row["components"].values())
            assert total == pytest.approx(row["e2e_s"], rel=0.01), (
                f"{label} uid={row['uid']}: components {row['components']} "
                f"don't sum to e2e {row['e2e_s']}"
            )
    # without a cache every prompt token is forwarded; with one, the hit
    # share must equal the measured prefill-token reduction
    assert plain["cache_hit_share"] == 0.0
    reduction = 1.0 - cached_m["prefill_tokens"] / plain_m["prefill_tokens"]
    assert cached["cache_hit_share"] == pytest.approx(reduction, abs=0.02)
    assert cached["cache_hit_share"] > 0.3     # the workload does share
    assert cached["mean_cache_saved_est_s"] >= 0.0


def test_tracer_off_is_token_identical(setup):
    """Zero-overhead contract: the tracer must be invisible in the
    tokens — same engine config with and without tracing produces
    byte-identical outputs."""
    cfg, params, prompts = setup
    reqs = lambda: [Request(prompt=p, max_new_tokens=n)  # noqa: E731
                    for p, (_, n) in zip(prompts, MIXED)]
    plain = ServingEngine(params, cfg, num_slots=3, num_pages=32,
                          page_size=4, max_context=64)
    traced = ServingEngine(params, cfg, num_slots=3, num_pages=32,
                           page_size=4, max_context=64,
                           tracer=RequestTracer(
                               registry=MetricsRegistry(enabled=True)))
    outs_a, _ = plain.run(reqs())
    outs_b, _ = traced.run(reqs())
    for a, b in zip(outs_a, outs_b):
        np.testing.assert_array_equal(a.generated, b.generated)
        assert a.finish_reason == b.finish_reason


# -- deadline shedding (graceful degradation, ISSUE 9) ---------------------


def test_deadline_shed_counter_output_and_tracer_contract(setup):
    """The shed contract end to end: a queued request past deadline
    terminates with finish_reason="shed", rides ``serving.shed_total``
    (against ``serving.requests_total`` — the SLO shed-fraction ratio),
    completes its tracer timeline with a ``shed`` terminal event, and
    stays OUT of the served-latency histograms."""
    cfg, params, prompts = setup
    reg = MetricsRegistry(enabled=True)
    tracer = RequestTracer(registry=reg)
    eng = ServingEngine(params, cfg, num_slots=2, num_pages=32,
                        page_size=4, max_context=64, registry=reg,
                        tracer=tracer)
    served = Request(prompt=prompts[0], max_new_tokens=4)
    stale = Request(prompt=prompts[1], max_new_tokens=4, deadline_s=0.0)
    outs, metrics = eng.run([served, stale])

    assert reg.counter("serving.requests_total").value == 2
    assert reg.counter("serving.shed_total").value == 1
    assert metrics["shed_requests"] == 1
    by_reason = {o.finish_reason: o for o in outs}
    shed_out = by_reason["shed"]
    assert shed_out.uid == stale.uid
    assert list(shed_out.generated) == []
    # never served: None (matching per_request), NOT 0.0 — a zero would
    # read as an instant first token in any unfiltered aggregation
    assert shed_out.ttft_s is None and shed_out.decode_tokens_per_s is None
    assert shed_out.e2e_latency_s == shed_out.queue_latency_s > 0
    # the served request is untouched by its neighbor's shedding
    assert len(by_reason["length"].generated) == 4

    # tracer: terminal `shed` event, finish reason on the timeline,
    # and the served-latency histograms only saw the SERVED request
    tl = {t.uid: t for t in tracer.completed}[stale.uid]
    assert tl.finish_reason == "shed"
    assert [e["kind"] for e in tl.events][-1] == "shed"
    assert reg.histogram("serving.ttft_seconds")._count == 1
    assert reg.histogram("serving.e2e_latency_seconds")._count == 1


def test_all_requests_shed_is_not_a_stall(setup):
    """Shedding IS progress (the queue shrank): a run whose every
    request sheds must terminate cleanly — no stall-watchdog trigger,
    no livelock — and /healthz semantics follow (shedding never fires
    a flight-recorder trigger, so health stays 200)."""
    from pipegoose_tpu.telemetry import FlightRecorder

    cfg, params, prompts = setup
    recorder = FlightRecorder("/tmp/unused_bb_shed", capacity=8)
    eng = ServingEngine(params, cfg, num_slots=2, num_pages=32,
                        page_size=4, max_context=64, recorder=recorder,
                        stall_patience=3)
    outs, metrics = eng.run([
        Request(prompt=p, max_new_tokens=4, deadline_s=0.0)
        for p in prompts[:3]
    ])
    assert [o.finish_reason for o in outs] == ["shed"] * 3
    assert metrics["shed_requests"] == 3
    # the degraded-but-healthy contract: no trigger fired, no dump
    assert recorder.last_trigger is None and recorder.dumps == []
