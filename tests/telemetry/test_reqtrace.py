"""Request tracer units: phase attribution sums to e2e by construction,
bounded event rings, Perfetto rendering, and the disabled-path cost
guard (the engine's per-tick branch when tracing is off)."""
import threading
from types import SimpleNamespace

import pytest

from pipegoose_tpu.telemetry.registry import MetricsRegistry
from pipegoose_tpu.telemetry.reqtrace import (
    COMPONENTS,
    NULL_TRACER,
    RequestTracer,
    request_trace_events,
)


def _req(uid, prompt_len=8, max_new=4):
    return SimpleNamespace(
        uid=uid, prompt_len=prompt_len, max_new_tokens=max_new, slot=None,
        hit_tokens=0, generated=[], finish_reason=None,
    )


@pytest.fixture()
def reg():
    return MetricsRegistry(enabled=True)


def _tracer(reg, **kw):
    t = [0.0]
    tr = RequestTracer(registry=reg, clock=lambda: t[0], **kw)
    return tr, t


def test_components_are_contiguous_segments_and_sum_to_e2e(reg):
    """queue/prefill/decode/stall are lifecycle segments — their sum IS
    submit→done, exactly, including across a preemption."""
    tr, t = _tracer(reg)
    r = _req(0)
    tr.on_submit(r, 0.0)
    r.slot, r.hit_tokens = 1, 4
    tr.on_admit(r, 1.0)                      # queue = 1.0
    tr.on_prefill_chunk(r, 1.5, dur_s=0.4, tokens=4)
    tr.on_first_token(r, 2.0)                # prefill = 1.0
    tr.on_decode_tick(r, 2.5, dur_s=0.5)
    t[0] = 3.0
    tr.on_preempt(r)                         # decode += 1.0
    tr.on_admit(r, 4.0)                      # stall = 1.0
    tr.on_prefill_chunk(r, 4.5, dur_s=0.4, tokens=8)
    tr.on_resume(r, 5.0)                     # prefill += 1.0 (re-prefill)
    r.finish_reason = "length"
    tr.on_done(r, 6.0)                       # decode += 1.0
    (row,) = tr.attribution_summary()["requests"]
    assert row["components"] == {
        "queue_s": 1.0, "prefill_s": 2.0, "restore_s": 0.0,
        "transfer_s": 0.0, "decode_s": 2.0, "stall_s": 1.0,
    }
    assert row["e2e_s"] == 6.0
    assert sum(row["components"].values()) == pytest.approx(row["e2e_s"])
    # TTFT decomposes from the accumulator snapshot at the first token
    assert row["ttft_s"] == 2.0
    assert row["ttft_components"] == {
        "queue_s": 1.0, "prefill_s": 1.0, "restore_s": 0.0,
        "transfer_s": 0.0, "decode_s": 0.0, "stall_s": 0.0,
    }
    assert row["preemptions"] == 1
    # cache-savings estimate: prefill paid 2.0s for 12 forwarded tokens,
    # 4 tokens hit -> 2.0 * 4/12
    assert row["cache_saved_est_s"] == pytest.approx(2.0 * 4 / 12)


def test_attrib_histograms_observed_on_done(reg):
    tr, _ = _tracer(reg)
    for uid in range(3):
        r = _req(uid)
        tr.on_submit(r, 0.0)
        r.slot = 0
        tr.on_admit(r, 1.0)
        tr.on_first_token(r, 2.0)
        r.finish_reason = "length"
        tr.on_done(r, 3.0)
    snap = reg.snapshot()
    assert snap["counters"]["serving.attrib.requests_total"] == 3
    for c in ("queue", "prefill", "decode", "stall"):
        assert snap["histograms"][f"serving.attrib.{c}_seconds"]["count"] == 3
    assert snap["histograms"]["serving.attrib.queue_seconds"]["max"] == 1.0


def test_event_ring_is_bounded_but_attribution_stays_exact(reg):
    tr, _ = _tracer(reg, max_events=8)
    r = _req(0)
    tr.on_submit(r, 0.0)
    r.slot = 0
    tr.on_admit(r, 1.0)
    tr.on_first_token(r, 2.0)
    for i in range(100):
        tr.on_decode_tick(r, 2.0 + i * 0.01, dur_s=0.01)
    r.finish_reason = "length"
    tr.on_done(r, 10.0)
    tl = tr.snapshot()["completed"][0]
    assert len(tl["events"]) == 8
    assert tl["events_dropped"] == 104 - 8  # submit+admit+first+100+done
    assert tl["decode_ticks"] == 100          # counters, not the ring
    # the dropped submit/admit events cannot corrupt the accounting
    assert tl["components"]["queue_s"] == 1.0
    assert sum(tl["components"].values()) == pytest.approx(tl["e2e_s"])


def test_readmit_keeps_first_admissions_hit_tokens(reg):
    tr, _ = _tracer(reg)
    r = _req(0)
    tr.on_submit(r, 0.0)
    r.slot, r.hit_tokens = 0, 6
    tr.on_admit(r, 1.0)
    tr.on_preempt(r, 2.0)
    r.hit_tokens = 8          # re-admission hits more (its own tokens)
    tr.on_admit(r, 3.0)
    r.finish_reason = "length"
    tr.on_done(r, 4.0)
    (row,) = tr.attribution_summary()["requests"]
    assert row["hit_tokens"] == 6  # user-visible cache benefit: first admit


def test_perfetto_rows_per_slot_with_markers(reg):
    tr, t = _tracer(reg)
    r = _req(0)
    tr.on_submit(r, 0.0)
    r.slot, r.hit_tokens = 2, 0
    tr.on_admit(r, 1.0)
    tr.on_cow(r, 1.2)
    tr.on_prefill_chunk(r, 1.5, dur_s=0.3, tokens=8)
    tr.on_first_token(r, 2.0)
    tr.on_spec(r, 2.5, dur_s=0.5, drafted=3, accepted=1)  # a reject
    t[0] = 3.0
    tr.on_preempt(r)
    tr.on_admit(r, 4.0)
    tr.on_resume(r, 5.0)
    r.finish_reason = "eos"
    tr.on_done(r, 6.0)
    events = request_trace_events(tr)
    names = [e["name"] for e in events]
    threads = {e["args"]["name"] for e in events if e["name"] == "thread_name"}
    assert "slot 2" in threads and "queue / preempted" in threads
    markers = {e["name"] for e in events if e["ph"] == "i"}
    assert {"req0 preempt", "req0 cow", "req0 spec_reject",
            "req0 first_token"} <= markers
    slices = {e["name"]: e for e in events if e["ph"] == "X"}
    assert {"req0 queue", "req0 prefill", "req0 decode", "req0 stall",
            "req0 chunk"} <= set(slices)
    # phase slices ride the slot track; waits ride the queue track
    assert slices["req0 prefill"]["tid"] == 2
    assert slices["req0 queue"]["tid"] == slices["req0 stall"]["tid"]
    assert slices["req0 queue"]["tid"] != 2
    assert "process_name" in names


def test_in_flight_timelines_visible_and_blackbox_names_them(reg):
    tr, _ = _tracer(reg)
    stuck = _req(7)
    tr.on_submit(stuck, 0.0)
    stuck.slot = 0
    tr.on_admit(stuck, 1.0)
    done = _req(8)
    tr.on_submit(done, 0.0)
    done.slot = 1
    tr.on_admit(done, 1.0)
    tr.on_first_token(done, 2.0)
    done.finish_reason = "length"
    tr.on_done(done, 3.0)
    payload = tr.blackbox_payload()
    assert [tl["uid"] for tl in payload["in_flight"]] == [7]
    assert [tl["uid"] for tl in payload["last_completed"]] == [8]
    snap = tr.snapshot()
    assert len(snap["in_flight"]) == 1 and len(snap["completed"]) == 1
    # open phase slices still render for in-flight requests
    ev = request_trace_events(tr)
    assert any(e["name"] == "req7 prefill" and e["args"].get("open")
               for e in ev if e["ph"] == "X")


def test_completed_ring_is_bounded(reg):
    tr, _ = _tracer(reg, keep_completed=4)
    for uid in range(10):
        r = _req(uid)
        tr.on_submit(r, 0.0)
        r.slot = 0
        tr.on_admit(r, 1.0)
        r.finish_reason = "length"
        tr.on_done(r, 2.0)
    assert [tl["uid"] for tl in tr.snapshot()["completed"]] == [6, 7, 8, 9]


def test_concurrent_snapshot_while_recording(reg):
    """The ops endpoint reads while the engine thread mutates — both
    under the tracer lock; this just has to not corrupt or raise."""
    tr, _ = _tracer(reg)
    stop = threading.Event()
    errs = []

    def reader():
        while not stop.is_set():
            try:
                tr.snapshot()
            except Exception as e:  # noqa: BLE001
                errs.append(e)

    th = threading.Thread(target=reader)
    th.start()
    for uid in range(200):
        r = _req(uid)
        tr.on_submit(r, 0.0)
        r.slot = 0
        tr.on_admit(r, 1.0)
        r.finish_reason = "length"
        tr.on_done(r, 2.0)
    stop.set()
    th.join()
    assert not errs


def test_disabled_tracer_guard_under_5us(empty_iterations):
    """The engine's hot-loop contract: with ``tracer=None`` (the
    default) the per-tick tracing hook — ``ServingEngine._trace_tick``
    — is one attribute read + branch, same budget as a disabled
    registry metric (300 iterations of an empty loop:
    ``conftest.empty_iterations``). Timed on the REAL method (unbound, against a
    tracer-less stand-in) so a regression in the guard itself fails
    here."""
    from pipegoose_tpu.serving.engine import ServingEngine

    fake_engine = SimpleNamespace(tracer=None)
    active = [_req(i) for i in range(4)]

    def tick():
        ServingEngine._trace_tick(fake_engine, active, 0.0, 0.0)

    assert empty_iterations(tick) < 300
    # the NULL_TRACER fallback hooks are no-op methods with the same bound
    assert empty_iterations(
        lambda: NULL_TRACER.on_decode_tick(active[0], 0.0, 0.0)
    ) < 300


def test_validation():
    with pytest.raises(ValueError, match="max_events"):
        RequestTracer(registry=MetricsRegistry(), max_events=2)
    with pytest.raises(ValueError, match="keep_completed"):
        RequestTracer(registry=MetricsRegistry(), keep_completed=0)


def test_set_clock_reanchors_wall_offset(reg):
    tr, _ = _tracer(reg)
    off0 = tr.wall_offset
    tr.set_clock(lambda: -1000.0)
    assert tr.wall_offset != off0
    tr.set_clock(tr.clock)  # same object: no-op


# -- disagg transfer phase (serving/disagg/, ISSUE 13) ----------------------


def test_transfer_phase_is_additive_and_exact(reg):
    """The disagg lifecycle: queue -> prefill -> (first token at
    handoff) -> transfer -> decode. TTFT excludes the transfer (the
    token exists at handoff); the five components still sum to e2e
    exactly."""
    tr, t = _tracer(reg)
    r = _req(0)
    tr.on_submit(r, 0.0)
    r.slot = 0
    tr.on_admit(r, 1.0)                      # queue = 1.0
    tr.on_prefill_chunk(r, 1.5, dur_s=0.4, tokens=8)
    # streamed chunk lands DURING prefill: counters only, no transition
    tr.on_transfer_chunk(r, 1.6, dur_s=0.05, tokens=8, pages=2,
                         nbytes=4096)
    tr.on_first_token(r, 2.0)                # prefill = 1.0
    tr.on_transfer_start(r, 2.0)             # decode += 0.0
    tr.on_transfer_chunk(r, 2.5, dur_s=0.1, tokens=4, pages=1,
                         nbytes=2048)
    tr.on_transfer_done(r, 3.0)              # transfer = 1.0
    r.finish_reason = "length"
    tr.on_done(r, 5.0)                       # decode += 2.0
    (row,) = tr.attribution_summary()["requests"]
    assert row["components"] == {
        "queue_s": 1.0, "prefill_s": 1.0, "restore_s": 0.0,
        "transfer_s": 1.0, "decode_s": 2.0, "stall_s": 0.0,
    }
    assert sum(row["components"].values()) == pytest.approx(row["e2e_s"])
    assert row["ttft_s"] == 2.0              # queue + prefill, no transfer
    tl = tr.completed[-1]
    assert tl.transfer_chunks == 2
    assert tl.transfer_pages == 3
    assert tl.transfer_bytes == 4096 + 2048
    assert tl.transfer_compute_s == pytest.approx(0.15)
    # the attribution histogram saw the new component
    snap = reg.snapshot()
    assert snap["histograms"]["serving.attrib.transfer_seconds"]["count"] == 1


def test_transfer_failure_books_requeue_as_queue_time(reg):
    """The fallback path: transfer fails, the request re-submits on the
    decode pool — the post-failure wait books as queue, the sum stays
    exact."""
    tr, t = _tracer(reg)
    r = _req(1)
    tr.on_submit(r, 0.0)
    r.slot = 0
    tr.on_admit(r, 1.0)
    tr.on_first_token(r, 2.0)
    tr.on_transfer_start(r, 2.0)
    tr.on_submit(r, 3.0)                     # fallback resubmit: transfer=1
    tr.on_admit(r, 4.0)                      # queue += 1
    tr.on_resume(r, 5.0)                     # (re-)prefill = 1
    r.finish_reason = "length"
    tr.on_done(r, 6.0)                       # decode += 1
    (row,) = tr.attribution_summary()["requests"]
    assert row["components"]["transfer_s"] == 1.0
    assert row["components"]["queue_s"] == 2.0
    assert sum(row["components"].values()) == pytest.approx(row["e2e_s"])


def test_perfetto_transfer_track(reg):
    """transfer_start/chunk/done render on a dedicated transfer track
    with a named thread row."""
    tr, t = _tracer(reg)
    r = _req(2)
    tr.on_submit(r, 0.0)
    r.slot = 1
    tr.on_admit(r, 1.0)
    tr.on_first_token(r, 2.0)
    tr.on_transfer_start(r, 2.0)
    tr.on_transfer_chunk(r, 2.5, dur_s=0.1, tokens=4, pages=1,
                         nbytes=2048)
    tr.on_transfer_done(r, 3.0)
    r.finish_reason = "length"
    tr.on_done(r, 4.0)
    evs = request_trace_events(tr)
    xfer = [e for e in evs if e.get("cat") == "request.transfer"]
    assert len(xfer) == 1 and xfer[0]["tid"] == 2_000
    assert xfer[0]["dur"] == pytest.approx(1e6)      # 1 s in µs
    chunks = [e for e in evs if e.get("cat") == "request.transfer_chunk"]
    assert len(chunks) == 1 and chunks[0]["tid"] == 2_000
    assert chunks[0]["args"]["nbytes"] == 2048
    rows = [e["args"]["name"] for e in evs
            if e.get("ph") == "M" and e["name"] == "thread_name"]
    assert any(name.startswith("transfer") for name in rows)
