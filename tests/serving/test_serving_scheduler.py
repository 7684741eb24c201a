"""Continuous-batching scheduler lifecycle: FIFO admission against the
page budget, lazy page growth, eviction/reclamation, and the refill
of a slot freed mid-stream."""
import numpy as np
import pytest

from pipegoose_tpu.serving import PagePool, Request, Scheduler, Status


def _req(prompt_len, max_new, eos=None):
    return Request(
        prompt=np.arange(1, prompt_len + 1, dtype=np.int64),
        max_new_tokens=max_new, eos_token_id=eos,
    )


def test_submit_validates():
    sched = Scheduler(2, PagePool(9, 4), max_context=32)
    with pytest.raises(ValueError, match="empty prompt"):
        sched.submit(_req(0, 4), now=0.0)
    with pytest.raises(ValueError, match="max_new_tokens"):
        sched.submit(_req(4, 0), now=0.0)
    with pytest.raises(ValueError, match="context"):
        sched.submit(_req(30, 4), now=0.0)  # 34 > 32
    with pytest.raises(ValueError, match="pool only"):
        # fits max_context but not the pool: 8 allocatable pages = 32
        # slots, yet max_context bounds at 32 too -> shrink the pool
        sched2 = Scheduler(2, PagePool(5, 4), max_context=32)
        sched2.submit(_req(20, 8), now=0.0)


def test_admission_respects_worst_case_reservation():
    """Second request's WORST case (not current use) must fit before it
    is admitted, so lazy growth can never fail mid-flight."""
    pool = PagePool(9, 4)  # 8 allocatable pages
    sched = Scheduler(2, pool, max_context=32)
    sched.submit(_req(8, 16), now=0.0)   # worst case 6 pages
    sched.submit(_req(4, 8), now=0.0)    # worst case 3 pages -> 9 > 8
    admitted = sched.admit(now=1.0)
    assert [r.prompt_len for r in admitted] == [8]
    assert admitted[0].status is Status.PREFILL
    assert admitted[0].t_admit == 1.0
    # prompt pages allocated eagerly, decode pages reserved lazily
    assert len(admitted[0].pages) == 2
    assert admitted[0].outstanding == 4
    # head of line still queued: only 2 free-beyond-reservation pages
    assert len(sched.queue) == 1
    assert sched.admit(now=2.0) == []


def test_lazy_growth_and_reclamation():
    pool = PagePool(9, 4)
    sched = Scheduler(1, pool, max_context=32)
    sched.submit(_req(4, 5), now=0.0)  # worst 3 pages: 1 prompt + 2 decode
    (req,) = sched.admit(now=0.0)
    assert (len(req.pages), req.outstanding) == (1, 2)
    for step, tok in enumerate([7, 7, 7, 7, 7]):
        sched.ensure_page(req)
        sched.record_token(req, tok, now=float(step))
    # 4 prompt + 4 cached generated needed page 2 at the 5th token
    assert req.status is Status.DONE and req.finish_reason == "length"
    assert req.pages == [] and req.outstanding == 0
    assert pool.used_count == 0 and sched._outstanding_total == 0
    assert sched.all_done()


def test_eos_finishes_early_and_frees_slot():
    pool = PagePool(17, 4)
    sched = Scheduler(2, pool, max_context=32)
    sched.submit(_req(4, 8, eos=9), now=0.0)
    sched.submit(_req(4, 8), now=0.0)
    a, b = sched.admit(now=0.0)
    sched.record_token(a, 9, now=1.0)  # eos on the first token
    assert a.status is Status.DONE and a.finish_reason == "eos"
    assert sched.slots[a.slot] is None  # slot reusable mid-stream
    assert b.status is Status.PREFILL  # untouched
    assert a.t_first_token == a.t_done == 1.0


def test_freed_slot_is_refilled_mid_stream():
    """Continuous admission: a slot freed while its neighbour is still
    decoding is refilled by the very next ``admit``."""
    sched = Scheduler(2, PagePool(33, 4), max_context=32)
    reqs = [_req(4, 4, eos=5) for _ in range(3)]
    for r in reqs:
        sched.submit(r, now=0.0)
    first = sched.admit(now=0.0)
    assert [r.uid for r in first] == [0, 1]
    sched.record_token(first[0], 5, now=1.0)  # finishes, slot frees
    sched.record_token(first[1], 1, now=1.0)  # still decoding
    assert [r.uid for r in sched.admit(now=2.0)] == [2]
    assert first[1].status is not Status.DONE
    assert sorted(r.uid for r in sched.slots) == [1, 2]


def test_preempt_requeues_in_original_submit_order():
    """Preempting several requests in ANY order re-queues them by
    original submit order, ahead of never-admitted arrivals — FIFO
    determinism survives preemption patterns (a bare appendleft would
    reverse two same-tick preemptions)."""
    sched = Scheduler(2, PagePool(33, 4), max_context=32)
    a, b, c = _req(4, 4), _req(4, 4), _req(4, 4)
    for r in (a, b, c):
        sched.submit(r, now=0.0)
    admitted = sched.admit(now=0.0)           # a, b take the slots
    assert [r.uid for r in admitted] == [0, 1]
    sched.preempt(b)                 # preempt in REVERSE order
    sched.preempt(a)
    assert [r.uid for r in sched.queue] == [0, 1, 2]
    assert a.pages == [] and sched.pool.used_count == 0
    readmitted = sched.admit(now=2.0)
    assert [r.uid for r in readmitted] == [0, 1]


def test_timestamp_contract_preserved_across_preempt_readmit():
    """ISSUE 8 satellite: t_submit/t_admit/t_first_token mark the FIRST
    submission/admission/token and survive preempt -> re-admit
    untouched — queue_latency_s and ttft_s must measure the
    user-visible waits, never a requeue artifact, so the attribution
    layer can trust the fields it decomposes."""
    sched = Scheduler(1, PagePool(33, 4), max_context=32)
    r = _req(4, 8)
    sched.submit(r, now=1.0)
    sched.admit(now=2.0)
    sched.ensure_page(r)
    sched.record_token(r, 7, now=3.0)
    assert (r.t_submit, r.t_admit, r.t_first_token) == (1.0, 2.0, 3.0)
    sched.preempt(r)
    assert (r.t_submit, r.t_admit, r.t_first_token) == (1.0, 2.0, 3.0)
    (readmitted,) = sched.admit(now=9.0)
    assert readmitted is r
    assert r.t_admit == 2.0, "re-admission must not rewrite t_admit"
    assert r.t_first_token == 3.0
    # the derived latencies the engine exports from these fields
    assert r.t_admit - r.t_submit == 1.0          # queue_latency_s
    assert r.t_first_token - r.t_submit == 2.0    # ttft_s
    # record_token after resume must not move the first-token mark
    sched.ensure_page(r)
    sched.record_token(r, 8, now=10.0)
    assert r.t_first_token == 3.0


def test_tracer_hooks_fire_on_lifecycle_transitions():
    """The scheduler owns submit/admit/preempt/first-token/done, so it
    drives those tracer hooks; events arrive with the scheduler's own
    ``now`` values (one time domain)."""
    calls = []

    class SpyTracer:
        def on_submit(self, req, t):
            calls.append(("submit", req.uid, t))

        def on_admit(self, req, t):
            calls.append(("admit", req.uid, t))

        def on_preempt(self, req, t=None):
            calls.append(("preempt", req.uid, t))

        def on_first_token(self, req, t):
            calls.append(("first_token", req.uid, t))

        def on_done(self, req, t):
            calls.append(("done", req.uid, t))

    sched = Scheduler(1, PagePool(33, 4), max_context=32,
                      tracer=SpyTracer())
    r = _req(4, 2)
    sched.submit(r, now=1.0)
    sched.admit(now=2.0)
    sched.preempt(r)
    sched.admit(now=4.0)
    sched.ensure_page(r)
    sched.record_token(r, 7, now=5.0)
    sched.ensure_page(r)
    sched.record_token(r, 7, now=6.0)   # length-finishes (max_new=2)
    assert [c[0] for c in calls] == [
        "submit", "admit", "preempt", "admit", "first_token", "done",
    ]
    assert calls[0][2] == 1.0 and calls[1][2] == 2.0
    assert calls[3][2] == 4.0 and calls[4][2] == 5.0 and calls[5][2] == 6.0


def test_fifo_head_of_line_is_deterministic():
    """A small request behind a too-big head does NOT jump the queue —
    admission order is a pure function of submit order."""
    pool = PagePool(5, 4)  # 4 allocatable pages
    sched = Scheduler(2, pool, max_context=16)
    sched.submit(_req(8, 8), now=0.0)   # 4 pages: admitted
    sched.submit(_req(8, 8), now=0.0)   # 4 pages: blocked
    sched.submit(_req(1, 1), now=0.0)   # 1 page: would fit, must wait
    admitted = sched.admit(now=0.0)
    assert [r.uid for r in admitted] == [0]
    assert [r.uid for r in sched.queue] == [1, 2]


# -- deadline shedding (graceful degradation, ISSUE 9) ---------------------


def test_deadline_shed_at_admission():
    """A queued request past its ``deadline_s`` is shed at the
    admission checkpoint — terminal finish_reason="shed", drained via
    ``drain_shed`` — while in-deadline requests admit normally."""
    sched = Scheduler(2, PagePool(33, 4), max_context=32)
    stale = Request(prompt=np.arange(1, 5, dtype=np.int64),
                    max_new_tokens=4, deadline_s=0.5)
    fresh = _req(4, 4)
    sched.submit(stale, now=0.0)
    sched.submit(fresh, now=0.0)
    admitted = sched.admit(now=1.0)   # 1.0 - 0.0 > 0.5: stale expired
    assert [r.uid for r in admitted] == [fresh.uid]
    shed = sched.drain_shed()
    assert shed == [stale]
    assert stale.status is Status.DONE
    assert stale.finish_reason == "shed"
    assert stale.t_done == 1.0 and stale.generated == []
    assert sched.drain_shed() == []   # drained exactly once


def test_admitted_requests_never_shed():
    """Admission is the ONLY deadline checkpoint: an admitted request
    has paid its prefill and runs to completion even past deadline."""
    sched = Scheduler(1, PagePool(33, 4), max_context=32)
    r = Request(prompt=np.arange(1, 5, dtype=np.int64),
                max_new_tokens=2, deadline_s=0.5)
    sched.submit(r, now=0.0)
    sched.admit(now=0.1)
    assert r.status is Status.PREFILL
    sched.admit(now=99.0)             # way past deadline, already in
    assert r.status is Status.PREFILL and sched.drain_shed() == []
    sched.ensure_page(r)
    sched.record_token(r, 7, now=100.0)
    sched.ensure_page(r)
    sched.record_token(r, 7, now=101.0)
    assert r.finish_reason == "length"


def test_preempted_request_never_shed_on_readmission():
    """A preempted request is back in the queue but HAS been admitted
    (t_admit set) and holds paid-for prefill + generated tokens — the
    shed scan must skip it even past deadline, or preemption under
    memory pressure silently discards completed work."""
    sched = Scheduler(1, PagePool(33, 4), max_context=32)
    r = Request(prompt=np.arange(1, 5, dtype=np.int64),
                max_new_tokens=4, deadline_s=0.5)
    sched.submit(r, now=0.0)
    sched.admit(now=0.1)
    sched.ensure_page(r)
    sched.record_token(r, 7, now=0.2)      # paid prefill, one token out
    sched.preempt(r)
    assert r.status is Status.QUEUED and r.t_admit == 0.1
    (readmitted,) = sched.admit(now=99.0)  # way past deadline
    assert readmitted is r and sched.drain_shed() == []
    assert r.generated == [7]


def test_shed_fires_tracer_terminal_hook():
    calls = []

    class SpyTracer:
        def on_submit(self, req, t):
            calls.append(("submit", req.uid))

        def on_shed(self, req, t):
            calls.append(("shed", req.uid, t))

    sched = Scheduler(1, PagePool(33, 4), max_context=32,
                      tracer=SpyTracer())
    r = Request(prompt=np.arange(1, 5, dtype=np.int64),
                max_new_tokens=4, deadline_s=0.0)
    sched.submit(r, now=0.0)
    sched.admit(now=1.0)
    assert calls == [("submit", 0), ("shed", 0, 1.0)]


def test_negative_deadline_rejected():
    sched = Scheduler(1, PagePool(33, 4), max_context=32)
    with pytest.raises(ValueError, match="deadline_s"):
        sched.submit(Request(prompt=np.arange(1, 5, dtype=np.int64),
                             max_new_tokens=4, deadline_s=-1.0), now=0.0)


# -- disaggregated transfer ledger (serving/disagg/, ISSUE 13) --------------
#
# The pages-attached ledger case: a TRANSFER-staged request reserved
# its worst case up front, materializes pages chunk by chunk off the
# wire, and at admit_with_pages debits ONLY its unmaterialized tail —
# never a second full prefill. These pins are what keeps disagg
# admission from stranding a neighbor's reservation.


def test_begin_transfer_reserves_worst_case():
    pool = PagePool(9, 4)                     # 8 allocatable pages
    sched = Scheduler(2, pool, max_context=32)
    r = _req(8, 16)                           # worst 6 pages
    r.uid = 100                               # cross-scheduler uid
    assert sched.begin_transfer(r, now=0.0)
    snap = sched.capacity_snapshot()
    assert snap["outstanding_pages"] == 6
    assert snap["transfer_requests"] == 1
    # owed = whole target (nothing materialized) + whole decode budget
    assert snap["transfer_tokens_owed"] == 8 + 16
    # a competitor sees the reservation: worst 3 > 8 - 6 free-beyond
    sched.submit(_req(4, 8), now=0.0)
    assert not sched.can_admit(sched.queue[0])
    assert sched.admit(now=1.0) == []
    # and the ledger refuses a second transfer it cannot cover
    r2 = _req(8, 8)                           # worst 4 > 2
    r2.uid = 101
    assert not sched.begin_transfer(r2, now=0.0)
    assert sched.capacity_snapshot()["outstanding_pages"] == 6


def test_transfer_pages_materializes_and_owed_shrinks_to_tail():
    pool = PagePool(17, 4)
    sched = Scheduler(2, pool, max_context=64)
    r = _req(16, 8)                           # 4 prompt pages + 2 decode
    r.uid = 7
    assert sched.begin_transfer(r, now=0.0)
    pages = sched.transfer_pages(r, 8)        # first shipment: 2 pages
    assert len(pages) == 2
    snap = sched.capacity_snapshot()
    # the request object is untouched — it may still be live on the
    # prefill scheduler while pages stream (the whole point)
    assert r.pages == [] and r.status is Status.QUEUED
    # owed: unmaterialized tail (16 - 8) + decode budget only
    assert snap["transfer_tokens_owed"] == 8 + 8
    # 2 of the 6 reserved pages materialized: 4 still outstanding
    assert snap["outstanding_pages"] == 4
    pages = sched.transfer_pages(r, 16)       # rest of the prompt
    assert len(pages) == 4
    assert sched.capacity_snapshot()["transfer_tokens_owed"] == 8


def test_admit_with_pages_skips_prefill_and_debits_only_tail():
    pool = PagePool(17, 4)
    sched = Scheduler(2, pool, max_context=64)
    r = _req(16, 8)
    r.uid = 7
    assert sched.begin_transfer(r, now=0.0)
    sched.transfer_pages(r, 16)
    r.status = Status.TRANSFER                # finish_handoff marked it
    assert sched.admit_with_pages(r, first_token=9, now=2.0)
    assert r.status is Status.DECODE
    assert r.generated == [9]
    assert r.prefilled_len == 16              # the whole prompt: no prefill
    assert len(r.pages) == 4
    assert r.outstanding == 2                 # ONLY the decode tail
    snap = sched.capacity_snapshot()
    assert snap["transfer_requests"] == 0
    assert snap["outstanding_pages"] == 2
    assert r.t_admit == 2.0
    # decode proceeds exactly like a locally prefilled request
    for t in range(7):
        sched.ensure_page(r)
        sched.record_token(r, 7, now=3.0 + t)
    assert r.status is Status.DONE
    assert pool.used_count == 0               # everything reclaimed
    assert sched.capacity_snapshot()["outstanding_pages"] == 0


def test_admit_with_pages_needs_handoff_and_free_slot():
    pool = PagePool(17, 4)
    sched = Scheduler(1, pool, max_context=64)
    r = _req(8, 4)
    r.uid = 1
    assert sched.begin_transfer(r, now=0.0)
    sched.transfer_pages(r, 8)
    with pytest.raises(ValueError, match="handed-off"):
        sched.admit_with_pages(r, 9, now=1.0)  # still QUEUED elsewhere
    r.status = Status.TRANSFER
    blocker = _req(4, 4)
    sched.submit(blocker, now=0.0)
    sched.admit(now=0.5)                      # takes the only slot
    assert not sched.admit_with_pages(r, 9, now=1.0)
    assert r.uid in sched.transfers           # stage intact, retry later
    for t in range(4):
        sched.ensure_page(blocker)
        sched.record_token(blocker, 7, now=1.0 + t)
    assert sched.admit_with_pages(r, 9, now=6.0)


def test_abort_transfer_restores_ledger_and_pages():
    pool = PagePool(17, 4)
    sched = Scheduler(2, pool, max_context=64)
    r = _req(16, 8)
    r.uid = 3
    free0 = pool.free_count
    assert sched.begin_transfer(r, now=0.0)
    sched.transfer_pages(r, 12)
    assert pool.free_count == free0 - 3
    sched.abort_transfer(r)
    assert pool.free_count == free0
    assert sched.capacity_snapshot()["outstanding_pages"] == 0
    assert sched.capacity_snapshot()["transfer_requests"] == 0
    with pytest.raises(ValueError, match="not staged"):
        sched.abort_transfer(r)


def test_prefill_only_ledger_reserves_prompt_not_decode():
    """The prefill pool's side of the same satellite: a pool that
    never decodes must not reserve decode pages — a request whose
    prompt fits admits even when prompt + max_new would not."""
    pool = PagePool(5, 4)                     # 4 allocatable pages
    sched = Scheduler(2, pool, max_context=16, prefill_only=True,
                      chunk_tokens=8)
    r = _req(16, 64)                          # prompt 4 pages; decode huge
    sched.submit(r, now=0.0)                  # fits: worst = prompt only
    (admitted,) = sched.admit(now=0.0)
    assert admitted is r
    snap = sched.capacity_snapshot()
    # owed tokens: the prefill target only, no decode budget
    assert snap["active_tokens_remaining"] == 0
    plain = Scheduler(2, PagePool(5, 4), max_context=96)
    with pytest.raises(ValueError, match="pool only"):
        plain.submit(_req(16, 64), now=0.0)


def test_submit_reuse_uid_preserves_cross_scheduler_identity():
    sched = Scheduler(1, PagePool(9, 4), max_context=32)
    r = _req(4, 4)
    r.uid = 41                                # foreign-scheduler uid
    sched.submit(r, now=0.0, reuse_uid=True)
    assert r.uid == 41
    fresh = _req(4, 4)
    sched.submit(fresh, now=0.0)
    # the local counter does NOT chase a reused uid: cross-scheduler
    # uniqueness is the caller's (disagg: one prefill counter; control
    # plane: disjoint UID_STRIDE blocks per replica) — chasing would
    # leak this counter into another replica's block
    assert fresh.uid == 0



# -- ledger consistency after an aborted run (ISSUE 15 satellite) -----------


def _assert_ledger_balanced(sched, pool, free0):
    snap = sched.capacity_snapshot()
    assert snap["outstanding_pages"] == 0, snap
    assert snap["transfer_requests"] == 0, snap
    assert snap["transfer_tokens_owed"] == 0, snap
    assert snap["queued_requests"] == 0 and snap["active_requests"] == 0
    assert pool.free_count == free0, (pool.free_count, free0)


def test_ledger_balances_after_abort_mid_transfer_staging_decode():
    """A decode scheduler abandoned mid-transfer-staging (the pool-
    death path: abort_transfer on the incomplete stage, preempt +
    withdraw the rest) ends with a balanced ledger: no stranded
    reservations, no transfer records, every page back."""
    pool = PagePool(17, 4)
    sched = Scheduler(2, pool, max_context=64)
    free0 = pool.free_count
    live = _req(8, 8)                         # a normally admitted peer
    sched.submit(live, now=0.0)
    sched.admit(now=0.0)
    staged = _req(16, 8)
    staged.uid = 100
    staged.status = Status.TRANSFER
    assert sched.begin_transfer(staged, now=1.0)
    sched.transfer_pages(staged, 8)           # 2 pages materialized
    snap = sched.capacity_snapshot()
    assert snap["transfer_requests"] == 1 and snap["outstanding_pages"] > 0
    # the aborted-run teardown: transfer staging aborted, live work
    # preempted + withdrawn (exactly what crash salvage does)
    sched.abort_transfer(staged)
    sched.preempt(live)
    sched.withdraw(live)
    _assert_ledger_balanced(sched, pool, free0)


def test_ledger_balances_after_abort_mid_prefill_prefill_only():
    """The prefill-only twin: a prefill pool abandoned mid-chunk (some
    prompt pages allocated, reservation outstanding) balances after
    preempt + withdraw — the pool-death harvest path."""
    pool = PagePool(9, 4)
    sched = Scheduler(2, pool, max_context=32, prefill_only=True,
                      chunk_tokens=4)
    free0 = pool.free_count
    a, b = _req(12, 4), _req(8, 4)
    sched.submit(a, now=0.0)
    sched.submit(b, now=0.0)
    sched.admit(now=0.0)
    sched.ensure_pages(a, 8)                  # mid-prefill: 2 of 3 pages
    assert sched.capacity_snapshot()["outstanding_pages"] > 0
    for r in (a, b):
        sched.preempt(r)
        sched.withdraw(r)
    _assert_ledger_balanced(sched, pool, free0)
    # the harvested requests are re-submittable elsewhere
    other = Scheduler(2, PagePool(9, 4), max_context=32,
                      prefill_only=True, chunk_tokens=4)
    other.submit(a, now=9.0, reuse_uid=True)
    assert a.uid is not None and a.status is Status.QUEUED
