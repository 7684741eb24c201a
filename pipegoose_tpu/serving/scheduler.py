"""Continuous-batching scheduler: request lifecycle + slot/page admission.

The request lifecycle is QUEUED -> PREFILL -> DECODE -> DONE. A fixed
number of decode SLOTS bounds the jitted step's batch dim (static
shapes); the scheduler's job is to keep those slots full:

- **admission** pops the FIFO queue into free slots whenever the page
  pool can cover the candidate's WORST-CASE footprint
  (``ceil((prompt + max_new) / page_size)``) on top of every active
  request's outstanding reservation. Pages are then allocated LAZILY —
  the first prefill chunk's pages at admission (the WHOLE prompt's when
  chunked prefill is off: one monolithic chunk), decode pages one at a
  time as the write position crosses a page boundary — so
  short-finishing requests never hold their worst case, while the
  reservation arithmetic guarantees a lazy ``alloc`` can never fail
  mid-flight. Head-of-line blocking is deliberate: FIFO admission keeps
  the schedule deterministic.
- **prefix caching** (``prefix_cache=PrefixCache(pool)``) short-cuts
  admission: the longest page-aligned cached prefix of the prompt is
  SHARED (refcount bump, no alloc, no prefill) and only the unique tail
  is prefilled. The admission ledger then counts
  ``free + cache-evictable`` as capacity and debits pages the hit pins
  (refcount 1 -> 2), so a reservation made when a page looked evictable
  can never be stranded by a later hit; ``_alloc`` evicts
  least-recently-used unpinned cache pages on demand.
- **cache kinds**: a pool with a ``window`` kind (``PagePool.kinds``;
  a model whose sliding-window layers keep a ring of pages,
  serving/blocks.py) is accounted kind by kind: admission reserves and
  allocates ``pages_for(.., kind)`` of each, lazy growth fills the ring
  until it is whole (after which a new logical page takes over the
  oldest entry: ``PagePool.recycled``), finish and preemption return
  both kinds.
- **a state a slot** (a model whose layers keep a recurrence's state
  beside their keys and values, serving/blocks.py): the state bank has
  a row a SLOT, so the scheduler allocates nothing for it. What it owes
  the state is its order: ``admit`` fills the LOWEST free slot (the
  decode step walks the bank only as far as the highest live one), an
  admitted request is prefilled before its first decode step (the
  engine puts the prefill's state in the slot's row, so a slot's
  leftover state is never read), and :meth:`Scheduler.preempt` keeps
  the generated tokens, from which re-admission re-prefills: no state
  is saved.
- **eviction** frees a finished request's pages and reservation the
  step its last token is emitted — shared pages just drop a reference —
  so the next ``admit`` can re-use both the slot and the pages
  mid-stream (continuous batching). :meth:`Scheduler.preempt` is the
  mid-flight variant: a live request's pages all go back (cache-shared
  ones survive in the cache) and the request re-queues at the HEAD;
  re-admission re-prefills ``prompt + generated[:-1]`` (hitting the
  cache for the shared prefix) and resumes decoding with the last
  generated token pending — token-for-token identical to an
  uninterrupted run.
"""
from __future__ import annotations

import enum
from collections import deque
from dataclasses import dataclass, field
from typing import List, Optional, Tuple

import numpy as np

from pipegoose_tpu.serving.kv_pool import PagePool


class Status(enum.Enum):
    QUEUED = "queued"
    PREFILL = "prefill"
    DECODE = "decode"
    # disaggregated serving (serving/disagg/): the request's KV pages
    # are in flight between pools — left the prefill scheduler via
    # finish_handoff, staged on the decode scheduler via begin_transfer
    TRANSFER = "transfer"
    DONE = "done"


@dataclass
class Request:
    """One generation request. Engine/scheduler fill the lifecycle
    fields; callers provide the first three."""

    prompt: np.ndarray                 # (S,) token ids
    max_new_tokens: int
    eos_token_id: Optional[int] = None
    # graceful degradation: seconds from submit after which the request
    # is SHED at admission time instead of admitted (the answer would
    # arrive too late to matter, so spending prefill+decode on it only
    # makes every other request later). None = never shed.
    deadline_s: Optional[float] = None
    # multi-tenant identity: who this request belongs to. The scheduler
    # itself is tenant-blind; the control plane's fair-share ledger
    # (serving/control_plane/tenants.py) keys on it, the tracer carries
    # it through timeline events, and per-request metric dicts report it.
    tenant: Optional[str] = None

    uid: Optional[int] = None
    # fleet-trace identity (telemetry/fleettrace.py): minted once at
    # ControlPlane.submit ingress and carried by THIS object through
    # every dispatch, drain migration, crash salvage, disagg handoff
    # and kv-tier pull — uids are replica-local (and reused by design
    # on salvage), so the trace_id is the only safe cross-replica join
    # key. None for requests that never crossed a control plane.
    # Deliberately NOT scrubbed by clear_residency(): identity, like
    # timestamps, survives the degraded salvage path.
    trace_id: Optional[int] = None
    status: Status = Status.QUEUED
    generated: List[int] = field(default_factory=list)
    slot: Optional[int] = None
    pages: List[int] = field(default_factory=list)
    # the window kind's ring (pools that have one): entry r holds the
    # newest logical page j with j % ring == r
    window_pages: List[int] = field(default_factory=list)
    window_logical: int = 0            # logical pages the ring has held
    outstanding: int = 0               # worst-case pages not yet allocated
    prefilled_len: int = 0             # tokens whose KV is in pages + forwarded
    hit_tokens: int = 0                # of those, tokens served by the cache
    cow: Optional[Tuple[int, int]] = None  # (src page, valid tokens) pending copy
    finish_reason: Optional[str] = None
    # timestamp contract (attribution depends on it): t_submit and
    # t_admit mark the FIRST submission/admission and survive
    # preempt -> re-admit untouched, as does t_first_token — so
    # queue_latency_s and ttft_s always measure the user-visible waits,
    # never a requeue artifact. ttft_observed dedupes the engine's TTFT
    # histogram observation (exactly once per request, whichever
    # prefill path(s) the request crosses).
    t_submit: Optional[float] = None
    t_admit: Optional[float] = None
    t_first_token: Optional[float] = None
    t_done: Optional[float] = None
    ttft_observed: bool = False

    def clear_residency(self) -> None:
        """Scrub the scheduler-residency fields (slot, pages,
        reservation, COW, prefill progress) WITHOUT touching identity,
        tokens, or timestamps — the crash-salvage paths' best-effort
        reset before re-submitting a request harvested off a broken
        scheduler onto a healthy one (the normal lifecycle resets these
        through preempt/admit; this is for when those paths raised)."""
        self.slot = None
        self.pages = []
        self.window_pages = []
        self.window_logical = 0
        self.outstanding = 0
        self.cow = None
        self.prefilled_len = self.hit_tokens = 0

    @property
    def prompt_len(self) -> int:
        return int(np.asarray(self.prompt).shape[0])

    @property
    def cached_len(self) -> int:
        """Tokens currently in the KV pages: the whole prompt plus every
        generated token except the pending one (the decode step writes
        the pending token before attending)."""
        return self.prompt_len + max(len(self.generated) - 1, 0)

    @property
    def target_len(self) -> int:
        """Tokens a (re-)prefill must put in the pages before decoding
        can resume: the prompt, plus — after a preemption — every
        generated token except the pending last one. Equals
        ``cached_len`` by construction; named separately because during
        PREFILL it is the goal, not the state."""
        return self.cached_len

    @property
    def tokens(self) -> np.ndarray:
        return np.concatenate(
            [np.asarray(self.prompt, np.int64),
             np.asarray(self.generated, np.int64)]
        )


class Scheduler:
    def __init__(self, num_slots: int, pool: PagePool, max_context: int,
                 prefix_cache=None, chunk_tokens: Optional[int] = None,
                 tracer=None, prefill_only: bool = False):
        if num_slots < 1:
            raise ValueError("need at least one decode slot")
        if chunk_tokens is not None and (
                chunk_tokens < pool.page_size or chunk_tokens % pool.page_size):
            raise ValueError(
                f"chunk_tokens={chunk_tokens} must be a positive multiple "
                f"of page_size={pool.page_size} (chunks end on page "
                f"boundaries so every chunk's pages exist before it runs)"
            )
        self.num_slots = num_slots
        self.pool = pool
        self.max_context = max_context
        self.cache = prefix_cache
        self.chunk_tokens = chunk_tokens
        # disaggregated prefill pool (serving/disagg/): requests here
        # only ever hold their PROMPT's pages — they hand off to a
        # decode pool at prefill completion instead of decoding — so
        # the admission ledger reserves pages_for(prompt) rather than
        # pages_for(prompt + max_new). Reserving the decode worst case
        # on a pool that never decodes would throttle prefill admission
        # by pages nobody here will ever write.
        self.prefill_only = prefill_only
        # request-lifecycle observer (telemetry/reqtrace.py): the
        # scheduler owns the lifecycle transitions, so it drives the
        # tracer's submit/admit/preempt/first-token/done hooks; None
        # (the default) costs one attribute read + branch per event
        self.tracer = tracer
        self.slots: List[Optional[Request]] = [None] * num_slots
        self.queue: deque = deque()
        # deadline-shed requests since the last drain_shed() — the
        # engine drains these per tick to count them and emit outputs
        self.shed: List[Request] = []
        # inbound cross-pool transfers staged via begin_transfer,
        # uid -> {"req", "pages", "outstanding", "tokens"}: pages
        # materialize here chunk by chunk until admit_with_pages binds
        # the request to a slot (serving/disagg/). Scheduler-side
        # records, NOT request fields — the request may still be live
        # on its prefill scheduler while pages stream.
        self.transfers: dict = {}
        self._outstanding_total = 0
        self._next_uid = 0
        # lifetime count of admissions deferred by the memory ledger's
        # worst-case check (the head didn't fit): the goodput ledger
        # reads the per-tick delta to book a no-progress tick as
        # admission-blocked wall rather than a stall (always on — one
        # int increment on a path that just did pool arithmetic)
        self.admission_deferrals = 0

    def _worst_tokens(self, req: Request) -> int:
        """Tokens the admission ledger reserves pages for: the decode
        worst case, or just the prompt on a prefill-only pool."""
        if self.prefill_only:
            return req.prompt_len
        return req.prompt_len + req.max_new_tokens

    # -- lifecycle ---------------------------------------------------------

    def submit(self, req: Request, now: float,
               reuse_uid: bool = False) -> None:
        worst = self.pool.pages_for(self._worst_tokens(req))
        if req.prompt_len < 1:
            raise ValueError("empty prompt")
        if req.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if req.deadline_s is not None and req.deadline_s < 0:
            raise ValueError(
                f"deadline_s must be >= 0, got {req.deadline_s}"
            )
        if self._worst_tokens(req) > self.max_context:
            raise ValueError(
                f"request needs {self._worst_tokens(req)} "
                f"context but the engine was sized for {self.max_context}"
            )
        if worst > self.pool.capacity:
            raise ValueError(
                f"request worst case is {worst} pages but the pool only "
                f"has {self.pool.capacity}"
            )
        if not (reuse_uid and req.uid is not None):
            # reuse_uid=True: a cross-scheduler flow (the disagg
            # fallback and the crash-salvage resubmit path re-submitting
            # a request onto another pool) keeps the uid its tracer
            # timeline is keyed by; the CALLER owns uniqueness across
            # the schedulers involved — disagg uids all come from the
            # prefill scheduler's counter, and the control plane mints
            # each replica's uids from a disjoint block (UID_STRIDE).
            # The local counter deliberately does NOT jump past a
            # reused uid: jumping would leak this scheduler's counter
            # into another replica's block, recreating the very
            # collision the blocks exist to prevent.
            req.uid = self._next_uid
            self._next_uid += 1
        if req.t_submit is None:
            # FIRST submission only — the same contract admit() keeps for
            # t_admit: a request MIGRATED between replicas (control-plane
            # drain: withdraw here, submit there) keeps the user-visible
            # submit time, so queue_latency_s/ttft_s never go negative
            # against a preserved t_admit
            req.t_submit = now
        req.status = Status.QUEUED
        self.queue.append(req)
        if self.tracer is not None:
            self.tracer.on_submit(req, now)

    def _shed_expired(self, now: float) -> None:
        """Graceful degradation: drop QUEUED requests already past
        their deadline — serving them would spend decode slots on
        answers nobody is waiting for while fresh requests queue behind
        them. Shedding is load-dependent but deterministic given the
        same arrival times and schedule; shed requests land in
        ``self.shed`` (terminal, finish_reason="shed") for the engine
        to drain. Only the never-admitted QUEUE sheds: an admitted
        request has paid its prefill and always runs to completion —
        including one preempted back into the queue (``t_admit`` set),
        which already holds generated tokens."""
        if not any(r.deadline_s is not None for r in self.queue):
            return
        kept: deque = deque()
        for req in self.queue:
            if (req.deadline_s is not None
                    and req.t_admit is None
                    and req.t_submit is not None
                    and now - req.t_submit > req.deadline_s):
                req.status = Status.DONE
                req.finish_reason = "shed"
                req.t_done = now
                self.shed.append(req)
                if self.tracer is not None:
                    self.tracer.on_shed(req, now)
            else:
                kept.append(req)
        self.queue = kept

    def drain_shed(self) -> List[Request]:
        """Shed requests since the last drain (engine tick bookkeeping:
        counter + terminal outputs)."""
        out, self.shed = self.shed, []
        return out

    def _admission_check(self, req: Request):
        """The admission ledger, side-effect-free: can the pool (plus
        evictable cache pages, minus pins a cache hit would take) cover
        ``req``'s worst case beyond all outstanding reservations?
        Returns ``(fits, hit)`` — the SINGLE implementation both
        :meth:`admit` and the router-facing :meth:`can_admit` probe
        evaluate, so probe and admission cannot disagree on the same
        state (pinned by test). ``lookup`` is side-effect-free, so a
        False verdict leaves the cache LRU order and every refcount
        untouched."""
        target = req.target_len
        worst = self.pool.pages_for(self._worst_tokens(req))
        hit = None
        shared: List[int] = []
        evictable = pinned = 0
        if self.cache is not None and (
            self.pool.free_count + self.cache.cached_pages
            - self._outstanding_total
            < worst - (target - 1) // self.pool.page_size
        ):
            # O(1) reject: even if EVERY cached page were evictable
            # and the hit were the longest possible, the head can't
            # fit — skip the trie walk + whole-trie evictable scan.
            # (A head blocked only by the EXACT ledger still rescans
            # each tick; acceptable until caches reach a size where
            # incremental evictable accounting pays for itself.)
            return False, None
        if self.cache is not None:
            # >= 1 token must be forwarded: its logits produce the
            # next token (resumed requests re-derive their pending)
            hit = self.cache.lookup(req.tokens[:target],
                                    max_tokens=target - 1)
            shared = hit.pages
            pins = shared + (
                [hit.cow_page] if hit.cow_page is not None else []
            )
            pinned = sum(1 for p in pins if self.pool.refcount(p) == 1)
            evictable = self.cache.evictable_count()
        need_new = worst - len(shared)
        if (self.pool.free_count + evictable - pinned
                - self._outstanding_total < need_new):
            return False, hit
        if not self._window_fits(req):
            return False, hit
        return True, hit

    def _window_fits(self, req: Request) -> bool:
        """The ledger of the pool's ``window`` kind, where it has one: a
        request's ring at its worst case beside the entries of every
        admitted ring not yet allocated."""
        win = self.pool.window
        if win is None:
            return True
        worst = self.pool.pages_for(self._worst_tokens(req), "window")
        owed = sum(self.pool.pages_for(self._worst_tokens(r), "window")
                   - len(r.window_pages) for r in self.active())
        return win.free_count - owed >= worst

    def can_admit(self, req: Request) -> bool:
        """Side-effect-free admission probe: would :meth:`admit` admit
        ``req`` RIGHT NOW if it sat at the head of the queue? Evaluates
        the exact ledger admit() uses (:meth:`_admission_check` is the
        shared implementation) plus slot availability, without debiting
        the reservation total, pinning a hit's pages, or touching the
        cache's LRU clock — the control-plane router calls this per
        routing decision, and a probe that mutated state would skew the
        very admission it predicts."""
        if not any(s is None for s in self.slots):
            return False
        return self._admission_check(req)[0]

    def capacity_snapshot(self) -> dict:
        """Read-only load + capacity view (free/evictable pages, queued
        tokens) — the router's tie-break signal. ``queued_tokens`` and
        ``active_tokens_remaining`` count work still owed: prefill
        targets plus undecoded new-token budgets — on a prefill-only
        pool a request owes no decode, so only its prefill target
        counts. ``transfer_tokens_owed`` is the pages-attached ledger
        case: a TRANSFER-staged request already holds the KV of its
        materialized prefix, so it owes only the UNMATERIALIZED tail of
        its target plus its decode budget — counting its full prefill
        again would double-bill work the prefill pool already paid and
        skew routing/autoscaling load signals. Like :meth:`can_admit`,
        this never mutates anything."""
        active = self.active()

        def owed_new(r: Request) -> int:
            if self.prefill_only:
                return 0
            return max(r.max_new_tokens - len(r.generated), 0)

        snap = {
            "free_slots": sum(1 for s in self.slots if s is None),
            "num_slots": self.num_slots,
            "free_pages": self.pool.free_count,
            "evictable_pages": (self.cache.evictable_count()
                                if self.cache is not None else 0),
            "outstanding_pages": self._outstanding_total,
            "queued_requests": len(self.queue),
            "queued_tokens": sum(
                r.target_len + owed_new(r) for r in self.queue
            ),
            "active_requests": len(active),
            "active_tokens_remaining": sum(owed_new(r) for r in active),
            "transfer_requests": len(self.transfers),
            "transfer_tokens_owed": sum(
                max(s["req"].target_len - s["tokens"], 0)
                + owed_new(s["req"])
                for s in self.transfers.values()
            ),
        }
        led = self.pool.ledger
        if led is not None:
            # memory-pressure signal for the router/autoscaler: the
            # ledger forecaster's steps-to-exhaustion (None = no trend)
            s = led.steps_to_exhaustion
            snap["steps_to_exhaustion"] = (
                None if s == float("inf") else s)
        return snap

    def withdraw(self, req: Request) -> Request:
        """Remove a QUEUED request from this scheduler (control-plane
        drain: this replica gives the request up so another replica's
        :meth:`submit` can take it). Only queue members can be
        withdrawn — an active request must be :meth:`preempt`-ed back
        into the queue first, which releases its pages. Lifecycle
        timestamps survive (submit/admit both preserve existing marks),
        so withdraw → submit elsewhere books the wait between them as
        stall time, never as a fresh queue latency."""
        try:
            self.queue.remove(req)
        except ValueError:
            raise ValueError(
                f"request uid={req.uid} is not queued on this scheduler"
            )
        req.slot = None
        return req

    def admit(self, now: float) -> List[Request]:
        """Move queued requests into free slots while the pool (plus
        evictable cache pages) can cover their worst case beyond all
        outstanding reservations. A prefix-cache hit shares the matched
        pages and shrinks both the worst case and the prefill. Queued
        requests past their ``deadline_s`` are SHED first (admission is
        the deadline checkpoint). Returns the newly admitted requests
        (they still need a prefill for their unique tail, possibly
        empty chunks at a time)."""
        self._shed_expired(now)
        admitted: List[Request] = []
        while self.queue:
            free_slots = [i for i, s in enumerate(self.slots) if s is None]
            if not free_slots:
                break
            req = self.queue[0]
            target = req.target_len
            worst = self.pool.pages_for(self._worst_tokens(req))
            fits, hit = self._admission_check(req)
            led = self.pool.ledger
            if led is not None:
                # admission-pressure feed for the exhaustion forecaster:
                # the head's worst-case need, and whether memory let it in
                led.note_admission(worst, fits)
            if not fits:
                self.admission_deferrals += 1
                break  # FIFO head-of-line: deterministic admission order
            shared: List[int] = hit.pages if hit is not None else []
            need_new = worst - len(shared)
            self.queue.popleft()
            req.slot = free_slots[0]
            self.slots[req.slot] = req
            req.status = Status.PREFILL
            if req.t_admit is None:
                # FIRST admission only: a preempted request's re-admit
                # must not rewrite queue_latency_s (the attribution
                # layer books the requeue wait as stall time instead)
                req.t_admit = now
            req.cow = None
            req.pages = []
            req.prefilled_len = req.hit_tokens = 0
            if hit is not None:
                # pins shared + COW source pages, tagged to this request
                self.cache.acquire(hit, owner=req.uid)
                req.pages = list(shared)
                req.prefilled_len = hit.tokens
                req.hit_tokens = hit.total_tokens
                if hit.cow_page is not None:
                    req.cow = (hit.cow_page, hit.cow_tokens)
            cow_tokens = req.cow[1] if req.cow else 0
            chunk_end = target if self.chunk_tokens is None else min(
                req.prefilled_len + cow_tokens + self.chunk_tokens, target
            )
            n_now = self.pool.pages_for(chunk_end) - len(req.pages)
            req.pages += self._alloc(n_now, tag=("req", req.uid))
            req.window_pages = []
            self._grow_window(req, chunk_end)
            req.outstanding = need_new - n_now
            self._outstanding_total += req.outstanding
            admitted.append(req)
            if self.tracer is not None:
                self.tracer.on_admit(req, now)
        return admitted

    def preempt(self, req: Request) -> None:
        """Mid-stream eviction under memory pressure (or an operator's
        rebalance): give back every page — shared prefix pages survive
        in the cache for the re-admission to hit — and re-queue the
        request ahead of never-admitted arrivals, ordered by ORIGINAL
        submit order among preempted peers (a bare appendleft would
        reverse two requests preempted in the same tick), so FIFO
        determinism survives any preemption pattern. Generated tokens
        are kept; re-admission re-prefills prompt + generated minus the
        pending token, which decode then resumes on."""
        if req.status not in (Status.PREFILL, Status.DECODE):
            raise ValueError(f"cannot preempt a {req.status.value} request")
        self._release_all(req)
        self._outstanding_total -= req.outstanding
        req.outstanding = 0
        self.slots[req.slot] = None
        req.slot = None
        req.prefilled_len = req.hit_tokens = 0
        req.status = Status.QUEUED
        # t_admit marks a previously admitted (re-queued) request;
        # fresh submissions have none and always sort after them
        pos = 0
        while (pos < len(self.queue)
               and self.queue[pos].t_admit is not None
               and self.queue[pos].uid < req.uid):
            pos += 1
        self.queue.insert(pos, req)
        if self.tracer is not None:
            self.tracer.on_preempt(req)

    # -- disaggregated prefill/decode (serving/disagg/) --------------------

    def finish_handoff(self, req: Request, now: float) -> None:
        """Prefill-pool exit: the request's prompt KV has been EXPORTED
        (the engine's handoff hook runs before this) — free the slot,
        the pages, and the reservation, but do NOT finish the request:
        it leaves this scheduler as ``Status.TRANSFER`` and lives on in
        the decode pool. Fires the tracer's first-token hook (the first
        token exists the moment prefill emits it — the handoff carries
        it) and opens the ``transfer`` attribution phase; ``on_done``
        belongs to the decode scheduler that finishes the request."""
        if req.status is not Status.PREFILL:
            raise ValueError(
                f"cannot hand off a {req.status.value} request"
            )
        if req.t_first_token is None:
            req.t_first_token = now
            if self.tracer is not None:
                self.tracer.on_first_token(req, now)
        self._release_all(req)
        self._outstanding_total -= req.outstanding
        req.outstanding = 0
        self.slots[req.slot] = None
        req.slot = None
        req.status = Status.TRANSFER
        if self.tracer is not None:
            self.tracer.on_transfer_start(req, now)

    def begin_transfer(self, req: Request, now: float) -> bool:
        """Stage an inbound cross-pool transfer: reserve the request's
        FULL decode worst case against ``free + evictable`` capacity
        before any page is imported, exactly like :meth:`admit` would —
        so lazy growth during the transfer and the decode that follows
        can never fail. Returns False (no side effects) when the
        ledger cannot cover it right now: the transfer queue holds the
        handoff and retries — that backpressure is the disagg engine's
        admission control.

        The staging state lives in a SCHEDULER-side record
        (``self.transfers[uid]``), never on the request: while pages
        stream, the same ``Request`` object is still live on the
        PREFILL scheduler (that is the point of streaming), so its
        ``status``/``pages``/``prefilled_len`` belong to that side
        until :meth:`admit_with_pages` takes ownership. No cache
        lookup happens: the pages come off the wire, not from this
        pool's prefix cache."""
        worst = self.pool.pages_for(self._worst_tokens(req))
        if worst > self.pool.capacity:
            raise ValueError(
                f"request worst case is {worst} pages but the pool only "
                f"has {self.pool.capacity}"
            )
        if self._worst_tokens(req) > self.max_context:
            raise ValueError(
                f"request needs {self._worst_tokens(req)} context but "
                f"the engine was sized for {self.max_context}"
            )
        if req.uid in self.transfers:
            raise ValueError(f"uid={req.uid} is already staged here")
        evictable = (self.cache.evictable_count()
                     if self.cache is not None else 0)
        if (self.pool.free_count + evictable
                - self._outstanding_total < worst):
            return False
        self.transfers[req.uid] = {
            "req": req, "pages": [], "outstanding": worst, "tokens": 0,
        }
        self._outstanding_total += worst
        return True

    def transfer_pages(self, req: Request, n_tokens: int) -> List[int]:
        """Lazy growth for a staged transfer: allocate destination
        pages to cover ``n_tokens`` materialized positions (the import
        scatters the wire payload into them) and return the stage's
        full page list. Same never-fail contract as
        :meth:`ensure_pages` — the reservation was made by
        :meth:`begin_transfer`, and the cache-ledger hole is closed by
        the same owner-retraction path."""
        stage = self.transfers.get(req.uid)
        if stage is None:
            raise RuntimeError(
                f"transfer_pages on unstaged uid={req.uid}"
            )
        while len(stage["pages"]) * self.pool.page_size < n_tokens:
            stage["pages"] += self._alloc(1, owner=req,
                                          tag=("stage", req.uid))
            stage["outstanding"] -= 1
            self._outstanding_total -= 1
        stage["tokens"] = max(stage["tokens"], n_tokens)
        return stage["pages"]

    def abort_transfer(self, req: Request) -> None:
        """Transfer failed: release every imported page and the whole
        reservation. The caller re-submits the request for a local
        re-prefill (the disagg fallback path) once the prefill pool
        has let go of it — ``submit`` restores the QUEUED lifecycle."""
        stage = self.transfers.pop(req.uid, None)
        if stage is None:
            raise ValueError(f"uid={req.uid} is not staged here")
        if stage["pages"]:
            if self.pool.ledger is not None:
                self.pool.tag = ("stage", req.uid)
            self.pool.release(stage["pages"])
        self._outstanding_total -= stage["outstanding"]

    def alloc_for_restore(self, n: int) -> List[int]:
        """Best-effort page allocation for the kv_tier restore path
        (serving/kv_tier/): evict cold cache pages like any alloc, but
        return UP TO ``n`` pages instead of retracting live requests —
        a restore is opportunistic, not owed. No ledger debit is
        needed: the caller inserts the restored chain into the prefix
        cache and releases its own reference immediately, so the pages
        re-enter the ``free + evictable`` total the reservation
        arithmetic spends — capacity is moved, never consumed."""
        if n <= 0:
            return []
        if self.cache is not None and self.pool.free_count < n:
            self.cache.evict(n - self.pool.free_count)
        got = min(n, self.pool.free_count)
        if got and self.pool.ledger is not None:
            self.pool.tag = ("restore",)
        return self.pool.alloc(got) if got else []

    def admit_with_pages(self, req: Request, first_token: Optional[int],
                         now: float, *,
                         prefilled_len: Optional[int] = None) -> bool:
        """The disagg admission: bind a fully materialized transfer to
        a free slot and SKIP prefill entirely — the pages already hold
        the prompt's KV, so the request debits nothing beyond the tail
        reservation :meth:`begin_transfer` made, and decoding starts on
        the handoff's first token immediately. Returns False when no
        slot is free (the stage keeps its pages + reservation). The
        request object must have LEFT its prefill scheduler by now
        (``finish_handoff`` marks it ``Status.TRANSFER``) — this is the
        ownership handover point where the staged pages become the
        request's own. ``t_admit`` survives from the prefill-pool
        admission (first admission wins), so queue latency stays the
        user-visible wait.

        ``prefilled_len`` < ``target_len`` is the PARTIAL variant (the
        kv_tier cross-replica pull): the staged pages cover only the
        pulled page-aligned prefix, no first token exists yet, and the
        request stays ``Status.PREFILL`` so the engine's chunked
        prefill RESUMES at ``prefilled_len`` — admission-by-transfer
        composing with the ordinary prefill machinery instead of
        bypassing it."""
        stage = self.transfers.get(req.uid)
        if stage is None:
            raise ValueError(f"uid={req.uid} is not staged here")
        if req.status is not Status.TRANSFER:
            raise ValueError(
                f"admit_with_pages needs a handed-off request, got "
                f"{req.status.value} (still live on the prefill pool?)"
            )
        free_slots = [i for i, s in enumerate(self.slots) if s is None]
        if not free_slots:
            return False
        del self.transfers[req.uid]
        req.slot = free_slots[0]
        self.slots[req.slot] = req
        req.status = Status.PREFILL   # momentary: record_token -> DECODE
        req.pages = list(stage["pages"])
        led = self.pool.ledger
        if led is not None:
            # ownership handover, no refcount change: staged transfer
            # pages become this request's KV in the ledger too
            led.retag(req.pages, ("stage", req.uid), ("req", req.uid))
        req.outstanding = stage["outstanding"]
        req.cow = None
        if req.t_admit is None:
            req.t_admit = now
        req.hit_tokens = 0
        if prefilled_len is not None and prefilled_len < req.target_len:
            if first_token is not None:
                raise ValueError(
                    "a partial admit_with_pages carries no first token "
                    "(prefill has not finished anywhere yet)"
                )
            if prefilled_len % self.pool.page_size:
                raise ValueError(
                    f"prefilled_len={prefilled_len} must be page-aligned "
                    f"(pulled pages hold whole blocks)"
                )
            req.prefilled_len = prefilled_len
            if self.tracer is not None:
                self.tracer.on_transfer_done(req, now, resume="prefill")
            return True
        req.prefilled_len = req.target_len
        if self.tracer is not None:
            self.tracer.on_transfer_done(req, now)
        self.record_token(req, int(first_token), now)
        return True

    def ensure_pages(self, req: Request, n_tokens: int) -> None:
        """Lazy growth to cover ``n_tokens`` cached positions (decode:
        one past the pending write; chunked prefill: the chunk's end;
        speculation: the draft bundle's end). Cannot fail: admission
        reserved the worst case against free + evictable capacity, and
        the one hole in that ledger — a LATER ``insert`` hanging a
        live request's child under a node an earlier admission already
        credited as evictable, which makes the ancestor unrecoverable
        with no debit — is closed by RETRACTION (``_alloc(owner=req)``
        preempts the newest other active request; it re-queues and
        re-prefills through the cache). The submit-time
        ``worst <= capacity`` check guarantees retraction terminates:
        with every other request preempted and the cache drained, the
        owner's worst case always fits."""
        if req.status not in (Status.PREFILL, Status.DECODE):
            # growing a slotless request would drive its reservation
            # negative and leak the pages at re-admission — callers
            # iterating a materialized batch must re-check status after
            # any neighbor's ensure_pages (it may have retracted them)
            raise RuntimeError(
                f"ensure_pages on a {req.status.value} request "
                f"(retracted mid-batch by a neighbor's lazy growth?)"
            )
        while len(req.pages) < self.pool.pages_for(n_tokens):
            req.pages += self._alloc(1, owner=req, tag=("req", req.uid))
            req.outstanding -= 1
            self._outstanding_total -= 1
        self._grow_window(req, n_tokens)

    def _grow_window(self, req: Request, n_tokens: int) -> None:
        """The window kind's ring of ``req`` grown to hold ``n_tokens``
        positions: entries are allocated until the ring is whole; from
        then on a new logical page takes over the oldest entry (counted
        in ``PagePool.recycled``), and nothing is allocated or freed."""
        pool = self.pool
        if pool.window is None:
            return
        have = len(req.window_pages)
        need = pool.pages_for(n_tokens, "window")
        if need > have:
            req.window_pages += pool.alloc(need - have, "window")
        logical = pool.logical_pages(n_tokens)
        seen = req.window_logical
        if logical > seen:
            pool.recycled += max(logical, pool.ring) - max(seen, pool.ring)
            req.window_logical = logical

    def ensure_page(self, req: Request) -> None:
        """Decode-step growth: cover the pending token's write position."""
        self.ensure_pages(req, req.cached_len + 1)

    def record_token(self, req: Request, token: int, now: float) -> None:
        if req.t_first_token is None:
            req.t_first_token = now
            if self.tracer is not None:
                self.tracer.on_first_token(req, now)
        req.status = Status.DECODE
        req.generated.append(int(token))
        if req.eos_token_id is not None and int(token) == req.eos_token_id:
            self._finish(req, "eos", now)
        elif len(req.generated) >= req.max_new_tokens:
            self._finish(req, "length", now)

    def _alloc(self, n: int, owner: Optional[Request] = None,
               tag=None) -> List[int]:
        """Pool alloc that treats LRU-evictable cache pages as free.
        With ``owner`` set (the must-not-fail reservation path), a
        shortfall that eviction cannot cover retracts newest-first
        OTHER active requests until it can — see :meth:`ensure_pages`.
        Admission never passes ``owner``: its ledger check and alloc
        are atomic within one ``admit`` iteration (no insert can
        intervene), and a blocked admission simply waits. ``tag`` is
        the memory-ledger owner label for the allocated pages."""
        if n <= 0:
            return []
        if self.cache is not None and self.pool.free_count < n:
            self.cache.evict(n - self.pool.free_count)
            if self.pool.free_count < n and owner is not None:
                for victim in sorted(
                    (r for r in self.slots
                     if r is not None and r is not owner),
                    key=lambda r: r.uid, reverse=True,
                ):
                    self.preempt(victim)
                    self.cache.evict(n - self.pool.free_count)
                    if self.pool.free_count >= n:
                        break
        if self.pool.ledger is not None:
            # set AFTER any eviction/retraction above: those release
            # with their own tags, each event consuming the one-shot tag
            self.pool.tag = tag if tag is not None else (
                ("req", owner.uid) if owner is not None else None)
        return self.pool.alloc(n)

    def _release_all(self, req: Request) -> None:
        if req.cow is not None:          # un-run COW copy: drop the pin
            if self.pool.ledger is not None:
                self.pool.tag = ("cow", req.uid)
            self.pool.release([req.cow[0]])
            req.cow = None
        if req.pages:
            if self.pool.ledger is not None:
                self.pool.tag = ("req", req.uid)
            self.pool.release(req.pages)
            req.pages = []
        if req.window_pages:
            self.pool.release(req.window_pages, "window")
            req.window_pages = []
        req.window_logical = 0

    def _finish(self, req: Request, reason: str, now: float) -> None:
        req.status = Status.DONE
        req.finish_reason = reason
        req.t_done = now
        self._release_all(req)
        self._outstanding_total -= req.outstanding
        req.outstanding = 0
        self.slots[req.slot] = None
        if self.tracer is not None:
            self.tracer.on_done(req, now)

    # -- queries -----------------------------------------------------------

    def active(self) -> List[Request]:
        return [r for r in self.slots if r is not None]

    def all_done(self) -> bool:
        return not self.queue and all(s is None for s in self.slots)
