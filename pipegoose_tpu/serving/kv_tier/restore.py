"""Restore-vs-recompute decisions and the engine-side tier orchestration.

Two paths bring previously computed prefix pages back into a pool's
HBM without re-running prefill, both staged BEFORE normal admission so
the untouched admission/COW/chunking machinery serves the request
exactly as if the pages had never left:

- **Local host-tier restore** (``maybe_restore``): the queue head's
  prompt is probed against the radix cache, then the host tier is
  walked for the contiguous block run extending the HBM hit. Found
  slabs are scattered into freshly allocated pool pages through the
  same jitted import the disagg transfer uses, the chain is inserted
  into the prefix cache, and the pages are released to cache
  ownership — the very next ``Scheduler.admit`` sees a plain cache
  hit. Token-identical by construction: the slabs are the wire-exact
  bytes the eviction spilled.
- **Cross-replica pull** (``maybe_pull``): when the fleet directory
  (or an explicit peer hint) says another replica holds the prefix,
  the pages ship through a ``PoolTransfer`` between the two engines —
  peer HBM pages via the jitted gather, peer tier entries as-is (they
  are already host wire slabs) — staged through the scheduler's
  ``begin_transfer``/``transfer_pages``/``admit_with_pages`` ledger
  path, then the request RESUMES chunked prefill at the pulled
  length. Resharding happens at the host hop (tp=2 -> tp=1 works);
  int8 pages are never dequantized in flight.

:class:`RestorePlanner` decides restore-vs-recompute per prefix length
from the calibrated :class:`~pipegoose_tpu.planner.cost.CostModel`
(PR 13's fitted launch/bandwidth/overhead constants): a restore pays
per-shipment launches plus wire bytes over the link; a recompute pays
``2 * n_params`` FLOPs per token. No model (the default) means always
restore — on the CPU test rig there is nothing calibrated to consult.

Failure contract (exercised by testing/chaos.py's
``host_tier_io_error``): any :class:`HostTierError` /
:class:`TransferError` mid-restore degrades to recompute — partial
progress is kept when it is coherent (a front-to-back partial restore
is a valid shorter hit; a failed pull aborts its staging entirely and
re-queues), one ``kv_tier_fallback`` black box names the prefix, and
the trigger is consumed immediately (recovered-by-construction: the
recompute serves the request), so ``/healthz`` never flips. Never a
stall, never a lost request.

Host-side by design (jit-safety allowlisted): the only device programs
are the shared jitted export/import pair.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from pipegoose_tpu.serving.disagg.transfer import (
    PageHandoff,
    PoolTransfer,
    TransferError,
)
from pipegoose_tpu.serving.kv_tier.host_tier import HostTierError
from pipegoose_tpu.serving.scheduler import Status


def wire_page_bytes(engine) -> int:
    """Per-page wire bytes for planner estimates: int8 ships q+scale
    (``hd + 4`` bytes per position-head), fp ships the pool dtype."""
    cfg = engine.config
    ps = engine.page_size
    per_pos_head = (
        cfg.head_dim + 4 if engine.kv_dtype == "int8"
        else cfg.head_dim * int(np.dtype(cfg.dtype).itemsize)
    )
    return 2 * cfg.n_layer * ps * cfg.n_head * per_pos_head


class RestorePlanner:
    """Calibrated restore-vs-recompute decision.

    ``cost_model`` is a :class:`~pipegoose_tpu.planner.cost.CostModel`
    (ideally post-``calibrate``); ``n_params`` sizes the recompute side
    (``2 * n_params`` FLOPs/token, the standard forward estimate).
    Without a model (or with ``n_params=0``) the planner always says
    restore — the conservative default for the uncalibrated test rig,
    where wire bytes are tiny and prefill is the only real cost.
    ``min_tokens`` floors the decision (restoring one page may not be
    worth the launch even when the model is missing)."""

    def __init__(self, cost_model=None, *, n_params: int = 0,
                 min_tokens: int = 0):
        self.cost_model = cost_model
        self.n_params = int(n_params)
        self.min_tokens = int(min_tokens)

    def restore_cost_s(self, n_bytes: int, *, n_ops: int = 1,
                       cross_replica: bool = False) -> float:
        """Wire cost of moving ``n_bytes`` in ``n_ops`` shipments:
        host<->HBM staging rides the ICI constant, a cross-replica pull
        the DCI one (the calibrated fabrics the fleet actually has)."""
        cm = self.cost_model
        if cm is None:
            return 0.0
        bw = cm.dci_bytes_per_s if cross_replica else cm.ici_bytes_per_s
        return (n_ops * cm.collective_launch_s + n_bytes / max(bw, 1.0)
                + cm.step_overhead_s)

    def recompute_cost_s(self, n_tokens: int) -> float:
        cm = self.cost_model
        if cm is None:
            return float("inf")
        return (cm.step_overhead_s
                + 2.0 * self.n_params * n_tokens / max(cm.peak_flops, 1.0))

    def should_restore(self, n_tokens: int, n_bytes: int, *,
                       n_ops: int = 1, cross_replica: bool = False) -> bool:
        if n_tokens < self.min_tokens or n_tokens <= 0:
            return False
        if self.cost_model is None or self.n_params <= 0:
            return True
        return (
            self.restore_cost_s(n_bytes, n_ops=n_ops,
                                cross_replica=cross_replica)
            < self.recompute_cost_s(n_tokens)
        )


class RestoreManager:
    """Engine-side orchestrator of both tier paths.

    Owns the lazily compiled transfer programs (one self-transfer for
    spill/restore, one :class:`PoolTransfer` per peer engine for
    pulls), the per-run restored/pulled token accounting ``finish_run``
    reports, and the one-probe-per-request bookkeeping that keeps the
    hit/miss counters request-scoped rather than tick-scoped. Created
    by every paged-prefill engine (cheap — nothing compiles until the
    first spill or pull), so any engine with a prefix cache can serve
    as a pull PEER even without a host tier of its own."""

    def __init__(self, engine):
        self.engine = engine
        self.planner = RestorePlanner()
        self._self_xfer: Optional[PoolTransfer] = None
        self._peer_xfers: Dict[int, PoolTransfer] = {}
        # uid -> peer engine: the control plane's routing
        # hint that a specific peer holds this request's prefix
        self.pull_hints: Dict[int, Any] = {}
        self.default_peer = None
        # run-scoped accounting (reset by on_run_start)
        self.restored_tokens = 0
        self.pulled_tokens = 0
        self.pulls = 0
        self.fallbacks = 0
        self._handled: set = set()

    # -- wiring ------------------------------------------------------------

    def set_peer_source(self, peer) -> None:
        """Default pull source for every request (demo/tests; the
        control plane hints per request instead)."""
        self.default_peer = peer

    def hint_pull(self, req, peer) -> None:
        """Route hint: ``peer`` (a ServingEngine) likely holds ``req``'s
        prefix. Advisory — a stale hint costs one inventory walk."""
        self.pull_hints[req.uid] = peer

    def on_run_start(self) -> None:
        self.restored_tokens = 0
        self.pulled_tokens = 0
        self.pulls = 0
        self.fallbacks = 0
        self._handled.clear()

    def run_stats(self) -> dict:
        return {
            "restored_tokens": self.restored_tokens,
            "pulled_tokens": self.pulled_tokens,
            "pulls": self.pulls,
            "fallbacks": self.fallbacks,
        }

    def _self_transfer(self) -> PoolTransfer:
        """Engine->itself transfer: the spill export and restore import
        pair. Width 1 — tier entries are page-granular by contract."""
        if self._self_xfer is None:
            eng = self.engine
            self._self_xfer = PoolTransfer(
                eng, eng, wire_dtype=eng.host_tier_wire, width=1,
            )
        return self._self_xfer

    def _peer_transfer(self, peer) -> PoolTransfer:
        """Peer->engine transfer for pulls (compiled once per peer).
        Raises ValueError on geometry mismatch — the caller treats
        that peer as unpullable."""
        xfer = self._peer_xfers.get(id(peer))
        if xfer is None:
            width = max(
                1, (peer.prefill_chunk or peer.page_size) // peer.page_size
            )
            xfer = PoolTransfer(peer, self.engine, width=width)
            self._peer_xfers[id(peer)] = xfer
        return xfer

    # -- spill (prefix_cache.spill_hook) -----------------------------------

    def spill(self, chain: Tuple[int, ...], page: int) -> None:
        """Eviction intercept: capture the victim page's KV into the
        host tier at wire precision. Best-effort by the cache's
        contract — a failure loses the tier copy, never the eviction."""
        tier = self.engine.host_tier
        if tier is None:
            return
        ks, vs, _ = self._self_transfer().export([page])
        try:
            stored = tier.put(chain, ks, vs)
        except HostTierError:
            tier.spill_drops += 1
            return
        if stored:
            self._publish(chain, "host")

    def _publish(self, tokens, location: str) -> None:
        hook = self.engine.on_prefix_publish
        if hook is not None:
            hook(tokens, location)

    # -- the pre-admission intercept (engine.tick_once) --------------------

    def tick_intercept(self, now) -> None:
        """Runs right before ``Scheduler.admit`` each tick: give the
        queue head its one shot at a pull (peer hint) and/or a local
        tier restore, so the admission that follows sees the pages as
        ordinary cache hits. One probe per request uid — the counters
        stay request-scoped and a nothing-to-restore head is not
        re-walked every tick."""
        eng = self.engine
        sched = eng.sched
        while sched.queue and any(s is None for s in sched.slots):
            req = sched.queue[0]
            if req.uid in self._handled:
                return
            outcome = "no"
            if req.uid in self.pull_hints or self.default_peer is not None:
                outcome = self.maybe_pull(req, now)
                if outcome == "retry":
                    return  # ledger blocked: keep the hint, next tick
            self._handled.add(req.uid)
            if outcome == "admitted":
                continue   # head left the queue: probe the new head too
            if eng.host_tier is not None:
                self.maybe_restore(req, now)
            return  # head stays queued; the admission below takes it

    # -- local host-tier restore -------------------------------------------

    def maybe_restore(self, req, now) -> bool:
        """Restore the contiguous host-tier run extending ``req``'s HBM
        cache hit back into pool pages and insert the chain into the
        cache (pages end up cache-owned and evictable — admission then
        pins what it needs). Returns True when >= 1 page was restored."""
        eng = self.engine
        tier = eng.host_tier
        cache = eng.prefix_cache
        ps = eng.page_size
        cap = req.target_len - 1   # admission forwards >= 1 token
        toks = [int(t) for t in np.asarray(req.tokens)[:req.target_len]]
        hit = cache.lookup(toks, max_tokens=cap)
        h = hit.tokens // ps
        keys: List[Tuple[int, ...]] = []
        i = h
        while (i + 1) * ps <= cap and tier.contains(
                tuple(toks[:(i + 1) * ps])):
            keys.append(tuple(toks[:(i + 1) * ps]))
            i += 1
        tier.note_probe(len(keys))
        if not keys:
            return False
        n_bytes = sum(tier.entry_bytes(k) for k in keys)
        if not self.planner.should_restore(len(keys) * ps, n_bytes,
                                           n_ops=len(keys)):
            return False
        # Pin the matched chain before allocating: the allocation may
        # evict, and an evicted ancestor would orphan the insert below.
        cache.acquire(hit)
        try:
            pages = eng.sched.alloc_for_restore(len(keys))
            keys = keys[:len(pages)]
            if not keys:
                return False
            tr = eng.tracer
            t0 = now()
            if tr is not None:
                tr.on_restore_start(req, t0)
            xfer = self._self_transfer()
            done: List[int] = []
            try:
                for key, page in zip(keys, pages):
                    t_a = now()
                    ks, vs, nb = tier.get(key)
                    rec = PageHandoff(
                        req=req, page_index=len(key) // ps - 1, n_pages=1,
                        tokens_end=len(key), k=ks, v=vs, wire_bytes=nb,
                        final=False, first_token=None, t_created=t_a,
                    )
                    xfer.import_(rec, [page])
                    done.append(page)
                    if tr is not None:
                        t_b = now()
                        tr.on_restore_chunk(req, t_b, dur_s=t_b - t_a,
                                            tokens=ps, pages=1, nbytes=nb)
            except (HostTierError, TransferError, KeyError) as exc:
                if pages[len(done):]:
                    if eng.pool.ledger is not None:
                        eng.pool.tag = ("restore",)
                    eng.pool.release(pages[len(done):])
                self._fallback_box("host tier restore", req,
                                   keys[0], exc)
            if done:
                m = h + len(done)
                cache.insert(toks[:m * ps], list(hit.pages) + done)
                if eng.pool.ledger is not None:
                    eng.pool.tag = ("restore",)
                eng.pool.release(done)   # cache's share now owns them
                tier.note_restored(len(done))
                self.restored_tokens += len(done) * ps
                self._publish(toks[:m * ps], "hbm")
            if tr is not None:
                tr.on_restore_done(req, now())
            return bool(done)
        finally:
            # drop the probe pins acquire() took (anonymous owner=None
            # pins — the ledger tags must match acquire's)
            if hit.pages:
                if eng.pool.ledger is not None:
                    eng.pool.tag = ("req", None)
                eng.pool.release(hit.pages)
            if hit.cow_page is not None:
                if eng.pool.ledger is not None:
                    eng.pool.tag = ("cow", None)
                eng.pool.release([hit.cow_page])

    # -- cross-replica pull -------------------------------------------------

    def prefix_inventory(self, tokens, max_blocks: int
                         ) -> Tuple[List[int], List[Tuple[int, ...]]]:
        """PEER-side truth at export time: the HBM page ids of this
        engine's cached chain for ``tokens`` plus the tier keys of the
        contiguous run extending it (first gap stops — a pull lands
        front-to-back). The directory may claim more; this is what the
        peer still actually holds."""
        eng = self.engine
        cache = eng.prefix_cache
        tier = eng.host_tier
        ps = eng.page_size
        toks = [int(t) for t in np.asarray(tokens)][:max_blocks * ps]
        hit = cache.lookup(toks)
        pages = list(hit.pages)
        keys: List[Tuple[int, ...]] = []
        i = len(pages)
        while (i + 1) * ps <= len(toks) and tier is not None \
                and tier.contains(tuple(toks[:(i + 1) * ps])):
            keys.append(tuple(toks[:(i + 1) * ps]))
            i += 1
        return pages, keys

    def maybe_pull(self, req, now) -> str:
        """Pull ``req``'s prefix pages from a peer engine and admit it
        with them, resuming chunked prefill at the pulled length.
        Returns ``"admitted"`` / ``"retry"`` (ledger blocked — keep the
        hint) / ``"no"`` (peer adds nothing, or the pull failed and the
        request re-queued for recompute)."""
        eng = self.engine
        peer = self.pull_hints.get(req.uid) or self.default_peer
        if peer is None or peer is eng:
            self.pull_hints.pop(req.uid, None)
            return "no"
        mgr = getattr(peer, "kv_tier", None)
        cache = eng.prefix_cache
        ps = eng.page_size
        max_blocks = (req.target_len - 1) // ps
        if mgr is None or cache is None or max_blocks <= 0:
            self.pull_hints.pop(req.uid, None)
            return "no"
        toks = [int(t) for t in np.asarray(req.tokens)[:req.target_len]]
        local = cache.restorable_len(toks, eng.host_tier,
                                     max_tokens=req.target_len - 1)
        try:
            xfer = self._peer_transfer(peer)
        except ValueError:
            self.pull_hints.pop(req.uid, None)
            return "no"   # geometry-incompatible peer
        peer_pages, peer_keys = mgr.prefix_inventory(toks, max_blocks)
        n_avail = len(peer_pages) + len(peer_keys)
        pulled_tokens = n_avail * ps
        if pulled_tokens <= local:
            self.pull_hints.pop(req.uid, None)
            return "no"   # local cache + tier already cover as much
        n_bytes = (len(peer_pages) * wire_page_bytes(peer)
                   + sum(peer.host_tier.entry_bytes(k) for k in peer_keys))
        n_ops = -(-len(peer_pages) // xfer.width) + len(peer_keys)
        if not self.planner.should_restore(pulled_tokens - local, n_bytes,
                                           n_ops=n_ops, cross_replica=True):
            self.pull_hints.pop(req.uid, None)
            return "no"
        t0 = now()
        if not eng.sched.begin_transfer(req, t0):
            return "retry"
        self.pull_hints.pop(req.uid, None)
        eng.sched.withdraw(req)
        req.status = Status.TRANSFER
        tr = eng.tracer
        if tr is not None:
            tr.on_transfer_start(req, t0)
        try:
            idx = 0
            while idx < len(peer_pages):     # peer HBM pages, batched
                chunk = peer_pages[idx:idx + xfer.width]
                t_a = now()
                ks, vs, nb = xfer.export(chunk)
                end = (idx + len(chunk)) * ps
                dst = eng.sched.transfer_pages(req, end)
                rec = PageHandoff(
                    req=req, page_index=idx, n_pages=len(chunk),
                    tokens_end=end, k=ks, v=vs, wire_bytes=nb,
                    final=False, first_token=None, t_created=t_a,
                )
                xfer.import_(rec, dst[idx:idx + len(chunk)])
                if tr is not None:
                    t_b = now()
                    tr.on_transfer_chunk(req, t_b, dur_s=t_b - t_a,
                                         tokens=len(chunk) * ps,
                                         pages=len(chunk), nbytes=nb)
                idx += len(chunk)
            for j, key in enumerate(peer_keys):  # peer tier entries
                t_a = now()
                ks, vs, nb = peer.host_tier.get(key)
                blk = len(peer_pages) + j
                end = (blk + 1) * ps
                dst = eng.sched.transfer_pages(req, end)
                rec = PageHandoff(
                    req=req, page_index=blk, n_pages=1, tokens_end=end,
                    k=ks, v=vs, wire_bytes=nb, final=False,
                    first_token=None, t_created=t_a,
                )
                xfer.import_(rec, dst[blk:blk + 1])
                if tr is not None:
                    t_b = now()
                    tr.on_restore_chunk(req, t_b, dur_s=t_b - t_a,
                                        tokens=ps, pages=1, nbytes=nb)
        except (HostTierError, TransferError, KeyError) as exc:
            eng.sched.abort_transfer(req)
            req.clear_residency()
            eng.sched.submit(req, now(), reuse_uid=True)
            if tr is not None:
                # stitched fleet traces surface the degraded pull: the
                # leg recomputed instead of importing the peer's pages
                tr.annotate(req, "tier_fallback", path="cross-replica pull")
            self._fallback_box("cross-replica pull", req,
                               tuple(toks[:ps]), exc)
            return "no"
        if not eng.sched.admit_with_pages(req, None, now(),
                                          prefilled_len=pulled_tokens):
            # no free slot (cannot happen from tick_intercept, which
            # checks first — defensive for direct callers)
            eng.sched.abort_transfer(req)
            req.clear_residency()
            eng.sched.submit(req, now(), reuse_uid=True)
            return "no"
        self.pulls += 1
        self.pulled_tokens += pulled_tokens
        self.restored_tokens += len(peer_keys) * ps
        return "admitted"

    # -- failure fallback ---------------------------------------------------

    def _fallback_box(self, path: str, req, key, exc: Exception) -> None:
        """One black box per degradation, naming the prefix — then the
        trigger is consumed immediately (the recompute that follows
        serves the request, so this is recovered-by-construction and
        must not flip /healthz). A pre-existing pending trigger
        survives (the plane's recovered-consume pattern)."""
        self.fallbacks += 1
        rec = self.engine.recorder
        if rec is None:
            return
        run = self.engine._run
        pending = rec.last_trigger
        chain = tuple(int(t) for t in key)
        trig = rec.fire_trigger(
            "kv_tier_fallback",
            f"{path} failed for uid={req.uid} "
            f"prefix={chain[:8]}{'...' if len(chain) > 8 else ''} "
            f"({len(chain)} tokens): {exc} — degrading to recompute",
            getattr(run, "tick", 0) if run is not None else 0,
            details={
                "path": path,
                "uid": req.uid,
                "prefix_head": list(chain[:16]),
                "prefix_len": len(chain),
                "error": str(exc),
            },
        )
        if rec.last_trigger is trig:
            rec.take_trigger()
            if pending is not None:
                rec.last_trigger = pending
