"""Cache-aware request placement over serving replicas.

Each replica's radix prefix cache is an independent store; without
placement awareness, a request whose prefix is hot on replica A lands
on replica B by round-robin luck and pays a full prefill. The router
turns hit rate into a decision:

- **cache_aware** (default): probe every accepting replica that can
  admit the request (``Scheduler.can_admit`` — the side-effect-free
  admission ledger) with the prefix cache's read-only
  ``longest_prefix_len`` and pick the replica holding the LONGEST
  cached prefix of the request's tokens. Ties break by load — fewest
  queued + in-flight tokens owed, then most free + evictable pages,
  then the stable replica index (determinism). The probe is a shadow
  read of each replica's published prefixes: nothing is pinned, no LRU
  clock moves, so probing N replicas costs N trie walks and perturbs
  none of them.
- **round_robin**: rotate over admitting replicas — the baseline arm
  cache-aware placement is compared against.

Every decision lands in a bounded log (the ``/debug/fleet`` forensics
and the Perfetto router track — ``telemetry.chrometrace.
router_trace_events``) plus ``router.*`` counters.
"""
from __future__ import annotations

from collections import deque
from typing import Any, Dict, List, Optional

from pipegoose_tpu.serving.control_plane.replica import Replica
from pipegoose_tpu.telemetry.registry import get_registry

POLICIES = ("cache_aware", "round_robin", "disagg")


class ShadowIndex:
    """Router-side radix over the prompts ROUTED to one replica — the
    shadow of that replica's prefix cache, block-granular (one node per
    ``page_size`` token block, same keying as the real trie).

    Fed by placements, not only by published pages: the real cache
    publishes a prefix only when its prefill completes, so during a
    bursty cold start every probe reads 0 and same-prefix requests
    scatter by the load tie-break — each replica then pays its own cold
    prefill for the same prefix. Recording the placement OPTIMISTICALLY
    (the routed prompt's pages WILL be published a few ticks later)
    keeps the second occurrence of a prefix behind the first one's
    replica, which is the whole point of cache-aware routing. The
    read-only ``longest_prefix_len`` probe of the real cache remains
    the ground truth the router maxes this against — a shadow that
    over-claims after an eviction costs one suboptimal placement, never
    correctness (admission re-checks everything).

    Bounded: past ``max_blocks`` nodes the shadow resets empty and
    rebuilds from subsequent placements + probes (coarse, self-healing,
    and O(1) — a per-chain LRU would cost more than the misroutes it
    prevents at this size)."""

    __slots__ = ("page_size", "max_blocks", "_root", "_blocks",
                 "resets_total", "on_reset")

    def __init__(self, page_size: int, max_blocks: int = 4096):
        self.page_size = int(page_size)
        self.max_blocks = int(max_blocks)
        self._root: Dict[tuple, dict] = {}
        self._blocks = 0
        self.resets_total = 0        # cap-triggered resets only
        self.on_reset = None         # callback(shadow) at each cap reset

    def insert(self, tokens) -> None:
        ps = self.page_size
        toks = [int(t) for t in tokens]
        children = self._root
        for i in range(len(toks) // ps):
            blk = tuple(toks[i * ps:(i + 1) * ps])
            node = children.get(blk)
            if node is None:
                if self._blocks >= self.max_blocks:
                    self.clear()
                    self.resets_total += 1
                    if self.on_reset is not None:
                        self.on_reset(self)
                    return
                node = {}
                children[blk] = node
                self._blocks += 1
            children = node

    def longest_match(self, tokens) -> int:
        """Matched tokens, page-granular (the shadow has no COW-head
        notion — the probe of the real cache supplies that
        refinement)."""
        ps = self.page_size
        toks = [int(t) for t in tokens]
        children = self._root
        i = 0
        while (i + 1) * ps <= len(toks):
            node = children.get(tuple(toks[i * ps:(i + 1) * ps]))
            if node is None:
                break
            children = node
            i += 1
        return i * ps

    def clear(self) -> None:
        self._root = {}
        self._blocks = 0


class Router:
    def __init__(self, policy: str = "cache_aware", *, registry=None,
                 max_decisions: int = 512,
                 affinity_slack_tokens: int = 192,
                 memory_pressure_steps: float = 0.0,
                 memory_pressure_penalty_tokens: int = 8192):
        if policy not in POLICIES:
            raise ValueError(
                f"unknown routing policy {policy!r} (expected one of "
                f"{POLICIES})"
            )
        if affinity_slack_tokens < 0:
            raise ValueError(
                f"affinity_slack_tokens must be >= 0, got "
                f"{affinity_slack_tokens}"
            )
        if memory_pressure_steps < 0 or memory_pressure_penalty_tokens < 0:
            raise ValueError(
                "memory_pressure_steps and memory_pressure_penalty_tokens "
                "must be >= 0"
            )
        self.policy = policy
        self.affinity_slack_tokens = int(affinity_slack_tokens)
        # memory-ledger routing signal: a replica whose steps-to-
        # exhaustion forecast (capacity_snapshot, present only when a
        # MemoryLedger is attached) is at or below
        # ``memory_pressure_steps`` carries a synthetic token debt, so
        # cache affinity stops piling prefixes onto a pool about to
        # start evicting them. 0 disables (default).
        self.memory_pressure_steps = float(memory_pressure_steps)
        self.memory_pressure_penalty_tokens = int(
            memory_pressure_penalty_tokens)
        self.registry = registry if registry is not None else get_registry()
        self.decisions: deque = deque(maxlen=max_decisions)
        self._rr_next = 0
        self._shadows: Dict[str, ShadowIndex] = {}  # replica name -> shadow
        reg = self.registry
        self._m_decisions = reg.counter("router.decisions_total")
        self._m_cache_routed = reg.counter(
            "router.cache_routed_total",
            help="decisions where a nonzero cached prefix chose the replica",
        )
        self._m_matched = reg.counter(
            "router.matched_tokens_total",
            help="prefix tokens already cached on the chosen replica",
        )
        self._m_unplaceable = reg.counter(
            "router.unplaceable_total",
            help="route() calls where no replica could admit",
        )
        self._m_shadow_resets = reg.counter(
            "router.shadow_resets_total",
            help="shadow-index cap resets (graceful degradation: the "
                 "shadow rebuilds from subsequent placements)",
        )

    def route(self, req: Any, replicas: List[Replica],
              now: float, seq: Optional[int] = None) -> Optional[Replica]:
        """Pick the replica for ``req`` among ``replicas`` (None when
        no accepting replica can admit it right now — the dispatcher
        requeues and retries next tick). Pure reads: the only mutation
        anywhere is the router's own decision log/counters."""
        if self.policy == "disagg":
            raise ValueError(
                "the disagg policy dispatches through route_disagg("
                "prefill_replicas, decode_replicas) — one pool cannot "
                "serve both roles"
            )
        cands = [rep for rep in replicas
                 if rep.accepting and rep.engine.sched.can_admit(req)]
        if not cands:
            self._m_unplaceable.inc()
            return None
        matched = 0
        if self.policy == "round_robin":
            chosen = cands[self._rr_next % len(cands)]
            self._rr_next += 1
        else:
            tokens = req.tokens   # prompt + generated: a migrated
            # request probes with everything its re-prefill will walk,
            # so the replica that cached its prefix pre-drain wins
            matched, chosen = self._pick_cache_aware(cands, tokens)
        chosen.dispatched += 1
        self._m_decisions.inc()
        if matched:
            self._m_cache_routed.inc()
            self._m_matched.inc(matched)
        self.decisions.append({
            "t": now,
            "seq": seq,   # control-plane dispatch sequence (uid is
            # replica-local and not assigned until the target submits)
            # trace_id is the FLEET-stable identity (fleettrace.py):
            # it joins this decision to the stitched timeline a uid
            # cannot (uids change per leg, trace_ids never do)
            "trace_id": getattr(req, "trace_id", None),
            "tenant": req.tenant,
            "replica": chosen.name,
            "policy": self.policy,
            "matched_tokens": matched,
            "prompt_len": req.prompt_len,
            "candidates": len(cands),
        })
        return chosen

    def _replica_load(self, rep: Replica, snap: Optional[dict] = None
                      ) -> int:
        if snap is None:
            snap = rep.engine.sched.capacity_snapshot()
        # transfer_tokens_owed: a staged cross-pool transfer owes only
        # its unmaterialized tail + decode budget (scheduler ledger),
        # but it IS load this pool will pay — count it or disagg
        # dispatch piles onto a pool whose queue merely LOOKS empty
        load = (snap["queued_tokens"] + snap["active_tokens_remaining"]
                + snap.get("transfer_tokens_owed", 0))
        if self.memory_pressure_steps > 0:
            steps = snap.get("steps_to_exhaustion")
            if steps is not None and steps <= self.memory_pressure_steps:
                load += self.memory_pressure_penalty_tokens
        return load

    def _pick_cache_aware(self, cands: List[Replica], tokens):
        """The cache-aware scoring shared by ``cache_aware`` routing
        and the disagg decode-replica pin: rank every candidate by the
        longest cached prefix it already holds — the read-only
        ``longest_prefix_len`` probe maxed with the router-side shadow
        (which covers the publication lag) — with an IMBALANCE GUARD:
        take the FIRST candidate in (match desc, owed-tokens asc,
        free+evictable pages desc, stable index) order whose load stays
        within ``affinity_slack_tokens`` of the fleet minimum. Pure
        affinity piles a hot prefix onto one replica while its peers
        idle (p99 pays the queue); pure load-balancing scatters the
        prefix and every replica pays its own cold prefill. The guard
        bounds the pile-up to a fixed token debt, and a spill warms the
        spill target's cache, so the cost is one cold prefill per guard
        trip. Records the placement in the winner's shadow and returns
        ``(matched_tokens, replica)``."""
        scored = []
        for rep in cands:
            cache = rep.engine.prefix_cache
            m = (cache.longest_prefix_len(tokens)
                 if cache is not None else 0)
            shadow = self._shadows.get(rep.name)
            if shadow is not None:
                # max(published, placed): the shadow covers the
                # publication lag, the probe is the ground truth
                m = max(m, shadow.longest_match(tokens))
            snap = rep.engine.sched.capacity_snapshot()
            headroom = snap["free_pages"] + snap["evictable_pages"]
            scored.append((-m, self._replica_load(rep, snap), -headroom,
                           rep.index, rep))
        scored.sort(key=lambda s: s[:4])
        min_load = min(s[1] for s in scored)
        best = next(s for s in scored
                    if s[1] <= min_load + self.affinity_slack_tokens)
        matched, chosen = -best[0], best[4]
        shadow = self._shadows.get(chosen.name)
        if shadow is None:
            shadow = ShadowIndex(chosen.engine.page_size)
            shadow.on_reset = lambda _s: self._m_shadow_resets.inc()
            self._shadows[chosen.name] = shadow
        shadow.insert(tokens)
        return matched, chosen

    def route_disagg(self, req: Any, prefill_replicas: List[Replica],
                     decode_replicas: List[Replica], now: float,
                     seq: Optional[int] = None):
        """Disaggregated dispatch (serving/disagg/): pick the PREFILL
        replica by least owed work among accepting replicas that can
        admit the prompt (their prefill-only ledgers reserve prompt
        pages only), and PIN the DECODE replica up front — cache-aware
        over the decode pool (longest cached prefix, shadow-covered,
        load-guarded exactly like ``cache_aware``), because the decode
        replica is where the request's KV will live and where a later
        request sharing its prefix must land. Pinning at route time is
        what makes decode-pool affinity a decision rather than
        whatever pool had a free slot when the transfer completed.
        Returns ``(prefill_replica, decode_replica)`` or ``None`` when
        either pool has no candidate right now."""
        p_cands = [rep for rep in prefill_replicas
                   if rep.accepting and rep.engine.sched.can_admit(req)]
        d_cands = [rep for rep in decode_replicas if rep.accepting]
        if not p_cands or not d_cands:
            self._m_unplaceable.inc()
            return None
        prefill = min(p_cands,
                      key=lambda rep: (self._replica_load(rep), rep.index))
        matched, decode = self._pick_cache_aware(d_cands, req.tokens)
        prefill.dispatched += 1
        decode.dispatched += 1
        self._m_decisions.inc()
        if matched:
            self._m_cache_routed.inc()
            self._m_matched.inc(matched)
        self.decisions.append({
            "t": now,
            "seq": seq,
            "trace_id": getattr(req, "trace_id", None),
            "tenant": req.tenant,
            "policy": "disagg",
            "replica": decode.name,      # the pin: where the KV lands
            "prefill_replica": prefill.name,
            "matched_tokens": matched,
            "prompt_len": req.prompt_len,
            "candidates": len(p_cands) + len(d_cands),
        })
        return prefill, decode

    def drop_replica(self, name: str) -> None:
        """Forget a drained/stopped replica's shadow (its cache is
        going away with it)."""
        self._shadows.pop(name, None)

    def clear_shadows(self) -> None:
        for shadow in self._shadows.values():
            shadow.clear()

    def stats(self) -> Dict[str, Any]:
        return {
            "policy": self.policy,
            "decisions_total": self._m_decisions.value,
            "cache_routed_total": self._m_cache_routed.value,
            "matched_tokens_total": self._m_matched.value,
            "unplaceable_total": self._m_unplaceable.value,
            "shadow_resets_total": self._m_shadow_resets.value,
            "recent_decisions": list(self.decisions)[-16:],
        }
