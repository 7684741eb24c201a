"""ControlPlane: the front door over N serving-engine replicas.

Drives the replicas' steppable-run API (``start_run`` / ``tick_once``
/ ``take_finished`` / ``finish_run``) in one host thread:

    while work remains:
        autoscale            (fleet SLO burn -> add replica / drain one)
        shed expired ingress (tenant-queue deadline valve)
        dispatch             (ledger DRR batch -> router placement ->
                              replica.submit_request; migrated-out
                              requests re-place FIRST — they already
                              paid admission once)
        tick every busy replica  (each advances prefills + one decode
                                  step, exactly like a lone engine)
        collect finished     (per-tenant TTFT/e2e observation,
                              completion bookkeeping)
        progress drains      (DRAINING replica empties -> STOPPED,
                              metrics captured)

Placement is strictly read-only against the replicas (``can_admit``,
``capacity_snapshot``, ``longest_prefix_len``); the only cross-replica
state is the control plane's own (ledger queues, router log, fleet
registry). Determinism: same requests + same replica factory + same
tick schedule => same placements, same tokens (greedy parity is
per-engine; routing is lexicographic over deterministic scores).

Every replica gets its OWN ``MetricsRegistry``; ``fleet`` is the
merged view (telemetry/fleet.py) the fleet ``SLOMonitor`` and
``/debug/fleet`` read. Per-tenant TTFT/e2e land in the control plane's
registry as ``serving.tenant.<name>.*`` — ``per_tenant_slo_targets``
builds one SLO target per tenant over them.
"""
from __future__ import annotations

import math
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from pipegoose_tpu.serving.control_plane.autoscaler import Autoscaler
from pipegoose_tpu.serving.control_plane.replica import (
    Replica,
    ReplicaState,
)
from pipegoose_tpu.serving.control_plane.router import Router
from pipegoose_tpu.serving.control_plane.tenants import TenantLedger
from pipegoose_tpu.serving.engine import RequestOutput
from pipegoose_tpu.serving.kv_tier.directory import PrefixDirectory
from pipegoose_tpu.serving.scheduler import Request, Status
from pipegoose_tpu.telemetry.fleet import FleetRegistry
from pipegoose_tpu.telemetry.registry import MetricsRegistry
from pipegoose_tpu.telemetry.slo import SLOTarget


def per_tenant_slo_targets(
    tenants: Sequence[str], *,
    ttft_objective_s: float = 0.5, ttft_p: float = 0.95,
) -> List[SLOTarget]:
    """One TTFT latency target per tenant over the control plane's
    ``serving.tenant.<name>.ttft_seconds`` histograms — the per-tenant
    half of the fleet verdict (a single hot tenant breaching ITS
    target while the fleet aggregate looks fine is a fairness page,
    not a capacity one)."""
    return [
        SLOTarget(name=f"tenant_{t}_ttft",
                  metric=f"serving.tenant.{t}.ttft_seconds",
                  objective=ttft_objective_s, target=ttft_p)
        for t in tenants
    ]


#: uid block reserved per replica: replica i mints uids from
#: i * UID_STRIDE, so a salvage resubmit with ``reuse_uid`` can never
#: collide with a live uid on the survivor it lands on — the "caller
#: owns cross-scheduler uniqueness" contract Scheduler.submit states,
#: made true by construction (a replica would have to serve a million
#: requests in one process to leak into its neighbor's block).
UID_STRIDE = 1_000_000


class ControlPlane:
    """Front door over N replicas (module docstring).

    ``replica_factory(name, registry) -> ServingEngine`` builds one
    replica engine wired to ITS registry; engines must enable the
    paged prefill path (``prefix_cache=True`` and/or
    ``prefill_chunk=``) — drain migration re-admits requests that
    already hold generated tokens, which the monolithic prefill cannot
    resume. ``policy`` is the routing arm ("cache_aware" |
    "round_robin"). ``autoscaler`` (optional) consumes the fleet SLO
    monitor; without one, :meth:`scale_up` / :meth:`start_drain` are
    the operator's manual controls (and the test seam).
    """

    def __init__(self, replica_factory: Callable[[str, MetricsRegistry], Any],
                 *, n_replicas: int = 2, policy: str = "cache_aware",
                 ledger: Optional[TenantLedger] = None,
                 autoscaler: Optional[Autoscaler] = None,
                 registry: Optional[MetricsRegistry] = None,
                 stall_patience: int = 200,
                 recorder: Optional[Any] = None,
                 suspect_after_ticks: int = 5,
                 failed_after_ticks: int = 20,
                 probation_ticks: int = 8,
                 pull_hints: bool = True,
                 fleet_tracer: Optional[Any] = None,
                 memledger: bool = False,
                 goodput: Any = False):
        """``recorder``: optional ``telemetry.FlightRecorder`` — every
        replica failure dumps ONE ``replica_failure`` black box naming
        the replica and the salvaged/resubmitted/lost uids; an
        UNRECOVERED failure (admitted work lost, or no survivors) stays
        pending so ``/healthz`` flips 503. ``suspect_after_ticks`` /
        ``failed_after_ticks``: the heartbeat thresholds of the health
        state machine (ticks with work but no progress before
        SERVING->SUSPECT and ->FAILED; must satisfy suspect < failed <
        stall_patience so a single wedged replica is quarantined long
        before the whole-fleet watchdog gives up).
        ``probation_ticks``: dispatch cooldown after :meth:`rejoin`.
        ``pull_hints``: hint cross-replica KV pulls through the fleet
        prefix directory at placement (serving/kv_tier/); off, replicas
        recompute what their own cache misses —
        ``examples/control_plane_demo.py`` disables it to isolate
        placement from fleet prefix sharing.
        ``fleet_tracer``: optional ``telemetry.fleettrace.FleetTracer``
        — the plane mints a ``trace_id`` per ingress, marks every hop
        hand-over, attaches one named ``RequestTracer`` per replica
        (unless the factory attached its own), and the tracer stitches
        them into one cross-replica timeline per request (plane hops +
        replica phases == fleet e2e, the PR 8 contract fleet-wide).
        ``memledger``: attach one ``telemetry.MemoryLedger`` per
        replica (factory-attached ledgers are kept) — the fleet-minimum
        steps-to-exhaustion then feeds the autoscaler and
        ``fleet_status()`` grows a per-replica memory rollup.
        ``goodput``: ``True`` (or a ``telemetry.GoodputLedger``
        instance) attributes every replica-second of the run's wall
        into the goodput/badput taxonomy and mints one ``Incident`` per
        failure episode (telemetry/goodput.py) — ``fleet_status()``
        grows a ``goodput`` rollup, ``run()``'s metrics a ``goodput``
        row, and each ``replica_failure`` black box embeds its
        incident. Off (the default), the per-tick cost is one
        attribute read + branch."""
        if n_replicas < 1:
            raise ValueError(f"n_replicas must be >= 1, got {n_replicas}")
        if stall_patience < 1:
            raise ValueError(
                f"stall_patience must be >= 1, got {stall_patience}"
            )
        if not 1 <= suspect_after_ticks < failed_after_ticks:
            raise ValueError(
                f"need 1 <= suspect_after_ticks ({suspect_after_ticks}) "
                f"< failed_after_ticks ({failed_after_ticks})"
            )
        if failed_after_ticks >= stall_patience:
            raise ValueError(
                f"failed_after_ticks ({failed_after_ticks}) must be < "
                f"stall_patience ({stall_patience}): the fleet watchdog "
                f"must never fire before a wedged replica is quarantined"
            )
        if probation_ticks < 0:
            raise ValueError(
                f"probation_ticks must be >= 0, got {probation_ticks}"
            )
        self.replica_factory = replica_factory
        self.recorder = recorder
        self.pull_hints = pull_hints
        self.memledger = memledger
        self.fleettrace = fleet_tracer
        if (fleet_tracer is not None and recorder is not None
                and hasattr(recorder, "set_fleet_tracer")):
            recorder.set_fleet_tracer(fleet_tracer)
        self.suspect_after_ticks = suspect_after_ticks
        self.failed_after_ticks = failed_after_ticks
        self.probation_ticks = probation_ticks
        self.registry = (registry if registry is not None
                         else MetricsRegistry(enabled=True))
        # goodput wall-clock ledger (telemetry/goodput.py): True
        # constructs one publishing into the plane's registry; an
        # instance is adopted as-is (tests inject seeded ledgers)
        if goodput is True:
            from pipegoose_tpu.telemetry.goodput import GoodputLedger

            goodput = GoodputLedger(registry=self.registry)
        self.goodput = goodput or None
        self._tick = 0   # last tick seen by run() — lifecycle calls
        #                  outside the loop (rejoin/drain) stamp it
        self.router = Router(policy, registry=self.registry)
        self.ledger = ledger if ledger is not None else TenantLedger()
        self.autoscaler = autoscaler
        self.stall_patience = stall_patience
        self.fleet = FleetRegistry([("control_plane", self.registry)])
        self.replicas: List[Replica] = []
        self._next_replica = 0
        self._now: Callable[[], float] = time.perf_counter
        self._running = False
        self._started: List[Replica] = []    # replicas active this run
        self._migrated: List[Request] = []   # drain re-placement queue
        self._seq = 0                        # control-plane dispatch ids
        self._order: Dict[int, int] = {}     # id(req) -> submit order
        self._outputs: Dict[int, RequestOutput] = {}  # submit order -> out
        # crash salvage: requests flagged here re-submit with
        # reuse_uid=True (the resubmit-from-prompt degradation keeps
        # the uid its tracer timeline is keyed by)
        self._reuse: set = set()
        # unplanned capacity loss not yet compensated: +1 per replica
        # failure, -1 per scale_up/rejoin — the autoscaler's
        # "FAILED counts as capacity loss" signal
        self._capacity_gap = 0
        reg = self.registry
        self._m_replicas = reg.gauge("control_plane.replicas_serving")
        self._m_dispatched = reg.counter("control_plane.dispatched_total")
        self._m_migrated = reg.counter("control_plane.migrated_total")
        self._m_drains = reg.counter("control_plane.drains_total")
        self._m_scaleups = reg.counter("control_plane.scaleups_total")
        self._m_shed = reg.counter("control_plane.shed_total")
        self._m_failures = reg.counter("serving.fleet.failures_total")
        self._m_salvaged = reg.counter("serving.fleet.salvaged_total")
        self._m_resubmitted = reg.counter("serving.fleet.resubmitted_total")
        self._m_lost = reg.counter("serving.fleet.lost_total")
        # fleet prefix directory (serving/kv_tier/): which replica
        # holds which prefix, HBM or host tier — created lazily from
        # the first cached replica's page_size; None when the fleet
        # runs cache-less
        self.directory: Optional[PrefixDirectory] = None
        for _ in range(n_replicas):
            self._add_replica()

    # -- replica lifecycle -------------------------------------------------

    def _add_replica(self) -> Replica:
        name = f"replica{self._next_replica}"
        self._next_replica += 1
        reg = MetricsRegistry(enabled=True)
        engine = self.replica_factory(name, reg)
        if not getattr(engine, "_paged_prefill", False):
            raise ValueError(
                f"replica {name!r}: control-plane engines need the paged "
                f"prefill path (prefix_cache=True and/or prefill_chunk=) — "
                f"drain migration re-admits requests holding generated "
                f"tokens, which monolithic prefill cannot resume"
            )
        rep = Replica(name, engine, registry=reg, index=self._next_replica - 1)
        # fleet-unique uid blocks (see UID_STRIDE): outputs are keyed by
        # submit ORDER so this changes nothing user-visible, but tracer
        # timelines and reuse_uid salvage stay collision-free fleet-wide
        engine.sched._next_uid = max(engine.sched._next_uid,
                                     rep.index * UID_STRIDE)
        if engine.prefix_cache is not None:
            if self.directory is None:
                self.directory = PrefixDirectory(engine.page_size)
            directory = self.directory

            def _publish(tokens, location, _name=name, _dir=directory):
                _dir.publish(_name, tokens, location)

            engine.on_prefix_publish = _publish
        if self.memledger and getattr(engine, "memledger", None) is None:
            from pipegoose_tpu.telemetry.memledger import MemoryLedger

            engine.attach_memledger(MemoryLedger())
        if self.fleettrace is not None:
            # one NAMED RequestTracer per replica (fragments the
            # stitcher seals/joins); a factory-attached tracer is kept
            # — shared-tracer fleets still stitch via the composite
            # (trace_id, uid) timeline key
            tracer = getattr(engine, "tracer", None)
            if tracer is None:
                from pipegoose_tpu.telemetry.reqtrace import RequestTracer

                tracer = RequestTracer(registry=reg, name=name)
                engine.attach_tracer(tracer)
            elif getattr(tracer, "name", None) is None:
                tracer.name = name
            self.fleettrace.register_replica(name, tracer)
        self.replicas.append(rep)
        self.fleet.add_member(name, reg)
        if self._running:
            engine.start_run((), now=self._now)
            self._started.append(rep)
            if self.goodput is not None:
                # mid-run scale-up: the account's alive wall starts NOW
                self.goodput.touch(name, self._now(), "serving",
                                   self._tick)
        self._m_replicas.set(float(len(self.serving_replicas())))
        return rep

    def serving_replicas(self) -> List[Replica]:
        return [r for r in self.replicas
                if r.state is ReplicaState.SERVING]

    def failed_replicas(self) -> List[Replica]:
        return [r for r in self.replicas
                if r.state is ReplicaState.FAILED]

    def scale_up(self) -> Replica:
        """Add one replica (autoscaler "up", or the operator). The new
        engine compiles its programs on first use — on real fleets the
        factory hands back a pre-warmed engine. Closes one unit of
        unplanned capacity gap when a failure opened one."""
        closed_gap = self._capacity_gap > 0
        rep = self._add_replica()
        self._m_scaleups.inc()
        self._capacity_gap = max(0, self._capacity_gap - 1)
        if self.goodput is not None and closed_gap:
            # replacement capacity is accepting: the OLDEST open
            # incident's MTTR window closes here
            self.goodput.resolve_incident(None, self._tick, self._now(),
                                          "scale_up")
        return rep

    def rejoin(self, name: str, *,
               probation_ticks: Optional[int] = None) -> Replica:
        """Bring a FAILED replica back: clear its injected fault, flip
        it to SERVING **on probation** (ticked, but not routed fresh
        ingress for ``probation_ticks``), and restart its steppable run
        when one is live. The replica's scheduler must be empty —
        salvage emptied it on the clean path; residue means the failure
        left state this rejoin cannot trust."""
        match = [r for r in self.replicas if r.name == name]
        if not match:
            raise ValueError(f"no replica named {name!r}")
        rep = match[0]
        sched = rep.engine.sched
        if (rep.salvage_degraded or not sched.all_done()
                or sched._outstanding_total != 0 or sched.transfers):
            # a CLEAN salvage leaves all of these empty; the degraded
            # path scrubs slots/queue by hand, so all_done() alone
            # would wave a corrupted admission ledger back in
            raise ValueError(
                f"replica {name!r} still holds scheduler state (or its "
                f"salvage was degraded) — a partially salvaged failure "
                f"cannot rejoin (replace it with scale_up instead)"
            )
        rep.engine.inject_fault(None)
        if self.goodput is not None:
            # book the quarantine dwell up to this very moment, then
            # close the replica's incident: MTTR = detection -> HERE
            t_rejoin = self._now()
            self.goodput.touch(rep.name, t_rejoin, rep.state.value,
                               self._tick)
            self.goodput.resolve_incident(rep.name, self._tick,
                                          t_rejoin, "rejoin")
        rep.rejoin(self.probation_ticks if probation_ticks is None
                   else probation_ticks, tick=self._tick)
        self._capacity_gap = max(0, self._capacity_gap - 1)
        if self._running and not rep.engine.run_in_progress:
            rep.engine.start_run((), now=self._now)
            if rep not in self._started:
                self._started.append(rep)
        self._m_replicas.set(float(len(self.serving_replicas())))
        return rep

    def start_drain(self, name: Optional[str] = None) -> Replica:
        """Begin draining one replica (autoscaler "down", or the
        operator): routing stops immediately, its requests migrate to
        the re-placement queue (dispatched ahead of fresh ingress next
        tick), and the replica stops once empty. Defaults to the
        SERVING replica with the least work owed — the cheapest
        drain."""
        serving = self.serving_replicas()
        if len(serving) <= 1:
            raise ValueError("cannot drain the last serving replica")
        if name is None:
            def owed(rep: Replica) -> Tuple[int, int]:
                snap = rep.engine.sched.capacity_snapshot()
                return (snap["queued_tokens"]
                        + snap["active_tokens_remaining"], rep.index)
            rep = min(serving, key=owed)
        else:
            match = [r for r in serving if r.name == name]
            if not match:
                raise ValueError(f"no serving replica named {name!r}")
            rep = match[0]
        migrated = rep.start_drain(tick=self._tick)
        self.router.drop_replica(rep.name)
        if self.directory is not None:
            self.directory.retract_replica(rep.name)
        if self.fleettrace is not None:
            t_leave = self._now()
            for req in migrated:
                self.fleettrace.on_leave(req, rep.name, t_leave, "drain")
        self._migrated.extend(migrated)
        self._m_migrated.inc(len(migrated))
        self._m_drains.inc()
        self._m_replicas.set(float(len(self.serving_replicas())))
        return rep

    def clear_prefix_caches(self) -> None:
        """Drop every live replica's unpinned cached pages — the demo
        and test seam for measuring a COLD-cache trace on warm-compiled
        engines (routing decides the hit rate only while caches are
        filling; a fully warmed fleet hits everywhere under any
        policy)."""
        for rep in self.replicas:
            if (rep.state is not ReplicaState.STOPPED
                    and rep.engine.prefix_cache is not None):
                rep.engine.prefix_cache.clear()
                if rep.engine.host_tier is not None:
                    rep.engine.host_tier.clear()
        self.router.clear_shadows()
        if self.directory is not None:
            self.directory.clear()

    # -- ingress -----------------------------------------------------------

    def submit(self, req: Request, now: float) -> None:
        """Accept one request into the tenant ledger. The control plane
        stamps the submit time (``Scheduler.submit`` preserves it — the
        user-visible clock starts HERE, not at replica dispatch)."""
        if req.t_submit is None:
            req.t_submit = now
        if self.fleettrace is not None:
            # the trace's t0 is the SAME float as req.t_submit — the
            # stitched sum's left edge and the user-visible clock start
            # are one number, which is what makes the conservation
            # contract exact rather than approximate
            self.fleettrace.on_ingress(req, req.t_submit)
        self._order[id(req)] = len(self._order)
        self.ledger.submit(req)

    # -- the loop ----------------------------------------------------------

    def _dispatchable(self, rep: Replica, tick: int) -> bool:
        """The health-aware dispatch gate: SERVING (past probation)
        flows freely; SUSPECT is PROBED with exponential backoff (ONE
        routed request per probe window — the retry that discovers
        recovery without piling fresh work on a maybe-dead replica);
        FAILED/DRAINING/STOPPED never receive work."""
        if rep.state is ReplicaState.SERVING:
            return rep.probation_ticks_left == 0
        if rep.state is ReplicaState.SUSPECT:
            return rep.probe_allowed(tick)
        return False

    def _place(self, req: Request, rep: Replica, cands: List[Replica],
               tick: int) -> List[Replica]:
        """Submit ``req`` on ``rep`` and return the candidate set for
        the REST of this tick: placing on a SUSPECT replica consumes
        its probe window (backoff doubles) and removes it from the
        remaining candidates — one probe request per window, never a
        whole batch piled onto a maybe-dead replica."""
        rep.engine.submit_request(
            req, reuse_uid=id(req) in self._reuse
        )
        self._reuse.discard(id(req))
        rep.inflight[id(req)] = req
        if self.fleettrace is not None:
            self.fleettrace.on_dispatched(req, rep.name)
        if (self.pull_hints and self.directory is not None
                and rep.engine.kv_tier is not None):
            # fleet prefix sharing: when a PEER holds a longer prefix
            # than this replica could have, hint the pull — the
            # engine's pre-admission intercept verifies the peer's
            # actual inventory (the directory may be stale; a stale
            # hint costs one read-only probe, never correctness)
            m, holder, _loc = self.directory.longest_holder(
                req.tokens, exclude=rep.name
            )
            if holder is not None and m > 0:
                peer = self._peer_engine(holder)
                if peer is not None and peer is not rep.engine:
                    rep.engine.kv_tier.hint_pull(req, peer)
                    tracer = getattr(rep.engine, "tracer", None)
                    if tracer is not None:
                        # name the pull SOURCE on the timeline — the
                        # merged Perfetto export draws its arrow from
                        # this event's peer to the import completion
                        tracer.annotate(req, "pull_hint", peer=holder,
                                        matched_tokens=int(m))
        if rep.state is ReplicaState.SUSPECT:
            rep.note_probe(tick)
            return [c for c in cands if c is not rep]
        return cands

    def _peer_engine(self, name: str):
        """Live engine for a directory-named replica (pull source).
        FAILED/STOPPED replicas never serve pulls — their pages are
        gone or untrustworthy."""
        for rep in self.replicas:
            if rep.name == name and rep.state in (ReplicaState.SERVING,
                                                  ReplicaState.SUSPECT,
                                                  ReplicaState.DRAINING):
                return rep.engine
        return None

    def _dispatch(self, now: float, tick: int) -> int:
        """Place migrated/salvaged requests first, then one DRR batch
        of fresh ingress. A request no replica can admit right now goes
        back where it came from and retries next tick."""
        cands = [rep for rep in self.replicas
                 if self._dispatchable(rep, tick)]
        placed = 0
        if self.fleettrace is not None:
            self.fleettrace.on_dispatch_pass(now)
        still: List[Request] = []
        for req in self._migrated:
            rep = self.router.route(req, cands, now, seq=self._seq)
            if rep is None:
                still.append(req)
                continue
            self._seq += 1
            if self.fleettrace is not None:
                self.fleettrace.on_routed(req, now, rep.name)
            cands = self._place(req, rep, cands, tick)
            placed += 1
        self._migrated = still
        if self._migrated:
            return placed   # re-placement backlog first, fresh traffic waits
        # fresh-batch sizing counts HEALTHY capacity only: a suspect's
        # free slots must not inflate the DRR batch it may never serve
        free_slots = sum(
            rep.engine.sched.capacity_snapshot()["free_slots"]
            for rep in cands if rep.state is ReplicaState.SERVING
        )
        if free_slots < 1:
            return placed
        batch = self.ledger.next_batch(free_slots)
        if self.fleettrace is not None:
            for req in batch:
                self.fleettrace.on_ledger_pop(req, now)
        for i, req in enumerate(batch):
            rep = self.router.route(req, cands, now, seq=self._seq)
            if rep is None:
                # requeue the WHOLE unplaced tail, not just the failed
                # head — every batch member was already popped from its
                # tenant FIFO, so dropping one here would silently lose
                # the request (reversed: requeue_front prepends, so the
                # original FIFO order survives)
                for r in reversed(batch[i:]):
                    self.ledger.requeue_front(r)
                break
            self._seq += 1
            if self.fleettrace is not None:
                self.fleettrace.on_routed(req, now, rep.name)
            cands = self._place(req, rep, cands, tick)
            self._m_dispatched.inc()
            placed += 1
        return placed

    def _seq_for(self, req: Request) -> int:
        """Submit-order index for ``req`` — tolerant of carryovers: a
        request stranded by an ABORTED previous run (still queued on a
        replica or in the ledger) drains during the next run and gets
        appended past that run's own submit order instead of KeyError-
        ing the bookkeeping."""
        seq = self._order.get(id(req))
        if seq is None:
            seq = len(self._order)
            self._order[id(req)] = seq
        return seq

    def _observe_finished(self, req: Request, out: RequestOutput) -> None:
        reg = self.registry
        tenant = req.tenant or "default"
        self.ledger.record_done(req)
        reg.counter(f"serving.tenant.{tenant}.requests_total").inc()
        if out.finish_reason == "shed":
            reg.counter(f"serving.tenant.{tenant}.shed_total").inc()
        if out.ttft_s is not None:
            reg.histogram(f"serving.tenant.{tenant}.ttft_seconds").observe(
                out.ttft_s
            )
        if out.finish_reason != "shed":
            reg.histogram(
                f"serving.tenant.{tenant}.e2e_latency_seconds"
            ).observe(out.e2e_latency_s)
        if self.fleettrace is not None:
            self.fleettrace.on_finished(req, out)
        self._outputs[self._seq_for(req)] = out

    def _shed_expired(self, now: float) -> None:
        for req in self.ledger.shed_expired(now):
            self._m_shed.inc()
            if self.fleettrace is not None:
                self.fleettrace.on_plane_shed(req, req.t_done)
            tenant = req.tenant or "default"
            self.registry.counter(
                f"serving.tenant.{tenant}.requests_total").inc()
            self.registry.counter(
                f"serving.tenant.{tenant}.shed_total").inc()
            e2e = req.t_done - req.t_submit
            seq = self._seq_for(req)
            # ledger-shed requests never reached a scheduler, so they
            # have no replica uid — a UNIQUE negative sentinel keeps
            # the uid-keyed conventions of engine outputs intact
            self._outputs[seq] = RequestOutput(
                uid=-(seq + 1), prompt=np.asarray(req.prompt),
                generated=np.asarray(req.generated, np.int64),
                finish_reason="shed", queue_latency_s=e2e, ttft_s=None,
                decode_tokens_per_s=None, e2e_latency_s=e2e,
                tenant=req.tenant,
            )

    # -- unplanned failure: detection fan-in + in-flight salvage -----------

    def _output_from(self, req: Request) -> RequestOutput:
        """Plane-side output builder for a request that FINISHED on a
        replica whose run can no longer build it (the engine was
        aborted by the failure path) — mirrors the engine's own
        ``_build_output`` arithmetic."""
        e2e = req.t_done - req.t_submit
        if req.finish_reason == "shed":
            return RequestOutput(
                uid=req.uid, prompt=np.asarray(req.prompt),
                generated=np.asarray(req.generated, np.int64),
                finish_reason="shed", queue_latency_s=e2e, ttft_s=None,
                decode_tokens_per_s=None, e2e_latency_s=e2e,
                tenant=req.tenant,
            )
        decode_s = max(req.t_done - req.t_admit, 1e-9)
        return RequestOutput(
            uid=req.uid, prompt=np.asarray(req.prompt),
            generated=np.asarray(req.generated, np.int64),
            finish_reason=req.finish_reason,
            queue_latency_s=req.t_admit - req.t_submit,
            ttft_s=(req.t_first_token - req.t_submit
                    if req.t_first_token is not None else None),
            decode_tokens_per_s=len(req.generated) / decode_s,
            e2e_latency_s=e2e, tenant=req.tenant,
        )

    def _salvage_reset(self, req: Request, sched: Any) -> None:
        """Resubmit-from-prompt degradation: the request's scheduler-
        side state is unreachable (harvest raised), so scrub what we
        can reach, DROP the harvested tokens (greedy determinism
        re-emits them token-identically from the prompt) and flag the
        request for a reuse_uid re-submission. Every step is
        best-effort — the scheduler may be arbitrarily broken."""
        try:
            if req.slot is not None and sched.slots[req.slot] is req:
                sched.slots[req.slot] = None
        except Exception:  # noqa: BLE001 - dead scheduler, best effort
            pass
        try:
            sched.queue.remove(req)
        except Exception:  # noqa: BLE001
            pass
        req.generated = []
        req.clear_residency()
        self._reuse.add(id(req))

    def _fail_replica(self, rep: Replica, tick: int, reason: str) -> None:
        """Quarantine ``rep`` and salvage its admitted work: mark
        FAILED, drop its router shadow, best-effort abort its run, then
        harvest every request the PLANE knows it owns (``rep.inflight``
        — independent of the dead scheduler) and re-queue them ahead of
        fresh ingress. Per request: finished-but-untaken ones emit
        their output directly; live ones preempt/withdraw cleanly
        (pages released, generated tokens kept — the re-prefill path
        resumes at the pending token, token-identical); a request whose
        scheduler state is unreachable degrades to resubmit-from-prompt
        with ``reuse_uid`` (still token-identical by greedy
        determinism, wait books as stall). One ``replica_failure``
        black box names the replica, every uid by disposition, and the
        router verdict; a fully recovered failure (nothing lost,
        survivors serving) consumes its own trigger so ``/healthz``
        flips only on an UNRECOVERED failure."""
        rep.mark_failed(reason, tick=tick)
        self.router.drop_replica(rep.name)
        if self.directory is not None:
            self.directory.retract_replica(rep.name)
        self._m_failures.inc()
        self._capacity_gap += 1
        try:
            rep.engine.abort_run()
        except Exception:  # noqa: BLE001 - best effort on a dead engine
            pass
        sched = rep.engine.sched
        salvaged: List[int] = []
        resubmitted: List[int] = []
        completed: List[int] = []
        lost: List[int] = []
        harvest = sorted(rep.inflight.values(), key=self._seq_for)
        for req in harvest:
            try:
                if req.status is Status.DONE and req.finish_reason:
                    # finished before the crash, output never taken
                    self._observe_finished(req, self._output_from(req))
                    completed.append(req.uid)
                    continue
                if req.status in (Status.PREFILL, Status.DECODE):
                    sched.preempt(req)
                if req.status is Status.QUEUED:
                    sched.withdraw(req)
                salvaged.append(req.uid)
            except Exception:  # noqa: BLE001 - unreachable state path
                rep.salvage_degraded = True   # rejoin refuses from here
                try:
                    self._salvage_reset(req, sched)
                    resubmitted.append(req.uid)
                except Exception:  # noqa: BLE001 - truly gone
                    lost.append(req.uid)
                    if self.fleettrace is not None:
                        self.fleettrace.on_lost(req, self._now())
                    continue
            if self.fleettrace is not None:
                # seal the fragment on the dead replica: its wait to
                # re-route books as the salvage hop from here
                self.fleettrace.on_leave(req, rep.name, self._now(),
                                         "salvage")
            self._migrated.append(req)
        rep.inflight.clear()
        rep.salvaged_out += len(salvaged) + len(resubmitted)
        self._m_salvaged.inc(len(salvaged))
        self._m_resubmitted.inc(len(resubmitted))
        self._m_lost.inc(len(lost))
        self._m_replicas.set(float(len(self.serving_replicas())))
        incident = None
        if self.goodput is not None:
            # one Incident per failure episode, joined to the
            # chaos.injection ring for detection latency; it stays open
            # (capacity-gap integral accruing per tick) until rejoin or
            # scale_up closes its MTTR window
            incident = self.goodput.open_incident(
                "wedge" if reason.startswith("wedged") else "crash",
                rep.name, tick, self._now(), reason=reason,
                recorder=self.recorder,
                injection_kinds=("replica_crash", "replica_wedge"),
                salvaged_uids=salvaged, resubmitted_uids=resubmitted,
                completed_uids=completed, lost_uids=lost,
                capacity_gap=self._capacity_gap,
            )
        if self.recorder is None:
            return
        recovered = not lost and bool(self.serving_replicas())
        # an EARLIER unconsumed trigger (a previous unrecovered failure,
        # a decode stall...) must survive this dump: fire_trigger
        # overwrites last_trigger, and the recovered path below would
        # otherwise consume-and-clear a problem that is still real
        pending = self.recorder.last_trigger
        exemplar = None
        if self.fleettrace is not None:
            try:
                # the slowest completed fleet trace, dominant hop named
                # — so the box answers "what does this failure COST"
                # with a concrete request instead of bare counts
                exemplar = self.fleettrace.exemplar("e2e")
            except Exception:  # noqa: BLE001 - forensics must not raise
                exemplar = None
        trig = self.recorder.fire_trigger(
            "replica_failure",
            f"replica {rep.name} failed at tick {tick}: {reason} — "
            f"salvaged {len(salvaged)}, resubmitted {len(resubmitted)}, "
            f"completed {len(completed)}, lost {len(lost)}",
            tick,
            details={
                "replica": rep.name,
                "reason": reason,
                "exemplar": exemplar,
                "salvaged_uids": salvaged,
                "resubmitted_uids": resubmitted,
                "completed_uids": completed,
                "lost_uids": lost,
                "recovered": recovered,
                "incident": (incident.as_dict()
                             if incident is not None else None),
                "router": {
                    "verdict": "quarantined",
                    "shadow_dropped": True,
                    "serving_replicas": [
                        r.name for r in self.serving_replicas()
                    ],
                },
            },
        )
        if recovered and self.recorder.last_trigger is trig:
            # the black box stays on disk; only the PENDING flag (the
            # /healthz signal) clears — admitted work is safe on the
            # survivors, so the fleet is degraded, not down. An earlier
            # still-pending trigger is put back, not discarded.
            self.recorder.take_trigger()
            if pending is not None:
                self.recorder.last_trigger = pending

    def _fleet_memory_steps(self) -> Optional[float]:
        """Fleet MINIMUM of the per-replica steps-to-exhaustion
        forecast — the autoscaler's memory capacity signal. None when
        no serving replica has a ledger attached or every forecast is
        still infinite (no consumption trend yet)."""
        steps = [
            ml.steps_to_exhaustion
            for rep in self.serving_replicas()
            if (ml := getattr(rep.engine, "memledger", None)) is not None
        ]
        finite = [s for s in steps if not math.isinf(s)]
        return min(finite) if finite else None

    def _autoscale(self, tick: int, now: float) -> None:
        if self.autoscaler is None:
            return
        decision = self.autoscaler.decide(
            tick, len(self.serving_replicas()),
            # a prior drain's unplaced refugees count as backlog too:
            # draining ANOTHER replica while they wait is exactly the
            # churn the backlog guard exists to prevent
            self.ledger.pending() + len(self._migrated),
            now=now,
            n_failed=self._capacity_gap,
            memory_steps=self._fleet_memory_steps(),
        )
        if decision == "up":
            self.scale_up()
        elif decision == "down" and len(self.serving_replicas()) > 1:
            self.start_drain()

    def _busy(self) -> bool:
        return (bool(self._migrated) or self.ledger.pending() > 0
                or any(rep.busy for rep in self.replicas))

    def run(self, requests: Sequence[Request], now=time.perf_counter,
            tick_hook=None):
        """Serve ``requests`` across the fleet to completion; returns
        (outputs in submit order, fleet-metrics dict).
        ``tick_hook(plane, tick)`` is the orchestration seam (tests and
        benches force drains/scale-ups mid-run through it)."""
        if self._running:
            raise RuntimeError("control plane is already running")
        self._now = now
        if self.fleettrace is not None:
            self.fleettrace.set_clock(now)
        self._running = True
        self._outputs = {}
        self._order = {}
        self._migrated = []
        self._reuse = set()
        t0 = now()
        try:
            self._started = [rep for rep in self.replicas
                             if rep.state not in (ReplicaState.STOPPED,
                                                  ReplicaState.FAILED)]
            gp = self.goodput
            for rep in self._started:
                rep.engine.start_run((), now=now)
            if gp is not None:
                # alive wall opens at run start for every participant
                # (existing accounts book the between-runs gap into the
                # class their current state implies)
                t_open = now()
                for rep in self._started:
                    gp.touch(rep.name, t_open, rep.state.value, 0)
            for req in requests:
                self.submit(req, now())
            tick = 0
            idle_ticks = 0
            while self._busy():
                tick += 1
                self._tick = tick
                if tick_hook is not None:
                    tick_hook(self, tick)
                self._autoscale(tick, now())
                self._shed_expired(now())
                placed = self._dispatch(now(), tick)
                progressed = placed > 0
                marks = [] if gp is not None else None
                for rep in self.replicas:
                    if rep.state in (ReplicaState.STOPPED,
                                     ReplicaState.FAILED):
                        if (gp is not None
                                and rep.state is ReplicaState.FAILED):
                            # quarantined replicas burn wall too — the
                            # taxonomy is exhaustive over ALIVE
                            # replicas, and FAILED is alive-but-useless
                            marks.append((rep, "failed_quarantine"))
                        continue
                    if rep.probation_ticks_left > 0:
                        rep.probation_ticks_left -= 1
                    eng = rep.engine
                    pre = gp.pre_tick(rep) if gp is not None else None
                    had_work = not eng.sched.all_done()
                    ticked = False
                    if had_work:
                        try:
                            ticked = eng.tick_once()
                        except Exception as e:  # noqa: BLE001 - crash
                            # detection: ReplicaFault (the seam), the
                            # engine's own stall watchdog, anything
                            # escaping a replica tick — quarantine +
                            # salvage instead of taking the fleet down
                            self._fail_replica(
                                rep, tick,
                                f"tick_once raised "
                                f"{type(e).__name__}: {e}",
                            )
                            progressed = True  # handling IS progress
                            if gp is not None:
                                marks.append((rep, "failed_quarantine"))
                            continue
                    took = False
                    for req, out in eng.take_finished():
                        rep.inflight.pop(id(req), None)
                        self._observe_finished(req, out)
                        took = True
                    if ticked or took:
                        rep.note_progress(tick)
                        progressed = True
                    elif had_work:
                        # heartbeat miss with work pending: the wedge
                        # ladder (SERVING -> SUSPECT -> FAILED)
                        n = rep.note_no_progress()
                        if n >= self.failed_after_ticks:
                            self._fail_replica(
                                rep, tick,
                                f"wedged: no progress for {n} ticks "
                                f"with work pending",
                            )
                            progressed = True
                        elif n >= self.suspect_after_ticks:
                            rep.mark_suspect(tick)
                    rep.maybe_stop(tick)
                    if gp is not None:
                        marks.append(
                            (rep, gp.classify(rep, pre, had_work,
                                              ticked, took)))
                        kvt = getattr(eng, "kv_tier", None)
                        if (kvt is not None
                                and kvt.fallbacks > pre[2]):
                            gp.note_transfer_flap(
                                rep.name, tick, now(),
                                kvt.fallbacks - pre[2],
                                recorder=self.recorder,
                            )
                self._goodput_flush(marks, tick, now)
                if progressed:
                    idle_ticks = 0
                else:
                    idle_ticks += 1
                    if idle_ticks >= self.stall_patience:
                        raise RuntimeError(
                            f"control-plane stall: {self.ledger.pending()} "
                            f"queued + {len(self._migrated)} migrated "
                            f"requests, no replica made progress for "
                            f"{self.stall_patience} ticks"
                        )
            per_replica: Dict[str, dict] = {}
            for rep in self._started:
                if rep.engine.run_in_progress:
                    # drain any completion the last tick left behind
                    # before closing the run
                    for req, out in rep.engine.take_finished():
                        rep.inflight.pop(id(req), None)
                        self._observe_finished(req, out)
                    _, metrics = rep.engine.finish_run()
                    per_replica[rep.name] = metrics
                elif rep.final_metrics is not None:
                    per_replica[rep.name] = rep.final_metrics
        except BaseException:
            # the stall watchdog (or a raising tick_hook) must not
            # wedge the fleet: abort every replica's steppable run so a
            # retry can start_run again — best-effort PER replica (one
            # raising abort_run must not skip the rest, or they wedge
            # forever on "run already in progress")
            for rep in self._started:
                try:
                    rep.engine.abort_run()
                except Exception:  # noqa: BLE001 - teardown best effort
                    pass
            raise
        finally:
            self._running = False
        wall = max(now() - t0, 1e-9)
        outputs = [self._outputs[i] for i in sorted(self._outputs)]
        generated = sum(len(o.generated) for o in outputs)
        metrics = {
            "wall_time_s": round(wall, 6),
            "requests": len(outputs),
            "generated_tokens": generated,
            "decode_tokens_per_s": round(generated / wall, 2),
            # the fleet FLOP meter: prompt tokens actually forwarded
            # through any replica's prefill — cache-aware routing's
            # acceptance metric (fewer forwarded tokens, same output)
            "prefill_tokens": sum(
                m.get("prefill_tokens", 0) for m in per_replica.values()
            ),
            "shed_requests": sum(
                1 for o in outputs if o.finish_reason == "shed"
            ),
            "per_replica": per_replica,
            "router": self.router.stats(),
            "tenants": self.ledger.stats(),
        }
        if self.directory is not None:
            metrics["kv_directory"] = self.directory.stats()
        if self.autoscaler is not None:
            metrics["autoscaler"] = list(self.autoscaler.log)
        if self.goodput is not None:
            self.goodput.publish()
            metrics["goodput"] = self.goodput.summary()
        return outputs, metrics

    def _goodput_flush(self, marks, tick: int, now) -> None:
        """Book one tick's wall into the goodput ledger: every mark is
        (replica, class) and each replica's share is the wall since ITS
        last mark — the telescoping sum that makes conservation exact.
        Ledger off => one attribute load + compare (the <5 µs guard)."""
        if self.goodput is None:
            return
        gp = self.goodput
        t_mark = now()
        for rep, klass in marks:
            gp.account(rep.name, t_mark, klass, rep.state.value, tick)
        gp.on_tick(tick, t_mark)

    # -- observability -----------------------------------------------------

    def fleet_memory(self) -> Optional[Dict[str, Any]]:
        """Fleet memory rollup: each replica's ledger condensed to the
        numbers an operator pages on — per-class pages, conservation
        verdict, leak tally, exhaustion forecast, host-tier bytes —
        plus fleet aggregates (total bytes by class, the minimum
        forecast, whether ANY replica ever broke conservation). None
        when no replica carries a ledger."""
        per: Dict[str, Any] = {}
        totals: Dict[str, int] = {}
        for rep in self.replicas:
            ml = getattr(rep.engine, "memledger", None)
            if ml is None:
                continue
            c = ml.counts()
            cons = ml.conservation()
            steps = ml.steps_to_exhaustion
            per[rep.name] = {
                "classes_pages": c,
                "bytes_per_page": ml.bytes_per_page,
                "conservation_ok": cons["ok"],
                "conservation_failures": ml.conservation_failures,
                "leaks": (len(ml.last_audit["leaks"])
                          if ml.last_audit else 0),
                "mismatched_releases": ml.mismatched_releases,
                "steps_to_exhaustion": (None if math.isinf(steps)
                                        else steps),
                "fragmentation": round(ml.pool.fragmentation(), 4),
                "host_tier_bytes": (ml.host_tier.resident_bytes
                                    if ml.host_tier is not None else None),
            }
            for k, v in c.items():
                totals[k] = totals.get(k, 0) + v * ml.bytes_per_page
        if not per:
            return None
        return {
            "replicas": per,
            "total_bytes_by_class": totals,
            "min_steps_to_exhaustion": self._fleet_memory_steps(),
            "conservation_ok": all(r["conservation_ok"]
                                   for r in per.values()),
            "conservation_failures": sum(r["conservation_failures"]
                                         for r in per.values()),
            "leaks": sum(r["leaks"] for r in per.values()),
        }

    def fleet_status(self) -> Dict[str, Any]:
        """The ``/debug/fleet`` payload: per-replica state + load,
        router stats, per-tenant ledger shares, autoscaler audit log,
        memory-ledger rollup — everything JSON-able, snapshot-style."""
        rows = [rep.status() for rep in self.replicas]
        if self.goodput is not None:
            for row in rows:
                row["state_seconds"] = self.goodput.state_seconds(
                    row["name"])
        return {
            "replicas": rows,
            "serving": len(self.serving_replicas()),
            "failed": len(self.failed_replicas()),
            "capacity_gap": self._capacity_gap,
            "router": self.router.stats(),
            "kv_directory": (self.directory.stats()
                             if self.directory is not None else None),
            "tenants": self.ledger.stats(),
            "migrated_pending": len(self._migrated),
            "autoscaler": (list(self.autoscaler.log)
                           if self.autoscaler is not None else None),
            "memory": self.fleet_memory(),
            "goodput": (self.goodput.summary()
                        if self.goodput is not None else None),
        }
