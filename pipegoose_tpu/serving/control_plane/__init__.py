"""Multi-replica serving control plane (ROADMAP item 3).

One ``ServingEngine`` + one ``Scheduler`` serves one slice; "millions
of users" means N independently meshed engine replicas behind one
front door. This package is that front door:

- **Cache-aware routing** (:mod:`router`): every replica owns its own
  page pool and radix prefix cache; the router probes each replica's
  cache with the read-only ``longest_prefix_len`` and routes a request
  to the replica already holding its longest cached prefix, tie-broken
  by load (queued tokens + free/evictable pages via the scheduler's
  non-mutating ``can_admit``/``capacity_snapshot`` probes). Hit rate
  becomes a placement decision, not luck.
- **Per-tenant fairness** (:mod:`tenants`): weighted fair-share
  dispatch with priority classes and deficit accounting across
  replicas; deadline shedding (PR 9) is the pressure valve. One hot
  tenant cannot starve the rest (pinned by test).
- **SLO-driven elasticity** (:mod:`autoscaler`, :mod:`replica`): the
  fleet-merged burn-rate signal (telemetry/fleet.py aggregates every
  replica's registry) adds a replica or drains one; drain = stop
  routing, preempt in-flight requests, re-admit them elsewhere through
  the existing re-prefill-hits-the-cache path — scale-down drops ZERO
  admitted work (outputs token-identical to a no-drain run, pinned).

:class:`~pipegoose_tpu.serving.control_plane.plane.ControlPlane` is
the orchestrator driving the replicas' steppable-run API tick by tick
in one host thread; ``/debug/fleet`` (telemetry/opsserver.py) serves
its live :meth:`fleet_status`. See docs/serving.md "Control plane".
"""
from pipegoose_tpu.serving.control_plane.autoscaler import (
    Autoscaler,
    AutoscalerConfig,
)
from pipegoose_tpu.serving.control_plane.plane import ControlPlane
from pipegoose_tpu.serving.control_plane.replica import Replica, ReplicaState
from pipegoose_tpu.serving.control_plane.router import Router
from pipegoose_tpu.serving.control_plane.tenants import (
    TenantLedger,
    TenantSpec,
)

__all__ = [
    "Autoscaler",
    "AutoscalerConfig",
    "ControlPlane",
    "Replica",
    "ReplicaState",
    "Router",
    "TenantLedger",
    "TenantSpec",
]
