"""The search driver: enumerate -> AOT-compile -> score -> rank.

One candidate evaluation is exactly one mesh-doctor inspection
(telemetry/doctor.py ``diagnose`` — a shape-only lower+compile on fake
host devices, nothing executes) scored through the static cost model
(planner/cost.py). The driver owns the bookkeeping the acceptance bar
demands: an infeasible candidate is PRUNED WITH A REASON and counted
(``planner.pruned_infeasible`` gauge + a log line), never silently
dropped; a candidate whose build/compile raises becomes a pruned row
carrying the exception, so one broken config cannot abort a 30-config
search.

The model side is a builder object (duck-typed; see
``planner/bloom_builder.py``):

- ``builder.describe() -> dict`` — model metadata for the artifact;
- ``builder.tokens_per_step -> int`` — the global batch every
  candidate is scored on;
- ``builder.validity(candidate) -> Optional[str]`` — cheap
  model-divisibility checks, a reason string prunes;
- ``builder.build(candidate)`` — context manager yielding the dict
  ``diagnose`` needs (step, args, intended, labels, mesh,
  bubble_fraction), releasing its mesh/context on exit.
"""
from __future__ import annotations

import logging
from typing import Any, Iterable, Optional

from pipegoose_tpu.planner.cost import CostModel, hbm_check, score_breakdown
from pipegoose_tpu.planner.report import CandidateResult, PlanReport
from pipegoose_tpu.planner.space import Candidate, enumerate_candidates
from pipegoose_tpu.telemetry import doctor

logger = logging.getLogger("pipegoose_tpu.planner")

# the most recent PlanReport produced by run_plan in this process —
# what the ops server's /debug/plan serves when wired to
# last_plan_report (the CLI and ElasticRecovery's
# planner-backed replan both route through run_plan, so one cache
# covers every producer)
_LAST_PLAN_REPORT: Optional[PlanReport] = None


def last_plan_report() -> Optional[PlanReport]:
    """The newest :class:`PlanReport` this process produced (None until
    the first ``run_plan``) — pass ``plan=last_plan_report`` to
    ``OpsServer`` for a live ``/debug/plan``."""
    return _LAST_PLAN_REPORT


def evaluate_candidate(
    builder: Any,
    candidate: Candidate,
    cost_model: CostModel,
    keep_doctor: bool = True,
) -> CandidateResult:
    """Score one candidate: validity -> shape-only compile -> doctor ->
    HBM feasibility -> cost breakdown. Never raises for a bad
    candidate — failures become pruned rows with the reason."""
    reason = builder.validity(candidate)
    if reason is not None:
        return CandidateResult(candidate=candidate, feasible=False,
                               prune_reason=reason)
    try:
        with builder.build(candidate) as built:
            report = doctor.diagnose(
                built["step"], *built["args"],
                intended=built.get("intended"),
                labels=built.get("labels"),
                mesh=built.get("mesh"),
            )
            bubble = float(built.get("bubble_fraction", 0.0))
    except Exception as e:  # noqa: BLE001 - one config must not kill the search
        return CandidateResult(
            candidate=candidate, feasible=False,
            prune_reason=f"build/compile failed: {type(e).__name__}: {e}"[:300],
        )
    hbm_reason = hbm_check(report, cost_model)
    if hbm_reason is not None:
        return CandidateResult(
            candidate=candidate, feasible=False, prune_reason=hbm_reason,
            doctor=report if keep_doctor else None,
        )
    breakdown = score_breakdown(
        candidate, report, cost_model,
        tokens_per_step=builder.tokens_per_step,
        bubble_fraction=bubble,
    )
    return CandidateResult(
        candidate=candidate, feasible=True,
        score=float(breakdown["score"]), breakdown=breakdown,
        doctor=report if keep_doctor else None,
    )


def set_planner_gauges(report: PlanReport, registry: Any = None) -> None:
    """``planner.candidates_evaluated`` / ``planner.pruned_infeasible``
    / ``planner.top1_score`` next to the doctor gauges
    (docs/observability.md). One branch when telemetry is disabled."""
    from pipegoose_tpu.telemetry.registry import get_registry

    reg = registry if registry is not None else get_registry()
    if not reg.enabled:
        return
    reg.gauge(
        "planner.candidates_evaluated",
        help="candidate layouts scored by the last planner run",
    ).set(float(len(report.candidates)))
    reg.gauge(
        "planner.pruned_infeasible",
        help="candidates pruned (HBM/validity/compile) in the last run",
    ).set(float(len(report.pruned)))
    top = report.top
    reg.gauge(
        "planner.top1_score",
        help="predicted tokens/s of the last planner run's best layout",
    ).set(float(top.score) if top else 0.0)


def run_plan(
    builder: Any,
    candidates: Iterable[Candidate],
    cost_model: Optional[CostModel] = None,
    keep_doctor: bool = True,
    registry: Any = None,
    progress: Any = None,
) -> PlanReport:
    """Evaluate every candidate and return the ranked
    :class:`PlanReport`. ``progress`` is an optional callable
    ``(index, total, result)`` the CLIs use for live output."""
    cost_model = cost_model or CostModel.for_device()
    cands = list(candidates)
    results = []
    for i, cand in enumerate(cands):
        res = evaluate_candidate(builder, cand, cost_model,
                                 keep_doctor=keep_doctor)
        results.append(res)
        if progress is not None:
            progress(i, len(cands), res)
    report = PlanReport(
        device_kind=cost_model.device_kind,
        n_devices=int(cands[0].n_devices) if cands else 1,
        model=builder.describe(),
        tokens_per_step=int(builder.tokens_per_step),
        cost_model=cost_model.to_json(),
        candidates=results,
    )
    report.sort()
    unmodeled = [r.name for r in results
                 if r.feasible and not r.breakdown.get("compute_modeled",
                                                       True)]
    if unmodeled:
        logger.warning(
            "planner: %d candidate(s) scored WITHOUT compute time (the "
            "backend reported no cost-analysis FLOPs) — ranking is "
            "comm-time only for: %s", len(unmodeled), unmodeled,
        )
    pruned = report.pruned
    logger.info(
        "planner: %d candidate(s) evaluated, %d pruned infeasible, top-1 %s",
        len(results), len(pruned),
        report.top.name if report.top else "<none>",
    )
    for p in pruned:
        logger.info("planner: pruned %s — %s", p.name, p.prune_reason)
    set_planner_gauges(report, registry=registry)
    global _LAST_PLAN_REPORT
    _LAST_PLAN_REPORT = report
    return report


def plan_layout_at(
    builder: Any,
    n_devices: int,
    *,
    pp_sizes: Any = (1,),
    ep_sizes: Any = (1,),
    grad_comms: Any = ("fp32",),
    overlap: Any = (False,),
    remat: Any = (True,),
    n_microbatches: int = 2,
    cost_model: Optional[CostModel] = None,
    keep_doctor: bool = False,
    registry: Any = None,
    progress: Any = None,
) -> PlanReport:
    """Rank the layout space at an ARBITRARY device count — the
    elasticity query: "a slice died, N devices survive; what is the
    best feasible (dp, tp, pp) now?". Same machinery as a full plan
    (every candidate is the real step, shape-only compiled and scored),
    restricted by default to the recovery-relevant axes: fp32 wire, no
    overlap/remat sweep — recovery wants ONE good layout fast, not an
    exhaustive study. ``ElasticRecovery`` (trainer/elastic.py) calls
    this through :func:`best_layout_at` with the run's own builder."""
    cands = enumerate_candidates(
        n_devices, pp_sizes=pp_sizes, ep_sizes=ep_sizes,
        grad_comms=grad_comms, overlap=overlap, remat=remat,
        n_microbatches=n_microbatches,
    )
    return run_plan(
        builder, cands, cost_model=cost_model, keep_doctor=keep_doctor,
        registry=registry, progress=progress,
    )


def best_layout_at(
    builder: Any, n_devices: int, **plan_kwargs: Any
) -> Optional[Candidate]:
    """The winning :class:`Candidate` of :func:`plan_layout_at` (None
    when NO layout at that device count is feasible — the caller must
    surface that, not guess)."""
    report = plan_layout_at(builder, n_devices, **plan_kwargs)
    top = report.top
    return top.candidate if top is not None else None
