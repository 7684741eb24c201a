"""Compile-time parallelism planner: static search of the mesh/layout
space, no hardware needed.

The mesh doctor (telemetry/doctor.py) extracts per-collective wire
bytes, partitioner-inserted resharding, compiled FLOPs, and the HBM
peak from ONE shape-only lower+compile on fake host devices. This
package turns that single-config inspector into a search: enumerate
every (dp, tp, pp, ep) x overlap_tp x grad_comm x remat candidate for a
device count (planner/space.py), AOT-compile each through the real
``make_hybrid_train_step`` (planner/bloom_builder.py), score with a
static cost model — wire bytes over the ICI/DCI peer bandwidths, FLOPs
over ``PEAK_FLOPS``, analytic pipeline bubble, HBM vs the chip budget
(planner/cost.py) — and emit a ranked, JSON-round-tripping
:class:`PlanReport` (planner/report.py).

Entry points: :func:`run_plan` (library),
``scripts/plan_parallelism.py`` (CLI + ``--check`` CI gate),
``examples/plan_parallelism_demo.py``.
Docs: docs/planner.md.
"""
from pipegoose_tpu.planner.bloom_builder import BloomPlanModel
from pipegoose_tpu.planner.cost import CostModel, hbm_check, score_breakdown
from pipegoose_tpu.planner.planner import (
    best_layout_at,
    evaluate_candidate,
    last_plan_report,
    plan_layout_at,
    run_plan,
    set_planner_gauges,
)
from pipegoose_tpu.planner.report import CandidateResult, PlanReport
from pipegoose_tpu.planner.serving import (
    ServingCandidate,
    evaluate_serving_candidate,
    format_serving_plan,
    plan_serving_decode,
)
from pipegoose_tpu.planner.space import (
    Candidate,
    candidate_key,
    enumerate_candidates,
    find_candidate,
    mesh_factorizations,
)

__all__ = [
    "BloomPlanModel",
    "Candidate",
    "CandidateResult",
    "CostModel",
    "PlanReport",
    "ServingCandidate",
    "best_layout_at",
    "evaluate_serving_candidate",
    "format_serving_plan",
    "plan_serving_decode",
    "candidate_key",
    "enumerate_candidates",
    "evaluate_candidate",
    "plan_layout_at",
    "find_candidate",
    "hbm_check",
    "last_plan_report",
    "mesh_factorizations",
    "run_plan",
    "score_breakdown",
    "set_planner_gauges",
]
