#!/usr/bin/env python
"""Parallelism-planner CLI: rank every layout for a model/topology from
shape-only compiles on fake devices (pipegoose_tpu/planner/,
docs/planner.md).

"How do I run this model on N chips" as one call — the planner
enumerates the (dp, tp, pp) x overlap x grad_comm x remat space for the
device count, AOT-compiles each candidate's hybrid train step (nothing
executes), scores wire bytes / FLOPs / HBM / pipeline bubble against
the chip's spec budgets, and prints the ranked table:

    # rank layouts for a bloom-ish model on 8 fake devices
    python scripts/plan_parallelism.py --fake-devices 8

    # plan for real v5e chips without hardware, JSON artifact out
    python scripts/plan_parallelism.py --fake-devices 8 \
        --device-kind v5e --json plan.json --top-k 5

    # CI gate: exit 2 when the configured layout scores below the
    # planner's top-1 by more than --tolerance (or went infeasible)
    python scripts/plan_parallelism.py --fake-devices 8 \
        --check --tp 4 --dp 2 --overlap --grad-comm int8

Exit codes: 0 ok, 2 check violation.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

# runnable from anywhere: the repo root is the import root
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _bool_set(s: str):
    return {"both": (False, True), "on": (True,), "off": (False,)}[s]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="compile-time parallelism planner (static layout search)")
    ap.add_argument("--vocab", type=int, default=128)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--fake-devices", type=int, default=None,
                    help="run on N fake CPU devices")
    ap.add_argument("--device-kind", default=None,
                    help="score against this chip's spec budgets (v5e, "
                         "v5p, v4, ...) instead of the visible device — "
                         "plan for hardware you don't have")
    ap.add_argument("--hbm-gib", type=float, default=None,
                    help="override the per-chip HBM budget (GiB)")
    ap.add_argument("--pp", default="1",
                    help="comma list of pipeline sizes to enumerate "
                         "(default '1'; e.g. '1,2,4')")
    ap.add_argument("--microbatches", type=int, default=2,
                    help="pipeline microbatches for pp>1 candidates")
    ap.add_argument("--grad-comms", default="fp32,bf16,int8",
                    help="comma list of gradient wire formats to enumerate")
    ap.add_argument("--overlap-sweep", default="both",
                    choices=("both", "on", "off"),
                    help="ring collective-matmul overlap options")
    ap.add_argument("--remat-sweep", default="both",
                    choices=("both", "on", "off"),
                    help="rematerialization options")
    ap.add_argument("--top-k", type=int, default=None,
                    help="table rows to print (all by default)")
    ap.add_argument("--json", default=None,
                    help="write the PlanReport as JSON to this path")
    ap.add_argument("--quiet", action="store_true",
                    help="suppress the table and per-candidate progress "
                         "(check/JSON only)")
    # --check: the currently-configured layout, compared against top-1
    ap.add_argument("--check", action="store_true",
                    help="gate mode: exit 2 when the --tp/--dp/... layout "
                         "scores below top-1 by more than --tolerance")
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--dp", type=int, default=None,
                    help="default: devices // (tp * pp)")
    ap.add_argument("--pp-current", type=int, default=1,
                    help="pipeline size of the configured layout")
    ap.add_argument("--overlap", action="store_true",
                    help="configured layout uses overlap_tp")
    ap.add_argument("--grad-comm", default="fp32",
                    choices=("fp32", "bf16", "int8"))
    ap.add_argument("--no-remat", action="store_true",
                    help="configured layout runs without remat")
    ap.add_argument("--tolerance", type=float, default=0.5,
                    help="allowed score gap to top-1 in check mode "
                         "(0.5 = configured must reach 50%% of top-1)")
    # serving decode-layout mode (planner/serving.py, ROADMAP items 3+4):
    # analytic (tp, weight_dtype, kv_dtype) x HBM ranking — no compiles
    ap.add_argument("--serving-decode", action="store_true",
                    help="rank serving DECODE layouts instead of train "
                         "steps: (tp, weight_dtype, kv_dtype) vs the "
                         "chip's HBM budget + bandwidth, analytically")
    ap.add_argument("--num-pages", type=int, default=1024,
                    help="serving-decode mode: KV pool pages")
    ap.add_argument("--page-size", type=int, default=16,
                    help="serving-decode mode: tokens per page")
    ap.add_argument("--num-slots", type=int, default=8,
                    help="serving-decode mode: decode slots")
    ap.add_argument("--weight-dtype", default="fp",
                    choices=("fp", "int8", "int4"),
                    help="serving-decode --check: configured weight wire "
                         "precision")
    ap.add_argument("--kv-dtype", default="fp", choices=("fp", "int8"),
                    help="serving-decode --check: configured KV page dtype")
    args = ap.parse_args(argv)

    if args.fake_devices:
        from pipegoose_tpu.testing.fake_cluster import fake_cluster

        fake_cluster(args.fake_devices)

    import jax

    from pipegoose_tpu.models import bloom
    from pipegoose_tpu.planner import (
        BloomPlanModel,
        Candidate,
        CostModel,
        enumerate_candidates,
        run_plan,
    )

    n_devices = len(jax.devices())
    cfg = bloom.BloomConfig(
        vocab_size=args.vocab, hidden_size=args.hidden,
        n_layer=args.layers, n_head=args.heads,
    )
    cost_model = CostModel.for_device(
        args.device_kind,
        hbm_bytes=(args.hbm_gib * 1024**3 if args.hbm_gib else None),
    )

    if args.serving_decode:
        from pipegoose_tpu.planner import (
            format_serving_plan,
            plan_serving_decode,
        )

        plan = plan_serving_decode(
            cfg, n_devices, num_pages=args.num_pages,
            page_size=args.page_size, num_slots=args.num_slots,
            cost_model=cost_model,
        )
        if not args.quiet:
            print(format_serving_plan(plan))
        if args.json:
            from pipegoose_tpu.telemetry.exporters import atomic_write_text

            atomic_write_text(args.json, json.dumps(plan, indent=1))
            print(f"serving plan written: {args.json}")
        if args.check:
            # gate semantics, serving flavor: the configured
            # (tp, weight_dtype, kv_dtype) row must be FEASIBLE and
            # within --tolerance of the top score — same exit contract
            # as the train-step gate (exit 2 + the row's reason)
            name = (f"tp{args.tp}+w:{args.weight_dtype}"
                    f"+kv:{args.kv_dtype}")
            row = next((r for r in plan["rows"] if r["name"] == name),
                       None)
            if row is None:
                print(f"serving check FAILED: {name} is not in the "
                      f"enumerated space (tp must divide "
                      f"{plan['n_devices']} devices)")
                return 2
            if not row["feasible"]:
                print(f"serving check FAILED: {name} — {row['reason']}")
                return 2
            top = plan["rows"][0]
            if row["score"] < (1.0 - args.tolerance) * top["score"]:
                print(f"serving check FAILED: {name} scores "
                      f"{row['score']:,.0f} tok/s vs top-1 {top['name']} "
                      f"{top['score']:,.0f} (below "
                      f"{1.0 - args.tolerance:.0%})")
                return 2
            print(f"serving check: OK — {name} feasible "
                  f"({row['reason']}), {row['score']:,.0f} tok/s vs "
                  f"top-1 {top['score']:,.0f}")
        return 0

    model = BloomPlanModel(cfg, batch=args.batch, seq=args.seq)
    candidates = enumerate_candidates(
        n_devices,
        pp_sizes=tuple(int(x) for x in args.pp.split(",") if x),
        grad_comms=tuple(x for x in args.grad_comms.split(",") if x),
        overlap=_bool_set(args.overlap_sweep),
        remat=_bool_set(args.remat_sweep),
        n_microbatches=args.microbatches,
    )

    t0 = time.perf_counter()

    def progress(i, n, res):
        if args.quiet:
            return
        tag = (f"{res.score:,.0f} tok/s" if res.feasible
               else f"pruned: {res.prune_reason}")
        print(f"  [{i + 1}/{n}] {res.name}: {tag}", flush=True)

    report = run_plan(model, candidates, cost_model, progress=progress)
    elapsed = time.perf_counter() - t0

    if not args.quiet:
        print()
        print(report.format_table(top_k=args.top_k))
        print(f"\n{len(report.ranked)} ranked, {len(report.pruned)} pruned "
              f"in {elapsed:.1f}s")
    if args.json:
        from pipegoose_tpu.telemetry.exporters import atomic_write_text

        atomic_write_text(args.json, json.dumps(report.to_json(), indent=1))
        print(f"plan written: {args.json}")

    rc = 0
    if args.check:
        dp = args.dp
        if dp is None:
            dp = max(1, n_devices // (args.tp * args.pp_current))
        current = Candidate(
            dp=dp, tp=args.tp, pp=args.pp_current,
            overlap_tp=args.overlap, grad_comm=args.grad_comm,
            remat=not args.no_remat,
            n_microbatches=args.microbatches if args.pp_current > 1 else 1,
        )
        ok, msg = report.check(current, tolerance=args.tolerance)
        print(("plan check: OK — " if ok else "plan check: FAILED — ") + msg,
              file=sys.stdout if ok else sys.stderr)
        rc = 0 if ok else 2
    return rc


if __name__ == "__main__":
    sys.exit(main())
