"""Parallelism-planner demo: "how do I run this model on 8 chips" as
one static search (pipegoose_tpu/planner/, docs/planner.md, ISSUE 7).

Story: choosing (dp, tp) x overlap x grad_comm by hand means compiling
and timing every combination on hardware. The planner does the search
with ZERO device time — every candidate is one shape-only lower+compile
through the mesh doctor, scored by wire bytes over the chip's
interconnect bandwidths, compiled FLOPs over its peak, and HBM peak
against its budget. The demo:

1. ranks the full layout space for a bloom-tiny model on a faked
   8-device mesh (infeasible layouts pruned with stated reasons);
2. shows the top-1 is a zero-resharding hybrid config — its embedded
   doctor report contains NO partitioner-inserted collectives (the
   compiled plan is exactly the intended plan);
3. shows the planner's reasoning: the ring-overlap + int8-wire
   candidates win because the cost model sees their tensor-axis time
   hidden and their gradient bytes cut — the same effects docs/comm.md
   measured on hardware.

    python examples/plan_parallelism_demo.py --fake-devices 8
"""
from __future__ import annotations

import argparse


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--fake-devices", type=int, default=None,
                    help="run on N fake CPU devices")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--top-k", type=int, default=5)
    ap.add_argument("--steps", type=int, default=0,
                    help="unused (uniform example CLI; the planner "
                         "executes nothing)")
    args = ap.parse_args()
    if args.fake_devices:
        from pipegoose_tpu.testing import fake_cluster
        fake_cluster(args.fake_devices)

    import jax

    from pipegoose_tpu import telemetry
    from pipegoose_tpu.models import bloom
    from pipegoose_tpu.planner import (
        BloomPlanModel,
        CostModel,
        enumerate_candidates,
        run_plan,
    )

    reg = telemetry.get_registry()
    reg.enable()
    n = len(jax.devices())
    cfg = bloom.BloomConfig(
        vocab_size=128, hidden_size=64, n_layer=2, n_head=4
    )
    model = BloomPlanModel(cfg, batch=args.batch, seq=args.seq)
    # fp32-vs-int8 and overlap on/off is where the comm engine's wins
    # live; remat stays on (one knob fewer keeps the demo under a
    # minute — the CLI sweeps the full space)
    candidates = enumerate_candidates(
        n, grad_comms=("fp32", "int8"), remat=(True,)
    )
    print(f"enumerated {len(candidates)} candidate layout(s) for "
          f"{n} devices\n")
    report = run_plan(model, candidates, CostModel.for_device("cpu"))
    print(report.format_table(top_k=args.top_k))

    top = report.top
    assert top is not None, "no feasible candidate"
    b = top.breakdown

    # 2. the top-1 is a ZERO-RESHARDING config: its compiled schedule
    # contains only collectives the model wrote (ppermute ring hops,
    # the ZeRO reduce-scatter), nothing partitioner-inserted
    telemetry.assert_no_resharding(top.doctor)
    resharding = top.doctor.sharding.resharding_bytes
    print(f"\ntop-1 {top.name}: partitioner-inserted resharding bytes = "
          f"{resharding} (doctor-pinned zero)")

    # 3. the cost model's reasoning, in numbers
    print(f"top-1 anatomy: compute {b['compute_seconds'] * 1e3:.3f}ms + "
          f"comm {b['comm_seconds'] * 1e3:.3f}ms "
          f"({b['comm_seconds_by_axes']})")
    assert top.candidate.grad_comm == "int8" and top.candidate.overlap_tp, (
        "expected the ring-overlap + int8-wire candidate to rank first",
        top.name,
    )
    gauges = {k: reg.gauge(k).value for k in (
        "planner.candidates_evaluated", "planner.pruned_infeasible",
        "planner.top1_score",
    )}
    print(f"planner gauges: {gauges}")
    print(f"\ndone: ranked {len(report.ranked)} layouts "
          f"({len(report.pruned)} pruned with reasons); top-1 {top.name} "
          f"is a zero-resharding hybrid config")


if __name__ == "__main__":
    main()
