#!/usr/bin/env python
"""Mesh doctor CLI: compile a hybrid train step (and optionally the
serving decode step) on a host-device mesh and print/guard its
partitioning plan (pipegoose_tpu/telemetry/doctor.py).

Standalone CI gate: with ``--check`` the process exits non-zero when
the compiled program contains partitioner-inserted resharding
collectives, intended-vs-actual sharding mismatches, or large fully
replicated buffers — so a PartitionSpec regression fails a pipeline at
compile time on fake CPU devices, long before a TPU bench notices.

    # inspect a tp=2 x dp=4 BLOOM-ish step on 8 fake devices
    python scripts/mesh_doctor.py --fake-devices 8 --tp 2 --dp 4

    # CI gate: guards on, JSON artifact out, serving decode step too
    python scripts/mesh_doctor.py --fake-devices 8 --tp 2 --dp 4 \
        --check --serving --json mesh_doctor.json

Exit codes: 0 ok, 2 guard violation.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

# runnable from anywhere: the repo root is the import root
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build_train_report(args, ctx, cfg, params, bloom):
    import jax
    import jax.numpy as jnp
    import optax

    from pipegoose_tpu.optim.zero import DistributedOptimizer
    from pipegoose_tpu.parallel import (
        make_hybrid_train_step,
        train_step_intended_specs,
    )
    from pipegoose_tpu.telemetry import doctor

    specs = bloom.tp_specs(params)
    opt = DistributedOptimizer(
        optax.adam(1e-3), axis_name="data", grad_comm=args.grad_comm
    )

    def loss_fn(p, ids):
        return bloom.loss_fn(p, ids, None, ids, cfg, tp_axis="tensor")

    init_fn, make_step = make_hybrid_train_step(
        loss_fn, specs, opt, ctx, overlap_tp=args.overlap
    )
    opt_sds = jax.eval_shape(init_fn, params)  # shapes only, no init run
    step = make_step(params)
    batch = jax.ShapeDtypeStruct((args.batch, args.seq), jnp.int32)
    return doctor.diagnose(
        step, params, opt_sds, batch,
        intended=train_step_intended_specs(opt, params, specs, ctx.mesh),
        labels=("params", "opt_state", "batch"),
        mesh=ctx.mesh, large_bytes=args.large_bytes,
    )


def build_serving_reports(args, ctx, cfg, params, bloom):
    """Decode step AND the chunked-prefill program of the mixed step
    (prefix cache + chunking on): ISSUE 6 pins BOTH at zero
    partitioner-inserted resharding, so a PartitionSpec regression in
    either half of the serving tick dies here at compile time."""
    from pipegoose_tpu.serving import ServingEngine

    engine = ServingEngine(
        params, cfg, num_slots=2, num_pages=16, page_size=8,
        max_context=32, mesh=ctx.mesh, param_specs=bloom.tp_specs(params),
        prefix_cache=True, prefill_chunk=16,
    )
    return {
        "decode_step": engine.doctor(large_bytes=args.large_bytes),
        "prefill_chunk": engine.doctor_chunk(large_bytes=args.large_bytes),
    }


def run_guards(name, report, args) -> int:
    from pipegoose_tpu.telemetry import doctor

    rc = 0
    for guard, kwargs in (
        (doctor.assert_no_resharding, {"allow": args.allow}),
        (doctor.assert_matches_intended, {"allow": args.allow_paths}),
        (doctor.assert_fully_sharded,
         {"min_bytes": args.min_shard_bytes, "allow": args.allow_paths}),
    ):
        try:
            guard(report, **kwargs)
        except doctor.ShardingRegressionError as e:
            print(f"\n[{name}] GUARD VIOLATION ({guard.__name__}):\n{e}",
                  file=sys.stderr)
            rc = 2
    return rc


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="compiled-program sharding & memory inspector")
    ap.add_argument("--tp", type=int, default=2)
    ap.add_argument("--dp", type=int, default=4)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--vocab", type=int, default=128)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--fake-devices", type=int, default=None,
                    help="run on N fake CPU devices (XLA_FLAGS host "
                         "platform count)")
    ap.add_argument("--serving", action="store_true",
                    help="also doctor the paged decode step and the "
                         "chunked-prefill mixed-step program")
    ap.add_argument("--overlap", action="store_true",
                    help="build the ring collective-matmul train step "
                         "(config.overlap_tp — docs/comm.md)")
    ap.add_argument("--grad-comm", default="fp32",
                    choices=("fp32", "bf16", "int8"),
                    help="gradient-reduction wire precision for the "
                         "train step (distributed/compressed.py)")
    ap.add_argument("--expect-ppermute", action="store_true",
                    help="guard: fail (exit 2) unless the train step's "
                         "compiled schedule contains ppermute ring "
                         "collectives (the overlap gate, "
                         "tests/test_cli_gates.py)")
    ap.add_argument("--check", action="store_true",
                    help="run the regression guards; exit 2 on violation")
    ap.add_argument("--allow", action="append", default=[],
                    help="fnmatch pattern of tolerated resharding "
                         "collectives (op, source, or op:source)")
    ap.add_argument("--allow-paths", action="append", default=[],
                    help="fnmatch pattern of buffer paths exempt from "
                         "the mismatch/fully-sharded guards")
    ap.add_argument("--min-shard-bytes", type=int, default=1 << 16,
                    help="fully-sharded guard threshold (default 64KiB "
                         "— sized for the CLI's tiny demo model)")
    ap.add_argument("--large-bytes", type=int, default=1 << 16,
                    help="report-flag threshold for replicated buffers")
    ap.add_argument("--json", default=None,
                    help="write the report(s) as JSON to this path")
    ap.add_argument("--quiet", action="store_true",
                    help="suppress the tables (guards/JSON only)")
    args = ap.parse_args(argv)

    if args.fake_devices:
        from pipegoose_tpu.testing import force_cpu_devices

        force_cpu_devices(args.fake_devices)

    import jax

    from pipegoose_tpu.distributed import ParallelContext
    from pipegoose_tpu.models import bloom

    cfg = bloom.BloomConfig(
        vocab_size=args.vocab, hidden_size=args.hidden,
        n_layer=args.layers, n_head=args.heads,
        overlap_tp=args.overlap,
    )
    params = bloom.init_params(cfg, jax.random.PRNGKey(0))
    ctx = ParallelContext(tensor_parallel_size=args.tp,
                          data_parallel_size=args.dp)
    rc = 0
    blobs = {}
    try:
        reports = {"train_step": build_train_report(args, ctx, cfg, params,
                                                    bloom)}
        if args.serving:
            reports.update(build_serving_reports(args, ctx, cfg, params,
                                                 bloom))
        for name, report in reports.items():
            if not args.quiet:
                print(f"== {name} ==")
                print(report.format_table())
                print()
            blobs[name] = report.to_json()
            if args.check:
                rc = max(rc, run_guards(name, report, args))
            if args.expect_ppermute and name == "train_step":
                perms = [
                    c for c in report.sharding.collectives
                    if c.op == "collective-permute"
                    and c.source == "ppermute"
                ]
                if not perms:
                    print(
                        f"\n[{name}] GUARD VIOLATION (expect-ppermute): "
                        "no ppermute ring collectives in the compiled "
                        "schedule — the overlap path did not engage",
                        file=sys.stderr,
                    )
                    rc = 2
    finally:
        ctx.destroy()
    if args.json:
        with open(args.json, "w") as f:
            json.dump(blobs, f, indent=1)
        print(f"report written: {args.json}")
    print("mesh doctor:", "FAILED (sharding regression)" if rc else "OK")
    return rc


if __name__ == "__main__":
    sys.exit(main())
