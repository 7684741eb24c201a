"""Mesh-doctor demo: a silently mis-sharded weight, caught at compile
time, then fixed — without running a single training step.

Story (the failure mode ISSUE 4 exists for): a GSPMD/auto-parallel
train step over a Megatron-style MLP (column-sharded w1, row-sharded
w2 — the canonical tensor-parallel layout that needs NO gathers, only
one partial-sum all-reduce per matmul pair) is built with w1's
PartitionSpec accidentally left replicated. Nothing crashes — GSPMD
happily compiles it, the partitioner quietly inserts an all-gather to
re-shard the dataflow, and the only runtime symptom is a slower,
fatter step. The doctor (pipegoose_tpu/telemetry/doctor.py) diffs the
compiled program against the intended specs, names the offending
module path, and shows the inserted gather; the fixed spec then
compiles back to ZERO resharding-gather bytes and passes the same
guards that run in CI (scripts/mesh_doctor.py, tests/test_cli_gates.py).

    python examples/mesh_doctor_demo.py --fake-devices 8 --tp 2 --dp 4
"""
from __future__ import annotations

import argparse
import functools


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tp", type=int, default=2)
    ap.add_argument("--dp", type=int, default=4)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--ffn", type=int, default=256)
    ap.add_argument("--steps", type=int, default=2)  # unused; harness arg
    ap.add_argument("--fake-devices", type=int, default=None,
                    help="run on N fake CPU devices")
    args = ap.parse_args()
    if args.fake_devices:
        from pipegoose_tpu.testing import force_cpu_devices
        force_cpu_devices(args.fake_devices)

    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from pipegoose_tpu import telemetry
    from pipegoose_tpu.distributed import ParallelContext

    H, F, B = args.hidden, args.ffn, args.batch
    ctx = ParallelContext(tensor_parallel_size=args.tp,
                          data_parallel_size=args.dp)
    mesh = ctx.mesh
    key = jax.random.PRNGKey(0)
    params = {
        "mlp": {
            "w1": jax.random.normal(key, (H, F)) * 0.02,
            "w2": jax.random.normal(key, (F, H)) * 0.02,
        },
        "head": {"w": jax.random.normal(key, (H, 8)) * 0.02},
    }
    # the INTENDED layout: Megatron column/row pair, tiny head replicated
    intended = {
        "mlp": {"w1": P(None, "tensor"), "w2": P("tensor", None)},
        "head": {"w": P()},
    }
    # the DEFECT: w1 left replicated — compiles fine, gathers silently
    broken = {
        "mlp": {"w1": P(), "w2": P("tensor", None)},
        "head": {"w": P()},
    }
    opt = optax.adam(1e-3)

    def loss_fn(p, x):  # single-device code; GSPMD derives collectives
        h = jax.nn.gelu(x @ p["mlp"]["w1"]) @ p["mlp"]["w2"]
        return ((h @ p["head"]["w"]) ** 2).mean()

    def build(spec_tree):
        shardings = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), spec_tree,
            is_leaf=lambda x: isinstance(x, P))
        p = jax.tree_util.tree_map(jax.device_put, params, shardings)
        o = jax.jit(opt.init)(p)

        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def step(p, o, x):
            loss, grads = jax.value_and_grad(loss_fn)(p, x)
            updates, o = opt.update(grads, o, p)
            p = optax.apply_updates(p, updates)
            return jax.lax.with_sharding_constraint(p, shardings), o, loss

        return p, o, step

    x = jax.device_put(jnp.ones((B, H)),
                       NamedSharding(mesh, P("data", None)))

    def doctor(spec_tree):
        p, o, step = build(spec_tree)
        return telemetry.diagnose(
            step, p, o, x,
            intended=(intended, None, P("data", None)),
            labels=("params", "opt_state", "batch"),
            mesh=mesh, large_bytes=1 << 12,
        )

    # -- diagnose the broken build ----------------------------------------
    report = doctor(broken)
    offenders = report.sharding.mismatches()
    assert any("w1" in b.path for b in offenders), offenders
    print("DEFECT found by the doctor (no step was run):")
    for b in offenders:
        print(f"  {b.path}: intended {b.intended} -> actual {b.actual} "
              f"({', '.join(b.flags)})")
    gathers = [c for c in report.sharding.resharding_collectives
               if c.op in ("all-gather", "collective-permute", "all-to-all")]
    print(f"  partitioner-inserted gather traffic: "
          f"{sum(c.bytes for c in gathers)}B "
          f"({len(gathers)} collective(s))")
    try:
        telemetry.assert_matches_intended(report)
        raise SystemExit("guard unexpectedly passed")
    except telemetry.ShardingRegressionError as e:
        print(f"  guard fired as designed: {str(e).splitlines()[0]}")

    # -- the fix: build with the intended specs ---------------------------
    fixed = doctor(intended)
    telemetry.assert_matches_intended(fixed)
    # the auto path's partial-sum all-reduces are partitioner-derived by
    # construction; the guard pins that no GATHER resharding sneaks in
    telemetry.assert_no_resharding(fixed, allow=["all-reduce"])
    fixed_gathers = sum(
        c.bytes for c in fixed.sharding.resharding_collectives
        if c.op in ("all-gather", "collective-permute", "all-to-all"))
    assert fixed_gathers == 0, fixed.sharding.collectives
    print(f"\nFIXED plan: mismatches=0, resharding-gather bytes="
          f"{fixed_gathers}, replicated="
          f"{fixed.sharding.replicated_bytes}B/dev")
    print()
    print(fixed.format_table(max_rows=8))
    ctx.destroy()
    print(f"\ndone: doctor caught {len(offenders)} mis-sharded buffer(s); "
          f"fixed plan has zero resharding-gather bytes")


if __name__ == "__main__":
    main()
