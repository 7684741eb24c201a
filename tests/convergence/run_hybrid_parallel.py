"""Side-by-side convergence run: hybrid TP x DP + ZeRO-1 vs an
identically-seeded single-device reference — the reference's manual
acceptance workflow (tests/convergence/run_hybrid_parallel.py:83-177,
which trained bloom-560m on imdb logging wandb loss pairs). Here both
runs share one process/mesh and print a CSV of paired losses; any
divergence beyond tolerance exits nonzero.

Usage (CPU simulation; on TPU drop the env var):
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python tests/convergence/run_hybrid_parallel.py --steps 30
"""
from __future__ import annotations

import argparse
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax

from pipegoose_tpu.distributed import ParallelContext
from pipegoose_tpu.models import bloom
from pipegoose_tpu.optim.zero import DistributedOptimizer
from pipegoose_tpu.parallel import make_hybrid_train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tp", type=int, default=2)
    ap.add_argument("--dp", type=int, default=4)
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--tol", type=float, default=5e-3)
    ap.add_argument(
        "--model", choices=("toy", "560m"), default="toy",
        help="'560m' runs the real bloom-560m config — the reference's "
        "acceptance scale (run_hybrid_parallel.py:83-177)",
    )
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--out", default=None, help="write a JSON run record")
    ap.add_argument(
        "--platform", choices=("auto", "cpu"), default="auto",
        help="'cpu' pins the fake-CPU-device backend before first use",
    )
    args = ap.parse_args()

    if args.platform == "cpu":
        from pipegoose_tpu.testing import force_cpu_devices

        force_cpu_devices(max(8, args.tp * args.dp))

    if args.model == "560m":
        cfg = bloom.BloomConfig.bloom_560m()
    else:
        cfg = bloom.BloomConfig(vocab_size=512, hidden_size=128, n_layer=4, n_head=8)
    params = bloom.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    batches = [
        jnp.asarray(rng.randint(0, cfg.vocab_size, (args.batch, args.seq)))
        for _ in range(args.steps)
    ]

    # single-device reference
    opt = optax.adam(args.lr)
    st = opt.init(params)
    p_ref = params

    @jax.jit
    def ref_step(p, s, ids):
        loss, grads = jax.value_and_grad(bloom.loss_fn)(p, ids, None, ids, cfg)
        u, s2 = opt.update(grads, s, p)
        return optax.apply_updates(p, u), s2, loss

    ctx = ParallelContext(tensor_parallel_size=args.tp, data_parallel_size=args.dp)
    init_fn, make_step = make_hybrid_train_step(
        lambda p, ids: bloom.loss_fn(p, ids, None, ids, cfg, tp_axis="tensor"),
        bloom.tp_specs(params),
        DistributedOptimizer(optax.adam(args.lr), axis_name="data"),
        ctx,
    )
    opt_state = init_fn(params)
    step = make_step(params)
    p = params

    state = {"ref": (p_ref, st), "par": (p, opt_state)}

    def ref_fn(ids):
        p, s = state["ref"]
        p, s, loss = ref_step(p, s, ids)
        state["ref"] = (p, s)
        return loss

    def par_fn(ids):
        p, s = state["par"]
        p, s, loss = step(p, s, ids)
        state["par"] = (p, s)
        return loss

    sys.path.insert(0, __import__("os").path.dirname(__import__("os").path.abspath(__file__)))
    from _pairing import run_paired

    run_paired(
        batches, ref_fn, par_fn, args.tol, names=("ref", "hybrid"),
        out_path=args.out,
        meta={"model": args.model, "tp": args.tp, "dp": args.dp,
              "batch": args.batch, "seq": args.seq, "lr": args.lr,
              "backend": f"{jax.default_backend()}-{jax.device_count()}dev"},
    )


if __name__ == "__main__":
    main()
