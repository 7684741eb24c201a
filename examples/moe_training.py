"""Expert-parallel MoE training (Switch/Mixtral-style) — capability
parity with the reference's MoE convergence script
(tests/convergence/run_ep.py), TPU-first: EP x TP x DP on one mesh with
static-shape all_to_all dispatch.

    python examples/moe_training.py --fake-devices 8 --ep 2 --tp 2 --dp 2 --steps 20
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

from pipegoose_tpu.distributed import ParallelContext
from pipegoose_tpu.models import bloom_moe
from pipegoose_tpu.optim.zero import DistributedOptimizer
from pipegoose_tpu.trainer import LossLoggerCallback, Trainer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--ep", type=int, default=2)
    ap.add_argument("--tp", type=int, default=2)
    ap.add_argument("--dp", type=int, default=2)
    ap.add_argument("--experts", type=int, default=8)
    ap.add_argument("--top-k", type=int, default=2)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--fake-devices", type=int, default=None,
                    help="run on N fake CPU devices")
    args = ap.parse_args()
    if args.fake_devices:
        from pipegoose_tpu.testing import force_cpu_devices
        force_cpu_devices(args.fake_devices)

    ctx = ParallelContext(
        expert_parallel_size=args.ep,
        tensor_parallel_size=args.tp,
        data_parallel_size=args.dp,
    )
    cfg = bloom_moe.BloomMoEConfig(
        vocab_size=2048, hidden_size=256, n_layer=4, n_head=8,
        num_experts=args.experts, top_k=args.top_k,
    )
    params = bloom_moe.init_params(cfg, jax.random.PRNGKey(0))

    def loss_fn(p, ids, rng):
        rng = jax.random.fold_in(
            rng,
            jax.lax.axis_index("data") * args.ep + jax.lax.axis_index("expert"),
        )
        return bloom_moe.loss_fn(
            p, ids, None, ids, cfg, tp_axis="tensor", ep_axis="expert",
            rng=rng, train=True,
        )

    trainer = Trainer(
        loss_fn,
        params,
        bloom_moe.moe_specs(params),
        DistributedOptimizer(optax.adam(1e-4), axis_name="data"),
        ctx,
        batch_spec=P(("data", "expert")),
        loss_axis=("data", "expert"),
        grad_sync_axes=(("expert", "mean"),),
        with_rng=True,
        callbacks=[LossLoggerCallback(every=5)],
    )

    rng = np.random.RandomState(0)
    batches = (
        jnp.asarray(rng.randint(0, cfg.vocab_size, (args.batch, args.seq)))
        for _ in range(args.steps)
    )
    state = trainer.fit(batches, max_steps=args.steps)
    last = f"{float(state.last_loss):.4f}" if state.last_loss is not None else "n/a (no new steps)"
    print(f"done: {state.step} steps, final loss {last}")


if __name__ == "__main__":
    main()
