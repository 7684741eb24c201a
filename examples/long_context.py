"""Long-context training with ring attention (sequence parallelism) —
the capability the reference advertised but never implemented
(README.md:96; SURVEY.md §5).

    python examples/long_context.py --fake-devices 8 --sp 4 --dp 2 --seq 4096
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

from pipegoose_tpu.distributed import ParallelContext
from pipegoose_tpu.models import bloom
from pipegoose_tpu.optim.zero import DistributedOptimizer
from pipegoose_tpu.trainer import LossLoggerCallback, Trainer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sp", type=int, default=4)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--dp", type=int, default=2)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--fake-devices", type=int, default=None,
                    help="run on N fake CPU devices")
    args = ap.parse_args()
    if args.fake_devices:
        from pipegoose_tpu.testing import force_cpu_devices
        force_cpu_devices(args.fake_devices)

    ctx = ParallelContext(
        sequence_parallel_size=args.sp,
        tensor_parallel_size=args.tp,
        data_parallel_size=args.dp,
    )
    cfg = bloom.BloomConfig(
        vocab_size=2048, hidden_size=256, n_layer=4, n_head=8,
        dtype=jnp.bfloat16, remat=True,
    )
    params = bloom.init_params(cfg, jax.random.PRNGKey(0))

    def loss_fn(p, ids):
        return bloom.loss_fn_sp(
            p, ids, None, ids, cfg,
            tp_axis="tensor" if args.tp > 1 else None, sp_axis="seq",
        )

    trainer = Trainer(
        loss_fn,
        params,
        bloom.tp_specs(params),
        DistributedOptimizer(optax.adam(1e-4), axis_name="data"),
        ctx,
        batch_spec=P("data", "seq"),
        grad_sync_axes=(("seq", "sum"),),
        callbacks=[LossLoggerCallback(every=2)],
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
