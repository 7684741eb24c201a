"""Encoder (ALBERT) masked-LM training + fill-mask inference — the
bidirectional family through the same Trainer/mesh machinery as the
causal examples. The reference demonstrated encoders only via a DP test
on bert-tiny (tests/nn/data_parallel/test_data_parallel.py:18); here the
encoder trains TP x DP with ZeRO-1 and then fills masked tokens.

    python examples/encoder_mlm.py --fake-devices 8 --tp 2 --dp 4 --steps 20
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

from pipegoose_tpu.distributed import ParallelContext
from pipegoose_tpu.models import albert
from pipegoose_tpu.optim.zero import DistributedOptimizer
from pipegoose_tpu.trainer import LossLoggerCallback, Trainer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tp", type=int, default=2)
    ap.add_argument("--dp", type=int, default=4)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--steps", type=int, default=150)
    ap.add_argument("--mask-rate", type=float, default=0.15)
    ap.add_argument("--fake-devices", type=int, default=None,
                    help="run on N fake CPU devices")
    args = ap.parse_args()
    if args.fake_devices:
        from pipegoose_tpu.testing import force_cpu_devices
        force_cpu_devices(args.fake_devices)

    ctx = ParallelContext(
        tensor_parallel_size=args.tp, data_parallel_size=args.dp
    )
    cfg = albert.AlbertConfig(
        vocab_size=2048, embedding_size=64, hidden_size=256, n_layer=4,
        n_head=8, intermediate_size=512, max_position_embeddings=args.seq,
    )
    params = albert.init_params(cfg, jax.random.PRNGKey(0))
    mask_id = cfg.vocab_size - 1  # reserve the last id as [MASK]

    # batch = dict(ids=corrupted inputs, labels=originals, lmask=masked
    # positions) — the BERT objective: predict the original token at
    # every [MASK] slot
    def loss_fn(p, batch):
        return albert.loss_fn(
            p, batch["ids"], None, batch["labels"], cfg, tp_axis="tensor",
            label_mask=batch["lmask"],
        )

    trainer = Trainer(
        loss_fn,
        params,
        albert.tp_specs(params),
        DistributedOptimizer(optax.adam(1e-3), axis_name="data"),
        ctx,
        batch_spec={"ids": P("data"), "labels": P("data"), "lmask": P("data")},
        callbacks=[LossLoggerCallback(every=5)],
    )

    rng = np.random.RandomState(0)

    def make_batch():
        # learnable synthetic language: token = f(position, phase) so
        # the bidirectional context + position embeddings genuinely
        # predict the masked slots (random ids would be unlearnable)
        phase = rng.randint(0, 4, (args.batch, 1))
        pos = np.arange(args.seq)[None, :]
        labels = (pos + phase * args.seq) % (cfg.vocab_size - 1)
        lmask = (rng.rand(args.batch, args.seq) < args.mask_rate)
        ids = np.where(lmask, mask_id, labels)
        return {
            "ids": jnp.asarray(ids),
            "labels": jnp.asarray(labels),
            "lmask": jnp.asarray(lmask.astype(np.int32)),
        }

    state = trainer.fit((make_batch() for _ in range(args.steps)),
                        max_steps=args.steps)
    last = (
        f"{float(state.last_loss):.4f}"
        if state.last_loss is not None else "n/a (no new steps)"
    )
    print(f"done: {state.step} steps, final loss {last}")

    # fill-mask inference on the trained params (single-device path)
    demo = make_batch()
    filled = albert.fill_mask(
        trainer.params, demo["ids"][:1], mask_id, cfg
    )
    n_masked = int(demo["lmask"][:1].sum())
    n_right = int(
        ((filled == demo["labels"][:1]) & (demo["lmask"][:1] > 0)).sum()
    )
    print(f"fill-mask: recovered {n_right}/{n_masked} masked tokens")


if __name__ == "__main__":
    main()
