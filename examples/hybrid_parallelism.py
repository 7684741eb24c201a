"""Hybrid TP x DP training of BLOOM on TPU — the framework's flagship
entrypoint (capability parity with the reference's
examples/hybrid_parallelism.py, redesigned TPU-first: one mesh, one
compiled train step, no torchrun/process groups).

Run (any JAX device set; for a local smoke run on fake CPU devices):
    python examples/hybrid_parallelism.py --fake-devices 8 --tp 2 --dp 4 --steps 20

With a HF checkpoint (needs network/cache):
    python examples/hybrid_parallelism.py --model bigscience/bloom-560m
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np
import optax

from pipegoose_tpu.distributed import ParallelContext
from pipegoose_tpu.models import bloom
from pipegoose_tpu.optim.zero import DistributedOptimizer
from pipegoose_tpu.trainer import LossLoggerCallback, Trainer


def synthetic_batches(vocab, batch, seq, steps, seed=0):
    rng = np.random.RandomState(seed)
    for _ in range(steps):
        yield jnp.asarray(rng.randint(0, vocab, (batch, seq)))


def hf_batches(model_name, batch, seq, steps):
    """Tokenized text batches from HF datasets (reference uses imdb,
    examples/hybrid_parallelism.py)."""
    from datasets import load_dataset
    from transformers import AutoTokenizer

    tok = AutoTokenizer.from_pretrained(model_name)
    tok.pad_token = tok.pad_token or tok.eos_token
    ds = load_dataset("imdb", split="train")
    texts = [r["text"] for r in ds.select(range(batch * steps))]
    for i in range(steps):
        chunk = texts[i * batch : (i + 1) * batch]
        enc = tok(chunk, padding="max_length", truncation=True, max_length=seq,
                  return_tensors="np")
        yield jnp.asarray(enc["input_ids"])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tp", type=int, default=2)
    ap.add_argument("--dp", type=int, default=2)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--lr", type=float, default=1e-4)
    ap.add_argument("--model", default=None,
                    help="HF checkpoint (e.g. bigscience/bloom-560m); default: tiny random")
    ap.add_argument("--bf16", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--fake-devices", type=int, default=None,
                    help="run on N fake CPU devices")
    args = ap.parse_args()
    if args.fake_devices:
        from pipegoose_tpu.testing import force_cpu_devices
        force_cpu_devices(args.fake_devices)

    ctx = ParallelContext(tensor_parallel_size=args.tp, data_parallel_size=args.dp)
    dtype = jnp.bfloat16 if args.bf16 else jnp.float32

    if args.model:
        from transformers import BloomForCausalLM

        from pipegoose_tpu.models.hf import bloom_params_from_hf

        hf = BloomForCausalLM.from_pretrained(args.model)
        cfg, params = bloom_params_from_hf(hf, dtype=dtype)
        batches = hf_batches(args.model, args.batch, args.seq, args.steps)
    else:
        cfg = bloom.BloomConfig(
            vocab_size=2048, hidden_size=256, n_layer=4, n_head=8, dtype=dtype
        )
        params = bloom.init_params(cfg, jax.random.PRNGKey(0))
        batches = synthetic_batches(cfg.vocab_size, args.batch, args.seq, args.steps)

    def loss_fn(p, ids):
        return bloom.loss_fn(p, ids, None, ids, cfg, tp_axis="tensor")

    callbacks = [LossLoggerCallback(every=5)]
    if args.ckpt_dir:
        from pipegoose_tpu.trainer import CheckpointCallback

        callbacks.append(CheckpointCallback(args.ckpt_dir, every=100))

    trainer = Trainer(
        loss_fn,
        params,
        bloom.tp_specs(params),
        DistributedOptimizer(optax.adam(args.lr), axis_name="data"),
        ctx,
        callbacks=callbacks,
        resume_dir=args.ckpt_dir,
    )
    state = trainer.fit(batches, max_steps=args.steps)
    last = f"{float(state.last_loss):.4f}" if state.last_loss is not None else "n/a (no new steps)"
    print(f"done: {state.step} steps, final loss {last}")


if __name__ == "__main__":
    main()
