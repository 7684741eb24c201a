"""Real-hardware convergence run: bloom-560m byte-level LM on local text.

The reference's public evidence of correctness is convergence curves
(wandb links, reference README.md:87-92) from training bloom-560m on
imdb. This environment has no dataset egress, so the corpus is the
repository's own text (source + docs, ~1 MB) tokenized at the BYTE
level — real, structured natural-ish data with a well-defined held-out
split — trained on the REAL flagship config (bloom-560m, bf16, flash
kernels, remat, Adam) on the TPU.

What this demonstrates (and the CPU equivalence records cannot):
- the full single-chip train step LEARNS on hardware: train loss falls
  from ~ln(vocab) toward byte-entropy levels, val loss tracks it;
- sustained multi-step optimization with the bench configuration (the
  bench itself runs 10 steps from init).

Steps live inside lax.scan, ten to a dispatch; the per-chunk loss fetch
is where the host waits for the device.

    PYTHONPATH=. python scripts/train_tpu_convergence.py \
        [out.json] [--steps 300]
"""
from __future__ import annotations

import functools
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import optax

REPO = Path(__file__).resolve().parent.parent


def build_corpus() -> bytes:
    """Deterministic corpus: all tracked text files of the repo."""
    parts = []
    for pat in ("pipegoose_tpu/**/*.py", "tests/**/*.py", "docs/**/*.md",
                "*.md", "examples/*.py", "native/*.cpp"):
        for f in sorted(REPO.glob(pat)):
            parts.append(f.read_bytes())
    return b"\n\n".join(parts)


def batches(data: np.ndarray, rng: np.random.RandomState, n: int, b: int, s: int):
    """(n, b, s+0) random contiguous byte windows."""
    starts = rng.randint(0, len(data) - s - 1, size=(n, b))
    return np.stack(
        [[data[st:st + s] for st in row] for row in starts]
    ).astype(np.int32)


def main() -> None:
    steps = 300
    if "--steps" in sys.argv:
        steps = int(sys.argv[sys.argv.index("--steps") + 1])
    model = "bloom"
    if "--model" in sys.argv:
        model = sys.argv[sys.argv.index("--model") + 1]
    # per-model default paths, under the directory a chip call brings
    # back; the committed docs/acceptance/ records are never overwritten
    default_out = f"chiprun_out/TRAIN_TPU_{model.upper()}.json"
    out_path = (
        sys.argv[1]
        if len(sys.argv) > 1 and not sys.argv[1].startswith("--")
        else default_out
    )
    if "--cpu" in sys.argv:
        jax.config.update("jax_platforms", "cpu")

    from pipegoose_tpu.models import bloom, mixtral

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    b, s, inner = (8, 1024, 10) if on_tpu else (2, 128, 2)

    corpus = np.frombuffer(build_corpus(), dtype=np.uint8)
    split = int(len(corpus) * 0.9)
    train_data, val_data = corpus[:split], corpus[split:]
    print(f"corpus {len(corpus)} bytes, train {split}, val {len(val_data)}",
          file=sys.stderr)

    if model == "mixtral":
        # ~450M-param sparse-MoE sibling: GQA + SwiGLU experts + top-2
        # routing + the GQA flash kernels — the BASELINE config-5 family
        # exercised end-to-end on hardware (single-chip, EP dense here)
        cfg = (
            mixtral.MixtralConfig(
                vocab_size=32000, hidden_size=1024, intermediate_size=1792,
                n_layer=8, n_head=16, n_kv_head=4, num_experts=8, top_k=2,
                capacity_factor=1.25, dtype=jnp.bfloat16, remat=True,
                use_flash=True,
            )
            if on_tpu
            else mixtral.MixtralConfig(
                vocab_size=512, hidden_size=64, intermediate_size=96,
                n_layer=2, n_head=4, n_kv_head=2, num_experts=2, top_k=1,
            )
        )
        mod = mixtral
        model_name = "mixtral-moe-450m (8 experts, top-2, GQA, byte-level ids)"
    else:
        cfg = (
            bloom.BloomConfig.bloom_560m(dtype=jnp.bfloat16, remat=True,
                                         use_flash=True)
            if on_tpu
            else bloom.BloomConfig(vocab_size=512, hidden_size=128, n_layer=2,
                                   n_head=4)
        )
        mod = bloom
        model_name = "bloom-560m (byte-level ids over local text corpus)"
    # byte ids 0..255 live inside the real vocab; the model simply never
    # sees the other ids (their embeddings stay at init)
    params = mod.init_params(cfg, jax.random.PRNGKey(0))
    opt = optax.chain(
        optax.clip_by_global_norm(1.0),
        optax.adamw(optax.linear_schedule(0.0, 2e-4, 20), weight_decay=0.01),
    )
    opt_state = opt.init(params)

    rng = np.random.RandomState(0)
    val_ids = jnp.asarray(batches(val_data, np.random.RandomState(1), 4, b, s))

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def run_chunk(params, opt_state, ids_chunk):
        def body(carry, ids):
            params, opt_state = carry
            loss, grads = jax.value_and_grad(mod.loss_fn)(
                params, ids, None, ids, cfg
            )
            updates, opt_state = opt.update(grads, opt_state, params)
            return (optax.apply_updates(params, updates), opt_state), loss

        (params, opt_state), losses = jax.lax.scan(
            body, (params, opt_state), ids_chunk
        )
        return params, opt_state, losses

    @jax.jit
    def val_loss(params, val_ids):
        def one(ids):
            return mod.loss_fn(params, ids, None, ids, cfg)
        # sequential over val batches: one (B,S,V) fp32 logits buffer at
        # a time (a vmap would materialize all of them at once — 32 GB)
        return jax.lax.map(one, val_ids).mean()

    n_chunks = steps // inner
    if n_chunks < 1:
        raise SystemExit(f"--steps {steps} < chunk size {inner}: nothing to run")
    steps = n_chunks * inner  # record what actually runs

    curve = []
    v0 = float(val_loss(params, val_ids))
    t0 = time.perf_counter()
    tokens = 0
    for chunk in range(n_chunks):
        ids = jnp.asarray(batches(train_data, rng, inner, b, s))
        params, opt_state, losses = run_chunk(params, opt_state, ids)
        losses = np.asarray(losses, np.float64)
        tokens += inner * b * s
        curve.append(
            {"step": (chunk + 1) * inner, "train_loss": round(float(losses[-1]), 4)}
        )
        print(curve[-1], file=sys.stderr)
    dt = time.perf_counter() - t0
    v1 = float(val_loss(params, val_ids))

    record = {
        "record": "real-hardware-training-convergence",
        "family": model,
        "device": getattr(dev, "device_kind", dev.platform),
        "model": model_name if on_tpu else f"{model}-tiny smoke",
        "batch": b, "seq": s, "steps": steps,
        "corpus_bytes": int(len(corpus)),
        "val_loss_init": round(v0, 4),
        "val_loss_final": round(v1, 4),
        "train_curve": curve,
        "tokens_per_sec": round(tokens / dt, 1),
        "note": "loss starts near ln(vocab_size) (uniform) and must "
                "fall toward byte-level text entropy; val on a held-out "
                "10% split of the corpus",
    }
    Path(out_path).parent.mkdir(parents=True, exist_ok=True)
    Path(out_path).write_text(json.dumps(record, indent=1))
    print(json.dumps({"val_loss_init": v0, "val_loss_final": v1,
                      "final_train": curve[-1]["train_loss"]}))


if __name__ == "__main__":
    main()
