"""Does the system still start on the chip? One process drives bloom-560m
through the entry points a user calls — ``Trainer.fit`` and
``ServingEngine.run`` — on one TPU chip, checks every result against the
repo's own plain references, and prints one JSON object per phase. The
last line of stdout is exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

and the exit code is 0 only if every phase passed on a TPU. Without an
accelerator it exits non-zero before any phase runs.

    python chip_smoke.py                # one chip: device, dispatch,
                                        # kernels, train, serve
    python chip_smoke.py --chips 4      # four chips: ONLY the tp2 x dp2
                                        # hybrid trainer vs single-device

Weights and data come from ``--seed``; nothing is read from the network.
Times printed here are smoke prints, not benchmark metrics.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
# JAX reads the variable itself at import; set it only where nobody has.
# A fixed path inside the checkout: the path is part of the cache key.
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      os.path.join(REPO, ".jax_cache"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import optax  # noqa: E402

from pipegoose_tpu import ParallelContext  # noqa: E402
from pipegoose_tpu.models import bloom, generate as gen  # noqa: E402
from pipegoose_tpu.optim.zero import DistributedOptimizer  # noqa: E402
from pipegoose_tpu.serving import Request, ServingEngine  # noqa: E402
from pipegoose_tpu.telemetry import derived  # noqa: E402
from pipegoose_tpu.trainer import Callback, Trainer  # noqa: E402

# bloom-560m at its published widths, full depth
SIZE = dict(
    model=dict(vocab_size=250880, hidden_size=1024, n_layer=24, n_head=16),
    batch=8, seq=1024, plain_batch=2, steps=6, lr=1e-4,
    serve=dict(page_size=16, max_context=1024, num_slots=8, num_pages=4096),
    prompt_buckets=(80, 128, 256, 384, 512), new_tokens=(32, 64),
    n_requests=16, n_oracle=4,
    # quantized-weight engines hold the fp and the quantized tree at
    # once; their short run gets a smaller pool so both fit the chip
    quant_requests=4, quant_new=8, quant_pages=1024,
    kernel=dict(b=8, s=1024, nh=16, hd=64, h=1024, v=250880),
)


class Report:
    """One JSON line per phase; a phase is ok iff all its checks are."""

    def __init__(self):
        self.failed = []

    def phase(self, name, checks, **info):
        bad = sorted(k for k, v in checks.items() if not v)
        if bad:
            self.failed.append(name)
        print(json.dumps({"phase": name, "ok": not bad, "failed_checks": bad,
                          **info}), flush=True)


def rel_err(a, b):
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return float(np.abs(a - b).max() / max(float(np.abs(b).max()), 1e-6))


# -- device ----------------------------------------------------------------


def device_info():
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def phase_device(rep, info):
    # an unknown kind raises, naming the table it is missing from
    peaks = {
        "peak_flops": derived.peak_flops_for(info["kind"]),
        "hbm_bytes": derived.hbm_bytes_for(info["kind"]),
        "hbm_bw_bytes_per_s": derived.hbm_bw_bytes_per_s_for(info["kind"]),
    }
    rep.phase("device", {"platform_is_tpu": info["platform"] == "tpu"},
              **info, **peaks, jax=jax.__version__,
              cache_dir=os.environ["JAX_COMPILATION_CACHE_DIR"])


# -- dispatch ---------------------------------------------------------------


def phase_dispatch(rep):
    """Does ``block_until_ready`` wait for the device, and what does one
    tiny dispatch cost? A long chain of (n, n) matmuls is enqueued; if
    the call returned early and the wait took the time, the wait is
    real."""
    n = 4096        # 64 of these matmuls keep a v5e busy for ~45 ms

    @jax.jit
    def chain(x):
        return jax.lax.fori_loop(0, 64, lambda _, y: (y @ y) * (1.0 / n), x)

    corner = jax.jit(lambda y: y[0, 0])
    x = jnp.ones((n, n), jnp.bfloat16)
    float(corner(chain(x)))                          # compile both
    t0 = time.perf_counter()
    y = chain(x)
    t_enqueue = time.perf_counter() - t0
    y.block_until_ready()
    t_ready = time.perf_counter() - t0
    t1 = time.perf_counter()
    float(corner(y))                                 # nothing left to wait for
    t_fetch_after = time.perf_counter() - t1

    tiny = jax.jit(lambda z: z + 1.0)
    z = jnp.zeros(())
    tiny(z).block_until_ready()
    samples = []
    for _ in range(200):
        t0 = time.perf_counter()
        tiny(z).block_until_ready()
        samples.append(time.perf_counter() - t0)
    flops = 64 * 2 * n ** 3
    rep.phase(
        "dispatch",
        # the wait, not the enqueue, must carry the device time
        {"block_until_ready_waits": t_ready > 4 * t_enqueue
         and t_fetch_after < 0.5 * t_ready},
        enqueue_s=t_enqueue, ready_s=t_ready,
        fetch_after_ready_s=t_fetch_after,
        chain_tflops_if_ready_is_real=flops / t_ready / 1e12,
        tiny_dispatch_median_s=float(np.median(samples)),
        tiny_dispatch_p90_s=float(np.percentile(samples, 90)),
    )


# -- kernels ----------------------------------------------------------------


def phase_kernels(rep, seed):
    """Each Pallas kernel of the main path, compiled at model width and
    compared on the device with the repo's plain-XLA reference."""
    from pipegoose_tpu.nn.sequence_parallel.ring_attention import (
        ring_flash_attention,
    )
    from pipegoose_tpu.ops import flash_attention as fa
    from pipegoose_tpu.ops.fused_ce import fused_ce_sums
    from pipegoose_tpu.quant import QuantSpec
    from pipegoose_tpu.quant.matmul import _matmul_xla, quantized_matmul
    from pipegoose_tpu.quant.weights import _quantize_kernel

    k = SIZE["kernel"]
    b, s, nh, hd, h, v = k["b"], k["s"], k["nh"], k["hd"], k["h"], k["v"]
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 32))
    bf = jnp.bfloat16
    slopes = jnp.asarray(bloom.alibi_slopes(nh))
    checks, info = {}, {}

    def record(name, fn, ref_fn, args, tol):
        """Run kernel and reference on the device; the kernel must be a
        Mosaic custom call in the compiled program."""
        kernel = jax.jit(fn).lower(*args).compile()
        text = kernel.as_text()
        out = jax.block_until_ready(kernel(*args))
        ref = jax.block_until_ready(jax.jit(ref_fn)(*args))
        errs = [rel_err(o, r) for o, r in zip(jax.tree_util.tree_leaves(out),
                                              jax.tree_util.tree_leaves(ref))]
        finite = all(bool(jnp.isfinite(o.astype(jnp.float32)).all())
                     for o in jax.tree_util.tree_leaves(out))
        checks[f"{name}.custom_call"] = "tpu_custom_call" in text
        checks[f"{name}.agrees"] = finite and max(errs) <= tol
        info[name] = {"rel_err": max(errs), "tol": tol}

    # flash attention, forward and backward, vs dense attention
    q, kk, vv = (jax.random.normal(next(keys), (b, s, nh, hd), bf)
                 for _ in range(3))

    def dense_attn(q, k, v):
        flat = lambda x: x.transpose(0, 2, 1, 3).reshape(b * nh, s, hd)  # noqa: E731
        out = fa._xla_reference(flat(q), flat(k), flat(v),
                                jnp.tile(slopes, b), hd ** -0.5, True)
        return out.reshape(b, nh, s, hd).transpose(0, 2, 1, 3)

    def flash(q, k, v):
        return fa.flash_attention(q, k, v, alibi_slopes=slopes,
                                  interpret=False)

    def grads_of(attn):
        return jax.grad(lambda q, k, v: (attn(q, k, v).astype(jnp.float32)
                                         * ct).sum(), argnums=(0, 1, 2))

    ct = jax.random.normal(next(keys), (b, s, nh, hd), jnp.float32)
    record("flash_fwd", flash, dense_attn, (q, kk, vv), 2e-2)
    record("flash_bwd", grads_of(flash), grads_of(dense_attn), (q, kk, vv),
           4e-2)
    record("ring_chunk",
           lambda q, k, v: ring_flash_attention(
               q, k, v, None, alibi_slopes=slopes, interpret=False),
           dense_attn, (q, kk, vv), 2e-2)

    # fused cross entropy, forward and backward, vs dense logits
    t = b * s
    hid = jax.random.normal(next(keys), (t, h), bf)
    emb = (jax.random.normal(next(keys), (v, h), jnp.float32) * 0.02).astype(bf)
    tgt = jax.random.randint(next(keys), (t,), 0, v)
    tw = jnp.ones((t,), jnp.float32)

    def fused_loss(hid, emb):
        tot, cnt = fused_ce_sums(hid, emb, tgt, tw, interpret=False)
        return tot / cnt

    def dense_loss(hid, emb):
        # chunked over tokens so (T, V) f32 logits never exist at once
        def chunk(carry, xs):
            hc, tc = xs
            logits = jnp.dot(hc, emb.T, preferred_element_type=jnp.float32)
            lse = jax.nn.logsumexp(logits, axis=-1)
            picked = jnp.take_along_axis(logits, tc[:, None], axis=1)[:, 0]
            return carry + (lse - picked).sum(), None

        n_chunks = max(1, t // 512)
        tot, _ = jax.lax.scan(
            jax.checkpoint(chunk), jnp.float32(0),
            (hid.reshape(n_chunks, -1, h), tgt.reshape(n_chunks, -1)))
        return tot / t

    record("fused_ce_fwd", fused_loss, dense_loss, (hid, emb), 1e-2)
    record("fused_ce_bwd", jax.grad(fused_loss, argnums=(0, 1)),
           jax.grad(dense_loss, argnums=(0, 1)), (hid, emb), 4e-2)

    # dequant-fused matmuls vs the XLA lane with the same math
    x = jax.random.normal(next(keys), (b, h), bf)
    w = jax.random.normal(next(keys), (h, 4 * h), jnp.float32) * 0.02
    for mode in ("int8", "int4"):
        leaf = _quantize_kernel(w, QuantSpec(mode, 32))
        record(f"matmul_{mode}",
               lambda x, qw, sc: quantized_matmul(x, qw, sc, impl="pallas",
                                                  interpret=False),
               lambda x, qw, sc: _matmul_xla(x.astype(jnp.float32), qw, sc,
                                             mode == "int4"),
               (x, leaf["q"], leaf["scale"]), 2e-2)

    rep.phase("kernels", checks, kernels=info)


# -- train ------------------------------------------------------------------


class StepClock(Callback):
    """Per-step wall time with the device drained at both ends."""

    def __init__(self):
        self.seconds = []

    def on_step_start(self, trainer, step):
        jax.block_until_ready(trainer.params)
        self._t0 = time.perf_counter()

    def on_step_end(self, trainer, step, loss):
        jax.block_until_ready((loss, trainer.params))
        self.seconds.append(time.perf_counter() - self._t0)


def make_trainer(cfg, params, ctx, lr, callbacks=()):
    return Trainer(
        loss_fn=lambda p, ids: bloom.loss_fn(p, ids, None, ids, cfg,
                                             tp_axis="tensor"),
        params=params,
        param_specs=bloom.tp_specs(params),
        optimizer=DistributedOptimizer(optax.adam(lr), axis_name="data"),
        parallel_context=ctx,
        callbacks=list(callbacks),
    )


def train_config(**kw):
    return bloom.BloomConfig(**SIZE["model"], dtype=jnp.bfloat16, remat=True,
                             **kw)


def token_batch(seed, batch):
    return jnp.asarray(np.random.RandomState(seed).randint(
        0, SIZE["model"]["vocab_size"], (batch, SIZE["seq"])), jnp.int32)


def step_text(trainer, ids):
    """The compiled train step's text. A persistent-cache hit after the
    step has already run, so this costs no second compile."""
    return trainer._step_fn.lower(trainer.params, trainer.opt_state,
                                  ids).compile().as_text()


def phase_train(rep, seed):
    cfg = train_config(use_flash=True, fused_ce=True)
    plain = train_config(use_flash=False, fused_ce=False)
    params = bloom.init_params(cfg, jax.random.PRNGKey(seed))
    ids = token_batch(seed, SIZE["batch"])
    small = ids[:SIZE["plain_batch"]]

    ctx = ParallelContext(tensor_parallel_size=1, data_parallel_size=1)
    clock = StepClock()
    trainer = make_trainer(cfg, params, ctx, SIZE["lr"], [clock])
    # first-step loss vs the plain path (XLA attention, full logits) on
    # the same params, at a batch whose logits the plain path can hold
    fast_loss = trainer.evaluate([small])
    plain_loss = float(jax.jit(
        lambda p, x: bloom.loss_fn(p, x, None, x, plain))(params, small))
    del params

    t0 = time.perf_counter()
    trainer.fit([ids], max_steps=1)                   # compile + warm-up
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    text = step_text(trainer, ids)
    relower_s = time.perf_counter() - t0
    state = trainer.fit([ids] * SIZE["steps"], max_steps=SIZE["steps"])
    losses = [float(x) for x in state.losses]
    timed = clock.seconds[1:]
    step_s = float(np.median(timed))
    stats = jax.devices()[0].memory_stats() or {}
    rep.phase(
        "train",
        {
            "steps_taken": len(losses) == SIZE["steps"],
            "loss_finite": bool(np.isfinite(losses).all()),
            "loss_falls_on_repeated_batch": losses[-1] < losses[0],
            "step_has_custom_call": "tpu_custom_call" in text,
            "fast_path_agrees_with_plain":
                abs(fast_loss - plain_loss) <= 2e-2 * abs(plain_loss),
        },
        config=f"bloom hidden {cfg.hidden_size} x {cfg.n_layer} layers, "
               f"bf16, remat, use_flash, fused_ce",
        batch=SIZE["batch"], seq=SIZE["seq"], losses=losses,
        fast_loss_small_batch=fast_loss, plain_loss_small_batch=plain_loss,
        compile_and_first_step_s=compile_s, relower_after_run_s=relower_s,
        step_s_median=step_s, step_s_all=timed,
        tokens_per_s=SIZE["batch"] * SIZE["seq"] / step_s,
        peak_bytes_in_use=stats.get("peak_bytes_in_use"),
    )
    ctx.destroy()


# -- serve ------------------------------------------------------------------


def make_requests(seed, n, buckets=None, new_tokens=None):
    rng = np.random.RandomState(seed)
    buckets = buckets or SIZE["prompt_buckets"]
    lo, hi = new_tokens or SIZE["new_tokens"]
    ps = SIZE["serve"]["page_size"]
    reqs = []
    for i in range(n):
        # lengths fall in a few page buckets so the engine compiles a
        # few prefill programs, not one per request
        length = buckets[i % len(buckets)] - int(rng.randint(0, ps))
        prompt = rng.randint(1, SIZE["model"]["vocab_size"], (length,))
        reqs.append((prompt, int(rng.randint(lo, hi + 1))))
    return reqs


def run_engine(params, cfg, reqs, **kw):
    """Serve ``reqs`` on a fresh engine. Returns the generated tokens,
    whether the pool drained, the wall time (compiles included) and the
    compiled decode step's text (a cache hit: the step has just run)."""
    eng = ServingEngine(params, cfg, **{**SIZE["serve"], **kw})
    t0 = time.perf_counter()
    outs, _ = eng.run([Request(prompt=p, max_new_tokens=n) for p, n in reqs])
    wall = time.perf_counter() - t0
    toks = [np.asarray(o.generated) for o in outs]
    drained = eng.pool.used_count == 0
    shape = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)  # noqa: E731
    text = eng._step.lower(
        eng.params, jax.ShapeDtypeStruct((eng._carry_size,), jnp.int32),
        jax.tree_util.tree_map(shape, eng.k_pages),
        jax.tree_util.tree_map(shape, eng.v_pages),
    ).compile().as_text()
    del eng
    gc.collect()
    return toks, drained, wall, text


def reference_tokens(params, cfg, prompt, n):
    out = gen.generate(params, jnp.asarray(prompt)[None], cfg,
                       max_new_tokens=n)
    return np.asarray(out)[0, len(prompt):]


def same_tokens(a, b):
    """(all identical, number of identical requests, first divergence)."""
    same = [x.shape == y.shape and bool((x == y).all()) for x, y in zip(a, b)]
    first = next(({"request": i,
                   "at": int(np.argmax(x != y)) if x.shape == y.shape else -1}
                  for i, (x, y) in enumerate(zip(a, b)) if not same[i]), None)
    return all(same), sum(same), first


def phase_serve(rep, seed):
    cfg = bloom.BloomConfig(**SIZE["model"], dtype=jnp.bfloat16)
    params = bloom.init_params(cfg, jax.random.PRNGKey(seed))
    reqs = make_requests(seed, SIZE["n_requests"])
    n_gen = sum(n for _, n in reqs)
    checks, info = {}, {"requests": len(reqs), "generated_tokens": n_gen,
                        "prompt_lens": [len(p) for p, _ in reqs],
                        **SIZE["serve"]}

    # the engine vs per-request generate()
    base, drained, wall, _ = run_engine(params, cfg, reqs)
    oracle = [reference_tokens(params, cfg, p, n)
              for p, n in reqs[:SIZE["n_oracle"]]]
    ok, n_same, first = same_tokens(base[:len(oracle)], oracle)
    checks["gather.identical_to_generate"] = ok
    checks["gather.pool_drained"] = drained
    checks["gather.all_tokens_emitted"] = sum(map(len, base)) == n_gen
    info["gather"] = {"wall_s_with_compiles": wall, "oracle_requests":
                      len(oracle), "identical": n_same, "first_diff": first}

    # quantized weights: the engine's dequant-fused matmuls vs
    # generate() over the same quantized tree
    from pipegoose_tpu.quant import QuantSpec, quantize_params

    qreqs = make_requests(seed + 1, SIZE["quant_requests"],
                          buckets=SIZE["prompt_buckets"][1:2],
                          new_tokens=(SIZE["quant_new"],) * 2)
    for mode in ("int8", "int4"):
        toks, drained, wall, text = run_engine(
            params, cfg, qreqs, weight_dtype=mode,
            num_pages=SIZE["quant_pages"])
        qparams = quantize_params(params, QuantSpec(mode, 32))
        oracle = [reference_tokens(qparams, cfg, *qreqs[0])]
        ok, n_same, first = same_tokens(toks[:1], oracle)
        checks[f"{mode}_weights.identical_to_generate"] = ok
        checks[f"{mode}_weights.pool_drained"] = drained
        checks[f"{mode}_weights.step_has_custom_call"] = (
            "tpu_custom_call" in text)
        info[f"{mode}_weights"] = {"wall_s_with_compiles": wall,
                                   "requests": len(qreqs),
                                   "first_diff": first}
        del qparams
    rep.phase("serve", checks, **info)


# -- four chips: the hybrid trainer ------------------------------------------


def phase_hybrid(rep, seed):
    """tp=2 x dp=2 + ZeRO-1 through Trainer, against the same steps of
    the single-device Trainer on device 0, in this process."""
    steps = 3
    cfg = train_config(use_flash=True, fused_ce=True)
    params = bloom.init_params(cfg, jax.random.PRNGKey(seed))
    params, cfg = bloom.pad_for_tp(params, cfg, 2)
    # kept on the host: a full copy living on device 0 would count
    # against it when the four devices' memory is compared
    params = jax.tree_util.tree_map(np.asarray, params)
    ids = token_batch(seed, SIZE["batch"])
    devices = jax.devices()

    ctx = ParallelContext(tensor_parallel_size=2, data_parallel_size=2)
    mesh = ctx.mesh
    layout = [{"id": d.id, "coords": list(getattr(d, "coords", ())),
               "axes": {a: int(i) for a, i in zip(mesh.axis_names, idx)
                        if mesh.shape[a] > 1}}
              for idx, d in np.ndenumerate(mesh.devices)]
    clock = StepClock()
    trainer = make_trainer(cfg, params, ctx, SIZE["lr"], [clock])
    t0 = time.perf_counter()
    trainer.fit([ids], max_steps=1)
    compile_s = time.perf_counter() - t0
    text = step_text(trainer, ids)
    state = trainer.fit([ids] * steps, max_steps=steps)
    hybrid_losses = [float(x) for x in state.losses]
    single_device = [
        jax.tree_util.keystr(path)
        for path, leaf in jax.tree_util.tree_leaves_with_path(trainer.params)
        if len(leaf.sharding.device_set) == 1
    ]
    # with the trainer alive: each device's share of params + ZeRO
    # state. The check below reads bytes_in_use; peak_bytes_in_use is
    # printed but not compared, since device 0's peak also holds the
    # unsharded tree that init_params made there before it was cut
    stats = [d.memory_stats() or {} for d in devices]
    in_use = [st.get("bytes_in_use") for st in stats]
    del trainer, state
    ctx.destroy()
    gc.collect()

    ctx1 = ParallelContext(tensor_parallel_size=1, data_parallel_size=1,
                           devices=devices[:1])
    single = make_trainer(cfg, params, ctx1, SIZE["lr"])
    single_losses = [float(x) for x in
                     single.fit([ids] * steps, max_steps=steps).losses]
    ctx1.destroy()

    diffs = [abs(a - b) / abs(b) for a, b in zip(hybrid_losses, single_losses)]
    known = [b for b in in_use if b]
    rep.phase(
        "hybrid_tp2_dp2",
        {
            "four_devices": len(devices) == 4,
            "losses_match_single_device": len(diffs) == steps
            and max(diffs) <= 2e-2,
            "loss_finite": bool(np.isfinite(hybrid_losses).all()),
            "no_param_on_one_device": not single_device,
            "bytes_in_use_within_25pct": len(known) == len(devices)
            and max(known) <= 1.25 * min(known),
            "step_has_collectives": "all-reduce" in text
            or "reduce-scatter" in text,
            "step_has_custom_call": "tpu_custom_call" in text,
        },
        mesh_axes=dict(zip(mesh.axis_names, mesh.devices.shape)),
        mesh_devices=layout, hybrid_losses=hybrid_losses,
        single_losses=single_losses, max_rel_diff=max(diffs),
        params_on_one_device=single_device[:5],
        bytes_in_use=in_use,
        peak_bytes_in_use=[st.get("peak_bytes_in_use") for st in stats],
        compile_and_first_step_s=compile_s,
        step_s_all=clock.seconds[1:],
        collectives={op: text.count(op) for op in
                     ("all-reduce", "reduce-scatter", "all-gather",
                      "collective-permute")},
    )


# -- driver -----------------------------------------------------------------


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the tp2 x dp2 hybrid trainer and the "
                         "single-device trainer it is compared with")
    args = ap.parse_args()

    info = device_info()
    if info["platform"] != "tpu":
        sys.exit(f"chip_smoke: no TPU (jax found {info}); nothing was run")
    if info["count"] < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} "
                 f"devices, jax found {info['count']}")

    rep = Report()
    phase_device(rep, info)
    if args.chips == 4:
        phase_hybrid(rep, args.seed)
    else:
        phase_dispatch(rep)
        phase_kernels(rep, args.seed)
        phase_train(rep, args.seed)
        gc.collect()
        phase_serve(rep, args.seed)
    ok = not rep.failed
    if not ok:
        print(f"chip_smoke: failed phases: {rep.failed}", file=sys.stderr)
    print(json.dumps({"ok": ok, "device": info}), flush=True)
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
