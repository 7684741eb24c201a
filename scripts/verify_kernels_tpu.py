"""Compile-and-verify every Pallas kernel on the real TPU chip.

All flash tests in tests/ run with ``interpret=True`` on the CPU mesh;
block shapes, VMEM budgets, and scalar-prefetch layouts routinely pass
interpret mode and fail (or crawl) on hardware. This script runs each
kernel COMPILED (``interpret=False``) on the TPU, checks numerics
against the dense XLA reference, times the flash-vs-XLA A/B, and writes
a JSON record. ``chip_smoke.py`` checks the bloom-560m kernels on every
run; this script adds the variants other model families use.

Usage (one process per chip):

    python scripts/verify_kernels_tpu.py [out.json]

Covers:
- flash fwd+bwd: causal+ALiBi (BLOOM), padded mask, GQA (nkv<nh),
  sliding window (Mixtral), non-causal  (ops/flash_attention.py)
- ring-flash chunk kernels via ring_flash_attention's sp=1 path, which
  invokes flash_ring_chunk / flash_chunk_dq / flash_chunk_dkv compiled
  (nn/sequence_parallel/ring_attention.py)
- timing: fwd and fwd+bwd wall-clock vs the XLA (S,S) path at a
  realistic shape.
"""
from __future__ import annotations

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from pipegoose_tpu.ops import flash_attention as fa


def dense_reference(q, k, v, slopes, scale, causal, attention_mask=None,
                    window=None):
    """(B, S, nh, hd) dense attention with ALiBi/padding/window — the
    ground truth every kernel variant is checked against (f32 math)."""
    b, s, nh, hd = q.shape
    nkv = k.shape[2]
    if nkv != nh:  # GQA: expand shared kv heads for the dense path
        g = nh // nkv
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    if attention_mask is not None:
        kv_pos, kv_neg = fa.mask_to_kv_bias(attention_mask)
    else:
        kv_pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.float32)[None], (b, s))
        kv_neg = jnp.zeros((b, s), jnp.float32)
    scores = scores + slopes[None, :, None, None] * kv_pos[:, None, None, :]
    scores = scores + kv_neg[:, None, None, :]
    qi = jnp.arange(s)[:, None]
    ki = jnp.arange(s)[None, :]
    keep = jnp.ones((s, s), bool)
    if causal:
        keep = keep & (ki <= qi)
    if window is not None:
        keep = keep & (qi - ki < window)
    scores = jnp.where(keep[None, None], scores, fa.NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def rel_err(a, b):
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    denom = max(float(np.abs(b).max()), 1e-6)
    return float(np.abs(a - b).max() / denom)


def check_variant(name, *, b=2, s=512, nh=8, nkv=None, hd=64, causal=True,
                  alibi=True, padded=False, window=None, dtype=jnp.bfloat16):
    nkv = nkv or nh
    key = jax.random.PRNGKey(0)
    kq, kk, kv_, kg = jax.random.split(key, 4)
    q = jax.random.normal(kq, (b, s, nh, hd), dtype)
    k = jax.random.normal(kk, (b, s, nkv, hd), dtype)
    v = jax.random.normal(kv_, (b, s, nkv, hd), dtype)
    slopes = (
        jnp.asarray([2.0 ** (-(i + 1)) for i in range(nh)], jnp.float32)
        if alibi else jnp.zeros((nh,), jnp.float32)
    )
    mask = None
    if padded:
        lens = np.full((b,), s)
        lens[0] = s - 3 * (s // 8)  # ragged right padding
        mask = jnp.asarray(np.arange(s)[None, :] < lens[:, None]).astype(jnp.int32)
    scale = hd ** -0.5

    def flash_loss(q, k, v):
        out = fa.flash_attention(
            q, k, v, alibi_slopes=slopes, attention_mask=mask,
            causal=causal, interpret=False, window=window,
        )
        return (out.astype(jnp.float32) ** 2).sum(), out

    def ref_loss(q, k, v):
        out = dense_reference(q, k, v, slopes, scale, causal,
                              attention_mask=mask, window=window)
        return (out.astype(jnp.float32) ** 2).sum(), out

    (_, out_f), grads_f = jax.jit(
        jax.value_and_grad(flash_loss, argnums=(0, 1, 2), has_aux=True)
    )(q, k, v)
    (_, out_r), grads_r = jax.jit(
        jax.value_and_grad(ref_loss, argnums=(0, 1, 2), has_aux=True)
    )(q, k, v)
    jax.block_until_ready((out_f, grads_f, out_r, grads_r))

    if padded:  # padded rows hold uniform garbage by design — compare valid only
        m = np.asarray(mask)[:, :, None, None].astype(bool)
        sel = lambda x: np.asarray(x, np.float32) * m  # noqa: E731
    else:
        sel = lambda x: np.asarray(x, np.float32)  # noqa: E731

    errs = {
        "out": rel_err(sel(out_f), sel(out_r)),
        "dq": rel_err(sel(grads_f[0]), sel(grads_r[0])),
        "dk": rel_err(np.asarray(grads_f[1], np.float32),
                      np.asarray(grads_r[1], np.float32)),
        "dv": rel_err(np.asarray(grads_f[2], np.float32),
                      np.asarray(grads_r[2], np.float32)),
    }
    ok = all(e < 2.5e-2 for e in errs.values())  # bf16 in, f32 accum
    return {"variant": name, "ok": ok, "max_rel_err": errs}


def check_ring_chunks(b=2, s=512, nh=8, hd=64, dtype=jnp.bfloat16):
    """ring_flash_attention with axis_name=None compiles and runs
    flash_ring_chunk + flash_chunk_dq/dkv on the chip (sp=1 path)."""
    from pipegoose_tpu.nn.sequence_parallel.ring_attention import (
        ring_flash_attention,
    )

    key = jax.random.PRNGKey(1)
    kq, kk, kv_ = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, s, nh, hd), dtype)
    k = jax.random.normal(kk, (b, s, nh, hd), dtype)
    v = jax.random.normal(kv_, (b, s, nh, hd), dtype)
    slopes = jnp.asarray([2.0 ** (-(i + 1)) for i in range(nh)], jnp.float32)
    lens = np.full((b,), s)
    lens[0] = s - s // 4
    mask = jnp.asarray(np.arange(s)[None, :] < lens[:, None]).astype(jnp.float32)
    scale = hd ** -0.5

    def ring_loss(q, k, v):
        out = ring_flash_attention(
            q, k, v, axis_name=None, alibi_slopes=slopes, kv_side=mask,
            interpret=False,
        )
        return (out.astype(jnp.float32) ** 2).sum(), out

    def ref_loss(q, k, v):
        # the ring path uses plain (non-cumsum) key positions for ALiBi —
        # matches HF for right padding; mirror that here
        b_, s_ = mask.shape
        kv_pos = jnp.broadcast_to(
            jnp.arange(s_, dtype=jnp.float32)[None], (b_, s_)
        )
        scores = jnp.einsum(
            "bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)
        ) * scale
        scores = scores + slopes[None, :, None, None] * kv_pos[:, None, None, :]
        scores = scores + jnp.where(mask[:, None, None, :] > 0, 0.0, fa.NEG_INF)
        keep = jnp.arange(s_)[None, :] <= jnp.arange(s_)[:, None]
        scores = jnp.where(keep[None, None], scores, fa.NEG_INF)
        p = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32)).astype(q.dtype)
        return (out.astype(jnp.float32) ** 2).sum(), out

    (_, out_f), grads_f = jax.jit(
        jax.value_and_grad(ring_loss, argnums=(0, 1, 2), has_aux=True)
    )(q, k, v)
    (_, out_r), grads_r = jax.jit(
        jax.value_and_grad(ref_loss, argnums=(0, 1, 2), has_aux=True)
    )(q, k, v)
    jax.block_until_ready((out_f, grads_f, out_r, grads_r))

    m = np.asarray(mask)[:, :, None, None].astype(bool)
    errs = {
        "out": rel_err(np.asarray(out_f, np.float32) * m,
                       np.asarray(out_r, np.float32) * m),
        "dq": rel_err(np.asarray(grads_f[0], np.float32) * m,
                      np.asarray(grads_r[0], np.float32) * m),
        "dk": rel_err(np.asarray(grads_f[1], np.float32),
                      np.asarray(grads_r[1], np.float32)),
        "dv": rel_err(np.asarray(grads_f[2], np.float32),
                      np.asarray(grads_r[2], np.float32)),
    }
    ok = all(e < 2.5e-2 for e in errs.values())
    return {"variant": "ring-flash-chunks(sp=1,causal,alibi,padded)",
            "ok": ok, "max_rel_err": errs}


def time_ab(b=8, s=2048, nh=16, hd=64, dtype=jnp.bfloat16, iters=20):
    """Flash-vs-XLA wall clock. The iteration loop lives INSIDE jit
    (lax.scan, output chained into the next input so steps serialize);
    the timed call ends in block_until_ready."""
    from jax import lax

    key = jax.random.PRNGKey(2)
    kq, kk, kv_ = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, s, nh, hd), dtype)
    k = jax.random.normal(kk, (b, s, nh, hd), dtype)
    v = jax.random.normal(kv_, (b, s, nh, hd), dtype)
    slopes = jnp.asarray([2.0 ** (-(i + 1)) for i in range(nh)], jnp.float32)
    scale = hd ** -0.5

    def flash_out(q):
        return fa.flash_attention(
            q, k, v, alibi_slopes=slopes, causal=True, interpret=False
        )

    def xla_out(q):
        return dense_reference(q, k, v, slopes, scale, True)

    def bench(out_fn, grad):
        if grad:
            step = jax.grad(lambda x: (out_fn(x).astype(jnp.float32) ** 2).sum())
        else:
            step = out_fn

        @jax.jit
        def chain(q):
            def body(c, _):
                return step(c).astype(dtype), ()
            o, _ = lax.scan(body, q, None, length=iters)
            return o.astype(jnp.float32).sum()

        jax.block_until_ready(chain(q))  # compile + warm
        t0 = time.perf_counter()
        jax.block_until_ready(chain(q))
        return (time.perf_counter() - t0) / iters * 1e3  # ms

    res = {
        "shape": [b, s, nh, hd],
        "fwd_ms": {"flash": bench(flash_out, False), "xla": bench(xla_out, False)},
        "fwd_bwd_ms": {"flash": bench(flash_out, True), "xla": bench(xla_out, True)},
    }
    res["fwd_speedup"] = round(res["fwd_ms"]["xla"] / res["fwd_ms"]["flash"], 3)
    res["fwd_bwd_speedup"] = round(
        res["fwd_bwd_ms"]["xla"] / res["fwd_bwd_ms"]["flash"], 3
    )
    return res


def check_fused_ce(layout="vh", t=1024, h=1024, v=250_880,
                   dtype=jnp.bfloat16):
    """Fused vocab CE (ops/fused_ce.py) COMPILED at the real bench
    vocab: loss + both grads vs the materialized-logits reference.
    ``layout``: vh = tied (V,H) embedding, hv = untied (H,V) head."""
    from pipegoose_tpu.ops.fused_ce import fused_ce_sums

    key = jax.random.PRNGKey(2)
    kh, kw = jax.random.split(key)
    hid = jax.random.normal(kh, (t, h), dtype) * 0.3
    w = jax.random.normal(
        kw, (v, h) if layout == "vh" else (h, v), dtype
    ) * 0.02
    targets = jnp.asarray(np.random.RandomState(0).randint(0, v, (t,)))
    token_w = jnp.asarray(
        (np.random.RandomState(1).rand(t) < 0.9).astype(np.float32)
    )

    def fused_loss(hid, w):
        tot, cnt = fused_ce_sums(
            hid, w, targets, token_w, interpret=False, weight_layout=layout
        )
        return tot / cnt

    def ref_loss(hid, w):
        hid32 = hid.astype(jnp.float32)
        w32 = w.astype(jnp.float32)
        eq = "th,vh->tv" if layout == "vh" else "th,hv->tv"
        logits = jnp.einsum(eq, hid32, w32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        pred = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
        per = lse - pred
        return (per * token_w).sum() / token_w.sum()

    fl, (fdh, fdw) = jax.jit(
        jax.value_and_grad(fused_loss, argnums=(0, 1))
    )(hid, w)
    rl, (rdh, rdw) = jax.jit(
        jax.value_and_grad(ref_loss, argnums=(0, 1))
    )(hid, w)
    jax.block_until_ready((fl, fdh, fdw, rl, rdh, rdw))
    errs = {
        "loss": abs(float(fl) - float(rl)) / max(abs(float(rl)), 1e-6),
        "dh": rel_err(fdh, rdh),
        "dw": rel_err(fdw, rdw),
    }
    ok = all(e < 2.5e-2 for e in errs.values())
    return {"variant": f"fused-ce-{layout}", "ok": ok, "max_rel_err": errs,
            "shape": {"t": t, "h": h, "v": v}}


def main():
    out_path = sys.argv[1] if len(sys.argv) > 1 else "chiprun_out/KERNELS_TPU.json"
    os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
    dev = jax.devices()[0]
    record = {
        "record": "pallas-kernels-compiled-on-hardware",
        "device": getattr(dev, "device_kind", str(dev)),
        "platform": dev.platform,
        "interpret": False,
        "variants": [],
    }
    variants = [
        ("causal+alibi (BLOOM)", dict(alibi=True)),
        ("causal no-bias", dict(alibi=False)),
        ("non-causal", dict(alibi=False, causal=False)),
        ("padded mask", dict(alibi=True, padded=True)),
        ("GQA g=4", dict(alibi=False, nh=8, nkv=2)),
        ("sliding window=128 (Mixtral)", dict(alibi=False, window=128)),
        ("GQA g=4 + window=128", dict(alibi=False, nh=8, nkv=2, window=128)),
        ("long seq 4096", dict(alibi=True, s=4096, b=1)),
    ]
    for name, kw in variants:
        t0 = time.perf_counter()
        try:
            r = check_variant(name, **kw)
        except Exception as e:  # noqa: BLE001
            r = {"variant": name, "ok": False,
                 "error": f"{type(e).__name__}: {e}"[:400]}
        r["wall_s"] = round(time.perf_counter() - t0, 1)
        record["variants"].append(r)
        print(json.dumps(r), flush=True)

    t0 = time.perf_counter()
    try:
        r = check_ring_chunks()
    except Exception as e:  # noqa: BLE001
        r = {"variant": "ring-flash-chunks", "ok": False,
             "error": f"{type(e).__name__}: {e}"[:400]}
    r["wall_s"] = round(time.perf_counter() - t0, 1)
    record["variants"].append(r)
    print(json.dumps(r), flush=True)

    for layout in ("vh", "hv"):
        t0 = time.perf_counter()
        try:
            r = check_fused_ce(layout)
        except Exception as e:  # noqa: BLE001
            r = {"variant": f"fused-ce-{layout}", "ok": False,
                 "error": f"{type(e).__name__}: {e}"[:400]}
        r["wall_s"] = round(time.perf_counter() - t0, 1)
        record["variants"].append(r)
        print(json.dumps(r), flush=True)

    try:
        record["timing"] = time_ab()
        print(json.dumps(record["timing"]), flush=True)
    except Exception as e:  # noqa: BLE001
        record["timing"] = {"error": f"{type(e).__name__}: {e}"[:400]}

    record["all_ok"] = all(v.get("ok") for v in record["variants"])
    with open(out_path, "w") as f:
        json.dump(record, f, indent=1)
    print(f"wrote {out_path} all_ok={record['all_ok']}")


if __name__ == "__main__":
    main()
