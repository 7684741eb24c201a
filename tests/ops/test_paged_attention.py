"""Fused Pallas paged-attention kernel (ISSUE 20), interpret mode.

The kernel walks page tables directly — per-tile DMA of raw page
planes (int8 ``{q, scale}`` dequantized in-register), ALiBi-biased
online softmax, one HBM pass, no contiguous KV materialization. These
tests pin it against two references: ``paged_attention_reference``
(gather + plain XLA softmax over the same page table — the exact math
``serving/kv_pool.py``'s gather path computes) and a hand-rolled dense
attention over only each row's valid prefix, which proves the
causal-over-global-position mask really excludes stale tails, NULL
pages, and unwritten offsets rather than the two impls sharing a
masking bug. The VMEM feasibility guard (fused_ce idiom: loud for
compiled runs, exempt under interpret) gets its unit here too."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from pipegoose_tpu.distributed.compat import shard_map
from pipegoose_tpu.ops.paged_attention import (
    check_paged_tile,
    paged_attention,
    paged_attention_reference,
    paged_tile_geometry,
)
from pipegoose_tpu.serving.kv_pool import quantize_kv

PS, NH, HD = 4, 4, 16      # page_size, n_heads, head_dim
NPAGES, W = 24, 5          # pool pages, table width


def _slopes(n):
    return jnp.asarray([2.0 ** (-(i + 1)) for i in range(n)], jnp.float32)


def _make_pool(rng, quantized):
    """Random fp pages; garbage EVERYWHERE including the NULL page —
    the mask, not zeroed memory, must keep invalid keys out."""
    k = jnp.asarray(rng.randn(NPAGES, PS, NH, HD), jnp.float32)
    v = jnp.asarray(rng.randn(NPAGES, PS, NH, HD), jnp.float32)

    def rows(x):   # the pool's layout: a position's heads in one row
        return x.reshape(NPAGES, PS, NH * HD)

    if not quantized:
        return rows(k), rows(v), k, v
    kq, ks = quantize_kv(k)
    vq, vs = quantize_kv(v)
    kd = (kq.astype(jnp.float32) * ks[..., None])
    vd = (vq.astype(jnp.float32) * vs[..., None])
    return ({"q": rows(kq), "scale": ks}, {"q": rows(vq), "scale": vs},
            kd, vd)


def _dense_rows(q, kd, vd, table, start, slopes):
    """Per-row dense attention over ONLY the valid prefix: gather the
    row's pages by hand, truncate to start+c+1 tokens, plain softmax."""
    B, C = q.shape[:2]
    out = np.zeros((B, C, NH, HD), np.float32)
    qn, tn = np.asarray(q), np.asarray(table)
    for b in range(B):
        keys = np.concatenate([np.asarray(kd)[tn[b, w]] for w in range(W)])
        vals = np.concatenate([np.asarray(vd)[tn[b, w]] for w in range(W)])
        for c in range(C):
            n = int(start[b]) + c + 1
            for h in range(NH):
                s = keys[:n, h] @ qn[b, c, h] * HD ** -0.5
                s = s + float(slopes[h]) * np.arange(n)
                p = np.exp(s - s.max())
                p /= p.sum()
                out[b, c, h] = p @ vals[:n, h]
    return out


@pytest.fixture(scope="module")
def case():
    rng = np.random.RandomState(0)
    table = jnp.asarray(
        rng.permutation(np.arange(1, NPAGES))[: 3 * W].reshape(3, W),
        jnp.int32,
    )
    # row 0 full, row 1 ends MID-page, row 2 nearly empty: the ragged
    # starts exercise partial-last-page masking in one case
    start = jnp.asarray([PS * W - 4, 6, 1], jnp.int32)
    q = jnp.asarray(rng.randn(3, 4, NH, HD), jnp.float32)
    return rng, table, start, q


@pytest.mark.parametrize("quantized", [False, True], ids=["fp", "int8"])
def test_kernel_matches_gather_reference(case, quantized):
    rng, table, start, q = case
    kp, vp, _, _ = _make_pool(rng, quantized)
    slopes = _slopes(NH)
    out = paged_attention(q, kp, vp, table, start, slopes=slopes,
                          interpret=True)
    ref = paged_attention_reference(q, kp, vp, table, start, slopes=slopes)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("quantized", [False, True], ids=["fp", "int8"])
def test_mask_excludes_everything_past_the_row_cursor(case, quantized):
    """Against the independent dense-prefix reference: tokens past
    start+c (stale tails, unwritten page offsets, whole garbage pages)
    contribute NOTHING, for every ragged row."""
    rng, table, start, q = case
    kp, vp, kd, vd = _make_pool(rng, quantized)
    slopes = _slopes(NH)
    out = paged_attention(q, kp, vp, table, start, slopes=slopes,
                          interpret=True)
    ref = _dense_rows(q, kd, vd, table, start, slopes)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("quantized", [False, True], ids=["fp", "int8"])
def test_auto_lane_matches_interpret_kernel(case, quantized):
    """interpret=None off-TPU routes the compiled XLA one-pass lane
    (what the CPU serving engine and smoke bench actually run); it must
    agree with the Pallas interpreter AND the gather reference."""
    rng, table, start, q = case
    kp, vp, _, _ = _make_pool(rng, quantized)
    slopes = _slopes(NH)
    auto = jax.jit(
        lambda *a: paged_attention(*a, slopes=slopes)
    )(q, kp, vp, table, start)
    kern = paged_attention(q, kp, vp, table, start, slopes=slopes,
                           interpret=True)
    ref = paged_attention_reference(q, kp, vp, table, start, slopes=slopes)
    np.testing.assert_allclose(np.asarray(auto), np.asarray(kern),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(auto), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_single_query_decode_shape(case):
    rng, table, start, _ = case
    kp, vp, _, _ = _make_pool(rng, False)
    q1 = jnp.asarray(rng.randn(3, 1, NH, HD), jnp.float32)
    out = paged_attention(q1, kp, vp, table, start, slopes=_slopes(NH),
                          interpret=True)
    assert out.shape == (3, 1, NH, HD)
    ref = paged_attention_reference(q1, kp, vp, table, start,
                                    slopes=_slopes(NH))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_tp2_head_sharded_matches_single_device(case, devices):
    """The GSPMD contract: under a head-sharded shard_map the kernel
    computes each shard's heads independently and the stitched result
    equals the unsharded run (layout, not location)."""
    from jax.sharding import Mesh

    rng, table, start, q = case
    kp, vp, _, _ = _make_pool(rng, True)
    slopes = _slopes(NH)
    full = paged_attention(q, kp, vp, table, start, slopes=slopes,
                           interpret=True)
    mesh = Mesh(np.array(jax.devices()[:2]), ("tensor",))
    # heads are major inside a row: sharding the rows shards the heads
    pspec = {"q": P(None, None, "tensor"), "scale": P(None, None, "tensor")}

    def body(q, kp, vp, table, start, slopes):
        return paged_attention(q, kp, vp, table, start, slopes=slopes,
                               interpret=True)

    sharded = jax.jit(shard_map(
        body, mesh,
        (P(None, None, "tensor", None), pspec, pspec, P(), P(), P("tensor")),
        P(None, None, "tensor", None), check_vma=False,
    ))(q, kp, vp, table, start, slopes)
    np.testing.assert_allclose(np.asarray(sharded), np.asarray(full),
                               rtol=1e-5, atol=1e-5)


# --- VMEM feasibility guard (fused_ce idiom) --------------------------------


def test_tile_geometry_reports_footprint():
    g = paged_tile_geometry(PS, NH, HD, 1, quantized=False)
    assert g["fits"] is True and g["vmem_bytes"] <= g["vmem_budget_bytes"]
    gq = paged_tile_geometry(PS, NH, HD, 1, quantized=True)
    # the quantized tile streams an extra scale plane per operand
    assert gq["vmem_bytes"] > g["vmem_bytes"]
    assert paged_tile_geometry(4096, 1, 4096, 1, quantized=True)["fits"] is False


def test_guard_raises_compiled_exempt_interpret():
    """Never a silent fallback to gather: an infeasible page_size x
    head_dim tile refuses to compile, loudly, naming the footprint.
    The interpreter has no VMEM limit, so interpret runs are exempt."""
    with pytest.raises(ValueError, match="VMEM"):
        check_paged_tile(4096, 1, 4096, 1, quantized=True, interpret=False)
    g = check_paged_tile(4096, 1, 4096, 1, quantized=True, interpret=True)
    assert g["fits"] is False          # reported honestly even when exempt
    ok = check_paged_tile(PS, NH, HD, 1, quantized=True, interpret=False)
    assert ok["fits"] is True
