"""Flash-attention kernel vs XLA reference (interpret mode on CPU —
same kernel code path the TPU compiles)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pipegoose_tpu.models.bloom import alibi_slopes
from pipegoose_tpu.ops.flash_attention import _xla_reference, flash_attention
from pipegoose_tpu.testing import kernel_calls, saved_residuals

B, S, NH, HD = 2, 128, 4, 64


def _qkv(key=0):
    ks = jax.random.split(jax.random.PRNGKey(key), 3)
    return tuple(jax.random.normal(kk, (B, S, NH, HD)) for kk in ks)


def _ref(q, k, v, slopes, causal=True):
    b, s, nh, hd = q.shape

    def flat(x):
        return x.transpose(0, 2, 1, 3).reshape(b * nh, s, hd)

    sl = jnp.broadcast_to(slopes[None], (b, nh)).reshape(b * nh)
    out = _xla_reference(flat(q), flat(k), flat(v), sl, hd**-0.5, causal)
    return out.reshape(b, nh, s, hd).transpose(0, 2, 1, 3)


def test_causal_alibi_matches_reference():
    q, k, v = _qkv()
    slopes = jnp.asarray(alibi_slopes(NH))
    out = flash_attention(q, k, v, slopes, interpret=True)
    ref = _ref(q, k, v, slopes)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-6)


def test_noncausal_no_alibi():
    q, k, v = _qkv(1)
    out = flash_attention(q, k, v, None, causal=False, interpret=True)
    ref = _ref(q, k, v, jnp.zeros(NH), causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-6)


def test_odd_sequence_blocks():
    """S=96 -> block size 32 path."""
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q, k, v = (jax.random.normal(kk, (1, 96, 2, 64)) for kk in ks)
    slopes = jnp.asarray(alibi_slopes(2))
    out = flash_attention(q, k, v, slopes, interpret=True)
    ref = _ref(q, k, v, slopes)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-6)


def test_grads_flow():
    q, k, v = _qkv(3)
    slopes = jnp.asarray(alibi_slopes(NH))

    def loss(q, k, v):
        return (flash_attention(q, k, v, slopes, interpret=True) ** 2).sum()

    def ref_loss(q, k, v):
        return (_ref(q, k, v, slopes) ** 2).sum()

    g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g, gr, "qkv"):
        # atol: the fused backward's delta subtraction cancels exactly in
        # the XLA ref but leaves f32 roundoff here (different reductions)
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=5e-5, err_msg=name
        )


def test_bf16():
    q, k, v = (x.astype(jnp.bfloat16) for x in _qkv(4))
    slopes = jnp.asarray(alibi_slopes(NH))
    out = flash_attention(q, k, v, slopes, interpret=True)
    assert out.dtype == jnp.bfloat16
    ref = _ref(q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32), slopes)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref), rtol=3e-2, atol=3e-2
    )


def test_padded_batch_matches_reference():
    """Right-padded batch: flash with attention_mask == XLA reference with
    the same kv_pos/kv_neg biases (forward AND backward)."""
    q, k, v = _qkv(5)
    slopes = jnp.asarray(alibi_slopes(NH))
    mask = np.ones((B, S), np.int32)
    mask[0, S - 40:] = 0  # right padding
    mask[1, S - 7:] = 0
    mask = jnp.asarray(mask)
    m = mask.astype(jnp.float32)
    kpos = (jnp.cumsum(m, axis=-1) - 1.0) * m
    kneg = (1.0 - m) * (-1e9)

    def flat_bs(x):
        return jnp.broadcast_to(x[:, None, :], (B, NH, S)).reshape(B * NH, S)

    def ref_fn(q, k, v):
        def flat(x):
            return x.transpose(0, 2, 1, 3).reshape(B * NH, S, HD)

        sl = jnp.broadcast_to(slopes[None], (B, NH)).reshape(B * NH)
        out = _xla_reference(
            flat(q), flat(k), flat(v), sl, HD**-0.5, True,
            kpos=flat_bs(kpos), kneg=flat_bs(kneg),
        )
        return out.reshape(B, NH, S, HD).transpose(0, 2, 1, 3)

    out = flash_attention(q, k, v, slopes, attention_mask=mask, interpret=True)
    ref = ref_fn(q, k, v)
    # compare only valid query rows (padded-query rows are garbage in both)
    valid = np.asarray(mask, bool)
    np.testing.assert_allclose(
        np.asarray(out)[valid], np.asarray(ref)[valid], rtol=2e-5, atol=2e-6
    )

    # gradients, weighting the loss by the mask like the model's CE does
    w = m[:, :, None, None]

    def loss(q, k, v):
        o = flash_attention(q, k, v, slopes, attention_mask=mask, interpret=True)
        return ((o * w) ** 2).sum()

    def ref_loss(q, k, v):
        return ((ref_fn(q, k, v) * w) ** 2).sum()

    g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g, gr, "qkv"):
        assert np.isfinite(np.asarray(a)).all(), name
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5, err_msg=name
        )


def test_bloom_flash_padded_matches_plain():
    """use_flash=True BLOOM == standard path on a PADDED batch: loss and
    parameter gradients (the round-1 'unpadded batches only' restriction,
    models/bloom.py:69, is gone)."""
    import dataclasses

    from pipegoose_tpu.models import bloom

    cfg = bloom.BloomConfig(vocab_size=64, hidden_size=64, n_layer=2, n_head=4)
    cfg_f = dataclasses.replace(cfg, use_flash=True)
    params = bloom.init_params(cfg, jax.random.PRNGKey(0))
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 64, (2, 32)))
    mask = np.ones((2, 32), np.int32)
    mask[0, 20:] = 0
    mask[1, 27:] = 0
    mask = jnp.asarray(mask)

    from jax.flatten_util import ravel_pytree

    ref_loss, ref_g = jax.value_and_grad(bloom.loss_fn)(params, ids, mask, ids, cfg)
    out_loss, out_g = jax.value_and_grad(bloom.loss_fn)(params, ids, mask, ids, cfg_f)
    np.testing.assert_allclose(float(out_loss), float(ref_loss), rtol=2e-4)
    flat_r, _ = ravel_pytree(ref_g)
    flat_o, _ = ravel_pytree(out_g)
    assert np.isfinite(np.asarray(flat_o)).all()
    np.testing.assert_allclose(
        np.asarray(flat_o), np.asarray(flat_r), rtol=5e-3, atol=1e-4
    )


def test_bloom_with_flash_matches_plain():
    """use_flash=True BLOOM == standard path on unpadded input."""
    import dataclasses

    from pipegoose_tpu.models import bloom

    cfg = bloom.BloomConfig(vocab_size=64, hidden_size=64, n_layer=2, n_head=4)
    params = bloom.init_params(cfg, jax.random.PRNGKey(0))
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 64, (2, 16)))
    ref = bloom.forward(params, ids, None, cfg)
    cfg_f = dataclasses.replace(cfg, use_flash=True)
    out = bloom.forward(params, ids, None, cfg_f)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("tp", [1, 2])
def test_bloom_flash_at_head_width_64_matches_plain(tp):
    """Heads of 64 (bloom-560m's width): the kernels take q, k and v as
    ``(B, S, heads * 64)``, two heads a tile, through the model's
    unchanged ``flash_attention(q, k, v, ...)`` call; loss and every
    parameter's gradient equal the plain path's, on one device and with
    the heads sharded over two."""
    import dataclasses

    from jax.flatten_util import ravel_pytree
    from jax.sharding import PartitionSpec as P

    from pipegoose_tpu.distributed import ParallelContext
    from pipegoose_tpu.distributed.compat import shard_map
    from pipegoose_tpu.models import bloom

    cfg = bloom.BloomConfig(vocab_size=64, hidden_size=256, n_layer=2, n_head=4)
    cfg_f = dataclasses.replace(cfg, use_flash=True)
    params = bloom.init_params(cfg, jax.random.PRNGKey(0))
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 64, (2, 32)))
    mask = np.ones((2, 32), np.int32)
    mask[0, 20:] = 0
    mask = jnp.asarray(mask)
    ref_loss, ref_g = jax.value_and_grad(bloom.loss_fn)(params, ids, mask, ids, cfg)

    def step(axis):
        return jax.value_and_grad(lambda p, i, m: bloom.loss_fn(
            p, i, m, i, cfg_f, tp_axis=axis))

    jaxpr = jax.make_jaxpr(step(None))(params, ids, mask)
    for name, calls in _kernel_operands(jaxpr).items():
        assert all(c[1:4] == [(2, 32, 256)] * 3 for c in calls), (name, calls)
    if tp == 1:
        out_loss, out_g = step(None)(params, ids, mask)
    else:
        ctx = ParallelContext(tensor_parallel_size=2, data_parallel_size=4)
        try:
            specs = bloom.tp_specs(params)
            out_loss, out_g = shard_map(
                step("tensor"), mesh=ctx.mesh, in_specs=(specs, P(), P()),
                out_specs=(P(), specs), check_vma=False)(params, ids, mask)
        finally:
            ctx.destroy()
    np.testing.assert_allclose(float(out_loss), float(ref_loss), rtol=2e-4)
    flat_r, _ = ravel_pytree(ref_g)
    flat_o, _ = ravel_pytree(out_g)
    assert np.isfinite(np.asarray(flat_o)).all()
    np.testing.assert_allclose(np.asarray(flat_o), np.asarray(flat_r),
                               rtol=5e-3, atol=1e-4)


@pytest.mark.parametrize("family", ["llama", "mixtral"])
def test_rope_family_flash_matches_plain(family):
    """use_flash=True for the RoPE families (zero ALiBi slopes, padding
    via kv_neg) == the standard dense-mask path: loss and parameter
    gradients on a PADDED batch."""
    import dataclasses

    from jax.flatten_util import ravel_pytree

    if family == "llama":
        from pipegoose_tpu.models import llama as mod

        cfg = mod.LlamaConfig(
            vocab_size=64, hidden_size=64, intermediate_size=112,
            n_layer=2, n_head=4, n_kv_head=2,
        )

        def loss(p, ids, mask, c):
            return mod.loss_fn(p, ids, mask, ids, c)
    else:
        from pipegoose_tpu.models import mixtral as mod

        cfg = mod.MixtralConfig(
            vocab_size=64, hidden_size=64, intermediate_size=112,
            n_layer=2, n_head=4, n_kv_head=2, num_experts=4, top_k=2,
        )

        def loss(p, ids, mask, c):
            return mod.loss_fn(p, ids, mask, ids, c, train=False)

    params = mod.init_params(cfg, jax.random.PRNGKey(0))
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 64, (2, 32)))
    mask = np.ones((2, 32), np.int32)
    mask[0, 20:] = 0
    mask[1, 27:] = 0
    mask = jnp.asarray(mask)
    cfg_f = dataclasses.replace(cfg, use_flash=True)

    ref_loss, ref_g = jax.value_and_grad(loss)(params, ids, mask, cfg)
    out_loss, out_g = jax.value_and_grad(loss)(params, ids, mask, cfg_f)
    np.testing.assert_allclose(float(out_loss), float(ref_loss), rtol=2e-4)
    fr, _ = ravel_pytree(ref_g)
    fo, _ = ravel_pytree(out_g)
    assert np.isfinite(np.asarray(fo)).all()
    np.testing.assert_allclose(
        np.asarray(fo), np.asarray(fr), rtol=5e-3, atol=1e-4
    )


def test_gqa_grouped_kv_matches_repeated():
    """Native GQA (un-repeated K/V via grouped index maps) == the same
    attention with K/V explicitly repeated: forward and gradients."""
    B, S, NKV, G, HD2 = 2, 64, 2, 3, 64
    nh = NKV * G
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (B, S, nh, HD2))
    k = jax.random.normal(ks[1], (B, S, NKV, HD2))
    v = jax.random.normal(ks[2], (B, S, NKV, HD2))
    mask = np.ones((B, S), np.int32)
    mask[0, 50:] = 0
    mask = jnp.asarray(mask)

    def grouped(q, k, v):
        return flash_attention(q, k, v, None, attention_mask=mask, interpret=True)

    def repeated(q, k, v):
        kr = jnp.repeat(k, G, axis=2)
        vr = jnp.repeat(v, G, axis=2)
        return flash_attention(q, kr, vr, None, attention_mask=mask, interpret=True)

    out_g = grouped(q, k, v)
    out_r = repeated(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out_g), np.asarray(out_r), rtol=2e-5, atol=2e-6
    )

    w = mask.astype(jnp.float32)[:, :, None, None]
    gg = jax.grad(lambda q, k, v: ((grouped(q, k, v) * w) ** 2).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda q, k, v: ((repeated(q, k, v) * w) ** 2).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gg, gr, "qkv"):
        assert np.isfinite(np.asarray(a)).all(), name
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=5e-5, err_msg=name
        )


# -- the kernels at explicit blocks, every kind of block ---------------------
#
# One program of each case meets a skipped block (whose index map is
# clamped to a kept one), a block wholly inside the rule and a block the
# diagonal or the window's edge crosses, with block_q != block_k both
# ways; the last cases take the blocks ``_pick_blocks`` gives a v5e for
# their shape. The backward runs both ways: ``flash_bwd`` (one pass over
# the score tiles) and the ``flash_dq`` + ``flash_dkv`` pair it falls
# back to, which sum in the same order: the same bits at the same blocks.

from pipegoose_tpu.ops import flash_attention as fa  # noqa: E402

KINDS = ("fwd", "dq", "dkv", "bwd")

# what ``_vmem_limit_bytes`` gives on a v5e (half of 128 MiB), and here,
# where no TPU is attached (the compiler's default)
V5E_LIMIT, DEFAULT_LIMIT = 64 * 2**20, 16 * 2**20


def _dense(q, k, v, slopes, scale, causal, window, kpos, kneg, g):
    """``_xla_reference`` where it has the semantics (no window, g = 1,
    float32); else the same dense math with the window rule, the shared
    K/V rows repeated, and ``p`` rounded to the operands' dtype before
    ``p v`` as the models' plain paths do."""
    if window is None and g == 1 and q.dtype == jnp.float32:
        return _xla_reference(q, k, v, slopes, scale, causal, kpos, kneg)
    s = q.shape[1]
    k, v, kpos, kneg = (jnp.repeat(x, g, axis=0) for x in (k, v, kpos, kneg))
    scores = jnp.einsum("bqd,bkd->bqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    scores = scores + slopes[:, None, None] * kpos[:, None, :] + kneg[:, None, :]
    qi, ki = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    keep = jnp.ones((s, s), bool)
    if causal:
        keep = keep & (ki <= qi)
    if window is not None:
        keep = keep & (qi - ki < window)
    scores = jnp.where(keep[None], scores, fa.NEG_INF)
    p = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bqk,bkd->bqd", p, v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


# name: (seq, head width, g, causal, window, padded, dtype, blocks or None)
KERNEL_CASES = {
    "causal_64x128_hd64": (512, 64, 1, True, None, False, jnp.float32, (64, 128)),
    "causal_128x64_hd128": (512, 128, 1, True, None, False, jnp.float32, (128, 64)),
    "causal_64x256_hd256": (512, 256, 1, True, None, False, jnp.float32, (64, 256)),
    "window_64x128": (512, 64, 1, True, 160, False, jnp.float32, (64, 128)),
    "window_128x64": (512, 64, 1, True, 160, False, jnp.float32, (128, 64)),
    "noncausal_64x128": (256, 64, 1, False, None, False, jnp.float32, (64, 128)),
    "noncausal_window_128x64": (512, 64, 1, False, 96, False, jnp.float32, (128, 64)),
    "padded_64x128": (512, 64, 1, True, None, True, jnp.float32, (64, 128)),
    "padded_128x64": (512, 64, 1, True, None, True, jnp.float32, (128, 64)),
    "gqa2_64x128": (512, 64, 2, True, None, True, jnp.float32, (64, 128)),
    "gqa2_window_128x64": (512, 64, 2, True, 160, False, jnp.float32, (128, 64)),
    "bf16_64x128_hd64": (512, 64, 1, True, None, False, jnp.bfloat16, (64, 128)),
    "bf16_128x64_hd256": (512, 256, 1, True, None, True, jnp.bfloat16, (128, 64)),
    "picked_blocks_hd64": (2048, 64, 1, True, None, False, jnp.float32, None),
    "picked_blocks_hd128_window": (2048, 128, 1, True, 700, True, jnp.float32, None),
    "bf16_256x128_hd128_padded": (512, 128, 1, True, None, True, jnp.bfloat16, (256, 128)),
    "gqa2_noncausal_64x128_hd128": (256, 128, 2, False, None, True, jnp.float32, (64, 128)),
    "one_block_hd256": (128, 256, 1, True, None, False, jnp.float32, (128, 128)),
}


@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_kernels_at_explicit_blocks_match_the_dense_reference(case):
    s, hd, g, causal, window, padded, dtype, blocks = KERNEL_CASES[case]
    nkv = 1
    bh = nkv * g
    ks = jax.random.split(jax.random.PRNGKey(len(case)), 4)
    q, do = (jax.random.normal(kk, (bh, s, hd)).astype(dtype) for kk in ks[:2])
    k, v = (jax.random.normal(kk, (nkv, s, hd)).astype(dtype) for kk in ks[2:])
    slopes = jnp.asarray(alibi_slopes(4))[:bh] if causal else jnp.zeros(bh)
    valid = np.ones((nkv, s), np.float32)
    if padded:
        valid[:, s - 75:] = 0.0  # right padding, not on a block's edge
    kpos, kneg = fa.mask_to_kv_bias(jnp.asarray(valid))
    rows = np.asarray(jnp.repeat(jnp.asarray(valid), g, axis=0), bool)
    # a model's loss masks the padded queries: their cotangent is zero
    do = do * jnp.asarray(rows)[:, :, None].astype(dtype)
    scale = hd ** -0.5
    rule = (scale, causal)

    bq_bk = {kind: blocks or fa._pick_blocks(s, hd, q.dtype.itemsize, kind,
                                             V5E_LIMIT)
             for kind in KINDS}
    out, lse = fa._flash_fwd_pallas(q, k, v, slopes, kpos, kneg, *rule,
                                    *bq_bk["fwd"], True, g, window)
    delta = (do.astype(jnp.float32) * out.astype(jnp.float32)).sum(-1)
    dq = fa._flash_dq_pallas(q, k, v, do, lse, delta, slopes, kpos, kneg,
                             *rule, *bq_bk["dq"], True, g, window)
    dk, dv = fa._flash_dkv_pallas(q, k, v, do, lse, delta, slopes, kpos, kneg,
                                  *rule, *bq_bk["dkv"], True, g, window)
    one = fa._flash_bwd_pallas(q, k, v, do, lse, delta, slopes, kpos, kneg,
                               *rule, *bq_bk["bwd"], True, g, window)
    if bq_bk["bwd"] == bq_bk["dq"] == bq_bk["dkv"]:
        # one order of every sum: not close, the same
        for a, b, name in zip(one, (dq, dk, dv), ("dq", "dk", "dv")):
            np.testing.assert_array_equal(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                err_msg=f"{case}: flash_bwd's {name} against the pair's")
    dk, dv, dk1, dv1 = (x.astype(jnp.float32).reshape(nkv, g, s, hd).sum(1)
                        for x in (dk, dv, one[1], one[2]))

    ref, vjp = jax.vjp(
        lambda q, k, v: _dense(q, k, v, slopes, scale, causal, window,
                               kpos, kneg, g), q, k, v)
    rq, rk, rv = vjp(do)
    if dtype == jnp.float32:
        # today's relative tolerances; the absolute ones follow the
        # length of the float32 sums (2e-6 and 5e-5 at today's 128)
        fwd_tol = dict(rtol=2e-5, atol=2e-8 * s)
        grad_tol = dict(rtol=1e-4, atol=4e-7 * s)
    else:
        fwd_tol = grad_tol = dict(rtol=3e-2, atol=3e-2)

    def close(a, b, tol, name):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.isfinite(a).all(), name
        np.testing.assert_allclose(a, b, err_msg=f"{case}: {name}", **tol)

    close(np.asarray(out, np.float32)[rows], np.asarray(ref, np.float32)[rows],
          fwd_tol, "out")
    close(dq, rq, grad_tol, "dq")
    close(dk, rk, grad_tol, "dk")
    close(dv, rv, grad_tol, "dv")
    close(one[0], rq, grad_tol, "flash_bwd dq")
    close(dk1, rk, grad_tol, "flash_bwd dk")
    close(dv1, rv, grad_tol, "flash_bwd dv")


# -- the block function alone ------------------------------------------------

def test_vmem_limit_is_the_compilers_default_where_no_tpu_is_attached():
    assert fa._vmem_limit_bytes() == DEFAULT_LIMIT


# the kernels that take two heads of 64 as one 128-lane tile
PAIRED_KINDS = ("fwd_paired", "bwd_paired")


@pytest.mark.parametrize("limit", [DEFAULT_LIMIT, 32 * 2**20, V5E_LIMIT])
@pytest.mark.parametrize("kind", KINDS + PAIRED_KINDS)
@pytest.mark.parametrize("width", [64, 96, 128, 256, 512, 1024, 2048])
def test_pick_blocks_divide_the_sequence_inside_the_vmem_budget(
        kind, width, limit):
    for seq in (128, 384, 512, 1024, 1536, 2048, 4096, 8192, 32768):
        for itemsize in (2, 4):
            bq, bk = fa._pick_blocks(seq, width, itemsize, kind, limit)
            assert seq % bq == 0 and seq % bk == 0, (seq, bq, bk)
            assert bq <= 1024 and bk <= 1024
            used = fa._working_set_bytes(kind, bq, bk, width, itemsize, seq)
            if min(bq, bk) > 8:     # (8, 8) is the floor, fit or not
                assert used <= limit * 3 // 4, (seq, itemsize, bq, bk, used)
            # the largest that fits: the side halved last, twice as
            # large again, would pass the budget
            wider = (2 * bq, bk) if bq < bk else (bq, 2 * bk)
            if max(wider) <= 1024 and seq % max(wider) == 0:
                assert fa._working_set_bytes(kind, *wider, width, itemsize,
                                             seq) > limit * 3 // 4, \
                    (seq, itemsize, bq, bk)


@pytest.mark.parametrize("seq,blocks", [(64, (64, 64)), (96, (32, 32)),
                                        (200, (8, 8)), (100, (100, 100)),
                                        (128, (128, 128))])
def test_pick_blocks_keep_todays_answer_for_tiny_and_odd_sequences(seq, blocks):
    for kind in KINDS:
        for width in (64, 128, 256):
            for limit in (DEFAULT_LIMIT, V5E_LIMIT):
                assert fa._pick_blocks(seq, width, 4, kind, limit) == blocks
            assert blocks == (fa._pick_block(seq, 128), fa._pick_block(seq, 512))


@pytest.mark.parametrize("seq,width", [(2048, 64), (2048, 128), (4096, 256)])
def test_pick_blocks_at_the_cells_shapes(seq, width):
    """bf16 at the three train cells' shapes: 1,024 x 1,024 on a v5e; no
    query block under 256 and more scores a grid step than the 128 x 512
    the kernels had even under the compiler's default limit."""
    for kind in KINDS:
        assert fa._pick_blocks(seq, width, 2, kind, V5E_LIMIT) == (1024, 1024)
        bq, bk = fa._pick_blocks(seq, width, 2, kind, DEFAULT_LIMIT)
        # more scores a step than the 128 x 512 the kernels had; as many
        # for ``flash_bwd`` at width 256 x 4,096 positions, where dQ's
        # accumulator and result take 8 of the default limit's 12 MiB,
        # whatever the blocks, and leave 256 x 256
        least = 128 * 512 + (kind != "bwd")
        assert bq >= 256 and bq * bk >= least, (kind, bq, bk)
    # on a v5e the one-kernel backward runs at all three (``_flash_bwd``)
    assert fa._working_set_bytes("bwd", 1024, 1024, width, 2, seq) \
        <= V5E_LIMIT * 3 // 4
    if width == 64:
        # two heads a tile: the paired kernels, at the same blocks
        for kind in PAIRED_KINDS:
            assert fa._pick_blocks(seq, 128, 2, kind, V5E_LIMIT) == (1024, 1024)
        assert fa._pairs_heads(seq, 16, width, 1, 2) is True


# -- which backward runs: a fact of the shape and the device's VMEM ---------

# name: (seq, width, dtype, limit, the kernels of the backward)
BACKWARD_PATHS = {
    # the three train cells on a v5e
    "cell_560m": (2048, 64, jnp.bfloat16, V5E_LIMIT, ["flash_bwd"]),
    "cell_1b7": (2048, 128, jnp.bfloat16, V5E_LIMIT, ["flash_bwd"]),
    "cell_glm": (4096, 256, jnp.bfloat16, V5E_LIMIT, ["flash_bwd"]),
    "f32_w512": (2048, 512, jnp.float32, V5E_LIMIT, ["flash_bwd"]),
    "32k_w128": (32768, 128, jnp.bfloat16, V5E_LIMIT, ["flash_bwd"]),
    # dQ's float32 accumulator and its result: 8 bytes a lane a position
    # at bf16, 64 MiB at 65,536 x 128 against a budget of 48
    "64k_w128": (65536, 128, jnp.bfloat16, V5E_LIMIT,
                 ["flash_dkv", "flash_dq"]),
    "32k_w256": (32768, 256, jnp.bfloat16, V5E_LIMIT,
                 ["flash_dkv", "flash_dq"]),
    # where no TPU is attached (interpret mode) the budget is 12 MiB
    "cell_glm_default": (4096, 256, jnp.bfloat16, DEFAULT_LIMIT,
                         ["flash_bwd"]),
    "16k_w128_default": (16384, 128, jnp.bfloat16, DEFAULT_LIMIT,
                         ["flash_dkv", "flash_dq"]),
}


@pytest.mark.parametrize("case", sorted(BACKWARD_PATHS))
def test_the_backward_is_one_kernel_where_its_accumulator_fits(
        monkeypatch, case):
    """``_flash_bwd`` takes ``flash_bwd`` where ``_working_set_bytes``
    says dQ's whole-sequence accumulator fits beside the blocks, and the
    pair where it does not: read off the gradient's jaxpr by kernel
    name, nothing runs."""
    seq, width, dtype, limit, want = BACKWARD_PATHS[case]
    monkeypatch.setattr(fa, "_vmem_limit_bytes", lambda: limit)
    x = jax.ShapeDtypeStruct((1, seq, 2, width), dtype)
    grad = jax.make_jaxpr(jax.grad(
        lambda q, k, v: flash_attention(q, k, v, None, interpret=True)
        .astype(jnp.float32).sum(), argnums=(0, 1, 2)))(x, x, x)
    ran = sorted(name for name in ("flash_bwd", "flash_dq", "flash_dkv")
                 if kernel_calls(grad, name))
    assert ran == want and kernel_calls(grad, "flash_fwd") == 1
    assert all(kernel_calls(grad, name) == 1 for name in ran)


@pytest.mark.parametrize("g,window", [(1, None), (2, 48)])
def test_a_shape_that_falls_back_to_the_pair_has_the_same_gradients(
        monkeypatch, g, window):
    """A VMEM limit too small for dQ's accumulator (192 positions x 128
    lanes x 12 bytes = 288 KiB against three quarters of 256): the pair
    runs, at smaller blocks, and its gradients are the one kernel's to
    float32 rounding and the dense reference's."""
    b, s, nkv, hd = 1, 192, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(46), 4)
    q, ct = (jax.random.normal(kk, (b, s, nkv * g, hd)) for kk in ks[:2])
    k, v = (jax.random.normal(kk, (b, s, nkv, hd)) for kk in ks[2:])
    slopes = jnp.asarray(alibi_slopes(nkv * g))
    mask = jnp.asarray(np.arange(s) < s - 21, jnp.int32)[None]
    ct = ct * mask[:, :, None, None]

    def grads():
        fn = lambda q, k, v: flash_attention(  # noqa: E731
            q, k, v, slopes, attention_mask=mask, window=window,
            interpret=True)
        jaxpr = jax.make_jaxpr(lambda q, k, v: jax.vjp(fn, q, k, v)[1](ct))(
            q, k, v)
        ran = {name: kernel_calls(jaxpr, name)
               for name in ("flash_bwd", "flash_dq", "flash_dkv")}
        return jax.vjp(fn, q, k, v)[1](ct), ran

    one, ran = grads()
    assert ran == {"flash_bwd": 1, "flash_dq": 0, "flash_dkv": 0}
    monkeypatch.setattr(fa, "_vmem_limit_bytes", lambda: 256 * 2**10)
    pair, ran = grads()
    assert ran == {"flash_bwd": 0, "flash_dq": 1, "flash_dkv": 1}
    for a, b_, name in zip(one, pair, ("dq", "dk", "dv")):
        assert np.isfinite(np.asarray(b_)).all(), name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), rtol=1e-4,
                                   atol=4e-7 * s, err_msg=name)
    if window is None:
        kpos, kneg = fa.mask_to_kv_bias(mask)
        heads = nkv * g

        def flat(x):
            return x.transpose(0, 2, 1, 3).reshape(-1, s, hd)

        def dense(q, k, v):
            out = _xla_reference(
                flat(q), flat(k), flat(v), slopes, hd ** -0.5, True,
                jnp.repeat(kpos, heads, axis=0), jnp.repeat(kneg, heads, axis=0))
            return out.reshape(b, heads, s, hd).transpose(0, 2, 1, 3)

        want = jax.vjp(dense, q, k, v)[1](ct)
        for a, b_, name in zip(pair, want, ("dq", "dk", "dv")):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=1e-4, atol=4e-7 * s, err_msg=name)


# -- what a block's checkpoint keeps of the kernel -------------------------

# name: (heads, kv heads, head width, window)
REMAT_CASES = {
    # the first and the third take the paired layout (PR 49)
    "mha_hd64": (4, 4, 64, None),
    "gqa2": (4, 2, 64, None),
    "window": (4, 4, 64, 48),
    "hd256": (2, 2, 256, None),
}


@pytest.mark.parametrize("case", sorted(REMAT_CASES))
def test_a_checkpointed_block_keeps_the_kernels_residuals(case):
    """Under ``remat_policy()`` backward takes ``out`` and ``lse`` from
    the forward pass: the gradient of two scanned blocks holds ONE
    ``flash_fwd`` a block (two under a bare ``jax.checkpoint``, which
    ignores the names) and equals the gradient with no checkpoint."""
    nh, nkv, hd, window = REMAT_CASES[case]
    b, s, h = 2, 128, 32
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    ws = {
        "qkv": jax.random.normal(ks[0], (2, h, (nh + 2 * nkv) * hd)) * h**-0.5,
        "out": jax.random.normal(ks[1], (2, nh * hd, h)) * (nh * hd) ** -0.5,
    }
    x = jax.random.normal(ks[2], (b, s, h))
    slopes = jnp.asarray(alibi_slopes(nh))

    def block(w, x):
        q, k, v = jnp.split(x @ w["qkv"], [nh * hd, (nh + nkv) * hd], axis=-1)
        ctx = flash_attention(
            q.reshape(b, s, nh, hd), k.reshape(b, s, nkv, hd),
            v.reshape(b, s, nkv, hd), slopes, window=window, interpret=True)
        return x + jnp.tanh(ctx.reshape(b, s, nh * hd) @ w["out"])

    def loss(wrap):
        step = wrap(block)
        return lambda ws, x: (jax.lax.scan(
            lambda c, w: (step(w, c), None), x, ws)[0] ** 2).sum()

    grads = {
        "plain": jax.grad(loss(lambda f: f), argnums=(0, 1)),
        "bare": jax.grad(loss(jax.checkpoint), argnums=(0, 1)),
        "kept": jax.grad(loss(lambda f: jax.checkpoint(
            f, policy=fa.remat_policy())), argnums=(0, 1)),
    }
    jaxprs = {name: jax.make_jaxpr(g)(ws, x) for name, g in grads.items()}
    # two blocks
    assert {n: kernel_calls(j, "flash_fwd") for n, j in jaxprs.items()} == {
        "plain": 2, "bare": 4, "kept": 2}
    for name, j in jaxprs.items():
        assert (kernel_calls(j, "flash_bwd"), kernel_calls(j, "flash_dq"),
                kernel_calls(j, "flash_dkv")) == (2, 0, 0), name
    # what the policy keeps of one block: the kernel's two residuals,
    # the result in the layout the call took (two heads of 64 a
    # 128-lane tile where they pair, a head a row otherwise)
    paired = hd == 64 and nh == nkv and nh % 2 == 0
    kept = saved_residuals(
        lambda w, x: jax.checkpoint(block, policy=fa.remat_policy())(w, x).sum(),
        jax.tree_util.tree_map(lambda a: a[0], ws), x)
    assert (f"f32[{b * nh},{s}]", "named 'flash_lse'") in kept
    out = f"f32[{b},{s},{nh * hd}]" if paired else f"f32[{b * nh},{s},{hd}]"
    assert out in {shape for shape, _ in kept}, kept
    want = grads["plain"](ws, x)
    for name in ("kept", "bare"):
        got = grads[name](ws, x)
        for a, b_ in zip(jax.tree_util.tree_leaves(got),
                         jax.tree_util.tree_leaves(want)):
            # the saved values are the ones the kernel would compute
            # again: the same bits, not merely close ones
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b_),
                                          err_msg=f"{case}: {name}")


def test_remat_policy_keeps_what_the_callers_policy_keeps():
    """``remat_policy(extra)`` saves the flash names AND what ``extra``
    saves; alone, nothing a block computes but the two residuals."""
    from jax.ad_checkpoint import checkpoint_name

    q = k = v = jnp.ones((1, 64, 2, 64))

    def f(q, k, v):
        ctx = flash_attention(q, k, v, None, interpret=True)
        # squared, so that backward needs the named value itself
        return (checkpoint_name(jnp.sin(ctx), "mine") ** 2).sum()

    def kept(policy):
        return set(saved_residuals(jax.checkpoint(f, policy=policy), q, k, v))

    alone = kept(fa.remat_policy())
    assert ("f32[2,64]", "named 'flash_lse'") in alone
    # ``out`` goes on into the block, so jax reports the rounding it
    # puts on a saved value that is also used, not the name; two heads
    # of 64 are one 128-lane tile, kept as the model holds it
    assert {shape for shape, _ in alone} == {"f32[2,64]", "f32[1,64,128]"}
    both = kept(fa.remat_policy(
        jax.checkpoint_policies.save_only_these_names("mine")))
    assert alone < both
    assert {shape for shape, _ in both - alone} == {"f32[1,64,2,64]"}


# -- two heads a 128-lane tile (head width 64) -------------------------------
#
# Where two heads fill one tile ``flash_attention`` hands the kernels
# ``(B, S, nh * 64)`` arrays, a pair of heads a grid row (PERF.md, PR
# 49). The cases go through the public call at explicit small blocks, so
# one program meets skipped, whole and crossed blocks; the dispatch is a
# fact of the call's shapes, read off the jaxpr.

def _flat(x):
    b, s, h, hd = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, hd)


def _todays_path(q, k, v, slopes, kpos, kneg, causal=True, window=None):
    """``flash_attention`` as it ran before the paired layout, for every
    shape: heads over positions, ``_flash``, and back."""
    b, s, nh, hd = q.shape
    nkv = k.shape[2]
    sl = jnp.broadcast_to(slopes[None], (b, nh)).reshape(b * nh)

    def rows(x):
        return jnp.broadcast_to(x.astype(jnp.float32)[:, None, :],
                                (b, nkv, s)).reshape(b * nkv, s)

    out = fa._flash(_flat(q), _flat(k), _flat(v), sl.astype(jnp.float32),
                    rows(kpos), rows(kneg), float(hd ** -0.5), causal, True,
                    nh // nkv, window)
    return out.reshape(b, nh, s, hd).transpose(0, 2, 1, 3)


def _kernel_operands(jaxpr):
    """Operand shapes of the jaxpr's flash kernels, by kernel name."""
    found = {}

    def walk(j):
        for eqn in getattr(j, "jaxpr", j).eqns:
            if eqn.primitive.name == "pallas_call":
                found.setdefault(eqn.params["name"], []).append(
                    [v.aval.shape for v in eqn.invars])
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr)
    return found


# name: (rows, seq, heads, causal, alibi, left padding, window, dtype, blocks)
PAIRED_CASES = {
    "causal_alibi_2h_64x128": (2, 512, 2, True, True, False, None, jnp.float32, (64, 128)),
    "causal_alibi_16h_128x64": (1, 256, 16, True, True, False, None, jnp.float32, (128, 64)),
    "left_padded_2h_64x128": (2, 512, 2, True, True, True, None, jnp.float32, (64, 128)),
    "left_padded_16h_128x64": (2, 256, 16, True, True, True, None, jnp.float32, (128, 64)),
    "window_2h_64x128": (2, 512, 2, True, True, False, 160, jnp.float32, (64, 128)),
    "window_4h_128x64_padded": (1, 512, 4, True, False, True, 160, jnp.float32, (128, 64)),
    "noncausal_2h_64x128": (2, 256, 2, False, False, False, None, jnp.float32, (64, 128)),
    "noncausal_window_4h_128x64": (1, 512, 4, False, False, True, 96, jnp.float32, (128, 64)),
    "bf16_causal_alibi_2h_64x128": (2, 512, 2, True, True, False, None, jnp.bfloat16, (64, 128)),
    "bf16_left_padded_16h_128x64": (1, 256, 16, True, True, True, None, jnp.bfloat16, (128, 64)),
    "picked_blocks_4h": (1, 2048, 4, True, True, False, None, jnp.float32, None),
}


@pytest.mark.parametrize("case", sorted(PAIRED_CASES))
def test_paired_heads_match_the_dense_reference(monkeypatch, case):
    b, s, nh, causal, alibi, padded, window, dtype, blocks = PAIRED_CASES[case]
    hd = 64
    if blocks:
        monkeypatch.setattr(fa, "_pick_blocks", lambda *a: blocks)
    ks = jax.random.split(jax.random.PRNGKey(len(case)), 4)
    q, k, v, ct = (jax.random.normal(kk, (b, s, nh, hd)).astype(dtype)
                   for kk in ks)
    slopes = jnp.asarray(alibi_slopes(nh)) if alibi else jnp.zeros(nh)
    valid = np.ones((b, s), np.float32)
    if padded:
        valid[0, :75] = 0.0  # left padding, not on a block's edge
    kpos, kneg = fa.mask_to_kv_bias(jnp.asarray(valid))
    # a model's loss masks the padded queries: their cotangent is zero
    ct = ct * jnp.asarray(valid)[:, :, None, None].astype(dtype)

    def fn(q, k, v):
        return flash_attention(q, k, v, slopes, kv_pos=kpos, kv_neg=kneg,
                               causal=causal, window=window, interpret=True)

    ran = _kernel_operands(jax.make_jaxpr(
        lambda q, k, v: jax.vjp(fn, q, k, v)[1](ct))(q, k, v))
    assert sorted(ran) == ["flash_bwd", "flash_fwd"], ran
    for name, calls in ran.items():
        assert len(calls) == 1
        # slopes, then the tensors as the model holds them; no
        # (rows * heads, seq, 64) operand anywhere
        assert calls[0][1:4] == [(b, s, nh * hd)] * 3, (name, calls[0])
        assert (b * nh, s, hd) not in calls[0]

    def dense(q, k, v):
        out = _dense(_flat(q), _flat(k), _flat(v), jnp.tile(slopes, b),
                     hd ** -0.5, causal, window, jnp.repeat(kpos, nh, axis=0),
                     jnp.repeat(kneg, nh, axis=0), 1)
        return out.reshape(b, nh, s, hd).transpose(0, 2, 1, 3)

    out, vjp = jax.vjp(fn, q, k, v)
    ref, ref_vjp = jax.vjp(dense, q, k, v)
    if dtype == jnp.float32:
        fwd_tol = dict(rtol=2e-5, atol=2e-8 * s)
        grad_tol = dict(rtol=1e-4, atol=4e-7 * s)
    else:
        fwd_tol = grad_tol = dict(rtol=3e-2, atol=3e-2)
    rows = np.asarray(valid, bool)
    np.testing.assert_allclose(np.asarray(out, np.float32)[rows],
                               np.asarray(ref, np.float32)[rows], **fwd_tol)
    grads = vjp(ct)
    for got, want, name in zip(grads, ref_vjp(ct), ("dq", "dk", "dv")):
        got = np.asarray(got, np.float32)
        assert np.isfinite(got).all(), name
        np.testing.assert_allclose(got, np.asarray(want, np.float32),
                                   err_msg=f"{case}: {name}", **grad_tol)
    if dtype == jnp.float32 and blocks:
        # per head the one-head kernels' arithmetic at the same blocks:
        # the same sums in the same order
        old, old_vjp = jax.vjp(lambda q, k, v: _todays_path(
            q, k, v, slopes, kpos, kneg, causal, window), q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(old),
                                   rtol=1e-6, atol=1e-6)
        for got, want in zip(grads, old_vjp(ct)):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=1e-5, atol=1e-5)


# name: (heads, kv heads, head width): an odd head count, GQA at width
# 64, width 128, and width 32 (four heads would fill a tile: not paired)
UNPAIRED_CASES = {
    "odd_heads_hd64": (3, 3, 64),
    "gqa2_hd64": (4, 2, 64),
    "hd128": (2, 2, 128),
    "hd32": (4, 4, 32),
}


@pytest.mark.parametrize("case", sorted(UNPAIRED_CASES))
def test_a_shape_that_does_not_pair_takes_todays_path(case):
    """Such a call lowers to the text of the path as it was (heads over
    positions, ``_flash``, and back) and has its gradients."""
    nh, nkv, hd = UNPAIRED_CASES[case]
    b, s = 2, 128
    ks = jax.random.split(jax.random.PRNGKey(49), 4)
    q, ct = (jax.random.normal(kk, (b, s, nh, hd)) for kk in ks[:2])
    k, v = (jax.random.normal(kk, (b, s, nkv, hd)) for kk in ks[2:])
    slopes = jnp.asarray(alibi_slopes(nh))
    mask = jnp.asarray(np.arange(s) >= 21, jnp.int32)[None].repeat(b, 0)
    kpos, kneg = fa.mask_to_kv_bias(mask)

    def new(q, k, v, slopes, kpos, kneg):
        return flash_attention(q, k, v, slopes, kv_pos=kpos, kv_neg=kneg,
                               interpret=True)

    args = (q, k, v, slopes, kpos, kneg)

    def grads(fn):
        return lambda *a: jax.vjp(lambda q, k, v: fn(q, k, v, *a[3:6]),
                                  *a[:3])[1](a[6])

    def lowered(fn):
        text = jax.jit(grads(fn)).lower(*args, ct).as_text()
        # locations aside
        return [ln.split(" loc(")[0] for ln in text.splitlines()
                if not ln.startswith("#loc")]

    assert lowered(new) == lowered(_todays_path)
    for got, want in zip(grads(new)(*args, ct), grads(_todays_path)(*args, ct)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_a_paired_call_that_cannot_keep_dqs_accumulator_takes_todays_path(
        monkeypatch):
    """The choice holds for forward and backward alike: where the
    one-kernel backward does not fit at the paired blocks, the forward
    is today's too."""
    monkeypatch.setattr(fa, "_vmem_limit_bytes", lambda: 256 * 2**10)
    x = jax.ShapeDtypeStruct((1, 192, 2, 64), jnp.float32)
    ran = _kernel_operands(jax.make_jaxpr(jax.grad(
        lambda q, k, v: flash_attention(q, k, v, None, interpret=True).sum(),
        argnums=(0, 1, 2)))(x, x, x))
    assert sorted(ran) == ["flash_dkv", "flash_dq", "flash_fwd"]
    assert ran["flash_fwd"][0][1] == (2, 192, 64)


def test_flash_calls_are_counted_when_traced():
    """``flash.calls`` and ``flash.paired_calls`` count TRACED calls: the
    layout is a property of the compiled program, so running a compiled
    step again counts nothing."""
    from pipegoose_tpu.telemetry.registry import get_registry

    reg = get_registry()
    was = reg.enabled
    reg.enable()
    try:
        def counts():
            return (reg.counter("flash.calls").value,
                    reg.counter("flash.paired_calls").value)

        start = counts()
        paired = jax.jit(lambda q: flash_attention(q, q, q, interpret=True))
        q = jnp.ones((1, 64, 2, 64))
        paired(q)
        assert counts() == (start[0] + 1, start[1] + 1)
        paired(q)  # compiled: nothing is traced
        assert counts() == (start[0] + 1, start[1] + 1)
        jax.make_jaxpr(lambda q: flash_attention(q, q, q, interpret=True))(
            jnp.ones((1, 64, 3, 64)))  # an odd head count
        assert counts() == (start[0] + 2, start[1] + 1)
        # a gradient traces the call once: one layout for both passes
        jax.make_jaxpr(jax.grad(lambda q: flash_attention(
            q, q, q, interpret=True).sum()))(q)
        assert counts() == (start[0] + 3, start[1] + 2)
    finally:
        if not was:
            reg.disable()
