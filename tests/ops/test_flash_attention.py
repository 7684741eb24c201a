"""Flash-attention kernel vs XLA reference (interpret mode on CPU —
same kernel code path the TPU compiles)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pipegoose_tpu.models.bloom import alibi_slopes
from pipegoose_tpu.ops.flash_attention import _xla_reference, flash_attention

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
