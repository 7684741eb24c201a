"""The shared kernels at what GLM-4.7-Flash asks of them, in interpret
mode: the flash kernels at head width 256 against the plain XLA
attention, and the fused cross entropy over a vocabulary that is not a
multiple of its block (padded rows masked by ``valid_size``), called
twice on one weight as the main head and the MTP module call it."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pipegoose_tpu.ops import flash_attention as fa
from pipegoose_tpu.ops.flash_attention import _xla_reference, flash_attention
from pipegoose_tpu.ops.fused_ce import fused_ce_sums
from pipegoose_tpu.testing import kernel_calls


def _flat(x):
    b, s, nh, hd = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * nh, s, hd)


def _plain(q, k, v, scale):
    b, s, nh, hd = q.shape
    out = _xla_reference(_flat(q), _flat(k), _flat(v),
                         jnp.zeros((b * nh,)), scale, True)
    return out.reshape(b, nh, s, hd).transpose(0, 2, 1, 3)


@pytest.mark.parametrize("backward", ["flash_bwd", "pair"])
def test_flash_kernels_at_head_width_256_match_plain_attention(
        monkeypatch, backward):
    """Forward and gradients at width 256, the backward both ways: the
    one kernel, and (a VMEM limit too small for dQ's whole-sequence
    accumulator: 256 x 256 x 12 bytes against three quarters of 512 KiB)
    the ``flash_dq`` + ``flash_dkv`` pair it falls back to."""
    b, s, nh, hd = 1, 256, 2, 256
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v = (jax.random.normal(kk, (b, s, nh, hd)) for kk in ks[:3])
    ct = jax.random.normal(ks[3], (b, s, nh, hd))
    scale = hd ** -0.5
    if backward == "pair":
        monkeypatch.setattr(fa, "_vmem_limit_bytes", lambda: 512 * 2**10)

    def flash(q, k, v):
        return flash_attention(q, k, v, scale=scale, interpret=True)

    ran = jax.make_jaxpr(lambda q, k, v: jax.vjp(flash, q, k, v)[1](ct))(
        q, k, v)
    assert {name: kernel_calls(ran, name)
            for name in ("flash_bwd", "flash_dq", "flash_dkv")} == (
        {"flash_bwd": 1, "flash_dq": 0, "flash_dkv": 0}
        if backward == "flash_bwd" else
        {"flash_bwd": 0, "flash_dq": 1, "flash_dkv": 1})
    out, vjp = jax.vjp(flash, q, k, v)
    want, want_vjp = jax.vjp(lambda q, k, v: _plain(q, k, v, scale), q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)
    for name, got, ref in zip(("dq", "dk", "dv"), vjp(ct), want_vjp(ct)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=5e-5, err_msg=name)


def _dense_ce(h, w, targets, weights, valid):
    logits = (h @ w.T)[:, :valid]
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0]
    return -(picked * weights).sum(), weights.sum()


def test_fused_ce_over_a_vocabulary_that_is_no_multiple_of_its_block():
    """90 real rows padded to 96 = 3 blocks of 32; what the padding
    holds cannot reach the loss or a gradient."""
    t, hd, valid, padded = 40, 16, 90, 96
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    h1, h2 = (jax.random.normal(k, (t, hd)) for k in ks[:2])
    w = jax.random.normal(ks[2], (padded, hd))
    t1, t2 = (jax.random.randint(k, (t,), 0, valid) for k in ks[3:])
    weights = (jnp.arange(t) < t - 2).astype(jnp.float32)

    def fused(h1, h2, w):
        a, na = fused_ce_sums(h1, w, t1, jnp.ones(t), None, valid,
                              block_v=32, interpret=True)
        b, nb = fused_ce_sums(h2, w, t2, weights, None, valid,
                              block_v=32, interpret=True)
        return a / na + 0.3 * b / nb

    def dense(h1, h2, w):
        a, na = _dense_ce(h1, w, t1, jnp.ones(t), valid)
        b, nb = _dense_ce(h2, w, t2, weights, valid)
        return a / na + 0.3 * b / nb

    got, got_g = jax.value_and_grad(fused, argnums=(0, 1, 2))(h1, h2, w)
    want, want_g = jax.value_and_grad(dense, argnums=(0, 1, 2))(h1, h2, w)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
    for a, b in zip(got_g, want_g):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)
    assert float(jnp.abs(got_g[2][valid:]).max()) == 0.0
    # garbage in the padded rows changes nothing
    bent = fused(h1, h2, w.at[valid:].set(1e4))
    np.testing.assert_allclose(float(bent), float(want), rtol=1e-5)
