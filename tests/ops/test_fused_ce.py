"""Fused CE kernel (ops/fused_ce.py) vs the reference CE path:
values AND gradients, single-device and vocab-parallel, padded-vocab
masking included. Interpret mode on CPU (same verification strategy as
the flash kernels, tests/ops/test_flash_attention.py)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from pipegoose_tpu.nn.tensor_parallel.layers import (
    vocab_parallel_cross_entropy,
)
from pipegoose_tpu.ops import fused_ce
from pipegoose_tpu.ops.fused_ce import fused_ce_sums

from pipegoose_tpu.distributed.compat import shard_map

T, H, V = 24, 32, 128


@pytest.fixture(scope="module")
def data():
    rng = np.random.RandomState(0)
    h = jnp.asarray(rng.randn(T, H), jnp.float32) * 0.3
    w = jnp.asarray(rng.randn(V, H), jnp.float32) * 0.3
    targets = jnp.asarray(rng.randint(0, 100, (T,)))
    token_w = jnp.asarray((rng.rand(T) < 0.8).astype(np.float32))
    return h, w, targets, token_w


def _ref_sums(h, w, targets, token_w, axis_name=None, valid=None):
    logits = jnp.einsum("th,vh->tv", h, w, preferred_element_type=jnp.float32)
    per_tok = vocab_parallel_cross_entropy(
        logits, targets, axis_name, valid_size=valid
    )
    return (per_tok * token_w).sum(), token_w.sum()


def test_fused_matches_reference_value(data):
    h, w, targets, token_w = data
    ref_tot, ref_cnt = _ref_sums(h, w, targets, token_w)
    tot, cnt = fused_ce_sums(h, w, targets, token_w, interpret=True)
    assert abs(float(tot) - float(ref_tot)) < 1e-3
    assert float(cnt) == float(ref_cnt)


def test_fused_matches_reference_grads(data):
    h, w, targets, token_w = data

    def ref_loss(h, w):
        tot, cnt = _ref_sums(h, w, targets, token_w)
        return tot / cnt

    def fused_loss(h, w):
        tot, cnt = fused_ce_sums(h, w, targets, token_w, interpret=True)
        return tot / cnt

    (rl, (rdh, rdw)) = jax.value_and_grad(ref_loss, argnums=(0, 1))(h, w)
    (fl, (fdh, fdw)) = jax.value_and_grad(fused_loss, argnums=(0, 1))(h, w)
    assert abs(float(fl) - float(rl)) < 1e-4
    np.testing.assert_allclose(np.asarray(fdh), np.asarray(rdh),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(fdw), np.asarray(rdw),
                               rtol=1e-4, atol=1e-5)


# the one backward kernel against the dense head's dh and dw (float32,
# interpret mode). (tokens, vocabulary, block_t, block_v, the most tokens
# a super-block may hold, valid_size, visits of a carried dw tile)
BWD_CASES = {
    "one_super_block": (24, 128, 8, 32, 4096, None, 1),
    # dw_j is written on the first visit and read, added to and written
    # back on three more; four vocabulary tiles between two visits
    "carried_four_visits": (64, 128, 8, 32, 16, None, 4),
    # ONE vocabulary tile: a visit reads what the step before wrote
    "carried_single_vocab_tile": (48, 32, 8, 32, 16, None, 3),
    # a super-block of one token tile: read, add and write in one step
    "carried_one_tile_super_blocks": (24, 64, 8, 32, 8, None, 3),
    "padded_vocabulary": (40, 128, 8, 32, 16, 100, 3),
    # 37 tokens: padded to 40 for the token block, then to 3 x 16 by the
    # backward for its super-blocks
    "ragged_tokens": (37, 128, 8, 32, 16, None, 3),
}


def _dense_loss(h, w_vh, targets, token_w, valid):
    tot, cnt = _ref_sums(h, w_vh, targets, token_w, valid=valid)
    return tot / cnt


@pytest.mark.parametrize("tensor", [1, 2])
@pytest.mark.parametrize("layout", ["vh", "hv"])
@pytest.mark.parametrize("case", sorted(BWD_CASES))
def test_fused_backward_matches_the_dense_head(monkeypatch, devices, case,
                                               layout, tensor):
    """``dh`` and ``dw`` of ``fused_ce_bwd`` equal the dense head's, in
    both weight layouts, alone and over a tensor axis of 2 (the shard's
    column offset, ``dh`` summed over the axis), with zero-weight tokens
    in every case; the carried cases visit each ``dw`` tile more than
    once, so a read-modify-write that is missed or lands late shows."""
    t, v, block_t, block_v, max_super, valid, visits = BWD_CASES[case]
    monkeypatch.setattr(fused_ce, "_MAX_SUPER_TOKENS", max_super)
    padded = -(-t // block_t) * block_t
    assert fused_ce._pick_super_block(
        padded, block_t, block_v, H, 4, 16 * 2**20)[1] == visits
    rng = np.random.RandomState(3)
    h = jnp.asarray(rng.randn(t, H), jnp.float32) * 0.3
    w = jnp.asarray(rng.randn(v, H), jnp.float32) * 0.3
    targets = jnp.asarray(rng.randint(0, valid or v, (t,)))
    token_w = jnp.asarray((rng.rand(t) < 0.8).astype(np.float32))
    rl, (rdh, rdw) = jax.value_and_grad(_dense_loss, argnums=(0, 1))(
        h, w, targets, token_w, valid)
    axis = "tensor" if tensor > 1 else None

    def loss(h, w):
        tot, cnt = fused_ce_sums(
            h, w, targets, token_w, axis, valid, block_t=block_t,
            block_v=block_v, interpret=True, weight_layout=layout)
        return tot / cnt

    fn = jax.value_and_grad(loss, argnums=(0, 1))
    w_in = w if layout == "vh" else w.T
    if tensor > 1:
        w_spec = P("tensor") if layout == "vh" else P(None, "tensor")
        mesh = jax.sharding.Mesh(np.asarray(devices[:tensor]), ("tensor",))
        fn = jax.jit(shard_map(fn, mesh=mesh, in_specs=(P(), w_spec),
                               out_specs=(P(), (P(), w_spec)),
                               check_vma=False))
    fl, (fdh, fdw) = fn(h, w_in)
    assert abs(float(fl) - float(rl)) < 1e-4
    np.testing.assert_allclose(np.asarray(fdh), np.asarray(rdh),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(fdw if layout == "vh" else fdw.T), np.asarray(rdw),
        rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("layout", ["vh", "hv"])
def test_two_passes_over_one_weight_add_their_dw(monkeypatch, data, layout):
    """GLM's shape of use: the head is passed twice a step (main head
    and MTP module) on ONE weight, so autodiff adds two ``dw`` results,
    each carried over its own super-blocks."""
    h, w, targets, token_w = data
    monkeypatch.setattr(fused_ce, "_MAX_SUPER_TOKENS", 8)
    h2, targets2 = h[::-1] * 0.5, (targets + 7) % V

    def dense(h, h2, w):
        return (_dense_loss(h, w, targets, token_w, None)
                + 0.3 * _dense_loss(h2, w, targets2, token_w, None))

    def fused(h, h2, w_in):
        def one(x, tg):
            tot, cnt = fused_ce_sums(x, w_in, tg, token_w, block_t=8,
                                     block_v=32, interpret=True,
                                     weight_layout=layout)
            return tot / cnt
        return one(h, targets) + 0.3 * one(h2, targets2)

    want = jax.grad(dense, argnums=(0, 1, 2))(h, h2, w)
    got = jax.grad(fused, argnums=(0, 1, 2))(
        h, h2, w if layout == "vh" else w.T)
    got = got[:2] + (got[2] if layout == "vh" else got[2].T,)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


# (tokens, block_t, block_v, hidden, itemsize, limit MiB) -> (tiles a
# super-block, super-blocks): the three train cells on a v5e's 64 MiB,
# the compiler's default 16 MiB, a tile count no power of two divides
@pytest.mark.parametrize("shape, want", [
    ((16384, 256, 512, 1024, 2, 64), (16, 4)),
    ((16384, 256, 512, 2048, 2, 64), (4, 16)),
    ((16384, 256, 256, 2048, 2, 64), (8, 8)),
    ((16384, 256, 512, 1024, 2, 16), (1, 64)),
    ((61 * 256, 256, 512, 1024, 2, 64), (16, 4)),
    ((24, 8, 32, 32, 4, 16), (3, 1)),
])
def test_super_block_comes_from_the_shapes_and_the_vmem(shape, want):
    t, bt, bv, hd, itemsize, mib = shape
    ni, n_super = fused_ce._pick_super_block(t, bt, bv, hd, itemsize,
                                             mib * 2**20)
    assert (ni, n_super) == want
    # every token in a super-block, padded by less than a tile each
    assert 0 <= ni * n_super - -(-t // bt) < n_super


# the head as one chip of each train cell sees it: (tokens, block_v,
# hidden); bloom-560m, bloom-1b7 over tensor 2, GLM's slice (block_v 256)
@pytest.mark.parametrize("mib", [16, 64, 96])
@pytest.mark.parametrize("shape", [(16384, 512, 1024), (16384, 512, 2048),
                                   (16384, 256, 2048)])
def test_super_block_fits_the_limit_it_is_given(shape, mib):
    """Never zero tiles, every token in a super-block, and a super-block
    of more than one tile is never what passes three quarters of the
    limit (where ONE tile already does, at the compiler's default 16 MiB
    and hidden 2,048, the kernel asks for that tile's bytes instead)."""
    t, bv, hd = shape
    limit = mib * 2**20
    ni, n_super = fused_ce._pick_super_block(t, 256, bv, hd, 2, limit)
    assert ni >= 1 and n_super >= 1 and ni * n_super * 256 >= t
    assert ni * 256 <= fused_ce._MAX_SUPER_TOKENS
    held = fused_ce._bwd_working_set_bytes(ni * 256, 256, bv, hd, 2)
    assert held <= limit * 3 // 4 or ni == 1
    # and it is the most the limit lets it hold
    if ni * 256 < fused_ce._MAX_SUPER_TOKENS and held <= limit * 3 // 4:
        assert fused_ce._bwd_working_set_bytes(
            2 * ni * 256, 256, bv, hd, 2) > limit * 3 // 4


def test_fused_valid_size_masks_padded_slots(data):
    """Targets never point at padded slots, but padded columns must be
    excluded from the log-sum-exp (pad_vocab semantics)."""
    h, w, targets, token_w = data
    valid = 100
    ref_tot, _ = _ref_sums(h, w, targets, token_w, valid=valid)
    tot, _ = fused_ce_sums(
        h, w, targets, token_w, valid_size=valid, interpret=True
    )
    assert abs(float(tot) - float(ref_tot)) < 1e-3


def test_fused_vocab_parallel_matches_dense(data, devices):
    """tp=4 vocab-sharded fused CE == single-device: loss AND both
    cotangents (incl. the fused f-operator psum of dh)."""
    h, w, targets, token_w = data
    valid = 100

    def ref_loss(h, w):
        tot, cnt = _ref_sums(h, w, targets, token_w, valid=valid)
        return tot / cnt

    rl, (rdh, rdw) = jax.value_and_grad(ref_loss, argnums=(0, 1))(h, w)

    from pipegoose_tpu.distributed import ParallelContext

    ctx = ParallelContext(tensor_parallel_size=4, data_parallel_size=2)
    try:
        def tp_loss(h, w):
            tot, cnt = fused_ce_sums(
                h, w, targets, token_w, axis_name="tensor",
                valid_size=valid, interpret=True,
            )
            return tot / cnt

        fn = jax.jit(
            shard_map(
                lambda h, w: jax.value_and_grad(tp_loss, argnums=(0, 1))(h, w),
                mesh=ctx.mesh,
                in_specs=(P(), P("tensor")),
                out_specs=(P(), (P(), P("tensor"))),
                check_vma=False,
            )
        )
        fl, (fdh, fdw) = fn(h, w)
        assert abs(float(fl) - float(rl)) < 1e-4
        np.testing.assert_allclose(np.asarray(fdh), np.asarray(rdh),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(fdw), np.asarray(rdw),
                                   rtol=1e-4, atol=1e-5)
    finally:
        ctx.destroy()


def test_fused_bf16_inputs(data):
    """bf16 hidden/embedding (the bench dtype): f32 accumulation inside
    the kernel keeps the loss within bf16 rounding of the f32 reference."""
    h, w, targets, token_w = data
    hb, wb = h.astype(jnp.bfloat16), w.astype(jnp.bfloat16)
    ref_tot, _ = _ref_sums(
        hb.astype(jnp.float32), wb.astype(jnp.float32), targets, token_w
    )
    tot, _ = fused_ce_sums(hb, wb, targets, token_w, interpret=True)
    assert abs(float(tot) - float(ref_tot)) / max(abs(float(ref_tot)), 1) < 2e-2


def test_bloom_loss_fused_matches_default(devices):
    """config.fused_ce=True reproduces the default loss path's value and
    grads end-to-end (single device + TP2), masked batch included."""
    import dataclasses

    from pipegoose_tpu.distributed import ParallelContext
    from pipegoose_tpu.models import bloom

    cfg = bloom.BloomConfig(vocab_size=128, hidden_size=64, n_layer=2, n_head=4)
    cfg_f = dataclasses.replace(cfg, fused_ce=True)
    params = bloom.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(5)
    ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (2, 24)))
    mask = np.ones((2, 24), np.int32)
    mask[1, 20:] = 0
    mask = jnp.asarray(mask)

    rl, rg = jax.value_and_grad(
        lambda p: bloom.loss_fn(p, ids, mask, ids, cfg)
    )(params)
    fl, fg = jax.value_and_grad(
        lambda p: bloom.loss_fn(p, ids, mask, ids, cfg_f)
    )(params)
    assert abs(float(fl) - float(rl)) < 1e-4
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-5
        ),
        fg, rg,
    )

    ctx = ParallelContext(tensor_parallel_size=2, data_parallel_size=4)
    try:
        specs = bloom.tp_specs(params)
        fn = jax.jit(
            shard_map(
                lambda p: jax.value_and_grad(
                    lambda p: bloom.loss_fn(p, ids, mask, ids, cfg_f,
                                            tp_axis="tensor")
                )(p),
                mesh=ctx.mesh,
                in_specs=(specs,),
                out_specs=(P(), specs),
                check_vma=False,
            )
        )
        tl, tg = fn(params)
        assert abs(float(tl) - float(rl)) < 1e-4
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-5
            ),
            tg, rg,
        )
    finally:
        ctx.destroy()


def test_fused_hv_layout_matches_vh(data):
    """weight_layout='hv' (untied (H, V) column head) must agree with
    'vh' on the transposed weight — value and both grads."""
    h, w, targets, token_w = data

    def loss_vh(h, w):
        tot, cnt = fused_ce_sums(h, w, targets, token_w, interpret=True)
        return tot / cnt

    def loss_hv(h, w_t):
        tot, cnt = fused_ce_sums(
            h, w_t, targets, token_w, interpret=True, weight_layout="hv"
        )
        return tot / cnt

    rl, (rdh, rdw) = jax.value_and_grad(loss_vh, argnums=(0, 1))(h, w)
    fl, (fdh, fdwt) = jax.value_and_grad(loss_hv, argnums=(0, 1))(h, w.T)
    assert abs(float(fl) - float(rl)) < 1e-4
    np.testing.assert_allclose(np.asarray(fdh), np.asarray(rdh),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(fdwt.T), np.asarray(rdw),
                               rtol=1e-4, atol=1e-5)
    with pytest.raises(ValueError, match="weight_layout"):
        fused_ce_sums(h, w, targets, token_w, weight_layout="hw")


def test_infeasible_block_v_raises_compiled_passes_interpret(data):
    """V_local with no feasible tile (no halving of block_v >= 8 divides
    it) must fail loudly for compiled runs instead of dying in Mosaic —
    but the interpreter has no VMEM limit, so the whole-vocab fallback
    still runs there (and still matches the reference)."""
    h, _, _, token_w = data
    rng = np.random.RandomState(1)
    # odd AND larger than the default block_v=512: no halving divides
    # it, so the fallback would be a whole-vocab (1001, H) tile
    v_odd = 1001
    w = jnp.asarray(rng.randn(v_odd, H), jnp.float32) * 0.3
    targets = jnp.asarray(rng.randint(0, v_odd, (T,)))
    with pytest.raises(ValueError, match="VMEM-infeasible"):
        fused_ce_sums(h, w, targets, token_w, interpret=False)
    ref_tot, ref_cnt = _ref_sums(h, w, targets, token_w)
    tot, cnt = fused_ce_sums(h, w, targets, token_w, interpret=True)
    assert abs(float(tot) - float(ref_tot)) < 1e-3
    assert float(cnt) == float(ref_cnt)


def test_small_unaligned_vocab_raises_compiled_passes_interpret(data):
    """V_local SMALLER than the requested block but with no >= 8
    divisor (e.g. 300 = 4 x 75) used to slip past the guard — the old
    check only fired when the fallback tile EXCEEDED the requested
    block — and die in Mosaic as a ragged whole-vocab tile. The
    fallback is now detected on both sides of block_v (ISSUE 5
    satellite); the interpreter still runs it and still matches."""
    h, _, _, token_w = data
    rng = np.random.RandomState(2)
    v_small = 300
    w = jnp.asarray(rng.randn(v_small, H), jnp.float32) * 0.3
    targets = jnp.asarray(rng.randint(0, v_small, (T,)))
    with pytest.raises(ValueError, match="VMEM-infeasible"):
        fused_ce_sums(h, w, targets, token_w, interpret=False)
    ref_tot, ref_cnt = _ref_sums(h, w, targets, token_w)
    tot, cnt = fused_ce_sums(h, w, targets, token_w, interpret=True)
    assert abs(float(tot) - float(ref_tot)) < 1e-3
    assert float(cnt) == float(ref_cnt)


def test_llama_and_mixtral_fused_ce_match_default(devices):
    """config.fused_ce on the untied-head families reproduces the
    default loss (llama untied + tied; mixtral incl. aux/z)."""
    import dataclasses

    from pipegoose_tpu.models import llama, mixtral

    rng = np.random.RandomState(9)

    for tied in (False, True):
        cfg = llama.LlamaConfig(
            vocab_size=128, hidden_size=64, intermediate_size=96,
            n_layer=2, n_head=4, n_kv_head=2, tie_word_embeddings=tied,
        )
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        ids = jnp.asarray(rng.randint(0, 128, (2, 24)))
        rl, rg = jax.value_and_grad(
            lambda p: llama.loss_fn(p, ids, None, ids, cfg)
        )(params)
        cfg_f = dataclasses.replace(cfg, fused_ce=True)
        fl, fg = jax.value_and_grad(
            lambda p: llama.loss_fn(p, ids, None, ids, cfg_f)
        )(params)
        assert abs(float(fl) - float(rl)) < 1e-4, ("llama", tied)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-3, atol=2e-5
            ),
            fg, rg,
        )

    mcfg = mixtral.MixtralConfig(
        vocab_size=128, hidden_size=64, intermediate_size=96, n_layer=2,
        n_head=4, n_kv_head=2, num_experts=2, top_k=1, router_jitter=0.0,
    )
    mparams = mixtral.init_params(mcfg, jax.random.PRNGKey(1))
    mids = jnp.asarray(rng.randint(0, 128, (2, 24)))
    mcfg_f = dataclasses.replace(mcfg, fused_ce=True)
    rl, rg = jax.value_and_grad(
        lambda p: mixtral.loss_fn(p, mids, None, mids, mcfg, train=False)
    )(mparams)
    fl, fg = jax.value_and_grad(
        lambda p: mixtral.loss_fn(p, mids, None, mids, mcfg_f, train=False)
    )(mparams)
    assert abs(float(fl) - float(rl)) < 1e-4, ("mixtral", fl, rl)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-3, atol=2e-5
        ),
        fg, rg,
    )


def test_fused_hv_vocab_parallel_matches_dense(data, devices):
    """hv layout under tp=4: the column-sharded (H, V/tp) head's shard
    offset and lse/tl combine must reproduce the dense loss and grads
    (the untied llama/mixtral TP configuration)."""
    h, w, targets, token_w = data
    w_hv = jnp.asarray(np.asarray(w).T)  # (H, V)
    valid = 100

    def ref_loss(h, w_hv):
        logits = jnp.einsum("th,hv->tv", h, w_hv,
                            preferred_element_type=jnp.float32)
        per_tok = vocab_parallel_cross_entropy(
            logits, targets, None, valid_size=valid
        )
        return (per_tok * token_w).sum() / token_w.sum()

    rl, (rdh, rdw) = jax.value_and_grad(ref_loss, argnums=(0, 1))(h, w_hv)

    from pipegoose_tpu.distributed import ParallelContext

    ctx = ParallelContext(tensor_parallel_size=4, data_parallel_size=2)
    try:
        def tp_loss(h, w_hv):
            tot, cnt = fused_ce_sums(
                h, w_hv, targets, token_w, axis_name="tensor",
                valid_size=valid, interpret=True, weight_layout="hv",
            )
            return tot / cnt

        fn = jax.jit(
            shard_map(
                lambda h, w: jax.value_and_grad(tp_loss, argnums=(0, 1))(h, w),
                mesh=ctx.mesh,
                in_specs=(P(), P(None, "tensor")),
                out_specs=(P(), (P(), P(None, "tensor"))),
                check_vma=False,
            )
        )
        fl, (fdh, fdw) = fn(h, w_hv)
        assert abs(float(fl) - float(rl)) < 1e-4
        np.testing.assert_allclose(np.asarray(fdh), np.asarray(rdh),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(fdw), np.asarray(rdw),
                                   rtol=1e-4, atol=1e-5)
    finally:
        ctx.destroy()


def test_sp_heads_fused_ce_match_default(devices):
    """config.fused_ce in the SEQUENCE-PARALLEL heads (bloom tied-vh,
    llama untied-hv, mixtral hv): SP loss with the fused kernel ==
    SP loss with materialized logits, ragged mask included. This is the
    long-context configuration where the (B, S_local, V) buffer is the
    thing that OOMs."""
    import dataclasses

    from pipegoose_tpu.distributed import ParallelContext
    from pipegoose_tpu.models import bloom, llama, mixtral

    rng = np.random.RandomState(11)
    ids = jnp.asarray(rng.randint(0, 128, (2, 32)))
    mask = np.ones((2, 32), np.int32)
    mask[1, 28:] = 0
    mask = jnp.asarray(mask)

    cases = [
        ("bloom", bloom, bloom.BloomConfig(
            vocab_size=128, hidden_size=64, n_layer=2, n_head=4), {}),
        ("llama", llama, llama.LlamaConfig(
            vocab_size=128, hidden_size=64, intermediate_size=96,
            n_layer=2, n_head=4, n_kv_head=2), {}),
        ("mixtral", mixtral, mixtral.MixtralConfig(
            vocab_size=128, hidden_size=64, intermediate_size=96,
            n_layer=2, n_head=4, n_kv_head=2, num_experts=2, top_k=1,
            router_jitter=0.0), {"train": False}),
    ]
    ctx = ParallelContext(sequence_parallel_size=4, data_parallel_size=2)
    try:
        for name, mod, cfg, kw in cases:
            params = mod.init_params(cfg, jax.random.PRNGKey(0))
            cfg_f = dataclasses.replace(cfg, fused_ce=True)

            def run(c):
                fn = jax.jit(
                    shard_map(
                        lambda p, i, m: mod.loss_fn_sp(
                            p, i, m, i, c, sp_axis="seq", **kw
                        ),
                        mesh=ctx.mesh,
                        in_specs=(P(), P(None, "seq"), P(None, "seq")),
                        out_specs=P(),
                        check_vma=False,
                    )
                )
                return float(fn(params, ids, mask))

            ref, fused = run(cfg), run(cfg_f)
            assert abs(fused - ref) < 1e-4, (name, fused, ref)
    finally:
        ctx.destroy()


def test_pp_heads_fused_ce_match_default(devices):
    """config.fused_ce in the PIPELINE heads (GPipe + 1F1B): the last
    stage's per-microbatch logits buffer — the PP step's largest
    tensor — replaced by the fused kernel with identical loss."""
    import dataclasses

    from pipegoose_tpu.distributed import ParallelContext
    from pipegoose_tpu.models import bloom, llama, mixtral

    rng = np.random.RandomState(13)
    ids = jnp.asarray(rng.randint(0, 128, (4, 16)))

    cases = [
        ("bloom", bloom, bloom.BloomConfig(
            vocab_size=128, hidden_size=64, n_layer=4, n_head=4), {}),
        ("llama", llama, llama.LlamaConfig(
            vocab_size=128, hidden_size=64, intermediate_size=96,
            n_layer=4, n_head=4, n_kv_head=2), {}),
        ("mixtral", mixtral, mixtral.MixtralConfig(
            vocab_size=128, hidden_size=64, intermediate_size=96,
            n_layer=4, n_head=4, n_kv_head=2, num_experts=2, top_k=1,
            router_jitter=0.0), {"train": False}),
    ]
    ctx = ParallelContext(pipeline_parallel_size=4, data_parallel_size=2)
    try:
        for name, mod, cfg, kw in cases:
            params = mod.init_params(cfg, jax.random.PRNGKey(0))
            cfg_f = dataclasses.replace(cfg, fused_ce=True)
            specs = mod.pp_specs(params)

            for runtime in ("loss_fn_pp", "loss_fn_1f1b"):
                loss_fn = getattr(mod, runtime)

                def run(c):
                    fn = jax.jit(
                        shard_map(
                            lambda p, i: loss_fn(
                                p, i, None, i, c, n_microbatches=2,
                                pipe_axis="pipe", **kw
                            ),
                            mesh=ctx.mesh,
                            in_specs=(specs, P()),
                            out_specs=P(),
                            check_vma=False,
                        )
                    )
                    return float(fn(params, ids))

                ref, fused = run(cfg), run(cfg_f)
                assert abs(fused - ref) < 1e-4, (name, runtime, fused, ref)
    finally:
        ctx.destroy()

