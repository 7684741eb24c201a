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


def _assert_loss_and_grads_match_the_dense_head(
        devices, h, w, targets, token_w, valid, layout, tensor, block_t,
        block_v):
    """Loss, ``dh`` and ``dw`` of ``fused_ce_sums`` (interpreter) against
    the dense head's, in the given weight layout, alone or over a tensor
    axis of ``tensor`` devices."""
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
    _assert_loss_and_grads_match_the_dense_head(
        devices, h, w, targets, token_w, valid, layout, tensor, block_t,
        block_v)


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


# the forward's plan at the head one chip of each train cell sees,
# (tokens, local rows, hidden, itemsize, limit MiB) -> (block_t, token
# tiles resident, super-blocks, block_v): on a v5e's 64 MiB, the
# compiler's default 16 MiB, 96 MiB; then small and odd shapes: tokens
# under a tile, a tile count no power of two divides, a shard only 8
# divides (its whole 296 rows are one tile), the shifted 16,376 tokens
FWD_PLANS = [
    ((16384, 250880, 1024, 2, 64), (256, 8, 8, 2560)),
    ((16384, 125440, 2048, 2, 64), (256, 4, 16, 1792)),
    ((16384, 19456, 2048, 2, 64), (256, 8, 8, 1024)),
    ((16384, 250880, 1024, 2, 16), (256, 2, 32, 640)),
    ((16384, 125440, 2048, 2, 16), (256, 1, 64, 256)),
    ((16384, 19456, 2048, 2, 16), (256, 1, 64, 256)),
    ((16384, 250880, 1024, 2, 96), (256, 16, 4, 3584)),
    ((16384, 125440, 2048, 2, 96), (256, 4, 16, 2560)),
    ((16384, 19456, 2048, 2, 96), (256, 8, 8, 2432)),
    ((24, 128, 32, 4, 16), (32, 1, 1, 128)),
    ((37, 296, 32, 4, 16), (64, 1, 1, 296)),
    ((61 * 256, 250880, 1024, 2, 64), (256, 8, 8, 2560)),
    ((8 * 2047, 250880, 1024, 2, 64), (256, 8, 8, 2560)),
]


@pytest.mark.parametrize("shape, want", FWD_PLANS)
def test_forward_plan_comes_from_the_shapes_and_the_vmem(shape, want):
    t, v, hd, itemsize, mib = shape
    bt, ni, n_super, bv = fused_ce._pick_fwd_plan(t, v, hd, itemsize,
                                                  mib * 2**20)
    assert (bt, ni, n_super, bv) == want
    # a tile divides the shard; every token is in a super-block, padded
    # by less than a tile each
    assert v % bv == 0
    assert 0 <= ni * n_super - -(-t // bt) < n_super


@pytest.mark.parametrize("mib", [16, 64, 96])
@pytest.mark.parametrize("shape", [(16384, 250880, 1024),
                                   (16384, 125440, 2048),
                                   (16384, 19456, 2048)])
def test_forward_plan_fits_the_limit_it_is_given(shape, mib):
    """The plan's working set never passes three quarters of the limit
    unless it is ONE token tile at the smallest vocabulary tile (where
    the kernel asks for that tile's bytes instead), and neither more
    resident tokens nor the next vocabulary tile would still fit. On a
    v5e's limit and above: at least 1,024 tokens resident (at most 16
    walks of the head) and under 8,000 grid steps a call."""
    t, v, hd = shape
    limit = mib * 2**20
    bt, ni, n_super, bv = fused_ce._pick_fwd_plan(t, v, hd, 2, limit)
    tiles = fused_ce._fwd_vocab_tiles(v)
    assert bv in tiles and ni * n_super * bt >= t

    def held(ni, bv):
        return fused_ce._fwd_working_set_bytes(ni * bt, bt, bv, hd, 2)

    assert held(ni, bv) <= limit * 3 // 4 or (ni == 1 and bv == tiles[0])
    if 2 * ni * bt <= fused_ce._MAX_SUPER_TOKENS:
        assert held(2 * ni, bv) > limit * 3 // 4
    if bv != tiles[-1]:
        assert held(ni, tiles[tiles.index(bv) + 1]) > limit * 3 // 4
    if mib >= 64:
        assert ni * bt >= 1024 and n_super <= 16
        assert n_super * (v // bv) <= 8000


@pytest.mark.parametrize("rows, want", [
    (250880, [128, 256, 512, 640, 896, 1024, 1280, 1792, 2560, 3584]),
    (125440, [128, 256, 512, 640, 896, 1280, 1792, 2560, 3584]),
    (19456, [128, 256, 512, 1024, 2432]),
    (296, [8, 296]),      # 8 x 37: no lane multiple divides it
    (1001, []),           # nothing a compiled kernel can take
])
def test_forward_vocabulary_tiles_divide_the_shard(rows, want):
    assert fused_ce._fwd_vocab_tiles(rows) == want


# the forward at a plan that is NOT the caller's tile (block_t 16,
# block_v 64 go to the backward): token tiles of 8, two a super-block,
# three super-blocks (40 tokens padded to 48), four vocabulary tiles of
# 128 a shard; targets on both sides of every tile border, the last
# valid column and column 0; valid_size ON a tile border of the last
# shard (384 + 512 * (tensor - 1)) and inside a tile (400)
@pytest.mark.parametrize("tensor", [1, 2])
@pytest.mark.parametrize("layout", ["vh", "hv"])
@pytest.mark.parametrize("valid_in_last_shard", [384, 400, None])
def test_forward_at_its_own_plan_matches_the_dense_head(
        monkeypatch, devices, layout, tensor, valid_in_last_shard):
    monkeypatch.setattr(fused_ce, "_FWD_BLOCK_T", 8)
    monkeypatch.setattr(fused_ce, "_FWD_MAX_BLOCK_V", 128)
    monkeypatch.setattr(fused_ce, "_MAX_SUPER_TOKENS", 16)
    t, shard = 40, 512
    v = shard * tensor
    valid = (None if valid_in_last_shard is None
             else v - shard + valid_in_last_shard)
    assert fused_ce._pick_fwd_plan(t, shard, H, 4, 16 * 2**20) == (
        8, 2, 3, 128)
    rng = np.random.RandomState(11)
    h = jnp.asarray(rng.randn(t, H), jnp.float32) * 0.3
    w = jnp.asarray(rng.randn(v, H), jnp.float32) * 0.3
    last = (valid or v) - 1
    edges = [0, 127, 128, 255, 256, 383, 384, last - 1, last]
    if tensor > 1:
        edges += [shard - 1, shard, shard + 127, shard + 128]
    targets = rng.randint(0, valid or v, (t,))
    targets[:len(edges)] = edges
    targets = jnp.asarray(targets)
    token_w = jnp.asarray((rng.rand(t) < 0.8).astype(np.float32))
    _assert_loss_and_grads_match_the_dense_head(
        devices, h, w, targets, token_w, valid, layout, tensor, 16, 64)


def test_forward_per_token_results_match_the_dense_head(monkeypatch):
    """``lse`` and the target logit of every token, not their weighted
    sum: several super-blocks and vocabulary tiles, ragged tokens (the
    padded rows are cut off), a masked tail of the vocabulary."""
    monkeypatch.setattr(fused_ce, "_FWD_BLOCK_T", 8)
    monkeypatch.setattr(fused_ce, "_FWD_MAX_BLOCK_V", 128)
    monkeypatch.setattr(fused_ce, "_MAX_SUPER_TOKENS", 16)
    t, v, valid = 37, 384, 300
    rng = np.random.RandomState(12)
    h = jnp.asarray(rng.randn(t, H), jnp.float32) * 0.3
    w = jnp.asarray(rng.randn(v, H), jnp.float32) * 0.3
    targets = jnp.asarray(rng.randint(0, valid, (t,)), jnp.int32)
    logits = jnp.where(jnp.arange(v) < valid, h @ w.T, -jnp.inf)
    for vh in (True, False):
        lse, tl = fused_ce._fwd_pallas(
            h, w if vh else w.T, targets, jnp.zeros((1,), jnp.int32), valid,
            True, vh)
        assert lse.shape == tl.shape == (t,)
        np.testing.assert_allclose(
            np.asarray(lse), np.asarray(jax.nn.logsumexp(logits, axis=1)),
            rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(
            np.asarray(tl),
            np.asarray(jnp.take_along_axis(logits, targets[:, None], 1)[:, 0]),
            rtol=1e-5, atol=1e-5)


def test_traced_bloom_loss_records_the_forward_plan_once_a_trace():
    """``fused_ce.fwd_calls`` counts TRACED head passes and the gauges
    hold the plan that trace took: grid steps, walks of the head and the
    counted VMEM (``telemetry.get_registry()``); a compiled step run
    again records nothing."""
    import dataclasses

    from pipegoose_tpu.models import bloom
    from pipegoose_tpu.telemetry.registry import get_registry

    cfg = dataclasses.replace(
        bloom.BloomConfig(vocab_size=256, hidden_size=64, n_layer=1,
                          n_head=4), fused_ce=True)
    params = bloom.init_params(cfg, jax.random.PRNGKey(0))
    ids = jnp.asarray(np.random.RandomState(5).randint(0, 256, (2, 24)))
    reg = get_registry()
    was = reg.enabled
    reg.enable()
    try:
        calls = reg.counter("fused_ce.fwd_calls")
        start = calls.value
        step = jax.jit(jax.grad(
            lambda p: bloom.loss_fn(p, ids, None, ids, cfg)))
        step(params)
        assert calls.value == start + 1
        bt, ni, n_super, bv = fused_ce._pick_fwd_plan(
            2 * 23, 256, 64, 4, 16 * 2**20)
        assert reg.gauge("fused_ce.fwd_head_walks").value == n_super
        assert reg.gauge("fused_ce.fwd_grid_steps").value \
            == n_super * (256 // bv)
        assert reg.gauge("fused_ce.fwd_vmem_bytes").value \
            == fused_ce._fwd_working_set_bytes(ni * bt, bt, bv, 64, 4)
        step(params)  # compiled: nothing is traced
        assert calls.value == start + 1
    finally:
        if not was:
            reg.disable()


def test_bench_script_rehearses_off_the_chip(capsys):
    """``scripts/bench_fused_ce.py`` off the TPU: no time is reported,
    both kernels' plans are printed and the interpreter's forward is
    held against a dense head at the shape asked for."""
    import importlib.util
    import json
    import os

    path = os.path.join(os.path.dirname(__file__), "..", "..", "scripts",
                        "bench_fused_ce.py")
    spec = importlib.util.spec_from_file_location("bench_fused_ce", path)
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    assert bench.main(["--tokens", "40", "--rows", "384", "--hidden", "32",
                       "--valid", "300", "--dtype", "float32"]) == 0
    line = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert line["device"]["platform"] == "cpu" and "fwd" not in line
    assert line["timed"].startswith("not measured")
    assert line["plan"]["fwd"]["block_v"] == 384
    assert line["plan"]["bwd"]["block_v"] == 128
    assert max(line["interpreter_against_dense"].values()) < 1e-4


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

