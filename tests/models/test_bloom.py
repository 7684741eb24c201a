"""BLOOM parity vs HuggingFace torch implementation — milestone M1 of
SURVEY.md §7.4 ('bloom-560m forward matches HF logits', tested at tiny
scale like the reference's Muennighoff/bloom-tiny-random fixtures,
tests/nn/tensor_parallel/conftest.py:4-9 — built locally from a random
config since this environment has no network)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from pipegoose_tpu.distributed import ParallelContext
from pipegoose_tpu.models import bloom
from pipegoose_tpu.models.hf import bloom_params_from_hf, bloom_params_to_hf_state_dict

from pipegoose_tpu.distributed.compat import shard_map


@pytest.fixture(scope="module")
def hf_model():
    torch = pytest.importorskip("torch")
    from transformers import BloomConfig as HFBloomConfig, BloomForCausalLM

    torch.manual_seed(0)
    cfg = HFBloomConfig(
        vocab_size=128,
        hidden_size=64,
        n_layer=3,
        n_head=4,
        use_cache=False,
    )
    model = BloomForCausalLM(cfg)
    model.eval()
    return model


@pytest.fixture(scope="module")
def inputs():
    rng = np.random.RandomState(42)
    input_ids = rng.randint(0, 128, size=(2, 10))
    attention_mask = np.ones((2, 10), dtype=np.int64)
    attention_mask[1, 7:] = 0  # padded sample exercises the mask path
    return input_ids, attention_mask


def _hf_logits(hf_model, input_ids, attention_mask):
    import torch

    with torch.no_grad():
        out = hf_model(
            input_ids=torch.tensor(input_ids),
            attention_mask=torch.tensor(attention_mask),
        )
    return out.logits.numpy()


def test_single_device_logits_match_hf(hf_model, inputs):
    input_ids, attention_mask = inputs
    cfg, params = bloom_params_from_hf(hf_model)
    logits = bloom.forward(params, jnp.asarray(input_ids), jnp.asarray(attention_mask), cfg)
    ref = _hf_logits(hf_model, input_ids, attention_mask)
    # compare on valid positions (HF pads attention differently on masked tails)
    valid = attention_mask.astype(bool)
    np.testing.assert_allclose(
        np.asarray(logits)[valid], ref[valid], rtol=2e-4, atol=2e-4
    )


def test_tp4_logits_match_single_device(hf_model, inputs, devices):
    """TP=2 sharded forward == single-device forward (the reference's
    hybrid-equivalence pattern, tests/test_hybrid.py:19-78)."""
    input_ids, attention_mask = inputs
    cfg, params = bloom_params_from_hf(hf_model)
    ref = bloom.forward(params, jnp.asarray(input_ids), jnp.asarray(attention_mask), cfg)

    ctx = ParallelContext(tensor_parallel_size=2, data_parallel_size=2)
    try:
        specs = bloom.tp_specs(params)

        fn = shard_map(
            lambda p, i, m: bloom.forward(p, i, m, cfg, tp_axis="tensor"),
            mesh=ctx.mesh,
            in_specs=(specs, P(), P()),
            out_specs=P(None, None, "tensor"),
            check_vma=False,
        )
        out = fn(params, jnp.asarray(input_ids), jnp.asarray(attention_mask))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-4)
    finally:
        ctx.destroy()


def test_loss_and_grads_finite(hf_model, inputs):
    input_ids, attention_mask = inputs
    cfg, params = bloom_params_from_hf(hf_model)
    ids, mask = jnp.asarray(input_ids), jnp.asarray(attention_mask)
    loss, grads = jax.value_and_grad(bloom.loss_fn)(params, ids, mask, ids, cfg)
    assert np.isfinite(float(loss))
    leaves = jax.tree_util.tree_leaves(grads)
    assert all(np.all(np.isfinite(np.asarray(g))) for g in leaves)
    assert any(float(jnp.abs(g).max()) > 0 for g in leaves)


def test_loss_matches_hf(hf_model, inputs):
    import torch

    input_ids, attention_mask = inputs
    cfg, params = bloom_params_from_hf(hf_model)
    # all-ones mask: HF's loss ignores attention_mask weighting, so
    # compare on the unpadded batch only
    ids = input_ids[:1]
    m = np.ones_like(ids)
    with torch.no_grad():
        hf_loss = hf_model(
            input_ids=torch.tensor(ids),
            attention_mask=torch.tensor(m),
            labels=torch.tensor(ids),
        ).loss.item()
    ours = float(bloom.loss_fn(params, jnp.asarray(ids), jnp.asarray(m), jnp.asarray(ids), cfg))
    assert abs(ours - hf_loss) < 2e-3, (ours, hf_loss)


def test_roundtrip_state_dict(hf_model):
    cfg, params = bloom_params_from_hf(hf_model)
    sd = bloom_params_to_hf_state_dict(params)
    orig = {k: v.detach().numpy() for k, v in hf_model.state_dict().items()}
    for k, v in orig.items():
        if k in sd:
            np.testing.assert_allclose(sd[k], v, rtol=1e-6)
    # every original key except tied lm_head must be covered
    missing = set(orig) - set(sd)
    assert not missing, missing


def test_remat_same_result(hf_model, inputs):
    input_ids, attention_mask = inputs
    cfg, params = bloom_params_from_hf(hf_model)
    import dataclasses

    cfg_remat = dataclasses.replace(cfg, remat=True)
    ids, mask = jnp.asarray(input_ids), jnp.asarray(attention_mask)
    l1 = float(bloom.loss_fn(params, ids, mask, ids, cfg))
    l2 = float(bloom.loss_fn(params, ids, mask, ids, cfg_remat))
    assert abs(l1 - l2) < 1e-5


def test_tp_grads_match_single_device(hf_model, inputs, devices):
    """Full-model gradient equivalence TP=2 vs single device — regression
    for the LM-head f-operator (a missing copy_to_tensor_group leaves
    every grad upstream of the LM head as a partial sum under TP)."""
    input_ids, attention_mask = inputs
    cfg, params = bloom_params_from_hf(hf_model)
    ids, mask = jnp.asarray(input_ids), jnp.asarray(attention_mask)

    ref_grads = jax.grad(bloom.loss_fn)(params, ids, mask, ids, cfg)

    ctx = ParallelContext(tensor_parallel_size=2, data_parallel_size=2)
    try:
        specs = bloom.tp_specs(params)
        fn = shard_map(
            jax.grad(lambda p, i, m: bloom.loss_fn(p, i, m, i, cfg, tp_axis="tensor")),
            mesh=ctx.mesh,
            in_specs=(specs, P(), P()),
            out_specs=specs,
            check_vma=False,
        )
        tp_grads = fn(params, ids, mask)
        flat_ref = jax.tree_util.tree_leaves_with_path(ref_grads)
        flat_tp = jax.tree_util.tree_leaves(tp_grads)
        for (path, r), t in zip(flat_ref, flat_tp):
            np.testing.assert_allclose(
                np.asarray(t), np.asarray(r), rtol=5e-3, atol=1e-5,
                err_msg=str(path),
            )
    finally:
        ctx.destroy()


def test_pad_for_tp_odd_vocab(devices):
    """GPT-2-sized vocab (odd) under TP: pad_for_tp pads the embedding,
    CE masks padded slots, loss matches the unpadded single-device run."""
    cfg = bloom.BloomConfig(vocab_size=101, hidden_size=32, n_layer=2, n_head=4)
    params = bloom.init_params(cfg, jax.random.PRNGKey(0))
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 101, (2, 8)))
    ref = float(bloom.loss_fn(params, ids, None, ids, cfg))

    p2, cfg2 = bloom.pad_for_tp(params, cfg, 4)
    assert cfg2.vocab_size == 104 and cfg2.valid_vocab_size == 101
    # single-device padded loss equals unpadded (padded slots masked)
    same = float(bloom.loss_fn(p2, ids, None, ids, cfg2))
    assert abs(same - ref) < 1e-5

    ctx = ParallelContext(tensor_parallel_size=4, data_parallel_size=2)
    try:
        specs = bloom.tp_specs(p2)
        fn = jax.jit(
            shard_map(
                lambda p, i: bloom.loss_fn(p, i, None, i, cfg2, tp_axis="tensor"),
                mesh=ctx.mesh,
                in_specs=(specs, P()),
                out_specs=P(),
                check_vma=False,
            )
        )
        out = float(fn(p2, ids))
        assert abs(out - ref) < 2e-4, (out, ref)
    finally:
        ctx.destroy()


# -- the fused projection's columns, by kind under a 128-lane head ----------

def _interleaved_qkv(blk, x, config, tp_axis, overlap=False):
    """The split as it stood before the regroup (HF's ``[head][q|k|v]
    [head_dim]`` columns taken apart as a five-dimensional view): the
    reference every case below holds ``bloom._project_qkv`` to."""
    from pipegoose_tpu.nn.tensor_parallel.layers import column_parallel_linear

    tp = jax.lax.axis_size(tp_axis) if tp_axis else 1
    fused = column_parallel_linear(blk["qkv"], x, tp_axis, overlap=overlap)
    b, s = fused.shape[:2]
    fused = fused.reshape(b, s, config.n_head // tp, 3, config.head_dim)
    return fused[..., 0, :], fused[..., 1, :], fused[..., 2, :]


def _attention_case(head_dim, use_flash, with_bias, n_head=2, seq=128):
    cfg = bloom.BloomConfig(vocab_size=64, hidden_size=n_head * head_dim,
                            n_layer=1, n_head=n_head, use_flash=use_flash)
    params = bloom.init_params(cfg, jax.random.PRNGKey(0))
    blk = jax.tree_util.tree_map(lambda a: a[0], params["blocks"])["attn"]
    # init_params gives zero biases: make the bias carry something
    blk["qkv"]["bias"] = 0.1 * jax.random.normal(
        jax.random.PRNGKey(1), blk["qkv"]["bias"].shape)
    if not with_bias:
        del blk["qkv"]["bias"]
    x = jax.random.normal(jax.random.PRNGKey(2), (2, seq, cfg.hidden_size))
    mask = jnp.ones((2, seq), jnp.int32).at[1, seq - 5:].set(0)
    return cfg, blk, x, bloom.attention_bias(mask, cfg)


@pytest.mark.parametrize("with_bias", [True, False], ids=["bias", "nobias"])
@pytest.mark.parametrize("use_flash", [False, True], ids=["xla", "flash"])
@pytest.mark.parametrize("head_dim", [32, 64, 128])
def test_projection_by_kind_matches_the_interleaved_split(
        monkeypatch, head_dim, use_flash, with_bias):
    """Under a 128-lane head the projection's columns are regrouped by
    kind in the graph; every output column is the same dot product, so
    the attention's result equals the interleaved split's to the bit in
    float32, and the gradients with respect to the STORED kernel and
    bias (HF's order) and to ``x`` within round-off. At 128 the split
    stays and the two are the same program."""
    cfg, blk, x, bias = _attention_case(head_dim, use_flash, with_bias)

    def attend(blk, x):
        return bloom._attention(blk, x, bias, cfg, None)

    def loss(blk, x):
        return (attend(blk, x) * jnp.cos(jnp.arange(x.shape[-1]))).sum()

    got, got_grads = attend(blk, x), jax.grad(loss, argnums=(0, 1))(blk, x)
    monkeypatch.setattr(bloom, "_project_qkv", _interleaved_qkv)
    want, want_grads = attend(blk, x), jax.grad(loss, argnums=(0, 1))(blk, x)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert jax.tree_util.tree_structure(got_grads) \
        == jax.tree_util.tree_structure(want_grads)
    for g, w in zip(jax.tree_util.tree_leaves(got_grads),
                    jax.tree_util.tree_leaves(want_grads)):
        assert g.shape == w.shape
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-5, atol=2e-5)
    # the stored tree is HF's whatever the path: (H, 3H) and (3H,)
    assert got_grads[0]["qkv"]["kernel"].shape \
        == (cfg.hidden_size, 3 * cfg.hidden_size)


def test_at_a_whole_tile_head_the_lowered_attention_is_the_interleaved_split(
        monkeypatch):
    """``head_dim`` 128 (bloom-1b7): the condition is off and the lowered
    text of ``_attention`` and of its gradient is the interleaved
    split's, line for line."""
    cfg, blk, x, bias = _attention_case(128, False, True)

    def lowered():
        fn = jax.grad(lambda blk, x: bloom._attention(
            blk, x, bias, cfg, None).sum(), argnums=(0, 1))
        return jax.jit(fn).lower(blk, x).as_text()

    ours = lowered()
    monkeypatch.setattr(bloom, "_project_qkv", _interleaved_qkv)
    assert ours == lowered()
    assert "x3x128x" in ours  # the five-dimensional view


def test_quantized_projection_is_regrouped_with_its_scales():
    """A quantized leaf's ``q`` and per-column ``scale`` carry the
    columns in their last dimension as the kernel and the bias do:
    ``_columns_by_kind`` permutes every leaf alike, int8 (a scale a
    column) and int4 (a scale a group a column), and the regrouped
    projection's columns are the interleaved one's, moved."""
    from pipegoose_tpu.nn.tensor_parallel.layers import column_parallel_linear
    from pipegoose_tpu.quant.weights import QuantSpec, quantize_params

    nh, hd = 2, 64
    kernel = jax.random.normal(jax.random.PRNGKey(0), (nh * hd, 3 * nh * hd))
    bias = jax.random.normal(jax.random.PRNGKey(1), (3 * nh * hd,))
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 8, nh * hd))
    for spec in (QuantSpec("int8"), QuantSpec("int4", 32)):
        leaf = quantize_params(
            {"blocks": {"attn": {"qkv": {"kernel": kernel, "bias": bias}}}},
            spec)["blocks"]["attn"]["qkv"]
        assert "q" in leaf and "kernel" not in leaf
        regrouped = bloom._columns_by_kind(leaf, nh, hd)
        assert jax.tree_util.tree_map(jnp.shape, regrouped) \
            == jax.tree_util.tree_map(jnp.shape, leaf)
        want = column_parallel_linear(leaf, x, None) \
            .reshape(2, 8, nh, 3, hd).swapaxes(-3, -2).reshape(2, 8, -1)
        got = column_parallel_linear(regrouped, x, None)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_tp2_shard_regroups_its_own_heads(devices, monkeypatch):
    """Under ``tp_axis`` a shard holds whole heads and regroups its own
    ``3 * local_heads * head_dim`` columns: the sharded attention equals
    the unsharded one, and the interleaved split's under the same
    mesh."""
    cfg, blk, x, bias = _attention_case(64, False, True, n_head=4, seq=16)
    ref = bloom._attention(blk, x, bias, cfg, None)
    ctx = ParallelContext(tensor_parallel_size=2, data_parallel_size=4)
    try:
        specs = bloom.tp_specs({"blocks": {"attn": jax.tree_util.tree_map(
            lambda a: a[None], blk)}})["blocks"]["attn"]
        specs = jax.tree_util.tree_map(
            lambda s: P(*s[1:]), specs, is_leaf=lambda s: isinstance(s, P))

        def sharded():
            return shard_map(
                lambda blk, x: bloom._attention(blk, x, bias, cfg, "tensor"),
                mesh=ctx.mesh, in_specs=(specs, P()), out_specs=P(),
                check_vma=False)(blk, x)

        got = sharded()
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)
        monkeypatch.setattr(bloom, "_project_qkv", _interleaved_qkv)
        np.testing.assert_allclose(np.asarray(got), np.asarray(sharded()),
                                   rtol=1e-5, atol=1e-6)
    finally:
        ctx.destroy()


@pytest.mark.parametrize("head_dim", [32, 64, 128])
def test_stored_parameters_keep_hfs_layout(head_dim):
    """What is stored, checkpointed and sharded is HF's ``[head][q|k|v]
    [head_dim]`` at every width: the regroup lives in the graph. The
    tree's shapes, its gradient's, and the HF state dict's fused weight
    (the stored kernel transposed, untouched)."""
    cfg = bloom.BloomConfig(vocab_size=64, hidden_size=2 * head_dim,
                            n_layer=2, n_head=2)
    params = bloom.init_params(cfg, jax.random.PRNGKey(0))
    h = cfg.hidden_size
    assert params["blocks"]["attn"]["qkv"]["kernel"].shape == (2, h, 3 * h)
    assert params["blocks"]["attn"]["qkv"]["bias"].shape == (2, 3 * h)
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 64, (2, 8)))
    grads = jax.grad(bloom.loss_fn)(params, ids, None, ids, cfg)
    assert jax.tree_util.tree_map(jnp.shape, grads) \
        == jax.tree_util.tree_map(jnp.shape, params)
    sd = bloom_params_to_hf_state_dict(params)
    np.testing.assert_array_equal(
        sd["transformer.h.1.self_attention.query_key_value.weight"],
        np.asarray(params["blocks"]["attn"]["qkv"]["kernel"][1]).T)
    # a head's q, k, v rows as HF reads them: [head][q|k|v][head_dim]
    view = np.asarray(params["blocks"]["attn"]["qkv"]["kernel"][1]) \
        .reshape(h, 2, 3, head_dim)
    np.testing.assert_array_equal(
        sd["transformer.h.1.self_attention.query_key_value.weight"]
        .reshape(2, 3, head_dim, h)[1, 2], view[:, 1, 2, :].T)


@pytest.mark.parametrize("head_dim,by_kind", [(64, True), (128, False)])
def test_projections_by_kind_are_counted_when_traced(head_dim, by_kind):
    """``bloom.qkv_projections`` counts every traced ``_attention``,
    ``bloom.qkv_by_kind`` those that regrouped: equal under a 128-lane
    head, 0 of some at a whole-tile head. Remat and the gradient trace a
    block more than once, so the two are compared, not counted."""
    from pipegoose_tpu.telemetry.registry import get_registry

    cfg = bloom.BloomConfig(vocab_size=64, hidden_size=2 * head_dim,
                            n_layer=2, n_head=2, remat=True)
    params = bloom.init_params(cfg, jax.random.PRNGKey(0))
    ids = jnp.zeros((1, 8), jnp.int32)
    reg = get_registry()
    was = reg.enabled
    reg.enable()
    try:
        def counts():
            return (reg.counter("bloom.qkv_projections").value,
                    reg.counter("bloom.qkv_by_kind").value)

        start = counts()
        step = jax.jit(jax.grad(
            lambda p: bloom.loss_fn(p, ids, None, ids, cfg)))
        step(params)
        traced = counts()
        projections = traced[0] - start[0]
        assert projections > 0
        assert traced[1] - start[1] == (projections if by_kind else 0)
        step(params)  # compiled: nothing is traced
        assert counts() == traced
    finally:
        if not was:
            reg.disable()
