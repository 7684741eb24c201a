"""TP-layer correctness vs dense single-device reference — the analog of
the reference's tests/nn/tensor_parallel/test_parallelizer.py and
test_loss.py pattern: compute unsharded reference values, assert the
sharded run matches (SURVEY.md §4)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from pipegoose_tpu.distributed import ParallelContext
from pipegoose_tpu.nn.tensor_parallel import (
    column_parallel_linear,
    layer_norm,
    row_parallel_linear,
    vocab_parallel_cross_entropy,
    vocab_parallel_embedding,
)

from pipegoose_tpu.distributed.compat import shard_map

TP = 4


@pytest.fixture()
def ctx(devices):
    c = ParallelContext(tensor_parallel_size=TP, data_parallel_size=2)
    yield c
    c.destroy()


def test_column_parallel_linear(ctx):
    key = jax.random.PRNGKey(0)
    k1, k2, k3 = jax.random.split(key, 3)
    x = jax.random.normal(k1, (2, 6, 16))
    kernel = jax.random.normal(k2, (16, 32)) * 0.1
    bias = jax.random.normal(k3, (32,))
    ref = x @ kernel + bias

    fn = shard_map(
        lambda p, v: column_parallel_linear(p, v, "tensor", gather_output=True),
        mesh=ctx.mesh,
        in_specs=({"kernel": P(None, "tensor"), "bias": P("tensor")}, P()),
        out_specs=P(),
        check_vma=False,
    )
    out = fn({"kernel": kernel, "bias": bias}, x)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


def test_row_parallel_linear(ctx):
    key = jax.random.PRNGKey(1)
    k1, k2, k3 = jax.random.split(key, 3)
    x = jax.random.normal(k1, (2, 6, 32))
    kernel = jax.random.normal(k2, (32, 16)) * 0.1
    bias = jax.random.normal(k3, (16,))
    ref = x @ kernel + bias

    fn = shard_map(
        lambda p, v: row_parallel_linear(p, v, "tensor", input_is_parallel=False),
        mesh=ctx.mesh,
        in_specs=({"kernel": P("tensor", None), "bias": P()}, P()),
        out_specs=P(),
        check_vma=False,
    )
    out = fn({"kernel": kernel, "bias": bias}, x)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


def test_column_row_composition(ctx):
    """Column (no gather) -> Row (input_is_parallel): the Megatron MLP
    pattern — one all-reduce total, intermediate stays sharded."""
    key = jax.random.PRNGKey(2)
    k1, k2, k3 = jax.random.split(key, 3)
    x = jax.random.normal(k1, (4, 16))
    w1 = jax.random.normal(k2, (16, 64)) * 0.1
    w2 = jax.random.normal(k3, (64, 16)) * 0.1
    ref = jnp.maximum(x @ w1, 0) @ w2

    def mlp(p, v):
        h = column_parallel_linear({"kernel": p["w1"]}, v, "tensor")
        h = jnp.maximum(h, 0)
        return row_parallel_linear({"kernel": p["w2"]}, h, "tensor")

    fn = shard_map(
        mlp,
        mesh=ctx.mesh,
        in_specs=({"w1": P(None, "tensor"), "w2": P("tensor", None)}, P()),
        out_specs=P(),
        check_vma=False,
    )
    np.testing.assert_allclose(fn({"w1": w1, "w2": w2}, x), ref, rtol=1e-5, atol=1e-5)


def test_vocab_parallel_embedding(ctx):
    vocab, emb = 64, 16
    key = jax.random.PRNGKey(3)
    weight = jax.random.normal(key, (vocab, emb))
    ids = jax.random.randint(jax.random.PRNGKey(4), (2, 10), 0, vocab)
    ref = jnp.take(weight, ids, axis=0)

    fn = shard_map(
        lambda p, i: vocab_parallel_embedding(p, i, "tensor"),
        mesh=ctx.mesh,
        in_specs=({"weight": P("tensor", None)}, P()),
        out_specs=P(),
        check_vma=False,
    )
    np.testing.assert_allclose(fn({"weight": weight}, ids), ref, rtol=1e-6)


def test_layer_norm():
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 8, 16))
    params = {"scale": jnp.ones(16) * 1.5, "bias": jnp.full(16, 0.25)}
    out = layer_norm(params, x)
    mean = x.mean(-1, keepdims=True)
    var = x.var(-1, keepdims=True)
    ref = (x - mean) / jnp.sqrt(var + 1e-5) * 1.5 + 0.25
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-5)


def test_vocab_parallel_cross_entropy(ctx):
    vocab, bs, seq = 64, 2, 6
    logits = jax.random.normal(jax.random.PRNGKey(6), (bs, seq, vocab)) * 3
    targets = jax.random.randint(jax.random.PRNGKey(7), (bs, seq), 0, vocab)
    ref = vocab_parallel_cross_entropy(logits, targets, None)

    fn = shard_map(
        lambda l, t: vocab_parallel_cross_entropy(l, t, "tensor"),
        mesh=ctx.mesh,
        in_specs=(P(None, None, "tensor"), P()),
        out_specs=P(),
        check_vma=False,
    )
    np.testing.assert_allclose(fn(logits, targets), ref, rtol=1e-5, atol=1e-5)


def test_vocab_parallel_cross_entropy_grad(ctx):
    """Gradient equals softmax - one_hot, matching the reference's
    hand-derived backward (loss.py:71-89) computed here by autodiff."""
    vocab, bs = 16, 4
    logits = jax.random.normal(jax.random.PRNGKey(8), (bs, vocab)) * 2
    targets = jax.random.randint(jax.random.PRNGKey(9), (bs,), 0, vocab)

    def mean_loss_sharded(l, t):
        return vocab_parallel_cross_entropy(l, t, "tensor").mean()

    # reference grad: (softmax - onehot)/bs
    ref_grad = (jax.nn.softmax(logits) - jax.nn.one_hot(targets, vocab)) / bs

    fn = shard_map(
        jax.grad(mean_loss_sharded),
        mesh=ctx.mesh,
        in_specs=(P(None, "tensor"), P()),
        out_specs=P(None, "tensor"),
        check_vma=False,
    )
    np.testing.assert_allclose(fn(logits, targets), ref_grad, rtol=1e-4, atol=1e-5)


def test_vocab_parallel_embedding_grad(ctx):
    """Weight grads must match the dense reference exactly — a plain psum
    combine would scale them by the TP degree (regression for the
    psum-transpose hazard)."""
    vocab, emb = 32, 8
    weight = jax.random.normal(jax.random.PRNGKey(10), (vocab, emb))
    ids = jax.random.randint(jax.random.PRNGKey(11), (4, 5), 0, vocab)

    def dense_loss(w):
        return (jnp.take(w, ids, axis=0) ** 2).sum()

    ref_grad = jax.grad(dense_loss)(weight)

    def sharded_loss(p):
        out = vocab_parallel_embedding(p, ids, "tensor")
        return (out**2).sum()

    fn = shard_map(
        jax.grad(sharded_loss),
        mesh=ctx.mesh,
        in_specs=({"weight": P("tensor", None)},),
        out_specs={"weight": P("tensor", None)},
        check_vma=False,
    )
    g = fn({"weight": weight})["weight"]
    np.testing.assert_allclose(g, ref_grad, rtol=1e-5, atol=1e-6)


def test_padded_vocab_ce_matches_unpadded(ctx):
    """pad_vocab + valid_size masking: loss over a padded vocab equals the
    unpadded loss (padded slots excluded from the log-sum-exp)."""
    vocab, padded = 60, 64
    logits = jax.random.normal(jax.random.PRNGKey(12), (4, vocab))
    targets = jax.random.randint(jax.random.PRNGKey(13), (4,), 0, vocab)
    ref = vocab_parallel_cross_entropy(logits, targets, None)

    padded_logits = jnp.pad(logits, ((0, 0), (0, padded - vocab)))
    fn = shard_map(
        lambda l, t: vocab_parallel_cross_entropy(l, t, "tensor", valid_size=vocab),
        mesh=ctx.mesh,
        in_specs=(P(None, "tensor"), P()),
        out_specs=P(),
        check_vma=False,
    )
    np.testing.assert_allclose(fn(padded_logits, targets), ref, rtol=1e-5, atol=1e-6)


def test_chunked_ce_matches_plain(ctx):
    """chunked_ce_sums == full-logits CE (loss AND grads), single-device
    and under TP, with a ragged mask and a chunk-count that doesn't
    divide the sequence (pad path). The chunking bounds the logits
    working set to 1/n_chunks (the 8 GB fp32 buffer at bloom-560m's
    b8 x s1024)."""
    import dataclasses

    from pipegoose_tpu.models import bloom

    cfg = bloom.BloomConfig(vocab_size=128, hidden_size=64, n_layer=2, n_head=4)
    params = bloom.init_params(cfg, jax.random.PRNGKey(0))
    ids = jnp.asarray(np.random.RandomState(1).randint(0, 128, (2, 13)))
    mask = np.ones((2, 13), np.int32)
    mask[0, -4:] = 0
    mask = jnp.asarray(mask)

    ref_l, ref_g = jax.value_and_grad(bloom.loss_fn)(params, ids, mask, ids, cfg)
    cfg_c = dataclasses.replace(cfg, ce_chunks=4)  # 12 % 4 == 0, but 13-1... pad exercised with 5
    got_l, got_g = jax.value_and_grad(bloom.loss_fn)(params, ids, mask, ids, cfg_c)
    assert abs(float(ref_l) - float(got_l)) < 1e-5
    for (p, r), g in zip(
        jax.tree_util.tree_leaves_with_path(ref_g),
        jax.tree_util.tree_leaves(got_g),
    ):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(r), rtol=1e-4, atol=1e-6, err_msg=str(p)
        )

    # pad path: 5 chunks over 12 shifted tokens
    cfg_p = dataclasses.replace(cfg, ce_chunks=5)
    pad_l = float(bloom.loss_fn(params, ids, mask, ids, cfg_p))
    assert abs(float(ref_l) - pad_l) < 1e-5

    # TP: vocab-parallel CE inside the chunk scan
    specs = bloom.tp_specs(params)
    fn = jax.jit(
        shard_map(
            lambda p, i, m: bloom.loss_fn(p, i, m, i, cfg_c, tp_axis="tensor"),
            mesh=ctx.mesh,
            in_specs=(specs, P(), P()),
            out_specs=P(),
            check_vma=False,
        )
    )
    tp_l = float(fn(params, ids, mask))
    assert abs(tp_l - float(ref_l)) < 2e-4, (tp_l, float(ref_l))
