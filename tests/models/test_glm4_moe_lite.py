"""GLM-4.7-Flash (``models/glm4_moe_lite.py``) at a tiny size on the
CPU, seeded weights, against the benchmark's plain reference
(``benchmark/reference/glm4_moe_lite_ref.py``), which shares no code
with it: loss and every leaf's gradient, the share test, MTP's
targets, the tensor-parallel form."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import program_glm4_moe_lite as adapter
from benchmark import weights_glm4_moe_lite as weights
from benchmark.reference import glm4_moe_lite_ref as ref
from pipegoose_tpu.models import glm4_moe_lite as glm

B, S, VOCAB = 3, 24, 90

# a configuration file's content at toy widths; 16 experts of which this
# share holds 4..7; the 90 rows of the vocabulary padded to 96
TINY = {
    "hidden_size": 32, "intermediate_size": 64, "moe_intermediate_size": 24,
    "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "n_routed_experts": 4, "router_experts": 16, "experts_held": [4, 4],
    "n_shared_experts": 1, "num_experts_per_tok": 4,
    "routed_scaling_factor": 1.8, "norm_topk_prob": True, "n_group": 1,
    "topk_group": 1, "num_nextn_predict_layers": 1, "q_lora_rank": 24,
    "kv_lora_rank": 16, "qk_nope_head_dim": 12, "qk_rope_head_dim": 4,
    "v_head_dim": 16, "rope_theta": 10000, "rms_norm_eps": 1e-5,
    "tie_word_embeddings": False, "vocab_size": VOCAB, "vocab_pad_to": 32,
    "mtp_loss_weight": 0.3, "initializer_range": 0.3, "dtype": "float32",
}


def _setup(options, config=TINY, seed=5):
    sizes = adapter.sizes(config)
    cfg = adapter.make_config(config, options)
    flat = weights.make(weights.seed_key(seed), sizes, jnp.float32)
    ids = jnp.asarray(np.random.RandomState(seed).randint(
        0, VOCAB, (B, S)).astype(np.int32))
    return sizes, cfg, flat, adapter.to_tree(flat, config), ids


@pytest.mark.parametrize("options", [
    {"remat": True, "use_flash": False, "fused_ce": False},
    {"remat": False, "use_flash": True, "fused_ce": True},
], ids=["dense", "flash+fused_ce"])
def test_loss_and_every_leafs_gradient_agree_with_the_reference(options):
    sizes, cfg, flat, tree, ids = _setup(options)
    (loss, counters), grads = jax.jit(jax.value_and_grad(
        lambda p: glm.loss_and_counters(p, ids, None, ids, cfg),
        has_aux=True))(tree)

    def rows(f):
        main, mtp = jax.lax.map(lambda r: ref.row_losses(f, r, sizes), ids)
        both = main + sizes["mtp_loss_weight"] * mtp
        return both.mean(), (main.mean(), mtp.mean())

    (want, (main, mtp)), want_grads = jax.jit(
        jax.value_and_grad(rows, has_aux=True))(flat)
    assert abs(float(loss) - float(want)) < 2e-5 * float(want)
    got = adapter.from_tree(grads, TINY)
    assert set(got) == set(want_grads)
    for name, g in want_grads.items():
        scale = float(jnp.abs(g).max()) + 1e-12
        assert float(jnp.abs(got[name] - g).max()) < 2e-4 * scale + 1e-7, name
    # the two terms apart, as the reference has them
    np.testing.assert_allclose(float(counters["loss_main"]), float(main),
                               rtol=2e-5)
    np.testing.assert_allclose(float(counters["loss_mtp"]), float(mtp),
                               rtol=2e-5)
    # the selection bias: in the program's tree, random, no gradient
    assert float(jnp.abs(tree["blocks"]["router"]["bias"]).max()) > 0
    assert float(jnp.abs(got["moe_router_b"]).max()) == 0.0


def test_counters_count_the_picks_on_the_held_experts():
    sizes, cfg, flat, tree, ids = _setup({})
    _, counters = jax.jit(
        lambda p: glm.loss_and_counters(p, ids, None, ids, cfg))(tree)
    rows = np.asarray(counters["rows_per_expert"])
    assert rows.shape == (3, 4)          # two stacked layers + MTP, 4 held
    share = rows.sum() / (3 * B * S * 4)
    np.testing.assert_allclose(float(counters["local_pick_share"]), share,
                               rtol=1e-6)
    assert 0.0 < share < 1.0


def test_the_shares_add_up_to_the_uncut_layer():
    """16 experts as 4 shares of 4: the four shares' routed parts, with
    the shared expert counted once, are what the uncut reference gives
    for the whole layer."""
    config = dict(TINY, experts_held=[0, 16], n_routed_experts=16)
    whole = adapter.sizes(config)
    flat = weights.make(weights.seed_key(3), whole, jnp.float32)
    layer = {k[len("mtp_"):]: v for k, v in flat.items()
             if k.startswith("mtp_") and k[len("mtp_"):] in ref.ATTN + ref.MOE}
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, 32))
    want = jax.jit(lambda x: jax.lax.map(
        lambda row: ref.moe(row, layer, whole), x))(x)
    blk = adapter.to_tree(flat, config)["mtp"]["block"]
    shared = glm._swiglu(blk["shared"], x, None)
    total = shared
    for first in (0, 4, 8, 12):
        cfg = adapter.make_config(
            dict(config, experts_held=[first, 4], n_routed_experts=4), {})
        part = dict(blk, experts=jax.tree_util.tree_map(
            lambda a: a[first:first + 4], blk["experts"]))
        y, rows = jax.jit(lambda p, x: glm.moe(p, x, cfg))(part, x)
        total = total + (y - shared)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=1e-4 * float(jnp.abs(want).max()))


def test_nearly_every_token_on_one_held_expert_is_the_references_answer():
    """No token is dropped at any load: a router bias that sends every
    token to expert 5 (held) first, and the layer still gives what the
    reference gives."""
    sizes = adapter.sizes(TINY)
    cfg = adapter.make_config(TINY, {})
    flat = weights.make(weights.seed_key(9), sizes, jnp.float32)
    flat["mtp_router_b"] = jnp.zeros(16).at[5].set(10.0)
    layer = {k[len("mtp_"):]: v for k, v in flat.items()
             if k.startswith("mtp_") and k[len("mtp_"):] in ref.ATTN + ref.MOE}
    blk = adapter.to_tree(flat, TINY)["mtp"]["block"]
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 16, 32))
    y, rows = jax.jit(lambda p, x: glm.moe(p, x, cfg))(blk, x)
    assert int(rows[1]) == 32            # expert 5 is the share's second
    want = jax.jit(lambda x: jax.lax.map(
        lambda row: ref.moe(row, layer, sizes), x))(x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               atol=1e-4 * float(jnp.abs(want).max()))


def test_mtp_targets_are_two_ahead_and_the_last_two_positions_masked():
    sizes, cfg, flat, tree, ids = _setup({})
    x, shared, _ = glm._trunk(tree, ids, None, cfg, None)
    hidden, _ = glm._mtp_hidden(tree, x, ids, shared, cfg, None)
    logits = glm.logits_fn(tree, hidden, cfg)[..., :VOCAB]
    logp = jax.nn.log_softmax(logits, axis=-1)
    want = -jnp.take_along_axis(
        logp[:, :S - 2], ids[:, 2:, None], axis=-1).mean()
    _, counters = glm.loss_and_counters(tree, ids, None, ids, cfg)
    np.testing.assert_allclose(float(counters["loss_mtp"]), float(want),
                               rtol=1e-5)
    # what the last two positions hold cannot reach the loss
    bent = hidden.at[:, S - 2:].set(1e3)
    tot, cnt = glm._ce_sums(
        tree, bent, jnp.roll(ids, -2, axis=1),
        jnp.broadcast_to((jnp.arange(S) < S - 2).astype(jnp.float32), (B, S)),
        cfg, None)
    np.testing.assert_allclose(float(tot / cnt), float(want), rtol=1e-5)


def test_tensor_2_x_data_2_trains_as_one_device_does(devices):
    import optax

    from pipegoose_tpu import ParallelContext
    from pipegoose_tpu.optim.zero import DistributedOptimizer
    from pipegoose_tpu.trainer import Trainer

    sizes, cfg, flat, tree, _ = _setup({"remat": True})
    batches = [np.random.RandomState(i).randint(0, VOCAB, (4, 16)).astype(
        np.int32) for i in range(3)]
    losses = {}
    for tp, dp in ((1, 1), (2, 2)):
        ctx = ParallelContext(tensor_parallel_size=tp, data_parallel_size=dp,
                              devices=devices[:tp * dp])
        try:
            trainer = Trainer(
                params=tree, param_specs=glm.tp_specs(tree),
                optimizer=DistributedOptimizer(optax.adam(1e-2),
                                               axis_name="data"),
                parallel_context=ctx,
                **adapter.trainer_kwargs(cfg, tree))
            trainer.fit(batches)
            losses[tp] = [float(x) for x in trainer.state.losses]
        finally:
            ctx.destroy()
    np.testing.assert_allclose(losses[2], losses[1], rtol=2e-5)
