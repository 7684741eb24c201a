"""LongCat-Flash at a small size on the CPU against the benchmark's plain
reference (``benchmark/reference/longcat_flash_ref.py``), with the
published SHAPE of things: key width != value width, fewer rotary dims
than plain ones, more experts routed to than are held, zero-compute
experts among the picks, a bias that changes the selection. The full
forward; the absorbed form against the expanded one; the router; the
identity picks; the shares of an expert layer adding up to the uncut
layer; the block's order; and each of them failing under the mutation it
guards against."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import program_longcat_flash as adapter
from benchmark import weights_longcat_flash as weights
from benchmark.reference import longcat_flash_ref as ref
from pipegoose_tpu.models import longcat_flash as lc
from pipegoose_tpu.nn.expert_parallel.routers import SoftmaxTopKRouter

# the benchmark's configuration file, at toy widths
CONFIG = {
    "vocab_size": 96, "hidden_size": 64, "ffn_hidden_size": 96,
    "expert_ffn_hidden_size": 32, "num_layers": 2, "num_attention_heads": 4,
    "kv_lora_rank": 32, "q_lora_rank": 48, "qk_rope_head_dim": 8,
    "v_head_dim": 12, "qk_nope_head_dim": 16, "mla_scale_q_lora": True,
    "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
    "n_routed_experts": 2, "router_experts": 8, "experts_held": [0, 2],
    "zero_expert_num": 4, "zero_expert_type": "identity", "moe_topk": 3,
    "rms_norm_eps": 1e-5, "rope_theta": 10000000, "norm_topk_prob": False,
    "initializer_range": 0.1, "router_bias_std": 0.03, "dtype": "float32",
}
TOL = 3e-4            # float32 against float32, logits of order 1


def _model(dtype="float32", seed=1, **more):
    config = dict(CONFIG, dtype=dtype, **more)
    sizes = adapter.sizes(config)
    flat = weights.make(weights.seed_key(seed), sizes, jnp.dtype(dtype))
    return (config, sizes, flat, adapter.make_config(config),
            adapter.to_tree(flat, config))


def _w32(flat):
    return {k: v.astype(jnp.float32) for k, v in flat.items()}


def _ref_logits(flat, sizes, tokens, **kw):
    hid = ref.hidden(_w32(flat), jnp.asarray(tokens), sizes, **kw)
    return np.asarray(ref.logits(_w32(flat), hid))


def _tokens(n=45, seed=0):
    return np.random.RandomState(seed).randint(1, 96, (n,))


def test_full_forward_is_the_references():
    _, sizes, flat, cfg, params = _model()
    tokens = _tokens()
    got = np.asarray(lc.forward(params, jnp.asarray(tokens)[None], cfg))[0]
    want = _ref_logits(flat, sizes, tokens)
    assert np.abs(want).max() > 0.5
    np.testing.assert_allclose(got, want, atol=TOL)


def test_the_feed_forwards_in_blocks_give_the_whole_forward():
    _, _, _, cfg, params = _model()
    ids = jnp.asarray(_tokens(48))[None]
    whole = lc.forward(params, ids, cfg)
    cut = lc.forward(params, ids, dataclasses.replace(cfg,
                                                      ffn_block_tokens=16))
    np.testing.assert_allclose(np.asarray(cut), np.asarray(whole), atol=1e-5)


def test_the_flash_kernels_take_the_values_padded_to_the_keys_width():
    """Keys 24 lanes wide, values 12: through the flash kernels (the
    values padded, the padding dropped) what the dense scores give."""
    _, _, _, cfg, params = _model()
    ids = jnp.asarray(np.random.RandomState(2).randint(1, 96, (1, 256)))
    dense = lc.forward(params, ids, cfg)
    flash = lc.forward(params, ids, dataclasses.replace(cfg, use_flash=True))
    np.testing.assert_allclose(np.asarray(flash), np.asarray(dense),
                               atol=2e-5)


def test_a_lower_precision_fails_the_float32_tolerance():
    _, sizes, flat, _, _ = _model()
    _, _, flat16, cfg16, params16 = _model("bfloat16")
    tokens = _tokens()
    got = np.asarray(lc.forward(params16, jnp.asarray(tokens)[None], cfg16))
    want = _ref_logits(flat16, sizes, tokens)
    assert np.abs(got[0] - want).max() > 10 * TOL
    # and the reference's own control: fp8 moves the logits further still
    low = _ref_logits(flat, sizes, tokens, precision="fp8")
    assert np.abs(low - _ref_logits(flat, sizes, tokens)).max() > 100 * TOL


def test_absorbed_attention_is_the_expanded_one():
    """One attention over a whole sequence, both forms from one set of
    weights, to float32 rounding: the absorbed form reads the latent
    rows alone, as the decode step does."""
    _, _, _, cfg, params = _model()
    at = params["layers"][0]["half1"]["attn"]
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 37, 64), jnp.float32)
    pos = jnp.arange(37)[None]
    q, row = lc.project(at, x, pos, cfg)
    want = lc.attend_expanded(at, q, row, cfg)
    assert row.shape == (1, 37, 32 + 8)
    qa = lc.absorb_query(at, q, cfg)                         # (1, S, H, 40)
    scores = jnp.einsum("bqhr,bkr->bhqk", qa, row) * (16 + 8) ** -0.5
    keep = pos[0][None, :] <= pos[0][:, None]
    probs = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
    u = jnp.einsum("bhqk,bkr->bqhr", probs, row[..., :32])
    got = lc.attend_out(at, u.reshape(1, 37, -1), cfg)
    assert np.abs(np.asarray(want)).max() > 0.05
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_the_rotary_layouts_give_the_same_scores():
    """The program lays the pairs out as [evens | odds] (HF's), the
    reference turns them in place: one permutation of q and k alike."""
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 9, 3, 8))
    y = jax.random.normal(jax.random.PRNGKey(1), (1, 9, 1, 8))
    pos = jnp.arange(9)[None]
    a = jnp.einsum("bqhd,bkd->bhqk", lc._rope(x, pos, 1e7),
                   lc._rope(y, pos, 1e7)[:, :, 0])
    b = jnp.einsum("qhd,kd->hqk", ref._rope(x[0], 1e7),
                   ref._rope(y[0], 1e7)[:, 0])
    np.testing.assert_allclose(np.asarray(a[0]), np.asarray(b), atol=1e-5)


# -- the router and the zero-compute experts ---------------------------------

def _router_case():
    _, sizes, flat, cfg, params = _model()
    x = jax.random.normal(jax.random.PRNGKey(5), (64, 64), jnp.float32)
    return sizes, flat, cfg, params["layers"][0], x


def test_router_selects_on_the_biased_score_and_weighs_by_the_plain_one():
    sizes, flat, cfg, blk, x = _router_case()
    routing = cfg.router()(blk["router"], x)
    z = jax.nn.softmax(x @ blk["router"]["gate"]["kernel"], axis=-1)
    bias = blk["router"]["bias"]
    assert routing.experts.shape == (64, 3) and z.shape == (64, 12)
    _, want = jax.lax.top_k(z + bias, 3)
    np.testing.assert_array_equal(np.asarray(routing.experts),
                                  np.asarray(want))
    # x 6, from z and not from z + b, not renormalised
    w = np.asarray(jnp.take_along_axis(z, want, axis=-1)) * 6
    np.testing.assert_allclose(np.asarray(routing.weights), w, rtol=1e-6)
    assert np.abs(np.asarray(routing.weights).sum(-1) - 1).max() > 0.05
    # the bias changes at least one pick, and some picks cost nothing
    _, plain = jax.lax.top_k(z, 3)
    assert (np.sort(np.asarray(plain)) != np.sort(np.asarray(want))).any()
    assert (np.asarray(want) >= 8).any()
    # the reference's dense weights say the same
    dense = np.asarray(ref.routing_weights(x, _w32(flat), 0, sizes))
    got = np.zeros_like(dense)
    np.put_along_axis(got, np.asarray(routing.experts),
                      np.asarray(routing.weights), axis=-1)
    np.testing.assert_allclose(got, dense, atol=1e-5)


@pytest.mark.parametrize("mutation", ["no_bias", "biased_weights",
                                      "renormalised", "unscaled"])
def test_a_mutated_router_is_not_the_references(mutation):
    sizes, flat, cfg, blk, x = _router_case()
    dense = np.asarray(ref.routing_weights(x, _w32(flat), 0, sizes))
    z = jax.nn.softmax(x @ blk["router"]["gate"]["kernel"], axis=-1)
    bias = blk["router"]["bias"]
    if mutation == "no_bias":
        routing = cfg.router()(dict(blk["router"], bias=0 * bias), x)
    elif mutation == "biased_weights":
        _, e = jax.lax.top_k(z + bias, 3)
        routing = cfg.router()(blk["router"], x)._replace(
            weights=6 * jnp.take_along_axis(z + bias, e, axis=-1))
    elif mutation == "renormalised":
        routing = SoftmaxTopKRouter(12, 3, scaling=6.0, normalize=True)(
            blk["router"], x)
    else:
        routing = SoftmaxTopKRouter(12, 3)(blk["router"], x)
    got = np.zeros_like(dense)
    np.put_along_axis(got, np.asarray(routing.experts),
                      np.asarray(routing.weights), axis=-1)
    assert np.abs(got - dense).max() > 1e-3


def test_identity_picks_add_their_summed_weight_times_the_token():
    sizes, flat, cfg, blk, x = _router_case()
    routed, zero, counters = lc.moe_parts(blk, x, cfg)
    routing = cfg.router()(blk["router"], x)
    w = np.where(np.asarray(routing.experts) >= 8,
                 np.asarray(routing.weights), 0.0).sum(-1)
    assert (w > 0).sum() > 10
    np.testing.assert_allclose(np.asarray(zero), w[:, None] * np.asarray(x),
                               atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(zero), np.asarray(ref.moe_identity(x, _w32(flat), 0,
                                                      sizes)), atol=1e-5)
    assert int(counters["zero_picks"]) == int(
        (np.asarray(routing.experts) >= 8).sum())
    assert int(counters["picks"]) == 64 * 3
    # rows that are not live go nowhere and add nothing
    live = jnp.arange(64) < 40
    routed2, zero2, c2 = lc.moe_parts(blk, x, cfg, live)
    assert int(c2["picks"]) == 40 * 3
    assert not np.asarray(zero2)[40:].any()
    assert not np.asarray(routed2)[40:].any()
    np.testing.assert_allclose(np.asarray(zero2)[:40], np.asarray(zero)[:40])


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """Four shares of two experts: their routed parts, the identity part
    ONCE and the dense path once are the uncut reference's whole layer
    (all 8 routed experts in one tree)."""
    whole_cfg = dict(CONFIG, n_routed_experts=8, experts_held=[0, 8])
    sizes = adapter.sizes(whole_cfg)
    flat = weights.make(weights.seed_key(7), sizes, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(9), (50, 64), jnp.float32)
    want = np.asarray(ref.moe(x, _w32(flat), 1, sizes))
    total = np.zeros_like(want)
    for k in range(4):
        held = (2 * k, 2)
        share = dict(flat)
        for name in ("gate", "up", "down"):
            share[f"l1_ex_{name}"] = flat[f"l1_ex_{name}"][2 * k:2 * k + 2]
        config = dict(whole_cfg, n_routed_experts=2, experts_held=list(held))
        cfg = adapter.make_config(config)
        blk = adapter.to_tree(share, config)["layers"][1]
        routed, zero, counters = lc.moe_parts(blk, x, cfg)
        # the share's routed part is the reference's for the same experts
        np.testing.assert_allclose(
            np.asarray(routed), np.asarray(ref.moe_routed(
                x, _w32(share), 1, sizes, held=held)), atol=TOL)
        total += np.asarray(routed)
    total += np.asarray(zero)                  # the identity part: once
    assert np.abs(np.asarray(zero)).max() > 0.05
    np.testing.assert_allclose(total, want, atol=TOL)
    # counted on every share it would be three times too much
    assert np.abs(total + 3 * np.asarray(zero) - want).max() > 0.05


# -- the block's order ---------------------------------------------------------

def _block_by_halves(blk, h, cfg, order="published"):
    """One block over a whole sequence through the functions the paged
    programs run it by; ``order`` permutes where the shortcut leaves or
    lands."""
    pos = jnp.arange(h.shape[1])[None]
    eps = cfg.rms_norm_eps
    h0, h1 = blk["half0"], blk["half1"]

    def attn(half, x):
        q, row = lc.project(half["attn"], lc.rms_norm(half["ln_in"], x, eps),
                            pos, cfg)
        return lc.attend_expanded(half["attn"], q, row, cfg)

    b0, s, _ = lc.first_half(blk, h, attn(h0, h), cfg)
    if order == "published":
        return lc.second_half(blk, b0, s, attn(h1, b0), cfg)
    if order == "lands_before_second_attention":
        return lc.second_half(blk, b0 + s, 0 * s, attn(h1, b0 + s), cfg)
    # leaves from the second half's normed input
    a1 = b0 + attn(h1, b0)
    late, _ = lc.moe(blk, lc.rms_norm(h1["ln_post"], a1, eps), cfg)
    return lc.second_half(blk, b0, late, attn(h1, b0), cfg)


@pytest.mark.parametrize("order", ["published",
                                   "lands_before_second_attention",
                                   "leaves_after_second_attention"])
def test_the_shortcut_leaves_and_lands_where_the_reference_has_it(order):
    _, sizes, flat, cfg, params = _model()
    h = 0.5 * jax.random.normal(jax.random.PRNGKey(11), (1, 30, 64))
    want = np.asarray(ref.block(h[0], _w32(flat), 1, sizes))
    got = np.asarray(_block_by_halves(params["layers"][1], h, cfg, order))[0]
    if order == "published":
        np.testing.assert_allclose(got, want, atol=TOL)
    else:
        assert np.abs(got - want).max() > 0.02
