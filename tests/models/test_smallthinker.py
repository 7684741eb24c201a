"""SmallThinker at a small size on the CPU against the benchmark's plain
reference (``benchmark/reference/smallthinker_ref.py``): the full
forward; what makes the block its own (global layers without any
position encoding, a router that reads the attention's input, ReGLU
experts, weights a softmax over the chosen logits); every expert held,
so no pick is absent."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import program_smallthinker, weights_smallthinker
from benchmark.reference import smallthinker_ref
from pipegoose_tpu.models import smallthinker
from pipegoose_tpu.nn.expert_parallel.experts import (
    grouped_experts,
    reglu_grouped,
    swiglu_grouped,
)
from pipegoose_tpu.nn.expert_parallel.routers import (
    SoftmaxTopKRouter,
    TopKRouting,
)

WINDOW = 8
# the benchmark's configuration file, at toy widths: two global layers
# without position encoding around three rotary layers under a window
CONFIG = {
    "vocab_size": 96, "hidden_size": 64, "num_hidden_layers": 5,
    "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 16,
    "rms_norm_eps": 1e-6, "moe_ffn_hidden_size": 32,
    "moe_num_primary_experts": 8, "moe_num_active_primary_experts": 3,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "rope_layout": [0, 1, 1, 1, 0], "sliding_window_layout": [0, 1, 1, 1, 0],
    "sliding_window_size": WINDOW, "rope_theta": 1500000, "rope_scaling": None,
    "max_position_embeddings": 16384, "tie_word_embeddings": False,
    "experts_held": [0, 8], "initializer_range": 0.1, "dtype": "float32",
}


def _model(dtype="float32", seed=1, **more):
    config = dict(CONFIG, dtype=dtype, **more)
    sizes = program_smallthinker.sizes(config)
    flat = weights_smallthinker.make(weights_smallthinker.seed_key(seed),
                                     sizes, jnp.dtype(dtype))
    return (config, sizes, flat, program_smallthinker.make_config(config),
            program_smallthinker.to_tree(flat, config))


def _ref_logits(flat, sizes, tokens):
    w32 = {k: v.astype(jnp.float32) for k, v in flat.items()}
    hid = smallthinker_ref.hidden(w32, jnp.asarray(tokens), sizes)
    return np.asarray(smallthinker_ref.logits(w32, hid))


def test_full_forward_is_the_references():
    """Float32 against float32: what is left is the order of sums (the
    program's grouped products against the reference's loop over every
    expert): 2e-4 on logits of order 1 (read: 2e-6)."""
    _, sizes, flat, cfg, params = _model()
    tokens = np.random.RandomState(0).randint(1, 96, (2, 45))
    got = np.asarray(smallthinker.forward(params, jnp.asarray(tokens), cfg))
    for row in range(2):
        want = _ref_logits(flat, sizes, tokens[row])
        assert np.abs(want).max() > 0.5
        np.testing.assert_allclose(got[row], want, atol=2e-4)


def test_a_bfloat16_forward_fails_the_float32_tolerance():
    """The same forward in bfloat16 (weights rounded once, shared with
    the reference): off by 3e-3 and more, ten times the float32
    tolerance, so computing a precision lower fails it."""
    _, sizes, flat, cfg, params = _model("bfloat16")
    tokens = np.random.RandomState(0).randint(1, 96, (1, 45))
    got = np.asarray(smallthinker.forward(params, jnp.asarray(tokens), cfg))
    assert np.abs(got[0] - _ref_logits(flat, sizes, tokens[0])).max() > 3e-3


def _scores(q, k):
    g = q.shape[2] // k.shape[2]
    return np.einsum("bqhd,bnhd->bhqn", np.asarray(q),
                     np.repeat(np.asarray(k), g, axis=2))


def test_global_layers_have_no_position_encoding_and_window_layers_rotary():
    """Layer 0 (``rope_layout`` 0): its queries and keys are the same
    arrays wherever the positions say the tokens stand, shifted by a
    constant or stretched. Layer 1 (layout 1): its cached keys move
    with a constant shift (rotary is relative, so that shift alone
    leaves the SCORES where they were, to rounding), and its scores
    move when the positions are stretched."""
    _, _, _, cfg, params = _model()
    h = jnp.asarray(np.random.RandomState(2).randn(1, 12, 64), jnp.float32)
    pos = jnp.arange(12)[None]
    for layer, rotary in ((0, False), (1, True)):
        blk = params["layers"][layer]
        q, k, v, _ = smallthinker.qkv(blk, h, pos, cfg, layer)
        qs, ks, vs, _ = smallthinker.qkv(blk, h, pos + 1000, cfg, layer)
        qx, kx, _, _ = smallthinker.qkv(blk, h, pos * 3, cfg, layer)
        np.testing.assert_array_equal(np.asarray(v), np.asarray(vs))
        if not rotary:
            for a, b in ((q, qs), (k, ks), (q, qx), (k, kx)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            continue
        assert np.abs(np.asarray(k) - np.asarray(ks)).max() > 0.1
        # float32 angles at position 1,000: 1e-3 on scores of order 1
        np.testing.assert_allclose(_scores(qs, ks), _scores(q, k), atol=1e-3)
        assert np.abs(_scores(qx, kx) - _scores(q, k)).max() > 0.05


def test_the_router_reads_the_first_norms_output_not_the_seconds():
    """Layer 0's picks (the rows on each expert, and the routing ``qkv``
    hands to ``finish``) stay where they are when ``ln_2`` is replaced,
    though the layer's output moves; they move with ``ln_1``."""
    _, _, _, cfg, params = _model()
    ids = jnp.asarray(np.random.RandomState(3).randint(1, 96, (1, 40)))
    scale = jnp.asarray(1.0 + np.random.RandomState(4).randn(64), jnp.float32)

    def with_scale(name):
        layers = list(params["layers"])
        layers[0] = dict(layers[0], **{name: {"scale": scale}})
        return dict(params, layers=layers)

    def picks(p):
        x = jnp.take(p["embed"]["weight"], ids, axis=0)
        routing = smallthinker.qkv(p["layers"][0], x, jnp.arange(40)[None],
                                   cfg, 0)[3]
        hidden, _, rows = smallthinker._trunk(p, ids, cfg)
        return np.asarray(routing.experts), np.asarray(rows[0]), \
            np.asarray(hidden)

    e0, rows0, hid0 = picks(params)
    e2, rows2, hid2 = picks(with_scale("ln_2"))
    e1, rows1, _ = picks(with_scale("ln_1"))
    np.testing.assert_array_equal(e2, e0)
    np.testing.assert_array_equal(rows2, rows0)
    assert np.abs(hid2 - hid0).max() > 1e-2
    assert (e1 != e0).any() and (rows1 != rows0).any()


def test_routing_weights_are_a_softmax_over_the_chosen_logits():
    """``SoftmaxTopKRouter(normalize=True)``: the softmax over all the
    outputs, renormalised over the chosen, IS the softmax over the
    chosen logits alone; and the reference's count-not-sort picks are
    the same picks."""
    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.randn(37, 64), jnp.float32)
    w = jnp.asarray(rng.randn(64, 8) * 0.3, jnp.float32)
    routing = SoftmaxTopKRouter(8, 3, normalize=True)(
        {"gate": {"kernel": w}, "bias": jnp.zeros((8,))}, x)
    z = np.asarray(x) @ np.asarray(w)
    top = np.argsort(-z, axis=-1)[:, :3]
    np.testing.assert_array_equal(np.asarray(routing.experts), top)
    chosen = np.take_along_axis(z, top, axis=-1)
    want = np.exp(chosen - chosen.max(-1, keepdims=True))
    want /= want.sum(-1, keepdims=True)
    np.testing.assert_allclose(np.asarray(routing.weights), want, atol=1e-6)
    np.testing.assert_allclose(np.asarray(routing.weights).sum(-1), 1.0,
                               atol=1e-6)
    sizes = {"moe_num_active_primary_experts": 3}
    dense = np.asarray(smallthinker_ref.routing_weights(x, w, sizes))
    assert ((dense > 0).sum(-1) == 3).all()
    np.testing.assert_allclose(np.take_along_axis(dense, top, axis=-1), want,
                               atol=1e-6)


def test_reglu_grouped_is_a_dense_loop_over_the_picks():
    """``down(relu(gate x) * up x)`` of each token's picks, weighted,
    by a loop over tokens and picks in numpy; SwiGLU on the same rows
    gives another result (the activation is not decoration)."""
    rng = np.random.RandomState(6)
    t, k, e, h, f = 29, 3, 8, 64, 32
    x = rng.randn(t, h).astype(np.float32)
    mats = {n: rng.randn(*s).astype(np.float32) * 0.2 for n, s in
            (("gate", (e, h, f)), ("up", (e, h, f)), ("down", (e, f, h)))}
    experts = np.stack([rng.permutation(e)[:k] for _ in range(t)])
    weights = rng.rand(t, k).astype(np.float32)
    routing = TopKRouting(jnp.asarray(experts, jnp.int32),
                          jnp.asarray(weights), jnp.zeros((t, e)))
    params = {n: {"kernel": jnp.asarray(m)} for n, m in mats.items()}
    got, rows = grouped_experts(params, jnp.asarray(x), routing, (0, e),
                                mlp_fn=reglu_grouped)
    want = np.zeros((t, h), np.float32)
    for i in range(t):
        for j in range(k):
            ex = experts[i, j]
            inner = np.maximum(x[i] @ mats["gate"][ex], 0) \
                * (x[i] @ mats["up"][ex])
            want[i] += weights[i, j] * (inner @ mats["down"][ex])
    # float32 both sides, sums in another order
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-4)
    np.testing.assert_array_equal(np.asarray(rows),
                                  np.bincount(experts.ravel(), minlength=e))
    silu, _ = grouped_experts(params, jnp.asarray(x), routing, (0, e),
                              mlp_fn=swiglu_grouped)
    assert np.abs(np.asarray(silu) - want).max() > 1e-2


def test_every_expert_is_held_so_no_pick_is_absent():
    """The guide's share test does not apply: the served cut holds every
    expert. ``held`` is all of them, and every one of a sequence's T * k
    picks lands on a held expert in every layer."""
    published = smallthinker.SmallThinkerConfig()
    assert published.held == (0, published.moe_num_primary_experts) == (0, 64)
    _, _, _, cfg, params = _model()
    assert cfg.held == (0, cfg.moe_num_primary_experts)
    ids = jnp.asarray(np.random.RandomState(7).randint(1, 96, (2, 33)))
    _, _, rows = smallthinker._trunk(params, ids, cfg)
    assert len(rows) == 5
    for r in rows:
        assert int(r.sum()) == 2 * 33 * cfg.moe_num_active_primary_experts


def test_long_sequences_go_through_the_experts_in_blocks():
    """``moe_block_tokens``: the same values, the rows a block at a
    time, the picks made before attention cut the same way (what lets
    an 8k prompt's picks fit beside the pool)."""
    _, _, _, cfg, params = _model()
    tokens = jnp.asarray(np.random.RandomState(1).randint(1, 96, (1, 48)))
    whole = smallthinker.forward(params, tokens, cfg)
    blocked = smallthinker.forward(
        params, tokens, dataclasses.replace(cfg, moe_block_tokens=16))
    np.testing.assert_allclose(np.asarray(blocked), np.asarray(whole),
                               atol=1e-5)


@pytest.mark.parametrize("bad,match", [
    ({"moe_primary_router_apply_softmax": False},
     "moe_primary_router_apply_softmax"),
    ({"rope_scaling": {"type": "yarn"}}, "rope_scaling"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings"),
    ({"rope_layout": (0, 1)}, "one entry for each"),
    ({"num_attention_heads": 7}, "do not divide"),
    ({"experts_held": (60, 8)}, "experts_held"),
])
def test_what_is_not_built_is_refused_by_name(bad, match):
    _, _, _, cfg, _ = _model()
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(cfg, **bad)


def test_a_mesh_is_refused_by_name():
    _, _, _, cfg, _ = _model()
    with pytest.raises(ValueError, match="smallthinker is served on one "
                                         "device"):
        cfg.paged_model(tp_axis="tensor")
