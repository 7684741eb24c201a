"""Laguna at a small size on the CPU against the benchmark's plain
reference (``benchmark/reference/laguna_ref.py``): the full forward;
prefill then decode through the paged pool, logits compared, with window
pages taken over several times and a global layer walking more than one
chunk; a bfloat16 run failing a float32 tolerance; the shares of a
sparse layer adding up to the uncut layer. Both head counts, the gate,
both rotary sets and the dense and the sparse feed-forward are in the
one model."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import program_laguna, weights_laguna
from benchmark.reference import laguna_ref
from pipegoose_tpu.models import laguna
from pipegoose_tpu.serving import kv_pool
from pipegoose_tpu.serving.blocks import ring_pages

FULL, SLIDING = laguna.FULL, laguna.SLIDING
WINDOW, PS, WALK = 8, 4, 8
# the benchmark's configuration file, at toy widths
CONFIG = {
    "vocab_size": 96, "hidden_size": 64, "intermediate_size": 128,
    "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
    "num_hidden_layers": 5, "num_key_value_heads": 2, "head_dim": 16,
    "num_experts": 8, "router_experts": 16, "experts_held": [0, 8],
    "num_experts_per_tok": 4, "moe_routed_scaling_factor": 2.5,
    "norm_topk_prob": True, "rms_norm_eps": 1e-6, "sliding_window": WINDOW,
    "mlp_only_layers": [0],
    "layer_types": [FULL, SLIDING, SLIDING, SLIDING, FULL],
    "num_attention_heads_per_layer": [4, 6, 6, 6, 4],
    "rope_parameters": {
        FULL: {"rope_theta": 500000, "rope_type": "yarn", "factor": 8,
               "original_max_position_embeddings": 16, "beta_slow": 1,
               "beta_fast": 32, "attention_factor": 1.2,
               "partial_rotary_factor": 0.5},
        SLIDING: {"rope_type": "default", "rope_theta": 10000,
                  "partial_rotary_factor": 1}},
    "initializer_range": 0.1, "dtype": "float32",
}


def _model(dtype="float32", seed=1, **more):
    config = dict(CONFIG, dtype=dtype, **more)
    sizes = program_laguna.sizes(config)
    flat = weights_laguna.make(weights_laguna.seed_key(seed), sizes,
                               jnp.dtype(dtype))
    return (config, sizes, flat, program_laguna.make_config(config),
            program_laguna.to_tree(flat, config))


def _ref_logits(flat, sizes, tokens):
    w32 = {k: v.astype(jnp.float32) for k, v in flat.items()}
    hid = laguna_ref.hidden(w32, jnp.asarray(tokens), sizes)
    return np.asarray(laguna_ref.logits(w32, hid))


def test_full_forward_is_the_references():
    """Float32 against float32: what is left is the order of sums (the
    program's grouped products and fused projections against the
    reference's einsums): 2e-4 on logits of order 1."""
    _, sizes, flat, cfg, params = _model()
    tokens = np.random.RandomState(0).randint(1, 96, (2, 45))
    got = np.asarray(laguna.forward(params, jnp.asarray(tokens), cfg))
    for row in range(2):
        want = _ref_logits(flat, sizes, tokens[row])
        assert np.abs(want).max() > 0.5
        np.testing.assert_allclose(got[row], want, atol=2e-4)


def test_yarn_and_plain_rotary_tables_are_the_references():
    for kind, rope in CONFIG["rope_parameters"].items():
        inv, scale, rot = laguna.rope_frequencies(rope, 16)
        inv_r, scale_r, rot_r = laguna_ref.rope_frequencies(rope, 16)
        np.testing.assert_allclose(inv, inv_r, rtol=1e-6)
        assert (scale, rot) == (scale_r, rot_r)
    # the published sets: YaRN turns half of a head's dims, slowest
    # frequency divided by the factor; plain RoPE turns them all
    real = dict(laguna.ROPE_S_2_1)
    inv, scale, rot = laguna.rope_frequencies(dict(real[FULL]), 128)
    assert rot == 64 and scale == pytest.approx(1.4852030263919618)
    assert inv[0] == pytest.approx(1.0)                  # fastest: kept
    assert inv[-1] == pytest.approx(
        500000.0 ** -(62 / 64) / 128, rel=1e-5)          # slowest: / factor
    inv, scale, rot = laguna.rope_frequencies(dict(real[SLIDING]), 128)
    assert (rot, scale) == (128, 1.0)


def _serve(dtype, monkeypatch, prompt_len=21, new=24, seed=1):
    """One sequence: the model's own prefill (right-padded to a page
    multiple), its cache written into the two kinds of pool, then
    ``new`` decode steps through the page tables, two dead slots beside
    it. Returns (logits at every decoded position, the tokens)."""
    monkeypatch.setattr(kv_pool, "WALK_KEYS", WALK)
    config, sizes, flat, cfg, params = _model(dtype, seed)
    model = cfg.paged_model()
    ring = ring_pages(WINDOW, PS)
    assert ring == 3
    rng = np.random.RandomState(3)
    tokens = list(rng.randint(1, 96, (prompt_len,)))
    width = 16                                 # 64 positions a table
    kp, vp = kv_pool.init_pages(model, 40, PS, window_pages=3 * ring + 1)
    bucket = -(-prompt_len // PS) * PS
    ids = np.zeros((1, bucket), np.int32)
    ids[0, :prompt_len] = tokens
    mask = (np.arange(bucket) < prompt_len)[None].astype(np.int32)
    logits, cache = model.prefill(params, jnp.asarray(ids), jnp.asarray(mask))
    pages = {"global": np.zeros((width,), np.int32),
             "window": np.asarray([4, 5, 6], np.int32)}
    pages["global"][:12] = np.arange(20, 32)   # 48 positions: 6 chunks of 8
    kp, vp = kv_pool.write_prompt_pages(
        kp, vp, cache, {k: jnp.asarray(v) for k, v in pages.items()},
        jnp.asarray(0), PS, jnp.asarray(prompt_len))
    table = {k: jnp.asarray(np.stack([v, 0 * v, 0 * v]))
             for k, v in pages.items()}
    step = jax.jit(lambda p, t, kp, vp, s: kv_pool.paged_decode_step(
        p, t, kp, vp, table, s, model, with_counters=True))
    out = [np.asarray(logits)[0]]
    for i in range(new):
        tokens.append(int(out[-1].argmax()))
        lg, kp, vp, counters = step(
            params, jnp.asarray([tokens[-1], 0, 0]), kp, vp,
            jnp.asarray([prompt_len + i, 0, 0]))
        out.append(np.asarray(lg)[0])
        rows = np.asarray(counters["rows_per_expert"])
        # four sparse layers, 8 held experts; the dead slots' picks go
        # to no expert: at most the live row's four
        assert rows.shape == (4, 8) and rows.sum(axis=1).max() <= 4
    return np.stack(out), np.asarray(tokens), flat, sizes


def test_prefill_then_decode_through_the_pool_is_the_references(monkeypatch):
    """45 positions with a window of 8 in a ring of three pages of 4:
    the window's pages are taken over ten times; the global layers walk
    six chunks of 8 keys. Every decoded position's logits against the
    reference's full forward over the whole sequence. Float32 both
    sides: 3e-4 (sums in another order, logits of order 1)."""
    got, tokens, flat, sizes = _serve("float32", monkeypatch)
    assert len(tokens) == 45 and -(-45 // PS) - 3 >= 9
    want = _ref_logits(flat, sizes, tokens)[20:]
    np.testing.assert_allclose(got, want, atol=3e-4)
    assert (got.argmax(-1) == want.argmax(-1)).all()


def test_a_bfloat16_run_fails_the_float32_tolerance(monkeypatch):
    """The same procedure in bfloat16 (weights rounded once, shared
    with the reference): off by 3e-3 and more, ten times the float32
    tolerance, so computing a precision lower fails it."""
    got, tokens, flat, sizes = _serve("bfloat16", monkeypatch)
    want = _ref_logits(flat, sizes, tokens)[20:]
    assert np.abs(got - want).max() > 3e-3


def test_the_shares_add_up_to_the_uncut_layer():
    """Experts 0-7's part plus experts 8-15's part, the shared expert
    counted once, is the uncut reference's sparse layer: in the program
    (``laguna.moe`` told which half it holds) and in the reference."""
    config = dict(CONFIG, num_experts=16, experts_held=[0, 16])
    sizes = program_laguna.sizes(config)
    flat = weights_laguna.make(weights_laguna.seed_key(2), sizes,
                               jnp.float32)
    cfg = program_laguna.make_config(config)
    blk = program_laguna.to_tree(flat, config)["layers"][1]
    x = jnp.asarray(np.random.RandomState(5).randn(1, 37, 64), jnp.float32)
    whole = np.asarray(laguna_ref.moe(x[0], flat, 1, sizes))
    shared = np.asarray(laguna_ref._swiglu(
        x[0], flat["l1_sh_gate"], flat["l1_sh_up"], flat["l1_sh_down"],
        "float32"))
    parts, parts_ref, picks = [], [], 0
    for first in (0, 8):
        half = dataclasses.replace(cfg, experts_held=(first, 8))
        mine = dict(blk, experts=jax.tree_util.tree_map(
            lambda a: a[first:first + 8], blk["experts"]))
        y, rows = laguna.moe(mine, x, half)
        parts.append(np.asarray(y)[0] - shared)
        picks += int(rows.sum())
        cut = dict(flat, **{f"l1_ex_{k}": flat[f"l1_ex_{k}"][first:first + 8]
                            for k in ("gate", "up", "down")})
        parts_ref.append(np.asarray(laguna_ref.moe(
            x[0], cut, 1, sizes, held=(first, 8))) - shared)
    assert picks == 37 * 4                  # every pick fell on one half
    np.testing.assert_allclose(parts[0] + parts[1] + shared, whole, atol=2e-5)
    np.testing.assert_allclose(parts_ref[0] + parts_ref[1] + shared, whole,
                               atol=2e-5)
    assert np.abs(parts[0]).max() > 1e-3 and np.abs(parts[1]).max() > 1e-3


def test_long_sequences_go_through_the_feed_forward_in_blocks():
    """``moe_block_tokens``: the same values, the rows a block at a
    time (what lets an 8k prompt's picks fit beside the pool)."""
    _, _, _, cfg, params = _model()
    tokens = jnp.asarray(np.random.RandomState(1).randint(1, 96, (1, 48)))
    whole = laguna.forward(params, tokens, cfg)
    blocked = laguna.forward(
        params, tokens, dataclasses.replace(cfg, moe_block_tokens=16))
    np.testing.assert_allclose(np.asarray(blocked), np.asarray(whole),
                               atol=1e-5)
