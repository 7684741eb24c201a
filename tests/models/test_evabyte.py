"""EvaByte at a small size on the CPU against the benchmark's plain
reference (``benchmark/reference/evabyte_ref.py``), with the published
SHAPE of things: a window of four chunks, sequences over three windows
that end inside a chunk, ``phi`` and ``mu`` far from zero, eight output
heads. The full forward on both lanes; the prefill's logits, ring rows
and summaries; and each rule of EVA failing under the mutation it guards
against: the pooling's weights, ``mu`` on the key alone, ONE normaliser,
which summaries a query sees, the block window, RoPE before pooling, the
float32 residual stream."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import program_evabyte as adapter
from benchmark import weights_evabyte as weights
from benchmark.reference import evabyte_ref as ref
from benchmark.reference import laguna_ref
from pipegoose_tpu.models import evabyte as eb

# the benchmark's configuration file, at toy widths
CONFIG = {
    "vocab_size": 40, "hidden_size": 64, "intermediate_size": 96,
    "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 4, "num_pred_heads": 8, "window_size": 64,
    "chunk_size": 16, "num_chunks": None, "rope_theta": 100000,
    "rope_scaling": None, "rms_norm_eps": 1e-5, "norm_add_unit_offset": True,
    "fp32_skip_add": True, "fp32_logits": True, "fp32_ln": False,
    "mixedp_attn": True, "attention_bias": False, "attention_class": "eva",
    "hidden_act": "silu", "tie_word_embeddings": False, "init_std": 0.1,
    "init_fn": "v2", "init_cutoff_factor": None, "lazy_init": True,
    "max_position_embeddings": 32768, "max_seq_length": 32768,
    "model_type": "evabyte", "dtype": "float32",
    "phi_std": 1.0, "mu_std": 0.25,
}
W, C = CONFIG["window_size"], CONFIG["chunk_size"]
LONG = 3 * W + 37             # over three windows, five bytes into a chunk
TOL = 3e-5                    # float32 against float32, logits of order 1


def _model(dtype="float32", seed=1):
    config = dict(CONFIG, dtype=dtype)
    sizes = adapter.sizes(config)
    flat = weights.make(weights.seed_key(seed), sizes, jnp.dtype(dtype))
    return (sizes, flat, adapter.make_config(config),
            adapter.to_tree(flat, config))


def _tokens(n=LONG, seed=0):
    return np.random.RandomState(seed).randint(0, 40, (n,))


def _want(flat, sizes, tokens, **kw):
    return np.asarray(ref.forward(flat, jnp.asarray(tokens), sizes, **kw))


@pytest.fixture(scope="module")
def model():
    return _model()


@pytest.fixture(scope="module")
def want(model):
    sizes, flat, _, _ = model
    return _want(flat, sizes, _tokens())


@pytest.mark.parametrize("use_flash", [False, True], ids=["dense", "flash"])
def test_all_eight_heads_are_the_references(model, want, use_flash):
    """Three windows and a partial chunk: every position's logits of all
    eight output heads, on the dense lane and through the flash kernels
    (the window part's result and log-sum-exp handed to the summary
    part's chunk kernel as its carry)."""
    _, _, cfg, params = model
    cfg = dataclasses.replace(cfg, use_flash=use_flash)
    got = np.asarray(eb.forward(params, jnp.asarray(_tokens())[None], cfg))[0]
    assert got.shape == (LONG, 8, 40) and np.abs(want).max() > 0.5
    np.testing.assert_allclose(got, want, atol=TOL)
    # the summaries carry weight: the heads differ, and a query past the
    # first window reads otherwise without them (mutations below)
    assert np.abs(want[:, 0] - want[:, 1]).max() > 0.1


def test_the_feed_forward_in_blocks_gives_the_whole_forward(model):
    _, _, cfg, params = model
    ids = jnp.asarray(_tokens(3 * W))[None]
    whole = eb.forward(params, ids, cfg)
    cut = eb.forward(params, ids, dataclasses.replace(cfg,
                                                      ffn_block_tokens=W))
    np.testing.assert_allclose(np.asarray(cut), np.asarray(whole), atol=1e-5)


@pytest.mark.parametrize("at_once", [1, 2, 3, 8])
def test_the_heads_a_group_at_a_time_give_the_whole_forward(
        monkeypatch, model, want, at_once):
    """The whole sequence's attention takes the heads in groups of the
    largest divisor of the head count within ``ATTN_HEADS_AT_ONCE`` (4
    heads here: singly, in pairs, singly, all at once): the logits are
    the reference's and the prefill's rows and summaries come back with
    their heads in order."""
    _, _, cfg, params = model
    monkeypatch.setattr(eb, "ATTN_HEADS_AT_ONCE", at_once)
    ids = jnp.asarray(_tokens())[None]
    got = np.asarray(eb.forward(params, ids, cfg))[0]
    np.testing.assert_allclose(got, want, atol=TOL)
    ids = jnp.asarray(_tokens(3 * W))[None]
    _, cache = eb.prefill(params, ids, jnp.ones_like(ids), cfg)
    monkeypatch.setattr(eb, "ATTN_HEADS_AT_ONCE", 4)
    _, whole = eb.prefill(params, ids, jnp.ones_like(ids), cfg)
    for kind in ("window", "global"):
        for name in ("k", "v"):
            assert cache[kind][name].shape == whole[kind][name].shape
            np.testing.assert_allclose(np.asarray(cache[kind][name]),
                                       np.asarray(whole[kind][name]),
                                       atol=1e-5)


def test_a_lower_precision_fails_the_float32_tolerance(model, want):
    sizes, flat, _, _ = model
    _, _, cfg16, params16 = _model("bfloat16")
    got = np.asarray(eb.forward(params16, jnp.asarray(_tokens())[None],
                                cfg16))[0]
    assert np.abs(got - want).max() > 30 * TOL
    lower = _want(flat, sizes, _tokens(), precision="fp8")
    assert np.abs(lower - want).max() > 100 * TOL


@pytest.mark.parametrize("n", [LONG, 2 * W, W - 3, 2 * W + C])
def test_prefill_returns_logits_ring_rows_and_complete_summaries(model, n):
    """A right-padded bucket: head 0's logits after the last REAL byte;
    the rotated keys and the values from ``(n // W) * W`` on; the
    summary of every chunk, of which the first ``n // C`` are whole
    chunks of real bytes and equal the reference's."""
    sizes, flat, cfg, params = model
    tokens = _tokens(n, seed=n)
    bucket = -(-n // C) * C
    ids = np.zeros((1, bucket), np.int32)
    ids[0, :n] = tokens
    mask = (np.arange(bucket) < n).astype(np.int32)[None]
    logits, cache = eb.prefill(params, jnp.asarray(ids), jnp.asarray(mask),
                               cfg)
    np.testing.assert_allclose(np.asarray(logits)[0],
                               _want(flat, sizes, tokens)[-1, 0], atol=TOL)
    start = n // W * W
    assert int(cache["window"]["start"]) == start
    held = min(W, eb._padded(bucket, W))
    assert cache["window"]["k"].shape == (2, 1, held, 4, 16)
    # layer 0's keys and summaries by the reference's own functions
    x = eb.rms1({"scale": flat["ln1"][0]}, flat["embed"][tokens], 1e-5,
                jnp.float32)
    rope = {"rope_theta": sizes["rope_theta"]}
    k = laguna_ref._rope((x @ flat["k"][0]).reshape(n, 4, 16), rope)
    v = (x @ flat["v"][0]).reshape(n, 4, 16)
    np.testing.assert_allclose(
        np.asarray(cache["window"]["k"])[0, 0, :n - start],
        np.asarray(k)[start:], atol=TOL)
    whole = n // C
    k_sum, v_sum = ref.summaries(k[:whole * C], v[:whole * C], flat["phi"][0],
                                 flat["mu"][0], sizes)
    assert cache["global"]["k"].shape[2] >= whole
    np.testing.assert_allclose(np.asarray(cache["global"]["k"])[0, 0, :whole],
                               np.asarray(k_sum), atol=TOL)
    np.testing.assert_allclose(np.asarray(cache["global"]["v"])[0, 0, :whole],
                               np.asarray(v_sum), atol=TOL)


# -- each rule, shown once by breaking it -----------------------------------

def _mutated(monkeypatch, model, **patch):
    """The program's logits with ``evabyte``'s functions replaced."""
    _, _, cfg, params = model
    for name, fn in patch.items():
        monkeypatch.setattr(eb, name, fn)
    return np.asarray(eb.forward(params, jnp.asarray(_tokens())[None],
                                 cfg))[0]


def _fails_past_the_first_window(got, want):
    """A broken summary or rule changes no query of window 0 (it has no
    summaries to see) and fails the tolerance after it."""
    np.testing.assert_allclose(got[:W], want[:W], atol=TOL)
    assert np.abs(got[W:] - want[W:]).max() > 100 * TOL


def test_uniform_pooling_fails(monkeypatch, model, want):
    def pool(blk, k, v):
        return (k.mean(-3) + blk["attn"]["mu"], v.mean(-3))

    _fails_past_the_first_window(_mutated(monkeypatch, model, pool=pool), want)


def test_a_dropped_mu_fails_and_so_does_mu_on_the_value(monkeypatch, model,
                                                       want):
    real = eb.pool

    def no_mu(blk, k, v):
        k_sum, v_sum = real(blk, k, v)
        return k_sum - blk["attn"]["mu"], v_sum

    def mu_on_both(blk, k, v):
        k_sum, v_sum = real(blk, k, v)
        return k_sum, v_sum + blk["attn"]["mu"]

    for pool in (no_mu, mu_on_both):
        with monkeypatch.context() as m:
            _fails_past_the_first_window(_mutated(m, model, pool=pool), want)


def _attention_with(rule):
    """``eva_attention``'s dense lane with the softmax, the window or
    the visibility taken from ``rule``."""
    def attention(q, k, v, blk, c):
        _, s, nh, hd = q.shape
        n_sum = s // C
        k_sum, v_sum = eb.pool(blk, k.reshape(1, n_sum, C, nh, hd),
                               v.reshape(1, n_sum, C, nh, hd))
        pos, chunk = jnp.arange(s), jnp.arange(n_sum)
        scale = hd ** -0.5
        exact = jnp.einsum("qhd,khd->hqk", q[0], k[0]) * scale
        pooled = jnp.einsum("qhd,chd->hqc", q[0], k_sum[0]) * scale
        keep, seen = rule(pos[:, None], pos[None, :], chunk[None, :])
        exact = jnp.where(keep & (pos[None, :] <= pos[:, None]), exact,
                          -jnp.inf)
        pooled = jnp.where(seen, pooled, -jnp.inf)
        if rule is two_softmaxes:
            # each part normalised alone, then averaged
            p_k = jax.nn.softmax(exact, -1)
            p_c = jnp.where(seen.any(-1, keepdims=True),
                            jax.nn.softmax(jnp.where(
                                seen.any(-1, keepdims=True), pooled, 0.0),
                                -1), 0.0)
            ctx = jnp.einsum("hqk,khd->qhd", p_k, v[0]) \
                + jnp.einsum("hqc,chd->qhd", p_c, v_sum[0])
            ctx = ctx / (1.0 + seen.any(-1)[:, None, None])
        else:
            p = jax.nn.softmax(jnp.concatenate([exact, pooled], -1), -1)
            ctx = jnp.einsum("hqk,khd->qhd", p[..., :s], v[0]) \
                + jnp.einsum("hqc,chd->qhd", p[..., s:], v_sum[0])
        return ctx.reshape(1, s, nh * hd), k_sum, v_sum
    return attention


def _whole_chunks(s, window):
    """The dense harness takes any whole number of chunks."""
    return -(-s // C) * C


def the_rule(q, k, c):
    return k >= q // W * W, c < q // W * (W // C)


def two_softmaxes(q, k, c):
    return the_rule(q, k, c)


def open_window_seen(q, k, c):
    """Every COMPLETE chunk behind the query, its own window's too."""
    return k >= q // W * W, (c + 1) * C - 1 < q


def sliding_window(q, k, c):
    """The last W keys exact, and the chunks wholly before them."""
    return q - k < W, (c + 1) * C - 1 <= q - W


def test_the_rule_written_densely_is_the_references(monkeypatch, model, want):
    """The harness of the mutations below gives the reference where it
    holds EVA's own rule, so each mutation is the one thing changed."""
    got = _mutated(monkeypatch, model, _padded=_whole_chunks,
                   eva_attention=_attention_with(the_rule))
    np.testing.assert_allclose(got, want, atol=TOL)


@pytest.mark.parametrize("rule", [two_softmaxes, open_window_seen,
                                  sliding_window], ids=lambda r: r.__name__)
def test_another_rule_fails(monkeypatch, model, want, rule):
    got = _mutated(monkeypatch, model, _padded=_whole_chunks,
                   eva_attention=_attention_with(rule))
    if rule is open_window_seen:
        # window 0 sees its own complete chunks: wrong from byte 16 on
        np.testing.assert_allclose(got[:C], want[:C], atol=TOL)
        assert np.abs(got[C:W] - want[C:W]).max() > 100 * TOL
    else:
        _fails_past_the_first_window(got, want)


def test_pooling_keys_before_the_rotary_fails(monkeypatch, model, want):
    """A summary is pooled from ROTATED keys: un-rotating them first
    (position 0's angle for every key of a chunk) changes it."""
    real = eb.pool

    def pool(blk, k, v):
        return real(blk, jnp.broadcast_to(k[..., :1, :, :], k.shape), v)

    _fails_past_the_first_window(_mutated(monkeypatch, model, pool=pool), want)


def test_the_residual_stream_stays_float32():
    """In bfloat16 the products are rounded, the stream is not: the
    hidden state a layer hands on is float32 and differs from one
    rounded to bfloat16 after every add."""
    _, _, cfg, params = _model("bfloat16")
    ids = jnp.asarray(_tokens(W))[None]
    seen = []
    real = eb.finish

    def finish(blk, h, ctx, config):
        out = real(blk, h, ctx, config)
        seen.append((h.dtype, out.dtype))
        return out

    eb.finish, kept = finish, eb.finish
    try:
        exact = np.asarray(eb.forward(params, ids, cfg))
        eb.finish = lambda blk, h, ctx, config: real(
            blk, h, ctx, config).astype(jnp.bfloat16).astype(jnp.float32)
        rounded = np.asarray(eb.forward(params, ids, cfg))
    finally:
        eb.finish = kept
    assert seen and all(a == b == jnp.float32 for a, b in seen)
    assert np.abs(exact - rounded).max() > 1e-3


def test_what_the_config_does_not_build_is_refused_by_name():
    for key, value in [("attention_class", "softmax"), ("rope_scaling", {}),
                       ("attention_bias", True), ("num_chunks", 4),
                       ("fp32_skip_add", False)]:
        with pytest.raises(ValueError, match=key):
            eb.EvaByteConfig(**{key: value})
    with pytest.raises(ValueError, match="one key-value head"):
        eb.EvaByteConfig(num_key_value_heads=8)
