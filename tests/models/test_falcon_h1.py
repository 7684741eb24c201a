"""Falcon-H1 at a small size on the CPU against the benchmark's plain
reference (``benchmark/reference/falcon_h1_ref.py``, whose recurrence is
a ``lax.scan`` a token): the full forward; the chunked matrix form of
the recurrence against the token-by-token one at lengths that are no
multiple of the chunk; prefill then decode through pool and state bank
against the full forward at every generated position; a bucket-padded
prompt leaving the state of the unpadded one, a prompt shorter than the
convolution too; a bfloat16 run and a state zeroed at the hand-over each
failing the float32 tolerance."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import program_falcon_h1, weights_falcon_h1
from benchmark.reference import falcon_h1_ref
from pipegoose_tpu.models import falcon_h1
from pipegoose_tpu.serving import kv_pool

PS, WALK, CHUNK = 4, 8, 8
# the benchmark's configuration file, at toy widths: every multiplier
# its own value (none 1), so that one left out changes the result
CONFIG = {
    "vocab_size": 96, "hidden_size": 64, "intermediate_size": 128,
    "num_hidden_layers": 3, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "rms_norm_eps": 1e-5,
    "rope_theta": 1e11, "mamba_d_ssm": 64, "mamba_n_heads": 4,
    "mamba_d_head": 16, "mamba_n_groups": 2, "mamba_d_state": 8,
    "mamba_d_conv": 4, "mamba_chunk_size": CHUNK,
    "embedding_multiplier": 3.0, "lm_head_multiplier": 0.5,
    "attention_in_multiplier": 0.9, "attention_out_multiplier": 0.7,
    "key_multiplier": 0.6, "ssm_in_multiplier": 0.8,
    "ssm_out_multiplier": 0.75,
    "ssm_multipliers": [0.9, 1.1, 0.8, 1.2, 0.7],
    "mlp_multipliers": [0.8, 0.6],
    "initializer_range": 0.3, "in_proj_std": 0.3, "conv_std": 0.5,
    "state_dtype": "float32", "dtype": "float32",
}


def _model(dtype="float32", seed=1, **more):
    config = dict(CONFIG, dtype=dtype, **more)
    sizes = program_falcon_h1.sizes(config)
    flat = weights_falcon_h1.make(weights_falcon_h1.seed_key(seed), sizes,
                                  jnp.dtype(dtype))
    return (sizes, flat, program_falcon_h1.make_config(config),
            program_falcon_h1.to_tree(flat, config))


def _ref_logits(flat, sizes, tokens):
    w32 = {k: v.astype(jnp.float32) for k, v in flat.items()}
    hid = falcon_h1_ref.hidden(w32, jnp.asarray(tokens), sizes)
    return np.asarray(falcon_h1_ref.logits(w32, hid, sizes))


def test_full_forward_is_the_references():
    """Float32 against float32: what is left is the order of sums (the
    chunked form's matrices against a scan a token, fused projections
    against einsums): 2e-4 on logits of order 1. 45 tokens: five whole
    chunks of 8 and a ragged one."""
    sizes, flat, cfg, params = _model()
    tokens = np.random.RandomState(0).randint(1, 96, (2, 45))
    got = np.asarray(falcon_h1.forward(params, jnp.asarray(tokens), cfg))
    for row in range(2):
        want = _ref_logits(flat, sizes, tokens[row])
        assert np.abs(want).max() > 0.5
        np.testing.assert_allclose(got[row], want, atol=2e-4)


def test_the_configuration_counts_what_the_published_one_does():
    c = falcon_h1.FalconH1Config()
    assert (c.conv_dim, c.in_proj_dim) == (5120, 9248)
    mup = c.mup_vector()
    assert mup.shape == (9248,)
    assert [float(mup[i]) for i in (0, 4096, 8192, 8704, 9216)] == \
        pytest.approx(list(c.ssm_multipliers))
    np.testing.assert_array_equal(
        mup, falcon_h1_ref.mup_vector(
            {**{k: getattr(c, k) for k in program_falcon_h1.PUBLISHED}}))
    assert dict((n, s) for n, s, _ in c.state_shapes()) == {
        "ssm": (32, 128, 256), "conv": (3, 5120)}
    with pytest.raises(ValueError, match="mamba_n_heads x mamba_d_head"):
        falcon_h1.FalconH1Config(mamba_d_ssm=4000)


@pytest.mark.parametrize("length", [1, 5, 8, 19, 24, 45])
def test_the_chunked_form_is_the_token_by_token_recurrence(length):
    """``ssd_chunked`` at chunk 8 against the reference's scan a token,
    at lengths under, on and across chunk edges; decays between 0.2 and
    0.999 as the weights are drawn. Float32: 1e-5 on outputs of order
    0.1-1."""
    rng = np.random.RandomState(length)
    h, p, g, n = 4, 16, 2, 8
    x = jnp.asarray(rng.randn(1, length, h, p), jnp.float32)
    bm = jnp.asarray(rng.randn(1, length, g, n), jnp.float32) * 0.5
    cm = jnp.asarray(rng.randn(1, length, g, n), jnp.float32) * 0.5
    dt = jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(1e-1),
                                        (1, length, h))), jnp.float32)
    a = -jnp.asarray(rng.uniform(1, 16, (h,)), jnp.float32)
    y, last = falcon_h1.ssd_chunked(x, dt, a, bm, cm, CHUNK)
    want_y, want_last = falcon_h1_ref.recurrence(x[0], dt[0], a, bm[0], cm[0])
    assert np.abs(np.asarray(want_y)).max() > 1e-3
    np.testing.assert_allclose(np.asarray(y)[0], want_y, atol=1e-5)
    np.testing.assert_allclose(np.asarray(last)[0], want_last, atol=1e-5)
    # a position whose dt is 0 (padding) leaves the state as it was
    pad = 3
    y2, last2 = falcon_h1.ssd_chunked(
        *(jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2),
                  constant_values=v)
          for t, v in ((x, 7.0), (dt, 0.0))), a,
        *(jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0)), constant_values=7.0)
          for t in (bm, cm)), CHUNK)
    np.testing.assert_allclose(np.asarray(last2), np.asarray(last),
                               atol=1e-6)


def _serve(dtype, monkeypatch, prompt_len=21, new=24, seed=1, slot=1,
           spoil=None):
    """One sequence: the model's own prefill (right-padded to a page
    multiple), its keys and values written into the pool and its state
    into row ``slot`` of the bank, then ``new`` decode steps through
    page table and bank, two dead slots beside it whose rows hold
    garbage. ``spoil(state)``: what happens to the prefill's state on
    its way into the bank. Returns (logits at every decoded position,
    the tokens, flat weights, sizes)."""
    monkeypatch.setattr(kv_pool, "WALK_KEYS", WALK)
    sizes, flat, cfg, params = _model(dtype, seed)
    model = cfg.paged_model()
    rng = np.random.RandomState(3)
    tokens = list(rng.randint(1, 96, (prompt_len,)))
    width, slots = 16, 3                       # 64 positions a table
    kp, vp = kv_pool.init_pages(model, 40, PS)
    bank = {k: jnp.asarray(rng.randn(*v.shape), v.dtype)    # leftovers
            for k, v in kv_pool.init_state(model, slots).items()}
    assert {k: v.shape for k, v in bank.items()} == {
        "ssm": (3, slots, 4, 16, 8), "conv": (3, slots, 3, 96)}
    bucket = -(-prompt_len // PS) * PS
    ids = np.zeros((1, bucket), np.int32)
    ids[0, :prompt_len] = tokens
    mask = (np.arange(bucket) < prompt_len)[None].astype(np.int32)
    logits, cache = model.prefill(params, jnp.asarray(ids), jnp.asarray(mask))
    pages = np.zeros((width,), np.int32)
    pages[:12] = np.arange(20, 32)             # 48 positions: 6 chunks of 8
    kp, vp = kv_pool.write_prompt_pages(
        kp, vp, cache, jnp.asarray(pages), jnp.asarray(0), PS,
        jnp.asarray(prompt_len))
    state = cache["state"] if spoil is None else spoil(cache["state"])
    bank = kv_pool.write_state(bank, state, jnp.asarray(slot))
    table = np.zeros((slots, width), np.int32)
    table[slot] = pages
    step = jax.jit(lambda p, t, kp, vp, s, bank: kv_pool.paged_decode_step(
        p, t, kp, vp, jnp.asarray(table), s, model, state=bank))
    out = [np.asarray(logits)[0]]
    dead = {k: np.asarray(v[:, [i for i in range(slots) if i != slot]])
            for k, v in bank.items()}
    for i in range(new):
        tokens.append(int(out[-1].argmax()))
        tok, lens = np.zeros((slots,), np.int32), np.zeros((slots,), np.int32)
        tok[slot], lens[slot] = tokens[-1], prompt_len + i
        lg, kp, vp, bank = step(params, jnp.asarray(tok), kp, vp,
                                jnp.asarray(lens), bank)
        out.append(np.asarray(lg)[slot])
    # a slot that holds no request keeps what it held
    for k, v in bank.items():
        np.testing.assert_array_equal(
            np.asarray(v[:, [i for i in range(slots) if i != slot]]), dead[k])
    return np.stack(out), np.asarray(tokens), flat, sizes


def test_prefill_then_decode_through_pool_and_bank_is_the_references(
        monkeypatch):
    """45 positions: a 21-token prompt (two whole chunks of the
    recurrence and a ragged one, right-padded to 24), then 24 decode
    steps whose attention walks six chunks of 8 keys and whose mixer
    reads and overwrites the slot's state. Every decoded position's
    logits against the reference's full forward over the whole sequence.
    Float32 both sides: 3e-4 (sums in another order, logits of order 1).
    The same tolerance a bfloat16 run and a zeroed state fail, below."""
    got, tokens, flat, sizes = _serve("float32", monkeypatch)
    assert len(tokens) == 45
    want = _ref_logits(flat, sizes, tokens)[20:]
    np.testing.assert_allclose(got, want, atol=3e-4)
    assert (got.argmax(-1) == want.argmax(-1)).all()


def test_a_bfloat16_run_fails_the_float32_tolerance(monkeypatch):
    """The same procedure in bfloat16 (weights rounded once, shared with
    the reference; the state still float32): off by 3e-3 and more, ten
    times the float32 tolerance, so computing a precision lower fails
    it."""
    got, tokens, flat, sizes = _serve("bfloat16", monkeypatch)
    want = _ref_logits(flat, sizes, tokens)[20:]
    assert np.abs(got - want).max() > 3e-3


@pytest.mark.parametrize("leaf", ["ssm", "conv"])
def test_a_state_zeroed_at_the_hand_over_fails_the_float32_tolerance(
        monkeypatch, leaf):
    """The prefill's state lost on its way into the slot (the
    recurrence's H, or the convolution's last inputs): the first decoded
    positions are off by far more than the tolerance, the last ones
    still by more than it (decays up to 0.999 a token: 24 tokens later
    the state has not forgotten)."""
    got, tokens, flat, sizes = _serve(
        "float32", monkeypatch,
        spoil=lambda st: dict(st, **{leaf: jnp.zeros_like(st[leaf])}))
    want = _ref_logits(flat, sizes, tokens)[20:]
    assert np.abs(got[0] - want[0]).max() < 3e-4      # the prefill's own
    assert np.abs(got[1] - want[1]).max() > 3e-2
    if leaf == "ssm":
        assert np.abs(got[-1] - want[-1]).max() > 3e-4


@pytest.mark.parametrize("prompt_len", [1, 2, 3, 7, 21])
def test_a_padded_prompt_leaves_the_state_of_the_unpadded_one(prompt_len):
    """The prefill over a bucket of 24 against the prefill over the
    prompt alone: the same logits, H and convolution inputs (zeros where
    the prompt has fewer than three tokens), whatever tokens fill the
    padding; and H is the reference's recurrence after the last real
    token."""
    _, _, cfg, params = _model()
    rng = np.random.RandomState(prompt_len)
    tokens = rng.randint(1, 96, (prompt_len,))
    ids = rng.randint(1, 96, (1, 24))           # padding: any tokens
    ids[0, :prompt_len] = tokens
    mask = (np.arange(24) < prompt_len)[None].astype(np.int32)
    lg, cache = falcon_h1.prefill(params, jnp.asarray(ids),
                                  jnp.asarray(mask), cfg)
    lg1, alone = falcon_h1.prefill(
        params, jnp.asarray(tokens)[None],
        jnp.ones((1, prompt_len), jnp.int32), cfg)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(lg1), atol=2e-5)
    for name in ("ssm", "conv"):
        np.testing.assert_allclose(np.asarray(cache["state"][name]),
                                   np.asarray(alone["state"][name]),
                                   atol=2e-5)
    conv = np.asarray(cache["state"]["conv"])   # (L, 1, 3, C)
    assert conv.shape == (3, 1, 3, 96)
    blank = max(0, 3 - prompt_len)
    assert not conv[:, :, :blank].any() and conv[:, :, blank:].any()
    # keys and values of the real positions are the unpadded run's
    np.testing.assert_allclose(np.asarray(cache["k"])[:, :, :prompt_len],
                               np.asarray(alone["k"]), atol=2e-5)


def test_the_walk_reads_and_writes_as_far_as_the_highest_live_slot(
        monkeypatch):
    """``update_state_rows`` over 8 slots, 4 a trip: with slot 2 the
    highest live one the first trip's rows are handed to ``fn`` and the
    second trip's never; a row's result comes back in its own place;
    nothing alive: no trip."""
    monkeypatch.setattr(kv_pool, "STATE_ROWS", 4)
    assert kv_pool.state_walk_plan(8) == (4, 2)
    assert kv_pool.state_walk_plan(6) == (3, 2)     # no trip overhangs
    assert kv_pool.state_walk_plan(64)[1] * kv_pool.state_walk_plan(64)[0] \
        == 64
    assert [kv_pool.walked_state_rows(s, 4) for s in (-1, 0, 3, 4, 7)] == \
        [0, 1, 1, 2, 2]
    bank = {"h": jnp.arange(2 * 8 * 3, dtype=jnp.float32).reshape(2, 8, 3)}
    xs = jnp.arange(8, dtype=jnp.float32)

    def fn(rows, x):
        return {"h": rows["h"] + 100.0}, rows["h"].sum(-1) + x

    for live_slots, touched in (((0, 2), 4), ((5,), 8), ((), 0)):
        live = jnp.zeros((8,), bool).at[jnp.asarray(live_slots, int)].set(
            True) if live_slots else jnp.zeros((8,), bool)
        new, ys = jax.jit(lambda b, l: kv_pool.update_state_rows(
            b, 1, l, fn, xs))(bank, live)
        want = np.asarray(bank["h"]).copy()
        want[1, :touched] += 100.0
        np.testing.assert_array_equal(np.asarray(new["h"]), want)
        want_y = np.zeros((8,), np.float32)
        want_y[:touched] = np.asarray(bank["h"])[1, :touched].sum(-1) \
            + np.arange(touched)
        np.testing.assert_array_equal(np.asarray(ys), want_y)
