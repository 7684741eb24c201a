"""SmallThinker through the paged programs at a small size on the CPU:
prefill then decode through the two kinds of pool against the plain
reference's full forward, logits compared, in a batch whose one row's
ring fills, wraps and is taken over in place while another stays well
short of the window; the same model through ``ServingEngine``'s default
path (no branch on the family: the description alone), both kinds
drained; the ring's two counters against a hand count; the opt-in modes
refused by name."""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import program_smallthinker, weights_smallthinker
from benchmark.reference import smallthinker_ref
from pipegoose_tpu.models import smallthinker
from pipegoose_tpu.serving import Request, ServingEngine, kv_pool
from pipegoose_tpu.serving.blocks import ring_pages
from pipegoose_tpu.telemetry import MetricsRegistry

WINDOW, PS, WALK, CONTEXT = 16, 4, 8, 64
RING = 5                                     # 16 / 4 + 1 pages
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
MIXED = [(30, 20), (7, 5), (41, 9), (12, 30), (5, 6)]


def _model(dtype="float32", seed=1):
    config = dict(CONFIG, dtype=dtype)
    sizes = program_smallthinker.sizes(config)
    flat = weights_smallthinker.make(weights_smallthinker.seed_key(seed),
                                     sizes, jnp.dtype(dtype))
    return (sizes, flat, program_smallthinker.make_config(config),
            program_smallthinker.to_tree(flat, config))


def _ref_logits(flat, sizes, tokens):
    w32 = {k: v.astype(jnp.float32) for k, v in flat.items()}
    hid = smallthinker_ref.hidden(w32, jnp.asarray(tokens), sizes)
    return np.asarray(smallthinker_ref.logits(w32, hid))


def _serve(dtype, monkeypatch, new=36):
    """Two sequences in a batch of three slots (the third dead): each
    through the model's own prefill (right-padded to a page multiple),
    its cache written into the two kinds of pool, then ``new`` decode
    steps of the long one through the page tables; the short one decodes
    beside it for its first 6 steps and then stands still (its slot goes
    dead). Returns, a row, (logits at every decoded position, tokens)."""
    monkeypatch.setattr(kv_pool, "WALK_KEYS", WALK)
    sizes, flat, cfg, params = _model(dtype)
    model = cfg.paged_model()
    assert ring_pages(WINDOW, PS) == RING
    rng = np.random.RandomState(3)
    prompts = [list(rng.randint(1, 96, (n,))) for n in (9, 3)]
    width = CONTEXT // PS
    kp, vp = kv_pool.init_pages(model, 40, PS, window_pages=3 * RING + 1)
    pages = [{"global": np.zeros((width,), np.int32),
              "window": np.arange(1 + RING * r, 1 + RING * (r + 1),
                                  dtype=np.int32)} for r in range(2)]
    pages[0]["global"][:12] = np.arange(20, 32)     # 48 positions
    pages[1]["global"][:3] = np.arange(33, 36)
    out = [[], []]
    for r, prompt in enumerate(prompts):
        n = len(prompt)
        bucket = -(-n // PS) * PS
        ids = np.zeros((1, bucket), np.int32)
        ids[0, :n] = prompt
        mask = (np.arange(bucket) < n)[None].astype(np.int32)
        logits, cache = model.prefill(params, jnp.asarray(ids),
                                      jnp.asarray(mask))
        kp, vp = kv_pool.write_prompt_pages(
            kp, vp, cache, {k: jnp.asarray(v) for k, v in pages[r].items()},
            jnp.asarray(0), PS, jnp.asarray(n))
        out[r].append(np.asarray(logits)[0])
    table = {k: jnp.asarray(np.stack([pages[0][k], pages[1][k],
                                      0 * pages[0][k]]))
             for k in ("global", "window")}
    step = jax.jit(lambda p, t, kp, vp, s: kv_pool.paged_decode_step(
        p, t, kp, vp, table, s, model, with_counters=True))
    tokens = [list(p) for p in prompts]
    for i in range(new):
        alive = [True, i < 6]
        for r in range(2):
            if alive[r]:
                tokens[r].append(int(out[r][-1].argmax()))
        lens = [len(tokens[r]) - 1 if alive[r] else 0 for r in range(2)]
        lg, kp, vp, counters = step(
            params, jnp.asarray([tokens[0][-1],
                                 tokens[1][-1] if alive[1] else 0, 0]),
            kp, vp, jnp.asarray(lens + [0]))
        for r in range(2):
            if alive[r]:
                out[r].append(np.asarray(lg)[r])
        rows = np.asarray(counters["rows_per_expert"])
        # five layers of 8 experts; a dead slot's picks go to no expert
        assert rows.shape == (5, 8)
        assert (rows.sum(axis=1) == 3 * sum(alive)).all()
    return [(np.stack(o), np.asarray(t)) for o, t in zip(out, tokens)], \
        flat, sizes


def test_prefill_then_decode_through_the_pool_is_the_references(monkeypatch):
    """Row 0: 9 prompt positions and 36 decoded, window 16 in a ring of
    five pages of 4: the ring fills at position 20, wraps, and its pages
    are taken over in place six times; the global layers (no position
    encoding at all) walk six chunks of 8 keys. Row 1 stays at 9
    positions, well short of the window, in the same steps: both ring
    states in one batch. Every decoded position's logits against the
    reference's full forward over the whole sequence. Float32 both
    sides: 3e-4 (sums in another order, logits of order 1)."""
    served, flat, sizes = _serve("float32", monkeypatch)
    (got0, tok0), (got1, tok1) = served
    assert len(tok0) == 45 and -(-45 // PS) - RING >= 6
    assert len(tok1) == 9 < WINDOW
    for got, tokens, n_prompt in ((got0, tok0, 9), (got1, tok1, 3)):
        want = _ref_logits(flat, sizes, tokens)[n_prompt - 1:]
        assert np.abs(want).max() > 0.5
        np.testing.assert_allclose(got, want, atol=3e-4)
        assert (got.argmax(-1) == want.argmax(-1)).all()


def test_a_bfloat16_run_fails_the_float32_tolerance(monkeypatch):
    """The same procedure in bfloat16 (weights rounded once, shared
    with the reference): off by 3e-3 and more, ten times the float32
    tolerance, so computing a precision lower fails it."""
    served, flat, sizes = _serve("bfloat16", monkeypatch)
    got, tokens = served[0]
    want = _ref_logits(flat, sizes, tokens)[8:]
    assert np.abs(got - want).max() > 3e-3


# -- through ServingEngine's default path ---------------------------------------

@pytest.fixture(scope="module")
def model():
    _, _, cfg, params = _model()
    rng = np.random.RandomState(7)
    return cfg, params, [rng.randint(1, 96, (s,)) for s, _ in MIXED]


def _engine(cfg, params, **kw):
    kw = {"num_slots": 3, "num_pages": 48, "page_size": PS,
          "max_context": CONTEXT, **kw}
    return ServingEngine(params, cfg, **kw)


def _assert_greedy(cfg, params, prompt, generated):
    """``generated`` is what the model's own full forward puts first
    after every prefix (the sequence right-padded, which a causal model
    does not see)."""
    tokens = np.zeros((1, CONTEXT), np.int32)
    n = len(prompt) + len(generated)
    tokens[0, :n] = np.concatenate([prompt, generated])
    best = np.asarray(smallthinker.forward(
        params, jnp.asarray(tokens), cfg).argmax(-1))[0]
    np.testing.assert_array_equal(generated, best[len(prompt) - 1:n - 1])


def test_engine_serves_it_by_its_description_and_drains_both_kinds(
        model, monkeypatch):
    """Mixed lengths over three slots (so slots are reused): the tokens
    are the model's own full forward's, greedy; no window page is ever
    held beyond slots x ring; both kinds drain; the experts' counters
    and the ring's come out of the run."""
    monkeypatch.setattr(kv_pool, "WALK_KEYS", WALK)
    cfg, params, prompts = model
    reg = MetricsRegistry(enabled=True)
    eng = _engine(cfg, params, registry=reg)
    assert eng.model.kinds == ("global", "window")
    assert eng.model.window_rule == "sliding" and eng.pool.ring == RING
    assert eng.k_pages["global"].shape == (2, 48, PS, 2 * 16)
    assert eng.k_pages["window"].shape == (3, 3 * RING + 1, PS, 2 * 16)
    outs, metrics = eng.run([Request(prompt=p, max_new_tokens=n)
                             for p, (_, n) in zip(prompts, MIXED)])
    for o, p, (_, n) in zip(outs, prompts, MIXED):
        assert len(o.generated) == n
        _assert_greedy(cfg, params, p, o.generated)
    assert eng.pool.used_by_kind() == {"global": 0, "window": 0}
    assert metrics["pages_by_kind"]["window"]["peak_in_use"] <= 3 * RING
    assert metrics["window_pages_recycled"] > 0
    experts = metrics["experts"]
    assert experts["held_a_step"] == 5 * 8
    assert len(experts["touched_by_step"]) == metrics["decode_steps"]
    window = metrics["window"]
    assert 0.0 < window["wrapped_row_share"] < 1.0
    assert 0.0 < window["rows_useful_share"] < 1.0
    gauges = reg.snapshot()["gauges"]
    assert gauges["serving.ring_wrapped_share"] == window["wrapped_row_share"]
    assert gauges["serving.ring_rows_useful_share"] \
        == window["rows_useful_share"]
    assert 0.0 < gauges["serving.experts_touched_share"] <= 1.0


def test_the_rings_counters_are_a_hand_count(model, monkeypatch):
    """A scripted run: of the lengths each plain decode step was sent,
    the live rows at or past the window over the live rows; and the
    key columns their windows hold, ``min(pos + 1, window)`` a row, over
    what the walk gathered for them: every live row the chunks of 8 keys
    up to the FURTHEST row's position, three chunks (the ring's five
    pages, two a chunk) at most."""
    monkeypatch.setattr(kv_pool, "WALK_KEYS", WALK)
    cfg, params, prompts = model
    eng = _engine(cfg, params)
    sent = []
    step = eng._step

    def spy(p, carry, kp, vp):
        sent.append(np.array(eng._unpack_carry(np.asarray(carry))[1]))
        return step(p, carry, kp, vp)

    eng._step = spy
    clock = itertools.count()
    _, metrics = eng.run(
        [Request(prompt=p, max_new_tokens=n)
         for p, (_, n) in zip(prompts, MIXED)],
        now=lambda: next(clock) * 1e-3)
    assert len(sent) == metrics["decode_steps"] > 0
    live = wrapped = needed = gathered = 0
    for lens in sent:
        pos = [int(x) for x in lens if x > 0]
        chunks = min(max(pos) // WALK + 1, 3)
        live += len(pos)
        wrapped += sum(p >= WINDOW for p in pos)
        needed += sum(min(p + 1, WINDOW) for p in pos)
        gathered += len(pos) * chunks * WALK
    assert 0 < wrapped < live
    assert metrics["window"]["wrapped_row_share"] == pytest.approx(
        wrapped / live, abs=1e-6)
    assert metrics["window"]["rows_useful_share"] == pytest.approx(
        needed / gathered, abs=1e-6)
    # rows of 7 to 12 positions beside rows of 30 to 50: well under 1
    assert metrics["window"]["rows_useful_share"] < 0.8


REFUSED = {
    "prefix_cache": {"prefix_cache": True},
    "speculative": {"speculative": (1, 2)},
    "prefill_chunk": {"prefill_chunk": 8},
    "kv_dtype": {"kv_dtype": "int8"},
    "weight_dtype": {"weight_dtype": "int8"},
    "host_tier": {"host_tier": object(), "prefix_cache": False},
    "mesh": {"mesh": object()},
    "memledger": {"memledger": True},
}


@pytest.mark.parametrize("mode", sorted(REFUSED))
def test_the_modes_built_for_one_cache_kind_refuse_it_by_name(model, mode):
    cfg, params, _ = model
    with pytest.raises(ValueError, match=f"{mode} is not built for a "
                                         f"model with 2 cache kinds"):
        _engine(cfg, params, **REFUSED[mode])
