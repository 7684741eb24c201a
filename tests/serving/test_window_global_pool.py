"""Window and global layers in one allocator, through ``ServingEngine``
at a small size on the CPU: a model with two cache kinds (Laguna:
sliding layers keep a ring of pages, full layers every page) under the
same scheduler, tick and page pool as BLOOM. Pages are accounted by
kind; a window layer never holds more than window + page keys of a
sequence; finish, preemption and an aborted run return both kinds; the
opt-in modes built for one cache kind refuse such a model by name; the
decode walk is counted by kind; BLOOM is served through the same block
description."""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pipegoose_tpu.models import bloom, generate as gen, laguna
from pipegoose_tpu.serving import Request, ServingEngine, kv_pool
from pipegoose_tpu.serving.blocks import bloom_model, describe, ring_pages
from pipegoose_tpu.serving.kv_pool import PagePool
from pipegoose_tpu.serving.scheduler import Scheduler, Status
from pipegoose_tpu.telemetry import MetricsRegistry

FULL, SLIDING = laguna.FULL, laguna.SLIDING
WINDOW, PS, WALK, CONTEXT = 8, 4, 8, 64
RING = 3                                     # 8 / 4 + 1 pages
MIXED = [(30, 20), (7, 12), (41, 9), (12, 30), (5, 6)]


def _config(**more):
    return laguna.LagunaConfig(
        vocab_size=96, hidden_size=64, intermediate_size=128,
        num_hidden_layers=5, num_key_value_heads=2, head_dim=16,
        num_experts=16, num_experts_per_tok=4, moe_intermediate_size=32,
        shared_expert_intermediate_size=32, sliding_window=WINDOW,
        layer_types=(FULL, SLIDING, SLIDING, SLIDING, FULL),
        num_attention_heads_per_layer=(4, 6, 6, 6, 4), experts_held=(0, 8),
        initializer_range=0.1, **more)


@pytest.fixture(scope="module")
def model():
    cfg = _config()
    params = laguna.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(7)
    return cfg, params, [rng.randint(1, 96, (s,)) for s, _ in MIXED]


def _engine(cfg, params, **kw):
    kw = {"num_slots": 3, "num_pages": 48, "page_size": PS,
          "max_context": CONTEXT, **kw}
    return ServingEngine(params, cfg, **kw)


@jax.jit
def _forward(params, tokens):
    return laguna.forward(params, tokens, _config()).argmax(-1)


def _assert_greedy(params, prompt, generated):
    """``generated`` is what the model's own full forward puts first
    after every prefix (one program: the sequence right-padded, which
    a causal model does not see)."""
    tokens = np.zeros((1, CONTEXT), np.int32)
    n = len(prompt) + len(generated)
    tokens[0, :n] = np.concatenate([prompt, generated])
    best = np.asarray(_forward(params, jnp.asarray(tokens)))[0]
    np.testing.assert_array_equal(generated, best[len(prompt) - 1:n - 1])


# -- the allocator ---------------------------------------------------------------


def test_pool_accounts_pages_by_kind():
    assert ring_pages(512, 16) == 33 and ring_pages(8, 4) == 3
    pool = PagePool(16, PS, window_pages=3 * RING + 1, ring=RING)
    assert pool.kinds == ("global", "window")
    assert pool.pages_for(41) == 11 and pool.pages_for(41, "window") == RING
    assert pool.pages_for(5, "window") == 2
    g, w = pool.alloc(4), pool.alloc(2, "window")
    assert pool.used_by_kind() == {"global": 4, "window": 2}
    assert pool.used_count == 6 and pool.free_count == 11
    assert pool.of("window").free_count == 3 * RING - 2
    assert 0 not in w                       # the kind's own NULL page
    pool.release(g)
    pool.release(w, "window")
    assert pool.used_count == 0
    with pytest.raises(RuntimeError):
        pool.alloc(3 * RING + 1, "window")
    plain = PagePool(16, PS)
    assert plain.kinds == ("global",) and plain.window is None
    with pytest.raises(ValueError, match="no 'window' kind"):
        plain.alloc(1, "window")
    with pytest.raises(ValueError, match="ring"):
        PagePool(16, PS, window_pages=4)


def test_scheduler_keeps_a_ring_and_returns_both_kinds():
    pool = PagePool(32, PS, window_pages=2 * RING + 1, ring=RING)
    sched = Scheduler(2, pool, CONTEXT)
    a = Request(prompt=np.arange(1, 30), max_new_tokens=20)      # 29 tokens
    b = Request(prompt=np.arange(1, 6), max_new_tokens=3)        # 5 tokens
    sched.submit(a, 0.0)
    sched.submit(b, 0.0)
    assert sched.admit(0.0) == [a, b]
    # the whole prompt's global pages; the ring whole, or as far as the
    # prompt reaches
    assert len(a.pages) == 8 and len(a.window_pages) == RING
    assert len(b.pages) == 2 and len(b.window_pages) == 2
    assert pool.recycled == 8 - RING         # a's prompt passed its ring
    for req in (a, b):
        sched.record_token(req, 1, 0.1)
    for i in range(8):                       # a: positions 29..36
        sched.ensure_page(a)
        sched.record_token(a, 1, 0.2)
        assert len(a.window_pages) == RING
        assert len(a.window_pages) * PS <= WINDOW + PS
    assert len(a.pages) == 10 and pool.recycled == 10 - RING
    sched.ensure_page(b)                     # position 5: same page
    assert len(b.window_pages) == 2
    sched.record_token(b, 1, 0.2)
    sched.ensure_page(b)
    sched.record_token(b, 1, 0.3)            # b: done at 3 tokens
    assert b.status is Status.DONE and not b.pages and not b.window_pages
    assert pool.used_by_kind() == {"global": 10, "window": RING}
    sched.preempt(a)
    assert pool.used_count == 0 and not a.window_pages
    assert a.window_logical == 0 and a.status is Status.QUEUED
    # a third ring does not fit beside two: the ledger of the window kind
    c = Request(prompt=np.arange(1, 30), max_new_tokens=2)
    held = pool.alloc(2 * RING - 1, "window")
    assert not sched._window_fits(c)
    pool.release(held, "window")
    assert sched._window_fits(c)


# -- through the engine -------------------------------------------------------------


def test_engine_serves_two_cache_kinds_and_drains_both(model, monkeypatch):
    """Mixed lengths over three slots (so slots are reused): the tokens
    are the model's own full forward's, greedy; sequences of 40 and
    more pass their ring of three pages many times over; no window page
    is ever held beyond slots x ring; both kinds drain."""
    monkeypatch.setattr(kv_pool, "WALK_KEYS", WALK)
    cfg, params, prompts = model
    reg = MetricsRegistry(enabled=True)
    eng = _engine(cfg, params, registry=reg)
    assert eng.model.kinds == ("global", "window")
    assert eng.pool.ring == RING
    assert eng.pool.window.capacity == 3 * RING
    assert set(eng.k_pages) == {"global", "window"}
    assert eng.k_pages["global"].shape == (2, 48, PS, 2 * 16)
    assert eng.k_pages["window"].shape == (3, 3 * RING + 1, PS, 2 * 16)
    seen = []
    outs, metrics = eng.run(
        [Request(prompt=p, max_new_tokens=n)
         for p, (_, n) in zip(prompts, MIXED)],
        tick_hook=lambda e, tick: seen.append(e.pool.used_by_kind()))
    for o, p, (_, n) in zip(outs, prompts, MIXED):
        assert len(o.generated) == n
        _assert_greedy(params, p, o.generated)
    assert eng.pool.used_count == 0
    assert eng.pool.used_by_kind() == {"global": 0, "window": 0}
    assert max(u["window"] for u in seen) == 3 * RING
    assert max(u["global"] for u in seen) > 3 * RING
    by_kind = metrics["pages_by_kind"]
    assert by_kind["window"] == {"capacity": 3 * RING, "peak_in_use": 3 * RING,
                                 "occupancy": by_kind["window"]["occupancy"]}
    assert 0.0 < by_kind["window"]["occupancy"] <= 1.0
    assert by_kind["global"]["peak_in_use"] <= 48
    # logical pages past the rings: 50 + 19 + 50 + 42 + 11 positions
    want = sum(max(-(-(s + n - 1) // PS) - RING, 0) for s, n in MIXED)
    assert metrics["window_pages_recycled"] == want
    gauges = reg.snapshot()
    assert gauges["counters"]["serving.window_pages_recycled_total"] == want
    assert "serving.pages_in_use.window" in gauges["gauges"]
    assert "serving.pages_in_use.global" in gauges["gauges"]
    assert 0.0 < gauges["gauges"]["serving.experts_touched_share"] <= 1.0
    # the experts' counters out of the jitted step: four sparse layers
    # of 8 held experts, a step at a time
    experts = metrics["experts"]
    assert len(experts["touched_by_step"]) == metrics["decode_steps"]
    assert experts["held_a_step"] == 4 * 8
    assert 0 < max(experts["touched_by_step"]) <= 4 * 8
    assert experts["rows_max_over_mean"] >= 1.0
    assert 0.0 < experts["touched_share"] <= 1.0
    report = eng.memory_report()["kv"]["by_kind"]
    assert report["global"]["layers"] == 2 and report["window"]["layers"] == 3
    assert report["window"]["num_pages"] == 3 * RING + 1
    total = sum(k["fp_bytes"] for k in report.values())
    assert total == eng.memory_report()["kv"]["total_bytes"]


def test_key_share_is_counted_by_kind(model, monkeypatch):
    """A scripted run: the window layers' walk is their ring's two
    chunks of 8 keys at most, the global layers' as far as the longest
    sequence; ``window_key_share`` is the one over the other, by the
    arithmetic the device takes its trip counts from, on the lengths
    each step was sent."""
    monkeypatch.setattr(kv_pool, "WALK_KEYS", WALK)
    cfg, params, prompts = model
    eng = _engine(cfg, params)
    sent = []
    step = eng._step

    def spy(p, carry, kp, vp):
        # the lengths the step ran on, out of its packed inputs (sent or
        # carried on the device); a copy, before the call donates them
        sent.append(np.array(eng._unpack_carry(np.asarray(carry))[1]))
        return step(p, carry, kp, vp)

    eng._step = spy
    clock = itertools.count()
    _, metrics = eng.run(
        [Request(prompt=p, max_new_tokens=n)
         for p, (_, n) in zip(prompts, MIXED)],
        now=lambda: next(clock) * 1e-3)
    assert len(sent) == metrics["decode_steps"] > 0
    ring_keys = 2 * WALK                         # 3 pages: 2 chunks of 2
    walked = [min((int(s.max()) // WALK + 1) * WALK, CONTEXT) for s in sent]
    ringed = [min(w, ring_keys) for w in walked]
    assert metrics["decode_key_share"] == pytest.approx(
        sum(walked) / (len(sent) * CONTEXT), abs=1e-6)
    assert metrics["decode_key_share_by_kind"]["window"] == pytest.approx(
        sum(ringed) / (len(sent) * ring_keys), abs=1e-6)
    assert metrics["window_key_share"] == pytest.approx(
        sum(ringed) / sum(walked), abs=1e-6)
    # sequences of up to 50 positions: the window layers read well
    # under what a global read of the same steps walks
    assert metrics["window_key_share"] < 0.6


def test_an_aborted_run_and_a_preemption_return_both_kinds(model):
    cfg, params, prompts = model
    eng = _engine(cfg, params)
    eng.start_run([Request(prompt=prompts[0], max_new_tokens=20),
                   Request(prompt=prompts[2], max_new_tokens=9)])
    for _ in range(4):
        eng.tick_once()
    used = eng.pool.used_by_kind()
    assert used["global"] > 0 and used["window"] == 2 * RING
    victim = eng.sched.active()[0]
    eng.sched.preempt(victim)
    assert eng.pool.used_by_kind()["window"] == RING
    eng.sched.withdraw(victim)
    for req in list(eng.sched.active()):
        eng.sched.preempt(req)
        eng.sched.withdraw(req)
    eng.abort_run()
    assert eng.pool.used_count == 0 and eng.sched.all_done()
    # the engine is reusable, and drains again
    outs, _ = eng.run([Request(prompt=prompts[1], max_new_tokens=5)])
    _assert_greedy(params, prompts[1], outs[0].generated)
    assert eng.pool.used_count == 0


REFUSED = {
    "prefix_cache": {"prefix_cache": True},
    "speculative": {"speculative": (1, 2)},
    "prefill_chunk": {"prefill_chunk": 8},
    "kv_dtype": {"kv_dtype": "int8"},
    "weight_dtype": {"weight_dtype": "int8"},
    "host_tier": {"host_tier": object(), "prefix_cache": False},
    "prefill_only": {"prefill_only": True, "prefill_chunk": None},
    "mesh": {"mesh": object()},
    "memledger": {"memledger": True},
}


@pytest.mark.parametrize("mode", sorted(REFUSED))
def test_a_model_with_two_cache_kinds_refuses_the_mode_by_name(model, mode):
    cfg, params, _ = model
    kw = REFUSED[mode]
    if mode == "prefill_only":
        kw = {"prefill_only": True, "prefill_chunk": 8}
        mode = "prefill_chunk|prefill_only"
    with pytest.raises(ValueError, match=f"({mode}) is not built for a "
                                         f"model with 2 cache kinds"):
        _engine(cfg, params, **kw)


def test_the_defaults_spelled_out_are_not_refused(model):
    cfg, params, _ = model
    eng = _engine(cfg, params, kv_dtype="fp", weight_dtype="fp",
                  prefix_cache=False)
    assert eng.model.kinds == ("global", "window")


# -- BLOOM through the same description ------------------------------------------------


def test_bloom_is_the_first_instance_of_the_description():
    cfg = bloom.BloomConfig(vocab_size=64, hidden_size=64, n_layer=2, n_head=4)
    params = bloom.init_params(cfg, jax.random.PRNGKey(0))
    desc = describe(cfg)
    assert desc.kinds == ("global",) and desc.n_layer == 2
    assert desc.groups[0].stacked and desc.left_pad and not desc.counters
    assert describe(desc) is desc
    assert type(describe(_config())) is type(desc)
    eng = ServingEngine(params, cfg, num_slots=2, num_pages=16, page_size=PS,
                        max_context=32)
    assert eng.model.kinds == ("global",) and eng.pool.window is None
    assert eng.k_pages.shape == (2, 16, PS, 64)      # a bare bank
    prompt = np.random.RandomState(1).randint(1, 64, (9,))
    outs, metrics = eng.run([Request(prompt=prompt, max_new_tokens=6)])
    want = gen.generate(params, jnp.asarray(prompt)[None], cfg,
                        max_new_tokens=6)
    np.testing.assert_array_equal(outs[0].generated, np.asarray(want)[0, 9:])
    assert metrics["pages_by_kind"] == {
        "global": {"capacity": 15, "peak_in_use": 4}}
    assert "experts" not in metrics and "window_key_share" not in metrics
    # the paged programs take the description itself as well as a config
    kp, vp = kv_pool.init_pages(bloom_model(cfg), 16, PS)
    assert kp.shape == eng.k_pages.shape
