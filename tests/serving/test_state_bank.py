"""A state a slot beside the paged keys and values, through
``ServingEngine`` at a small size on the CPU: a model whose every block
keeps a recurrence's state (Falcon-H1) under the same scheduler, tick
and page pool as BLOOM and Laguna. A reused slot starts from its
prefill's state; dead slots never reach live ones; preemption and
re-admission serve the same tokens; an aborted run frees every slot; the
opt-in modes built for global pages alone refuse such a model by name;
the state's counters are what the steps were sent; BLOOM's and Laguna's
descriptions build the programs they built."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pipegoose_tpu.models import bloom, falcon_h1, laguna
from pipegoose_tpu.serving import Request, ServingEngine, kv_pool
from pipegoose_tpu.serving.blocks import describe
from pipegoose_tpu.serving.scheduler import Status
from pipegoose_tpu.telemetry import MetricsRegistry

PS, CONTEXT = 4, 64
MIXED = [(30, 20), (7, 12), (41, 9), (2, 10), (12, 30), (5, 6)]


def _config(**more):
    return falcon_h1.FalconH1Config(
        vocab_size=96, hidden_size=64, intermediate_size=128,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, mamba_d_ssm=64, mamba_n_heads=4, mamba_d_head=16,
        mamba_n_groups=2, mamba_d_state=8, mamba_chunk_size=8,
        lm_head_multiplier=0.5, embedding_multiplier=3.0,
        initializer_range=0.3, **more)


@pytest.fixture(scope="module")
def model():
    cfg = _config()
    params = falcon_h1.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(7)
    return cfg, params, [rng.randint(1, 96, (s,)) for s, _ in MIXED]


def _engine(cfg, params, **kw):
    kw = {"num_slots": 3, "num_pages": 48, "page_size": PS,
          "max_context": CONTEXT, **kw}
    return ServingEngine(params, cfg, **kw)


@jax.jit
def _forward(params, tokens):
    return falcon_h1.forward(params, tokens, _config())


def _assert_greedy(params, prompt, generated):
    """``generated`` is what the model's own full forward (the chunked
    recurrence from an empty state, no cache) puts first after every
    prefix, and by a margin: a state off by a little would move the
    logits, not only the pick."""
    tokens = np.zeros((1, CONTEXT), np.int32)
    n = len(prompt) + len(generated)
    tokens[0, :n] = np.concatenate([prompt, generated])
    logits = np.asarray(_forward(params, jnp.asarray(tokens)))[0]
    np.testing.assert_array_equal(
        generated, logits.argmax(-1)[len(prompt) - 1:n - 1])


def _requests(prompts):
    return [Request(prompt=p, max_new_tokens=n)
            for p, (_, n) in zip(prompts, MIXED)]


def test_a_reused_slot_starts_from_its_prefills_state(model):
    """Six requests over three slots: every slot is handed on at least
    once, to a request of another length, and every request is served
    the tokens of the full forward from an empty state. Prompts of 2 and
    5 tokens (under and over the convolution's three inputs) among
    them."""
    cfg, params, prompts = model
    reg = MetricsRegistry()
    reg.enable()
    eng = _engine(cfg, params, registry=reg)
    assert set(eng.state) == {"ssm", "conv"}
    assert eng.state["ssm"].shape == (3, 3, 4, 16, 8)
    assert eng.state["ssm"].dtype == jnp.float32
    outs, metrics = eng.run(_requests(prompts))
    for out in outs:
        _assert_greedy(params, out.prompt, out.generated)
    assert eng.pool.used_count == 0 and eng.sched.all_done()
    st = metrics["state"]
    assert st["slots"] == 3 and st["writes"] == 6
    assert st["bytes_per_slot"] == 3 * (4 * 16 * 8 * 4 + 3 * 96 * 4)
    assert st["peak_slots_in_use"] == 3
    assert st["occupancy"] == metrics["slot_occupancy"]
    # three slots are one trip: a step with any row alive walks all three
    assert st["rows_updated"] == 3 * metrics["decode_steps"]
    assert 0 < st["rows_live"] <= st["rows_updated"]
    assert st["rows_live"] == sum(len(o.generated) - 1 for o in outs)
    assert reg.counter("serving.state_writes_total").value == 6
    assert eng.memory_report()["state"]["total_bytes"] == \
        3 * st["bytes_per_slot"]


def test_dead_slots_never_reach_live_ones(model, monkeypatch):
    """A request served alone in an engine whose other slots hold
    garbage, and beside neighbours that come and go, gets the same
    tokens; the walk over the bank stops at the highest live slot."""
    monkeypatch.setattr(kv_pool, "STATE_ROWS", 4)
    cfg, params, prompts = model
    eng = _engine(cfg, params, num_slots=8)
    rng = np.random.RandomState(1)
    eng.state = {k: jnp.asarray(rng.randn(*v.shape), v.dtype)
                 for k, v in eng.state.items()}
    garbage = {k: np.asarray(v) for k, v in eng.state.items()}
    alone, metrics = eng.run([Request(prompt=prompts[0], max_new_tokens=20)])
    _assert_greedy(params, alone[0].prompt, alone[0].generated)
    # slot 0 alone was alive: four of eight rows a step (one trip of 4),
    # and the rows past the walk are as they were
    assert kv_pool.state_walk_plan(8) == (4, 2)
    assert metrics["state"]["rows_updated"] == 4 * metrics["decode_steps"]
    assert metrics["state"]["rows_live"] == metrics["decode_steps"]
    for k, v in eng.state.items():
        np.testing.assert_array_equal(np.asarray(v)[:, 1:], garbage[k][:, 1:])
    crowd, _ = eng.run(_requests(prompts))
    np.testing.assert_array_equal(crowd[0].generated[:20],
                                  alone[0].generated)


def test_preemption_and_readmission_serve_the_same_tokens(model):
    """A request preempted in the middle of its decode gives its slot
    and pages back, is re-admitted (to whatever slot is free),
    re-prefills prompt plus generated tokens, and goes on: the tokens of
    an undisturbed run, and no state was ever saved."""
    cfg, params, prompts = model
    calm, _ = _engine(cfg, params).run(_requests(prompts)[:3])

    def preempt(engine, tick):
        if tick in (6, 11):
            live = [r for r in engine.sched.active()
                    if r.status is Status.DECODE]
            engine.sched.preempt(live[0])

    eng = _engine(cfg, params)
    outs, metrics = eng.run(_requests(prompts)[:3], tick_hook=preempt)
    for out, want in zip(outs, calm):
        np.testing.assert_array_equal(out.generated, want.generated)
        _assert_greedy(params, out.prompt, out.generated)
    assert metrics["prefills"] == 5 and metrics["state"]["writes"] == 5
    assert eng.pool.used_count == 0


def test_an_aborted_run_frees_every_slot(model):
    cfg, params, prompts = model
    eng = _engine(cfg, params)
    eng.start_run(_requests(prompts)[:3])
    for _ in range(4):
        eng.tick_once()
    assert len(eng.sched.active()) == 3
    for req in list(eng.sched.active()):
        eng.sched.preempt(req)
        eng.sched.withdraw(req)
    eng.abort_run()
    assert eng.pool.used_count == 0 and eng.sched.all_done()
    assert all(s is None for s in eng.sched.slots)
    # the engine is reusable: the slots' leftover states are not read
    outs, _ = eng.run([Request(prompt=prompts[1], max_new_tokens=12)])
    _assert_greedy(params, prompts[1], outs[0].generated)


REFUSED = {
    "prefix_cache": {"prefix_cache": True},
    "speculative": {"speculative": (1, 2)},
    "prefill_chunk": {"prefill_chunk": 8},
    "kv_dtype": {"kv_dtype": "int8"},
    "weight_dtype": {"weight_dtype": "int8"},
    "host_tier": {"host_tier": object(), "prefix_cache": False},
    "prefill_only": {"prefill_only": True, "prefill_chunk": 8},
    "mesh": {"mesh": object()},
    "memledger": {"memledger": True},
}


@pytest.mark.parametrize("mode", sorted(REFUSED))
def test_a_model_with_a_state_refuses_the_mode_by_name(model, mode):
    cfg, params, _ = model
    named = "prefill_chunk|prefill_only" if mode == "prefill_only" else mode
    with pytest.raises(ValueError, match=f"({named}) is not built for a "
                                         f"model with a state a slot"):
        _engine(cfg, params, **REFUSED[mode])


def test_the_paged_programs_refuse_what_the_engine_refuses(model):
    cfg, params, _ = model
    desc = describe(cfg)
    kp, vp = kv_pool.init_pages(desc, 16, PS)
    i32 = jnp.int32
    with pytest.raises(ValueError, match="prefill chunk is not built"):
        kv_pool.paged_prefill_chunk(
            params, jnp.zeros((1, 8), i32), kp, vp, jnp.zeros((1, 16), i32),
            jnp.zeros((1,), i32), jnp.ones((1,), i32), cfg)
    with pytest.raises(ValueError, match="over its state bank"):
        kv_pool.paged_decode_step(
            params, jnp.zeros((2,), i32), kp, vp, jnp.zeros((2, 16), i32),
            jnp.zeros((2,), i32), cfg)
    with pytest.raises(ValueError, match="served on one device"):
        cfg.paged_model("tensor")
    assert _engine(cfg, params, kv_dtype="fp", weight_dtype="fp",
                   prefix_cache=False).state


# -- models without a state: the programs they built ---------------------------


def _laguna():
    cfg = laguna.LagunaConfig(
        vocab_size=96, hidden_size=64, intermediate_size=128,
        num_hidden_layers=5, num_key_value_heads=2, head_dim=16,
        num_experts=16, num_experts_per_tok=4, moe_intermediate_size=32,
        shared_expert_intermediate_size=32, sliding_window=8,
        layer_types=(laguna.FULL,) + (laguna.SLIDING,) * 3 + (laguna.FULL,),
        num_attention_heads_per_layer=(4, 6, 6, 6, 4), experts_held=(0, 8),
        initializer_range=0.1)
    return cfg, laguna.init_params(cfg, jax.random.PRNGKey(0))


def _bloom():
    cfg = bloom.BloomConfig(vocab_size=64, hidden_size=64, n_layer=2, n_head=4)
    return cfg, bloom.init_params(cfg, jax.random.PRNGKey(0))


@pytest.mark.parametrize("family", ["bloom", "laguna"])
def test_a_model_without_state_builds_the_programs_it_built(family):
    """No bank, and the decode and page-write programs are, operation
    for operation, the ones a description without the state's hooks
    lowers to: the same arguments (an empty bank is no argument), the
    same text."""
    cfg, params = {"bloom": _bloom, "laguna": _laguna}[family]()
    eng = ServingEngine(params, cfg, num_slots=2, num_pages=16, page_size=PS,
                        max_context=32)
    assert eng.state == {} and not eng.model.state
    assert all(g.mix is None for g in eng.model.groups)
    args = (params, jnp.zeros((eng._carry_size,), jnp.int32), eng.k_pages,
            eng.v_pages)
    built = eng._step.lower(*args).as_text()

    def _step(params, carry, k_pages, v_pages):
        # what the engine jits where no bank exists: the step over its
        # packed inputs, and the next step's beside its results
        tokens, seq_lens, table = eng._unpack_carry(carry)
        logits, k_pages, v_pages, counters = kv_pool.paged_decode_step(
            params, tokens, k_pages, v_pages, table, seq_lens, eng.model,
            with_counters=True)
        nxt = logits.argmax(-1)
        live = seq_lens > 0
        head = jnp.concatenate(
            [jnp.where(live, nxt.astype(jnp.int32), 0), seq_lens + live])
        carry = jax.lax.dynamic_update_slice(carry, head, (0,))
        return nxt, k_pages, v_pages, counters, {}, carry   # {}: no leaf

    plain = jax.jit(_step, donate_argnums=(1, 2, 3)).lower(*args).as_text()
    assert built == plain
    main = next(x for x in built.splitlines() if "public @main(" in x)
    assert main.count("%arg") == len(jax.tree_util.tree_leaves(args))
    # the write takes what it took: no bank, no slot
    outs, metrics = eng.run([Request(prompt=np.arange(1, 10),
                                     max_new_tokens=5)])
    assert len(outs[0].generated) == 5 and "state" not in metrics
    assert "state" not in eng.memory_report()
