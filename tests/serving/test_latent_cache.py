"""A paged LATENT cache through ``ServingEngine`` at a small size on the
CPU: a model whose cached row is one latent row a token an attention
(LongCat-Flash: two attentions a block), in ONE bank, under the same
scheduler, tick, allocator and carry as the other served families.
Logits of prefill + decode through the bank against the benchmark's plain
reference, across page and walk-chunk boundaries; slots handed on;
preemption; the pool's one bank and the step's arguments; page bytes;
the counters of the zero-compute experts; every opt-in mode refused by
name."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import program_longcat_flash as adapter
from benchmark import weights_longcat_flash as weights
from benchmark.reference import longcat_flash_ref as ref
from pipegoose_tpu.serving import Request, ServingEngine, kv_pool
from pipegoose_tpu.serving.blocks import describe
from pipegoose_tpu.serving.scheduler import Status
from pipegoose_tpu.telemetry import MetricsRegistry

PS, CONTEXT, WALK = 4, 64, 8
LANES, STORED = 32 + 8, 128           # [c | kr], and what the bank keeps
MIXED = [(30, 20), (7, 12), (41, 9), (2, 10), (12, 30), (5, 6)]
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


@pytest.fixture(scope="module")
def model():
    sizes = adapter.sizes(CONFIG)
    flat = weights.make(weights.seed_key(3), sizes, jnp.float32)
    rng = np.random.RandomState(7)
    return (adapter.make_config(CONFIG), adapter.to_tree(flat, CONFIG), flat,
            sizes, [rng.randint(1, 96, (s,)) for s, _ in MIXED])


@pytest.fixture(autouse=True)
def short_walk(monkeypatch):
    # two pages a trip: the longest rows walk eight chunks
    monkeypatch.setattr(kv_pool, "WALK_KEYS", WALK)


def _engine(cfg, params, **kw):
    kw = {"num_slots": 3, "num_pages": 48, "page_size": PS,
          "max_context": CONTEXT, **kw}
    return ServingEngine(params, cfg, **kw)


def _requests(prompts):
    return [Request(prompt=p, max_new_tokens=n)
            for p, (_, n) in zip(prompts, MIXED)]


def _ref_logits(flat, sizes, tokens):
    hid = ref.hidden(flat, jnp.asarray(tokens), sizes)
    return np.asarray(ref.logits(flat, hid))


def _assert_the_references_picks(flat, sizes, prompt, generated):
    """Every served token is the one the reference's full forward over
    prompt + generated (no cache) puts first."""
    tokens = np.concatenate([prompt, generated])
    logits = _ref_logits(flat, sizes, tokens)
    np.testing.assert_array_equal(
        generated, logits.argmax(-1)[len(prompt) - 1:len(tokens) - 1])


def test_prefill_then_decode_through_the_bank_gives_the_references_logits(
        model):
    """The model's prefill, the page write and decode steps of two rows
    of unlike lengths, logits against the reference's full forward:
    across page boundaries (every fourth token) and walk chunks (every
    eighth), with a dead slot between the live ones."""
    cfg, params, flat, sizes, _ = model
    desc = describe(cfg)
    pages, none = kv_pool.init_pages(desc, 40, PS)
    assert none is None and pages.shape == (4, 40, PS, STORED)
    rng = np.random.RandomState(0)
    seqs = [rng.randint(1, 96, (n,)) for n in (37, 9)]
    n_new, width = 14, CONTEXT // PS
    table = np.zeros((3, width), np.int32)
    free = iter(range(1, 40))
    got = {0: [], 2: []}
    for slot, seq in zip((0, 2), seqs):
        bucket = -(-len(seq) // PS) * PS
        ids = np.zeros((1, bucket), np.int32)
        ids[0, :len(seq)] = seq
        logits, cache = desc.prefill(params, jnp.asarray(ids),
                                     jnp.asarray(ids > 0, jnp.int32))
        assert set(cache) == {"rows"}
        assert cache["rows"].shape == (4, 1, bucket, LANES)
        got[slot].append(np.asarray(logits)[0])
        table[slot, :width] = [next(free) for _ in range(width)]
        pages, none = kv_pool.write_prompt_pages(
            pages, None, cache, jnp.asarray(table[slot]), jnp.asarray(0),
            PS, jnp.asarray(len(seq)))
        assert none is None
    lens = np.array([len(seqs[0]), 0, len(seqs[1])], np.int32)
    full = [list(s) for s in seqs]
    for _ in range(n_new):
        tokens = np.zeros((3,), np.int32)
        for i, slot in enumerate((0, 2)):
            full[i].append(int(got[slot][-1].argmax()))
            tokens[slot] = full[i][-1]
        logits, pages, none, counters = kv_pool.paged_decode_step(
            params, jnp.asarray(tokens), pages, None, jnp.asarray(table),
            jnp.asarray(lens), cfg, with_counters=True)
        assert none is None
        for slot in (0, 2):
            got[slot].append(np.asarray(logits)[slot])
        lens = lens + (lens > 0)
        assert counters["rows_per_expert"].shape == (2, 2)
        # two live rows, three picks each, a block
        np.testing.assert_array_equal(np.asarray(counters["picks"]), [6, 6])
    for i, slot in enumerate((0, 2)):
        want = _ref_logits(flat, sizes, np.asarray(full[i]))
        assert np.abs(want).max() > 0.5
        np.testing.assert_allclose(
            np.stack(got[slot]), want[len(seqs[i]) - 1:], atol=3e-4)
    # behind a row's 40 lanes the bank holds zeros
    assert not np.asarray(pages)[..., LANES:].any()


def test_requests_of_several_lengths_are_served_the_references_tokens(model):
    """Six requests over three slots (every slot handed on, to a request
    of another length), prompts from 2 to 41 tokens."""
    cfg, params, flat, sizes, prompts = model
    reg = MetricsRegistry()
    reg.enable()
    eng = _engine(cfg, params, registry=reg)
    outs, metrics = eng.run(_requests(prompts))
    for out in outs:
        _assert_the_references_picks(flat, sizes, out.prompt, out.generated)
    assert eng.pool.used_count == 0 and eng.sched.all_done()
    assert metrics["prefills"] == 6
    # the latent walks count as any global walk does
    assert 0 < metrics["decode_key_share"] < 1
    assert set(metrics["pages_by_kind"]) == {"global"}
    experts = metrics["experts"]
    # 2 held experts x 2 blocks; picks that cost nothing beside them
    assert experts["held_a_step"] == 4
    assert 0 < experts["touched_share"] <= 1
    assert 0 < experts["zero_pick_share"] < 1
    assert reg.gauge("serving.zero_pick_share").value > 0
    assert "state" not in metrics


def test_the_pool_is_one_bank_and_no_program_takes_a_second(model):
    cfg, params, _, _, prompts = model
    eng = _engine(cfg, params)
    desc = eng.model
    assert desc.latent.lanes == LANES and desc.latent.value_lanes == 32
    assert desc.latent.q_heads == 4 and desc.banks == 1
    assert desc.latent.scale == (16 + 8) ** -0.5
    # two attentions a block: a bank layer each
    assert desc.layers_of("global") == 4 and desc.n_layer == 2
    assert eng.v_pages is None and eng.state == {}
    assert eng.k_pages.shape == (4, 48, PS, STORED)
    assert len(eng._pool()) == 1
    carry = jnp.zeros((eng._carry_size,), jnp.int32)
    low = eng._step.lower(params, carry, eng.k_pages)
    main = next(x for x in low.as_text().splitlines()
                if "public @main(" in x)
    # the weights, the carry and ONE bank: nothing stands in for a second
    assert main.count("%arg") == len(jax.tree_util.tree_leaves(params)) + 2
    assert main.count("tensor<4x48x4x128xf32>") == 2      # in, and out
    with pytest.raises(TypeError):
        eng._step.lower(params, carry, eng.k_pages, eng.k_pages)
    ids = jnp.zeros((1, 8), jnp.int32)
    cache = jax.eval_shape(eng._prefill, params, ids, ids)[1]
    assert jax.tree_util.tree_map(lambda x: x.shape, cache) == {
        "rows": (4, 1, 8, LANES)}
    text = eng._write.lower(
        eng.k_pages, cache, jnp.zeros((CONTEXT // PS,), jnp.int32),
        jnp.asarray(0), jnp.asarray(5)).as_text()
    main = next(x for x in text.splitlines() if "public @main(" in x)
    assert main.count("%arg") == 5
    # the engine goes on serving with the one bank
    outs, _ = eng.run(_requests(prompts)[:2])
    assert eng.v_pages is None and len(outs) == 2


def test_page_bytes_come_from_the_description(model):
    cfg, params, _, _, _ = model
    rep = _engine(cfg, params).memory_report()
    kv = rep["kv"]
    # one bank of 4 bank layers x 48 pages x 4 rows x 128 stored lanes
    assert kv["total_bytes"] == 4 * 48 * PS * STORED * 4
    assert kv["bytes_per_page"] == 4 * PS * STORED * 4
    assert kv["page_capacity_ratio"] == 1.0
    assert kv["by_kind"]["global"] == {
        "layers": 4, "num_pages": 48, "fp_bytes": kv["total_bytes"]}


def test_preemption_and_readmission_serve_the_same_tokens(model):
    cfg, params, flat, sizes, prompts = model
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
        _assert_the_references_picks(flat, sizes, out.prompt, out.generated)
    assert metrics["prefills"] == 5 and eng.pool.used_count == 0


def test_profile_and_doctor_take_the_one_bank(model):
    cfg, params, _, _, prompts = model
    eng = _engine(cfg, params)
    paths = [b.path for b in eng.doctor().sharding.buffers]
    assert any(p.startswith("k_pages") for p in paths)
    assert not any(p.startswith("v_pages") for p in paths)
    eng.profile(steps=1, warmup=1)
    assert eng.v_pages is None
    outs, _ = eng.run(_requests(prompts)[:1])
    assert len(outs[0].generated) == MIXED[0][1]


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
def test_a_model_with_a_latent_row_refuses_the_mode_by_name(model, mode):
    cfg, params, _, _, _ = model
    named = "prefill_chunk|prefill_only" if mode == "prefill_only" else mode
    with pytest.raises(ValueError, match=f"({named}) is not built for a "
                                         f"model with a latent row a token"):
        _engine(cfg, params, **REFUSED[mode])


def test_the_paged_programs_refuse_what_the_engine_refuses(model):
    cfg, params, _, _, _ = model
    desc = describe(cfg)
    with pytest.raises(ValueError, match="no int8 bank"):
        kv_pool.init_pages(desc, 16, PS, kv_dtype="int8")
    with pytest.raises(ValueError, match="one shard"):
        kv_pool.init_pages(desc, 16, PS, tp=2)
    with pytest.raises(ValueError, match="served on one device"):
        cfg.paged_model("tensor")
    assert _engine(cfg, params, kv_dtype="fp", weight_dtype="fp",
                   prefix_cache=False).v_pages is None


def test_a_stacked_group_attends_once_a_layer(model):
    import dataclasses

    cfg, params, _, _, _ = model
    desc = describe(cfg)
    stacked = dataclasses.replace(desc, groups=tuple(
        dataclasses.replace(g, stacked=True) for g in desc.groups))
    pages, _ = kv_pool.init_pages(desc, 16, PS)
    i32 = jnp.int32
    with pytest.raises(ValueError, match="its group is not stacked"):
        kv_pool.paged_decode_step(
            params, jnp.zeros((2,), i32), pages, None,
            jnp.zeros((2, 16), i32), jnp.zeros((2,), i32), stacked)


def test_the_programs_carry_the_models_scopes(model):
    """The named scopes a traced run shows (PERF.md §3) are in the
    lowered programs' own locations."""
    cfg, params, _, _, _ = model
    eng = _engine(cfg, params)
    carry = jnp.zeros((eng._carry_size,), jnp.int32)
    step = eng._step.lower(params, carry, eng.k_pages).as_text(debug_info=True)
    for scope in ("mla.proj", "mla.absorb", "moe.route", "moe.zero",
                  "moe.dispatch", "moe.experts", "moe.combine",
                  "scmoe.shortcut"):
        assert scope in step, scope
    ids = jnp.zeros((1, 8), jnp.int32)
    prefill = eng._prefill.lower(params, ids, ids).as_text(debug_info=True)
    assert "mla.attn" in prefill and "mla.absorb" not in prefill
