"""ONE attention over TWO caches through ``ServingEngine`` at a small size
on the CPU: a model whose window layers keep a ring of exact keys under
the BLOCK rule and, in global pages, a pooled key and value a chunk of
every closed window (EvaByte), under the same scheduler, tick, allocator
and carry as the other served families. Logits of prefill + decode
through both banks against the benchmark's plain reference, across
chunk, page, walk and WINDOW boundaries; a summary written once a chunk;
global pages a row a chunk; page bytes; slots handed on; preemption; the
walks' counters; the rules the description can say; every opt-in mode
refused by name."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import program_evabyte as adapter
from benchmark import weights_evabyte as weights
from benchmark.reference import evabyte_ref as ref
from pipegoose_tpu.serving import Request, ServingEngine, kv_pool
from pipegoose_tpu.serving.blocks import (
    BLOCK,
    GLOBAL,
    SLIDING,
    WINDOW,
    Summaries,
    describe,
    ring_pages,
    summaries_seen,
    window_start,
)
from pipegoose_tpu.serving.scheduler import Status
from pipegoose_tpu.telemetry import MetricsRegistry

W, C = 64, 16                         # window, chunk = page size
PS, CONTEXT, WALK = C, 448, 16        # a page a trip of a walk
RING = W // PS                        # a block window's own pages
TABLE = -(-CONTEXT // (C * PS))       # global entries a slot: 28 chunks
# a prompt over three windows ending inside a chunk; one ending on a
# window's last byte; short ones; one decoding across two windows' ends
MIXED = [(3 * W + 37, 45), (W, 30), (9, 20), (2 * W + 2, 70), (300, 100),
         (17, 5)]
CONFIG = {
    "vocab_size": 40, "hidden_size": 64, "intermediate_size": 96,
    "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 4, "num_pred_heads": 8, "window_size": W,
    "chunk_size": C, "num_chunks": None, "rope_theta": 100000,
    "rope_scaling": None, "rms_norm_eps": 1e-5, "norm_add_unit_offset": True,
    "fp32_skip_add": True, "fp32_logits": True, "fp32_ln": False,
    "mixedp_attn": True, "attention_bias": False, "attention_class": "eva",
    "hidden_act": "silu", "tie_word_embeddings": False, "init_std": 0.1,
    "init_fn": "v2", "init_cutoff_factor": None, "lazy_init": True,
    "max_position_embeddings": 32768, "max_seq_length": 32768,
    "model_type": "evabyte", "dtype": "float32",
    "phi_std": 1.0, "mu_std": 0.25,
}


@pytest.fixture(scope="module")
def model():
    sizes = adapter.sizes(CONFIG)
    flat = weights.make(weights.seed_key(3), sizes, jnp.float32)
    rng = np.random.RandomState(7)
    return (adapter.make_config(CONFIG), adapter.to_tree(flat, CONFIG), flat,
            sizes, [rng.randint(0, 40, (s,)) for s, _ in MIXED])


@pytest.fixture(autouse=True)
def short_walk(monkeypatch):
    # a page a trip: the ring is four trips, 300 positions' summaries two
    monkeypatch.setattr(kv_pool, "WALK_KEYS", WALK)


def _engine(cfg, params, **kw):
    kw = {"num_slots": 3, "num_pages": 8, "page_size": PS,
          "max_context": CONTEXT, **kw}
    return ServingEngine(params, cfg, **kw)


def _requests(prompts):
    return [Request(prompt=p, max_new_tokens=n)
            for p, (_, n) in zip(prompts, MIXED)]


def _ref_logits(flat, sizes, tokens):
    """Head 0's logits, which a step serves."""
    return np.asarray(ref.forward(flat, jnp.asarray(tokens), sizes))[:, 0]


def _assert_the_references_picks(flat, sizes, prompt, generated):
    tokens = np.concatenate([prompt, generated])
    logits = _ref_logits(flat, sizes, tokens)
    np.testing.assert_array_equal(
        generated, logits.argmax(-1)[len(prompt) - 1:len(tokens) - 1])


def _prefilled(model, lengths, slots=3):
    """Banks and tables with a prompt of each of ``lengths`` prefilled
    into slots 0, 2, .. (a dead slot between), and the logits each
    prefill gave."""
    cfg, params, _, _, _ = model
    desc = describe(cfg)
    kp, vp = kv_pool.init_pages(desc, 12, PS, window_pages=slots * RING + 1)
    rng = np.random.RandomState(0)
    seqs = [rng.randint(0, 40, (n,)) for n in lengths]
    tables = {GLOBAL: np.zeros((slots, TABLE), np.int32),
              WINDOW: np.zeros((slots, RING), np.int32)}
    free = {GLOBAL: iter(range(1, 12)), WINDOW: iter(range(1, 16))}
    first = {}
    for slot, seq in zip(range(0, slots, 2), seqs):
        bucket = -(-len(seq) // PS) * PS
        ids = np.zeros((1, bucket), np.int32)
        ids[0, :len(seq)] = seq
        mask = (np.arange(bucket) < len(seq)).astype(np.int32)[None]
        logits, cache = desc.prefill(params, jnp.asarray(ids),
                                     jnp.asarray(mask))
        assert set(cache) == {GLOBAL, WINDOW}
        first[slot] = np.asarray(logits)[0]
        for kind, width in ((GLOBAL, TABLE), (WINDOW, RING)):
            tables[kind][slot] = [next(free[kind]) for _ in range(width)]
        kp, vp = kv_pool.write_prompt_pages(
            kp, vp, cache, {k: jnp.asarray(t[slot]) for k, t in tables.items()},
            jnp.asarray(0), PS, jnp.asarray(len(seq)), stride=desc.stride)
    return desc, kp, vp, tables, seqs, first


def test_prefill_then_decode_through_both_banks_gives_the_references_logits(
        model):
    """The model's prefill, the page write and 45 decode steps of two
    rows, logits against the reference's full forward: a row three
    windows and 37 bytes long that decodes across chunk ends (a summary
    written, a page taken: every 16th byte), the walks' trips and a
    WINDOW's end (byte 256: the ring starts over, four more windows'
    worth of summaries become visible at once), a row whose prompt ends
    on a window's last byte (its ring empty, its first query seeing the
    four summaries the prefill wrote), a dead slot between them."""
    cfg, params, flat, sizes, _ = model
    desc, kp, vp, tables, seqs, first = _prefilled(model, (3 * W + 37, W))
    assert {k: v.shape for k, v in kp.items()} == {
        GLOBAL: (2, 12, PS, 64), WINDOW: (2, 3 * RING + 1, PS, 64)}
    got = {slot: [x] for slot, x in first.items()}
    lens = np.array([len(seqs[0]), 0, len(seqs[1])], np.int32)
    full = {0: list(seqs[0]), 2: list(seqs[1])}
    step = jax.jit(lambda *a: kv_pool.paged_decode_step(
        *a, cfg, with_counters=True))
    written = 0
    for _ in range(45):
        tokens = np.zeros((3,), np.int32)
        for slot in (0, 2):
            full[slot].append(int(got[slot][-1].argmax()))
            tokens[slot] = full[slot][-1]
        logits, kp, vp, counters = step(
            params, jnp.asarray(tokens), kp, vp,
            {k: jnp.asarray(t) for k, t in tables.items()}, jnp.asarray(lens))
        for slot in (0, 2):
            got[slot].append(np.asarray(logits)[slot])
        counters = dict(zip(kv_pool.SUMMARY_COUNTERS,
                            np.asarray(counters["summary_rows"])))
        live = lens[lens > 0]
        assert counters["rows_live"] == 2
        # a layer's walks, over the two live rows
        assert int(counters["window_rows_needed"]) == (live % W + 1).sum()
        assert int(counters["summary_rows_needed"]) == \
            (live // W * (W // C)).sum()
        # the ring is walked as far as the furthest row stands INTO its
        # window, a page a trip
        assert int(counters["window_rows_gathered"]) == \
            2 * ((live % W).max() // WALK + 1) * WALK
        written += int(counters["summaries_written"])
        assert int(counters["summaries_written"]) == \
            ((live + 1) % C == 0).sum()
        lens = lens + (lens > 0)
    for i, slot in enumerate((0, 2)):
        want = _ref_logits(flat, sizes, np.asarray(full[slot]))
        assert np.abs(want).max() > 0.5
        np.testing.assert_allclose(
            np.stack(got[slot]), want[len(seqs[i]) - 1:], atol=3e-5)
    # every chunk completed while decoding was written, and written ONCE
    assert written == sum((n + 45) // C - n // C for n in (3 * W + 37, W))


def test_the_summary_bank_holds_the_references_summaries_a_row_a_chunk(model):
    """After a prefill of 229 bytes and decoding to 274: row ``c`` of a
    slot's global pages is the reference's summary of chunk ``c`` for
    each of the 17 complete chunks (14 from the prefill, 3 written by
    the steps that completed them), and nothing was written past them."""
    cfg, params, flat, sizes, _ = model
    n = 3 * W + 37
    desc, kp, vp, tables, seqs, first = _prefilled(model, (n,), slots=1)
    tokens, logits = list(seqs[0]), first[0]
    lens = np.array([n], np.int32)
    for _ in range(45):
        tokens.append(int(logits.argmax()))
        out, kp, vp = kv_pool.paged_decode_step(
            params, jnp.asarray(tokens[-1:]), kp, vp,
            {k: jnp.asarray(t) for k, t in tables.items()}, jnp.asarray(lens),
            cfg)
        logits, lens = np.asarray(out)[0], lens + 1
    total = int(lens[0])
    # layer 0's rotated keys and values by the reference's own functions
    from benchmark.reference import laguna_ref
    from pipegoose_tpu.models import evabyte as eb

    x = eb.rms1({"scale": flat["ln1"][0]},
                flat["embed"][np.asarray(tokens[:total])], 1e-5, jnp.float32)
    k = laguna_ref._rope((x @ flat["k"][0]).reshape(total, 4, 16),
                         {"rope_theta": sizes["rope_theta"]})
    v = (x @ flat["v"][0]).reshape(total, 4, 16)
    whole = total // C
    k_sum, v_sum = ref.summaries(k[:whole * C], v[:whole * C], flat["phi"][0],
                                 flat["mu"][0], sizes)
    rows_k = kv_pool.gather_pages(kp[GLOBAL][0], jnp.asarray(tables[GLOBAL]),
                                  16)[0]
    rows_v = kv_pool.gather_pages(vp[GLOBAL][0], jnp.asarray(tables[GLOBAL]),
                                  16)[0]
    assert whole == 17
    np.testing.assert_allclose(np.asarray(rows_k)[:whole], np.asarray(k_sum),
                               atol=3e-5)
    np.testing.assert_allclose(np.asarray(rows_v)[:whole], np.asarray(v_sum),
                               atol=3e-5)
    assert not np.asarray(rows_k)[whole:].any()


def test_requests_of_several_lengths_are_served_the_references_tokens(model):
    """Six requests over three slots (every slot handed on, to a request
    of another length), prompts from 9 to 300 bytes, one ending on a
    window's last byte, outputs that cross up to two windows' ends."""
    cfg, params, flat, sizes, prompts = model
    reg = MetricsRegistry()
    reg.enable()
    eng = _engine(cfg, params, registry=reg)
    outs, metrics = eng.run(_requests(prompts))
    for out in outs:
        _assert_the_references_picks(flat, sizes, out.prompt, out.generated)
    assert eng.pool.used_count == 0 and eng.sched.all_done()
    assert metrics["prefills"] == 6
    assert set(metrics["pages_by_kind"]) == {GLOBAL, WINDOW}
    # 400 positions' summaries lie in two global pages; a ring is whole
    assert metrics["pages_by_kind"][GLOBAL]["peak_in_use"] <= 5
    assert metrics["pages_by_kind"][WINDOW]["peak_in_use"] == 3 * RING
    assert metrics["window_pages_recycled"] > 0
    eva = metrics["eva"]
    assert 0 < eva["rows_useful_share"] < 1
    assert eva["rows_useful_share"] == pytest.approx(
        (eva["window_rows_needed"] + eva["summary_rows_needed"])
        / (eva["window_rows_gathered"] + eva["summary_rows_gathered"]),
        abs=1e-6)
    assert 0 < eva["summary_key_share"] < 1
    # every chunk that ended while decoding, of every request
    assert eva["summaries_written"] == sum(
        (s + n - 1) // C - s // C for s, n in MIXED)
    assert reg.gauge("serving.eva_rows_useful_share").value == \
        eva["rows_useful_share"]
    assert reg.gauge("serving.eva_summary_key_share").value == \
        eva["summary_key_share"]
    share = metrics["decode_key_share_by_kind"]
    assert 0 < share[GLOBAL] < 1 and 0 < share[WINDOW] <= 1


def test_global_pages_grow_a_page_every_256_positions(model):
    """A global page holds ``page_size`` summaries of ``chunk`` positions
    each: the allocator counts it so, the scheduler grows it so, and the
    bucket a prompt is forwarded in stays whole pages of POSITIONS."""
    cfg, params, _, _, prompts = model
    eng = _engine(cfg, params)
    pool = eng.pool
    assert pool.stride == C and eng.table_width == TABLE == 2
    assert [pool.pages_for(n) for n in (1, 16, 256, 257, 448)] == \
        [1, 1, 1, 2, 2]
    assert [pool.pages_for(n, WINDOW) for n in (1, 17, 64, 65, 448)] == \
        [1, 2, 4, 4, 4]
    assert [pool.logical_pages(n) for n in (1, 17, 300)] == [1, 2, 19]
    req = Request(prompt=prompts[3], max_new_tokens=200)    # 130 + 200
    eng.start_run([req], now=lambda: 0.0)
    grown = {}
    while not eng.sched.all_done():
        eng.tick_once()
        if req.status is Status.DECODE:
            grown.setdefault(len(req.pages), req.cached_len)
            # the positions written so far, a row a chunk
            assert len(req.pages) == pool.pages_for(req.cached_len)
            assert len(req.window_pages) == RING
    eng.finish_run()
    # the second page is taken by the step that writes position 256
    assert grown == {1: 131, 2: 257}
    assert eng._first_call_s.keys() >= {("prefill", 144), ("write", 144)}
    assert pool.used_count == 0


def test_page_bytes_and_the_steps_arguments_come_from_the_description(model):
    cfg, params, _, _, _ = model
    eng = _engine(cfg, params)
    desc = eng.model
    assert desc.kinds == (GLOBAL, WINDOW) and desc.stride == C
    assert desc.layers_of(GLOBAL) == desc.layers_of(WINDOW) == 2
    assert desc.window == W and desc.window_rule == BLOCK
    assert desc.summaries.chunk == C
    kv = eng.memory_report()["kv"]
    page = PS * 64 * 4                  # rows x lanes x float32, a bank
    assert kv["by_kind"] == {
        GLOBAL: {"layers": 2, "num_pages": 8, "fp_bytes": 2 * 2 * 8 * page},
        WINDOW: {"layers": 2, "num_pages": 3 * RING + 1,
                 "fp_bytes": 2 * 2 * (3 * RING + 1) * page}}
    assert kv["total_bytes"] == sum(k["fp_bytes"]
                                    for k in kv["by_kind"].values())
    # both tables reach the step in its one packed buffer
    assert eng._carry_size == 3 * (2 + TABLE + RING)
    carry = jnp.zeros((eng._carry_size,), jnp.int32)
    low = eng._step.lower(params, carry, eng.k_pages, eng.v_pages)
    main = next(x for x in low.as_text().splitlines()
                if "public @main(" in x)
    assert main.count("%arg") == len(jax.tree_util.tree_leaves(params)) + 5


def test_preemption_and_readmission_serve_the_same_tokens(model):
    cfg, params, flat, sizes, prompts = model
    calm, _ = _engine(cfg, params).run(_requests(prompts)[:3])

    def preempt(engine, tick):
        if tick in (6, 23):
            live = [r for r in engine.sched.active()
                    if r.status is Status.DECODE]
            engine.sched.preempt(live[0])

    eng = _engine(cfg, params)
    outs, metrics = eng.run(_requests(prompts)[:3], tick_hook=preempt)
    for out, want in zip(outs, calm):
        np.testing.assert_array_equal(out.generated, want.generated)
        _assert_the_references_picks(flat, sizes, out.prompt, out.generated)
    # a preempted request re-prefills prompt + generated bytes: its ring
    # and every complete chunk's summary are made again
    assert metrics["prefills"] == 5 and eng.pool.used_count == 0


def test_the_programs_carry_the_scopes(model):
    cfg, params, _, _, _ = model
    eng = _engine(cfg, params)
    carry = jnp.zeros((eng._carry_size,), jnp.int32)
    step = eng._step.lower(params, carry, eng.k_pages,
                           eng.v_pages).as_text(debug_info=True)
    for scope in ("attn.rope", "eva.pool", "eva.summary_write",
                  "eva.read.window", "eva.read.summary"):
        assert scope in step, scope
    assert "eva.attn" not in step
    ids = jnp.zeros((1, 2 * W), jnp.int32)
    prefill = eng._prefill.lower(params, ids, ids).as_text(debug_info=True)
    assert "eva.attn" in prefill and "eva.read.window" not in prefill


# -- what the description can say --------------------------------------------

def _dense(q, k, v, k_sum, v_sum, pos, window, rule, chunk):
    """One query a row over its own sequence's keys and summaries."""
    out = []
    for b in range(q.shape[0]):
        t = int(pos[b])
        first = max(int(window_start(t, window, rule)), 0)
        seen = int(summaries_seen(t, window, chunk, rule))
        keys = np.concatenate([k[b, first:t + 1], k_sum[b, :seen]])
        vals = np.concatenate([v[b, first:t + 1], v_sum[b, :seen]])
        s = np.einsum("hd,nhd->hn", q[b], keys) * q.shape[-1] ** -0.5
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        out.append(np.einsum("hn,nhd->hd", p, vals).reshape(-1))
    return np.stack(out)


@pytest.mark.parametrize("with_summaries", [True, False],
                         ids=["summaries", "ring-alone"])
@pytest.mark.parametrize("rule", [BLOCK, SLIDING])
def test_either_window_rule_reads_with_summaries_or_without(rule,
                                                            with_summaries):
    """A sliding window with summaries and a block window without are
    said by the same fields and read by the same walks."""
    rng = np.random.RandomState(5)
    ring = ring_pages(W, PS, rule)       # 4 under "block", 5 "sliding"
    nh, hd, n = 2, 8, 200
    pos = np.array([199, 70, 5])
    k, v = (rng.randn(3, n, nh, hd).astype(np.float32) for _ in range(2))
    k_sum, v_sum = (rng.randn(3, n // C, nh, hd).astype(np.float32)
                    for _ in range(2))
    q = rng.randn(3, nh, hd).astype(np.float32)
    banks = {GLOBAL: np.zeros((1, 8, PS, nh * hd), np.float32),
             WINDOW: np.zeros((1, 16, PS, nh * hd), np.float32)}
    vbanks = {kind: b.copy() for kind, b in banks.items()}
    tables = {GLOBAL: np.zeros((3, 1), np.int32),
              WINDOW: np.zeros((3, ring), np.int32)}
    for b in range(3):
        tables[GLOBAL][b] = [1 + b]
        tables[WINDOW][b] = 1 + b * ring + np.arange(ring)
        banks[GLOBAL][0, 1 + b, :n // C] = k_sum[b].reshape(n // C, -1)
        vbanks[GLOBAL][0, 1 + b, :n // C] = v_sum[b].reshape(n // C, -1)
        for t in range(max(0, pos[b] - ring * PS + 1), pos[b] + 1):
            page = tables[WINDOW][b, t // PS % ring]
            banks[WINDOW][0, page, t % PS] = k[b, t].reshape(-1)
            vbanks[WINDOW][0, page, t % PS] = v[b, t].reshape(-1)
    args = (jnp.asarray(q)[:, None],
            {kind: jnp.asarray(x) for kind, x in banks.items()},
            {kind: jnp.asarray(x) for kind, x in vbanks.items()})
    at = jnp.asarray(pos)[:, None]
    if with_summaries:
        got = kv_pool._attend_summarised(
            *args, {GLOBAL: 0, WINDOW: 0},
            {kind: jnp.asarray(t) for kind, t in tables.items()}, at,
            jnp.float32, W, rule, C)
        want = _dense(q, k, v, k_sum, v_sum, pos, W, rule, C)
    else:
        got = kv_pool._attend_rows(
            args[0], args[1][WINDOW], args[2][WINDOW], 0,
            jnp.asarray(tables[WINDOW]), at, None, None, jnp.float32, W, rule)
        want = _dense(q, k, v, k_sum[:, :0], v_sum[:, :0], pos, W, rule, C)
    np.testing.assert_allclose(np.asarray(got)[:, 0], want, atol=1e-5)
    if rule == BLOCK and not with_summaries:
        # a block window's ring is its own pages in order, no page more
        with pytest.raises(ValueError, match="its own pages in order"):
            kv_pool._attend_rows(
                args[0], args[1][WINDOW], args[2][WINDOW], 0,
                jnp.zeros((3, ring + 1), jnp.int32), at, None, None,
                jnp.float32, W, rule)


def test_which_summaries_a_query_sees_follows_from_the_rule():
    block = [summaries_seen(t, 2048, 16, BLOCK)
             for t in (0, 2047, 2048, 4095, 4096, 26623)]
    assert block == [0, 0, 128, 128, 256, 12 * 128]
    sliding = [summaries_seen(t, 2048, 16, SLIDING)
               for t in (0, 2047, 2048, 2062, 2063, 4096)]
    assert sliding == [0, 0, 0, 0, 1, 128]
    assert int(window_start(5000, 2048, BLOCK)) == 4096
    assert int(window_start(5000, 2048, SLIDING)) == 2953
    assert ring_pages(2048, 16) == 129
    # a block window is its own whole pages, in order
    assert ring_pages(2048, 16, BLOCK) == 128
    with pytest.raises(ValueError, match="whole number of pages"):
        ring_pages(60, 16, BLOCK)


def test_the_description_refuses_what_it_cannot_hold(model):
    cfg, _, _, _, _ = model
    desc = describe(cfg)
    group = desc.groups[0]
    with pytest.raises(ValueError, match="beside a window layer's ring"):
        dataclasses.replace(desc, groups=(
            dataclasses.replace(group, kind=GLOBAL),))
    with pytest.raises(ValueError, match="global layers beside summaries"):
        dataclasses.replace(desc, groups=(group, dataclasses.replace(
            group, kind=GLOBAL, summaries=None)))
    with pytest.raises(ValueError, match="window_rule"):
        dataclasses.replace(desc, window_rule="strided")
    # a chunk has to be a page: the summary is pooled from ONE page
    other = dataclasses.replace(desc, groups=(dataclasses.replace(
        group, summaries=Summaries(chunk=8, pool=group.summaries.pool)),))
    with pytest.raises(ValueError, match="has to be the page size"):
        ServingEngine(model[1], other, num_slots=2, num_pages=8,
                      page_size=PS, max_context=CONTEXT).run(
            [Request(prompt=np.arange(5), max_new_tokens=3)])


REFUSED = {
    "prefix_cache": {"prefix_cache": True},
    "speculative": {"speculative": (1, 2)},
    "prefill_chunk": {"prefill_chunk": 16},
    "kv_dtype": {"kv_dtype": "int8"},
    "weight_dtype": {"weight_dtype": "int8"},
    "host_tier": {"host_tier": object(), "prefix_cache": False},
    "prefill_only": {"prefill_only": True, "prefill_chunk": 16},
    "mesh": {"mesh": object()},
    "memledger": {"memledger": True},
}


@pytest.mark.parametrize("mode", sorted(REFUSED))
def test_a_ring_with_summaries_refuses_the_mode_by_name(model, mode):
    cfg, params, _, _, _ = model
    named = "prefill_chunk|prefill_only" if mode == "prefill_only" else mode
    with pytest.raises(ValueError, match=f"({named}) is not built for a "
                                         f"model with 2 cache kinds"):
        _engine(cfg, params, **REFUSED[mode])


def test_the_paged_programs_refuse_what_the_engine_refuses(model):
    cfg, params, _, _, _ = model
    desc = describe(cfg)
    kp, vp = kv_pool.init_pages(desc, 8, PS, window_pages=2 * RING + 1)
    i32 = jnp.int32
    tables = {GLOBAL: jnp.zeros((2, TABLE), i32),
              WINDOW: jnp.zeros((2, RING), i32)}
    with pytest.raises(ValueError, match="write_ok caps a draft"):
        kv_pool.paged_decode_step(
            params, jnp.zeros((2,), i32), kp, vp, tables, jnp.zeros((2,), i32),
            cfg, write_ok=jnp.ones((2,), bool))
    with pytest.raises(ValueError, match="a prefill chunk reads one cache "
                                         "kind"):
        kv_pool.paged_prefill_chunk(
            params, jnp.zeros((2, 4), i32), kp, vp, tables,
            jnp.zeros((2,), i32), jnp.ones((2,), i32), cfg)
    with pytest.raises(ValueError, match="served on one device"):
        cfg.paged_model("tensor")
