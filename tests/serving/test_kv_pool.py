"""Paged KV-pool invariants: the free-list allocator never double-hands
a page, reclaims everything, and places pages deterministically; the
page-table gather/scatter reconstructs exactly what a contiguous cache
holds. These are the serving layer's memory-safety bedrock — a paging
bug shows up as silent cross-request KV corruption, not a crash."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pipegoose_tpu.distributed import ParallelContext
from pipegoose_tpu.models import bloom, generate as gen
from pipegoose_tpu.models.generate import forward_cached, init_cache
from pipegoose_tpu.serving import (
    NULL_PAGE,
    PagePool,
    Request,
    ServingEngine,
    gather_pages,
    init_pages,
    write_prompt_pages,
)
from pipegoose_tpu.serving.kv_pool import paged_decode_step

# n_head x head_dim: rows narrower than, equal to and wider than the 128
# lanes the pool's layout is about (bloom-560m is 16 x 64, bloom-1b7
# 16 x 128)
HEADS = {"4x16": (4, 16), "2x64": (2, 64), "2x128": (2, 128)}


# --- allocator --------------------------------------------------------------


def test_alloc_never_hands_out_null_or_duplicate():
    pool = PagePool(num_pages=17, page_size=4)
    seen = set()
    while pool.free_count:
        (p,) = pool.alloc(1)
        assert p != NULL_PAGE
        assert p not in seen, "double allocation"
        seen.add(p)
    assert len(seen) == pool.capacity == 16


def test_exhaustion_raises_and_free_restores():
    pool = PagePool(num_pages=9, page_size=4)
    pages = pool.alloc(8)
    with pytest.raises(RuntimeError, match="exhausted"):
        pool.alloc(1)
    pool.free(pages)
    assert pool.free_count == pool.capacity == 8
    assert pool.used_count == 0


def test_free_unowned_page_rejected():
    pool = PagePool(num_pages=9, page_size=4)
    with pytest.raises(RuntimeError, match="not allocated"):
        pool.free([3])
    pages = pool.alloc(2)
    pool.free(pages)
    with pytest.raises(RuntimeError, match="not allocated"):
        pool.free(pages)  # double free


def test_full_reclamation_after_interleaved_lifecycle():
    """Arbitrary alloc/free interleaving ends with every page back."""
    pool = PagePool(num_pages=33, page_size=8)
    rng = np.random.RandomState(0)
    live = []
    for _ in range(200):
        if live and (rng.rand() < 0.5 or pool.free_count < 4):
            pool.free(live.pop(rng.randint(len(live))))
        else:
            live.append(pool.alloc(int(rng.randint(1, 4))))
    for pages in live:
        pool.free(pages)
    assert pool.used_count == 0
    assert sorted(pool._free) == list(range(1, 33))


def test_placement_deterministic_under_eviction_order():
    """LIFO free list: the same submit/evict sequence yields the same
    physical placement, run after run (the reproducibility contract the
    scheduler's FIFO admission relies on)."""

    def run():
        pool = PagePool(num_pages=17, page_size=4)
        a = pool.alloc(3)
        b = pool.alloc(2)
        pool.free(a)
        c = pool.alloc(4)  # re-uses a's pages, LIFO order
        return a, b, c, list(pool.history)

    assert run() == run()


def test_pages_for_rounding():
    pool = PagePool(num_pages=5, page_size=16)
    assert pool.pages_for(1) == 1
    assert pool.pages_for(16) == 1
    assert pool.pages_for(17) == 2
    assert pool.pages_for(32) == 2


# --- gather / scatter reconstruction ---------------------------------------


def _config(heads, n_layer):
    nh, hd = HEADS[heads]
    return bloom.BloomConfig(vocab_size=64, hidden_size=nh * hd,
                             n_layer=n_layer, n_head=nh)


def _model(heads, n_layer=3):
    cfg = _config(heads, n_layer)
    return cfg, bloom.init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(scope="module", params=sorted(HEADS))
def tiny(request):
    return _model(request.param)


def _prefill(cfg, params, prompt, bucket):
    """forward_cached over a LEFT-padded prompt: (logits, cache, pad)."""
    pad = bucket - len(prompt)
    ids = np.zeros((1, bucket), np.int32)
    ids[0, pad:] = prompt
    mask = np.zeros((1, bucket), np.int32)
    mask[0, pad:] = 1
    logits, cache = forward_cached(
        params, jnp.asarray(ids), init_cache(cfg, 1, bucket), 0, cfg,
        extras={"mask": jnp.asarray(mask)},
    )
    return logits, cache, pad


def test_write_prompt_pages_reconstructs_contiguous_cache(tiny):
    """Scatter a LEFT-padded prefill cache into pages, gather it back
    through the page table — byte-identical to the unpadded cache rows,
    and the null page is untouched garbage territory."""
    cfg, params = tiny
    page_size, s, pad = 4, 9, 3  # 9 real tokens in a 12-slot bucket
    _, cache, _ = _prefill(cfg, params, np.arange(1, s + 1), s + pad)
    k_pages, v_pages = init_pages(cfg, num_pages=8, page_size=page_size)
    # a position's heads share one lane-dense row
    assert k_pages.shape == (cfg.n_layer, 8, page_size, cfg.hidden_size)
    phys = np.zeros((4,), np.int32)
    phys[:3] = [5, 2, 7]  # 3 pages cover 9 tokens, deliberately unordered
    k_pages, v_pages = write_prompt_pages(
        k_pages, v_pages, cache, jnp.asarray(phys), pad, page_size
    )

    table = jnp.asarray(phys)[None]  # (1, W)
    # (L, 1, W*ps, nh, hd): values come back split into heads
    got_k = np.asarray(gather_pages(k_pages, table, cfg.head_dim))
    got_v = np.asarray(gather_pages(v_pages, table, cfg.head_dim))
    want_k = np.asarray(cache["k"])[:, :, pad:]  # unpadded layout
    want_v = np.asarray(cache["v"])[:, :, pad:]
    np.testing.assert_array_equal(got_k[:, :, :s], want_k)
    np.testing.assert_array_equal(got_v[:, :, :s], want_v)
    # pad positions routed to the null page — no allocated page holds them
    np.testing.assert_array_equal(
        np.asarray(k_pages)[:, [1, 3, 4, 6]], 0.0
    )


def test_write_routes_padding_to_null_page(tiny):
    """Every pad position's write lands on page 0, so a future owner of
    any REAL page never sees another request's garbage."""
    cfg, params = tiny
    page_size, s, pad = 4, 5, 3
    _, cache, _ = _prefill(cfg, params, np.arange(1, s + 1), s + pad)
    k_pages, v_pages = init_pages(cfg, num_pages=8, page_size=page_size)
    phys = np.zeros((2,), np.int32)
    phys[:2] = [3, 6]
    k_pages, _ = write_prompt_pages(
        k_pages, v_pages, cache, jnp.asarray(phys), pad, page_size
    )
    k_np = np.asarray(k_pages)
    untouched = [p for p in range(1, 8) if p not in (3, 6)]
    np.testing.assert_array_equal(k_np[:, untouched], 0.0)


# --- the decode step over the in-place pool ---------------------------------


def _paged_rows(cfg, params, prompts, page_size, width, num_pages):
    """Prefill each prompt into its own pages: (k_pages, v_pages, table,
    seq_lens, first tokens)."""
    k_pages, v_pages = init_pages(cfg, num_pages, page_size)
    table = np.zeros((len(prompts), width), np.int32)
    nxt, first = 1, []
    for r, prompt in enumerate(prompts):
        n = -(-len(prompt) // page_size)
        table[r, :n] = range(nxt, nxt + n)
        nxt += n + 1      # leave a page for the row to grow into
        table[r, n] = nxt - 1
        logits, cache, pad = _prefill(cfg, params, prompt, width * page_size)
        k_pages, v_pages = write_prompt_pages(
            k_pages, v_pages, cache, jnp.asarray(table[r]), pad, page_size)
        first.append(int(jnp.argmax(logits[0])))
    seq = np.asarray([len(p) for p in prompts], np.int32)
    return k_pages, v_pages, jnp.asarray(table), seq, first


def test_decode_steps_match_generate(tiny):
    """Two ragged rows decoded through the page tables: every step's
    logits match the contiguous-cache decode of each row alone, and the
    greedy stream is generate()'s."""
    cfg, params = tiny
    rng = np.random.RandomState(1)
    prompts = [rng.randint(1, 64, (n,)) for n in (9, 5)]
    ps, width, steps = 4, 5, 4
    kp, vp, table, seq, first = _paged_rows(
        cfg, params, prompts, ps, width, num_pages=12)
    tok = jnp.asarray(first, jnp.int32)
    streams = [[t] for t in first]
    for i in range(steps - 1):
        logits, kp, vp = paged_decode_step(
            params, tok, kp, vp, table, jnp.asarray(seq + i), cfg)
        for r, prompt in enumerate(prompts):
            # the same sequence through forward_cached, uncached
            ids = np.concatenate([prompt, streams[r]])[None]
            want, _ = forward_cached(
                params, jnp.asarray(ids), init_cache(cfg, 1, ids.shape[1]),
                0, cfg)
            np.testing.assert_allclose(np.asarray(logits[r]),
                                       np.asarray(want[0]), atol=2e-4)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        for r in range(len(prompts)):
            streams[r].append(int(tok[r]))
    for r, prompt in enumerate(prompts):
        out = gen.generate(params, jnp.asarray(prompt)[None], cfg,
                           max_new_tokens=steps)
        assert streams[r] == list(np.asarray(out)[0, len(prompt):])


def _two_rows_mid_page(cfg, seed):
    """A pool of random values (so an untouched row is known by its
    bytes) and two rows about to write (page 4, offset 1) and (page 5,
    offset 2): (k_pages, v_pages, table, seq_lens, tokens)."""
    rng = np.random.RandomState(seed)
    shape = init_pages(cfg, 6, 4)[0].shape
    kp = jnp.asarray(rng.randn(*shape), jnp.float32)
    vp = jnp.asarray(rng.randn(*shape), jnp.float32)
    table = jnp.asarray([[2, 4], [5, 1]], jnp.int32)
    return (kp, vp, table, jnp.asarray([5, 2], jnp.int32),
            jnp.asarray([7, 9], jnp.int32))


def test_draft_layers_leave_deeper_layers_untouched(tiny):
    """``draft_layers=1`` is the layer loop's bound: one row a slot
    lands in layer 0, every deeper layer's plane is byte-for-byte what
    it was, and the logits are a one-block model's."""
    cfg, params = tiny
    kp, vp, table, seq, tok = _two_rows_mid_page(cfg, seed=2)
    logits, k1, v1 = paged_decode_step(params, tok, kp, vp, table, seq, cfg,
                                       draft_layers=1)
    for before, after in ((kp, k1), (vp, v1)):
        before, after = np.asarray(before), np.asarray(after)
        np.testing.assert_array_equal(after[1:], before[1:])
        changed = np.argwhere((after[0] != before[0]).any(-1))
        assert sorted(map(tuple, changed)) == [(4, 1), (5, 2)]
    shallow = jax.tree_util.tree_map(lambda a: a, params)
    shallow["blocks"] = jax.tree_util.tree_map(lambda a: a[:1],
                                               params["blocks"])
    want, _, _ = paged_decode_step(shallow, tok, kp[:1], vp[:1], table, seq,
                                   cfg)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want),
                               atol=1e-5)


def test_write_ok_false_routes_the_row_to_the_null_page(tiny):
    """A row with ``write_ok=False`` writes the NULL page's first row
    in every layer and nothing of its own pages."""
    cfg, params = tiny
    kp, vp, table, seq, tok = _two_rows_mid_page(cfg, seed=3)
    _, k1, _ = paged_decode_step(
        params, tok, kp, vp, table, seq, cfg,
        write_ok=jnp.asarray([True, False]))
    before, after = np.asarray(kp), np.asarray(k1)
    for layer in range(cfg.n_layer):
        changed = np.argwhere((after[layer] != before[layer]).any(-1))
        assert sorted(map(tuple, changed)) == [(NULL_PAGE, 0), (4, 1)]


@pytest.mark.parametrize("kv_dtype", [None, "int8"], ids=["fp", "int8"])
@pytest.mark.parametrize("heads", sorted(HEADS))
def test_tp2_head_sharded_rows_match_generate(devices, heads, kv_dtype):
    """The pool's rows sharded over the tensor axis give each shard its
    nh/2 heads: a tp=2 engine on the CPU mesh emits generate()'s
    tokens (int8 KV: the single-device int8 engine's)."""
    cfg, params = _model(heads, n_layer=2)
    rng = np.random.RandomState(5)
    reqs = [(rng.randint(1, 64, (n,)), m) for n, m in ((9, 5), (6, 4))]
    kw = dict(num_slots=2, num_pages=16, page_size=4, max_context=32,
              kv_dtype=kv_dtype)

    def run(eng):
        outs, _ = eng.run([Request(prompt=p, max_new_tokens=m)
                           for p, m in reqs])
        return [list(o.generated) for o in outs]

    if kv_dtype is None:
        want = [list(np.asarray(gen.generate(
            params, jnp.asarray(p)[None], cfg, max_new_tokens=m))[0, len(p):])
            for p, m in reqs]
    else:
        want = run(ServingEngine(params, cfg, **kw))
    ctx = ParallelContext(tensor_parallel_size=2, data_parallel_size=4)
    try:
        eng = ServingEngine(params, cfg, mesh=ctx.mesh,
                            param_specs=bloom.tp_specs(params), **kw)
        leaf = jax.tree_util.tree_leaves(eng.k_pages)[0]
        assert leaf.sharding.shard_shape(leaf.shape)[-1] == \
            cfg.hidden_size // 2
        assert run(eng) == want
    finally:
        ctx.destroy()


# -- int8 pools: transferred-in pages mixed with local writes (ISSUE 13) ----
#
# The disagg wire ships q + scale planes verbatim
# (export_page_slab/import_page_slab); a decode-pool page table then
# mixes transferred-in pages with locally written ones. The scale
# plane must ride EVERY path — gather, COW copy, export/import — or
# dequantization silently corrupts exactly one page's values.


def _int8_pool(cfg, num_pages=9, ps=4):
    kp, vp = init_pages(cfg, num_pages, ps, kv_dtype="int8")
    assert kp["q"].shape == (cfg.n_layer, num_pages, ps, cfg.hidden_size)
    assert kp["scale"].shape == (cfg.n_layer, num_pages, ps, cfg.n_head)
    return kp, vp


def _fake_cache(cfg, s, seed):
    rng = np.random.RandomState(seed)
    shape = (cfg.n_layer, 1, s, cfg.n_head, cfg.head_dim)
    return {"k": jnp.asarray(rng.randn(*shape).astype(np.float32)),
            "v": jnp.asarray(rng.randn(*shape).astype(np.float32))}


@pytest.mark.parametrize("heads", sorted(HEADS))
def test_int8_export_import_roundtrip_preserves_q_and_scale(heads):
    from pipegoose_tpu.serving.kv_pool import (
        export_page_slab,
        import_page_slab,
    )

    cfg = _config(heads, n_layer=2)
    kp, vp = _int8_pool(cfg)
    phys = np.zeros((4,), np.int32)
    phys[:2] = [1, 2]
    kp, vp = write_prompt_pages(kp, vp, _fake_cache(cfg, 8, 0), phys,
                                pad=0, page_size=4)
    ids = jnp.asarray([1, 2], jnp.int32)
    k_slab = export_page_slab(kp, ids, cfg.head_dim)
    v_slab = export_page_slab(vp, ids, cfg.head_dim)
    # the wire is q + scale, at wire dtypes — never fp
    assert set(k_slab) == {"q", "scale"}
    assert k_slab["q"].dtype == jnp.int8
    assert k_slab["scale"].dtype == jnp.float32
    # ... and in its own shape, heads apart, whatever the pool's rows are
    assert k_slab["q"].shape == (2, 2, 4, cfg.n_head, cfg.head_dim)
    assert k_slab["scale"].shape == (2, 2, 4, cfg.n_head)
    dst = jnp.asarray([5, 6], jnp.int32)
    kp = import_page_slab(kp, k_slab, dst)
    vp = import_page_slab(vp, v_slab, dst)
    for bank, src_ids in ((kp, [1, 2]),):
        np.testing.assert_array_equal(
            np.asarray(bank["q"][:, [5, 6]]), np.asarray(bank["q"][:, src_ids])
        )
        np.testing.assert_array_equal(
            np.asarray(bank["scale"][:, [5, 6]]),
            np.asarray(bank["scale"][:, src_ids]),
        )


@pytest.mark.parametrize("heads", sorted(HEADS))
def test_int8_gather_over_mixed_transferred_and_local_pages(heads):
    """A page table mixing transferred-in pages (5, 6) with a locally
    written one (3) dequantizes to exactly what the all-local table
    (1, 2, 3) does — transferred pages are first-class pool citizens."""
    from pipegoose_tpu.serving.kv_pool import (
        export_page_slab,
        import_page_slab,
    )

    cfg = _config(heads, n_layer=2)
    kp, vp = _int8_pool(cfg)
    phys = np.zeros((4,), np.int32)
    phys[:2] = [1, 2]
    kp, vp = write_prompt_pages(kp, vp, _fake_cache(cfg, 8, 0), phys,
                                pad=0, page_size=4)
    phys_b = np.zeros((4,), np.int32)
    phys_b[0] = 3
    kp, vp = write_prompt_pages(kp, vp, _fake_cache(cfg, 4, 1), phys_b,
                                pad=0, page_size=4)
    k_slab = export_page_slab(kp, jnp.asarray([1, 2], jnp.int32),
                              cfg.head_dim)
    v_slab = export_page_slab(vp, jnp.asarray([1, 2], jnp.int32),
                              cfg.head_dim)
    kp = import_page_slab(kp, k_slab, jnp.asarray([5, 6], jnp.int32))
    vp = import_page_slab(vp, v_slab, jnp.asarray([5, 6], jnp.int32))
    mixed = jnp.asarray([[5, 6, 3]], jnp.int32)
    local = jnp.asarray([[1, 2, 3]], jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(gather_pages(kp, mixed, cfg.head_dim)),
        np.asarray(gather_pages(kp, local, cfg.head_dim)),
    )
    np.testing.assert_array_equal(
        np.asarray(gather_pages(vp, mixed, cfg.head_dim)),
        np.asarray(gather_pages(vp, local, cfg.head_dim)),
    )


@pytest.mark.parametrize("heads", sorted(HEADS))
def test_int8_copy_page_of_transferred_page_carries_scale_plane(heads):
    """COW duplication of a transferred-in page copies its scale plane
    WITH the values — a reader of the copy dequantizes byte-identically
    to a reader of the source."""
    from pipegoose_tpu.serving import copy_page
    from pipegoose_tpu.serving.kv_pool import (
        export_page_slab,
        import_page_slab,
    )

    cfg = _config(heads, n_layer=2)
    kp, vp = _int8_pool(cfg)
    phys = np.zeros((4,), np.int32)
    phys[0] = 1
    kp, vp = write_prompt_pages(kp, vp, _fake_cache(cfg, 4, 2), phys,
                                pad=0, page_size=4)
    k_slab = export_page_slab(kp, jnp.asarray([1], jnp.int32),
                              cfg.head_dim)
    v_slab = export_page_slab(vp, jnp.asarray([1], jnp.int32),
                              cfg.head_dim)
    kp = import_page_slab(kp, k_slab, jnp.asarray([5], jnp.int32))
    vp = import_page_slab(vp, v_slab, jnp.asarray([5], jnp.int32))
    kp, vp = copy_page(kp, vp, jnp.asarray(5, jnp.int32),
                       jnp.asarray(7, jnp.int32))
    for bank in (kp, vp):
        np.testing.assert_array_equal(np.asarray(bank["q"][:, 7]),
                                      np.asarray(bank["q"][:, 5]))
        np.testing.assert_array_equal(np.asarray(bank["scale"][:, 7]),
                                      np.asarray(bank["scale"][:, 5]))
    np.testing.assert_array_equal(
        np.asarray(gather_pages(kp, jnp.asarray([[7]], jnp.int32), cfg.head_dim)),
        np.asarray(gather_pages(kp, jnp.asarray([[1]], jnp.int32), cfg.head_dim)),
    )


# -- the decode read over the rows as stored (PR 32) -------------------------
#
# ``_attend_rows`` contracts the gathered rows (B, K, nh*hd) against a
# block-diagonal query, a chunk of whole pages at a time, as far as the
# furthest live query. Its oracle is what the read was before: the
# reconstruction (gather_pages) through _attn_core under _key_bias.

READ_PS, READ_W, READ_WALK = 4, 7, 8     # 7 pages of 4: 3.5 chunks of 8 keys
READ_FULL = READ_PS * READ_W - 1         # the table's last position
# the largest valid position of each of three rows, at the walk's seams
# (chunk = 8 keys); a row at 0 with no page of its own is a dead slot
READ_LENGTHS = {
    "empty": (0, 0, 0),
    "one": (1, 0, 0),
    "chunk-1": (READ_WALK - 1, 3, 0),
    "chunk": (READ_WALK, 0, READ_WALK - 1),
    "chunk+1": (READ_WALK + 1, READ_WALK, 1),
    "full_among_dead": (READ_FULL, 0, 0),
}


def _oracle_read(q, k_pages, v_pages, layer, page_table, pos, qmask, slopes,
                 out_dtype, window=None, rule="sliding"):
    """``_attend_rows``'s signature over the reconstructed view."""
    from pipegoose_tpu.serving import kv_pool

    hd = q.shape[-1]
    k_l, v_l = jax.tree_util.tree_map(lambda a: a[layer], (k_pages, v_pages))
    bias = kv_pool._key_bias(
        slopes, pos, page_table.shape[1] * kv_pool.page_size_of(k_pages))
    return gen._attn_core(q, gather_pages(k_l, page_table, hd),
                          gather_pages(v_l, page_table, hd), bias, qmask,
                          out_dtype)


def _random_banks(cfg, num_pages, kv_dtype, seed, poison=None):
    """Two banks of random values; page ``poison`` all NaN (an int8
    bank's scales), so a read that touches it shows."""
    from pipegoose_tpu.serving.kv_pool import _rows, quantize_kv

    rng = np.random.RandomState(seed)
    shape = (cfg.n_layer, num_pages, READ_PS, cfg.n_head, cfg.head_dim)

    def bank():
        vals = rng.randn(*shape).astype(np.float32)
        if kv_dtype is None:
            if poison is not None:
                vals[:, poison] = np.nan
            return _rows(jnp.asarray(vals))
        q, scale = quantize_kv(jnp.asarray(vals))
        if poison is not None:
            scale = scale.at[:, poison].set(jnp.nan)
        return {"q": _rows(q), "scale": scale}

    return bank(), bank()


@pytest.mark.parametrize("lengths", sorted(READ_LENGTHS))
@pytest.mark.parametrize("c", [1, 3], ids=["c1", "c3"])
@pytest.mark.parametrize("kv_dtype", [None, "int8"], ids=["fp", "int8"])
@pytest.mark.parametrize("heads", sorted(HEADS))
def test_row_read_matches_the_reconstruction(monkeypatch, heads, kv_dtype, c,
                                             lengths):
    """The read over rows as stored gives the reconstruction's context
    at every seam of the walk, and touches no page of a chunk past the
    furthest live query: those hold NaN here."""
    from pipegoose_tpu.serving import kv_pool

    monkeypatch.setattr(kv_pool, "WALK_KEYS", READ_WALK)
    cfg = _config(heads, n_layer=2)
    nh, hd = cfg.n_head, cfg.head_dim
    poison = 11
    kp, vp = _random_banks(cfg, 12, kv_dtype, seed=len(lengths), poison=poison)
    last = np.asarray(READ_LENGTHS[lengths])
    n_valid = np.minimum(c, last + 1)
    start = last - (n_valid - 1)
    pos = jnp.asarray(start[:, None] + np.arange(c)[None, :], jnp.int32)
    qmask = None if c == 1 else jnp.asarray(
        np.arange(c)[None, :] < n_valid[:, None])
    rng = np.random.RandomState(7)
    # a live row owns pages up to its last position; a dead one none
    table = np.zeros((3, READ_W), np.int32)
    for r, n in enumerate(last):
        if n or r == 0:
            owned = n // READ_PS + 1
            table[r, :owned] = rng.choice(np.arange(1, poison), owned,
                                          replace=False)
    pages, _ = kv_pool.walk_plan(READ_PS, READ_W)
    walked = kv_pool.walked_chunks(int(last.max()), pages * READ_PS) * pages
    poisoned = table.copy()
    poisoned[:, walked:] = poison
    q = jnp.asarray(rng.randn(3, c, nh, hd), jnp.float32)
    slopes = jnp.asarray(bloom.alibi_slopes(nh))
    got = kv_pool._attend_rows(q, kp, vp, 1, jnp.asarray(poisoned), pos,
                               qmask, slopes, jnp.float32)
    want = _oracle_read(q, kp, vp, 1, jnp.asarray(table), pos, qmask, slopes,
                        jnp.float32)
    assert got.shape == (3, c, nh * hd)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


@pytest.mark.parametrize("program", ["step", "chunk"])
@pytest.mark.parametrize("kv_dtype", [None, "int8"], ids=["fp", "int8"])
@pytest.mark.parametrize("heads", sorted(HEADS))
def test_paged_programs_match_the_reconstruction_read(monkeypatch, heads,
                                                      kv_dtype, program):
    """The decode step (one row ``write_ok=False``) and a ragged prefill
    chunk through the whole layer loop: logits and both banks are what
    the reconstruction read gives, walked a chunk of two pages at a
    time over rows that end in different chunks."""
    from pipegoose_tpu.serving import kv_pool

    monkeypatch.setattr(kv_pool, "WALK_KEYS", READ_WALK)
    cfg, params = _model(heads, n_layer=2)
    kp, vp = _random_banks(cfg, 12, kv_dtype, seed=3)
    table = jnp.asarray([[2, 4, 7, 9, 0, 0, 0], [5, 1, 0, 0, 0, 0, 0],
                         [0] * READ_W], jnp.int32)
    seq = jnp.asarray([13, 6, 0], jnp.int32)

    def run():
        if program == "step":
            return paged_decode_step(
                params, jnp.asarray([7, 9, 0], jnp.int32), kp, vp, table, seq,
                cfg, write_ok=jnp.asarray([True, False, True]))
        ids = jnp.asarray(np.random.RandomState(4).randint(1, 64, (3, 3)))
        return kv_pool.paged_prefill_chunk(
            params, ids, kp, vp, table, seq, jnp.asarray([3, 2, 0]), cfg,
            all_logits=True)

    got = run()
    monkeypatch.setattr(kv_pool, "_attend_rows", _oracle_read)
    want = run()
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               atol=2e-4)
    for g, w in zip(jax.tree_util.tree_leaves(got[1:]),
                    jax.tree_util.tree_leaves(want[1:])):
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32), atol=2e-4)
