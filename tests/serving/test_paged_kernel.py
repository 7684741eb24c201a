"""ServingEngine(attn_kernel="paged") — the fused Pallas paged-
attention decode path (ISSUE 20), pinned with the PR 10 convention:
exact greedy TOKEN identity against the XLA gather reference (never
bitwise logits — the online softmax reassociates fp reductions), for
fp AND int8 pools, at tp in {1, 2}, across cold + warm prefix cache
(incl. the COW mid-page strict-prefix request), chunked prefill, and
speculative decode. Page-table edge cases go through the kernel at the
kv_pool level where the page state is inspectable: null-page routing
under ``write_ok``, a partial last page, and a table mixing
transferred-in (PR 12 slab import) + locally written pages. Plus the
PR 13 attribution pin (gather-vs-kernel step walls rank consistently
between ``profile()`` and the live run) and the doctor report logging
the guard-approved tile geometry."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pipegoose_tpu.distributed import ParallelContext
from pipegoose_tpu.models import bloom, generate as gen
from pipegoose_tpu.serving import Request, ServingEngine
from pipegoose_tpu.serving import kv_pool as kvp
from pipegoose_tpu.telemetry.doctor import DoctorReport, assert_no_resharding

KV_MODES = {"fp": None, "int8": "int8"}
# n_head x head_dim: the pool's rows narrower than, equal to and wider
# than 128 lanes (tests/serving/test_kv_pool.py)
HEADS = {"4x16": (4, 16), "2x64": (2, 64), "2x128": (2, 128)}


@pytest.fixture(scope="module")
def setup():
    cfg = bloom.BloomConfig(vocab_size=64, hidden_size=64, n_layer=2,
                            n_head=4)
    params = bloom.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(7)
    shared = rng.randint(1, 64, (13,))          # 3 full pages + tail @ ps=4
    reqs = [
        (np.concatenate([shared, rng.randint(1, 64, (k,))]), n)
        for k, n in [(3, 6), (5, 4)]
    ] + [
        (shared[:10], 5),                       # strict prefix: COW mid-page
        (rng.randint(1, 64, (7,)), 6),          # unrelated: pure miss
    ]
    return cfg, params, shared, reqs


def _reference(params, cfg, prompt, max_new):
    out = gen.generate(params, jnp.asarray(prompt)[None], cfg,
                       max_new_tokens=max_new)
    return np.asarray(out)[0, len(prompt):]


def _assert_parity(eng, params, cfg, reqs, label):
    outs, metrics = eng.run(
        [Request(prompt=p, max_new_tokens=n) for p, n in reqs]
    )
    for o, (p, n) in zip(outs, reqs):
        np.testing.assert_array_equal(
            o.generated, _reference(params, cfg, p, n),
            err_msg=f"{label}: request {o.uid} diverged from generate()",
        )
    return metrics


def test_attn_kernel_validation(setup):
    cfg, params, _, _ = setup
    with pytest.raises(ValueError, match="attn_impl"):
        ServingEngine(params, cfg, num_slots=1, num_pages=8, page_size=4,
                      max_context=16, attn_kernel="flash")
    eng = ServingEngine(params, cfg, num_slots=1, num_pages=8, page_size=4,
                        max_context=16)
    assert eng.attn_kernel == "gather"   # default OFF: gather unchanged


# --- greedy token identity: the full serving matrix through the kernel ------


@pytest.mark.parametrize("mode", sorted(KV_MODES))
def test_greedy_parity_cold_and_warm(setup, mode):
    """Cold (miss + COW) then warm (shared-page hits) through prefix
    cache + chunked prefill, every attention step on the kernel."""
    cfg, params, _, reqs = setup
    eng = ServingEngine(params, cfg, num_slots=2, num_pages=32,
                        page_size=4, max_context=64, prefix_cache=True,
                        prefill_chunk=8, kv_dtype=KV_MODES[mode],
                        attn_kernel="paged")
    cold = _assert_parity(eng, params, cfg, reqs, f"paged {mode} cold")
    warm = _assert_parity(eng, params, cfg, reqs, f"paged {mode} warm")
    assert warm["prefix_cache"]["hit_tokens"] > 0


def test_speculative_greedy_parity(setup):
    """Draft (write_ok-routed null-page writes) + ragged multi-token
    verify bundles, all through the kernel, int8 pool."""
    cfg, params, _, reqs = setup
    eng = ServingEngine(params, cfg, num_slots=2, num_pages=32,
                        page_size=4, max_context=64, speculative=(1, 3),
                        kv_dtype="int8", attn_kernel="paged")
    m = _assert_parity(eng, params, cfg, reqs, "paged int8 speculative")
    assert m["speculative"]["draft_tokens"] > 0


@pytest.mark.parametrize("mode", sorted(KV_MODES))
def test_tp2_greedy_parity_and_zero_resharding(setup, devices, mode):
    """Head-sharded pages at tp=2: the Pallas call lowers inside
    shard_map with ZERO partitioner resharding (doctor-pinned for both
    the decode step and the chunk program) and the token streams match
    single-device generate()."""
    cfg, params, _, reqs = setup
    ctx = ParallelContext(tensor_parallel_size=2, data_parallel_size=4)
    try:
        eng = ServingEngine(
            params, cfg, num_slots=2, num_pages=32, page_size=4,
            max_context=64, mesh=ctx.mesh,
            param_specs=bloom.tp_specs(params), prefix_cache=True,
            prefill_chunk=8, kv_dtype=KV_MODES[mode], attn_kernel="paged",
        )
        _assert_parity(eng, params, cfg, reqs[:3], f"tp2 paged {mode}")
        step = eng.doctor()
        assert_no_resharding(step)
        assert_no_resharding(eng.doctor_chunk())
        assert step.extras["paged_tile"]["fits"] is True
    finally:
        ctx.destroy()


# --- page-table edge cases through the kernel (kv_pool level) ---------------


@pytest.fixture(scope="module", params=sorted(HEADS))
def pool_state(request):
    """(cfg, params) at one head shape and a prefilled 3-row pool per
    kv mode: full row, mid-page partial row (partial LAST page),
    near-empty row."""
    nh, hd = HEADS[request.param]
    cfg = bloom.BloomConfig(vocab_size=64, hidden_size=nh * hd, n_layer=2,
                            n_head=nh)
    params = bloom.init_params(cfg, jax.random.PRNGKey(0))
    out = {"model": (cfg, params)}
    for mode, kv in KV_MODES.items():
        rng = np.random.RandomState(3)
        kp, vp = kvp.init_pages(cfg, 32, 4, kv_dtype=kv)
        table = jnp.asarray(
            rng.permutation(np.arange(1, 32))[:24].reshape(3, 8), jnp.int32)
        ids = jnp.asarray(rng.randint(1, 64, (3, 8)), jnp.int32)
        n_valid = jnp.asarray([8, 6, 3], jnp.int32)
        _, kp, vp = kvp.paged_prefill_chunk(
            params, ids, kp, vp, table, jnp.zeros((3,), jnp.int32),
            n_valid, cfg)
        out[mode] = (kp, vp, table, n_valid)
    return out


def _leaves(pages):
    return jax.tree_util.tree_leaves(pages)


@pytest.mark.parametrize("mode", sorted(KV_MODES))
def test_partial_last_page_decode_parity(pool_state, mode):
    """Rows whose cursor sits mid-page: the kernel masks the unwritten
    offsets of the last page exactly like the gather bias does —
    logits allclose, greedy token identical."""
    cfg, params = pool_state["model"]
    kp, vp, table, seq = pool_state[mode]
    tok = jnp.asarray([5, 9, 11], jnp.int32)
    ref, rk, rv = kvp.paged_decode_step(params, tok, kp, vp, table, seq, cfg)
    out, ok_, ov = kvp.paged_decode_step(params, tok, kp, vp, table, seq,
                                         cfg, attn_impl="paged")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)
    np.testing.assert_array_equal(np.argmax(np.asarray(out), -1),
                                  np.argmax(np.asarray(ref), -1))
    for a, b in zip(_leaves((rk, rv)), _leaves((ok_, ov))):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-4)


@pytest.mark.parametrize("mode", sorted(KV_MODES))
def test_write_ok_null_page_routing_parity(pool_state, mode):
    """Draft-mode rows with write_ok=False route their writes to the
    NULL page; the kernel's mask never reads them back. Parity on
    logits AND the resulting pools (the PR 6 contract, now through the
    kernel)."""
    cfg, params = pool_state["model"]
    kp, vp, table, seq = pool_state[mode]
    tok = jnp.asarray([5, 9, 11], jnp.int32)
    ok = jnp.asarray([True, False, True])
    ref, rk, rv = kvp.paged_decode_step(
        params, tok, kp, vp, table, seq, cfg, write_ok=ok, draft_layers=1)
    out, ok2, ov = kvp.paged_decode_step(
        params, tok, kp, vp, table, seq, cfg, write_ok=ok, draft_layers=1,
        attn_impl="paged")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)
    for a, b in zip(_leaves((rk, rv)), _leaves((ok2, ov))):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-4)


@pytest.mark.parametrize("mode", sorted(KV_MODES))
def test_mixed_imported_and_local_pages_parity(pool_state, mode):
    """A PR 12-shaped table: pages transferred in from another pool
    (slab export/import at DIFFERENT physical indices) mixed with pages
    the local pool then writes — decode + a follow-up chunk through the
    kernel match the gather reference token-for-token."""
    cfg, params = pool_state["model"]
    kp, vp, table, seq = pool_state[mode]
    src_ids = table[1, :2]               # row 1's first two pages
    dst_ids = jnp.asarray([29, 30], jnp.int32)
    fresh_k, fresh_v = kvp.init_pages(cfg, 32, 4, kv_dtype=KV_MODES[mode])
    fresh_k = kvp.import_page_slab(
        fresh_k, kvp.export_page_slab(kp, src_ids, cfg.head_dim), dst_ids)
    fresh_v = kvp.import_page_slab(
        fresh_v, kvp.export_page_slab(vp, src_ids, cfg.head_dim), dst_ids)
    # imported pages at new physical slots + a locally-written third
    # page, in one row's table
    mixed = jnp.zeros((1, 8), jnp.int32).at[0, 0].set(29).at[0, 1].set(30)
    mixed = mixed.at[0, 2].set(5)
    rng = np.random.RandomState(11)
    ids = jnp.asarray(rng.randint(1, 64, (1, 3)), jnp.int32)
    start = jnp.asarray([6], jnp.int32)   # row 1's valid prefix length
    n_valid = jnp.asarray([3], jnp.int32)
    streams = {}
    for impl in ("gather", "paged"):
        k, v = jax.tree_util.tree_map(lambda x: x, (fresh_k, fresh_v))
        _, k, v = kvp.paged_prefill_chunk(
            params, ids, k, v, mixed, start, n_valid, cfg, attn_impl=impl)
        toks, seq_i = [], start + 3
        t = jnp.asarray([7], jnp.int32)
        for _ in range(4):
            logits, k, v = kvp.paged_decode_step(
                params, t, k, v, mixed, seq_i, cfg, attn_impl=impl)
            t = jnp.argmax(logits, -1).astype(jnp.int32)
            toks.append(int(t[0]))
            seq_i = seq_i + 1
        streams[impl] = toks
    assert streams["gather"] == streams["paged"], streams


# --- PR 13 attribution: the component split moves with the kernel -----------


def test_profile_and_live_step_walls_rank_consistently(setup):
    """The CPU-smoke half of the bench pin: ``profile()``'s measured
    decode-step wall for the gather vs kernel engines must rank the
    same way as the live run's mean decode-step wall (the TPU numbers
    land in the bench artifact). Both engines also report a complete
    compute/comm/idle split that sums to the step wall."""
    cfg, params, _, reqs = setup
    walls = {}
    for impl in ("gather", "paged"):
        eng = ServingEngine(params, cfg, num_slots=2, num_pages=32,
                            page_size=4, max_context=64, kv_dtype="int8",
                            attn_kernel=impl)
        eng.run([Request(prompt=p, max_new_tokens=n) for p, n in reqs])
        _, m = eng.run([Request(prompt=p, max_new_tokens=n)
                        for p, n in reqs])
        prof = eng.profile(steps=3, warmup=1)
        assert prof.wall_step_s > 0
        # a complete split: every component present and non-negative
        # (on a multi-threaded CPU backend summed op times may exceed
        # the fenced wall, so the fractions need not sum to 1 here)
        assert prof.compute_fraction > 0
        assert prof.comm_fraction >= 0 and prof.idle_fraction >= 0
        walls[impl] = {
            "live": m["decode_step_time_s"] / max(m["decode_steps"], 1),
            "profiled": prof.wall_step_s,
        }
    live_ratio = walls["paged"]["live"] / walls["gather"]["live"]
    prof_ratio = walls["paged"]["profiled"] / walls["gather"]["profiled"]
    # rank agreement, with a dead band: if either measurement says the
    # arms are within 25% of each other the ordering is noise on a
    # shared CPU box, not signal
    if abs(live_ratio - 1) > 0.25 and abs(prof_ratio - 1) > 0.25:
        assert (live_ratio > 1) == (prof_ratio > 1), walls


# --- doctor report logs the guard-approved tile geometry --------------------


def test_doctor_logs_tile_geometry(setup):
    cfg, params, _, _ = setup
    eng = ServingEngine(params, cfg, num_slots=2, num_pages=16, page_size=4,
                        max_context=32, kv_dtype="int8",
                        attn_kernel="paged", prefill_chunk=8)
    tile = eng.doctor().extras["paged_tile"]
    assert tile["fits"] is True and tile["quantized"] is True
    assert tile["n_queries"] == 1
    chunk_tile = eng.doctor_chunk().extras["paged_tile"]
    assert chunk_tile["n_queries"] == 8    # the chunk program's C
    # extras survive the artifact round trip (forward-compat contract)
    rt = DoctorReport.from_json(
        json.loads(json.dumps(eng.last_doctor_report.to_json())))
    assert rt.extras["paged_tile"] == chunk_tile
    # gather engines don't grow the field
    plain = ServingEngine(params, cfg, num_slots=2, num_pages=16,
                          page_size=4, max_context=32)
    assert plain.doctor().extras is None
