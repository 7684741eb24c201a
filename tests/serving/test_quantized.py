"""Quantized inference (ISSUE 10): the serving oracle extended to the
``weight_dtype``/``kv_dtype`` knobs. The contract is PINNED greedy
token-identity on this model/seed — int8 per-channel weights, grouped
int4, and int8 per-position KV all reproduce the fp engine's streams
exactly here (divergence on other models is bounded by the perplexity
deltas below) — across the whole serving feature matrix: cold+warm
prefix cache, COW mid-page tails, evict→re-admit, and tp=2. Plus the
capacity meters the acceptance criteria quote: ``memory_report()``'s
page-capacity ratio and the doctor's zero-resharding + by-dtype HBM
split. Knobs-off stays byte-identical (same param objects, fp pool)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pipegoose_tpu.distributed import ParallelContext
from pipegoose_tpu.models import bloom, generate as gen
from pipegoose_tpu.quant import QuantSpec, quantize_params
from pipegoose_tpu.serving import Request, ServingEngine, Status
from pipegoose_tpu.serving.kv_pool import (
    dequantize_kv,
    gather_pages,
    init_pages,
    quantize_kv,
    write_prompt_pages,
)
from pipegoose_tpu.telemetry import MetricsRegistry
from pipegoose_tpu.telemetry.doctor import assert_no_resharding

# n_head x head_dim: the pool's rows narrower than, equal to and wider
# than 128 lanes (tests/serving/test_kv_pool.py)
HEADS = {"4x16": (4, 16), "2x64": (2, 64), "2x128": (2, 128)}

QUANT_MODES = {
    "int8w": dict(weight_dtype="int8"),
    "int4w": dict(weight_dtype="int4", weight_group_size=16),
    "int8kv": dict(kv_dtype="int8"),
    "int8w+int8kv": dict(weight_dtype="int8", kv_dtype="int8"),
}


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


# --- knobs-off: the PR 1/6 engine, untouched --------------------------------


def test_default_engine_is_unquantized(setup):
    """No knobs -> the exact fp engine: the param tree is passed
    through by OBJECT (quantize_params never runs) and the KV pool is
    a bare fp array pair, so every existing byte-identity pin over the
    default engine covers this path."""
    cfg, params, _, _ = setup
    eng = ServingEngine(params, cfg, num_slots=2, num_pages=16,
                        page_size=4, max_context=32)
    assert eng.weight_dtype is None and eng.kv_dtype is None
    assert eng.params is params
    # "fp" is the explicit alias on BOTH knobs (a planner row's
    # candidate dict feeds straight back into the constructor)
    alias = ServingEngine(params, cfg, num_slots=2, num_pages=16,
                          page_size=4, max_context=32,
                          weight_dtype="fp", kv_dtype="fp")
    assert alias.weight_dtype is None and alias.kv_dtype is None
    assert alias.params is params
    assert (eng.params["blocks"]["mlp"]["up"]["kernel"]
            is params["blocks"]["mlp"]["up"]["kernel"])
    assert isinstance(eng.k_pages, jax.Array)
    assert eng.k_pages.dtype == cfg.dtype


def test_kv_dtype_validation(setup):
    cfg, params, _, _ = setup
    with pytest.raises(ValueError, match="kv_dtype"):
        ServingEngine(params, cfg, num_slots=1, num_pages=8, page_size=4,
                      max_context=16, kv_dtype="int4")
    with pytest.raises(ValueError, match="weight_dtype"):
        ServingEngine(params, cfg, num_slots=1, num_pages=8, page_size=4,
                      max_context=16, weight_dtype="fp8")


# --- KV round-trip ----------------------------------------------------------


def test_kv_quantize_round_trip_bound():
    """Per-(position, head) symmetric int8: error <= scale/2, and the
    all-zero rows a fresh pool is full of survive (tiny-clamped scale,
    exact zero round-trip)."""
    x = jax.random.normal(jax.random.PRNGKey(1), (6, 4, 16))
    q, s = quantize_kv(x)
    assert q.dtype == jnp.int8 and s.shape == (6, 4)
    err = jnp.abs(dequantize_kv(q, s) - x)
    assert bool(jnp.all(err <= 0.5 * s[..., None] + 1e-7))
    qz, sz = quantize_kv(jnp.zeros((2, 3, 8)))
    assert bool(jnp.all(qz == 0)) and bool(jnp.all(sz > 0))
    np.testing.assert_array_equal(np.asarray(dequantize_kv(qz, sz)),
                                  np.zeros((2, 3, 8), np.float32))


@pytest.mark.parametrize("heads", sorted(HEADS))
def test_int8_bank_round_trip_through_the_pool(heads):
    """The int8 bank keeps its values in the pool's lane-dense rows and
    its scales one per (position, head): a prompt written and gathered
    back is exactly dequantize(quantize(cache)), within scale/2 of the
    cache, and the capacity meter reads hd*4/(hd+4) off the live bank."""
    nh, hd = HEADS[heads]
    cfg = bloom.BloomConfig(vocab_size=64, hidden_size=nh * hd, n_layer=2,
                            n_head=nh)
    kp, vp = init_pages(cfg, 8, 4, kv_dtype="int8")
    assert kp["q"].shape == (2, 8, 4, nh * hd) and kp["q"].dtype == jnp.int8
    assert kp["scale"].shape == (2, 8, 4, nh)
    rng = np.random.RandomState(0)
    cache = {n: jnp.asarray(rng.randn(2, 1, 10, nh, hd), jnp.float32)
             for n in ("k", "v")}
    phys = jnp.asarray([5, 2, 7, 0], jnp.int32)
    kp, vp = write_prompt_pages(kp, vp, cache, phys, 0, 4)
    for bank, name in ((kp, "k"), (vp, "v")):
        got = np.asarray(gather_pages(bank, phys[None], hd))[:, 0, :10]
        q, s = quantize_kv(cache[name][:, 0])
        np.testing.assert_array_equal(got, np.asarray(dequantize_kv(q, s)))
        err = np.abs(got - np.asarray(cache[name][:, 0]))
        assert (err <= 0.5 * np.asarray(s)[..., None] + 1e-7).all()
    eng = ServingEngine(bloom.init_params(cfg, jax.random.PRNGKey(0)), cfg,
                        num_slots=1, num_pages=8, page_size=4,
                        max_context=16, kv_dtype="int8")
    assert eng.memory_report()["kv"]["page_capacity_ratio"] == \
        pytest.approx(hd * 4 / (hd + 4), abs=1e-3)


# --- greedy parity: single device, the full mode matrix ---------------------


@pytest.mark.parametrize("mode", sorted(QUANT_MODES))
def test_greedy_parity_single_device(setup, mode):
    cfg, params, _, reqs = setup
    eng = ServingEngine(params, cfg, num_slots=2, num_pages=32,
                        page_size=4, max_context=64, **QUANT_MODES[mode])
    _assert_parity(eng, params, cfg, reqs, mode)


def test_perplexity_delta_within_contract(setup):
    """The accuracy contract docs/serving.md quotes: the REAL quantized
    forward (dequant-fused matmul) moves perplexity by < 1% at int8 and
    < 5% at grouped int4 on held-out tokens."""
    cfg, params, _, _ = setup
    ids = jax.random.randint(jax.random.PRNGKey(3), (2, 24), 1, 64)
    mask = jnp.ones_like(ids)
    base = float(bloom.loss_fn(params, ids, mask, ids, cfg))
    for spec, bound in ((QuantSpec("int8"), 0.01),
                        (QuantSpec("int4", 16), 0.05)):
        qp = quantize_params(params, spec)
        delta = abs(np.exp(float(bloom.loss_fn(qp, ids, mask, ids, cfg))
                           - base) - 1.0)
        assert delta < bound, (
            f"{spec.weight_dtype} ppl moved {delta:.4f} >= {bound}"
        )


# --- quant x prefix cache / COW / eviction ----------------------------------


def test_quant_cache_cold_and_warm_token_identical(setup):
    """int8 weights + int8 KV under the full cached+chunked stack: the
    cold run populates the cache with QUANTIZED pages, the warm run
    reuses them (hit tokens > 0) — tokens identical both times,
    including the COW mid-page strict-prefix request."""
    cfg, params, _, reqs = setup
    eng = ServingEngine(params, cfg, num_slots=2, num_pages=32,
                        page_size=4, max_context=64, prefix_cache=True,
                        prefill_chunk=8, weight_dtype="int8",
                        kv_dtype="int8")
    cold = _assert_parity(eng, params, cfg, reqs, "quant cold")
    warm = _assert_parity(eng, params, cfg, reqs, "quant warm")
    assert warm["prefix_cache"]["hit_tokens"] > 0
    assert warm["prefill_tokens"] < cold["prefill_tokens"]
    assert eng.pool.used_count == eng.prefix_cache.cached_pages


def test_quant_evict_and_readmit_matches_uninterrupted(setup):
    """Preempt a decoding request mid-stream on the int8 engine: its
    pages (values + scale planes) are dropped, re-admission re-prefills
    through the quantized path, and the stream is unchanged."""
    cfg, params, shared, _ = setup
    eng = ServingEngine(params, cfg, num_slots=2, num_pages=32,
                        page_size=4, max_context=64, prefix_cache=True,
                        prefill_chunk=8, kv_dtype="int8")
    eng.run([Request(prompt=shared, max_new_tokens=4)])       # warm cache
    free_before = eng.pool.free_count
    state = {"hits": 0}

    def preempt_once(engine, tick):
        if state["hits"]:
            return
        for r in engine.sched.active():
            if r.status is Status.DECODE and len(r.generated) >= 3:
                engine.sched.preempt(r)
                state["hits"] += 1
                return

    outs, metrics = eng.run(
        [Request(prompt=shared, max_new_tokens=8)], tick_hook=preempt_once
    )
    assert state["hits"] == 1 and metrics["prefills"] == 2
    np.testing.assert_array_equal(
        outs[0].generated, _reference(params, cfg, shared, 8),
        err_msg="int8 KV evict -> re-admit changed the token stream",
    )
    assert eng.pool.free_count == free_before


# --- capacity + doctor meters -----------------------------------------------


def test_memory_report_page_capacity_ratio(setup):
    """The >= 1.8x acceptance meter, measured off the LIVE pool arrays:
    at fp32/head_dim=16 an int8 page (values + fp32 scale plane) is
    exactly hd*4/(hd+4) = 3.2x smaller. Weights halve too, and the
    gauges land in the registry."""
    cfg, params, _, _ = setup
    reg = MetricsRegistry(enabled=True)
    fp = ServingEngine(params, cfg, num_slots=2, num_pages=16,
                       page_size=4, max_context=32)
    q = ServingEngine(params, cfg, num_slots=2, num_pages=16,
                      page_size=4, max_context=32,
                      weight_dtype="int8", kv_dtype="int8")
    fp_mem, q_mem = fp.memory_report(reg), q.memory_report(reg)
    assert fp_mem["kv"]["page_capacity_ratio"] == 1.0
    ratio = q_mem["kv"]["page_capacity_ratio"]
    assert ratio == pytest.approx(3.2) and ratio >= 1.8
    assert (q_mem["kv"]["bytes_per_page"]
            < fp_mem["kv"]["bytes_per_page"] / 1.8)
    assert (q_mem["weights"]["total_bytes"]
            < fp_mem["weights"]["total_bytes"] / 1.8)
    gauges = reg.snapshot()["gauges"]
    assert (gauges["serving.hbm.weights_bytes"]
            == q_mem["weights"]["total_bytes"])
    assert gauges["serving.hbm.kv_bytes"] == q_mem["kv"]["total_bytes"]
    assert gauges["serving.hbm.kv_page_capacity_ratio"] == pytest.approx(3.2)


def test_doctor_zero_resharding_and_dtype_split(setup):
    """The compiled quantized decode step carries no partitioner
    resharding, and the memory report's by-dtype split shows the int8
    params and pages next to their fp32 scale remnants."""
    cfg, params, _, _ = setup
    eng = ServingEngine(params, cfg, num_slots=2, num_pages=16,
                        page_size=4, max_context=32,
                        weight_dtype="int8", kv_dtype="int8")
    report = eng.doctor()
    assert_no_resharding(report)
    by = report.memory.by_dtype
    assert by["params"]["int8"] > by["params"]["float32"]
    assert by["k_pages"]["int8"] > by["k_pages"]["float32"]
    assert "int8" in report.memory.format_table()


# --- tp=2 -------------------------------------------------------------------


def test_tp2_quant_parity_and_doctor(setup, devices):
    """tp=2 shard_map serving with int8 weights (q + scale sharded by
    the derived specs) AND int8 head-sharded KV pages under the full
    cached+chunked stack: cold+warm token identity with single-device
    generate(), zero partitioner resharding in the compiled step."""
    cfg, params, _, reqs = setup
    ctx = ParallelContext(tensor_parallel_size=2, data_parallel_size=4)
    try:
        eng = ServingEngine(
            params, cfg, num_slots=2, num_pages=32, page_size=4,
            max_context=64, mesh=ctx.mesh,
            param_specs=bloom.tp_specs(params), prefix_cache=True,
            prefill_chunk=8, weight_dtype="int8", kv_dtype="int8",
        )
        _assert_parity(eng, params, cfg, reqs[:3], "tp2 cold")
        warm = _assert_parity(eng, params, cfg, reqs[:3], "tp2 warm")
        assert warm["prefix_cache"]["hit_tokens"] > 0
        assert_no_resharding(eng.doctor())
    finally:
        ctx.destroy()


def test_tp2_int4_group_guard(setup, devices):
    """int4 groups straddling a shard boundary fail at CONSTRUCTION
    with the per-shard dims in the message, not inside shard_map."""
    cfg, params, _, _ = setup
    ctx = ParallelContext(tensor_parallel_size=2, data_parallel_size=4)
    try:
        with pytest.raises(ValueError, match="per-shard contraction"):
            ServingEngine(
                params, cfg, num_slots=1, num_pages=8, page_size=4,
                max_context=16, mesh=ctx.mesh,
                param_specs=bloom.tp_specs(params),
                weight_dtype="int4", weight_group_size=48,
            )
    finally:
        ctx.destroy()
