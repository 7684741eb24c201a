"""Single-chip perf sweep on the real TPU: flash block sizes + model
config levers (remat, flash on/off) for the bloom-560m bench shape.

Timing as in bench.py: every timed region ends in block_until_ready.
One process per chip: run one sweep at a time.

    python scripts/sweep_tpu_perf.py \
        [kernel|model|fusedce|serving|comm|plan|control-plane|disagg]
    python scripts/sweep_tpu_perf.py serving --prefix-replay   # ISSUE 6:
        # Zipf shared-prefix replay arms (baseline / chunked / cached /
        # cached+spec) per slot count instead of the continuous-vs-
        # static A/B
    python scripts/sweep_tpu_perf.py serving --quant   # ISSUE 10: add
        # int8w / int8kv / int8w+int8kv arms (tokens/s, TTFT, HBM,
        # page-capacity ratio vs the fp rows); composes with
        # --prefix-replay
    python scripts/sweep_tpu_perf.py serving --paged   # ISSUE 20: add
        # the fused Pallas paged-attention arm (gather vs kernel
        # tokens/s + profiled decode-step component split at the
        # bloom-560m geometry)
    python scripts/sweep_tpu_perf.py plan   # ISSUE 7: static layout
        # ranking (pipegoose_tpu/planner/), then measure ONLY the
        # top-K (PLAN_TOP_K) and record predicted-vs-measured deltas
        # in the PLAN_JSON artifact
    python scripts/sweep_tpu_perf.py control-plane   # ISSUE 12: the
        # multi-tenant replay through round-robin vs cache-aware
        # routing at 2 and 4 replicas — forwarded prefill tokens,
        # TTFT, tenant shares, drain zero-drop verdict
    python scripts/sweep_tpu_perf.py disagg   # ISSUE 13: prefill pool
        # streaming KV pages into a decode pool vs one monolithic
        # engine — token identity, decode-pool tokens/s vs the
        # decode-only rate, wire-vs-fp byte savings, fp + int8 KV
"""
from __future__ import annotations

import functools
import json
import os as _os
import sys
import time

# runnable from anywhere: the repo root is the import root
sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def timed_chain(step_fn, x0, iters):
    """step_fn: x -> x (same shape/dtype). Returns ms/iter."""

    @jax.jit
    def chain(x):
        def body(c, _):
            return step_fn(c), ()
        o, _ = lax.scan(body, x, None, length=iters)
        return jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float32).sum(), o
        )

    jax.block_until_ready(chain(x0))  # compile+warm
    t0 = time.perf_counter()
    jax.block_until_ready(chain(x0))
    return (time.perf_counter() - t0) / iters * 1e3


def kernel_sweep():
    from pipegoose_tpu.ops import flash_attention as fa

    b, s, nh, hd = 8, 2048, 16, 64
    key = jax.random.PRNGKey(0)
    kq, kk, kv_ = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, s, nh, hd), jnp.bfloat16)
    k = jax.random.normal(kk, (b, s, nh, hd), jnp.bfloat16)
    v = jax.random.normal(kv_, (b, s, nh, hd), jnp.bfloat16)
    slopes = jnp.asarray([2.0 ** (-(i + 1)) for i in range(nh)], jnp.float32)

    results = {}
    orig = fa._pick_blocks
    for bq in (128, 256, 512):
        for bk in (128, 256, 512, 1024):
            if bq > s or bk > s:
                continue

            # the production call sites take (block_q, block_k) from
            # _pick_blocks(seq, head_dim, itemsize, kind, vmem limit):
            # override that
            fa._pick_blocks = lambda *shape, _b=(bq, bk): _b

            def fwd(x):
                return fa.flash_attention(
                    x, k, v, alibi_slopes=slopes, causal=True, interpret=False
                ).astype(jnp.bfloat16)

            def fwdbwd(x):
                return jax.grad(
                    lambda y: (fwd(y).astype(jnp.float32) ** 2).sum()
                )(x).astype(jnp.bfloat16)

            try:
                ms_f = timed_chain(fwd, q, 20)
                ms_fb = timed_chain(fwdbwd, q, 10)
                results[f"bq{bq}_bk{bk}"] = {
                    "fwd_ms": round(ms_f, 3), "fwd_bwd_ms": round(ms_fb, 3)
                }
            except Exception as e:  # noqa: BLE001
                results[f"bq{bq}_bk{bk}"] = {
                    "error": f"{type(e).__name__}: {e}"[:200]
                }
            print(f"bq{bq}_bk{bk}", json.dumps(results[f"bq{bq}_bk{bk}"]),
                  flush=True)
    fa._pick_blocks = orig
    print(json.dumps(results))


def model_sweep():
    import optax

    from pipegoose_tpu.models import bloom

    batch, seq, steps = 8, 1024, 8
    variants = {
        "remat+flash": dict(remat=True, use_flash=True),
        "remat+xla": dict(remat=True, use_flash=False),
        "attn+flash": dict(remat=True, remat_policy="attn", use_flash=True),
        "dots+flash+ce8": dict(
            remat=True, remat_policy="dots", use_flash=True, ce_chunks=8
        ),
        # b8 no-remat does not fit the chip's 16 GB (bench.py backs
        # off to b4 there); b4 is the largest no-remat batch
        "noremat+flash+ce8_b4": dict(
            remat=False, use_flash=True, ce_chunks=8, _batch=4
        ),
    }
    results = {}
    for name, kw in variants.items():
        kw = dict(kw)
        b = kw.pop("_batch", batch)
        cfg = bloom.BloomConfig.bloom_560m(dtype=jnp.bfloat16, **kw)
        while True:
            try:
                params = bloom.init_params(cfg, jax.random.PRNGKey(0))
                opt = optax.adam(1e-4)
                opt_state = opt.init(params)
                ids = jnp.asarray(
                    np.random.RandomState(0).randint(0, cfg.vocab_size, (b, seq))
                )

                @functools.partial(jax.jit, donate_argnums=(0, 1))
                def run(params, opt_state, ids, cfg=cfg):
                    def body(carry, _):
                        p, o = carry
                        loss, g = jax.value_and_grad(bloom.loss_fn)(
                            p, ids, None, ids, cfg
                        )
                        u, o = opt.update(g, o, p)
                        return (optax.apply_updates(p, u), o), loss
                    (p, o), losses = lax.scan(
                        body, (params, opt_state), None, length=steps
                    )
                    return p, o, losses[-1]

                params, opt_state, loss = jax.block_until_ready(
                    run(params, opt_state, ids))
                t0 = time.perf_counter()
                params, opt_state, loss = jax.block_until_ready(
                    run(params, opt_state, ids))
                dt = time.perf_counter() - t0
                tps = b * seq * steps / dt
                results[name] = {"tokens_per_sec": round(tps, 1), "batch": b}
                break
            except Exception as e:  # noqa: BLE001
                if "RESOURCE_EXHAUSTED" in str(e) and b > 1:
                    b //= 2
                    continue
                results[name] = {"error": f"{type(e).__name__}: {e}"[:300]}
                break
        print(name, json.dumps(results[name]), flush=True)
    print(json.dumps(results))


def fusedce_sweep():
    """Fused-CE block sizes + A/B vs the materialized-logits CE at the
    bench head shape (T = 8x1024 tokens, H=1024, V=250880)."""
    from pipegoose_tpu.ops import fused_ce as fc

    t, h, v = 8 * 1024, 1024, 250_880
    key = jax.random.PRNGKey(0)
    kh, kw = jax.random.split(key)
    hid = jax.random.normal(kh, (t, h), jnp.bfloat16) * 0.3
    w = jax.random.normal(kw, (v, h), jnp.bfloat16) * 0.02
    targets = jnp.asarray(np.random.RandomState(0).randint(0, v, (t,)))
    token_w = jnp.ones((t,), jnp.float32)

    results = {}

    def xla_ce(hid, w):
        logits = jnp.einsum(
            "th,vh->tv", hid.astype(jnp.float32), w.astype(jnp.float32)
        )
        lse = jax.nn.logsumexp(logits, axis=-1)
        pred = jnp.take_along_axis(logits, targets[:, None], -1)[:, 0]
        return ((lse - pred) * token_w).sum() / token_w.sum()

    def timed_grad(loss_fn, label):
        g = jax.jit(jax.value_and_grad(loss_fn, argnums=(0, 1)))
        jax.block_until_ready(g(hid, w))  # compile+warm
        t0 = time.perf_counter()
        jax.block_until_ready(g(hid, w))
        ms = (time.perf_counter() - t0) * 1e3
        results[label] = {"fwd_bwd_ms": round(ms, 2)}
        print(label, json.dumps(results[label]), flush=True)

    try:
        timed_grad(xla_ce, "xla_full_logits")
    except Exception as e:  # noqa: BLE001
        results["xla_full_logits"] = {"error": f"{type(e).__name__}: {e}"[:200]}
        print("xla_full_logits", json.dumps(results["xla_full_logits"]),
              flush=True)

    for bt in (128, 256, 512):
        for bv in (256, 512, 1024):
            label = f"fused_bt{bt}_bv{bv}"
            try:
                def fl(hid, w, _bt=bt, _bv=bv):
                    tot, cnt = fc.fused_ce_sums(
                        hid, w, targets, token_w, block_t=_bt, block_v=_bv,
                        interpret=False,
                    )
                    return tot / cnt
                timed_grad(fl, label)
            except Exception as e:  # noqa: BLE001
                results[label] = {"error": f"{type(e).__name__}: {e}"[:200]}
                print(label, json.dumps(results[label]), flush=True)
    print(json.dumps(results))


def comm_sweep():
    """Communication-engine A/B on the visible device mesh: the ring
    collective-matmul overlap vs the monolithic TP path, and the
    int8/bf16-quantized gradient reduction vs fp32, at the bloom-560m
    bench shape (docs/comm.md). Needs >= 2 devices — a single chip
    prints a skip record (the CPU smoke coverage lives in bench.py and
    tests/test_comm_hybrid.py)."""
    import optax

    from pipegoose_tpu.distributed import ParallelContext
    from pipegoose_tpu.models import bloom
    from pipegoose_tpu.optim.zero import DistributedOptimizer
    from pipegoose_tpu.parallel import make_hybrid_train_step

    ndev = len(jax.devices())
    if ndev < 2:
        print(json.dumps({"skipped": f"comm sweep needs >= 2 devices, "
                                     f"have {ndev}"}))
        return
    batch, seq, steps = 8, 1024, 8
    tp = 2 if ndev % 2 == 0 else 1
    variants = {
        "flash": dict(overlap=False, grad_comm="fp32"),
        "flash+overlap": dict(overlap=True, grad_comm="fp32"),
        "flash+int8ar": dict(overlap=False, grad_comm="int8"),
        "flash+bf16ar": dict(overlap=False, grad_comm="bf16"),
        "flash+overlap+int8ar": dict(overlap=True, grad_comm="int8"),
    }
    results = {}
    for name, kw in variants.items():
        b = batch
        while True:
            try:
                cfg = bloom.BloomConfig.bloom_560m(
                    dtype=jnp.bfloat16, remat=True, use_flash=True,
                    overlap_tp=kw["overlap"],
                )
                params = bloom.init_params(cfg, jax.random.PRNGKey(0))
                params, cfg = bloom.pad_for_tp(params, cfg, tp)
                ctx = ParallelContext(
                    tensor_parallel_size=tp, data_parallel_size=ndev // tp
                )
                try:
                    specs = bloom.tp_specs(params)
                    opt = DistributedOptimizer(
                        optax.adam(1e-4), axis_name="data",
                        grad_comm=kw["grad_comm"],
                    )

                    def loss_fn(p, ids, cfg=cfg):
                        return bloom.loss_fn(
                            p, ids, None, ids, cfg, tp_axis="tensor"
                        )

                    init_fn, make_step = make_hybrid_train_step(
                        loss_fn, specs, opt, ctx, overlap_tp=kw["overlap"]
                    )
                    opt_state = init_fn(params)
                    step = make_step(params)
                    ids = jnp.asarray(np.random.RandomState(0).randint(
                        0, cfg.valid_vocab_size or cfg.vocab_size, (b, seq)
                    ))
                    p = params
                    p, opt_state, loss = jax.block_until_ready(
                        step(p, opt_state, ids))  # compile + warm
                    t0 = time.perf_counter()
                    for _ in range(steps):
                        p, opt_state, loss = step(p, opt_state, ids)
                    jax.block_until_ready((p, opt_state, loss))
                    dt = time.perf_counter() - t0
                finally:
                    ctx.destroy()
                results[name] = {
                    "tokens_per_sec": round(b * seq * steps / dt, 1),
                    "batch": b, "mesh": f"tp{tp}xdp{ndev // tp}",
                }
                break
            except Exception as e:  # noqa: BLE001
                if "RESOURCE_EXHAUSTED" in str(e) and b > 1:
                    b //= 2
                    continue
                results[name] = {"error": f"{type(e).__name__}: {e}"[:300]}
                break
        print(name, json.dumps(results[name]), flush=True)
    print(json.dumps(results))


def plan_sweep():
    """Planner-guided sweep (pipegoose_tpu/planner/, docs/planner.md):
    rank the whole (dp, tp) x overlap x grad_comm layout space from
    shape-only compiles, then MEASURE only the top-K candidates with
    the comm-sweep timing recipe and record the predicted-vs-measured
    delta per candidate in the plan artifact (``PLAN_JSON``, default
    ``plan_report.json``) — the regression signal CI diffs next to the
    BENCH artifacts. ``PLAN_TOP_K`` (default 3) bounds the measured
    set; the static ranking itself costs no device time."""
    import os

    import optax

    from pipegoose_tpu.distributed import ParallelContext
    from pipegoose_tpu.models import bloom
    from pipegoose_tpu.optim.zero import DistributedOptimizer
    from pipegoose_tpu.parallel import (
        hybrid_step_kwargs,
        make_hybrid_train_step,
        parallel_context_sizes,
    )
    from pipegoose_tpu.planner import (
        BloomPlanModel,
        CostModel,
        enumerate_candidates,
        run_plan,
    )
    from pipegoose_tpu.telemetry.doctor import report_json_dumps
    from pipegoose_tpu.telemetry.exporters import atomic_write_text

    ndev = len(jax.devices())
    if ndev < 2:
        print(json.dumps({"skipped": f"plan sweep needs >= 2 devices, "
                                     f"have {ndev}"}))
        return
    on_tpu = jax.devices()[0].platform.lower() != "cpu"
    if on_tpu:
        cfg = bloom.BloomConfig.bloom_560m(
            dtype=jnp.bfloat16, remat=True, use_flash=True
        )
        batch, seq, steps = 8, 1024, 8
    else:
        cfg = bloom.BloomConfig(
            vocab_size=512, hidden_size=64, n_layer=2, n_head=4
        )
        batch, seq, steps = 8, 64, 3
    top_k = int(os.environ.get("PLAN_TOP_K", "3"))

    model = BloomPlanModel(cfg, batch=batch, seq=seq)
    candidates = enumerate_candidates(
        ndev, grad_comms=("fp32", "int8"), remat=(True,)
    )
    report = run_plan(model, candidates, CostModel.for_device())
    print(report.format_table(top_k=10), flush=True)

    def measure(c):
        import dataclasses

        ccfg = dataclasses.replace(
            cfg, overlap_tp=c.overlap_tp, remat=c.remat
        )
        params = bloom.init_params(ccfg, jax.random.PRNGKey(0))
        params, ccfg = bloom.pad_for_tp(params, ccfg, c.tp)
        ctx = ParallelContext(**parallel_context_sizes(c))
        try:
            specs = bloom.tp_specs(params)
            opt = DistributedOptimizer(
                optax.adam(1e-4), axis_name="data", grad_comm=c.grad_comm
            )

            def loss_fn(p, ids, ccfg=ccfg):
                return bloom.loss_fn(p, ids, None, ids, ccfg,
                                     tp_axis="tensor")

            init_fn, make_step = make_hybrid_train_step(
                loss_fn, specs, opt, ctx, **hybrid_step_kwargs(c)
            )
            opt_state = init_fn(params)
            step = make_step(params)
            ids = jnp.asarray(np.random.RandomState(0).randint(
                0, ccfg.valid_vocab_size or ccfg.vocab_size, (batch, seq)
            ))
            p = params
            p, opt_state, loss = jax.block_until_ready(
                step(p, opt_state, ids))  # compile + warm
            t0 = time.perf_counter()
            for _ in range(steps):
                p, opt_state, loss = step(p, opt_state, ids)
            jax.block_until_ready((p, opt_state, loss))
            dt = time.perf_counter() - t0
        finally:
            ctx.destroy()
        return {"tokens_per_sec": round(batch * seq * steps / dt, 1),
                "steps": steps}

    # measure the top-K only — the whole point: static search prunes the
    # space, hardware time goes to the few configs worth timing. NO
    # batch backoff on OOM (unlike comm_sweep): the planner scored THIS
    # workload, so a smaller batch would not be the predicted config —
    # an OOM is recorded as the finding it is.
    for res in report.ranked[:top_k]:
        if res.candidate.pp > 1:
            continue  # the timing loop above is the dense hybrid step
        try:
            measured = measure(res.candidate)
        except Exception as e:  # noqa: BLE001
            measured = {"error": f"{type(e).__name__}: {e}"[:300]}
        if "tokens_per_sec" in measured:
            report.record_measurement(res.candidate, measured)
        print(res.name, json.dumps(measured), flush=True)

    summary = report.predicted_vs_measured()
    print(json.dumps({"predicted_vs_measured": summary}))
    plan_path = os.environ.get("PLAN_JSON", "plan_report.json")
    if plan_path:
        atomic_write_text(plan_path, report_json_dumps(
            report.to_json(), indent=1
        ))
        print(f"plan artifact: {plan_path}")


def control_plane_sweep():
    """Multi-replica control plane (serving/control_plane/, ISSUE 12):
    the multi-tenant Zipf trace through round-robin vs cache-aware
    routing at 2 and 4 replicas on the real chip — forwarded prefill
    tokens, TTFT p50/p99, per-tenant dispatched shares, and the
    scale-down drain's zero-drop verdict per fleet size."""
    from pipegoose_tpu.models import bloom
    from pipegoose_tpu.serving.control_plane import (
        control_plane_replay_benchmark,
    )

    cfg = bloom.BloomConfig.bloom_560m(dtype=jnp.bfloat16)
    params = bloom.init_params(cfg, jax.random.PRNGKey(1))
    from pipegoose_tpu import telemetry

    reg = telemetry.get_registry()
    was_enabled = reg.enabled
    results = {}
    for replicas in (2, 4):
        label = f"replicas{replicas}"
        reg.disable()
        try:
            results[label] = control_plane_replay_benchmark(
                params, cfg, n_requests=8 * replicas, n_prefixes=6,
                prefix_len=96, suffix_lens=(8, 16), max_new=8,
                n_tenants=4, n_replicas=replicas, num_slots=1,
                num_pages=65, page_size=32, max_context=192,
            )
        except Exception as e:  # noqa: BLE001
            results[label] = {"error": f"{type(e).__name__}: {e}"[:300]}
        finally:
            if was_enabled:
                reg.enable()
        print(label, json.dumps(results[label]), flush=True)
    print(json.dumps(results))


def disagg_sweep():
    """Disaggregated prefill/decode (serving/disagg/, ISSUE 13): the
    skewed replay through a prefill pool streaming int8 KV pages into
    a decode pool vs one monolithic engine, on the real chip — token
    identity, decode-pool tokens/s vs the monolithic decode-only rate
    (the "prefill off the critical path" meter), TTFT p50/p99, and the
    wire-vs-fp byte savings, at fp and int8 KV."""
    from pipegoose_tpu.models import bloom
    from pipegoose_tpu.serving.disagg import disagg_serving_benchmark

    cfg = bloom.BloomConfig.bloom_560m(dtype=jnp.bfloat16)
    params = bloom.init_params(cfg, jax.random.PRNGKey(1))
    from pipegoose_tpu import telemetry

    reg = telemetry.get_registry()
    was_enabled = reg.enabled
    results = {}
    for label, kv in (("fp", None), ("int8kv", "int8")):
        reg.disable()
        try:
            results[label] = disagg_serving_benchmark(
                params, cfg, n_requests=12, n_prefixes=3, prefix_len=96,
                suffix_lens=(8, 16), max_new=16, num_slots=4,
                prefill_pages=65, decode_pages=65, page_size=32,
                max_context=256, prefill_chunk=64, kv_dtype=kv,
            )
        except Exception as e:  # noqa: BLE001
            results[label] = {"error": f"{type(e).__name__}: {e}"[:300]}
        finally:
            if was_enabled:
                reg.enable()
        print(label, json.dumps(results[label]), flush=True)
    print(json.dumps(results))


def serving_sweep(prefix_replay: bool = False, quant: bool = False,
                  tiered: bool = False, paged: bool = False):
    """Continuous-batching vs naive padded serving (serving/engine.py)
    across slot counts on the real chip: the decode-step savings grow
    with the slot count as long as the mixed-length workload keeps
    slots refillable. Prompt lengths stay inside one page bucket so
    each engine compiles a single prefill program.

    ``--prefix-replay`` swaps the workload for the ISSUE 6 Zipf-skewed
    shared-prefix replay and measures the four engine arms (monolithic
    baseline, chunked prefill, chunked + prefix cache, + speculative)
    per slot count — tokens/s, TTFT p50/p99, hit rate, prefill-token
    reduction, max decode gap.

    ``--quant`` (ROADMAP item 4) adds the int8w / int8kv / int8w+int8kv
    arms to whichever workload runs: tokens/s, TTFT, resident HBM, and
    the measured page-capacity ratio per slot count, pinned against the
    fp rows of the same run.

    ``--tiered`` (ISSUE 16) adds the KV-memory-hierarchy arms to the
    prefix replay: an overflow variant of the same workload (working
    set > HBM pages) through LRU-evict-and-recompute vs host-tier
    restore vs cross-replica pull — hit rate, TTFT p99, and the
    recompute-token reduction per slot count. Implies
    ``--prefix-replay``.

    ``--paged`` (ISSUE 20) adds the fused Pallas paged-attention arm
    to the A/B workload at the bloom-560m geometry: gather vs kernel
    decode tokens/s, token identity, and the profiled decode-step
    compute/comm/idle split per slot count — the on-hardware numbers
    the bench.py CPU smoke is a stand-in for."""
    from pipegoose_tpu.models import bloom
    from pipegoose_tpu.serving import (
        prefix_replay_benchmark,
        serving_ab_benchmark,
    )

    cfg = bloom.BloomConfig.bloom_560m(dtype=jnp.bfloat16)
    params = bloom.init_params(cfg, jax.random.PRNGKey(1))
    specs = [(10, 50), (30, 15), (20, 35), (5, 60), (28, 25), (12, 8),
             (25, 45), (8, 22), (17, 40), (22, 12), (9, 55), (14, 30)]
    # timed A/B runs with telemetry DISABLED: the continuous arm would
    # otherwise pay per-step event I/O the padded arm doesn't (the
    # __main__ wiring re-enables for the end-of-run snapshot)
    from pipegoose_tpu import telemetry

    reg = telemetry.get_registry()
    was_enabled = reg.enabled
    prefix_replay = prefix_replay or tiered
    results = {}
    for slots in (2, 4, 8):
        label = f"slots{slots}"
        reg.disable()
        try:
            if prefix_replay:
                results[label] = prefix_replay_benchmark(
                    params, cfg, n_requests=4 * slots, n_prefixes=3,
                    prefix_len=64, suffix_lens=(8, 16, 24), max_new=24,
                    num_slots=slots, num_pages=1 + 16 * slots,
                    page_size=32, max_context=256, prefill_chunk=64,
                    include_speculative=True, speculative=(4, 3),
                    include_quant=quant, include_tiered=tiered,
                )
            else:
                results[label] = serving_ab_benchmark(
                    params, cfg, specs, num_slots=slots,
                    num_pages=1 + 3 * slots, page_size=32, max_context=128,
                    quant_arms=quant, paged_kernel=paged,
                )
        except Exception as e:  # noqa: BLE001
            results[label] = {"error": f"{type(e).__name__}: {e}"[:300]}
        finally:
            if was_enabled:
                reg.enable()
        reg.event("sweep.result", label=label, **{
            k: v for k, v in results[label].items()
            if not isinstance(v, dict)
        })
        print(label, json.dumps(results[label]), flush=True)
    print(json.dumps(results))


if __name__ == "__main__":
    import os

    mode = sys.argv[1] if len(sys.argv) > 1 else "kernel"
    modes = {"kernel": kernel_sweep, "model": model_sweep,
             "fusedce": fusedce_sweep, "serving": serving_sweep,
             "comm": comm_sweep, "plan": plan_sweep,
             "control-plane": control_plane_sweep,
             "disagg": disagg_sweep}
    if mode not in modes:
        raise SystemExit(f"unknown mode {mode!r}; pick one of {sorted(modes)}")
    if mode == "serving":
        modes["serving"] = functools.partial(
            serving_sweep,
            prefix_replay="--prefix-replay" in sys.argv[2:],
            quant="--quant" in sys.argv[2:],
            tiered="--tiered" in sys.argv[2:],
            paged="--paged" in sys.argv[2:],
        )
    # telemetry JSONL artifact (the serving sweep's engines emit their
    # per-step time series into it; every mode gets a final snapshot) —
    # set SWEEP_TELEMETRY_JSONL="" to disable
    from pipegoose_tpu import telemetry

    tel_path = os.environ.get(
        "SWEEP_TELEMETRY_JSONL", f"sweep_{mode}_telemetry.jsonl"
    )
    tel = None
    if tel_path:
        reg = telemetry.get_registry()
        reg.enable()
        tel = telemetry.JSONLExporter(tel_path, registry=reg, mode="w")
        reg.event("sweep.start", mode=mode)
    try:
        modes[mode]()
    finally:
        if tel is not None:
            tel.export_snapshot(telemetry.get_registry())
            tel.close()
