"""Throughput benchmark: BLOOM-560m train step on the available device.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

Also writes a telemetry JSONL artifact (``BENCH_TELEMETRY_JSONL``,
default ``bench_telemetry.jsonl``; empty string disables): per-variant
events, the serving engine's per-step time series, and a final metrics
snapshot (pipegoose_tpu/telemetry/, docs/observability.md) — plus a
sibling Perfetto timeline (``BENCH_TRACE_JSON``, default
``bench_telemetry_trace.json``; open in ui.perfetto.dev) of the same
run's spans, and a request-trace artifact (``BENCH_REQTRACE_JSON``,
default ``bench_request_trace.json``) whose per-arm latency attribution
decomposes the prefix-replay TTFT deltas (telemetry/reqtrace.py).

The reference publishes no throughput numbers (BASELINE.md) — its
acceptance bar is convergence only. ``vs_baseline`` therefore reports
achieved MFU / 0.40, the north-star MFU threshold from BASELINE.json.

One process, one device attach. Without a TPU the bench fails;
``BENCH_FORCE_CPU=1`` asks for the CPU rehearsal instead (toy widths on
8 virtual devices), whose output says ``"device": "cpu"`` and carries
no MFU. Every timed region ends in ``block_until_ready`` (chip_smoke.py's
dispatch phase re-checks on every run that it waits for the device).

Compile cache: where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX's own
reading of it is the only configuration; where it is not, the bench on
the chip uses ``<checkout>/.jax_cache`` (the CPU rehearsal sets none).
"""
from __future__ import annotations

import functools
import json
import os
import sys
import time


def _peak_flops(device_kind: str) -> float:
    # the peak table lives in telemetry.derived (single source of truth
    # for the MFU denominator)
    from pipegoose_tpu.telemetry.derived import peak_flops_for

    return peak_flops_for(device_kind)


def run_bench(force_cpu: bool) -> None:
    if not force_cpu:
        # before jax is imported: it reads the variable itself
        os.environ.setdefault(
            "JAX_COMPILATION_CACHE_DIR",
            os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         ".jax_cache"),
        )
    if force_cpu:
        # CPU rehearsal, before the first backend touch: 8 virtual host
        # devices so the hybrid comm variants (overlap / int8
        # all-reduce need a mesh) run too; override=False keeps an
        # operator-set device count (the test-suite convention).
        from pipegoose_tpu.testing.fake_cluster import fake_cluster

        fake_cluster(8, override=False)
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from pipegoose_tpu.models import bloom

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    if not on_tpu and not force_cpu:
        sys.exit(
            f"bench: no TPU (jax found platform {dev.platform!r}); set "
            "BENCH_FORCE_CPU=1 for the CPU rehearsal"
        )
    device_kind = dev.device_kind if on_tpu else "cpu"

    # telemetry JSONL artifact alongside the stdout JSON line: variant
    # events + the serving engine's step time series + final snapshot.
    # File I/O only — the one-JSON-line stdout contract is untouched.
    from pipegoose_tpu import telemetry

    reg = telemetry.get_registry()
    tel_path = os.environ.get("BENCH_TELEMETRY_JSONL", "bench_telemetry.jsonl")
    tel = trace = None
    if tel_path:
        # enable ONLY when an artifact is wanted: an empty path opts out
        # of the measurement overhead (fenced spans, histograms) too
        reg.enable()
        # mode="w": each run_bench invocation owns the artifact
        tel = telemetry.JSONLExporter(tel_path, registry=reg, mode="w")
        # sibling Perfetto timeline of the same run (ui.perfetto.dev);
        # same opt-out, same per-run ownership (write() replaces)
        trace_path = os.environ.get(
            "BENCH_TRACE_JSON", os.path.splitext(tel_path)[0] + "_trace.json"
        )
        if trace_path:
            trace = telemetry.ChromeTraceExporter(trace_path, registry=reg)
        reg.event("bench.start", device=device_kind, on_tpu=on_tpu)

    if on_tpu:
        steps = 10
        # variant -> (config, batch, seq)
        variants = {
            "flash": (
                bloom.BloomConfig.bloom_560m(
                    dtype=jnp.bfloat16, remat=True, use_flash=True
                ),
                8, 1024,
            ),
            # fused Pallas CE (ops/fused_ce.py): the 8 GB fp32 logits
            # buffer never exists, so no-remat has the HBM to run at
            # full batch — the primary MFU>=0.40 candidates (round 5)
            "noremat+flash+fusedce": (
                bloom.BloomConfig.bloom_560m(
                    dtype=jnp.bfloat16, remat=False, use_flash=True,
                    fused_ce=True,
                ),
                8, 1024,
            ),
            "flash+fusedce": (
                bloom.BloomConfig.bloom_560m(
                    dtype=jnp.bfloat16, remat=True, use_flash=True,
                    fused_ce=True,
                ),
                8, 1024,
            ),
            "xla": (
                bloom.BloomConfig.bloom_560m(dtype=jnp.bfloat16, remat=True),
                8, 1024,
            ),
            # chunked CE keeps the 8 GB fp32 logits buffer off HBM
            # (docs/perf_tpu_v5e.md) — enables the no-remat variant
            "flash+ce8": (
                bloom.BloomConfig.bloom_560m(
                    dtype=jnp.bfloat16, remat=True, use_flash=True, ce_chunks=8
                ),
                8, 1024,
            ),
            # longer sequence, same token count: the flash kernels' edge
            # over XLA attention grows with S (docs/perf_tpu_v5e.md)
            "flash_s2048": (
                bloom.BloomConfig.bloom_560m(
                    dtype=jnp.bfloat16, remat=True, use_flash=True
                ),
                4, 2048,
            ),
            "noremat+flash+ce8": (
                bloom.BloomConfig.bloom_560m(
                    dtype=jnp.bfloat16, remat=False, use_flash=True, ce_chunks=8
                ),
                8, 1024,
            ),
        }
    else:  # CPU rehearsal
        steps = 3
        variants = {
            "xla": (
                bloom.BloomConfig(
                    vocab_size=1024, hidden_size=256, n_layer=4, n_head=8,
                    dtype=jnp.float32,
                ),
                2, 128,
            )
        }

    def measure(cfg, batch, seq):
        params = bloom.init_params(cfg, jax.random.PRNGKey(0))
        opt = optax.adam(1e-4)
        opt_state = opt.init(params)
        ids = jnp.asarray(
            np.random.RandomState(0).randint(0, cfg.vocab_size, (batch, seq))
        )

        # The timed steps run inside one jit (lax.scan): the window
        # holds device work only, closed by block_until_ready.
        # Donation: without it XLA holds old AND new params+opt state
        # live across the step — 2x state memory OOMs 560m+Adam on 16GB.
        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def run(params, opt_state, ids):
            def body(carry, _):
                params, opt_state = carry
                loss, grads = jax.value_and_grad(bloom.loss_fn)(
                    params, ids, None, ids, cfg
                )
                updates, opt_state = opt.update(grads, opt_state, params)
                return (optax.apply_updates(params, updates), opt_state), loss
            (params, opt_state), losses = jax.lax.scan(
                body, (params, opt_state), None, length=steps
            )
            return params, opt_state, losses[-1]

        # warmup/compile
        params, opt_state, loss = jax.block_until_ready(
            run(params, opt_state, ids)
        )

        t0 = time.perf_counter()
        params, opt_state, loss = jax.block_until_ready(
            run(params, opt_state, ids)
        )
        dt = time.perf_counter() - t0

        tokens_per_sec = batch * seq * steps / dt
        # model FLOPs per token: 6*N for dense matmuls + 12*L*H*seq attention
        n_params = sum(
            int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(params)
        )
        flops_per_token = 6 * n_params + 12 * cfg.n_layer * cfg.hidden_size * seq
        out = {
            "tokens_per_sec": round(tokens_per_sec, 1),
            "loss": float(loss),
        }
        if on_tpu:  # a CPU rehearsal carries no MFU
            out["mfu"] = round(
                tokens_per_sec * flops_per_token / _peak_flops(device_kind), 4
            )
        return out

    # communication-engine variants (docs/comm.md): the hybrid TP x DP
    # step with (a) the ring collective-matmul overlap path and (b) the
    # int8-quantized gradient reduction — variant -> (config, batch,
    # seq, tp, grad_comm). These need >= 2 devices (the CPU smoke fakes
    # 8); measured with the step's own jitted shard_map in a Python
    # loop (one warm-up) so the compiled program is the production one,
    # not a scan-wrapped cousin.
    if on_tpu:
        comm_base = dict(dtype=jnp.bfloat16, remat=True, use_flash=True)
        comm_shape = (8, 1024)
    else:
        # flash on CPU means interpreter-mode Pallas — keep the smoke's
        # variant LABELS (the TPU contract) but run XLA attention
        comm_base = dict(
            vocab_size=1024, hidden_size=256, n_layer=4, n_head=8,
            dtype=jnp.float32,
        )
        comm_shape = (8, 128)
    comm_variants = {
        "flash+overlap": (dict(comm_base, overlap_tp=True), 2, "fp32"),
        "flash+int8ar": (dict(comm_base), 1, "int8"),
        "flash+overlap+int8ar": (dict(comm_base, overlap_tp=True), 2, "int8"),
    }

    def measure_hybrid(cfg_kw, tp, grad_comm, batch, seq):
        import optax

        from pipegoose_tpu.distributed import ParallelContext
        from pipegoose_tpu.optim.zero import DistributedOptimizer
        from pipegoose_tpu.parallel import make_hybrid_train_step

        ndev = len(jax.devices())
        if ndev < 2 or ndev % max(tp, 1):
            raise RuntimeError(
                f"comm variant needs a mesh ({ndev} device(s), tp={tp})"
            )
        cfg = (
            bloom.BloomConfig.bloom_560m(**cfg_kw)
            if on_tpu else bloom.BloomConfig(**cfg_kw)
        )
        params = bloom.init_params(cfg, jax.random.PRNGKey(0))
        params, cfg = bloom.pad_for_tp(params, cfg, tp)
        ctx = ParallelContext(
            tensor_parallel_size=tp, data_parallel_size=ndev // tp
        )
        try:
            specs = bloom.tp_specs(params)
            opt = DistributedOptimizer(
                optax.adam(1e-4), axis_name="data", grad_comm=grad_comm
            )

            def hloss(p, ids):
                return bloom.loss_fn(p, ids, None, ids, cfg, tp_axis="tensor")

            init_fn, make_step = make_hybrid_train_step(
                loss_fn=hloss, param_specs=specs, optimizer=opt,
                parallel_context=ctx,
                overlap_tp=bool(cfg_kw.get("overlap_tp")),
            )
            opt_state = init_fn(params)
            step = make_step(params)
            ids = jnp.asarray(np.random.RandomState(0).randint(
                0, cfg.valid_vocab_size or cfg.vocab_size, (batch, seq)
            ))
            p = params
            p, opt_state, loss = jax.block_until_ready(
                step(p, opt_state, ids)  # compile+warm
            )
            t0 = time.perf_counter()
            for _ in range(steps):
                p, opt_state, loss = step(p, opt_state, ids)
            jax.block_until_ready((p, opt_state, loss))
            dt = time.perf_counter() - t0
            loss = float(loss)
        finally:
            ctx.destroy()
        tokens_per_sec = batch * seq * steps / dt
        n_params = sum(
            int(np.prod(q.shape)) for q in jax.tree_util.tree_leaves(params)
        )
        flops_per_token = 6 * n_params + 12 * cfg.n_layer * cfg.hidden_size * seq
        out = {
            "tokens_per_sec": round(tokens_per_sec, 1),
            "loss": loss,
            "mesh": f"tp{tp}xdp{ndev // tp}",
            "grad_comm": grad_comm,
        }
        if on_tpu:
            out["mfu"] = round(
                tokens_per_sec * flops_per_token
                / (_peak_flops(device_kind) * ndev), 4
            )
        return out

    def serving_block():
        """Continuous-batching vs naive padded batching at mixed
        sequence lengths (serving/engine.py A/B). Prompt lengths stay
        inside ONE page bucket so each arm compiles a single prefill
        program; the raggedness that padded batching pays for comes
        from the mixed max_new_tokens.

        Telemetry is DISABLED for the timed A/B — the continuous arm
        would otherwise pay a JSONL write+flush per decode step that
        the padded arm doesn't, skewing the reported speedup — and the
        per-step time series is captured by ONE extra instrumented run
        afterwards, outside the measurement.

        The block also replays a Zipf-skewed shared-prefix workload
        (ISSUE 6) through four engine arms — monolithic baseline,
        chunked prefill, chunked + prefix cache, + self-speculative —
        reporting tokens/s, TTFT p50/p99, the prefill-token (FLOP)
        reduction at the measured hit rate, and the max decode-step gap
        chunking bounds."""
        from pipegoose_tpu.serving import (
            Request,
            ServingEngine,
            prefix_replay_benchmark,
            serving_ab_benchmark,
        )

        if on_tpu:
            scfg = bloom.BloomConfig.bloom_560m(dtype=jnp.bfloat16)
            specs = [(10, 50), (30, 15), (20, 35), (5, 60),
                     (28, 25), (12, 8), (25, 45), (8, 22)]
            kw = dict(num_slots=4, num_pages=33, page_size=32,
                      max_context=128)
            replay_kw = dict(n_requests=16, n_prefixes=3, prefix_len=96,
                             suffix_lens=(8, 16, 24), max_new=16,
                             num_slots=4, num_pages=65, page_size=32,
                             max_context=256, prefill_chunk=64)
            cp_kw = dict(n_requests=16, n_prefixes=4, prefix_len=96,
                         suffix_lens=(8, 16), max_new=8, n_tenants=3,
                         n_replicas=2, num_slots=1, num_pages=65,
                         page_size=32, max_context=192)
            dg_kw = dict(n_requests=12, n_prefixes=3, prefix_len=96,
                         suffix_lens=(8, 16), max_new=16, num_slots=4,
                         prefill_pages=65, decode_pages=65, page_size=32,
                         max_context=256, prefill_chunk=64,
                         kv_dtype="int8")
        else:
            scfg = bloom.BloomConfig(
                vocab_size=512, hidden_size=128, n_layer=2, n_head=4,
                dtype=jnp.float32,
            )
            specs = [(6, 10), (3, 4), (7, 13), (2, 6)]
            kw = dict(num_slots=2, num_pages=13, page_size=8,
                      max_context=32)
            replay_kw = dict(n_requests=10, n_prefixes=3, prefix_len=48,
                             suffix_lens=(2, 4, 6), max_new=4,
                             num_slots=2, num_pages=33, page_size=8,
                             max_context=64, prefill_chunk=16)
            cp_kw = dict(n_requests=12, n_prefixes=4, prefix_len=48,
                         suffix_lens=(2, 4), max_new=2, n_tenants=3,
                         n_replicas=2, num_slots=1, num_pages=41,
                         page_size=8, max_context=64)
            dg_kw = dict(n_requests=8, n_prefixes=3, prefix_len=24,
                         suffix_lens=(2, 4), max_new=4, num_slots=2,
                         prefill_pages=33, decode_pages=33, page_size=8,
                         max_context=64, prefill_chunk=16,
                         kv_dtype="int8")
        sparams = bloom.init_params(scfg, jax.random.PRNGKey(1))
        # request-trace artifact (BENCH_REQTRACE_JSON, default
        # bench_request_trace.json; empty disables): one EXTRA traced
        # replay per arm AFTER the measurement, whose per-arm latency
        # attribution explains the cached-vs-baseline TTFT delta
        # (ISSUE 8) — queue/prefill/decode/stall components per request
        # plus the cache-savings share vs the prefill-token reduction.
        reqtrace_path = os.environ.get(
            "BENCH_REQTRACE_JSON", "bench_request_trace.json"
        )
        # fleet-trace artifact (BENCH_FLEETTRACE_JSON, default
        # bench_fleet_trace.json; empty disables): one EXTRA traced
        # control-plane replay AFTER the measurement whose stitched
        # cross-replica attribution (ISSUE 17) reports per-hop p50/p99
        # (ingress/ledger/route/dispatch/replica) plus the top-3
        # slowest tail exemplars, each naming its dominant hop
        fleettrace_path = os.environ.get(
            "BENCH_FLEETTRACE_JSON", "bench_fleet_trace.json"
        )
        was_enabled = reg.enabled
        reg.disable()
        try:
            # quant arms (ISSUE 10): fp/int8w/int8kv/int8w+int8kv rows —
            # tokens/s + TTFT + the measured HBM/page-capacity ratios —
            # land in the same serving artifact every bench run
            # paged-kernel arm (ISSUE 20): the fused Pallas
            # paged-attention kernel vs the XLA gather on the same
            # int8-pool workload — tokens/s, token identity, and the
            # profiled decode-step compute/comm/idle split
            res = serving_ab_benchmark(sparams, scfg, specs,
                                       quant_arms=True, paged_kernel=True,
                                       **kw)
            # KV memory hierarchy (ISSUE 16): an overflow replay whose
            # working set exceeds HBM pages, through LRU-recompute vs
            # host-tier restore vs cross-replica pull — hit rate, TTFT
            # p99, and the recompute-token reduction land in the same
            # artifact
            res["prefix_replay"] = prefix_replay_benchmark(
                sparams, scfg, seed=0, include_speculative=True,
                include_quant=True, include_tiered=True,
                trace=bool(reqtrace_path), **replay_kw,
            )
            # multi-replica control plane (ISSUE 12): the same
            # multi-tenant Zipf trace through 2 replicas at each
            # routing arm — cache-aware vs round-robin on forwarded
            # prefill tokens + TTFT, plus the scale-down drain's
            # zero-drop verdict
            from pipegoose_tpu.serving.control_plane import (
                control_plane_replay_benchmark,
            )

            res["control_plane"] = control_plane_replay_benchmark(
                sparams, scfg, seed=0,
                fleet_trace=bool(fleettrace_path), **cp_kw,
            )
            # disaggregated prefill/decode (ISSUE 13): the same skewed
            # replay through a prefill pool streaming int8 KV pages
            # into a decode pool vs one monolithic engine — token
            # identity, decode-pool rate vs the monolithic decode-only
            # rate, and the wire-vs-fp byte savings
            from pipegoose_tpu.serving.disagg import (
                disagg_serving_benchmark,
            )

            res["disagg"] = disagg_serving_benchmark(
                sparams, scfg, seed=0, **dg_kw,
            )
        finally:
            if was_enabled:
                reg.enable()
        if reqtrace_path and "request_trace" in res["prefix_replay"]:
            from pipegoose_tpu.telemetry.exporters import (
                atomic_write_text as _awt,
                safe_json_dumps as _sjd,
            )

            # the per-request rows live in the sibling artifact, the
            # stdout payload keeps only the cross-arm summary
            rt = res["prefix_replay"].pop("request_trace")
            _awt(reqtrace_path, _sjd({
                "device": device_kind,
                "replay": {k: v for k, v in replay_kw.items()},
                "ttft_per_arm": {
                    arm: {q: row[q] for q in ("ttft_p50_s", "ttft_p99_s")}
                    for arm, row in res["prefix_replay"].items()
                    if isinstance(row, dict) and "ttft_p50_s" in row
                },
                **rt,
            }, indent=1))
            res["prefix_replay"]["request_trace_summary"] = rt["summary"]
            res["prefix_replay"]["request_trace_json"] = reqtrace_path
        if fleettrace_path and "fleet_trace" in res["control_plane"]:
            from pipegoose_tpu.telemetry.exporters import (
                atomic_write_text as _awt,
                safe_json_dumps as _sjd,
            )

            # per-hop rows + exemplar traces live in the sibling
            # artifact; the stdout payload keeps only the pointer
            ftr = res["control_plane"].pop("fleet_trace")
            _awt(fleettrace_path, _sjd({
                "device": device_kind,
                "replay": {k: v for k, v in cp_kw.items()},
                **ftr,
            }, indent=1))
            res["control_plane"]["fleet_trace_json"] = fleettrace_path
        if tel is not None:
            srng = np.random.RandomState(0)
            vocab = getattr(scfg, "valid_vocab_size", None) or scfg.vocab_size
            # the instrumented replay also carries the live memory
            # ledger (ISSUE 18): peak per-owner-class occupancy +
            # fragmentation land in the serving payload and the
            # BENCH_HISTORY row, conservation-checked for free
            engine = ServingEngine(sparams, scfg, memledger=True, **kw)
            _, smetrics = engine.run([
                Request(prompt=srng.randint(1, vocab, (int(s),)),
                        max_new_tokens=int(n))
                for s, n in specs
            ])
            mem = smetrics.get("memory")
            if mem is not None:
                res["memory"] = mem
                reg.event("bench.serving_memory",
                          peak_pages=mem["peak_pages"],
                          peak_bytes=mem["peak_bytes"],
                          peak_fragmentation=mem["peak_fragmentation"],
                          conservation_failures=mem[
                              "conservation_failures"],
                          leaks=mem["leaks"])
            # the fleet goodput ledger's wall attribution (ISSUE 19):
            # availability lands in bench_telemetry.jsonl next to the
            # memory peaks, so an incident-burning bench run is visible
            # without opening the trace
            gp = res.get("control_plane", {}).get("goodput")
            if gp is not None:
                reg.event("bench.serving_goodput",
                          goodput_fraction=gp["goodput_fraction"],
                          badput_seconds=gp["badput_seconds"],
                          incidents=gp["incidents"],
                          conservation_ok=gp["conservation_ok"])
        return res

    def emit(results, serving=None) -> bool:
        ok = {k: v for k, v in results.items() if "error" not in v}
        if not ok:
            return False
        best = max(ok, key=lambda k: ok[k]["tokens_per_sec"])
        r = results[best]
        payload = {
            "metric": "bloom-560m train tokens/sec/chip"
            if on_tpu
            else "bloom-tiny train tokens/sec (cpu rehearsal)",
            "value": r["tokens_per_sec"],
            "unit": "tokens/sec/chip",
            "device": device_kind,
            "best_variant": best,
            "variants": results,
            "loss": r["loss"],
        }
        if on_tpu:  # a CPU rehearsal carries no MFU
            payload["vs_baseline"] = round(r["mfu"] / 0.40, 4)
            payload["mfu"] = r["mfu"]
        if serving is not None:
            payload["serving"] = serving
        print(json.dumps(payload), flush=True)
        return True

    results = {}
    for name, (cfg, batch, seq) in variants.items():
        # a failing variant (e.g. an experimental kernel) must not discard
        # the other variants' measurements; OOM backs off the batch size
        b = batch
        while True:
            try:
                results[name] = measure(cfg, b, seq)
                results[name]["batch"] = b
                results[name]["seq"] = seq
                reg.gauge(f"bench.{name}.tokens_per_s").set(
                    results[name]["tokens_per_sec"]
                )
                if on_tpu:
                    reg.gauge(f"bench.{name}.mfu").set(results[name]["mfu"])
                break
            except Exception as e:  # noqa: BLE001
                if "RESOURCE_EXHAUSTED" in str(e) and b > 1:
                    b //= 2
                    continue
                results[name] = {"error": f"{type(e).__name__}: {e}"[:500]}
                break
        reg.event("bench.variant", name=name, **results[name])

    # comm-engine variants AFTER the champions (same crash-isolation
    # argument; they must never cost the primary numbers); OOM backs
    # off the batch like the main loop
    cb, cs = comm_shape
    for name, (cfg_kw, tp, grad_comm) in comm_variants.items():
        b = cb
        while True:
            try:
                results[name] = measure_hybrid(cfg_kw, tp, grad_comm, b, cs)
                results[name]["batch"] = b
                results[name]["seq"] = cs
                reg.gauge(f"bench.{name}.tokens_per_s").set(
                    results[name]["tokens_per_sec"]
                )
                if on_tpu:
                    reg.gauge(f"bench.{name}.mfu").set(results[name]["mfu"])
                break
            except Exception as e:  # noqa: BLE001
                if "RESOURCE_EXHAUSTED" in str(e) and b > 1:
                    b //= 2
                    continue
                results[name] = {"error": f"{type(e).__name__}: {e}"[:500]}
                break
        reg.event("bench.variant", name=name, **{
            k: v for k, v in results[name].items() if not isinstance(v, dict)
        })

    # the best PLAIN variant (comm variants carry their own mesh/step
    # shape) + its one-step train fn: shared by the mesh-doctor
    # artifact (shape-only compile) and the BENCH_HISTORY profile (real
    # execution) below — ONE definition of "the benched step"
    ok_variants = [
        k for k, v in results.items() if "error" not in v and k in variants
    ]
    best_variant = (
        max(ok_variants, key=lambda k: results[k]["tokens_per_sec"])
        if ok_variants else None
    )

    def bench_one_step(cfg, opt):
        def one_step(params, opt_state, ids):
            loss, grads = jax.value_and_grad(bloom.loss_fn)(
                params, ids, None, ids, cfg
            )
            updates, opt_state = opt.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, loss
        return one_step

    # mesh-doctor artifact (BENCH_DOCTOR_JSON, default bench_doctor.json;
    # empty disables): the benched step's ACTUAL shardings + per-device
    # HBM table (telemetry/doctor.py), recorded per bench run so a
    # partitioning regression is visible in the artifact diff, not just
    # as a slower number. Shape-only AOT compile — nothing executes, and
    # a doctor failure never discards the measurements above.
    doctor_path = os.environ.get("BENCH_DOCTOR_JSON", "bench_doctor.json")
    if doctor_path and best_variant is not None:
        try:
            from pipegoose_tpu.telemetry import doctor as _doctor
            from pipegoose_tpu.telemetry.exporters import atomic_write_text

            dcfg, _, dseq = variants[best_variant]
            dbatch = results[best_variant]["batch"]
            p_sds = jax.eval_shape(
                lambda k: bloom.init_params(dcfg, k), jax.random.PRNGKey(0)
            )
            dopt = optax.adam(1e-4)
            o_sds = jax.eval_shape(dopt.init, p_sds)
            ids_sds = jax.ShapeDtypeStruct((dbatch, dseq), jnp.int32)

            report = _doctor.diagnose(
                jax.jit(bench_one_step(dcfg, dopt), donate_argnums=(0, 1)),
                p_sds, o_sds, ids_sds,
                labels=("params", "opt_state", "batch"),
            )
            _doctor.set_doctor_gauges(report, registry=reg)
            atomic_write_text(doctor_path, json.dumps({
                "variant": best_variant, "device": device_kind,
                "batch": dbatch, "seq": dseq,
                "report": report.to_json(),
            }, indent=1))
            if tel is not None:
                reg.event(
                    "bench.doctor", variant=best_variant, path=doctor_path,
                    replicated_bytes=report.sharding.replicated_bytes,
                    resharding_bytes=report.sharding.resharding_bytes,
                    hbm_peak_bytes=report.memory.peak_bytes,
                )
        except Exception as e:  # noqa: BLE001
            sys.stderr.write(f"bench doctor failed (non-fatal): {e}\n")

    # parallelism-planner artifact (BENCH_PLAN_JSON, default
    # bench_plan.json; empty disables): statically rank EXACTLY the
    # hybrid comm variants this run measured and record the
    # predicted-vs-measured delta per variant — the planner's
    # acceptance signal (ISSUE 7): top-1 agreement with the measured
    # best, or the divergence on the record. Shape-only compiles;
    # non-fatal like the doctor artifact.
    plan_path = os.environ.get("BENCH_PLAN_JSON", "bench_plan.json")
    comm_ok = [k for k, v in results.items()
               if "error" not in v and k in comm_variants]
    if plan_path and comm_ok:
        try:
            from pipegoose_tpu.planner import (
                BloomPlanModel,
                Candidate,
                CostModel,
                run_plan,
            )
            from pipegoose_tpu.telemetry.exporters import atomic_write_text

            ndev = len(jax.devices())
            base_kw = {k: v for k, v in comm_base.items() if k != "overlap_tp"}
            plan_cfg = (
                bloom.BloomConfig.bloom_560m(**base_kw)
                if on_tpu else bloom.BloomConfig(**base_kw)
            )
            cand_of = {
                name: Candidate(
                    dp=ndev // tp, tp=tp,
                    overlap_tp=bool(kw.get("overlap_tp")),
                    grad_comm=gc,
                    remat=bool(base_kw.get("remat", False)),
                )
                for name, (kw, tp, gc) in comm_variants.items()
            }
            # ONE workload per plan: variants whose OOM backoff shrank
            # the batch below the nominal comm batch were measured at a
            # DIFFERENT workload — planning them at cb would skew (or
            # validity-prune) the comparison, so they are listed as
            # skipped instead of silently mixed in
            plan_names = [n for n in comm_ok if results[n]["batch"] == cb]
            skipped = {n: f"measured at backed-off batch "
                          f"{results[n]['batch']} != {cb}"
                       for n in comm_ok if n not in plan_names}
            plan_model = BloomPlanModel(plan_cfg, batch=cb, seq=cs)
            plan_report = run_plan(
                plan_model, [cand_of[n] for n in plan_names],
                CostModel.for_device(device_kind), registry=reg,
            )
            for name in plan_names:
                plan_report.record_measurement(
                    cand_of[name],
                    {"tokens_per_sec": results[name]["tokens_per_sec"],
                     "bench_variant": name},
                )
            pvm = plan_report.predicted_vs_measured()
            atomic_write_text(plan_path, json.dumps({
                "device": device_kind,
                "variants": {n: cand_of[n].name for n in plan_names},
                "skipped_batch_mismatch": skipped,
                "predicted_vs_measured": pvm,
                "report": plan_report.to_json(),
            }, indent=1))
            if tel is not None:
                reg.event(
                    "bench.plan", path=plan_path,
                    rank_agreement=pvm.get("rank_agreement"),
                    predicted_best=pvm.get("predicted_best"),
                    measured_best=pvm.get("measured_best"),
                )
        except Exception as e:  # noqa: BLE001
            sys.stderr.write(f"bench planner failed (non-fatal): {e}\n")

    # serving throughput A/B LAST: the train numbers are the primary
    # contract, a serving failure must not discard them
    try:
        serving = serving_block()
    except Exception as e:  # noqa: BLE001
        serving = {"error": f"{type(e).__name__}: {e}"[:300]}

    # perf-trajectory history (BENCH_HISTORY_JSONL, default
    # BENCH_HISTORY.jsonl; empty disables): ONE summary row per bench
    # run — run id, per-arm tokens/s, best-variant MFU, and the
    # MEASURED component fractions of one profiled train step
    # (telemetry/xprof.py) — appended so the repo's perf trajectory is
    # machine-readable. The perf sentinel (telemetry/sentinel.py) reads
    # the tail as its baseline window and stamps a regression verdict
    # on the row ("idle time 2.1x baseline") before it is written.
    # Non-fatal like the doctor/plan artifacts.
    history_path = os.environ.get("BENCH_HISTORY_JSONL",
                                  "BENCH_HISTORY.jsonl")
    if history_path:
        try:
            from pipegoose_tpu.telemetry.sentinel import PerfSentinel
            from pipegoose_tpu.telemetry.xprof import profile_step

            row = {
                "run_id": time.strftime("%Y%m%dT%H%M%SZ", time.gmtime()),
                "device": device_kind,
                "arms": {
                    k: v["tokens_per_sec"] for k, v in results.items()
                    if "error" not in v
                },
            }
            if best_variant is not None:
                row["best_variant"] = best_variant
                row["tokens_per_s"] = results[best_variant]["tokens_per_sec"]
                if on_tpu:
                    row["mfu"] = results[best_variant]["mfu"]
                hcfg, _, hseq = variants[best_variant]
                hbatch = results[best_variant]["batch"]
                hparams = bloom.init_params(hcfg, jax.random.PRNGKey(0))
                hopt = optax.adam(1e-4)
                hopt_state = hopt.init(hparams)
                hids = jnp.asarray(np.random.RandomState(0).randint(
                    0, hcfg.vocab_size, (hbatch, hseq)))
                # the SAME step the doctor artifact above AOT-compiled,
                # this time executed for real under the profiler
                prof = profile_step(
                    jax.jit(bench_one_step(hcfg, hopt),
                            donate_argnums=(0, 1)),
                    hparams, hopt_state, hids, steps=2, warmup=2,
                    update_args=lambda out, a: (out[0], out[1], a[2]),
                )
                row["profile"] = {
                    "source": prof.source,
                    "wall_step_s": prof.wall_step_s,
                    "compute_s": prof.compute_s,
                    "comm_s": prof.comm_s,
                    "idle_s": prof.idle_s,
                    "comm_by_axes": prof.comm_by_axes,
                    "compute_fraction": round(prof.compute_fraction, 4),
                    "comm_fraction": round(prof.comm_fraction, 4),
                    "idle_fraction": round(prof.idle_fraction, 4),
                    "measured_mfu": prof.mfu,
                }
            # the instrumented serving replay's memory-ledger peaks
            # (ISSUE 18) ride the same trajectory row, so per-class KV
            # occupancy creep is as machine-readable as tokens/s
            if isinstance(serving, dict) and "memory" in serving:
                smem = serving["memory"]
                row["serving_memory"] = {
                    "peak_pages": smem["peak_pages"],
                    "peak_fragmentation": smem["peak_fragmentation"],
                    "conservation_failures":
                        smem["conservation_failures"],
                    "leaks": smem["leaks"],
                }
            # paged-attention kernel (ISSUE 20): both arms' profiled
            # decode-step component fractions ride the trajectory row,
            # so a kernel regression (compute share collapsing back
            # toward the gather path's idle-dominated split, or the
            # step wall ratio drifting) is machine-readable
            if (isinstance(serving, dict)
                    and isinstance(serving.get("paged_kernel"), dict)):
                spk = serving["paged_kernel"]
                row["serving_paged_kernel"] = {
                    arm: {
                        "step_wall_s": spk[arm]["step_wall_s"],
                        "compute_fraction": spk[arm]["compute_fraction"],
                        "comm_fraction": spk[arm]["comm_fraction"],
                        "idle_fraction": spk[arm]["idle_fraction"],
                    }
                    for arm in ("gather", "paged") if arm in spk
                } | {"summary": spk.get("summary")}
            # fleet goodput (ISSUE 19): availability fraction +
            # incident count per trajectory row — PerfSentinel can
            # watch goodput the same way it watches tokens/s
            if (isinstance(serving, dict)
                    and isinstance(serving.get("control_plane"), dict)
                    and serving["control_plane"].get("goodput")):
                sgp = serving["control_plane"]["goodput"]
                row["serving_goodput"] = {
                    "goodput_fraction": sgp["goodput_fraction"],
                    "incidents": sgp["incidents"],
                    "conservation_ok": sgp["conservation_ok"],
                }
            # baseline = same-device healthy rows only: a CPU rehearsal
            # judged against a TPU trajectory (or vice versa) would
            # stamp a bogus regression into the history forever
            sentinel = PerfSentinel.from_history(
                history_path, device=device_kind, window=8
            )
            verdict = sentinel.observe(row)
            if verdict is not None:
                reason = getattr(verdict, "reason",
                                 None) or verdict.get("reason")
                row["perf_regression"] = reason
                sys.stderr.write(f"bench perf sentinel: REGRESSION vs "
                                 f"history tail — {reason}\n")
            with open(history_path, "a") as hf:
                hf.write(json.dumps(row) + "\n")
            if tel is not None:
                reg.event("bench.history", path=history_path,
                          regression=row.get("perf_regression"))
        except Exception as e:  # noqa: BLE001
            sys.stderr.write(f"bench history failed (non-fatal): {e}\n")
    if tel is not None:
        reg.event("bench.serving", **{
            k: v for k, v in serving.items() if not isinstance(v, dict)
        })
        tel.export_snapshot(reg)
        tel.close()
    if trace is not None:
        trace.write()
        trace.close()
    if not emit(results, serving):
        raise RuntimeError(f"all bench variants failed: {results}")


def main() -> None:
    run_bench(force_cpu=bool(os.environ.get("BENCH_FORCE_CPU")))


if __name__ == "__main__":
    main()
