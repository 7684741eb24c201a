"""End-to-end telemetry demo: one observed training run + one observed
serving run, exported as a JSONL event stream and a Prometheus textfile
snapshot (pipegoose_tpu/telemetry/, docs/observability.md).

The artifacts carry: per-step train spans (``span.train.step.seconds``)
and events, a tokens/s gauge, an MFU gauge derived from the compiler's
own FLOP count of the jitted train step (``compiled_step_stats``), the
per-step comm-bytes gauge, and the serving engine's TTFT /
per-token-decode-latency histograms plus its occupancy time series.
Also cross-checks that engine telemetry agrees with the legacy
aggregate metrics dict (tokens/s within 1%).

    python examples/telemetry_demo.py --fake-devices 8 --tp 2 --dp 4
    JAX_PLATFORMS=cpu python examples/telemetry_demo.py --steps 5
"""
from __future__ import annotations

import argparse
import json
import os


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--out-dir", default="telemetry_out")
    ap.add_argument("--fake-devices", type=int, default=None,
                    help="run on N fake CPU devices")
    args = ap.parse_args()
    if args.fake_devices:
        from pipegoose_tpu.testing import force_cpu_devices
        force_cpu_devices(args.fake_devices)

    import jax
    import numpy as np
    import optax

    from pipegoose_tpu import telemetry
    from pipegoose_tpu.distributed import ParallelContext
    from pipegoose_tpu.models import bloom
    from pipegoose_tpu.optim.zero import DistributedOptimizer
    from pipegoose_tpu.serving import Request, ServingEngine
    from pipegoose_tpu.telemetry import TelemetryCallback
    from pipegoose_tpu.trainer import Trainer

    os.makedirs(args.out_dir, exist_ok=True)
    jsonl_path = os.path.join(args.out_dir, "telemetry.jsonl")
    prom_path = os.path.join(args.out_dir, "metrics.prom")
    reg = telemetry.get_registry()
    exporter = telemetry.JSONLExporter(jsonl_path, registry=reg)

    cfg = bloom.BloomConfig(vocab_size=512, hidden_size=128, n_layer=2,
                            n_head=4)
    params = bloom.init_params(cfg, jax.random.PRNGKey(0))

    # -- observed training run -------------------------------------------
    ctx = ParallelContext(tensor_parallel_size=args.tp,
                          data_parallel_size=args.dp)

    def loss_fn(p, ids):
        return bloom.loss_fn(p, ids, None, ids, cfg, tp_axis="tensor")

    def batches():
        rng = np.random.RandomState(0)
        for _ in range(args.steps):
            yield rng.randint(0, cfg.vocab_size, (args.batch, args.seq))

    trainer = Trainer(
        loss_fn,
        params,
        bloom.tp_specs(params),
        DistributedOptimizer(optax.adam(1e-3), axis_name="data"),
        ctx,
        callbacks=[TelemetryCallback(
            jsonl=exporter,     # shared stream: serving lands in it too
            auto_cost=True,     # MFU + comm bytes from the compiled step
            fence=True,         # exact per-step device attribution
        )],
    )
    state = trainer.fit(batches(), max_steps=args.steps)

    # -- observed serving run (same registry, same JSONL stream) ---------
    rng = np.random.RandomState(7)
    reqs = []
    for _ in range(args.requests):
        plen = int(rng.randint(2, 12))
        reqs.append(Request(prompt=rng.randint(1, cfg.vocab_size, (plen,)),
                            max_new_tokens=int(rng.randint(2, 10))))
    engine = ServingEngine(params, cfg, num_slots=2, num_pages=32,
                           page_size=4, max_context=64, registry=reg)
    outs, metrics = engine.run(reqs)

    # telemetry must agree with the legacy aggregate dict (within 1%)
    tel_tps = reg.gauge("serving.tokens_per_s").value
    legacy_tps = metrics["decode_tokens_per_s"]
    drift = abs(tel_tps - legacy_tps) / max(legacy_tps, 1e-9)
    assert drift < 0.01, (tel_tps, legacy_tps)

    # -- export -----------------------------------------------------------
    exporter.export_snapshot(reg)
    exporter.close()
    telemetry.PrometheusTextfileExporter(prom_path).write(reg)

    snap = reg.snapshot()
    mfu = snap["gauges"].get("train.mfu")
    summary = {
        "train_steps": state.step,
        "final_loss": round(float(state.last_loss), 4),
        "train_tokens_per_s": round(snap["gauges"]["train.tokens_per_s"], 1),
        "train_mfu": round(mfu, 6) if mfu is not None else None,
        "step_p50_s": round(
            snap["histograms"]["span.train.step.seconds"]["p50"], 6),
        "serving_ttft_p50_s": round(
            snap["histograms"]["serving.ttft_seconds"]["p50"], 6),
        "serving_decode_token_p50_s": round(
            snap["histograms"]["serving.decode_token_seconds"]["p50"], 6),
        "serving_tokens_per_s": round(tel_tps, 2),
        "legacy_tokens_per_s": legacy_tps,
        "jsonl": jsonl_path,
        "prom": prom_path,
    }
    print(json.dumps(summary, indent=2))
    print(
        f"done: {state.step} train steps + {len(outs)} served requests "
        f"observed; tokens/s agreement drift {drift:.2%}; artifacts in "
        f"{args.out_dir}/"
    )


if __name__ == "__main__":
    main()
