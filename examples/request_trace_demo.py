"""Request-tracing demo: a skewed-prefix serving replay with per-request
latency attribution, a live ops endpoint, and a /healthz probe that
flips to 503 under an injected decode stall.

The run wires the full request-observability stack (ISSUE 8,
docs/observability.md):

- ``ServingEngine(tracer=RequestTracer(...))`` — every request's
  lifecycle (admit, prefill chunks with cache-hit counts, first token,
  decode ticks, preemptions) is recorded and its TTFT/e2e decomposed
  into additive queue/prefill/decode/stall components;
- ``SLOMonitor`` — a TTFT SLO evaluated over fast+slow burn-rate
  windows, feeding /healthz;
- ``FlightRecorder`` — a (demo-injected) ``decode_stall`` trigger whose
  black box embeds the request timelines;
- ``OpsServer`` — /metrics (Prometheus text), /healthz (200 -> 503 on
  the stall), /debug/requests (the timelines as JSON), all on an
  ephemeral port;
- ``ChromeTraceExporter.add_request_timelines`` — one Perfetto track
  per decode slot, markers for preempt/COW, next to the host spans.

    python examples/request_trace_demo.py --fake-devices 8
    JAX_PLATFORMS=cpu python examples/request_trace_demo.py --requests 8
"""
from __future__ import annotations

import argparse
import json
import os
import shutil


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=10)
    ap.add_argument("--prefix-len", type=int, default=24)
    ap.add_argument("--max-new", type=int, default=4)
    ap.add_argument("--steps", type=int, default=2,
                    help="accepted for the shared example-runner CLI; "
                         "serving runs are request-driven")
    ap.add_argument("--out-dir", default="reqtrace_out")
    ap.add_argument("--fake-devices", type=int, default=None,
                    help="run on N fake CPU devices")
    args = ap.parse_args()
    if args.fake_devices:
        from pipegoose_tpu.testing import force_cpu_devices
        force_cpu_devices(args.fake_devices)

    import jax
    from urllib.request import urlopen
    from urllib.error import HTTPError

    from pipegoose_tpu import telemetry
    from pipegoose_tpu.models import bloom
    from pipegoose_tpu.serving import Request, ServingEngine, make_skewed_replay

    shutil.rmtree(args.out_dir, ignore_errors=True)
    os.makedirs(args.out_dir, exist_ok=True)

    reg = telemetry.get_registry()
    reg.enable()

    cfg = bloom.BloomConfig(vocab_size=128, hidden_size=64, n_layer=2,
                            n_head=4)
    params = bloom.init_params(cfg, jax.random.PRNGKey(0))

    recorder = telemetry.FlightRecorder(args.out_dir, capacity=32,
                                        registry=reg)
    tracer = telemetry.RequestTracer(registry=reg,
                                     keep_completed=2 * args.requests)
    engine = ServingEngine(
        params, cfg, num_slots=2, num_pages=33, page_size=8,
        max_context=64, prefix_cache=True, prefill_chunk=16,
        recorder=recorder, registry=reg,
    )

    replay = make_skewed_replay(
        n_requests=args.requests, n_prefixes=2, prefix_len=args.prefix_len,
        suffix_lens=(2, 4, 6), max_new=args.max_new, vocab=128, seed=0,
    )

    def requests():
        return [Request(prompt=p, max_new_tokens=n) for p, n in replay]

    engine.run(requests())       # cold: compiles + seeds the prefix cache
    engine.attach_tracer(tracer)  # trace the WARM replay only, so the
    outs, metrics = engine.run(requests())   # attribution has no compiles

    # -- attribution table -------------------------------------------------
    summary = tracer.attribution_summary()
    rows = {r["uid"]: r for r in summary["requests"]}
    print("per-request latency attribution (seconds):")
    print(f"{'uid':>4} {'queue':>8} {'prefill':>8} {'decode':>8} "
          f"{'stall':>8} {'e2e':>8} {'ttft':>8} {'hit_tok':>7}")
    for o in outs:
        r = rows[o.uid]
        c = r["components"]
        print(f"{o.uid:>4} {c['queue_s']:>8.4f} {c['prefill_s']:>8.4f} "
              f"{c['decode_s']:>8.4f} {c['stall_s']:>8.4f} "
              f"{r['e2e_s']:>8.4f} {r['ttft_s']:>8.4f} "
              f"{r['hit_tokens']:>7}")
        assert abs(sum(c.values()) - r["e2e_s"]) <= 0.01 * r["e2e_s"]
    print(f"mean components: {summary['mean_components']}")
    print(f"cache hit share: {summary['cache_hit_share']:.2%}")

    # -- Perfetto export ---------------------------------------------------
    trace_path = os.path.join(args.out_dir, "request_trace.json")
    exporter = telemetry.ChromeTraceExporter(trace_path, registry=reg)
    exporter.add_request_timelines(tracer)
    exporter.write()
    exporter.close()

    # -- ops endpoint + injected stall -------------------------------------
    slo = telemetry.SLOMonitor(
        telemetry.default_serving_slos(ttft_objective_s=5.0),
        registry=reg, recorder=recorder,
    )
    ops = telemetry.OpsServer(registry=reg, port=0, slo=slo,
                              recorder=recorder, tracer=tracer)
    url = ops.start()
    assert url is not None
    metrics_text = urlopen(url + "/metrics", timeout=5).read().decode()
    n_samples = len(telemetry.parse_prometheus_text(metrics_text))
    hz = urlopen(url + "/healthz", timeout=5)
    assert hz.status == 200 and json.loads(hz.read())["ok"] is True
    print(f"/metrics: {n_samples} samples; /healthz: 200 ok")

    dbg = json.loads(urlopen(url + "/debug/requests", timeout=5).read())
    assert len(dbg["completed"]) >= args.requests

    # inject a decode stall: the watchdog path fires the same trigger a
    # real livelock would, black-boxing the request timelines
    trig = recorder.trigger_decode_stall(
        0, "demo-injected stall: queue head can never be admitted",
        context={"injected": True},
    )
    try:
        urlopen(url + "/healthz", timeout=5)
        raise AssertionError("/healthz stayed 200 under a stall trigger")
    except HTTPError as e:
        body = json.loads(e.read())
        assert e.code == 503 and body["problems"][0]["name"] == "decode_stall"
        print(f"/healthz after injected stall: 503 "
              f"({body['problems'][0]['reason']})")
    box = json.load(open(trig.dump_path))
    assert "request_timelines" in box
    ops.stop()

    print(json.dumps({
        "requests": len(outs),
        "decode_tokens_per_s": metrics["decode_tokens_per_s"],
        "cache_hit_share": round(summary["cache_hit_share"], 4),
        "mean_ttft_s": round(summary["mean_ttft_s"], 6),
        "ops_metrics_samples": n_samples,
        "black_box": trig.dump_path,
        "trace": trace_path,
    }, indent=2))
    print(
        f"done: {len(outs)} requests attributed "
        f"(hit share {summary['cache_hit_share']:.0%}), /healthz flipped "
        f"200->503 on the injected stall; open {trace_path} in "
        f"ui.perfetto.dev"
    )


if __name__ == "__main__":
    main()
