"""Control-plane demo: N serving replicas behind one front door —
cache-aware routing, per-tenant fair-share dispatch, and a scale-down
drain that drops zero admitted work.

The run walks the multi-replica control plane (ISSUE 12,
docs/serving.md "Control plane"):

- two ``ServingEngine`` replicas (own scheduler, page pool, radix
  prefix cache each) driven tick-by-tick by a ``ControlPlane``;
- the SAME multi-tenant Zipf-skewed replay routed ``round_robin`` vs
  ``cache_aware`` — the cache-aware arm forwards measurably fewer
  prefill tokens because requests land on the replica already holding
  their longest cached prefix (asserted);
- per-tenant deficit-round-robin dispatch: the hot tenant's flood
  cannot monopolize the early dispatch slots (asserted on the router's
  decision log);
- a forced drain mid-run: in-flight requests preempt, migrate, and
  re-prefill on the surviving replica — outputs token-identical to the
  no-drain run (asserted);
- the fleet surface: merged per-replica metrics (``FleetRegistry``),
  ``/debug/fleet`` on a live ``OpsServer``, and the router's Perfetto
  decision track next to the usual host spans.

    python examples/control_plane_demo.py --fake-devices 8
    JAX_PLATFORMS=cpu python examples/control_plane_demo.py --requests 16
"""
from __future__ import annotations

import argparse
import json
import os
import shutil


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--prefix-len", type=int, default=48)
    ap.add_argument("--steps", type=int, default=2,
                    help="accepted for the shared example-runner CLI; "
                         "serving runs are request-driven")
    ap.add_argument("--out-dir", default="control_plane_out")
    ap.add_argument("--fake-devices", type=int, default=None,
                    help="run on N fake CPU devices")
    args = ap.parse_args()
    if args.fake_devices:
        from pipegoose_tpu.testing import force_cpu_devices
        force_cpu_devices(args.fake_devices)

    from urllib.request import urlopen

    import jax
    import numpy as np

    from pipegoose_tpu import telemetry
    from pipegoose_tpu.models import bloom
    from pipegoose_tpu.serving import (
        Request,
        ServingEngine,
        make_skewed_replay,
    )
    from pipegoose_tpu.serving.control_plane import ControlPlane

    shutil.rmtree(args.out_dir, ignore_errors=True)
    os.makedirs(args.out_dir, exist_ok=True)

    cfg = bloom.BloomConfig(vocab_size=64, hidden_size=32, n_layer=2,
                            n_head=2)
    params = bloom.init_params(cfg, jax.random.PRNGKey(0))
    replay = make_skewed_replay(
        n_requests=args.requests, n_prefixes=3,
        prefix_len=args.prefix_len, suffix_lens=(2, 4), max_new=2,
        vocab=64, seed=0, n_tenants=3,
    )

    def factory(name, registry):
        return ServingEngine(params, cfg, num_slots=1, num_pages=33,
                             page_size=8, max_context=96,
                             prefix_cache=True, registry=registry)

    def reqs():
        return [Request(prompt=p, max_new_tokens=n, tenant=t)
                for p, n, t in replay]

    # -- routing arms: the same trace, two placement policies ---------------
    forwarded = {}
    planes = {}
    # pull_hints off: the A/B isolates ROUTING — with fleet prefix
    # sharing on, a round-robin miss pulls the warm peer's KV pages
    # instead of recomputing and both arms forward the same count
    # (that arm is examples/kv_tier_demo.py's story)
    for policy in ("round_robin", "cache_aware"):
        plane = ControlPlane(factory, n_replicas=args.replicas,
                             policy=policy, pull_hints=False)
        plane.run(reqs())                    # compile + seed caches
        plane.clear_prefix_caches()          # cold caches, warm programs
        outs, metrics = plane.run(reqs())
        forwarded[policy] = metrics["prefill_tokens"]
        planes[policy] = plane
        print(f"{policy:>12}: forwarded {metrics['prefill_tokens']:4d} "
              f"prefill tokens, {metrics['decode_tokens_per_s']:.0f} "
              f"tok/s, shed {metrics['shed_requests']}")
    assert forwarded["cache_aware"] < forwarded["round_robin"], forwarded

    # -- fairness: DRR interleaves tenants in the dispatch order ------------
    plane = planes["cache_aware"]
    order = [d["tenant"] for d in plane.router.decisions][:6]
    print(f"first dispatch wave interleaves tenants: {order}")
    assert len(set(order)) >= 2, order

    # -- drain: scale-down drops zero admitted work -------------------------
    clean, _ = plane.run(reqs())

    def force_drain(p, tick):
        if tick == 3 and len(p.serving_replicas()) > 1:
            def owed(rep):
                s = rep.engine.sched.capacity_snapshot()
                return s["queued_tokens"] + s["active_tokens_remaining"]
            victim = max(p.serving_replicas(), key=owed)
            print(f"tick {tick}: draining {victim.name} "
                  f"({len(victim.engine.sched.active())} in flight)")
            p.start_drain(victim.name)

    drained, metrics = plane.run(reqs(), tick_hook=force_drain)
    assert len(drained) == len(clean)
    for a, b in zip(clean, drained):
        np.testing.assert_array_equal(a.generated, b.generated)
    migrated = int(plane._m_migrated.value)
    print(f"drain migrated {migrated} in-flight request(s); all "
          f"{len(drained)} outputs token-identical to the no-drain run")

    # -- the fleet surface: /debug/fleet + Perfetto router track ------------
    status = plane.fleet_status()
    with telemetry.OpsServer(registry=plane.fleet, port=0,
                             fleet=plane.fleet_status) as srv:
        body = json.loads(
            urlopen(srv.url + "/debug/fleet", timeout=5).read())
        assert body["router"]["decisions_total"] > 0
        n_metrics = len(telemetry.parse_prometheus_text(
            urlopen(srv.url + "/metrics", timeout=5).read().decode()))
    trace_path = os.path.join(args.out_dir, "trace.json")
    with telemetry.ChromeTraceExporter(trace_path,
                                       registry=plane.registry) as exp:
        exp.add_router_decisions(plane.router.decisions)
    print(json.dumps({
        "prefill_tokens": forwarded,
        "replicas": [r["name"] + ":" + r["state"]
                     for r in status["replicas"]],
        "tenants": {t: s["dispatched_token_share"]
                    for t, s in status["tenants"].items()},
        "fleet_metrics_exported": n_metrics,
        "trace": trace_path,
    }, indent=2))
    print(
        f"done: cache-aware routing forwarded "
        f"{forwarded['cache_aware']} vs {forwarded['round_robin']} "
        f"prefill tokens across {args.replicas} replicas; drain dropped "
        f"zero of {len(drained)} requests; open {trace_path} in "
        f"ui.perfetto.dev"
    )


if __name__ == "__main__":
    main()
