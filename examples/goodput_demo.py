"""Goodput & incident-ledger demo: where did the fleet's wall clock go,
and what did that crash actually cost?

The run walks the fleet goodput ledger (ISSUE 19,
docs/observability.md "Goodput & incidents"):

- a two-replica ``ControlPlane`` with ``goodput=True``: every
  replica-second of the run is attributed to exactly one class
  (productive / compile_warmup / idle / stall / suspect_probing /
  failed_quarantine / ...) under the conservation contract — per
  replica, class-seconds sum to alive wall within 1e-6 (asserted);
- a seeded ``replica_crash`` (the chaos harness) mid-run: the ledger
  mints ONE ``Incident`` joined to the ``chaos.injection``
  flight-recorder record (detection-latency ticks), accruing a
  capacity-gap integral in replica-seconds while the fleet runs
  degraded;
- ``rejoin`` closes the incident: MTTR (detection -> accepting again)
  and the SLO burn over the incident window land on the incident row
  (asserted > 0);
- the surfaces: the incident table on stdout, ``/debug/goodput`` on a
  live ``OpsServer``, and the per-replica STATE BAND track — one
  colored slice per class episode + incident instant markers — in a
  Perfetto trace next to the router's decision track.

    python examples/goodput_demo.py --fake-devices 8
    JAX_PLATFORMS=cpu python examples/goodput_demo.py --requests 12
"""
from __future__ import annotations

import argparse
import json
import os
import shutil


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=10)
    ap.add_argument("--crash-tick", type=int, default=4)
    ap.add_argument("--steps", type=int, default=2,
                    help="accepted for the shared example-runner CLI; "
                         "serving runs are request-driven")
    ap.add_argument("--out-dir", default="goodput_out")
    ap.add_argument("--fake-devices", type=int, default=None,
                    help="run on N fake CPU devices")
    args = ap.parse_args()
    if args.fake_devices:
        from pipegoose_tpu.testing import force_cpu_devices
        force_cpu_devices(args.fake_devices)

    from urllib.request import urlopen

    import jax

    from pipegoose_tpu import telemetry
    from pipegoose_tpu.models import bloom
    from pipegoose_tpu.serving import (
        Request,
        ServingEngine,
        make_skewed_replay,
    )
    from pipegoose_tpu.serving.control_plane import ControlPlane
    from pipegoose_tpu.testing.chaos import (
        ChaosMonkey,
        ChaosSchedule,
        Injection,
    )

    shutil.rmtree(args.out_dir, ignore_errors=True)
    os.makedirs(args.out_dir, exist_ok=True)

    cfg = bloom.BloomConfig(vocab_size=64, hidden_size=32, n_layer=2,
                            n_head=2)
    params = bloom.init_params(cfg, jax.random.PRNGKey(0))
    replay = make_skewed_replay(
        n_requests=args.requests, n_prefixes=3, prefix_len=32,
        suffix_lens=(2, 4), max_new=3, vocab=64, seed=0, n_tenants=2,
    )

    def factory(name, registry):
        return ServingEngine(params, cfg, num_slots=1, num_pages=33,
                             page_size=8, max_context=96,
                             prefix_cache=True, registry=registry)

    def reqs(seed=0):
        return [Request(prompt=p, max_new_tokens=n, tenant=t)
                for p, n, t in replay]

    # -- a crash mid-run: the ledger watches the whole arc ------------------
    recorder = telemetry.FlightRecorder(args.out_dir, capacity=256)
    plane = ControlPlane(factory, n_replicas=2, policy="cache_aware",
                         recorder=recorder, goodput=True)
    monkey = ChaosMonkey(
        ChaosSchedule([Injection(args.crash_tick, "replica_crash",
                                 (("replica", 1),))]),
        recorder=recorder,
    )
    outs, metrics = plane.run(reqs(), tick_hook=monkey.fleet_hook)
    print(f"crash run: {len(outs)}/{args.requests} requests finished "
          f"(salvage re-dispatched the victim's work)")

    # -- rejoin closes the incident: MTTR + capacity gap stop accruing ------
    plane.rejoin("replica1")
    outs2, _ = plane.run(reqs(seed=1))

    ledger = plane.goodput
    cons = ledger.conservation()
    assert cons["ok"], cons  # class-seconds == alive wall, per replica
    print(f"conservation: max error "
          f"{cons['max_error_s']:.2e}s across "
          f"{len(cons['replicas'])} replicas (contract: <= 1e-6)")

    summary = ledger.summary()
    print(f"goodput fraction {summary['goodput_fraction']:.2%} over "
          f"{summary['wall_seconds']:.2f}s fleet wall:")
    for klass, secs in sorted(summary["classes"].items(),
                              key=lambda kv: -kv[1]):
        print(f"  {klass:>18}: {secs:8.3f}s")

    # -- the incident table -------------------------------------------------
    incidents = ledger.report()["incident_log"]
    assert len(incidents) == 1, incidents
    inc = incidents[0]
    assert not inc["open"] and inc["resolved_by"] == "rejoin"
    assert inc["mttr_s"] > 0 and inc["capacity_gap_integral_s"] > 0
    print("incident ledger:")
    print(f"  #{inc['id']} {inc['kind']} on {inc['replica']} "
          f"(detected tick {inc['tick_detected']}, "
          f"injection join latency "
          f"{inc['detection_latency_ticks']} tick(s))")
    print(f"    MTTR {inc['mttr_s'] * 1e3:.1f}ms "
          f"({inc['mttr_ticks']} ticks, resolved by "
          f"{inc['resolved_by']}); capacity gap integral "
          f"{inc['capacity_gap_integral_s'] * 1e3:.1f} replica-ms")
    print(f"    salvaged uids {inc['salvaged_uids']}, lost "
          f"{inc['lost_uids']}; availability over window "
          f"{inc['slo_burn']['availability']:.2%}")

    # -- the surfaces: /debug/goodput + Perfetto state bands ----------------
    with telemetry.OpsServer(registry=plane.fleet, port=0,
                             fleet=plane.fleet_status,
                             goodput=ledger.report) as srv:
        body = json.loads(
            urlopen(srv.url + "/debug/goodput", timeout=5).read())
        assert body["incidents"] == 1 and body["conservation_ok"]
    trace_path = os.path.join(args.out_dir, "trace.json")
    with telemetry.ChromeTraceExporter(trace_path,
                                       registry=plane.registry) as exp:
        exp.add_goodput(ledger)
        exp.add_router_decisions(plane.router.decisions)
    print(
        f"done: {summary['goodput_fraction']:.2%} of "
        f"{summary['wall_seconds']:.2f} fleet replica-seconds were "
        f"productive; the crash cost "
        f"{inc['capacity_gap_integral_s'] * 1e3:.1f} replica-ms of "
        f"capacity (MTTR {inc['mttr_s'] * 1e3:.1f}ms); open "
        f"{trace_path} in ui.perfetto.dev for the state bands"
    )


if __name__ == "__main__":
    main()
