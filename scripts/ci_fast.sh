#!/usr/bin/env bash
# Fast-tier CI: the curated `pytest -m fast` smoke (one representative
# slice per subsystem, < 5 min on one core — see tests/conftest.py's
# FAST_FILES/FAST_TESTS tables) on fake CPU devices.
#
# The telemetry disabled-cost guards run FIRST and separately, so a
# perf regression in the always-on instrumentation (the < 5 µs
# counter/span contract, the health-off byte-identical-program
# contract) fails loudly up front instead of drowning in the tier's
# output:
#
#   ./scripts/ci_fast.sh            # guards + full fast tier
#   ./scripts/ci_fast.sh -x -q      # extra pytest args pass through
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=cpu

# Persistent XLA compilation cache for the SERVING smokes (same dir as
# tests/conftest.py — see there for why it is serving-only). Prefix a
# smoke's python invocation with $JAX_SERVING_CACHE_ENV to opt it in.
JAX_SERVING_CACHE_ENV="JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-$PWD/.jax_cache} JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS=0 JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES=0"

# Static jit-safety lint FIRST (scripts/lint_jit_safety.py): pure AST,
# no jax import — host-sync calls (.item(), np.asarray, time.*,
# jax.device_get) or bare excepts landing in a jit-path module fail in
# about a second, before anything compiles. Known host-side modules
# live in scripts/jit_safety_allowlist.txt.
echo "== jit-safety lint =="
python scripts/lint_jit_safety.py

echo "== telemetry disabled-cost guards =="
python -m pytest -q -p no:cacheprovider \
    "tests/telemetry/test_registry.py::test_disabled_overhead_under_5us" \
    "tests/telemetry/test_health.py::test_health_off_lowers_to_the_unchanged_program" \
    "$@"

# The sharding-regression gate (mesh doctor, telemetry/doctor.py):
# compile the hybrid train step AND the serving decode step AND the
# chunked-prefill mixed-step program (prefix cache + chunking on,
# ISSUE 6) on an 8-fake-device mesh and fail (exit 2) on
# partitioner-inserted resharding collectives, intended-vs-actual spec
# mismatches, or large replicated buffers — a broken PartitionSpec
# dies here at compile time, not in a TPU bench.
echo "== sharding-regression guard (mesh doctor) =="
python scripts/mesh_doctor.py --fake-devices 8 --tp 2 --dp 4 \
    --check --serving --quiet

# The comm-engine variant of the same gate: the ring-overlap train step
# must compile with ppermute collectives in place of the monolithic
# layer gather AND still zero partitioner-inserted resharding
# (docs/comm.md) — a regression that silently falls back to the
# monolithic path fails here, not in a TPU bench.
echo "== sharding-regression guard (mesh doctor, overlap variant) =="
python scripts/mesh_doctor.py --fake-devices 8 --tp 2 --dp 4 \
    --overlap --grad-comm int8 --check --expect-ppermute --quiet

# The parallelism-planner gate (pipegoose_tpu/planner/, ISSUE 7): rank
# the layout space for the smoke model on 8 fake devices and verify the
# expected-best config — the ring-overlap + int8-wire layout the comm
# engine exists to make fastest — still scores within tolerance of the
# planner's top-1. A regression that silently drops the ppermute
# overlap or the compressed gradient wire format collapses that
# config's relative score and exits 2 here, at compile time.
echo "== parallelism-planner gate =="
python scripts/plan_parallelism.py --fake-devices 8 \
    --grad-comms fp32,int8 --remat-sweep on \
    --check --tp 4 --dp 2 --overlap --grad-comm int8 \
    --tolerance 0.3 --quiet

# Ops-endpoint smoke (telemetry/opsserver.py, ISSUE 8): start the live
# endpoint on an ephemeral port, scrape /metrics and /healthz, and
# assert the exposition parses — the stdlib-only serving observability
# surface must come up before any engine does.
echo "== ops endpoint smoke =="
python - <<'PY'
import json
from urllib.request import urlopen

from pipegoose_tpu.telemetry.opsserver import OpsServer, parse_prometheus_text
from pipegoose_tpu.telemetry.registry import MetricsRegistry

reg = MetricsRegistry(enabled=True)
reg.counter("smoke.requests_total").inc(3)
reg.histogram("smoke.latency_seconds").observe(0.01)
with OpsServer(registry=reg, port=0) as srv:
    assert srv.url, "ops server refused to start"
    body = urlopen(srv.url + "/metrics", timeout=5).read().decode()
    parsed = parse_prometheus_text(body)
    assert parsed["smoke_requests_total"] == 3.0, body
    assert parsed["smoke_latency_seconds_count"] == 1.0, body
    hz = urlopen(srv.url + "/healthz", timeout=5)
    assert hz.status == 200 and json.loads(hz.read())["ok"] is True
print("ops endpoint smoke OK")
PY

# Chaos smoke (testing/chaos.py + trainer recovery, ISSUE 9): a SEEDED
# nonfinite-gradient bomb mid-run must be detected, black-boxed, and
# rolled back to the last checkpoint, and the run must finish with
# finite losses — the recovery path stays exercised on every CI run,
# not just when the robustness suites rotate through the fast tier.
echo "== chaos smoke (seeded nonfinite bomb -> recovery) =="
python - <<'PY'
import shutil
import tempfile

from pipegoose_tpu.testing import ChaosMonkey, ChaosSchedule, force_cpu_devices

force_cpu_devices(1)

import jax
import jax.numpy as jnp
import numpy as np
import optax

from pipegoose_tpu.distributed import ParallelContext
from pipegoose_tpu.models import bloom
from pipegoose_tpu.optim.zero import DistributedOptimizer
from pipegoose_tpu.telemetry import FlightRecorder
from pipegoose_tpu.trainer import AutoRecovery, CheckpointCallback, Trainer

cfg = bloom.BloomConfig(vocab_size=64, hidden_size=32, n_layer=2, n_head=2)
params = bloom.init_params(cfg, jax.random.PRNGKey(0))
out = tempfile.mkdtemp(prefix="chaos_smoke_")
try:
    schedule = ChaosSchedule.seeded(1234, max_step=4, min_step=2,
                                    nonfinite_grads=1)
    recorder = FlightRecorder(out + "/bb", capacity=16)
    monkey = ChaosMonkey(schedule, recorder=recorder,
                         checkpoint_dir=out + "/ckpt")
    recovery = AutoRecovery(out + "/ckpt", max_restores=2,
                            recorder=recorder)
    ctx = ParallelContext()
    trainer = Trainer(
        lambda p, ids: bloom.loss_fn(p, ids, None, ids, cfg,
                                     tp_axis="tensor"),
        params, bloom.tp_specs(params),
        DistributedOptimizer(optax.adam(1e-3), axis_name="data"), ctx,
        callbacks=[monkey, CheckpointCallback(out + "/ckpt", every=1),
                   recorder, recovery],
    )
    rng = np.random.RandomState(0)
    state = trainer.fit(
        jnp.asarray(rng.randint(1, cfg.vocab_size, (4, 8)))
        for _ in range(6)
    )
    assert len(monkey.applied) == 1, monkey.applied_json()
    assert recovery.restores == 1, recovery.restores
    assert state.losses and all(
        np.isfinite(float(l)) for l in state.losses
    ), state.losses
finally:
    shutil.rmtree(out, ignore_errors=True)
print("chaos smoke OK: injected nonfinite bomb recovered, losses finite")
PY

# Quant greedy-parity smoke (pipegoose_tpu/quant/ + serving, ISSUE 10):
# an int8-weight + int8-KV engine must serve the exact token streams of
# the fp engine on a shared-prefix workload, at >= 1.8x measured page
# capacity — the quantization accuracy contract stays exercised on
# every CI run before the tier proper.
echo "== quant greedy-parity smoke (int8 weights + int8 KV) =="
env $JAX_SERVING_CACHE_ENV python - <<'PY'
from pipegoose_tpu.testing import force_cpu_devices

force_cpu_devices(1)

import jax
import numpy as np

from pipegoose_tpu.models import bloom
from pipegoose_tpu.serving import Request, ServingEngine

cfg = bloom.BloomConfig(vocab_size=64, hidden_size=64, n_layer=2, n_head=4)
params = bloom.init_params(cfg, jax.random.PRNGKey(0))
rng = np.random.RandomState(7)
shared = rng.randint(1, 64, (9,))
reqs = [(np.concatenate([shared, rng.randint(1, 64, (k,))]), n)
        for k, n in [(2, 4), (4, 3)]]

def serve(**quant):
    eng = ServingEngine(params, cfg, num_slots=2, num_pages=16,
                        page_size=4, max_context=32, prefix_cache=True,
                        **quant)
    outs, _ = eng.run([Request(prompt=p, max_new_tokens=n)
                       for p, n in reqs])
    return eng, [np.asarray(o.generated) for o in outs]

_, fp = serve()
eng, q = serve(weight_dtype="int8", kv_dtype="int8")
for a, b in zip(fp, q):
    np.testing.assert_array_equal(a, b, err_msg="int8 engine diverged")
ratio = eng.memory_report()["kv"]["page_capacity_ratio"]
assert ratio >= 1.8, f"page capacity {ratio} < 1.8x"
print(f"quant smoke OK: greedy token-identical, {ratio}x page capacity")
PY

# Disagg smoke (serving/disagg/, ISSUE 13): a 2-pool CPU run — prefill
# pool streaming int8 KV pages into a decode pool — must emit token
# streams identical to one monolithic engine, with the tracer's new
# `transfer` phase keeping queue+prefill+transfer+decode+stall == e2e
# exactly. The cross-mesh handoff contract stays exercised on every CI
# run before the tier proper.
echo "== disagg smoke (2-pool token identity + exact attribution) =="
env $JAX_SERVING_CACHE_ENV python - <<'PY'
from pipegoose_tpu.testing import force_cpu_devices

force_cpu_devices(1)

import jax
import numpy as np

from pipegoose_tpu.models import bloom
from pipegoose_tpu.serving import DisaggEngine, Request, ServingEngine
from pipegoose_tpu.telemetry import MetricsRegistry
from pipegoose_tpu.telemetry.reqtrace import RequestTracer

cfg = bloom.BloomConfig(vocab_size=64, hidden_size=64, n_layer=2, n_head=4)
params = bloom.init_params(cfg, jax.random.PRNGKey(0))
rng = np.random.RandomState(7)
shared = rng.randint(1, 64, (9,))
reqs = [(np.concatenate([shared, rng.randint(1, 64, (k,))]), n)
        for k, n in [(2, 4), (4, 3)]]

def requests():
    return [Request(prompt=p, max_new_tokens=n) for p, n in reqs]

single = ServingEngine(params, cfg, num_slots=2, num_pages=16, page_size=4,
                       max_context=32, prefix_cache=True, prefill_chunk=8,
                       kv_dtype="int8", registry=MetricsRegistry())
ref, _ = single.run(requests())

reg = MetricsRegistry(enabled=True)
tracer = RequestTracer(registry=reg, keep_completed=8)
pe = ServingEngine(params, cfg, num_slots=2, num_pages=16, page_size=4,
                   max_context=32, prefix_cache=True, prefill_chunk=8,
                   prefill_only=True, kv_dtype="int8",
                   registry=MetricsRegistry())
de = ServingEngine(params, cfg, num_slots=2, num_pages=16, page_size=4,
                   max_context=32, prefix_cache=True, prefill_chunk=8,
                   kv_dtype="int8", registry=MetricsRegistry(),
                   stall_patience=10_000)
disagg = DisaggEngine(pe, de, max_inflight=4, registry=reg, tracer=tracer)
outs, metrics = disagg.run(requests())
for a, b in zip(ref, outs):
    np.testing.assert_array_equal(a.generated, b.generated,
                                  err_msg="disagg diverged")
for tl in tracer.completed:
    total = sum(tl.components.values())
    assert abs(total - tl.e2e_s) < 1e-6, (tl.uid, total, tl.e2e_s)
    assert tl.components["transfer_s"] > 0, "transfer phase missing"
xfer = metrics["transfer"]
assert xfer["wire_bytes"] < xfer["fp_equiv_bytes"], xfer
print(f"disagg smoke OK: token-identical across pools, attribution exact, "
      f"{xfer['pages']} pages at {xfer['wire_bytes']} wire bytes "
      f"({xfer['wire_savings_ratio']:.0%} under fp)")
PY

# Crash-recovery smoke (serving/control_plane/ + testing/chaos.py,
# ISSUE 15): a SEEDED replica_crash mid-run on a 2-replica fleet must
# be detected by the health state machine, the dead replica
# quarantined, and every admitted request SALVAGED onto the survivor —
# outputs token-identical to the no-crash fleet, zero requests lost.
echo "== crash-recovery smoke (2 replicas, seeded replica_crash) =="
env $JAX_SERVING_CACHE_ENV python - <<'PY'
import tempfile

from pipegoose_tpu.testing import (
    ChaosMonkey,
    ChaosSchedule,
    force_cpu_devices,
    schedule_fingerprint,
)

force_cpu_devices(1)

import jax
import numpy as np

from pipegoose_tpu.models import bloom
from pipegoose_tpu.serving import Request, ServingEngine, make_skewed_replay
from pipegoose_tpu.serving.control_plane import ControlPlane
from pipegoose_tpu.telemetry import FlightRecorder

cfg = bloom.BloomConfig(vocab_size=64, hidden_size=32, n_layer=2, n_head=2)
params = bloom.init_params(cfg, jax.random.PRNGKey(0))
replay = make_skewed_replay(n_requests=10, n_prefixes=3, prefix_len=32,
                            suffix_lens=(2, 4), max_new=2, vocab=64,
                            seed=0, n_tenants=2)
reqs = lambda: [Request(prompt=p, max_new_tokens=m, tenant=t)
                for p, m, t in replay]

def factory(name, registry):
    return ServingEngine(params, cfg, num_slots=1, num_pages=33,
                         page_size=8, max_context=96, prefix_cache=True,
                         registry=registry)

out = tempfile.mkdtemp(prefix="crash_smoke_")
recorder = FlightRecorder(out, capacity=64)
plane = ControlPlane(factory, n_replicas=2, recorder=recorder)
clean, _ = plane.run(reqs())
schedule = ChaosSchedule.seeded(99, max_step=6, min_step=4,
                                replica_crash=1, n_replicas=2)
assert schedule_fingerprint(schedule) == schedule_fingerprint(
    ChaosSchedule.seeded(99, max_step=6, min_step=4, replica_crash=1,
                         n_replicas=2)), "seeded schedule not reproducible"
monkey = ChaosMonkey(schedule, recorder=recorder)
crashed, metrics = plane.run(reqs(), tick_hook=monkey.fleet_hook)
assert len(monkey.applied) == 1, monkey.applied_json()
assert len(crashed) == len(clean) == 10, (len(clean), len(crashed))
for a, b in zip(clean, crashed):
    np.testing.assert_array_equal(a.generated, b.generated,
                                  err_msg="crash recovery diverged")
assert plane._m_failures.value == 1.0, "crash was not detected"
assert plane._m_lost.value == 0.0, "admitted requests were lost"
assert plane.fleet_status()["failed"] == 1
assert recorder.last_trigger is None, "recovered failure left /healthz red"
print(f"crash-recovery smoke OK: replica failed + quarantined, "
      f"{int(plane._m_salvaged.value + plane._m_resubmitted.value)} "
      f"request(s) salvaged, outputs token-identical, 0 lost")
PY

# Profile smoke (telemetry/xprof.py, ISSUE 14): measured step
# attribution of a tiny hybrid step on fake CPU devices — the
# compute + per-axis-collective + idle components must sum to the
# fenced step wall time within 5%, the profiled collective set must
# agree op-for-op with the mesh doctor's compiled schedule, and the
# StepProfile JSON must round-trip. The measured mirror of the doctor
# gates above stays exercised on every CI run.
echo "== profile smoke (measured step attribution) =="
python - <<'PY'
import json

from pipegoose_tpu.testing import force_cpu_devices

force_cpu_devices(8)

import jax
import jax.numpy as jnp
import numpy as np
import optax

from pipegoose_tpu.distributed import ParallelContext
from pipegoose_tpu.models import bloom
from pipegoose_tpu.optim.zero import DistributedOptimizer
from pipegoose_tpu.parallel import make_hybrid_train_step
from pipegoose_tpu.telemetry import diagnose
from pipegoose_tpu.telemetry.xprof import StepProfile, profile_step

cfg = bloom.BloomConfig(vocab_size=64, hidden_size=32, n_layer=2, n_head=2)
params = bloom.init_params(cfg, jax.random.PRNGKey(0))
ctx = ParallelContext(tensor_parallel_size=2, data_parallel_size=4)
try:
    specs = bloom.tp_specs(params)
    opt = DistributedOptimizer(optax.adam(1e-3), axis_name="data")
    init_fn, make_step = make_hybrid_train_step(
        lambda p, ids: bloom.loss_fn(p, ids, None, ids, cfg,
                                     tp_axis="tensor"),
        specs, opt, ctx,
    )
    opt_state = init_fn(params)
    step = make_step(params)
    ids = jnp.asarray(np.random.RandomState(0).randint(1, 64, (8, 8)))
    prof = profile_step(
        step, params, opt_state, ids, steps=3,
        update_args=lambda out, a: (out[0], out[1], a[2]),
        mesh=ctx.mesh,
    )
    assert prof.source == "device_trace", prof.source
    total = prof.compute_s + prof.comm_s + prof.idle_s
    assert abs(total - prof.wall_step_s) <= 0.05 * prof.wall_step_s, (
        total, prof.wall_step_s, prof.residual_s)
    # op-for-op agreement with the doctor's compiled schedule
    rep = diagnose(step, params, opt_state, ids, mesh=ctx.mesh)
    sched = {c.name for c in rep.sharding.collectives}
    measured = {c["name"] for c in prof.collectives}
    assert measured == sched, (sorted(measured ^ sched))
    rt = StepProfile.from_json(json.loads(json.dumps(prof.to_json())))
    assert rt.comm_by_axes == prof.comm_by_axes
    assert abs(rt.wall_step_s - prof.wall_step_s) < 1e-12
finally:
    ctx.destroy()
print(f"profile smoke OK: {len(prof.collectives)} collectives matched "
      f"op-for-op, compute/comm/idle = "
      f"{prof.compute_fraction:.0%}/{prof.comm_fraction:.0%}/"
      f"{prof.idle_fraction:.0%} of {prof.wall_step_s*1e3:.1f}ms")
PY

# KV-tier smoke (serving/kv_tier/, ISSUE 16): an int8 pool whose
# working set overflows HBM spills evicted prefix pages into the
# host-DRAM tier and restores them on replay — outputs token-identical
# to an all-HBM reference, the restore-aware latency attribution sums
# to e2e exactly, and the tier's resident bytes equal the int8 wire
# census (q+scale planes, never fp).
echo "== kv-tier smoke (host-DRAM spill/restore) =="
env $JAX_SERVING_CACHE_ENV python - <<'PY'
from pipegoose_tpu.testing import force_cpu_devices

force_cpu_devices(1)

import jax
import numpy as np

from pipegoose_tpu.models import bloom
from pipegoose_tpu.serving import Request, ServingEngine
from pipegoose_tpu.serving.kv_tier import HostTier
from pipegoose_tpu.serving.kv_tier.restore import wire_page_bytes
from pipegoose_tpu.telemetry.reqtrace import RequestTracer

cfg = bloom.BloomConfig(vocab_size=64, hidden_size=64, n_layer=2, n_head=4)
params = bloom.init_params(cfg, jax.random.PRNGKey(0))
rng = np.random.RandomState(7)
prefixes = [rng.randint(1, 64, (12,)) for _ in range(2)]
suffixes = [rng.randint(1, 64, (2,)) for _ in range(2)]


def phase(prefix):
    return [Request(prompt=np.concatenate([prefix, s]).astype(np.int32),
                    max_new_tokens=4) for s in suffixes]


kw = dict(num_slots=2, page_size=4, max_context=32, prefill_chunk=4,
          prefix_cache=True, kv_dtype="int8")
tier = HostTier(1 << 20)
eng = ServingEngine(params, cfg, num_pages=9, host_tier=tier, **kw)
tracer = RequestTracer()
eng.attach_tracer(tracer)
ref = ServingEngine(params, cfg, num_pages=33, **kw)

outs, routs, restored = [], [], 0
for pfx in (prefixes[0], prefixes[1], prefixes[0]):
    done, m = eng.run(phase(pfx))
    outs += [o.generated for o in done]
    restored += m.get("kv_tier", {}).get("restored_tokens", 0)
    rdone, _ = ref.run(phase(pfx))
    routs += [o.generated for o in rdone]
assert tier.spills > 0, "overflow never spilled into the tier"
assert restored > 0 and tier.restores > 0, "replay never restored"
for a, b in zip(outs, routs):
    np.testing.assert_array_equal(
        a, b, err_msg="spill->restore round trip diverged from all-HBM")
tls = list(tracer.completed)
assert tls, "tracer recorded nothing"
for tl in tls:
    total = sum(tl.components.values())
    assert abs(total - tl.e2e_s) <= 1e-6 * max(tl.e2e_s, 1.0), (
        tl.uid, total, tl.e2e_s, tl.components)
assert any(tl.components["restore_s"] > 0 for tl in tls), (
    "no request attributed restore time")
wire = wire_page_bytes(eng)
assert tier.resident_bytes == tier.resident_pages * wire, (
    tier.resident_bytes, tier.resident_pages, wire)
rep = eng.memory_report()["host_tier"]
assert rep["resident_bytes"] == tier.resident_bytes
print(f"kv-tier smoke OK: {tier.spills} page(s) spilled, "
      f"{tier.restores} restored ({restored} tokens), outputs "
      f"token-identical to all-HBM, attribution sums to e2e, "
      f"{tier.resident_pages} x {wire} B int8 wire slabs resident")
PY

# Fleet-trace smoke (telemetry/fleettrace.py, ISSUE 17): a 2-replica
# plane with a seeded replica_crash mid-run — every stitched
# cross-replica trace (plane hops + per-replica phases, INCLUDING the
# salvaged request's victim + survivor legs) must sum to its fleet e2e
# at 1e-6, and the replica_failure black box must embed a tail
# exemplar naming the dominant hop. The distributed-tracing exactness
# contract stays exercised on every CI run before the tier proper.
echo "== fleet-trace smoke (2 replicas, stitched crash-salvage trace) =="
env $JAX_SERVING_CACHE_ENV python - <<'PY'
import json
import tempfile

from pipegoose_tpu.testing import ChaosMonkey, ChaosSchedule, force_cpu_devices
from pipegoose_tpu.testing.chaos import Injection

force_cpu_devices(1)

import jax

from pipegoose_tpu.models import bloom
from pipegoose_tpu.serving import Request, ServingEngine, make_skewed_replay
from pipegoose_tpu.serving.control_plane import ControlPlane
from pipegoose_tpu.telemetry import FleetTracer, FlightRecorder
from pipegoose_tpu.telemetry.registry import MetricsRegistry

cfg = bloom.BloomConfig(vocab_size=64, hidden_size=32, n_layer=2, n_head=2)
params = bloom.init_params(cfg, jax.random.PRNGKey(0))
replay = make_skewed_replay(n_requests=8, n_prefixes=3, prefix_len=32,
                            suffix_lens=(2, 4), max_new=2, vocab=64,
                            seed=0, n_tenants=2)

def factory(name, registry):
    return ServingEngine(params, cfg, num_slots=1, num_pages=33,
                         page_size=8, max_context=96, prefix_cache=True,
                         registry=registry)

out = tempfile.mkdtemp(prefix="fleettrace_smoke_")
reg = MetricsRegistry(enabled=True)
ft = FleetTracer(registry=reg)
recorder = FlightRecorder(out, capacity=64)
plane = ControlPlane(factory, n_replicas=2, registry=reg,
                     recorder=recorder, fleet_tracer=ft)
monkey = ChaosMonkey(
    ChaosSchedule([Injection(4, "replica_crash", (("replica", 1),))]),
    recorder=recorder,
)
outs, _ = plane.run(
    [Request(prompt=p, max_new_tokens=m, tenant=t) for p, m, t in replay],
    tick_hook=monkey.fleet_hook,
)
assert len(outs) == 8 and len(monkey.applied) == 1, len(outs)
done = [t for t in ft.completed if not t.lost]
assert len(done) == 8, len(done)
salvaged = [t for t in done if len(t.legs) > 1]
assert salvaged, "crash produced no multi-leg stitched trace"
for t in done:
    row = t.attribution()
    assert abs(row["stitched_total_s"] - t.e2e_s) < 1e-6, (
        t.trace_id, row["stitched_total_s"], t.e2e_s)
    for leg in t.legs:
        assert leg["timeline"].trace_id == t.trace_id
box_path = [p for p in recorder.dumps if "replica_failure" in p][0]
with open(box_path) as f:
    box = json.load(f)
ex = box["trigger"]["details"]["exemplar"]
assert ex and ex["dominant_hop"], "black box lost its exemplar"
assert "fleet_traces" in box, "flight recorder dropped the trace embed"
print(f"fleet-trace smoke OK: {len(done)} stitched traces exact at 1e-6 "
      f"({len(salvaged)} salvaged across replicas, "
      f"{max(len(t.legs) for t in done)} legs max); replica_failure "
      f"exemplar names {ex['dominant_hop']}")
PY

# Memory-audit smoke (telemetry/memledger.py, ISSUE 18): a skewed
# overflow replay with the live memory ledger attached and the leak
# audit running EVERY tick — per-owner-class page accounting must sum
# to pool capacity exactly on every tick, the audit must find zero
# leaks/double-owners/strands, and /debug/memory must serve a parsing
# JSON report over real HTTP. The byte-exact conservation contract
# stays exercised on every CI run before the tier proper.
echo "== memory-audit smoke (ledger conservation + /debug/memory) =="
env $JAX_SERVING_CACHE_ENV python - <<'PY'
import json
from urllib.request import urlopen

from pipegoose_tpu.testing import force_cpu_devices

force_cpu_devices(1)

import jax

from pipegoose_tpu.models import bloom
from pipegoose_tpu.serving import Request, ServingEngine, make_skewed_replay
from pipegoose_tpu.telemetry import MemoryLedger
from pipegoose_tpu.telemetry.opsserver import OpsServer
from pipegoose_tpu.telemetry.registry import MetricsRegistry

cfg = bloom.BloomConfig(vocab_size=64, hidden_size=32, n_layer=2, n_head=2)
params = bloom.init_params(cfg, jax.random.PRNGKey(0))
replay = make_skewed_replay(n_requests=8, n_prefixes=2, prefix_len=8,
                            suffix_lens=(2, 4), max_new=4, vocab=64,
                            seed=0, working_set_factor=1.5,
                            num_pages=17, page_size=4)
eng = ServingEngine(params, cfg, num_slots=2, num_pages=17, page_size=4,
                    max_context=32, prefix_cache=True, prefill_chunk=4,
                    memledger=MemoryLedger(audit_every=1),
                    registry=MetricsRegistry(enabled=True))
breaks = []

def hook(engine, tick):
    cons = engine.memledger.conservation()
    if not cons["ok"]:
        breaks.append((tick, cons))

outs, metrics = eng.run(
    [Request(prompt=p, max_new_tokens=m) for p, m in replay],
    tick_hook=hook)
assert len(outs) == 8, len(outs)
ml = eng.memledger
assert breaks == [], f"conservation broke: {breaks[:3]}"
mem = metrics["memory"]
assert mem["conservation_failures"] == 0, mem
assert mem["leaks"] == 0 and ml.audits_run > 0, mem
assert ml.last_audit["ok"], ml.last_audit
with OpsServer(registry=eng.registry, port=0, memory=ml.report) as srv:
    body = urlopen(srv.url + "/debug/memory", timeout=5).read().decode()
rep = json.loads(body)
assert rep["conservation"]["ok"] is True, rep["conservation"]
total = sum(c["pages"] for c in rep["classes"].values())
assert total == rep["capacity_pages"], (total, rep["capacity_pages"])
print(f"memory-audit smoke OK: {ml.ticks} ticks conserved exactly, "
      f"{ml.audits_run} audits clean (0 leaks), /debug/memory parses "
      f"({rep['capacity_bytes']} B capacity, "
      f"peak request {mem['peak_pages'].get('request', 0)} page(s))")
PY

# Goodput smoke (telemetry/goodput.py, ISSUE 19): a 2-replica plane
# with the goodput ledger attached and a SEEDED replica_crash mid-run
# — per-replica class-seconds must sum to alive wall EXACTLY (the
# conservation contract at 1e-6), and the crash must mint exactly ONE
# incident that closes at rejoin with MTTR > 0 and a positive
# capacity-gap integral. The wall-attribution contract stays exercised
# on every CI run before the tier proper.
echo "== goodput smoke (conservation + seeded crash incident) =="
env $JAX_SERVING_CACHE_ENV python - <<'PY'
import tempfile

from pipegoose_tpu.testing import force_cpu_devices

force_cpu_devices(1)

import jax

from pipegoose_tpu.models import bloom
from pipegoose_tpu.serving import Request, ServingEngine, make_skewed_replay
from pipegoose_tpu.serving.control_plane import ControlPlane
from pipegoose_tpu.telemetry import FlightRecorder
from pipegoose_tpu.testing.chaos import ChaosMonkey, ChaosSchedule, Injection

cfg = bloom.BloomConfig(vocab_size=64, hidden_size=32, n_layer=2, n_head=2)
params = bloom.init_params(cfg, jax.random.PRNGKey(0))
replay = make_skewed_replay(n_requests=8, n_prefixes=3, prefix_len=32,
                            suffix_lens=(2, 4), max_new=3, vocab=64,
                            seed=0, n_tenants=2)

def factory(name, registry):
    return ServingEngine(params, cfg, num_slots=1, num_pages=33,
                         page_size=8, max_context=96, prefix_cache=True,
                         registry=registry)

recorder = FlightRecorder(tempfile.mkdtemp(), capacity=128)
plane = ControlPlane(factory, n_replicas=2, policy="cache_aware",
                     recorder=recorder, goodput=True)
monkey = ChaosMonkey(
    ChaosSchedule([Injection(4, "replica_crash", (("replica", 1),))]),
    recorder=recorder)
outs, metrics = plane.run(
    [Request(prompt=p, max_new_tokens=m, tenant=t) for p, m, t in replay],
    tick_hook=monkey.fleet_hook)
assert len(outs) == 8, len(outs)
plane.rejoin("replica1")
cons = plane.goodput.conservation()
assert cons["ok"] and cons["max_error_s"] <= 1e-6, cons
incidents = plane.goodput.report()["incident_log"]
assert len(incidents) == 1, incidents
inc = incidents[0]
assert inc["kind"] == "crash" and not inc["open"], inc
assert inc["resolved_by"] == "rejoin" and inc["mttr_s"] > 0, inc
assert inc["capacity_gap_integral_s"] > 0, inc
assert inc["detection_latency_ticks"] == 0, inc
gs = metrics["goodput"]
assert gs["conservation_ok"] and 0 < gs["goodput_fraction"] <= 1, gs
print(f"goodput smoke OK: {len(cons['replicas'])} replicas conserved "
      f"exactly (max err {cons['max_error_s']:.1e}s), 1 crash incident "
      f"MTTR {inc['mttr_s']*1e3:.1f}ms, gap integral "
      f"{inc['capacity_gap_integral_s']*1e3:.1f} replica-ms, goodput "
      f"{gs['goodput_fraction']:.0%}")
PY

# Paged-attention kernel smoke (ops/paged_attention.py, ISSUE 20):
# one int8 decode step through the paged one-pass attention (off-TPU
# auto mode: the compiled XLA lane of the kernel's algorithm) must
# match the XLA gather reference's logits (allclose) and greedy token
# exactly, and the VMEM feasibility guard must REFUSE an oversized
# tile for compiled runs instead of silently falling back to gather. (The tp=2 zero-resharding pin on the kernel step rides the
# mesh-doctor --serving gate above — its serving reports now include
# the paged decode/chunk programs.)
echo "== paged-attention kernel smoke (int8 parity + VMEM guard) =="
env $JAX_SERVING_CACHE_ENV python - <<'PY'
from pipegoose_tpu.testing import force_cpu_devices

force_cpu_devices(1)

import jax
import jax.numpy as jnp
import numpy as np

from pipegoose_tpu.models import bloom
from pipegoose_tpu.ops import check_paged_tile
from pipegoose_tpu.serving.kv_pool import (
    init_pages,
    paged_decode_step,
    paged_prefill_chunk,
)

cfg = bloom.BloomConfig(vocab_size=64, hidden_size=64, n_layer=2, n_head=4)
params = bloom.init_params(cfg, jax.random.PRNGKey(0))
rng = np.random.RandomState(3)
kp, vp = init_pages(cfg, 16, 4, kv_dtype="int8")
pt = jnp.asarray(rng.permutation(np.arange(1, 16))[:8][None], jnp.int32)
ids = jnp.asarray(rng.randint(1, 64, (1, 7)), jnp.int32)
n_valid = jnp.asarray([7], jnp.int32)
_, kp, vp = paged_prefill_chunk(params, ids, kp, vp, pt,
                                jnp.zeros((1,), jnp.int32), n_valid, cfg)
tok = jnp.asarray(rng.randint(1, 64, (1,)), jnp.int32)
ref, _, _ = paged_decode_step(params, tok, kp, vp, pt, n_valid, cfg)
out, _, _ = paged_decode_step(params, tok, kp, vp, pt, n_valid, cfg,
                              attn_impl="paged")
err = float(jnp.max(jnp.abs(ref - out)))
assert err < 1e-4, f"kernel diverged from gather: max |dlogits| = {err}"
assert int(jnp.argmax(ref, -1)[0]) == int(jnp.argmax(out, -1)[0])
# the guard refuses an infeasible tile loudly for compiled runs and
# stays exempt in interpret mode (the interpreter has no VMEM limit)
try:
    check_paged_tile(4096, 4096, 1, quantized=True, interpret=False)
    raise SystemExit("VMEM guard accepted an impossible tile")
except ValueError as e:
    assert "VMEM" in str(e), e
check_paged_tile(4096, 4096, 1, quantized=True, interpret=True)
print(f"paged kernel smoke OK: int8 decode step token-identical "
      f"(max |dlogits| {err:.1e}), VMEM guard raises on oversized tile")
PY

echo "== fast tier =="
python -m pytest tests/ -q -m fast -p no:cacheprovider \
    --continue-on-collection-errors "$@"
