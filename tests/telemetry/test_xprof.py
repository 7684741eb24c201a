"""Measured step attribution (telemetry/xprof.py, ISSUE 14): trace
parsing + schedule joining on synthetic events, the real
profiled shard_map program's per-axis buckets and sum-to-wall contract,
and the host-clock fallback."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from pipegoose_tpu.distributed.compat import shard_map
from pipegoose_tpu.telemetry import xprof
from pipegoose_tpu.telemetry.doctor import CollectiveInfo
from pipegoose_tpu.telemetry.registry import MetricsRegistry
from pipegoose_tpu.telemetry.xprof import (
    StepProfile,
    attribute_op_times,
    op_events,
    profile_step,
    set_profile_gauges,
)


def _ev(name, dur_us, module="jit_step", with_args=True):
    e = {"ph": "X", "pid": 1, "tid": 1, "ts": 0.0, "dur": dur_us,
         "name": name}
    if with_args:
        e["args"] = {"hlo_module": module, "hlo_op": name}
    return e


# -- parsing / attribution (pure host) ------------------------------------


def test_attribute_op_times_buckets_and_joins_schedule():
    """Durations divide by steps x devices; collective events join the
    doctor schedule by instruction name (async halves by stem) and
    inherit its axes + bytes; unmatched collectives land in '?'."""
    sched = [
        CollectiveInfo(op="all-reduce", bytes=256, mesh_axes=("tensor",),
                       source="psum", intentional=True, name="all-reduce.2"),
        CollectiveInfo(op="all-gather", bytes=512, mesh_axes=("data",),
                       source="", intentional=False, name="all-gather.7"),
    ]
    events = (
        # 2 steps x 2 devices = 4 executions of each instruction
        [_ev("dot.1", 100.0) for _ in range(4)]
        + [_ev("all-reduce.2", 40.0) for _ in range(4)]
        # async halves: -start and -done both attribute to the stem row
        + [_ev("all-gather-start.7", 10.0) for _ in range(4)]
        + [_ev("all-gather-done.7", 10.0) for _ in range(4)]
        + [_ev("all-to-all.9", 8.0) for _ in range(4)]  # not in schedule
    )
    att = attribute_op_times(events, steps=2, n_devices=2, schedule=sched)
    assert att["compute_s"] == pytest.approx(100e-6)
    assert att["comm_by_axes"]["tensor"] == pytest.approx(40e-6)
    assert att["comm_by_axes"]["data"] == pytest.approx(20e-6)
    assert att["comm_by_axes"]["?"] == pytest.approx(8e-6)
    assert att["comm_s"] == pytest.approx(68e-6)
    rows = {c["name"]: c for c in att["collectives"]}
    assert rows["all-reduce.2"]["bytes"] == 256
    assert rows["all-reduce.2"]["axes"] == ["tensor"]
    assert rows["all-gather-start.7"]["bytes"] == 512
    assert rows["all-to-all.9"]["bytes"] == 0
    assert rows["all-to-all.9"]["op"] == "all-to-all"
    assert att["top_ops"][0]["name"] == "dot.1"


@pytest.mark.parametrize("container", ["while.4", "conditional.2", "call"])
def test_attribute_op_times_counts_a_loop_by_its_body(container):
    """A while (conditional, call) event spans its body's ops, which
    are events of their own: counting both doubled the compute time of
    every program with a layer loop."""
    events = [_ev(container, 90.0), _ev("dot.1", 50.0), _ev("fusion.3", 30.0)]
    att = attribute_op_times(events, steps=1, n_devices=1)
    assert att["compute_s"] == pytest.approx(80e-6)
    assert container not in att["per_op"]


def test_op_events_module_filter_and_name_fallback():
    """Primary selection is args.hlo_module == module; traces whose op
    events carry no args fall back to the compiled module's
    instruction-name set."""
    events = [
        _ev("dot.1", 10.0, module="jit_step"),
        _ev("dot.1", 10.0, module="jit_other"),
        {"ph": "X", "name": "fusion.3", "dur": 5.0},   # no args
        {"ph": "M", "name": "process_name", "args": {}},
    ]
    got = op_events(events, "jit_step", {"dot.1", "fusion.3"})
    assert len(got) == 1 and got[0]["args"]["hlo_module"] == "jit_step"
    # no primary match at all -> name-set fallback picks argless events
    got = op_events(events, "jit_missing", {"fusion.3"})
    assert len(got) == 1 and got[0]["name"] == "fusion.3"


def test_step_profile_json_round_trip_and_components():
    p = StepProfile(
        steps=2, n_devices=4, wall_step_s=0.01, compute_s=0.004,
        comm_s=0.003, idle_s=0.003, residual_s=0.003,
        comm_by_axes={"tensor": 0.002, "data": 0.001},
        collectives=[{"name": "all-reduce.2", "op": "all-reduce",
                      "axes": ["tensor"], "seconds": 0.002, "bytes": 64,
                      "intentional": True}],
        source="device_trace", device_kind="cpu", module_name="jit_step",
        hlo_instructions=123, flops_per_device=1e9, mfu=0.1,
        fabric_utilization={"tensor": 0.5},
        top_ops=[{"name": "dot.1", "seconds": 0.004}],
        wall_steps_s=[0.01, 0.01],
    )
    assert p.compute_fraction == pytest.approx(0.4)
    assert p.components() == {
        "compute_s": 0.004, "idle_s": 0.003,
        "comm[tensor]_s": 0.002, "comm[data]_s": 0.001,
    }
    d = json.loads(json.dumps(p.to_json()))
    # the serialized form carries the derived fractions for artifacts
    assert d["comm_fraction"] == pytest.approx(0.3)
    rt = StepProfile.from_json(d)
    assert rt == p
    # forward compat: unknown keys at the top level are ignored
    d["new_field_from_the_future"] = {"x": 1}
    assert StepProfile.from_json(d) == p
    assert "all-reduce.2" in p.format_table()


def test_set_profile_gauges():
    reg = MetricsRegistry(enabled=True)
    p = StepProfile(
        steps=1, n_devices=1, wall_step_s=0.01, compute_s=0.005,
        comm_s=0.002, idle_s=0.003, residual_s=0.003,
        comm_by_axes={}, collectives=[], source="device_trace",
        device_kind="cpu", mfu=0.25,
    )
    set_profile_gauges(p, registry=reg)
    snap = reg.snapshot()["gauges"]
    assert snap["perf.compute_fraction"] == pytest.approx(0.5)
    assert snap["perf.comm_fraction"] == pytest.approx(0.2)
    assert snap["perf.idle_fraction"] == pytest.approx(0.3)
    assert snap["perf.measured_mfu"] == pytest.approx(0.25)


def test_find_trace_file_skips_perfetto(tmp_path):
    run = tmp_path / "plugins" / "profile" / "run1"
    run.mkdir(parents=True)
    (run / "host.trace.json.gz").write_bytes(b"x")
    (run / "perfetto_trace.json.gz").write_bytes(b"y")
    got = xprof.find_trace_file(str(tmp_path))
    assert got is not None and got.endswith("host.trace.json.gz")
    assert xprof.find_trace_file(str(tmp_path / "empty")) is None


# -- the real profiled program (compiling, tier-1) -------------------------


def _sharded_step(devices):
    mesh = Mesh(np.array(devices[:4]).reshape(2, 2), ("data", "tensor"))

    def f(x, w):
        y = jax.lax.psum(x @ w, "tensor")
        return jax.lax.pmean(y, "data")

    step = jax.jit(shard_map(
        f, mesh=mesh, in_specs=(P("data", "tensor"), P("tensor", None)),
        out_specs=P(None, None), check_vma=False,
    ))
    return step, mesh


def test_profile_step_sharded_program_axes_and_sum(devices):
    """The acceptance contract on a real compiled program: per-axis
    collective buckets from the doctor-schedule join, components sum to
    the fenced wall within 5%, JSON round-trips."""
    step, mesh = _sharded_step(devices)
    x = jnp.ones((8, 64))
    w = jnp.ones((64, 32))
    prof = profile_step(step, x, w, steps=3, mesh=mesh)
    assert prof.source == "device_trace"
    assert prof.n_devices == 4 and prof.steps == 3
    assert set(prof.comm_by_axes) == {"tensor", "data"}
    total = prof.compute_s + prof.comm_s + prof.idle_s
    assert total == pytest.approx(prof.wall_step_s, rel=0.05)
    assert prof.compute_s > 0 and prof.comm_s > 0
    assert prof.hlo_instructions and prof.hlo_instructions > 3
    names = {c["name"] for c in prof.collectives}
    # XLA names the instruction after the jax op (``psum.14``); the op
    # kind comes from the schedule join
    assert len(names) == 2
    assert all(c["op"] == "all-reduce" for c in prof.collectives)
    rt = StepProfile.from_json(json.loads(json.dumps(prof.to_json())))
    assert rt.comm_by_axes == prof.comm_by_axes
    assert rt.wall_steps_s == prof.wall_steps_s


def test_profile_step_host_clock_fallback(devices, monkeypatch):
    """A backend whose trace carries no op events degrades to the
    host-clock attribution: wall time lands on compute, loudly
    labelled, instead of crashing or reporting zeros."""
    monkeypatch.setattr(xprof, "find_trace_file", lambda d: None)
    step, mesh = _sharded_step(devices)
    prof = profile_step(step, jnp.ones((8, 64)), jnp.ones((64, 32)),
                        steps=2, warmup=1, mesh=mesh)
    assert prof.source == "host_clock"
    assert prof.compute_s == pytest.approx(prof.wall_step_s)
    assert prof.comm_s == 0.0 and prof.idle_s == 0.0
    assert prof.collectives == []


def test_profile_step_validates_inputs(devices):
    step, mesh = _sharded_step(devices)
    with pytest.raises(ValueError, match="steps"):
        profile_step(step, jnp.ones((8, 64)), jnp.ones((64, 32)), steps=0)
    with pytest.raises(ValueError, match="warmup"):
        profile_step(step, jnp.ones((8, 64)), jnp.ones((64, 32)),
                     warmup=-1)
