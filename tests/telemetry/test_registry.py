"""Metrics registry: counters/gauges/histograms, thread safety, the
disabled-overhead contract that lets instrumentation live in library
hot loops, and jit-trace safety (ISSUE 2 regression)."""
import json
import threading

import jax
import jax.numpy as jnp
import pytest

from pipegoose_tpu.telemetry import MetricsRegistry
from pipegoose_tpu.telemetry.registry import DEFAULT_TIME_BUCKETS


@pytest.fixture()
def reg():
    return MetricsRegistry(enabled=True)


def test_counter_gauge_basics(reg):
    c = reg.counter("req.total", help="requests")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("depth")
    g.set(7)
    g.set(3)
    assert g.value == 3.0


def test_metric_getters_idempotent_and_type_checked(reg):
    assert reg.counter("x") is reg.counter("x")
    with pytest.raises(TypeError, match="already registered"):
        reg.gauge("x")


def test_histogram_stats_and_quantiles(reg):
    h = reg.histogram("lat.seconds")
    for i in range(1, 101):
        h.observe(i / 1000)  # 1ms..100ms
    assert h.count == 100
    assert h.sum == pytest.approx(5.05)
    snap = h.snapshot()
    assert snap["min"] == pytest.approx(0.001)
    assert snap["max"] == pytest.approx(0.1)
    assert snap["p50"] == pytest.approx(0.05, rel=0.1)
    assert snap["p99"] == pytest.approx(0.1, rel=0.05)
    # bucket counts cover every observation exactly once
    assert sum(
        snap["buckets"][str(b)] for b in DEFAULT_TIME_BUCKETS
    ) + snap["buckets"]["+Inf"] == 100


def test_histogram_reservoir_bounded(reg):
    h = reg.histogram("r", reservoir=64)
    for i in range(10_000):
        h.observe(float(i))
    assert len(h._reservoir) == 64
    assert h.count == 10_000
    # reservoir quantiles stay in the observed range
    assert 0 <= h.quantile(0.5) < 10_000


def test_thread_safety_no_lost_increments(reg):
    c = reg.counter("t")
    h = reg.histogram("th")

    def work():
        for _ in range(10_000):
            c.inc()
            h.observe(0.001)

    threads = [threading.Thread(target=work) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 40_000
    assert h.count == 40_000


def test_disabled_registry_records_nothing():
    reg = MetricsRegistry(enabled=False)
    c = reg.counter("c")
    g = reg.gauge("g")
    h = reg.histogram("h")
    events = []
    reg.attach(events.append)
    c.inc()
    g.set(1.0)
    h.observe(1.0)
    reg.event("e")
    assert c.value == 0.0
    assert g.value != g.value  # NaN: never set
    assert h.count == 0
    assert events == []
    reg.enable()
    c.inc()
    assert c.value == 1.0


def test_disabled_overhead_under_5us(empty_iterations):
    """The CI overhead guard (ISSUE 2): instrumentation stays ON in
    library code because a disabled counter inc / span entry costs next
    to nothing. The bound is RELATIVE (``conftest.empty_iterations``):
    300 iterations of an empty loop; a span entry is ~130, quiet or
    loaded."""
    from pipegoose_tpu.telemetry import span

    reg = MetricsRegistry(enabled=False)
    c = reg.counter("c")

    def enter_span():
        with span("s", registry=reg):
            pass

    assert empty_iterations(c.inc) < 300
    assert empty_iterations(enter_span) < 300


def test_tracer_and_trace_time_mutation_noop(reg):
    """Counters/gauges/histograms touched inside jit-traced code no-op
    cleanly: no crash, no per-compile phantom counts, correct result."""
    c = reg.counter("jit.c")
    g = reg.gauge("jit.g")
    h = reg.histogram("jit.h")

    @jax.jit
    def f(x):
        c.inc()            # trace-time host mutation
        g.set(x.sum())     # tracer value
        h.observe(x[0])    # tracer value
        return x * 2

    for _ in range(3):
        out = f(jnp.arange(4.0))
    assert list(out) == [0.0, 2.0, 4.0, 6.0]
    assert c.value == 0.0
    assert g.value != g.value  # still NaN
    assert h.count == 0


def test_an_increment_of_the_trace_counts_once_a_trace(reg):
    """``inc(of_trace=True)`` is kept under a jit trace (a fact of the
    traced program: once per trace, nothing on later executions of the
    compiled program); a tracer amount and a disabled registry still
    record nothing."""
    c = reg.counter("jit.paths")

    @jax.jit
    def f(x):
        c.inc(of_trace=True)
        c.inc(x[0], of_trace=True)   # a tracer is never an amount
        return x * 2

    for _ in range(3):
        f(jnp.arange(4.0))
    assert c.value == 1.0
    f(jnp.arange(8.0))               # another shape: another compile
    assert c.value == 2.0
    reg.disable()
    jax.jit(lambda x: (c.inc(of_trace=True), x)[1])(jnp.arange(2.0))
    assert c.value == 2.0
    reg.enable()


def test_a_gauge_of_the_trace_holds_what_the_last_trace_set(reg):
    """``set(value, of_trace=True)`` is kept under a jit trace (a plan
    the traced program took); a tracer value, a plain ``set`` under a
    trace and a disabled registry still record nothing."""
    g = reg.gauge("jit.plan_steps")

    @jax.jit
    def f(x):
        g.set(x.shape[0], of_trace=True)
        g.set(x[0], of_trace=True)   # a tracer is never a value
        g.set(-1.0)                  # not asked for by name: dropped
        return x * 2

    f(jnp.arange(4.0))
    assert g.value == 4.0
    f(jnp.arange(8.0))               # another shape: another trace
    f(jnp.arange(4.0))               # compiled: nothing is traced
    assert g.value == 8.0
    reg.disable()
    jax.jit(lambda x: (g.set(2.0, of_trace=True), x)[1])(jnp.arange(2.0))
    assert g.value == 8.0
    reg.enable()


def test_snapshot_and_prometheus_render(reg):
    reg.counter("a.total", help="things").inc(3)
    reg.gauge("b.depth").set(2.0)
    reg.histogram("c.seconds").observe(0.02)
    snap = reg.snapshot()
    assert snap["counters"]["a.total"] == 3.0
    assert snap["gauges"]["b.depth"] == 2.0
    assert snap["histograms"]["c.seconds"]["count"] == 1
    json.dumps(snap)  # JSON-able contract (utils/profiler.py convention)

    text = reg.to_prometheus()
    assert "# TYPE a_total counter" in text
    assert "a_total 3.0" in text
    assert "b_depth 2.0" in text
    assert '# HELP a_total things' in text
    assert 'c_seconds_bucket{le="+Inf"} 1' in text
    assert "c_seconds_count 1" in text
    # cumulative buckets are monotone
    counts = [int(line.rsplit(" ", 1)[1]) for line in text.splitlines()
              if line.startswith("c_seconds_bucket")]
    assert counts == sorted(counts)


def test_events_dispatch_to_sinks(reg):
    got = []
    reg.attach(got.append)
    reg.event("step", i=1)
    reg.detach(got.append)
    reg.event("step", i=2)
    assert len(got) == 1
    assert got[0]["kind"] == "step" and got[0]["i"] == 1
    assert "ts" in got[0]
