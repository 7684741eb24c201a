"""Span tracing: nesting paths, fencing, event emission, and the
jit-trace no-op regression (ISSUE 2: spans entered inside traced code
must neither crash nor record)."""
import jax
import jax.numpy as jnp
import pytest

from pipegoose_tpu.telemetry import MetricsRegistry, span
from pipegoose_tpu.telemetry.spans import _NOOP, current_span_path


def test_span_records_histogram_and_event():
    reg = MetricsRegistry(enabled=True)
    events = []
    reg.attach(events.append)
    with span("load", registry=reg, attrs={"shard": 3}):
        pass
    h = reg.histogram("span.load.seconds")
    assert h.count == 1
    assert h.sum >= 0
    (ev,) = events
    assert ev["kind"] == "span" and ev["span"] == "load" and ev["shard"] == 3
    assert ev["dur_s"] >= 0


def test_nested_spans_join_paths():
    reg = MetricsRegistry(enabled=True)
    with span("step", registry=reg):
        assert current_span_path() == "step"
        with span("forward", registry=reg):
            assert current_span_path() == "step.forward"
            with span("attn", registry=reg):
                assert current_span_path() == "step.forward.attn"
        with span("backward", registry=reg):
            assert current_span_path() == "step.backward"
    assert current_span_path() is None
    hists = set(reg.snapshot()["histograms"])
    assert {
        "span.step.seconds",
        "span.step.forward.seconds",
        "span.step.forward.attn.seconds",
        "span.step.backward.seconds",
    } <= hists


def test_fence_blocks_on_device_work():
    reg = MetricsRegistry(enabled=True)
    with span("compute", registry=reg) as sp:
        x = jax.jit(lambda a: (a @ a).sum())(jnp.ones((64, 64)))
        sp.fence(x)
    assert reg.histogram("span.compute.seconds").count == 1
    # fencing a non-array must not raise
    with span("odd", registry=reg) as sp:
        sp.fence(object())
    assert reg.histogram("span.odd.seconds").count == 1


def test_disabled_registry_returns_shared_noop(annotations):
    """The contract since PR 26. Registry off: nothing is recorded and
    nothing fenced, but the annotation is still entered, so a profiler
    session sees the span. Under a jit trace: the shared no-op, which
    annotates nothing either."""
    reg = MetricsRegistry(enabled=False)
    events = []
    reg.attach(events.append)
    s = span("x", registry=reg)
    assert s is not _NOOP
    with s as sp:
        sp.fence(object())  # not kept: nothing to block on at exit
        assert current_span_path() == "x"
    assert sp._fences == []
    assert annotations == [("enter", "x"), ("exit", "x")]
    assert reg.snapshot()["histograms"] == {} and events == []
    assert current_span_path() is None

    seen = []

    @jax.jit
    def f(a):
        seen.append(span("traced", registry=reg))
        return a + 1

    f(jnp.zeros(2))
    assert seen == [_NOOP]
    assert annotations == [("enter", "x"), ("exit", "x")]


@pytest.mark.parametrize("enabled", [False, True])
def test_span_enters_trace_annotation_with_dotted_path(annotations, enabled):
    """A span is a ``TraceAnnotation`` named by its dotted path, whether
    or not the registry records it; children close before parents."""
    reg = MetricsRegistry(enabled=enabled)
    with span("serving.decode_step", registry=reg):
        with span("dispatch", registry=reg):
            pass
        with span("fetch", registry=reg):
            pass
    names = ["serving.decode_step", "serving.decode_step.dispatch",
             "serving.decode_step.fetch"]
    assert annotations == [
        ("enter", names[0]), ("enter", names[1]), ("exit", names[1]),
        ("enter", names[2]), ("exit", names[2]), ("exit", names[0])]
    recorded = set(reg.snapshot()["histograms"])
    assert recorded == ({f"span.{n}.seconds" for n in names} if enabled
                        else set())


def test_annotation_closes_when_the_body_raises(annotations):
    with pytest.raises(RuntimeError):
        with span("boom", registry=MetricsRegistry(enabled=False)):
            raise RuntimeError("x")
    assert annotations == [("enter", "boom"), ("exit", "boom")]
    assert current_span_path() is None


def test_span_inside_jit_noops_cleanly():
    """Regression: a span (and metrics) inside a jitted body is a clean
    no-op — compiled fn still runs, nothing is recorded, repeated
    executions don't accumulate phantom trace-time."""
    reg = MetricsRegistry(enabled=True)
    c = reg.counter("inner.count")

    @jax.jit
    def f(a):
        with span("traced", registry=reg) as sp:
            c.inc()
            sp.fence(a)  # fencing a tracer must not raise
            return a + 1

    for _ in range(4):
        out = f(jnp.zeros(3))
    assert list(out) == [1.0, 1.0, 1.0]
    assert c.value == 0.0
    assert not any(
        "traced" in k for k in reg.snapshot()["histograms"]
    )


def test_exception_inside_span_still_pops_stack():
    reg = MetricsRegistry(enabled=True)
    try:
        with span("boom", registry=reg):
            raise RuntimeError("x")
    except RuntimeError:
        pass
    assert current_span_path() is None
    # the aborted span still recorded its duration (observability of
    # failing regions is the point)
    assert reg.histogram("span.boom.seconds").count == 1


def test_stopiteration_exit_not_recorded():
    """A span around `next(it)` (trainer.fit's data span) must not log a
    phantom sample for the final exhausted pull — StopIteration is
    control flow, not work."""
    reg = MetricsRegistry(enabled=True)
    it = iter([1, 2])
    pulls = 0
    while True:
        try:
            with span("data", registry=reg):
                next(it)
            pulls += 1
        except StopIteration:
            break
    assert pulls == 2
    assert current_span_path() is None
    assert reg.histogram("span.data.seconds").count == 2  # not 3
