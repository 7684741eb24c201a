"""Span tracing: named, nestable regions on two clocks, with device fencing.

``with span("decode_step") as sp: ...`` does two things:

* it ALWAYS enters a ``jax.profiler.TraceAnnotation`` named by the
  span's dotted path, so a profiler session (``jax.profiler.start_trace``
  or ``.trace``) holds the region on the host plane, on the same clock as
  the device's ``XLA Ops`` line. With no session active the annotation
  does nothing (well under a microsecond);
* when the registry is enabled it also records the region's wall time
  (``time.perf_counter``) as a histogram (``span.<dotted.path>.seconds``)
  and a ``"span"`` event for the JSONL stream. A disabled registry
  records nothing.

Spans nest through a thread-local stack — a span opened inside another
takes the joined path (``step.forward``) on both clocks — which is how
the per-step breakdown (data/forward/backward/optimizer/comms) is
assembled without any global schema.

**Fencing.** JAX dispatch is asynchronous: the host returns from a
jitted call long before the device finishes, so a naive wall-time span
around a dispatch measures enqueue cost, not work. ``sp.fence(x)``
registers arrays to ``jax.block_until_ready`` at span exit so the
device work that produced them is attributed to THIS span. Fencing only
happens when the span records (registry enabled) — disabled runs keep
full async pipelining, under a profiler session too.

**Jit safety.** ``span()`` returns a shared no-op while a jit trace is
in progress: entering a span inside a traced function must neither
crash nor record or annotate trace-time (the fence would also be
meaningless — you cannot block on a tracer). Guarded by
tests/telemetry/test_spans.py.
"""
from __future__ import annotations

import threading
import time
from typing import Any, Optional

import jax

from pipegoose_tpu.telemetry.registry import (
    MetricsRegistry,
    _tracing,
    get_registry,
)

_tls = threading.local()


def _stack() -> list:
    s = getattr(_tls, "stack", None)
    if s is None:
        s = _tls.stack = []
    return s


class _NoopSpan:
    """Shared trace-time span: every operation is a no-op."""

    __slots__ = ()

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def fence(self, *arrays: Any) -> None:
        pass


_NOOP = _NoopSpan()


class Span:
    __slots__ = ("name", "path", "_registry", "_attrs", "_t0", "_fences",
                 "_annotation")

    def __init__(self, name: str, registry: Optional[MetricsRegistry],
                 attrs: Optional[dict] = None):
        self.name = name
        self.path = name  # finalized on __enter__ (nesting)
        self._registry = registry  # None: annotate only, record nothing
        self._attrs = attrs
        self._t0 = 0.0
        self._fences: list = []
        self._annotation = None

    def fence(self, *arrays: Any) -> None:
        """Block on these arrays at span exit so their device work lands
        in this span's duration (recording spans only)."""
        if self._registry is not None:
            self._fences.extend(arrays)

    def __enter__(self) -> "Span":
        stack = _stack()
        self.path = f"{stack[-1].path}.{self.name}" if stack else self.name
        stack.append(self)
        self._annotation = jax.profiler.TraceAnnotation(self.path)
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        for x in self._fences:
            try:
                jax.block_until_ready(x)
            except Exception:  # noqa: BLE001 - non-array fence targets
                pass
        dur = time.perf_counter() - self._t0
        self._annotation.__exit__(exc_type, exc, tb)
        stack = _stack()
        if stack and stack[-1] is self:
            stack.pop()
        reg = self._registry
        if reg is None or exc_type is StopIteration:
            # StopIteration is iterator-protocol control flow, not work:
            # a span around `next(it)` (trainer.fit's data span) would
            # otherwise log a phantom near-zero sample for the final
            # exhausted pull, skewing the data-time quantiles it exists
            # to report
            return False
        reg.histogram(f"span.{self.path}.seconds").observe(dur)
        reg.event("span", span=self.path, dur_s=dur,
                  **(self._attrs or {}))
        return False


def span(name: str, *, registry: Optional[MetricsRegistry] = None,
         attrs: Optional[dict] = None):
    """Context manager around a named region (see module docstring):
    a profiler annotation always, a registry record when that is enabled.

    Returns a shared no-op object while a jit trace is in progress. With
    the registry disabled and no profiler session the cost is one small
    object and an inert annotation (about a microsecond) — safe to
    leave in library hot loops.
    """
    if _tracing():
        return _NOOP
    reg = registry if registry is not None else get_registry()
    return Span(name, reg if reg._enabled else None, attrs)


def current_span_path() -> Optional[str]:
    """Dotted path of the innermost live span on this thread, or None."""
    stack = _stack()
    return stack[-1].path if stack else None
