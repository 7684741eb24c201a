"""Process-local metrics registry: counters, gauges, histograms.

The reference pipegoose has no metrics at all (its ``DistributedLogger``
is an empty stub, SURVEY.md §5); operating the ROADMAP's "heavy
traffic" north star needs them. Design constraints, in order:

1. **Near-zero overhead when disabled.** Library code (trainer loop,
   serving engine, decode driver) is instrumented UNCONDITIONALLY; the
   global registry starts disabled, so the un-observed cost of a
   ``counter.inc()`` is one attribute read and a branch, and that of a
   ``span()`` entry one small object and a profiler annotation that is
   inert outside a profiler session (each < 5 µs, guarded by
   tests/telemetry/test_registry.py). There is no "if telemetry:"
   litter at call sites.
2. **Safe under jit tracing.** Host-side metric mutation inside a
   traced function would record trace-time (once per COMPILE, not per
   execution) — every mutation no-ops when the value is a
   ``jax.core.Tracer`` or a trace is in progress, so instrumented
   helpers can be called from inside ``jax.jit`` bodies without either
   crashing or double counting.
3. **Thread-safe.** The serving engine and exporters may run on
   different threads; each metric carries its own lock, taken only on
   the enabled path.

Metrics are identified by dotted names (``serving.ttft_seconds``); the
Prometheus exporter sanitizes them. Histograms keep BOTH fixed bucket
counts (cheap, exporter-friendly) and a bounded reservoir (exact
quantiles for small runs, statistically sound for long ones).
"""
from __future__ import annotations

import random
import threading
import time
import zlib
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
from jax._src.core import trace_state_clean


def _tracing(value: Any = None) -> bool:
    """True when recording must no-op: a jit trace is in progress or the
    value itself is a tracer (mutating host state then would count per
    compile, not per execution)."""
    if isinstance(value, jax.core.Tracer):
        return True
    return not trace_state_clean()


class _AlwaysEnabled:
    """Enabled-flag stand-in for metrics constructed WITHOUT a registry
    (standalone use of the exported Counter/Gauge/Histogram classes):
    they record unconditionally, since there is no registry to toggle."""

    _enabled = True


_STANDALONE = _AlwaysEnabled()


class Counter:
    """Monotonic float counter."""

    __slots__ = ("name", "help", "_value", "_lock", "_registry")

    def __init__(self, name: str, help: str = "", registry: "MetricsRegistry" = None):
        self.name = name
        self.help = help
        self._value = 0.0
        self._lock = threading.Lock()
        self._registry = registry if registry is not None else _STANDALONE

    def inc(self, amount: float = 1.0, *, of_trace: bool = False) -> None:
        """``of_trace``: the increment records a fact of the TRACE
        itself (which path the traced program takes), so it is kept
        under a jit trace, where any other increment is dropped. It
        counts once PER TRACE of the calling code, not once a compile:
        a body that ``jax.checkpoint`` or ``lax.scan`` traces again
        counts again."""
        if not self._registry._enabled:
            return
        if isinstance(amount, jax.core.Tracer) or (_tracing() and not of_trace):
            return
        if amount < 0:
            raise ValueError(f"counter {self.name}: negative increment {amount}")
        with self._lock:
            self._value += float(amount)

    @property
    def value(self) -> float:
        return self._value


class Gauge:
    """Point-in-time float value (last write wins)."""

    __slots__ = ("name", "help", "_value", "_lock", "_registry")

    def __init__(self, name: str, help: str = "", registry: "MetricsRegistry" = None):
        self.name = name
        self.help = help
        self._value = float("nan")
        self._lock = threading.Lock()
        self._registry = registry if registry is not None else _STANDALONE

    def set(self, value: float, *, of_trace: bool = False) -> None:
        """``of_trace``: as ``Counter.inc``'s: the value is a fact of the
        TRACE itself (a plan the traced program took), kept under a jit
        trace, where any other write is dropped."""
        if not self._registry._enabled:
            return
        if isinstance(value, jax.core.Tracer) or (_tracing() and not of_trace):
            return
        with self._lock:
            self._value = float(value)

    @property
    def value(self) -> float:
        return self._value


# span/step durations in seconds: 10 µs dispatch noise up to minute-long
# compiles all land in a distinguishable bucket
DEFAULT_TIME_BUCKETS = (
    1e-5, 1e-4, 1e-3, 5e-3, 1e-2, 2.5e-2, 5e-2, 0.1, 0.25, 0.5,
    1.0, 2.5, 5.0, 10.0, 30.0, 60.0,
)


class Histogram:
    """Bucketed + reservoir histogram.

    Fixed cumulative-style bucket counts back the Prometheus export;
    a bounded reservoir (algorithm R, deterministic seed per metric so
    repeat runs export identical snapshots) backs exact-ish quantiles.
    """

    __slots__ = ("name", "help", "buckets", "_counts", "_sum", "_count",
                 "_min", "_max", "_reservoir", "_cap", "_rng", "_lock",
                 "_registry")

    def __init__(self, name: str, help: str = "",
                 buckets: Sequence[float] = DEFAULT_TIME_BUCKETS,
                 reservoir: int = 512, registry: "MetricsRegistry" = None):
        self.name = name
        self.help = help
        self.buckets = tuple(sorted(buckets))
        self._counts = [0] * (len(self.buckets) + 1)  # +1: +Inf bucket
        self._sum = 0.0
        self._count = 0
        self._min = float("inf")
        self._max = float("-inf")
        self._reservoir: List[float] = []
        self._cap = int(reservoir)
        # crc32, not hash(): str hashing is salted per process, and the
        # whole point of the fixed seed is identical reservoirs (hence
        # identical exported quantiles) across repeat runs
        self._rng = random.Random(zlib.crc32(name.encode()))
        self._lock = threading.Lock()
        self._registry = registry if registry is not None else _STANDALONE

    def observe(self, value: float) -> None:
        if not self._registry._enabled:
            return
        if _tracing(value):
            return
        v = float(value)
        with self._lock:
            i = 0
            for i, b in enumerate(self.buckets):  # noqa: B007
                if v <= b:
                    break
            else:
                i = len(self.buckets)
            self._counts[i] += 1
            self._sum += v
            self._count += 1
            self._min = min(self._min, v)
            self._max = max(self._max, v)
            if len(self._reservoir) < self._cap:
                self._reservoir.append(v)
            else:
                j = self._rng.randint(0, self._count - 1)
                if j < self._cap:
                    self._reservoir[j] = v

    @property
    def count(self) -> int:
        return self._count

    @property
    def sum(self) -> float:
        return self._sum

    def quantile(self, q: float) -> float:
        with self._lock:
            sample = sorted(self._reservoir)
        if not sample:
            return float("nan")
        idx = min(int(q * len(sample)), len(sample) - 1)
        return sample[idx]

    def snapshot(self) -> dict:
        with self._lock:
            counts = list(self._counts)
            n, s = self._count, self._sum
            lo, hi = self._min, self._max
        out = {
            "count": n,
            "sum": s,
            "mean": s / n if n else float("nan"),
            "min": lo if n else float("nan"),
            "max": hi if n else float("nan"),
            "p50": self.quantile(0.5),
            "p90": self.quantile(0.9),
            "p99": self.quantile(0.99),
            "buckets": {
                **{str(b): c for b, c in zip(self.buckets, counts)},
                "+Inf": counts[-1],
            },
        }
        return out


class MetricsRegistry:
    """Named home for counters/gauges/histograms plus an event fan-out.

    Metric getters are idempotent (same name -> same object) and
    type-checked: asking for ``counter("x")`` after ``gauge("x")`` is a
    programming error worth failing loudly on. ``event()`` dispatches a
    timestamped dict to attached sinks (exporters.JSONLExporter) — the
    time-series half of telemetry that aggregate metrics can't carry.
    """

    def __init__(self, enabled: bool = True):
        self._enabled = bool(enabled)
        self._metrics: Dict[str, Any] = {}
        self._sinks: List[Callable[[dict], None]] = []
        self._lock = threading.Lock()

    # -- lifecycle ---------------------------------------------------------

    @property
    def enabled(self) -> bool:
        return self._enabled

    def enable(self) -> None:
        self._enabled = True

    def disable(self) -> None:
        self._enabled = False

    def clear(self) -> None:
        """Drop all metrics and sinks (tests). Metric handles resolved
        BEFORE the clear stay functional but detach from the registry —
        their later updates are invisible to snapshot()/to_prometheus().
        Long-lived holders (e.g. a ServingEngine) must be rebuilt, or
        the registry replaced, rather than cleared under them."""
        with self._lock:
            self._metrics.clear()
            self._sinks = []

    # -- metric getters ----------------------------------------------------

    def _get(self, name: str, cls, **kwargs):
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = cls(name, registry=self, **kwargs)
                self._metrics[name] = m
            elif not isinstance(m, cls):
                raise TypeError(
                    f"metric {name!r} already registered as "
                    f"{type(m).__name__}, not {cls.__name__}"
                )
            return m

    def counter(self, name: str, help: str = "") -> Counter:
        return self._get(name, Counter, help=help)

    def gauge(self, name: str, help: str = "") -> Gauge:
        return self._get(name, Gauge, help=help)

    def histogram(self, name: str, help: str = "",
                  buckets: Sequence[float] = DEFAULT_TIME_BUCKETS,
                  reservoir: int = 512) -> Histogram:
        return self._get(name, Histogram, help=help, buckets=buckets,
                         reservoir=reservoir)

    def metrics(self) -> Dict[str, Any]:
        with self._lock:
            return dict(self._metrics)

    # -- events ------------------------------------------------------------

    def attach(self, sink: Callable[[dict], None]) -> None:
        with self._lock:
            if sink not in self._sinks:
                self._sinks.append(sink)

    def detach(self, sink: Callable[[dict], None]) -> None:
        with self._lock:
            if sink in self._sinks:
                self._sinks.remove(sink)

    def event(self, kind: str, **fields: Any) -> None:
        """Dispatch one timestamped event dict to every attached sink."""
        if not self._enabled or not self._sinks:
            return
        if _tracing():
            return
        ev = {"ts": time.time(), "kind": kind, **fields}
        for sink in list(self._sinks):
            sink(ev)

    # -- export ------------------------------------------------------------

    def snapshot(self) -> dict:
        """Plain-dict view of every metric (utils/profiler.py's JSON-able
        convention)."""
        out = {"counters": {}, "gauges": {}, "histograms": {}}
        for name, m in self.metrics().items():
            if isinstance(m, Counter):
                out["counters"][name] = m.value
            elif isinstance(m, Gauge):
                out["gauges"][name] = m.value
            elif isinstance(m, Histogram):
                out["histograms"][name] = m.snapshot()
        return out

    def to_prometheus(self) -> str:
        """Prometheus text exposition (textfile-collector flavor)."""
        lines: List[str] = []
        for name, m in sorted(self.metrics().items()):
            pname = _prom_name(name)
            if isinstance(m, Counter):
                lines.append(f"# TYPE {pname} counter")
                if m.help:
                    lines.append(f"# HELP {pname} {m.help}")
                lines.append(f"{pname} {_prom_value(m.value)}")
            elif isinstance(m, Gauge):
                lines.append(f"# TYPE {pname} gauge")
                if m.help:
                    lines.append(f"# HELP {pname} {m.help}")
                lines.append(f"{pname} {_prom_value(m.value)}")
            elif isinstance(m, Histogram):
                lines.append(f"# TYPE {pname} histogram")
                if m.help:
                    lines.append(f"# HELP {pname} {m.help}")
                with m._lock:  # consistent counts/sum/count vs observe()
                    counts = list(m._counts)
                    h_sum, h_count = m._sum, m._count
                cum = 0
                for b, c in zip(m.buckets, counts):
                    cum += c
                    lines.append(f'{pname}_bucket{{le="{b}"}} {cum}')
                cum += counts[-1]
                lines.append(f'{pname}_bucket{{le="+Inf"}} {cum}')
                lines.append(f"{pname}_sum {_prom_value(h_sum)}")
                lines.append(f"{pname}_count {h_count}")
        return "\n".join(lines) + "\n"


def _prom_name(name: str) -> str:
    out = "".join(c if c.isalnum() or c == "_" else "_" for c in name)
    if out and out[0].isdigit():
        out = "_" + out
    return out


def _prom_value(v: float) -> str:
    if v != v:  # NaN
        return "NaN"
    return repr(float(v))


# -- global default -------------------------------------------------------
#
# Library instrumentation targets this registry; it starts DISABLED so
# un-observed runs pay only the enabled-flag branch. Entry points that
# want telemetry (TelemetryCallback, examples/telemetry_demo)
# call enable().
_default = MetricsRegistry(enabled=False)


def get_registry() -> MetricsRegistry:
    return _default


def enable() -> None:
    _default.enable()


def disable() -> None:
    _default.disable()
