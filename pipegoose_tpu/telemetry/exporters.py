"""Telemetry exporters: JSONL event stream + Prometheus textfile.

(The third exporter shape — Perfetto/Chrome ``trace_event`` JSON — has
its own module, ``telemetry.chrometrace``: ``ChromeTraceExporter``
follows the same sink/rank conventions as ``JSONLExporter`` here, and
``trace_from_jsonl`` converts an existing JSONL stream offline.)

Two complementary shapes, both plain files (no daemon, no deps):

- ``JSONLExporter`` — an append-only event stream (one JSON object per
  line). Attach it to a registry and every ``registry.event(...)`` /
  span exit lands as a line; ``export_snapshot`` additionally embeds a
  full metrics snapshot as a ``"snapshot"`` event: a time series
  (occupancy, step durations) any line reader can follow.
- ``PrometheusTextfileExporter`` — the node-exporter textfile-collector
  convention: one atomic snapshot file a scraper ingests. Written via
  tmp+rename so a concurrent scrape never sees a torn file.

Both reuse ``DistributedLogger``'s rank convention: only the process
with ``jax.process_index() == rank`` writes (``rank=None`` = all
processes, each should then get its own path). The process index is
looked up lazily and cached after the first success, so constructing an
exporter never forces backend initialization.
"""
from __future__ import annotations

import json
import math
import os
import tempfile
import threading
from typing import IO, Optional

from pipegoose_tpu.telemetry.registry import MetricsRegistry
from pipegoose_tpu.utils.procindex import RankFilter as _RankFilter


def atomic_write_text(path: str, text: str, suffix: str = ".tmp") -> None:
    """tmp + rename so a concurrent reader never sees a torn file — the
    one atomic-write implementation every telemetry artifact writer
    (Prometheus textfile, black-box dumps, Chrome traces) shares."""
    d = os.path.dirname(os.path.abspath(path)) or "."
    os.makedirs(d, exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=d, suffix=suffix)
    try:
        with os.fdopen(fd, "w") as f:
            f.write(text)
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


class JSONLExporter:
    """Append-only JSONL event sink (see module docstring).

    Callable — satisfies the registry sink protocol — and attaches
    itself when constructed with ``registry=``.
    """

    def __init__(self, path: str, registry: Optional[MetricsRegistry] = None,
                 rank: Optional[int] = 0, mode: str = "a"):
        """``mode="a"`` (default) appends across exporter lifetimes —
        one long-lived stream; ``mode="w"`` truncates on first write,
        for per-run artifacts where stale events from a
        previous attempt must not interleave."""
        if mode not in ("a", "w"):
            raise ValueError(f"mode must be 'a' or 'w', got {mode!r}")
        self.path = path
        self._mode = mode
        self._rank_ok = _RankFilter(rank)
        self._file: Optional[IO[str]] = None
        self._lock = threading.Lock()
        self._registry = registry
        if registry is not None:
            registry.attach(self)

    def _handle(self) -> Optional[IO[str]]:
        if not self._rank_ok():
            return None
        if self._file is None:
            d = os.path.dirname(os.path.abspath(self.path))
            os.makedirs(d, exist_ok=True)
            self._file = open(self.path, self._mode)
        return self._file

    def __call__(self, event: dict) -> None:
        # serialize OUTSIDE the lock, then one locked write+flush: two
        # threads sharing this sink (serving engine + trainer callback)
        # must not interleave bytes into torn JSONL lines
        line = safe_json_dumps(event) + "\n"
        with self._lock:
            f = self._handle()
            if f is None:
                return
            f.write(line)
            f.flush()

    def export_snapshot(self, registry: Optional[MetricsRegistry] = None) -> None:
        """Write the full metrics snapshot as one ``"snapshot"`` event."""
        reg = registry or self._registry
        if reg is None:
            raise ValueError("no registry to snapshot")
        import time

        self({"ts": time.time(), "kind": "snapshot", **reg.snapshot()})

    def close(self) -> None:
        if self._registry is not None:
            self._registry.detach(self)
        if self._file is not None:
            self._file.close()
            self._file = None

    def __enter__(self) -> "JSONLExporter":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class PrometheusTextfileExporter:
    """Atomic Prometheus text-exposition snapshot writer."""

    def __init__(self, path: str, rank: Optional[int] = 0):
        self.path = path
        self._rank_ok = _RankFilter(rank)

    def write(self, registry: MetricsRegistry) -> Optional[str]:
        """Render ``registry`` and atomically replace ``self.path``;
        returns the path written, or None when rank-filtered out."""
        if not self._rank_ok():
            return None
        atomic_write_text(self.path, registry.to_prometheus(),
                          suffix=".prom.tmp")
        return self.path


def _jsonable(x):
    """Best-effort conversion for numpy/jax scalars reaching the stream.
    Non-finite values become strings: json.dumps would otherwise emit
    bare ``Infinity``/``NaN`` tokens, which are NOT JSON — jq, JS
    ``JSON.parse``, and log pipelines reject the artifact exactly when
    a nonfinite anomaly (the interesting case) is in it."""
    try:
        f = float(x)
    except (TypeError, ValueError):
        return repr(x)
    return f if math.isfinite(f) else repr(f)


def _sanitize(obj):
    """Recursively stringify non-finite floats (see ``_jsonable``) —
    plain python floats never reach a ``default=`` hook, so payloads
    holding inf/nan (health trees, NaN-loss events) need this pass."""
    if isinstance(obj, float):
        return obj if math.isfinite(obj) else repr(obj)
    if isinstance(obj, dict):
        return {k: _sanitize(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_sanitize(v) for v in obj]
    return obj


def safe_json_dumps(obj, **kwargs) -> str:
    """``json.dumps`` that emits strictly valid (RFC 8259) JSON: every
    non-finite float — nested or numpy/jax-scalar — lands as the string
    ``'inf'``/``'-inf'``/``'nan'``. All telemetry artifact writers
    (JSONL stream, black-box dumps, Chrome traces) route through it."""
    return json.dumps(_sanitize(obj), default=_jsonable, **kwargs)
