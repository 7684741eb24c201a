"""What every driver shares: finding files by name, the checks that
decide ``correct``, the traced window and the result line."""
from __future__ import annotations

import contextlib
import importlib.util
import json
import math
import os
import shutil
import tempfile
import time
from dataclasses import dataclass, field

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmark")


@dataclass
class Context:
    """What a driver is given."""
    cell: dict
    config: dict
    workload: dict
    seed: int
    seconds: float
    trace: bool
    devices: list
    peaks: dict
    watch: object
    checks: object
    # perf_counter when ``require_devices()`` returned: where ``setup_s``
    # starts
    t_chip: float = 0.0
    trace_summary: dict = None
    # left by a driver for its ``control`` (read_limits.py)
    reference: dict = None
    sample: list = None


@dataclass
class Result:
    """What a driver hands back."""
    end_to_end: dict            # metric name -> value, host clocks
    attempted: int
    failed: int
    t_window_start: float       # perf_counter at the first measured step
    memory_peak_bytes: int
    facts: dict = field(default_factory=dict)   # for the layer readers
    extra: dict = field(default_factory=dict)   # more keys for the result line
    trace: dict = None          # trace_reduce.summarise(), --trace 1 only


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    """Import one file by path; its name may hold dots and dashes."""
    if not os.path.exists(path):
        raise FileNotFoundError(path)
    name = "_bench_" + "".join(c if c.isalnum() else "_" for c in
                               os.path.relpath(path, ROOT))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(spec: dict, name: str, here: str = HERE) -> tuple:
    """(cell entry, configuration file's content, workload file's
    content) for one name in BENCHMARK.json."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    cell = cells[name]
    cfg = next(c for c in spec["configs"] if c["name"] == cell["config"])
    root = os.path.dirname(here)
    config = load_json(os.path.join(root, cfg["file"]))
    workload = load_json(os.path.join(here, "workloads", name + ".json"))
    return cell, config, workload


def metrics_for(entries: list, cell: str) -> list:
    """The metric entries that this cell reports."""
    return [m for m in entries
            if "workloads" not in m or cell in m["workloads"]]


class Checks:
    """Each number compared, beside its limit; ``correct`` is all of
    them. Printed on a line of its own in every run."""

    def __init__(self):
        self.rows = []

    def add(self, name: str, value, limit, ok=None, note: str = ""):
        if ok is None:
            ok = value is not None and value == value and value <= limit
        self.rows.append({"name": name, "value": value, "limit": limit,
                          "ok": bool(ok), "note": note})

    @property
    def correct(self) -> bool:
        return bool(self.rows) and all(r["ok"] for r in self.rows)


def worst_leaf_gap(got: dict, want: dict) -> tuple:
    """The worst leaf's gap between two sets of per-leaf norms:
    |got - want| over max(want, the median leaf's want), since some
    leaves' norms are all but zero. Returns (gap, leaf)."""
    ref = sorted(want.values())
    floor = ref[len(ref) // 2]
    worst, where = 0.0, next(iter(want))
    for k, w in want.items():
        gap = abs(got[k] - w) / max(w, floor, 1e-30)
        if gap != gap or gap > worst:
            worst, where = (float("inf") if gap != gap else gap), k
    return worst, where


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (q in 0..100) of a non-empty list."""
    xs = sorted(values)
    return float(xs[max(0, math.ceil(len(xs) * q / 100.0) - 1)])


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest device, as the backend reports."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks)) if peaks else 0


@contextlib.contextmanager
def traced_window(ctx):
    """Profile the enclosed window when ``ctx.trace``; the reduction is
    left in ``ctx.trace_summary``. The whole window carries the
    ``bench.window`` annotation, whose ends bound busy and idle time."""
    import jax

    if not ctx.trace:
        yield
        return
    from benchmark import trace_reduce

    tmp = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        jax.profiler.start_trace(tmp)
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
                yield
        finally:
            wall = time.perf_counter() - t0
            jax.profiler.stop_trace()
        trace = trace_reduce.load(trace_reduce.find_xplane(tmp))
        ctx.trace_summary = trace_reduce.summarise(trace, wall)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def refuse_compiles(ctx, before: int) -> None:
    """No run reports a window in which something compiled."""
    if ctx.watch.count != before:
        raise SystemExit(f"benchmark: {ctx.watch.count - before} "
                         f"compilation(s) inside the measured window: "
                         f"{ctx.watch.names[before:]}")


def annotate(name: str):
    """A host span the profiler sees, under the benchmark's prefix."""
    import jax

    return jax.profiler.TraceAnnotation("bench." + name)
