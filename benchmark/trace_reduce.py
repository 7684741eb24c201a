"""From a profiler trace (``.xplane.pb``) to numbers.

The reduction works on plain ``(name, start_ns, end_ns)`` tuples so that
it can be tested on hand-built planes; ``load`` is the only function
that touches ``jax.profiler.ProfileData``.

A device plane is one named ``/device:TPU:<n>``. Of its lines, ``XLA
Ops`` holds one event per executed HLO operation and ``XLA Modules`` one
per executed program; where a line of that name is missing the reducer
says so instead of guessing. Host annotations (``TraceAnnotation``)
arrive on the host plane's thread lines and are told apart by the
``bench.`` prefix the benchmark's drivers give them.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "bench."
WINDOW = "bench.window"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast)(-start|-done)?$")
_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")


@dataclass
class DevicePlane:
    name: str
    ops: list = field(default_factory=list)       # (name, start, end)
    modules: list = field(default_factory=list)


@dataclass
class Trace:
    devices: list = field(default_factory=list)   # DevicePlane
    host: list = field(default_factory=list)      # bench.* annotations
    window: tuple = None                          # (start_ns, end_ns)
    lines_seen: dict = field(default_factory=dict)


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    trace = Trace()
    for plane in data.planes:
        lines = list(plane.lines)
        trace.lines_seen[plane.name] = [ln.name for ln in lines]
        if DEVICE_PLANE.match(plane.name):
            dev = DevicePlane(plane.name)
            for ln in lines:
                if ln.name in (OPS_LINE, MODULES_LINE):
                    evs = [(e.name, int(e.start_ns),
                            int(e.start_ns + e.duration_ns))
                           for e in ln.events]
                    if ln.name == OPS_LINE:
                        dev.ops = evs
                    else:
                        dev.modules = evs
            trace.devices.append(dev)
        elif plane.name.startswith("/host:CPU"):
            for ln in lines:
                for e in ln.events:
                    if e.name.startswith(HOST_PREFIX):
                        ev = (e.name, int(e.start_ns),
                              int(e.start_ns + e.duration_ns))
                        if e.name == WINDOW:
                            trace.window = ev[1:]
                        else:
                            trace.host.append(ev)
    trace.devices.sort(key=lambda d: int(DEVICE_PLANE.match(d.name).group(1)))
    trace.host.sort(key=lambda e: e[1])
    return trace


# -- interval arithmetic ------------------------------------------------------


def clip(events, lo: int, hi: int) -> list:
    """Events cut to [lo, hi]; those wholly outside are dropped."""
    out = []
    for name, s, e in events:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out.append((name, s, e))
    return out


def merge(intervals) -> list:
    """Sorted, disjoint (start, end) covering the same points."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def union_ns(events) -> int:
    return sum(e - s for s, e in merge((s, e) for _, s, e in events))


def subtract(intervals, holes) -> list:
    """The parts of ``intervals`` (disjoint, sorted) outside ``holes``."""
    holes = merge(holes)
    out = []
    for s, e in intervals:
        cur = s
        for hs, he in holes:
            if he <= cur:
                continue
            if hs >= e:
                break
            if hs > cur:
                out.append((cur, hs))
            cur = max(cur, he)
            if cur >= e:
                break
        if cur < e:
            out.append((cur, e))
    return out


def gaps(events, lo: int, hi: int) -> list:
    """Idle (start, end) stretches of [lo, hi] in which no event runs."""
    return subtract([(lo, hi)], [(s, e) for _, s, e in events])


def op_base(name: str) -> str:
    """``%fusion.123 = ...`` / ``fusion.123`` -> ``fusion``: the name the
    trace prints, less the instance number, so instances add up."""
    name = name.split(" = ")[0].lstrip("%")
    return re.sub(r"[.\d]+$", "", name) or name


def time_by_op(events, top: int = 10, key=op_base) -> list:
    """[(name, seconds)] of SELF time by operation, most first. Nested
    events (a ``while`` around its body) would double count, so time is
    given to the innermost event covering each instant."""
    total = {}
    for name, ns in self_times(events):
        k = key(name)
        total[k] = total.get(k, 0) + ns
    rows = sorted(total.items(), key=lambda kv: -kv[1])[:top]
    return [[k, v / 1e9] for k, v in rows]


def self_times(events) -> list:
    """(name, self_ns): each event's duration less what its children
    (events wholly inside it) cover."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    out, stack = [], []          # stack of [name, start, end, child_ns]
    for name, s, e in evs:
        while stack and stack[-1][2] <= s:
            done = stack.pop()
            out.append((done[0], (done[2] - done[1]) - done[3]))
        if stack:
            stack[-1][3] += min(e, stack[-1][2]) - s
        stack.append([name, s, e, 0])
    while stack:
        done = stack.pop()
        out.append((done[0], (done[2] - done[1]) - done[3]))
    return out


def attribute_gaps(idle, host_events, top: int = 10,
                   min_ns: int = 10_000) -> list:
    """Idle time by what the host was doing: each idle stretch of
    ``min_ns`` or more goes to the ``bench.*`` annotation that covers
    most of it (``unannotated`` where none does), shorter ones to
    ``between_ops``. [(name, seconds)], most first. ``host_events`` are
    sorted by start; annotations are sequential on the driving thread,
    so the few that start last before a stretch's end are the only
    candidates."""
    starts = [hs for _, hs, _ in host_events]
    total = {}
    for s, e in idle:
        if e - s < min_ns:
            best = "between_ops"
        else:
            best, best_ns = "unannotated", 0
            hi = bisect.bisect_left(starts, e)
            for name, hs, he in host_events[max(0, hi - 8):hi]:
                ov = min(e, he) - max(s, hs)
                if ov > best_ns:
                    best, best_ns = name, ov
        total[best] = total.get(best, 0) + (e - s)
    rows = sorted(total.items(), key=lambda kv: -kv[1])[:top]
    return [[k, v / 1e9] for k, v in rows]


def opcode(name: str) -> str:
    """The HLO opcode of a trace event, whose name is the instruction:
    ``%psum.3 = bf16[8]{0} all-reduce(%x), ...`` -> ``all-reduce``. The
    instruction's own name says what jax called it (``psum``), and its
    operands may name other collectives, so neither is looked at."""
    m = _OPCODE.search(" " + name.partition(" = ")[2])
    return m.group(1) if m else ""


def exposed_collective_ns(ops) -> int:
    """Nanoseconds in which a collective runs on the device and nothing
    runs inside its span: the self time of the collective events. The
    ``XLA Ops`` line is serial, so whatever hides a collective sits
    between its ``-start`` and its ``-done``, outside both events; a
    ``while`` or ``call`` AROUND a collective hides nothing."""
    return sum(ns for name, ns in self_times(ops)
               if COLLECTIVE.match(opcode(name)))


# -- the summary a run carries ---------------------------------------------------


def summarise(trace: Trace, window_s: float) -> dict:
    """What the result line and the layer readers need from one trace.
    ``window_s`` is the host's length of the traced window, used where
    the trace holds no ``bench.window`` annotation."""
    if not trace.devices:
        raise RuntimeError(
            f"the trace holds no /device:TPU:<n> plane (planes and lines "
            f"seen: {trace.lines_seen})")
    missing = [d.name for d in trace.devices if not d.ops]
    if missing:
        raise RuntimeError(
            f"no {OPS_LINE!r} line on {missing} (lines seen: "
            f"{trace.lines_seen})")
    if trace.window is not None:
        lo, hi = trace.window
    else:
        lo = min(s for d in trace.devices for _, s, _ in d.ops)
        hi = lo + int(window_s * 1e9)
    per_dev = []
    for d in trace.devices:
        ops = clip(d.ops, lo, hi)
        per_dev.append({
            "name": d.name,
            "ops": ops,
            "modules": clip(d.modules, lo, hi),
            "busy_ns": union_ns(ops),
            "exposed_collective_ns": exposed_collective_ns(ops),
        })
    busiest = max(per_dev, key=lambda p: p["busy_ns"])
    return {
        "window_ns": hi - lo,
        "window": (lo, hi),
        "devices": per_dev,
        "busy_s": sum(p["busy_ns"] for p in per_dev) / len(per_dev) / 1e9,
        "window_s": (hi - lo) / 1e9,
        "host": clip(trace.host, lo, hi),
        "breakdown": {
            "device_ops": time_by_op(busiest["ops"], 10),
            "idle_gaps": attribute_gaps(
                gaps(busiest["ops"], lo, hi), clip(trace.host, lo, hi), 10),
        },
    }
