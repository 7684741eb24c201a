"""The engine's tick timeline laid beside the device's own line.

``ServingEngine.finish_run()["tick_timeline"]`` holds one row a
``tick_once``: the realtime clock at the tick's entry (``t_wall_ns``) and
the seconds of its phases in the order they ran (admit, prefill, prepare,
upload, call, fetch, record), so every phase boundary has a place on that
clock. A traced run holds, on the profiler's clock, the driver's
``bench.serve.tick`` annotation around every ``tick_once`` and, on the
device's ``XLA Modules`` line, one event an executed program (``jit__step``
the decode step, ``jit__write`` the page write, the prefill programs). The
join puts the three on one clock and says, tick by tick, when the device
started and ended the step the host had begun to send: the parts of the
idle time that ``breakdown.idle_gaps`` gives whole to ``bench.serve.tick``.

Like ``trace_reduce`` it works on plain tuples, so that it can be tested
on hand-built planes; it reads the modules line only, a few thousand
events a window, never the operations.

**It checks before it reads.** The annotation is entered immediately
before ``tick_once``, so once the constant between the two clocks is out
(the median of annotation start less ``t_wall_ns``), what is left says how
well the timeline is placed. Where that is over ``TOLERANCE_NS`` at p95,
where ticks and annotations differ in number, or where the decoding ticks
and the ``jit__step`` executions do not pair off one to one, the join says
why (``Join.why``) and every metric reads ``None``: a number from two
clocks that do not agree is worse than none.

**The device's line is not on the host's clock to the millisecond.** On
the chip (PR 42's first traced run) every ``jit__step`` execution STARTED
0.3 to 1.2 ms before the host entered the call that launched it: the
profiler lays its device planes beside its host planes by a
synchronisation of its own, and that was off by more than a millisecond.
So the join holds the device's line to causality instead of trusting it:
a step cannot start before its tick's ``call`` does, nor end after its
``fetch`` does, which bounds the constant ``shift`` to add to the device's
times from below (the largest ``call0 - start``) and from above (the
smallest ``fetch1 - end``) over the window's steps, as a clock is set
from round trips. The midpoint is taken, zero or not (the interval moved
by a millisecond from one run to the next, and where it held zero it
held it at its edge, the device starting 70 us after the host entered
the call), and half the interval's width is what launch, return and the
prefill's busy share may be off by (``shift_us``, ``shift_bounds_us`` on
the ``tick_join`` line); where no constant fits, the join refuses. The
gap between two steps, the upload, and launch + return do not depend on
it.

**The device's line may end before the window does.** In the chat cell
the profiler kept the device's events of the first 25.1 s of a 31.7 s
window (PR 42: 2,615 ``jit__step`` executions for 3,353 ticks that
decoded, every one of the missing after the line's last event). Steps
pair with the decoding ticks in order; ticks past the last step the
line holds are left out of every metric and counted (``unseen``); a
step missing anywhere else puts the rest a tick off, which causality
refuses.
"""
from __future__ import annotations

import bisect
import re
from dataclasses import dataclass, field
from typing import NamedTuple, Optional

from benchmark import harness, trace_reduce

TICK = "bench.serve.tick"
STEP_MODULE = re.compile(r"^jit__step\b")
# how far, at p95 over a window's ticks, a tick's entry by ``t_wall_ns``
# may lie from the start of the annotation around it, the constant taken
# out: ten times what PR 42's traced runs on the chip read at p95 (1.1 to
# 2.7 us, the Python tracer on; the longest single one 240 us), and
# under the 31-43 us by which an annotation outlasts its tick
TOLERANCE_NS = 25_000
# the parts a tick's wall falls into once the device's step is known, in
# order; ``between_ticks`` is the driver's own time up to the next tick
PARTS = ("admit", "prefill", "prepare", "launch", "step", "return",
         "record", "between_ticks")


class Tick(NamedTuple):
    """One tick's phase boundaries, nanoseconds on one clock."""
    enter: int
    prefill0: int       # admit ends
    prefill1: int       # the tick's prefills end, prepare starts
    upload0: int        # the host starts handing the step's arrays over
    call0: int          # the call of the jitted step starts
    fetch0: int         # the call has returned, the host waits
    fetch1: int         # the step's tokens are on the host
    leave: int
    rows: int
    prefills: int

    def shifted(self, ns: int) -> "Tick":
        return Tick(*(t + ns for t in self[:8]), self.rows, self.prefills)

    @property
    def plain(self) -> bool:
        """A decode step and no prefill before it."""
        return self.rows > 0 and self.prefills == 0


@dataclass
class Join:
    why: str = ""                   # non-empty: why nothing may be read
    ticks: list = field(default_factory=list)    # Tick, profiler's clock
    steps: list = field(default_factory=list)    # (start, end) or None
    busy: list = field(default_factory=list)     # merged module intervals
    origin_ns: int = 0              # profiler's clock less the wall clock
    shift_ns: int = 0               # added to the device's times
    shift_bounds_ns: tuple = ()     # what causality leaves it: (least, most)
    unseen: int = 0                 # decoding ticks past the device line's end
    off_ns: list = field(default_factory=list)   # placement, origin out
    slack_ns: list = field(default_factory=list)  # annotation less tick

    @property
    def ok(self) -> bool:
        return not self.why


def wall_ticks(timeline: dict) -> list:
    """The timeline's rows as ``Tick``s on the wall clock."""
    col = {name: i for i, name in enumerate(timeline["columns"])}
    phases = [col[p] for p in ("admit", "prefill", "prepare", "upload",
                               "call", "fetch", "record")]
    out = []
    for row in timeline["rows"]:
        t, cuts = 0.0, [int(row[col["t_wall_ns"]])]
        for i in phases:
            t += row[i]
            cuts.append(cuts[0] + round(1e9 * t))
        out.append(Tick(*cuts, int(row[col["rows"]]),
                        int(row[col["prefills"]])))
    return out


def join(timeline: dict, modules: list, host: list) -> Join:
    """``timeline``: ``finish_run()["tick_timeline"]``; ``modules``: the
    ``XLA Modules`` events ``(name, start_ns, end_ns)`` of the traced
    window; ``host``: its ``bench.*`` annotations."""
    if timeline.get("dropped"):
        return Join(why=f"the timeline's ring dropped "
                        f"{timeline['dropped']} ticks")
    ticks = wall_ticks(timeline)
    marks = sorted((s, e) for name, s, e in host if name == TICK)
    if len(marks) != len(ticks) or not ticks:
        return Join(why=f"{len(ticks)} ticks in the timeline, "
                        f"{len(marks)} {TICK} annotations")
    apart = [s - t.enter for (s, _), t in zip(marks, ticks)]
    # the median as an integer: the two clocks may count from instants
    # half a century apart, more nanoseconds than a float holds
    origin = sorted(apart)[(len(apart) - 1) // 2]
    j = Join(origin_ns=origin,
             off_ns=[a - origin for a in apart],
             slack_ns=[(e - s) - (t.leave - t.enter)
                       for (s, e), t in zip(marks, ticks)],
             busy=trace_reduce.merge((s, e) for _, s, e in modules))
    p95 = harness.percentile([abs(x) for x in j.off_ns], 95)
    if p95 > TOLERANCE_NS:
        j.why = (f"the ticks lie {p95 / 1e3:.0f} us from their annotations "
                 f"at p95, over {TOLERANCE_NS / 1e3:.0f}")
        return j
    j.ticks = [t.shifted(j.origin_ns) for t in ticks]
    execs = sorted((s, e) for name, s, e in modules
                   if STEP_MODULE.search(name))
    decoded = sum(1 for t in j.ticks if t.rows > 0)
    if len(execs) > decoded:
        j.why = (f"{len(execs)} jit__step executions for {decoded} ticks "
                 f"that decoded")
        return j
    # steps and decoding ticks in order, as far as the device's line goes
    step_of = iter(execs)
    steps = [next(step_of, None) if t.rows > 0 else None for t in j.ticks]
    seen = max((k + 1 for k, s in enumerate(steps) if s), default=0)
    if not seen:
        j.why = "the device's line holds no jit__step execution"
        return j
    j.unseen = decoded - len(execs)
    j.ticks, steps = j.ticks[:seen], steps[:seen]
    # what causality leaves of a constant between the two lines
    least = max(t.call0 - s[0] for t, s in zip(j.ticks, steps) if s)
    most = min(t.fetch1 - s[1] for t, s in zip(j.ticks, steps) if s)
    j.shift_bounds_ns = (least, most)
    if least > most:
        j.why = (f"no constant puts every jit__step inside its tick's "
                 f"call..fetch: at least {least} ns for the latest call, "
                 f"at most {most} for the soonest fetch")
        return j
    j.shift_ns = (least + most) // 2
    j.steps = [s and (s[0] + j.shift_ns, s[1] + j.shift_ns) for s in steps]
    j.busy = [(s + j.shift_ns, e + j.shift_ns) for s, e in j.busy]
    return j


def of(run) -> Optional[Join]:
    """The join of one run, or None: an untraced run, or a program that
    keeps no timeline."""
    timeline = run.facts.get("run_metrics", {}).get("tick_timeline")
    if run.trace is None or not timeline:
        return None
    return join(timeline, run.trace["devices"][0]["modules"],
                run.trace["host"])


# -- the metrics -----------------------------------------------------------


def _plain(j: Optional[Join]) -> list:
    """(tick, its step) of every plain tick; nothing where the join
    failed."""
    if j is None or not j.ok:
        return []
    return [(t, s) for t, s in zip(j.ticks, j.steps) if t.plain]


def _p50_ms(ns: list) -> Optional[float]:
    return harness.percentile(ns, 50) / 1e6 if ns else None


def step_launch_ms(j) -> Optional[float]:
    """p50 over plain ticks: the device starts the step, less the host
    entering ``upload``."""
    return _p50_ms([s[0] - t.upload0 for t, s in _plain(j)])


def step_return_ms(j) -> Optional[float]:
    """p50 over plain ticks: the host leaves ``fetch``, less the device
    ending the step."""
    return _p50_ms([t.fetch1 - s[1] for t, s in _plain(j)])


def step_upload_ms(j) -> Optional[float]:
    """Mean ``upload`` over the ticks that decoded."""
    if j is None or not j.ok:
        return None
    ns = [t.call0 - t.upload0 for t in j.ticks if t.rows > 0]
    return sum(ns) / len(ns) / 1e6 if ns else None


def plain_pairs(j: Optional[Join]) -> list:
    """((tick, step), (next tick, its step)) wherever a plain tick
    follows a plain tick."""
    if j is None or not j.ok:
        return []
    rows = list(zip(j.ticks, j.steps))
    return [(a, b) for a, b in zip(rows, rows[1:])
            if a[0].plain and b[0].plain]


def device_gap_ms(j) -> Optional[float]:
    """p50 over successive plain ticks: from one step's end on the
    device to the next one's start."""
    return _p50_ms([b[1][0] - a[1][1] for a, b in plain_pairs(j)])


# -- the idle by part, and what a traced run prints beside the metrics ---------


def idle_by_part_s(j: Join) -> dict:
    """The device's idle seconds from the first tick's entry to the last
    one's end, by the part of a tick they fall in (``PARTS``): ``launch``
    from the host entering ``upload`` to the device starting the step,
    ``return`` from its end to the host leaving ``fetch``. A tick that
    decoded nothing is admit, prefill and record."""
    cuts, names = [], []
    for k, (t, step) in enumerate(zip(j.ticks, j.steps)):
        if step is None:
            edges = (t.enter, t.prefill0, t.prefill1, t.prefill1,
                     t.prefill1, t.prefill1, t.prefill1, t.leave)
        else:
            edges = (t.enter, t.prefill0, t.prefill1, t.upload0, step[0],
                     step[1], t.fetch1, t.leave)
        cuts.extend(edges)
        names.extend(PARTS)
    cuts.append(j.ticks[-1].leave)
    total = dict.fromkeys(PARTS, 0)
    idle = trace_reduce.subtract([(cuts[0], cuts[-1])], j.busy)
    for lo, hi in idle:
        i = max(0, bisect.bisect_right(cuts, lo) - 1)
        while i < len(names) and cuts[i] < hi:
            total[names[i]] += max(0, min(hi, cuts[i + 1]) - max(lo, cuts[i]))
            i += 1
    return {k: v / 1e9 for k, v in total.items()}


def prefill_device_busy_pct(j) -> Optional[float]:
    """Over the ticks that prefilled: the share of their ``prefill``
    seconds in which some program ran on the device."""
    if j is None or not j.ok:
        return None
    wall = sum(t.prefill1 - t.prefill0 for t in j.ticks) / 1e9
    return 100.0 * (1.0 - idle_by_part_s(j)["prefill"] / wall) if wall \
        else None


def facts(j: Join) -> dict:
    """How the join went and what the gap is made of: the ``tick_join``
    line of a traced run."""
    absolute = [abs(x) for x in j.off_ns]
    out = {"ok": j.ok, "why": j.why, "ticks": len(j.off_ns),
           "origin_ns": j.origin_ns, "tolerance_us": TOLERANCE_NS / 1e3}
    if absolute:
        out["placement_off_us"] = {
            "p50": harness.percentile(absolute, 50) / 1e3,
            "p95": harness.percentile(absolute, 95) / 1e3,
            "max": max(absolute) / 1e3}
        # the annotation's length over the tick's own: entering and
        # leaving it, the most by which the origin can be early
        out["annotation_slack_us_p50"] = harness.percentile(
            j.slack_ns, 50) / 1e3
    if j.shift_bounds_ns:
        # the constant added to the device's times, and what causality
        # left it: launch, return and the prefill's busy share may be
        # off by half that interval's width
        out["shift_us"] = j.shift_ns / 1e3
        out["shift_bounds_us"] = [b / 1e3 for b in j.shift_bounds_ns]
    if not j.ok:
        return out
    pairs = plain_pairs(j)
    out.update(unseen=j.unseen, covered_ticks=len(j.ticks),
               decoded=sum(1 for t in j.ticks if t.rows > 0),
               plain=sum(1 for t in j.ticks if t.plain),
               prefilled=sum(1 for t in j.ticks if t.prefills > 0),
               plain_pairs=len(pairs), idle_s=idle_by_part_s(j))
    if pairs:
        # one step's end to the next one's start, term by term: the sum
        # of the terms IS the gap, pair by pair; their medians need not
        # add up to its median
        parts = {
            "return": [a[0].fetch1 - a[1][1] for a, _ in pairs],
            "record": [a[0].leave - a[0].fetch1 for a, _ in pairs],
            "between_ticks": [b[0].enter - a[0].leave for a, b in pairs],
            "admit": [b[0].prefill0 - b[0].enter for _, b in pairs],
            "prepare": [b[0].upload0 - b[0].prefill1 for _, b in pairs],
            "upload": [b[0].call0 - b[0].upload0 for _, b in pairs],
            "call_to_start": [b[1][0] - b[0].call0 for _, b in pairs],
        }
        p50 = {k: _p50_ms(v) for k, v in parts.items()}
        gap = device_gap_ms(j)
        out["gap_parts_p50_ms"] = p50
        out["gap_parts_sum_ms"] = sum(p50.values())
        out["gap_p50_ms"] = gap
        out["gap_mean_ms"] = sum(
            b[1][0] - a[1][1] for a, b in pairs) / len(pairs) / 1e6
        out["gap_unexplained_pct"] = 100.0 * (gap - sum(p50.values())) / gap
        out["step_device_p50_ms"] = _p50_ms(
            [s[1] - s[0] for _, s in _plain(j)])
    return out
