"""The tick timeline joined to the device's step executions
(``benchmark/tick_join.py``) and the five ``.serve`` readers, on
hand-built timelines, ``XLA Modules`` events and ``bench.serve.tick``
annotations: each metric on a plane built to give a known answer, and
``None`` wherever the two clocks cannot be shown to agree."""
import os

import pytest

from benchmark import harness, tick_join

READERS = os.path.join(harness.HERE, "layer_metrics")
METRICS = ("step_launch_ms.serve", "step_return_ms.serve",
           "step_upload_ms.serve", "device_gap_ms.serve",
           "prefill_device_busy_pct.serve")
COLUMNS = ["t_wall_ns", "t_start", "admit", "prefill", "prepare", "upload",
           "call", "fetch", "record", "rows", "prefills"]
US = 1_000
WALL0 = 1_790_000_000_000_000_000       # the realtime clock, ns
ORIGIN = -WALL0 + 7_000_000_000         # the profiler counts from its start
BETWEEN = 50                            # us of the driver between two ticks


def plain_tick(to_start, step=2000, fetch=2500):
    """A decode step alone: the device starts it ``to_start`` us after
    the call does and runs it ``step`` us."""
    return {"admit": 10, "prefill": 0, "prepare": 20, "upload": 100,
            "call": 200, "fetch": fetch, "record": 30, "rows": 4,
            "prefills": 0, "step": (to_start, step), "programs": []}


def prefill_tick():
    """A prefill of 1,000 us with its program busy for 400 of them and
    the page write for the last 100 and 200 more, then a step that the
    write holds back."""
    return {"admit": 10, "prefill": 1000, "prepare": 20, "upload": 100,
            "call": 200, "fetch": 2800, "record": 30, "rows": 1,
            "prefills": 1, "step": (500, 2000),
            "programs": [("jit__prefill(3)", 100, 400),
                         ("jit__write(5)", 900, 300)]}


def idle_tick():
    return {"admit": 10, "prefill": 0, "prepare": 0, "upload": 0, "call": 0,
            "fetch": 0, "record": 15, "rows": 0, "prefills": 0, "step": None,
            "programs": []}


# launch 920 / 400 / 500 / 400 us, return 500 / 400 / 300 / 400
WINDOW = [prefill_tick(), plain_tick(300), plain_tick(400), plain_tick(300)]


def plane(ticks, enter_us=0, leave_us=0):
    """(timeline, modules, host) of a scripted window. The annotation is
    entered ``enter_us`` before its tick and left ``leave_us`` after."""
    rows, modules, host = [], [], []
    wall = WALL0
    for t in ticks:
        rows.append([wall, (wall - WALL0) / 1e9]
                    + [t[k] / 1e6 for k in COLUMNS[2:9]]
                    + [t["rows"], t["prefills"]])
        length = sum(t[k] for k in COLUMNS[2:9]) * US
        host.append((tick_join.TICK, wall + ORIGIN - enter_us * US,
                     wall + ORIGIN + length + leave_us * US))
        prefill0 = wall + ORIGIN + t["admit"] * US
        for name, at, dur in t["programs"]:
            modules.append((name, prefill0 + at * US,
                            prefill0 + (at + dur) * US))
        if t["step"] is not None:
            call0 = prefill0 + (t["prefill"] + t["prepare"]
                                + t["upload"]) * US
            at, dur = t["step"]
            modules.append(("jit__step(11)", call0 + at * US,
                            call0 + (at + dur) * US))
        wall += length + BETWEEN * US
    host.append(("bench.serve.submit", WALL0 + ORIGIN - 9 * US,
                 WALL0 + ORIGIN - 5 * US))
    return {"columns": COLUMNS, "rows": rows, "dropped": 0}, modules, host


def run_of(timeline, modules, host, traced=True):
    metrics = {} if timeline is None else {"tick_timeline": timeline}
    trace = {"devices": [{"modules": modules}], "host": host}
    return harness.Result(end_to_end={}, attempted=1, failed=0,
                          t_window_start=0.0, memory_peak_bytes=0,
                          facts={"run_metrics": metrics},
                          trace=trace if traced else None)


def read(name, run):
    return harness.load_module(os.path.join(READERS, name + ".py")).read(run)


@pytest.mark.parametrize("name, want", [
    ("step_launch_ms.serve", 0.400),      # of 400, 500, 400 us
    ("step_return_ms.serve", 0.400),      # of 400, 300, 400
    ("step_upload_ms.serve", 0.100),      # all four ticks decoded
    ("device_gap_ms.serve", 0.810),       # of 1,010 and 810
    ("prefill_device_busy_pct.serve", 50.0),
])
def test_each_metric_on_a_plane_with_a_known_answer(name, want):
    assert read(name, run_of(*plane(WINDOW))) == pytest.approx(want)


def broken(kind):
    timeline, modules, host = plane(WINDOW)
    if kind == "a program without a timeline":
        timeline = None
    elif kind == "an untraced run":
        return run_of(timeline, modules, host, traced=False)
    elif kind == "an annotation short":
        host = host[1:]
    elif kind == "a tick short":
        timeline["rows"] = timeline["rows"][:-1]
    elif kind == "rows dropped by the ring":
        timeline["dropped"] = 2
    elif kind == "one tick in four placed 1 ms off":
        name, s, e = host[2]
        host[2] = (name, s + 1000 * US, e)
    elif kind == "a step the timeline has no tick for":
        modules.append(("jit__step(11)", modules[-1][2] + 10 * US,
                        modules[-1][2] + 20 * US))
    elif kind == "steps that no constant puts inside their ticks":
        # one step 2 ms early, the next 2 ms late
        for i, ms in ((-1, 2), (-2, -2)):
            n, s, e = modules[i]
            modules[i] = (n, s + ms * 1000 * US, e + ms * 1000 * US)
    return run_of(timeline, modules, host)


BROKEN = ["a program without a timeline", "an untraced run",
          "an annotation short", "a tick short", "rows dropped by the ring",
          "one tick in four placed 1 ms off",
          "a step the timeline has no tick for",
          "steps that no constant puts inside their ticks"]


@pytest.mark.parametrize("kind", BROKEN)
def test_every_reader_reads_none_where_the_clocks_cannot_be_joined(
        kind, capsys):
    run = broken(kind)
    assert [read(name, run) for name in METRICS] == [None] * 5
    j = tick_join.of(run)
    assert (j is None) == (kind in BROKEN[:2])
    line = capsys.readouterr().out
    if j is not None:
        # the refusal gives its reason, on the line a traced run prints
        assert not j.ok and j.why and '"ok": false' in line
        assert tick_join.facts(j)["why"] == j.why
        if kind == "steps that no constant puts inside their ticks":
            assert tick_join.facts(j)["shift_bounds_us"] == [
                2000.0 - 400, 400.0 - 2000]
    else:
        assert line == ""


def test_the_origin_between_the_clocks_is_taken_out_and_reported():
    j = tick_join.join(*plane(WINDOW))
    assert j.ok and j.origin_ns == ORIGIN
    assert set(j.off_ns) == {0} and set(j.slack_ns) == {0}
    assert j.ticks[0].enter == WALL0 + ORIGIN
    # entering the annotation takes 3 us and leaving it 2: the origin
    # comes out 3 us early, the annotation's slack says how far off it
    # can be, and the device's line, held to the same ticks, follows it
    j = tick_join.join(*plane(WINDOW, enter_us=3, leave_us=2))
    assert j.ok and j.origin_ns == ORIGIN - 3 * US
    assert set(j.slack_ns) == {5 * US} and j.shift_ns == -3 * US
    assert tick_join.step_launch_ms(j) == pytest.approx(0.400)
    assert tick_join.step_return_ms(j) == pytest.approx(0.400)
    assert tick_join.device_gap_ms(j) == pytest.approx(0.810)
    assert tick_join.facts(j)["annotation_slack_us_p50"] == 5.0


@pytest.mark.parametrize("early_us", [0, 200, 1500, -700])
def test_a_device_line_off_the_hosts_clock_is_held_to_causality(early_us):
    """The profiler lays the device's line beside the host's by a
    synchronisation of its own, which the join does not trust: it takes
    the midpoint of what causality leaves (every step starts after its
    call does and ends before its fetch does), zero or not. That is the
    truth where the soonest start after a call (300 us here) and the
    soonest fetch after an end (300) are equal, and half the interval's
    width off at most where they are not."""
    timeline, modules, host = plane(WINDOW)
    modules = [(n, s - early_us * US, e - early_us * US)
               for n, s, e in modules]
    j = tick_join.join(timeline, modules, host)
    assert j.ok and j.shift_ns == early_us * US
    assert j.shift_bounds_ns == ((early_us - 300) * US, (early_us + 300) * US)
    f = tick_join.facts(j)
    assert (f["shift_us"], f["shift_bounds_us"]) == (
        early_us, [early_us - 300, early_us + 300])
    assert tick_join.step_launch_ms(j) == pytest.approx(0.400)
    assert tick_join.step_return_ms(j) == pytest.approx(0.400)
    assert tick_join.device_gap_ms(j) == pytest.approx(0.810)
    assert tick_join.step_upload_ms(j) == pytest.approx(0.100)
    assert tick_join.prefill_device_busy_pct(j) == pytest.approx(50.0)
    assert f["idle_s"]["launch"] == pytest.approx(1720e-6)
    # the soonest start 500 us after its call, the soonest fetch 200
    # after its step: the midpoint is 150 us off, launch (600) reads that
    # much short and return (200) long; the gap and their sum stay
    uneven = [plain_tick(500), plain_tick(600, fetch=2700), plain_tick(500)]
    timeline, modules, host = plane(uneven)
    j = tick_join.join(timeline, [(n, s - early_us * US, e - early_us * US)
                                  for n, s, e in modules], host)
    assert j.shift_ns == (early_us - 150) * US
    assert tick_join.step_launch_ms(j) == pytest.approx(0.600 - 0.150)
    assert tick_join.step_return_ms(j) == pytest.approx(0.200 + 0.150)
    assert tick_join.device_gap_ms(j) == pytest.approx(1.010)


def test_a_device_line_that_ends_before_the_window_does():
    """The profiler may keep the device's events of a part of the window
    alone: ticks past the line's last step are counted and left out; a
    step missing anywhere else is refused."""
    ticks = [prefill_tick()] + [plain_tick(300), plain_tick(400)] * 4
    timeline, modules, host = plane(ticks)
    whole = tick_join.join(timeline, modules, host)
    assert whole.ok and whole.unseen == 0 and len(whole.ticks) == 9
    j = tick_join.join(timeline, modules[:-3], host)
    assert j.ok and j.unseen == 3 and len(j.ticks) == len(j.steps) == 6
    f = tick_join.facts(j)
    assert (f["ticks"], f["covered_ticks"], f["unseen"], f["decoded"],
            f["plain_pairs"]) == (9, 6, 3, 6, 4)
    assert tick_join.step_launch_ms(j) == tick_join.step_launch_ms(whole)
    assert tick_join.device_gap_ms(j) == pytest.approx(0.810)
    assert tick_join.prefill_device_busy_pct(j) == pytest.approx(50.0)
    # the idle is the covered ticks': nothing of the unseen ones' wall
    covered = j.ticks[-1].leave - j.ticks[0].enter
    assert sum(f["idle_s"].values()) == pytest.approx(
        (covered - sum(min(e, j.ticks[-1].leave) - s for s, e in j.busy
                       if s < j.ticks[-1].leave)) / 1e9)
    # a step lost in the middle puts every later one a tick off
    holed = tick_join.join(timeline, modules[:5] + modules[6:], host)
    assert not holed.ok and "no constant" in holed.why
    assert [read(name, run_of(timeline, modules[:5] + modules[6:], host))
            for name in METRICS] == [None] * 5
    # no step at all
    none = tick_join.join(timeline, modules[:2], host)
    assert not none.ok and "no jit__step" in none.why


def test_one_late_annotation_among_many_does_not_refuse_the_join():
    timeline, modules, host = plane(WINDOW + [plain_tick(300)] * 36)
    name, s, e = host[7]
    host[7] = (name, s + 1000 * US, e)
    j = tick_join.join(timeline, modules, host)
    assert j.ok and max(j.off_ns) == 1000 * US
    assert tick_join.facts(j)["placement_off_us"] == {
        "p50": 0.0, "p95": 0.0, "max": 1000.0}


def test_a_prefill_tick_feeds_the_prefill_metric_alone():
    # prefill ticks and nothing else: no plain tick, no plain pair
    run = run_of(*plane([prefill_tick(), prefill_tick()]))
    assert [read(name, run) for name in METRICS[:2]] == [None, None]
    assert read("device_gap_ms.serve", run) is None
    assert read("step_upload_ms.serve", run) == pytest.approx(0.100)
    assert read("prefill_device_busy_pct.serve", run) == pytest.approx(50.0)
    # plain ticks and nothing else: nothing prefilled
    run = run_of(*plane([plain_tick(300), plain_tick(400)]))
    assert read("prefill_device_busy_pct.serve", run) is None
    assert read("step_launch_ms.serve", run) == pytest.approx(0.400)
    # a prefill tick between two plain ones parts them: no pair, no gap,
    # and its own launch (920 us) and return (500) are in neither p50
    run = run_of(*plane([plain_tick(300), prefill_tick(), plain_tick(400)]))
    assert read("device_gap_ms.serve", run) is None
    assert read("step_launch_ms.serve", run) == pytest.approx(0.400)
    assert read("step_return_ms.serve", run) == pytest.approx(0.300)


def test_ticks_that_decode_nothing_pair_with_no_step():
    ticks = [idle_tick(), plain_tick(300), idle_tick(), plain_tick(300)]
    j = tick_join.join(*plane(ticks))
    assert j.ok and [s is None for s in j.steps] == [True, False] * 2
    # the idle tick between them parts the two plain ones
    assert tick_join.device_gap_ms(j) is None
    assert tick_join.step_upload_ms(j) == pytest.approx(0.100)


def test_the_gap_term_by_term_and_the_idle_by_part(capsys):
    run = run_of(*plane(WINDOW))
    assert read("device_gap_ms.serve", run) == pytest.approx(0.810)
    assert capsys.readouterr().out.startswith('tick_join {"ok": true')
    f = tick_join.facts(tick_join.of(run))
    assert (f["ticks"], f["decoded"], f["plain"], f["prefilled"],
            f["plain_pairs"]) == (4, 4, 3, 1, 2)
    assert f["gap_parts_p50_ms"] == pytest.approx({
        "return": 0.300, "record": 0.030, "between_ticks": 0.050,
        "admit": 0.010, "prepare": 0.020, "upload": 0.100,
        "call_to_start": 0.300})
    assert f["gap_parts_sum_ms"] == pytest.approx(0.810)
    assert f["gap_mean_ms"] == pytest.approx(0.910)
    assert f["gap_unexplained_pct"] == pytest.approx(0.0, abs=1e-9)
    assert f["step_device_p50_ms"] == pytest.approx(2.0)
    # the page write runs through the first tick's prepare and 180 us of
    # its launch; its prefill is busy for half its length
    assert f["idle_s"] == pytest.approx({
        "admit": 40e-6, "prefill": 500e-6, "prepare": 60e-6,
        "launch": 1720e-6, "step": 0.0, "return": 1600e-6,
        "record": 120e-6, "between_ticks": 150e-6})
    j = tick_join.of(run)
    window = j.ticks[-1].leave - j.ticks[0].enter
    assert sum(f["idle_s"].values()) == pytest.approx(
        (window - sum(e - s for s, e in j.busy)) / 1e9)
