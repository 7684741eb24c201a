"""The reduction from planes to numbers, on hand-built planes."""
import pytest

from benchmark import trace_reduce as tr

MS = 1_000_000


def ev(name, start_ms, end_ms):
    return (name, int(start_ms * MS), int(end_ms * MS))


def test_union_of_overlapping_intervals():
    ops = [ev("a", 0, 10), ev("b", 5, 12), ev("c", 20, 30), ev("d", 22, 25)]
    assert tr.union_ns(ops) == 22 * MS
    assert tr.merge([(5, 12), (0, 10), (20, 30)]) == [(0, 12), (20, 30)]


def test_gaps_are_the_complement_inside_the_window():
    ops = [ev("a", 2, 4), ev("b", 6, 8)]
    assert tr.gaps(ops, 0, 10 * MS) == [
        (0, 2 * MS), (4 * MS, 6 * MS), (8 * MS, 10 * MS)]
    assert tr.gaps([ev("a", 0, 10)], 0, 10 * MS) == []


def test_clip_cuts_events_to_the_window():
    assert tr.clip([ev("a", 0, 10), ev("b", 20, 30)], 5 * MS, 25 * MS) == [
        ("a", 5 * MS, 10 * MS), ("b", 20 * MS, 25 * MS)]


def test_self_time_goes_to_the_innermost_event():
    ops = [ev("%while.1 = (...) while(...)", 0, 100),
           ev("%fusion.7 = bf16[8] fusion(...)", 10, 40),
           ev("%fusion.8 = bf16[8] fusion(...)", 50, 70)]
    rows = dict(tr.time_by_op(ops))
    assert rows["fusion"] == pytest.approx(0.050)
    assert rows["while"] == pytest.approx(0.050)
    assert sum(rows.values()) == pytest.approx(tr.union_ns(ops) / 1e9)


def test_idle_gaps_are_named_by_the_covering_host_span():
    idle = [(0, 10 * MS), (20 * MS, 21 * MS), (30 * MS, 30 * MS + 5)]
    host = [ev("bench.serve.tick", 0, 8), ev("bench.serve.idle", 8, 25)]
    rows = dict(tr.attribute_gaps(idle, host))
    assert rows["bench.serve.tick"] == pytest.approx(0.010)
    assert rows["bench.serve.idle"] == pytest.approx(0.001)
    assert rows["between_ops"] == pytest.approx(5e-9)
    assert dict(tr.attribute_gaps([(40 * MS, 50 * MS)], host)) == {
        "unannotated": pytest.approx(0.010)}


def test_exposed_collective_is_the_collectives_self_time():
    ops = [  # named as the chip's trace names them: by jax's name, not the opcode
        ev("%while.2 = (s32[]{:T(128)}, f32[8]{0}) while(%tuple.1)", 0, 30),
        # a synchronous all-reduce: all of it exposed
        ev("%psum.3 = bf16[8,2048]{1,0:T(8,128)(2,1)} all-reduce(%fusion.9), "
           "channel_id=4", 0, 10),
        # an asynchronous pair: the compute between the two hides the wire
        ev("%all-gather-start.1 = (f32[4]{0}, f32[8]{0}) "
           "all-gather-start(%p.2)", 10, 11),
        ev("%fusion.1 = f32[8]{0:T(128)S(1)} fusion(%p.1), kind=kLoop", 11, 20),
        ev("%all-gather-done.1 = f32[8]{0} all-gather-done(%ags.1)", 20, 23),
        # compute that merely consumes a collective's result
        ev("%fusion.2 = f32[8]{0} fusion(%all-gather-done.1)", 23, 28)]
    assert [tr.opcode(n) for n, _, _ in ops] == [
        "while", "all-reduce", "all-gather-start", "fusion",
        "all-gather-done", "fusion"]
    assert tr.exposed_collective_ns(ops) == (10 + 1 + 3) * MS
    # something nested in a collective's span is not the collective's time
    ops.append(ev("%fusion.3 = f32[8]{0} fusion(%p.3)", 4, 6))
    assert tr.exposed_collective_ns(ops) == (8 + 1 + 3) * MS


def test_summary_takes_the_window_from_the_annotation():
    trace = tr.Trace(
        devices=[tr.DevicePlane("/device:TPU:0",
                                ops=[ev("%fusion.1 = f32[] fusion()", 0, 6),
                                     ev("%fusion.2 = f32[] fusion()", 8, 20)]),
                 tr.DevicePlane("/device:TPU:1",
                                ops=[ev("%fusion.1 = f32[] fusion()", 4, 8)])],
        host=[ev("bench.train.step", 0, 20)], window=(2 * MS, 12 * MS))
    s = tr.summarise(trace, window_s=99.0)
    assert s["window_s"] == pytest.approx(0.010)
    # device 0 is busy 2..6 and 8..12, device 1 4..8: mean of 8 and 4 ms
    assert s["busy_s"] == pytest.approx(0.006)
    assert dict(s["breakdown"]["idle_gaps"]) == {
        "bench.train.step": pytest.approx(0.002)}


def test_summary_refuses_a_trace_without_a_device_plane():
    with pytest.raises(RuntimeError, match="no /device:TPU"):
        tr.summarise(tr.Trace(lines_seen={"/host:CPU": ["python3"]}), 1.0)
