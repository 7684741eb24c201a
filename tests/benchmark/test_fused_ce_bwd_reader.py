"""The readers PR 41 added: the fused cross-entropy's one backward
kernel, found by name in the trace, three matmuls of 2*T*V*H a call. A
program whose backward runs under other names (the parent commit's
``fused_ce_dh`` / ``fused_ce_dw``) reads as ``None``, never as an
error; a call at the device's peak reads 100, never more."""
import json
import os

import pytest

from benchmark import harness, rooflines

REPO = os.path.dirname(harness.HERE)
PEAKS = {"flops_per_s": {"bfloat16": 1e12}, "hbm_bytes_per_s": 1e11}
SIZES = {"vocab_size": 4096, "hidden_size": 64, "n_layer": 2, "n_head": 4}
TOKENS = 2 * 128          # rows_per_replica x (seq - 1)
BWD = "%transpose_jvp_fused_ce_bwd__.1 = (f32[256,64], f32[4096,64]) " \
      "custom-call(%h, %w)"


def reader(name="fused_ce_bwd_roofline.train"):
    return harness.load_module(os.path.join(
        harness.HERE, "layer_metrics", name + ".py"))


def train_run(ops, tensor=1, sizes=SIZES, peaks=PEAKS):
    return harness.Result(
        end_to_end={}, attempted=1, failed=0, t_window_start=0.0,
        memory_peak_bytes=0,
        facts={"sizes": sizes, "rows_per_replica": 2, "seq": 129,
               "tensor": tensor, "peaks": peaks},
        trace=None if ops is None else {
            "devices": [{"ops": ops, "busy_ns": 1}]})


def one_call_s(tensor=1):
    flops, nbytes = reader().call_cost(TOKENS, 64, 4096 // tensor)
    least, bound = rooflines.least_time_s(flops, nbytes, PEAKS)
    assert bound == "compute"
    return least


def ns(seconds):
    return int(round(seconds * 1e9))


def test_a_call_is_three_matmuls_and_compute_bound_at_the_cells_shapes():
    ce = reader()
    for tokens, hidden, rows in ((16376, 1024, 250880),
                                 (16376, 2048, 125440),
                                 (16380, 2048, 19360)):
        flops, nbytes = ce.call_cost(tokens, hidden, rows)
        assert flops == 3 * 2.0 * tokens * rows * hidden
        # h, W and the three rows read once; dh and dw written once, in
        # float32
        assert nbytes == ((tokens + rows) * hidden * 2 + 3 * tokens * 4
                          + (tokens + rows) * hidden * 4)
        v5e = {"flops_per_s": {"bfloat16": 197e12},
               "hbm_bytes_per_s": 819e9}
        least, bound = rooflines.least_time_s(flops, nbytes, v5e)
        assert bound == "compute" and least > 10 * nbytes / 819e9
    # with the forward's one, four matmuls a step where a dense head's
    # forward and backward owe three (mfu_pct.train's 6 per parameter)
    assert (1 + ce.MATMULS) * 2 == pytest.approx(4 / 3 * 6)


@pytest.mark.parametrize("name, want", [
    (BWD, True),
    ("%fused_ce_bwd.7 = f32[8] custom-call(%a)", True),
    ("%transpose_jvp_fused_ce_dw__.1 = bf16[4096,64] custom-call(%a)", False),
    ("%transpose_jvp_fused_ce_dh__.1 = bf16[256,64] custom-call(%a)", False),
    ("%jvp_fused_ce_fwd_.1 = (f32[256]) custom-call(%a)", False),
    # a consumer that names the kernel among its operands is not a call
    ("%convert.4 = bf16[4096,64] convert(%transpose_jvp_fused_ce_bwd__.1)",
     False),
])
def test_the_kernel_is_found_by_its_own_name(name, want):
    assert reader().is_call(name) is want


@pytest.mark.parametrize("slowdown, tensor, calls", [
    (1.0, 1, 1), (2.0, 1, 1), (4.0, 1, 2), (2.0, 2, 1), (1.25, 2, 3)])
def test_share_is_least_time_over_the_calls_device_time(slowdown, tensor,
                                                        calls):
    """Every call ``slowdown`` times its roofline reads 100 / slowdown,
    however many calls the window holds and whatever share of the
    vocabulary a device has; events of other kernels count for nothing."""
    one = one_call_s(tensor)
    ops, t = [("%jvp_fused_ce_fwd_.1 = (f32[256]) custom-call(%a)", 0,
               ns(3 * one))], ns(3 * one)
    for n in range(calls):
        ops.append((BWD.replace(".1 =", f".{n + 1} ="), t,
                    t + ns(slowdown * one)))
        t += ns(slowdown * one)
        ops.append(("%convert.4 = bf16[4096,64] "
                    "convert(%transpose_jvp_fused_ce_bwd__.1)", t,
                    t + ns(one)))
        t += ns(one)
    got = reader().read(train_run(ops, tensor=tensor))
    assert got == pytest.approx(100.0 / slowdown, rel=1e-6)
    assert got <= 100.0 * (1 + 1e-5)     # whole nanoseconds


@pytest.mark.parametrize("ops", [
    None,                                            # not a traced run
    [],                                              # nothing ran
    [("%transpose_jvp_fused_ce_dh__.1 = bf16[256,64] custom-call(%a)", 0,
      40),
     ("%transpose_jvp_fused_ce_dw__.1 = bf16[4096,64] custom-call(%a)", 40,
      80)],                                          # the parent's backward
    [("%jvp__.1 = f32[256] custom-call(%a)", 0, 10)],  # unnamed kernels
    [(BWD, 5, 5)],                                   # a call of no length
])
def test_nothing_to_read_is_none_not_an_error(ops):
    assert reader().read(train_run(ops)) is None
    assert reader("fused_ce_bwd_roofline.train-moe").read(
        train_run(ops)) is None


def test_the_accepted_reader_counts_the_forward_alone_on_these_events():
    """``fused_ce_roofline.train`` matches ``fused_ce_fwd``,
    ``fused_ce_dh`` and ``fused_ce_dw`` by substring: with one backward
    kernel under a name that holds none of them it reads the forward's
    share of its one-matmul roofline, whatever the backward takes."""
    old = reader("fused_ce_roofline.train")
    fwd = "%jvp_fused_ce_fwd_.1 = (f32[256]) custom-call(%a)"
    flops, nbytes = old.call_cost("fused_ce_fwd", TOKENS, 64, 4096)
    one_fwd = rooflines.least_time_s(flops, nbytes, PEAKS)[0]
    for bwd_s in (one_call_s(), 5 * one_call_s()):
        ops = [(fwd, 0, ns(2 * one_fwd)),
               (BWD, ns(2 * one_fwd), ns(2 * one_fwd) + ns(bwd_s))]
        assert old.kernel_of(BWD) is None
        assert old.read(train_run(ops)) == pytest.approx(50.0, rel=1e-5)
    assert old.read(train_run([(BWD, 0, 40)])) is None


def test_busiest_device_is_the_one_read():
    one = one_call_s()
    run = train_run([(BWD, 0, ns(2 * one))])
    run.trace["devices"].append(
        {"ops": [(BWD, 0, ns(4 * one))], "busy_ns": 2})
    assert reader().read(run) == pytest.approx(25.0, rel=1e-6)


def test_expert_cell_reads_two_passes_over_the_valid_rows():
    """GLM's cell: two calls a step on one weight; the work is the
    19,360 valid rows, not the 19,456 the vocabulary is padded to."""
    sizes = {"vocab_size": 19360, "hidden_size": 2048}
    v5e = {"flops_per_s": {"bfloat16": 197e12}, "hbm_bytes_per_s": 819e9}
    ops = [("%transpose_jvp_fused_ce_bwd__.1 = (f32[16384,2048], "
            "f32[19456,2048]) custom-call(%h)", 0, 10 ** 8),
           ("%transpose_jvp_fused_ce_bwd__.2 = (f32[16384,2048], "
            "f32[19456,2048]) custom-call(%h)", 10 ** 8, 2 * 10 ** 8)]
    run = train_run(ops, sizes=sizes, peaks=v5e)
    run.facts.update(rows_per_replica=4, seq=4096)
    got = reader("fused_ce_bwd_roofline.train-moe").read(run)
    want = 100 * (3 * 2.0 * 4 * 4095 * 19360 * 2048 / 197e12) / 0.1
    assert got == pytest.approx(want, rel=1e-6)


def test_benchmark_json_lists_the_readers_where_they_find_something():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    entries = {m["name"]: m for m in spec["per_layer"]}
    for name, cells in (
            ("fused_ce_bwd_roofline.train",
             ["bloom-560m.train-b8s2048", "bloom-1b7.train-tp2dp2"]),
            ("fused_ce_bwd_roofline.train-moe",
             ["glm-4.7-flash.train-ep8share-b4s4096"])):
        m = entries[name]
        assert m["workloads"] == cells
        assert (m["layer"], m["moves"], m["source"], m["unit"]) == (
            "kernels", "train_tokens_per_s", "device_trace", "%")
        assert os.path.exists(os.path.join(
            harness.HERE, "layer_metrics", name + ".py"))
    # appended: what was there keeps its place
    assert [m["name"] for m in spec["per_layer"]][-2:] == list(entries)[-2:] \
        == ["fused_ce_bwd_roofline.train", "fused_ce_bwd_roofline.train-moe"]
