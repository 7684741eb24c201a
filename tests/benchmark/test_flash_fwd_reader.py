"""The reader PR 49 added: flash attention's forward kernel, found by
NAME in the trace, two matmuls of 2*seq*seq*width/2 a head a call
reckoned from the run's facts. It reads the same whether the kernel
returns a head a tile (``bf16[rows*heads, seq, width]``) or, at head
width 64 since PR 49, the model's own ``bf16[rows, seq, heads*width]``:
the shape ``flash_attn_roofline.train`` tells kernels by is gone there,
and that reader falls silent. Nothing to read is ``None``, never an
error; a call at the device's peak reads 100, never more."""
import json
import os

import pytest

from benchmark import harness, rooflines

REPO = os.path.dirname(harness.HERE)
PEAKS = {"flops_per_s": {"bfloat16": 1e12}, "hbm_bytes_per_s": 1e11}
V5E = {"flops_per_s": {"bfloat16": 197e12}, "hbm_bytes_per_s": 819e9}
SIZES = {"vocab_size": 4096, "hidden_size": 256, "n_layer": 2, "n_head": 4}
ROWS, SEQ = 2, 512
# one head a tile (the parent at every width, and widths of 128 and more)
FWD = "%jvp_flash_fwd_.1 = (bf16[8,512,64], f32[8,1,512]) custom-call(%q)"
# two heads a tile (head width 64 since PR 49): the model's own arrays
PAIRED = "%jvp_flash_fwd_.1 = (bf16[2,512,256], f32[8,1,512]) " \
         "custom-call(%q, %k, %v)"
BWD = "%transpose_jvp_flash_bwd__.1 = (bf16[2,512,256], bf16[2,512,256], " \
      "bf16[2,512,256]) custom-call(%q, %k)"
USER = "%convert.4 = bf16[2,512,256] convert(%jvp_flash_fwd_.1)"


def reader(name="flash_fwd_roofline.train"):
    return harness.load_module(os.path.join(
        harness.HERE, "layer_metrics", name + ".py"))


def train_run(ops, tensor=1, sizes=SIZES, peaks=PEAKS, rows=ROWS, seq=SEQ):
    return harness.Result(
        end_to_end={}, attempted=1, failed=0, t_window_start=0.0,
        memory_peak_bytes=0,
        facts={"sizes": sizes, "rows_per_replica": rows, "seq": seq,
               "tensor": tensor, "peaks": peaks},
        trace=None if ops is None else {
            "devices": [{"ops": ops, "busy_ns": 1}]})


def one_call_s(tensor=1):
    flops, nbytes = rooflines.flash_call_cost("fwd", ROWS, SEQ, 4 // tensor,
                                              64)
    least, bound = rooflines.least_time_s(flops, nbytes, PEAKS)
    assert bound == "compute"
    return least


def ns(seconds):
    return int(round(seconds * 1e9))


@pytest.mark.parametrize("name, want", [
    (FWD, True),
    (PAIRED, True),
    ("%flash_fwd.7 = (bf16[8,512,64], f32[8,1,512]) custom-call(%a)", True),
    ("%checkpoint_jvp_flash_fwd_.3 = (bf16[2,512,256], f32[8,1,512]) "
     "custom-call(%a)", True),
    (BWD, False),
    ("%flash_ring_fwd.1 = f32[8,512,64] custom-call(%a)", False),
    # a consumer that names the kernel among its operands is not a call
    (USER, False),
])
def test_the_kernel_is_found_by_its_own_name(name, want):
    assert reader().is_call(name) is want


@pytest.mark.parametrize("call", [FWD, PAIRED], ids=["one_head", "paired"])
@pytest.mark.parametrize("slowdown, tensor, calls", [
    (1.0, 1, 1), (2.0, 1, 1), (5.0, 1, 3), (2.0, 2, 1), (1.25, 2, 2)])
def test_share_is_least_time_over_the_calls_device_time(call, slowdown,
                                                        tensor, calls):
    """Every call ``slowdown`` times its roofline reads 100 / slowdown,
    however many calls the window holds, whatever share of the heads a
    device has and whichever layout the kernel returns; the backward and
    the kernel's consumers count for nothing."""
    one = one_call_s(tensor)
    ops, t = [(BWD, 0, ns(3 * one))], ns(3 * one)
    for n in range(calls):
        ops.append((call.replace(".1 =", f".{n + 1} ="), t,
                    t + ns(slowdown * one)))
        t += ns(slowdown * one)
        ops.append((USER, t, t + ns(one)))
        t += ns(one)
    got = reader().read(train_run(ops, tensor=tensor))
    # whole nanoseconds of a 0.13 ms call
    assert got == pytest.approx(100.0 / slowdown, rel=1e-5)
    assert got <= 100.0 * (1 + 1e-5)


@pytest.mark.parametrize("ops", [
    None,                                            # not a traced run
    [],                                              # nothing ran
    [(BWD, 0, 30)],                                  # no forward
    [("%jvp__.1 = (bf16[8,512,64], f32[8,1,512]) custom-call(%a)", 0, 10)],
    [(FWD, 5, 5)],                                   # a call of no length
])
def test_nothing_to_read_is_none_not_an_error(ops):
    assert reader().read(train_run(ops)) is None


def test_the_shape_reader_falls_silent_on_the_paired_layout_and_this_does_not():
    """What ``flash_attn_roofline.train`` makes of the two layouts: the
    one-head result is its forward; the paired result is nothing to it
    (nor is the paired backward), so in the 560m cell it reads ``None``
    since PR 49 while this reader and ``flash_bwd_roofline.train`` go
    on."""
    by_shape = reader("flash_attn_roofline.train")
    assert by_shape.classify(FWD, (8, SEQ, 64)) == "fwd"
    assert by_shape.classify(PAIRED, (8, SEQ, 64)) is None
    assert by_shape.classify(BWD, (8, SEQ, 64)) is None
    ops = [(PAIRED, 0, ns(4 * one_call_s())), (BWD, ns(1.0), ns(1.5))]
    assert by_shape.read(train_run(ops)) is None
    assert reader().read(train_run(ops)) == pytest.approx(25.0, rel=1e-5)
    assert reader("flash_bwd_roofline.train").read(train_run(ops)) is not None


def test_busiest_device_is_the_one_read():
    one = one_call_s()
    run = train_run([(PAIRED, 0, ns(2 * one))])
    run.trace["devices"].append(
        {"ops": [(PAIRED, 0, ns(4 * one))], "busy_ns": 2})
    assert reader().read(run) == pytest.approx(25.0, rel=1e-5)


# (rows, seq, heads on a device, width, ms a call in the ledger's traces)
CELLS = {"560m": (8, 2048, 16, 64, 2.0), "1b7_tp2": (8, 2048, 8, 128, 1.03)}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_cells_calls_are_compute_bound_and_read_well_under_100(cell):
    """At the two BLOOM train cells' shapes on a v5e: compute bounds a
    call, and the times the ledger's traces show for a call (PR 47's
    rows) read between 10 and 50: no count is too high."""
    rows, seq, heads, width, ms = CELLS[cell]
    flops, nbytes = rooflines.flash_call_cost("fwd", rows, seq, heads, width)
    assert flops == 2 * 2.0 * rows * heads * seq * seq * width / 2.0
    least, bound = rooflines.least_time_s(flops, nbytes, V5E)
    assert bound == "compute"
    sizes = {"hidden_size": heads * width, "n_head": heads}
    call = "%jvp_flash_fwd_.1 = (bf16[1,1,1], f32[1,1,1]) custom-call(%q)"
    got = reader().read(train_run([(call, 0, ns(ms / 1e3))], sizes=sizes,
                                  peaks=V5E, rows=rows, seq=seq))
    assert got == pytest.approx(100.0 * least / (ms / 1e3), rel=1e-4)
    assert 10.0 < got < 50.0


def test_benchmark_json_lists_the_reader_where_it_finds_something():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    entries = {m["name"]: m for m in spec["per_layer"]}
    cells = {w["name"] for w in spec["workloads"]}
    m = entries["flash_fwd_roofline.train"]
    listed = ["bloom-560m.train-b8s2048", "bloom-1b7.train-tp2dp2"]
    assert m["workloads"] == listed and set(listed) <= cells
    # where ``flash_bwd_roofline.train`` reads, this does, and as it does
    bwd = entries["flash_bwd_roofline.train"]
    assert {k: v for k, v in m.items() if k != "name"} \
        == {k: v for k, v in bwd.items() if k != "name"}
    assert os.path.exists(os.path.join(
        harness.HERE, "layer_metrics", m["name"] + ".py"))
    # appended behind what was there (a later PR's may follow it)
    names = [e["name"] for e in spec["per_layer"]]
    assert names.index(m["name"]) > names.index("prefill_attn_roofline.eva")
