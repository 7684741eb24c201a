"""The readers PR 46 added: flash attention's one backward kernel, found
by name in the trace, five matmuls of 2*seq*seq*width/2 a head a call.
A program whose backward runs under other names (the parent commit's
``flash_dq`` / ``flash_dkv``, or a shape that fell back to them) reads
as ``None``, never as an error; a call at the device's peak reads 100,
never more."""
import json
import os

import pytest

from benchmark import harness, rooflines

REPO = os.path.dirname(harness.HERE)
PEAKS = {"flops_per_s": {"bfloat16": 1e12}, "hbm_bytes_per_s": 1e11}
V5E = {"flops_per_s": {"bfloat16": 197e12}, "hbm_bytes_per_s": 819e9}
SIZES = {"vocab_size": 4096, "hidden_size": 256, "n_layer": 2, "n_head": 4}
ROWS, SEQ = 2, 512
BWD = "%transpose_jvp_flash_bwd__.1 = (bf16[8,512,64], bf16[8,512,64], " \
      "bf16[8,512,64]) custom-call(%q, %k)"
FWD = "%jvp_flash_fwd_.1 = (bf16[8,512,64], f32[8,1,512]) custom-call(%q)"
PAIR = [
    ("%transpose_jvp_flash_dq__.1 = bf16[8,512,64] custom-call(%q)", 0, 40),
    ("%transpose_jvp_flash_dkv__.1 = (bf16[8,512,64], bf16[8,512,64]) "
     "custom-call(%q)", 40, 90),
]
USER = "%convert.4 = bf16[8,512,64] convert(%transpose_jvp_flash_bwd__.1)"


def reader(name="flash_bwd_roofline.train"):
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
    flops, nbytes = reader().call_cost(ROWS, SEQ, 4 // tensor, 64)
    least, bound = rooflines.least_time_s(flops, nbytes, PEAKS)
    assert bound == "compute"
    return least


def ns(seconds):
    return int(round(seconds * 1e9))


# (rows, seq, heads on a device, width): bloom-560m, bloom-1b7 under
# tp=2, GLM-4.7-Flash
CELLS = {"560m": (8, 2048, 16, 64), "1b7_tp2": (8, 2048, 8, 128),
         "glm": (4, 4096, 20, 256)}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_a_call_is_five_matmuls_and_compute_bound_at_the_cells_shapes(cell):
    rows, seq, heads, width = CELLS[cell]
    bwd = reader()
    flops, nbytes = bwd.call_cost(rows, seq, heads, width)
    matmul = 2.0 * rows * heads * seq * seq * width / 2.0
    assert flops == 5 * matmul
    # q, k, v, dO read; dQ, dK, dV written; the lse and delta rows
    assert nbytes == (7 * rows * seq * heads * width * 2
                      + 2 * rows * seq * heads * 4)
    least, bound = rooflines.least_time_s(flops, nbytes, V5E)
    assert bound == "compute" and least > 3 * nbytes / 819e9
    # what the pair owed by the accepted arithmetic: 3 + 4 matmuls; the
    # forward's two beside the five are the seven a step runs since
    pair = sum(rooflines.flash_call_cost(kind, rows, seq, heads, width)[0]
               for kind in ("dq", "dkv"))
    assert pair == 7 * matmul and bwd.MATMULS == 5


@pytest.mark.parametrize("name, want", [
    (BWD, True),
    ("%flash_bwd.7 = (bf16[8,512,64], bf16[8,512,64], bf16[8,512,64]) "
     "custom-call(%a)", True),
    (PAIR[0][0], False),
    (PAIR[1][0], False),
    (FWD, False),
    ("%flash_ring_dq.1 = f32[8,512,64] custom-call(%a)", False),
    # a consumer that names the kernel among its operands is not a call
    (USER, False),
])
def test_the_kernel_is_found_by_its_own_name(name, want):
    assert reader().is_call(name) is want


def test_the_name_holds_none_of_the_names_the_accepted_readers_search_for():
    """``mla_flash_roofline.train-moe`` and the prefill readers match
    ``flash_fwd`` / ``flash_dq`` / ``flash_dkv`` by substring: the new
    kernel is none of them to those readers."""
    old = reader("mla_flash_roofline.train-moe")
    assert old.kernel_of(BWD) is None
    assert old.kernel_of(FWD) == "fwd"
    assert not any(name in reader().KERNEL for name in old.KINDS)


@pytest.mark.parametrize("slowdown, tensor, calls", [
    (1.0, 1, 1), (2.0, 1, 1), (4.0, 1, 2), (2.0, 2, 1), (1.25, 2, 3)])
def test_share_is_least_time_over_the_calls_device_time(slowdown, tensor,
                                                        calls):
    """Every call ``slowdown`` times its roofline reads 100 / slowdown,
    however many calls the window holds and whatever share of the heads
    a device has; events of other kernels count for nothing."""
    one = one_call_s(tensor)
    ops, t = [(FWD, 0, ns(3 * one))], ns(3 * one)
    for n in range(calls):
        ops.append((BWD.replace(".1 =", f".{n + 1} ="), t,
                    t + ns(slowdown * one)))
        t += ns(slowdown * one)
        ops.append((USER, t, t + ns(one)))
        t += ns(one)
    got = reader().read(train_run(ops, tensor=tensor))
    assert got == pytest.approx(100.0 / slowdown, rel=1e-6)
    assert got <= 100.0 * (1 + 1e-5)     # whole nanoseconds


@pytest.mark.parametrize("ops", [
    None,                                            # not a traced run
    [],                                              # nothing ran
    PAIR,                                            # the parent's backward
    [(FWD, 0, 30)] + PAIR,
    [("%transpose_jvp__.1 = (bf16[8,512,64], bf16[8,512,64], "
      "bf16[8,512,64]) custom-call(%a)", 0, 10)],    # unnamed kernels
    [(BWD, 5, 5)],                                   # a call of no length
])
def test_nothing_to_read_is_none_not_an_error(ops):
    assert reader().read(train_run(ops)) is None
    glm = {"num_attention_heads": 4, "qk_nope_head_dim": 48,
           "qk_rope_head_dim": 16}
    assert reader("flash_bwd_roofline.train-moe").read(
        train_run(ops, sizes=glm)) is None


def test_the_accepted_readers_on_these_events():
    """What the two older flash readers make of a step since PR 46.
    ``flash_attn_roofline.train`` tells kernels by their RESULTS and
    takes ``flash_bwd`` (three tensors) for a dK/dV call: four matmuls
    reckoned where five ran, so a backward AT its roofline reads 80
    there. ``mla_flash_roofline.train-moe`` searches by name and reads
    the forward alone, whatever the backward takes."""
    by_shape = reader("flash_attn_roofline.train")
    assert by_shape.classify(BWD, (8, SEQ, 64)) == "dkv"
    assert by_shape.classify(FWD, (8, SEQ, 64)) == "fwd"
    got = by_shape.read(train_run([(BWD, 0, ns(one_call_s()))]))
    assert got == pytest.approx(80.0, rel=1e-3)

    by_name = reader("mla_flash_roofline.train-moe")
    glm = {"num_attention_heads": 4, "qk_nope_head_dim": 48,
           "qk_rope_head_dim": 16}
    flops, nbytes = rooflines.flash_call_cost("fwd", ROWS, SEQ, 4, 64)
    one_fwd = rooflines.least_time_s(flops, nbytes, PEAKS)[0]
    for bwd_s in (one_call_s(), 5 * one_call_s()):
        ops = [(FWD, 0, ns(2 * one_fwd)),
               (BWD, ns(2 * one_fwd), ns(2 * one_fwd) + ns(bwd_s))]
        assert by_name.read(train_run(ops, sizes=glm)) == pytest.approx(
            50.0, rel=1e-5)
    assert by_name.read(train_run([(BWD, 0, 40)], sizes=glm)) is None


def test_busiest_device_is_the_one_read():
    one = one_call_s()
    run = train_run([(BWD, 0, ns(2 * one))])
    run.trace["devices"].append(
        {"ops": [(BWD, 0, ns(4 * one))], "busy_ns": 2})
    assert reader().read(run) == pytest.approx(25.0, rel=1e-6)


def test_expert_cell_reads_latent_attentions_width_and_heads():
    """GLM's cell: 6 calls a step (five blocks and the MTP module's),
    4 rows x 20 heads x (192 + 64) x 4,096 positions."""
    sizes = {"num_attention_heads": 20, "qk_nope_head_dim": 192,
             "qk_rope_head_dim": 64, "v_head_dim": 256}
    call = "%transpose_jvp_flash_bwd__.{} = (bf16[80,4096,256], " \
           "bf16[80,4096,256], bf16[80,4096,256]) custom-call(%q)"
    ops = [(call.format(n), n * 10 ** 7, (n + 1) * 10 ** 7)
           for n in range(6)]
    run = train_run(ops, sizes=sizes, peaks=V5E, rows=4, seq=4096)
    got = reader("flash_bwd_roofline.train-moe").read(run)
    want = 100 * (5 * 2.0 * 4 * 20 * 4096 * 4096 * 256 / 2 / 197e12) / 0.01
    assert got == pytest.approx(want, rel=1e-6)
    assert 0 < got <= 100


def test_benchmark_json_lists_the_readers_where_they_find_something():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    entries = {m["name"]: m for m in spec["per_layer"]}
    cells = {w["name"] for w in spec["workloads"]}
    for name, listed in (
            ("flash_bwd_roofline.train",
             ["bloom-560m.train-b8s2048", "bloom-1b7.train-tp2dp2"]),
            ("flash_bwd_roofline.train-moe",
             ["glm-4.7-flash.train-ep8share-b4s4096"])):
        m = entries[name]
        assert m["workloads"] == listed and set(listed) <= cells
        assert (m["layer"], m["moves"], m["source"], m["unit"],
                m["better"]) == ("kernels", "train_tokens_per_s",
                                 "device_trace", "%", "higher")
        assert os.path.exists(os.path.join(
            harness.HERE, "layer_metrics", name + ".py"))
