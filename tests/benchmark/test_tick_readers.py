"""The readers PR 26 added, on hand-built facts and on empty ones: the
engine's phase and set-up clocks (``finish_run()``), the page-write
program's executions and the fused-CE kernels by name in the trace. A
program that lacks the clock or the name (the parent commit) reads as
``None``, never as an error."""
import os

import pytest

from benchmark import harness, rooflines

READERS = os.path.join(harness.HERE, "layer_metrics")
PEAKS = {"flops_per_s": {"bfloat16": 1e12}, "hbm_bytes_per_s": 1e11}
PHASES = {"admit": 0.010, "prefill": 0.300, "prepare": 0.020,
          "dispatch": 0.050, "fetch": 9.0, "record": 0.040}


def reader(name):
    return harness.load_module(os.path.join(READERS, name + ".py"))


def serve_run(run_metrics, modules=None):
    trace = None if modules is None else {
        "devices": [{"modules": modules, "ops": [], "busy_ns": 1}]}
    return harness.Result(end_to_end={}, attempted=1, failed=0,
                          t_window_start=0.0, memory_peak_bytes=0,
                          facts={"run_metrics": run_metrics}, trace=trace)


@pytest.mark.parametrize("name, metrics, want", [
    ("prefill_stall_ms.chat",
     {"tick_phase_s": PHASES, "prefills": 30, "decode_steps": 100}, 10.0),
    ("prefill_stall_ms.chat",
     {"tick_phase_s": PHASES, "prefills": 0, "decode_steps": 100}, None),
    ("prefill_stall_ms.chat", {"prefills": 30, "decode_steps": 100}, None),
    ("tick_host_ms.chat",
     {"tick_phase_s": PHASES, "prefills": 30, "decode_steps": 100}, 1.2),
    ("tick_host_ms.chat",
     {"tick_phase_s": PHASES, "prefills": 30, "decode_steps": 0}, None),
    ("tick_host_ms.chat", {"prefills": 30, "decode_steps": 100}, None),
    ("engine_build_s.chat",
     {"setup": {"build_s": 1.25, "first_call_s": {}}}, 1.25),
    ("engine_build_s.chat", {}, None),
    ("program_first_call_s.chat",
     {"setup": {"build_s": 1.25, "first_call_s": {
         "step/0": 2.0, "prefill/64": 0.5, "write/64": 0.25}}}, 2.75),
    ("program_first_call_s.chat",
     {"setup": {"build_s": 1.25, "first_call_s": {}}}, None),
    ("program_first_call_s.chat", {}, None),
])
def test_engine_clock_readers(name, metrics, want):
    got = reader(name).read(serve_run(metrics))
    assert got == (want if want is None else pytest.approx(want))


def test_page_write_reader_means_the_write_programs_executions():
    read = reader("page_write_device_ms.chat").read
    modules = [("jit__step(123)", 0, 100_000_000),
               ("jit__write(7)", 100_000_000, 130_000_000),
               ("jit__prefill(9)", 130_000_000, 140_000_000),
               ("jit__write(8)", 140_000_000, 180_000_000)]
    assert read(serve_run({}, modules)) == pytest.approx(35.0)
    assert read(serve_run({}, modules[:1])) is None      # no write ran
    assert read(serve_run({})) is None                   # not a traced run


SIZES = {"vocab_size": 4096, "hidden_size": 64, "n_layer": 2, "n_head": 4}


def train_run(ops, tensor=1):
    return harness.Result(
        end_to_end={}, attempted=1, failed=0, t_window_start=0.0,
        memory_peak_bytes=0,
        facts={"sizes": SIZES, "rows_per_replica": 2, "seq": 129,
               "tensor": tensor, "peaks": PEAKS},
        trace={"devices": [{"ops": ops, "busy_ns": 1}]})


def test_fused_ce_reader_finds_the_kernels_by_name():
    ce = reader("fused_ce_roofline.train")
    assert ce.kernel_of("%jvp_fused_ce_fwd_.1 = (f32[256]) custom-call("
                        "%p), custom_call_target=\"tpu_custom_call\""
                        ) == "fused_ce_fwd"
    assert ce.kernel_of("%transpose_jvp_fused_ce_dw__.3 = bf16[4096,64] "
                        "custom-call(%x)") == "fused_ce_dw"
    # a consumer that only NAMES the kernel among its operands is not it
    assert ce.kernel_of("%fusion.9 = f32[] fusion(%jvp_fused_ce_fwd_.1)"
                        ) is None
    assert ce.kernel_of("%transpose_jvp___.2 = bf16[8] custom-call(%x)"
                        ) is None
    tokens, h, v = 2 * 128, 64, 4096
    one = 2.0 * tokens * v * h / PEAKS["flops_per_s"]["bfloat16"]
    for kind, matmuls in ce.MATMULS.items():
        flops, nbytes = ce.call_cost(kind, tokens, h, v)
        assert flops == matmuls * 2.0 * tokens * v * h
        assert rooflines.least_time_s(flops, nbytes, PEAKS)[1] == "compute"
    # the five matmuls that run are 5/3 of the three that forward and
    # backward of a dense head need (mfu_pct.train's 6 per parameter)
    assert sum(ce.MATMULS.values()) * 2 * v * h == pytest.approx(
        5 / 3 * 6 * v * h)

    def ns(seconds):
        return int(seconds * 1e9)

    ops = [("%jvp_fused_ce_fwd_.1 = f32[256] custom-call(%a)", 0,
            ns(2 * one)),
           ("%fusion.4 = f32[] fusion(%jvp_fused_ce_fwd_.1)", ns(2 * one),
            ns(3 * one)),
           ("%transpose_jvp_fused_ce_dh__.1 = bf16[256,64] custom-call(%a)",
            ns(3 * one), ns(7 * one)),
           ("%transpose_jvp_fused_ce_dw__.1 = bf16[4096,64] custom-call(%a)",
            ns(7 * one), ns(11 * one))]
    # least 1 + 2 + 2 matmuls over 2 + 4 + 4 of device time
    assert ce.read(train_run(ops)) == pytest.approx(50.0, rel=1e-6)
    # the vocabulary over tensor=2: half the rows a device, half the work
    assert ce.read(train_run(ops, tensor=2)) == pytest.approx(25.0, rel=1e-6)


def test_fused_ce_reader_reads_nothing_from_unnamed_kernels():
    """The parent commit's trace: custom calls named after transforms."""
    ce = reader("fused_ce_roofline.train")
    ops = [("%jvp__.1 = f32[256] custom-call(%a)", 0, 10),
           ("%transpose_jvp___.1 = bf16[256,64] custom-call(%a)", 10, 30)]
    assert ce.read(train_run(ops)) is None
    run = train_run(ops)
    run.trace = None
    assert ce.read(run) is None
