"""Derived gauges: MFU arithmetic against the peak table, HLO
communication-bytes accounting (synthetic text + a real compiled
shard_map program), compiled step stats, HBM fallback."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from pipegoose_tpu.distributed.compat import shard_map
from pipegoose_tpu.telemetry import derived


def test_peak_flops_table_substring_match():
    assert derived.peak_flops_for("TPU v5e") == 197e12
    assert derived.peak_flops_for("TPU v5 lite") == 197e12
    assert derived.peak_flops_for("v5p slice") == 459e12
    assert derived.peak_flops_for("cpu") == 1e12  # fake-device planning row
    with pytest.raises(ValueError, match="PEAK_FLOPS"):
        derived.peak_flops_for("TPU v9")


def test_mfu_arithmetic():
    # 1e12 FLOPs in 10ms on a 197e12-peak chip -> 1e14/1.97e14
    assert derived.mfu(1e12, 0.01, peak=197e12) == pytest.approx(
        1e14 / 197e12
    )
    # n_devices divides the peak pool
    assert derived.mfu(1e12, 0.01, peak=197e12, n_devices=4) == pytest.approx(
        1e14 / (4 * 197e12)
    )
    assert derived.mfu(1e12, 0.0, peak=1e12) == 0.0
    assert derived.tokens_per_second(100, 2.0) == 50.0
    assert derived.tokens_per_second(100, 0.0) == 0.0


def test_collective_bytes_parses_hlo_text():
    hlo = "\n".join([
        "  %ar = f32[8,16]{1,0} all-reduce(f32[8,16] %x), replica_groups={}",
        "  %ag = bf16[4,256]{1,0} all-gather(bf16[2,256] %y), dimensions={0}",
        "  %rs = f32[2,8]{1,0} reduce-scatter(f32[8,8] %z), dimensions={0}",
        "  %cp = u8[128]{0} collective-permute(u8[128] %w)",
        "  %a2a = f32[16]{0} all-to-all(f32[16] %v)",
        "  %dead = f32[999] add(f32[999] %a, f32[999] %b)",
    ])
    out = derived.collective_bytes(hlo)
    assert out["all-reduce"] == 8 * 16 * 4
    assert out["all-gather"] == 4 * 256 * 2
    assert out["reduce-scatter"] == 2 * 8 * 4
    assert out["collective-permute"] == 128
    assert out["all-to-all"] == 16 * 4
    assert out["total"] == sum(
        v for k, v in out.items() if k != "total"
    )


def test_collective_bytes_counts_async_start_once():
    # real XLA async form: the -start result tuple carries BOTH the
    # operand and output buffers; only the output half is the payload,
    # and the -done half must not count at all
    hlo = "\n".join([
        "  %s = (f32[64]{0}, f32[64]{0}) all-reduce-start(f32[64] %x)",
        "  %d = f32[64]{0} all-reduce-done((f32[64], f32[64]) %s)",
    ])
    out = derived.collective_bytes(hlo)
    assert out["all-reduce"] == 64 * 4


def test_collective_bytes_async_asymmetric_and_contexts():
    # asymmetric async collectives: the output half differs from the
    # input half, so "half the tuple" would miscount — all-gather grows
    # (2,256)->(4,256), reduce-scatter shrinks (8,8)->(2,8); trailing
    # scalar u32 context slots (collective-permute-start) are ignored
    hlo = "\n".join([
        "  %ag = (bf16[2,256]{1,0}, bf16[4,256]{1,0}) all-gather-start(bf16[2,256] %x)",
        "  %rs = (f32[8,8]{1,0}, f32[2,8]{1,0}) reduce-scatter-start(f32[8,8] %y)",
        "  %cp = (u8[128]{0}, u8[128]{0}, u32[], u32[]) collective-permute-start(u8[128] %z)",
    ])
    out = derived.collective_bytes(hlo)
    assert out["all-gather"] == 4 * 256 * 2
    assert out["reduce-scatter"] == 2 * 8 * 4
    assert out["collective-permute"] == 128


def test_compiled_step_stats_reports_flops_and_comms(devices):
    """One lower+compile yields XLA flops AND the all-reduce bytes of a
    psum'd shard_map program — the compiler-ground-truth MFU/comms
    inputs (GSPMD lineage, ISSUE 2)."""
    mesh = jax.sharding.Mesh(np.array(devices).reshape(8), ("d",))

    def f(x):
        return jax.lax.psum((x * x).sum(), "d")

    g = shard_map(f, mesh=mesh, in_specs=(P("d"),), out_specs=P())
    stats = derived.compiled_step_stats(g, jnp.ones((8, 128)))
    assert stats["flops"] > 0
    assert stats["comm_bytes"] >= 4  # the f32 psum scalar, at least
    assert "all-reduce" in stats["comm_by_op"]

    # a collective-free program reports zero comm bytes
    stats0 = derived.compiled_step_stats(lambda x: x * 2, jnp.ones(16))
    assert stats0["comm_bytes"] == 0
    assert stats0["comm_by_op"] == {}


def test_step_flops_matmul_scales():
    a = jnp.ones((32, 32))
    b = jnp.ones((128, 128))
    f = lambda x: x @ x  # noqa: E731
    small, big = derived.step_flops(f, a), derived.step_flops(f, b)
    assert small > 0
    # 4x dim -> 64x matmul FLOPs
    assert big == pytest.approx(64 * small, rel=0.01)


def test_hbm_utilization_empty_on_cpu():
    # CPU devices report no memory stats: the gauge source degrades to
    # an empty dict, never an exception
    assert derived.hbm_utilization() == {}


def test_collective_bytes_tuple_shaped_sync_variadic():
    # variadic SYNC forms print a tuple result whose elements are ALL
    # outputs (the ISSUE-4 satellite fix: structural tuple parsing
    # instead of treating the tuple like an async operand/output pair)
    hlo = "\n".join([
        "  %rs = (f32[2,8]{1,0}, f32[4]{0}) reduce-scatter(f32[8,8] %a, "
        "f32[16] %b), dimensions={0}",
        "  %cp = (f32[128]{0}, f32[128]{0}) collective-permute("
        "(f32[128], f32[128]) %p), source_target_pairs={{0,1},{1,0}}",
    ])
    out = derived.collective_bytes(hlo)
    assert out["reduce-scatter"] == 2 * 8 * 4 + 4 * 4
    assert out["collective-permute"] == 2 * 128 * 4


def test_collective_bytes_sync_permute_strips_context_slots():
    # sync collective-permute keeping trailing u32[] context slots: the
    # scalars are bookkeeping, not payload
    hlo = ("  %cp = (u8[128]{0}, u32[], u32[]) collective-permute("
           "u8[128] %z), source_target_pairs={{0,1}}")
    assert derived.collective_bytes(hlo)["collective-permute"] == 128


def test_collective_bytes_nested_variadic_start():
    # async variadic start: ((operands...), (outputs...), contexts) —
    # only the LAST nested tuple (the outputs) is payload
    hlo = "\n".join([
        "  %rs = ((f32[8,8]{1,0}, f32[16]{0}), (f32[2,8]{1,0}, f32[4]{0}), "
        "u32[], u32[]) reduce-scatter-start(f32[8,8] %a, f32[16] %b)",
        "  %d = (f32[2,8]{1,0}, f32[4]{0}) reduce-scatter-done(%rs)",
        "  %cps = ((u8[128]{0}), (u8[128]{0}), u32[], u32[]) "
        "collective-permute-start(u8[128] %z)",
    ])
    out = derived.collective_bytes(hlo)
    assert out["reduce-scatter"] == 2 * 8 * 4 + 4 * 4  # done half skipped
    assert out["collective-permute"] == 128


def test_iter_collectives_line_level():
    hlo = "\n".join([
        "  %ar = f32[8]{0} all-reduce(f32[8] %x)",
        "  %ag = (bf16[2,4]{1,0}, bf16[4,4]{1,0}) all-gather-start(bf16[2,4] %y)",
        "  %agd = bf16[4,4]{1,0} all-gather-done(%ag)",
    ])
    items = list(derived.iter_collectives(hlo))
    assert [(c["op"], c["bytes"], c["start"]) for c in items] == [
        ("all-reduce", 32, False),
        ("all-gather", 32, True),
    ]


@pytest.mark.parametrize("fn_name,table", [
    ("peak_flops_for", "PEAK_FLOPS"),
    ("ici_bytes_per_s_for", "PEAK_ICI_BYTES"),
    ("dci_bytes_per_s_for", "PEAK_DCI_BYTES"),
    ("hbm_bytes_for", "HBM_BYTES"),
    ("hbm_bw_bytes_per_s_for", "HBM_BW_BYTES"),
])
def test_unknown_device_kind_raises_naming_the_table(fn_name, table):
    """A device kind no row matches is an error that names the table —
    never a default (a typo'd --device-kind, or a chip nobody entered,
    would otherwise be scored against another machine's numbers)."""
    with pytest.raises(ValueError, match=table):
        getattr(derived, fn_name)("martian accelerator v9")


def test_known_device_kinds_never_warn():
    import warnings as _w

    for kind in ("TPU v5e", "TPU v5 lite", "v5p slice", "cpu",
                 "TPU v4"):
        with _w.catch_warnings():
            _w.simplefilter("error")
            assert derived.peak_flops_for(kind) > 0
            assert derived.ici_bytes_per_s_for(kind) > 0
            assert derived.hbm_bytes_for(kind) > 0
