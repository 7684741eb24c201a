"""The Pallas kernels of the bloom-560m path, compiled for a described
(not attached) TPU v5e at real widths. Nothing runs: the chip's compiler
accepts or refuses each kernel, which interpret-mode tests cannot show
(tile alignment, Mosaic legalization, VMEM). Results and times come from
``chip_smoke.py`` on the chip. The serving engine's pool programs are
compiled here too, for their STRUCTURE: each has to update the donated
KV pool in place (PERF.md, PR 27).

The topology is described inside a fixture of THIS file only: the worker
that runs the file loads the TPU library, every other worker never does.
"""
import functools
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from pipegoose_tpu.models import bloom, falcon_h1
from pipegoose_tpu.nn.sequence_parallel.ring_attention import (
    ring_flash_attention,
)
from pipegoose_tpu.ops import flash_attention as fa
from pipegoose_tpu.ops import fused_ce
from pipegoose_tpu.ops.flash_attention import flash_attention
from pipegoose_tpu.ops.fused_ce import fused_ce_sums
from pipegoose_tpu.quant.matmul import quantized_matmul
from pipegoose_tpu.serving import ServingEngine, kv_pool
from pipegoose_tpu.serving.kv_pool import import_page_slab

# bloom-560m: hidden 1024, 16 heads x 64, padded vocab 250880; train
# b8 x s1024, decode 8 slots over a 16-row-page pool.
B, S, NH, HD, H, V = 8, 1024, 16, 64, 1024, 250880
PS = 16


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure to describe = skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip (it warns and recompiles)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def as_default_device(one_chip):
    """The described chip as the default device while a case is traced:
    the flash kernels ask that device for its VMEM (``_vmem_limit_bytes``;
    with the CPU there they plan for the compiler's default limit)."""
    with jax.default_device(next(iter(one_chip.device_set))):
        yield


# the two BLOOM train cells' attention as one chip sees it: bloom-560m 8
# rows x 16 heads x 64, bloom-1b7 tp2dp2 8 rows x 8 local heads x 128,
# both at 2,048 positions: (rows, seq, heads, head_dim)
CELL_SHAPES = {"cell_560m": (8, 2048, 16, 64), "cell_1b7_tp2": (8, 2048, 8, 128)}


def _flash(grad, shape=(B, S, NH, HD), dtype=jnp.bfloat16, **rule):
    qkv = [(shape, dtype)] * 3
    slopes = ((shape[2],), jnp.float32)

    def fwd(q, k, v, sl):
        return flash_attention(q, k, v, alibi_slopes=sl, interpret=False,
                               **rule)

    if not grad:
        return fwd, qkv + [slopes]

    def loss(q, k, v, sl):
        return fwd(q, k, v, sl).astype(jnp.float32).sum()

    return jax.grad(loss, argnums=(0, 1, 2)), qkv + [slopes]


def _flash_gqa(q_shape, kv_heads, dtype=jnp.bfloat16, **rule):
    """The forward a rotary model's prefill makes: no slopes, ``kv_heads``
    KV heads under the queries' ``q_shape[2]``."""
    b, s, _, hd = q_shape
    kv = ((b, s, kv_heads, hd), dtype)

    def fwd(q, k, v):
        return flash_attention(q, k, v, alibi_slopes=None, interpret=False,
                               scale=hd ** -0.5, **rule)

    return fwd, [(q_shape, dtype), kv, kv]


def _ring_chunk(grad=False):
    shapes = [((B, S, NH, HD), jnp.bfloat16)] * 3 + [((NH,), jnp.float32)]

    def fn(q, k, v, sl):
        return ring_flash_attention(q, k, v, None, alibi_slopes=sl,
                                    interpret=False)

    if not grad:
        return fn, shapes

    def loss(q, k, v, sl):
        return fn(q, k, v, sl).astype(jnp.float32).sum()

    return jax.grad(loss, argnums=(0, 1, 2)), shapes


# the head as one chip of each train cell sees it: (tokens, local rows
# of the vocabulary, hidden, valid_size, block_v); GLM's model passes
# block_v 256 and masks the rows its vocabulary is padded by
CE_CELLS = {"cell_560m": (16384, 250880, 1024, None, 512),
            "cell_1b7_tp2": (16384, 125440, 2048, None, 512),
            "cell_glm": (16384, 19456, 2048, 19360, 256)}


def _fused_ce(grad, t=B * S, v=V, hidden=H, valid=None, block_v=512,
              layout="vh"):
    w_shape = (v, hidden) if layout == "vh" else (hidden, v)
    shapes = [((t, hidden), jnp.bfloat16), (w_shape, jnp.bfloat16),
              ((t,), jnp.int32), ((t,), jnp.float32)]

    def loss(h, w, tgt, tw):
        tot, cnt = fused_ce_sums(h, w, tgt, tw, None, valid, block_v=block_v,
                                 interpret=False, weight_layout=layout)
        return tot / cnt

    return (jax.grad(loss, argnums=(0, 1)) if grad else loss), shapes


def _quant_matmul(int4):
    k, n = H, 4 * H
    if int4:
        q, scale = ((k // 2, n), jnp.int8), ((k // 32, n), jnp.float32)
    else:
        q, scale = ((k, n), jnp.int8), ((n,), jnp.float32)
    fn = functools.partial(quantized_matmul, impl="pallas", interpret=False)
    return fn, [((B, k), jnp.bfloat16), q, scale]


CASES = {
    "flash_fwd": lambda: _flash(False),
    "flash_fwd_bwd": lambda: _flash(True),
    "flash_fwd_bwd_cell_560m": lambda: _flash(True, CELL_SHAPES["cell_560m"]),
    "flash_fwd_bwd_cell_1b7_tp2": lambda: _flash(True, CELL_SHAPES["cell_1b7_tp2"]),
    # the callers no cell runs, whose index maps clamp by their own rule:
    # a sliding window (mixtral), no rule at all (albert, ulysses). At
    # 16 heads x 64 two heads share a 128-lane tile (the paired kernels,
    # as in every case above but the 1b7 cell's); at 128 a head a tile
    "flash_fwd_bwd_window": lambda: _flash(True, (8, 2048, 16, 64), window=700),
    "flash_fwd_bwd_noncausal": lambda: _flash(True, (8, 2048, 16, 64),
                                              causal=False),
    "flash_fwd_bwd_window_hd128": lambda: _flash(True, (8, 2048, 8, 128),
                                                 window=700),
    "flash_fwd_bwd_noncausal_hd128": lambda: _flash(True, (8, 2048, 8, 128),
                                                    causal=False),
    # width 64 where the heads do not pair (an odd count): a head a
    # half-filled tile, the kernels as they were
    "flash_fwd_bwd_odd_heads_hd64": lambda: _flash(True, (8, 2048, 15, 64)),
    # float32 at width 512: the working set passes a v5e's budget at
    # 1,024 x 1,024 and the backward takes 512 x 512
    "flash_fwd_bwd_f32_w512": lambda: _flash(True, (1, 2048, 16, 512),
                                             jnp.float32),
    # SmallThinker's 8,192-token prefill: 7 query heads a KV head, and a
    # window layer's 4,096 keys
    "flash_fwd_g7_think": lambda: _flash_gqa((1, 8192, 28, 128), 4),
    "flash_fwd_g7_window_think": lambda: _flash_gqa((1, 8192, 28, 128), 4,
                                                    window=4096),
    "ring_chunk": _ring_chunk,
    "ring_chunk_bwd": lambda: _ring_chunk(True),
    "fused_ce_fwd": lambda: _fused_ce(False),
    "fused_ce_bwd": lambda: _fused_ce(True),
    # the untied (H, V) head of llama and mixtral, which no cell runs:
    # the carried dw tile is a block of columns
    "fused_ce_bwd_hv": lambda: _fused_ce(True, layout="hv"),
    "matmul_int8": lambda: _quant_matmul(False),
    "matmul_int4": lambda: _quant_matmul(True),
}


# the kernels each case runs, by the ``name=`` of their ``pallas_call``:
# the name the chip's trace prints for the kernel's ``XLA Ops`` events
KERNELS = {
    "flash_fwd": ["flash_fwd"],
    "flash_fwd_bwd": ["flash_fwd", "flash_bwd"],
    "flash_fwd_bwd_cell_560m": ["flash_fwd", "flash_bwd"],
    "flash_fwd_bwd_cell_1b7_tp2": ["flash_fwd", "flash_bwd"],
    "flash_fwd_bwd_window": ["flash_fwd", "flash_bwd"],
    "flash_fwd_bwd_noncausal": ["flash_fwd", "flash_bwd"],
    "flash_fwd_bwd_window_hd128": ["flash_fwd", "flash_bwd"],
    "flash_fwd_bwd_noncausal_hd128": ["flash_fwd", "flash_bwd"],
    "flash_fwd_bwd_odd_heads_hd64": ["flash_fwd", "flash_bwd"],
    "flash_fwd_bwd_f32_w512": ["flash_fwd", "flash_bwd"],
    "flash_fwd_g7_think": ["flash_fwd"],
    "flash_fwd_g7_window_think": ["flash_fwd"],
    "ring_chunk": ["flash_ring_fwd"],
    "ring_chunk_bwd": ["flash_ring_fwd", "flash_ring_dq", "flash_ring_dkv"],
    "fused_ce_fwd": ["fused_ce_fwd"],
    "fused_ce_bwd": ["fused_ce_fwd", "fused_ce_bwd"],
    "fused_ce_bwd_hv": ["fused_ce_fwd", "fused_ce_bwd"],
    "matmul_int8": ["int8_matmul"],
    "matmul_int4": ["int4_matmul"],
}


# with no device the flash kernels plan for the compiler's default 16
# MiB: at float32 x width 512 dQ's whole-sequence accumulator and result
# (2,048 x 512 x 12 bytes) are the whole budget, and the backward is the
# pair ``flash_bwd`` falls back to
NO_DEVICE_KERNELS = {
    "flash_fwd_bwd_f32_w512": ["flash_fwd", "flash_dq", "flash_dkv"],
}


def _shapes(shapes, **kw):
    return jax.tree_util.tree_map(
        lambda sd: jax.ShapeDtypeStruct(sd[0], sd[1], **kw),
        shapes, is_leaf=lambda x: isinstance(x, tuple),
    )


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, as_default_device, case):
    fn, shapes = CASES[case]()
    text = jax.jit(fn).lower(*_shapes(shapes, sharding=one_chip)) \
        .compile().as_text()
    assert "tpu_custom_call" in text
    # the compiled instruction, which is the trace's event, is named
    # after the kernel; jax wraps the name in the transforms it went
    # through (``%transpose_jvp_flash_bwd__.1``), so a reader searches
    called = [ln.split(" = ")[0] for ln in text.splitlines()
              if " custom-call(" in ln and "tpu_custom_call" in ln]
    for name in KERNELS[case]:
        assert any(name in instruction for instruction in called), \
            (name, called)


def _flash_calls(text):
    """The compiled program's Mosaic calls: ``[(instruction name, result
    shapes, operand shapes, the line)]``."""
    calls = []
    for ln in text.splitlines():
        if " custom-call(" in ln and "tpu_custom_call" in ln:
            head, _, rest = ln.strip().partition(" custom-call(")
            name, _, results = head.partition(" = ")
            operands = rest.split("), custom_call_target")[0]
            shape = re.compile(r"\b(?:bf16|f32|s32)\[[\d,]*\]")
            calls.append((name, shape.findall(results),
                          shape.findall(operands), ln.strip()))
    return calls


@pytest.mark.parametrize("cell", sorted(CELL_SHAPES))
def test_flash_results_keep_the_shapes_the_roofline_reader_tells_them_by(
        one_chip, as_default_device, cell):
    """``flash_attn_roofline.train`` tells flash kernels apart by their
    results (first ``bf16[rows*heads, seq, head_dim]``; a float32 row
    statistic second = forward, a second tensor = dK/dV, alone = dQ).
    Compiled at a cell's shape the program holds exactly ``flash_fwd``
    and ``flash_bwd`` (dQ, dK and dV from one kernel).

    ``cell_1b7_tp2`` (a head a 128-lane tile): the accepted reader takes
    ``flash_bwd`` for a dK/dV call (four matmuls where it runs five, so
    its share reads low: PERF.md section 7), the forward for the
    forward, and loses no call; nothing reads as dQ.

    ``cell_560m`` (two heads a tile since PR 49): the kernels take and
    return the model's own ``bf16[8,2048,1024]``, nothing of that size is
    transposed or copied between the parameters and the calls, and the
    reader finds NONE of them: ``flash_attn_roofline.train`` reads
    ``None`` in that cell (the silence, written down; ``mfu_pct.train``
    and ``flash_bwd_roofline.train``, by name, go on reading)."""
    import os

    from benchmark import harness

    reader = harness.load_module(os.path.join(
        os.path.dirname(harness.__file__), "layer_metrics",
        "flash_attn_roofline.train.py"))
    rows, seq, heads, hd = CELL_SHAPES[cell]
    if cell == "cell_560m":
        # the operands as the model holds them: (rows, seq, heads * hd)
        plane = (rows, seq, heads * hd)

        def loss(q, k, v, sl):
            q, k, v = (x.reshape(rows, seq, heads, hd) for x in (q, k, v))
            return flash_attention(q, k, v, alibi_slopes=sl, interpret=False) \
                .reshape(plane).astype(jnp.float32).sum()

        fn = jax.grad(loss, argnums=(0, 1, 2))
        shapes = [(plane, jnp.bfloat16)] * 3 + [((heads,), jnp.float32)]
    else:
        fn, shapes = _flash(True, CELL_SHAPES[cell])
    text = jax.jit(fn).lower(*_shapes(shapes, sharding=one_chip)) \
        .compile().as_text()
    calls = _flash_calls(text)
    kinds = {}
    for name, _, _, ln in calls:
        kind = reader.classify(ln, (rows * heads, seq, hd))
        kinds.setdefault(kind, []).append(name)
    assert "flash_dq" not in text and "flash_dkv" not in text
    if cell != "cell_560m":
        assert sorted(kinds) == ["dkv", "fwd"], kinds
        assert len(kinds["fwd"]) == 1 and "flash_fwd" in kinds["fwd"][0], kinds
        assert len(kinds["dkv"]) == 1 and "flash_bwd" in kinds["dkv"][0], kinds
        return
    assert list(kinds) == [None], kinds
    assert sorted("flash_fwd" in name for name, *_ in calls) == [False, True]
    assert sum("flash_bwd" in name for name, *_ in calls) == 1
    tensor = "bf16[%d,%d,%d]" % plane
    for name, results, operands, _ in calls:
        tensors = [s for s in results + operands if s.startswith("bf16")]
        assert tensors and set(tensors) == {tensor}, (name, tensors)
    # the reshape of what the model hands over: no array of the plane's
    # size is moved on the way in or out, and none is 64 wide
    moved = [ln.strip()[:160] for ln in text.splitlines()
             if re.search(r" (copy|transpose)\(", ln)
             and re.search(r"= \w+\[([\d,]*)\]", ln)
             and math.prod(int(d) for d in re.search(
                 r"= \w+\[([\d,]*)\]", ln).group(1).split(",") if d)
             >= math.prod(plane)]
    assert not moved, moved
    assert "bf16[%d,%d,%d]" % (rows * heads, seq, hd) not in text
    assert ",%d,%d]" % (heads, hd) not in text


def _remat_block_gradient(one_chip, heads, hd):
    """ONE checkpointed BLOOM block's gradient at 8 rows x 2,048
    positions, traced for the described chip (shapes alone)."""
    from functools import partial

    rows, seq = 8, 2048
    hidden = heads * hd
    cfg = bloom.BloomConfig(vocab_size=1024, hidden_size=hidden, n_layer=1,
                            n_head=heads, remat=True, use_flash=True,
                            dtype=jnp.bfloat16)
    params = jax.eval_shape(lambda: bloom.init_params(
        cfg, jax.random.PRNGKey(0)))
    blk = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype, sharding=one_chip),
        params["blocks"])
    x = jax.ShapeDtypeStruct((rows, seq, hidden), jnp.bfloat16,
                             sharding=one_chip)
    mask = jax.ShapeDtypeStruct((rows, seq), jnp.int32, sharding=one_chip)
    block = bloom._remat_wrap(
        partial(bloom._block, config=cfg, tp_axis=None), cfg)

    def loss(blk, x, mask):
        return block(blk, x, bloom.attention_bias(mask, cfg)) \
            .astype(jnp.float32).sum()

    return jax.jit(jax.grad(loss, argnums=(0, 1))).lower(blk, x, mask)


def test_remat_block_at_the_560m_shape_holds_no_plane_heads_over_positions(
        one_chip, as_default_device, monkeypatch):
    """The gradient of ONE checkpointed bloom-560m block (8 rows x 2,048
    positions x 16 heads of 64), compiled for the described v5e: what the
    train step's layer loop runs twice a layer. With a head a half-filled
    tile the compiler held q, k, v and the kernels' results heads over
    positions, ``bf16[8,16,2048,64]`` and ``bf16[128,2048,64]``, padded to
    twice their bytes, and copied a plane nine times on the way in and
    out (PERF.md, PR 49, step 0). Now: one ``flash_fwd`` (its result and
    lse saved, not run again) and one ``flash_bwd``, every tensor they
    touch the model's own ``bf16[8,2048,1024]`` row-major; NO array of a
    plane's size has the positions before a 64-wide last dimension.

    Since PR 53 the projection's columns are regrouped by kind
    (``bloom._project_qkv``): the interleaved ``(8, 2048, 16, 3, 64)``
    view, which the compiler kept sequence-minor and copied a plane of at
    every use (12 of the 14 plane-sized copies this test's parent held,
    PR 49's count), is gone in every dtype; the projection
    ``bf16[8,2048,3072]`` is row-major wherever it stands; the three
    slices' gradient is assembled in place, a third at a time
    (``dynamic-update-slice`` into ONE bf16 buffer: no ``pad``, no
    float32 plane, with no ``custom_vjp`` to ask for it); and the
    program's temporaries fall from 0.375 to 0.341 GB. TWO plane-sized
    copies are left, the number PR 49's round read with the regroup:
    ``flash_fwd``'s row-major result into the output projection's
    sequence-minor operand and dO back (ROADMAP A8 c). What stays
    sequence-minor (``{1,2,0}``) among the hidden-1024 arrays around the
    norms and the 1,024-wide matmuls is the compiler's own choice."""
    monkeypatch.setattr(fa, "_resolve_interpret", lambda interpret: False)
    rows, seq, heads, hd = CELL_SHAPES["cell_560m"]
    hidden = heads * hd
    compiled = _remat_block_gradient(one_chip, heads, hd).compile()
    text = compiled.as_text()
    calls = _flash_calls(text)
    assert sorted(("flash_fwd" in n, "flash_bwd" in n) for n, *_ in calls) \
        == [(False, True), (True, False)], [n for n, *_ in calls]
    plane = "bf16[%d,%d,%d]" % (rows, seq, hidden)
    for name, results, operands, ln in calls:
        tensors = [s for s in results + operands if s.startswith("bf16")]
        assert set(tensors) == {plane}, (name, tensors)
        assert plane + "{2,1,0" in ln
    moved = []
    for dims, op in re.findall(r"= \w+\[([\d,]+)\]\{[^}]*\} ([\w-]+)\(", text):
        dims = [int(d) for d in dims.split(",")]
        if math.prod(dims) >= rows * seq * hidden:
            assert dims[-2:] != [seq, hd], dims
            if op in ("copy", "transpose", "pad"):
                moved.append((op, dims))
    assert sorted(moved) == [("copy", [rows, seq, hidden])] * 2, moved
    assert "[%d,%d,%d,3,%d]" % (rows, seq, heads, hd) not in text
    fused = "bf16[%d,%d,%d]" % (rows, seq, 3 * hidden)
    layouts = set(re.findall(re.escape(fused) + r"\{([\d,]+)", text))
    assert layouts == {"2,1,0"}, layouts
    assert re.search(re.escape(fused) + r"\{[^}]*\} dynamic-update-slice\(",
                     text)
    assert compiled.memory_analysis().temp_size_in_bytes < 0.36e9


def test_remat_block_at_a_whole_tile_head_keeps_the_interleaved_split(
        one_chip, as_default_device, monkeypatch):
    """The same block at 16 heads of 128 (bloom-1b7's width): a head is
    a whole tile, ``head_dim % 128 == 0``, and the lowered gradient still
    takes the projection apart as ``(8, 2048, 16, 3, 128)``; no column
    of the weight is moved."""
    monkeypatch.setattr(fa, "_resolve_interpret", lambda interpret: False)
    text = _remat_block_gradient(one_chip, 16, 128).as_text()
    assert "8x2048x16x3x128x" in text
    assert "2048x16x3x128x" not in text.replace("8x2048x16x3x128x", "")
    by_kind = _remat_block_gradient(one_chip, 16, 64).as_text()
    assert "8x2048x16x3x64x" not in by_kind
    assert "1024x16x3x64x" in by_kind  # the weight's columns, regrouped


# width 256 at 4,096 positions is GLM-4.7-Flash's (its other kernels
# are in test_chip_compile_glm.py)
PLANNED_SHAPES = dict(CELL_SHAPES, cell_glm=(4, 4096, 20, 256))


@pytest.mark.parametrize("cell", sorted(PLANNED_SHAPES))
def test_flash_blocks_planned_for_the_default_limit_compile_under_it(
        one_chip, cell):
    """Where the device is not a TPU Pallas knows (here: the CPU is the
    default device) the kernels ask for the compiler's default 16 MiB and
    plan their blocks for it: the budget binds at every cell's shape, and
    the chip's compiler takes what the arithmetic let through."""
    _, seq, _, hd = PLANNED_SHAPES[cell]
    limit = fa._vmem_limit_bytes()
    assert limit == 16 * 2**20
    for kind in ("fwd", "dq", "dkv", "bwd"):
        bq, bk = fa._pick_blocks(seq, hd, 2, kind, limit)
        assert (bq, bk) != (1024, 1024) and bq >= 256, (kind, bq, bk)
    fn, shapes = _flash(True, PLANNED_SHAPES[cell])
    text = jax.jit(fn).lower(*_shapes(shapes, sharding=one_chip)) \
        .compile().as_text()
    for name in ("flash_fwd", "flash_bwd"):
        assert name in text


# (seq, width, dtype, blocks; None: those a v5e's limit gives): GLM's
# shape; float32 at width 256, and at 512 where the budget binds (dK/dV
# at 512 x 1,024, the one-kernel backward at 512 x 512); the 128 x 512
# the kernels had
V5E_LIMIT = 64 * 2**20
WORKING_SETS = {
    "bf16_w256": (4096, 256, jnp.bfloat16, None),
    "f32_w256": (2048, 256, jnp.float32, None),
    "f32_w512": (2048, 512, jnp.float32, None),
    "bf16_w64_128x512": (2048, 64, jnp.bfloat16, (128, 512)),
    # two heads of 64 a 128-lane tile, counted at the tile's width; its
    # kernels are the forward and the one-kernel backward
    "bf16_paired_w64": (2048, 64, jnp.bfloat16, None),
}
PAIRED_SETS = {"bf16_paired_w64"}


@pytest.mark.parametrize("case,kind", [
    (case, kind) for case in sorted(WORKING_SETS)
    for kind in ["fwd", "dq", "dkv", "bwd"]
    if case not in PAIRED_SETS or kind in ("fwd", "bwd")])
def test_working_set_bounds_what_the_compiler_needs(one_chip, monkeypatch,
                                                    case, kind):
    """``_working_set_bytes`` against the compiler: given exactly the
    bytes the arithmetic counts as its scoped-VMEM limit (no quarter to
    spare), the chip's compiler takes the kernel. Sixteen heads, so no
    operand is small enough for XLA to keep it in VMEM whole."""
    seq, hd, dtype, blocks = WORKING_SETS[case]
    itemsize = jnp.dtype(dtype).itemsize
    paired = case in PAIRED_SETS
    width, counted_as = (128, kind + "_paired") if paired else (hd, kind)
    bq, bk = blocks or fa._pick_blocks(seq, width, itemsize, counted_as,
                                       V5E_LIMIT)
    counted = fa._working_set_bytes(counted_as, bq, bk, width, itemsize, seq)
    monkeypatch.setattr(fa, "_vmem_limit_bytes", lambda: counted)
    x, row, sl = ((16, seq, hd), dtype), ((16, seq), jnp.float32), \
        ((16,), jnp.float32)
    if paired:
        # one batch row of sixteen heads: the tensors as the model holds
        # them, a per-key row a batch row, a float32 row a head
        x, krow = ((1, seq, 16 * hd), dtype), ((1, seq), jnp.float32)
    rule = (hd ** -0.5, True, bq, bk, False)
    if paired and kind == "fwd":
        fn = lambda q, k, v, s, kp, kn: fa._flash_fwd_paired_pallas(  # noqa: E731
            q, k, v, s, kp, kn, *rule)
        shapes = [x, x, x, sl, krow, krow]
    elif paired:
        fn = lambda q, k, v, do, out, lse, s, kp, kn: (  # noqa: E731
            fa._flash_bwd_paired_pallas(q, k, v, do, out, lse, s, kp, kn,
                                        *rule))
        shapes = [x, x, x, x, x, row, sl, krow, krow]
    elif kind == "fwd":
        fn = lambda q, k, v, s, kp, kn: fa._flash_fwd_pallas(  # noqa: E731
            q, k, v, s, kp, kn, *rule)
        shapes = [x, x, x, sl, row, row]
    else:
        kernel = {"dq": fa._flash_dq_pallas, "dkv": fa._flash_dkv_pallas,
                  "bwd": fa._flash_bwd_pallas}[kind]
        fn = lambda q, k, v, do, lse, delta, s, kp, kn: kernel(  # noqa: E731
            q, k, v, do, lse, delta, s, kp, kn, *rule)
        shapes = [x, x, x, x, row, row, sl, row, row]
    text = jax.jit(fn).lower(*_shapes(shapes, sharding=one_chip)) \
        .compile().as_text()
    assert f"flash_{kind}" in text


@pytest.mark.parametrize("cell", sorted(CE_CELLS))
def test_fused_ce_backward_is_one_kernel_at_the_cells_shapes(
        one_chip, as_default_device, cell):
    """Forward and backward of the head at a train cell's own shape: the
    backward is ONE instruction named ``fused_ce_bwd`` (the name
    ``fused_ce_bwd_roofline.train`` finds it by), the super-block the
    picker chose fits the scoped VMEM the kernel asks for, and the
    carried float32 ``dw`` is the only buffer of its size among the
    program's temporaries (the kernel reads and writes the one result,
    there is no second copy to add into)."""
    t, v, hidden, valid, block_v = CE_CELLS[cell]
    fn, shapes = _fused_ce(True, t, v, hidden, valid, block_v)
    compiled = jax.jit(fn).lower(*_shapes(shapes, sharding=one_chip)) \
        .compile()
    called = [ln.split(" = ")[0] for ln in compiled.as_text().splitlines()
              if " custom-call(" in ln and "tpu_custom_call" in ln]
    assert len(called) == 2, called
    assert sum("fused_ce_fwd" in c for c in called) == 1
    assert sum("fused_ce_bwd" in c for c in called) == 1
    limit = fa._vmem_limit_bytes()
    ni, n_super = fused_ce._pick_super_block(t, 256, block_v, hidden, 2,
                                             limit)
    assert n_super > 1 and ni * n_super * 256 == t
    assert fused_ce._bwd_working_set_bytes(
        ni * 256, 256, block_v, hidden, 2) <= limit * 3 // 4
    carried = v * hidden * 4
    assert carried <= compiled.memory_analysis().temp_size_in_bytes \
        < 2 * carried


@pytest.mark.parametrize("cell", sorted(CE_CELLS))
def test_backward_working_set_bounds_what_the_compiler_needs(
        one_chip, monkeypatch, cell):
    """``_bwd_working_set_bytes`` against the compiler: given exactly
    the bytes the arithmetic counts as its scoped-VMEM limit, the chip's
    compiler takes the kernel at the super-block a v5e's limit gives."""
    t, v, hidden, valid, block_v = CE_CELLS[cell]
    plan = fused_ce._pick_super_block(t, 256, block_v, hidden, 2, V5E_LIMIT)
    counted = fused_ce._bwd_working_set_bytes(plan[0] * 256, 256, block_v,
                                              hidden, 2)
    # the super-block planned for a v5e, no quarter to spare
    monkeypatch.setattr(fused_ce, "_pick_super_block", lambda *a: plan)
    monkeypatch.setattr(fa, "_vmem_limit_bytes", lambda: counted)
    row = ((t,), jnp.float32)

    def fn(h, w, tgt, lse, g, off):
        return fused_ce._bwd_pallas(h, w, tgt, lse, g, off, valid, 256,
                                    block_v, False, True)

    shapes = [((t, hidden), jnp.bfloat16), ((v, hidden), jnp.bfloat16),
              ((t,), jnp.int32), row, row, ((1,), jnp.int32)]
    text = jax.jit(fn).lower(*_shapes(shapes, sharding=one_chip)) \
        .compile().as_text()
    assert "fused_ce_bwd" in text


@pytest.mark.parametrize("cell", sorted(CE_CELLS))
def test_fused_ce_forward_is_one_kernel_at_the_cells_shapes(
        one_chip, as_default_device, cell):
    """The head's loss at a train cell's own shape, whatever tile the
    caller names for the backward: ONE instruction named
    ``fused_ce_fwd`` (the name ``fused_ce_roofline.train`` finds it by),
    at a plan of the kernel's own that fits the scoped VMEM it asks for,
    holds at least 1,024 tokens while the vocabulary is walked and takes
    under 8,000 grid steps; and the program holds no array of
    (tokens, rows) entries, the logits the kernel exists to spare."""
    t, v, hidden, valid, block_v = CE_CELLS[cell]
    fn, shapes = _fused_ce(False, t, v, hidden, valid, block_v)
    compiled = jax.jit(fn).lower(*_shapes(shapes, sharding=one_chip)) \
        .compile()
    text = compiled.as_text()
    called = [ln.split(" = ")[0] for ln in text.splitlines()
              if " custom-call(" in ln and "tpu_custom_call" in ln]
    assert len(called) == 1 and "fused_ce_fwd" in called[0], called
    limit = fa._vmem_limit_bytes()
    bt, ni, n_super, bv = fused_ce._pick_fwd_plan(t, v, hidden, 2, limit)
    assert v % bv == 0 and ni * n_super * bt == t
    assert ni * bt >= 1024 and n_super * (v // bv) <= 8000
    assert fused_ce._fwd_working_set_bytes(
        ni * bt, bt, bv, hidden, 2) <= limit * 3 // 4
    assert not re.search(rf"\[{t},{v}\]|\[{v},{t}\]", text)
    assert compiled.memory_analysis().temp_size_in_bytes < t * v


@pytest.mark.parametrize("cell", sorted(CE_CELLS))
def test_forward_working_set_bounds_what_the_compiler_needs(
        one_chip, monkeypatch, cell):
    """``_fwd_working_set_bytes`` against the compiler: given exactly
    the bytes the arithmetic counts as its scoped-VMEM limit, the chip's
    compiler takes the kernel at the plan a v5e's limit gives."""
    t, v, hidden, valid, _ = CE_CELLS[cell]
    plan = fused_ce._pick_fwd_plan(t, v, hidden, 2, V5E_LIMIT)
    bt, ni, _, bv = plan
    counted = fused_ce._fwd_working_set_bytes(ni * bt, bt, bv, hidden, 2)
    # the plan made for a v5e, no quarter to spare
    monkeypatch.setattr(fused_ce, "_pick_fwd_plan", lambda *a: plan)
    monkeypatch.setattr(fa, "_vmem_limit_bytes", lambda: counted)

    def fn(h, w, tgt, off):
        return fused_ce._fwd_pallas(h, w, tgt, off, valid, False, True)

    shapes = [((t, hidden), jnp.bfloat16), ((v, hidden), jnp.bfloat16),
              ((t,), jnp.int32), ((1,), jnp.int32)]
    text = jax.jit(fn).lower(*_shapes(shapes, sharding=one_chip)) \
        .compile().as_text()
    assert "fused_ce_fwd" in text


def test_train_step_holds_one_backward_kernel_and_none_of_the_old(
        monkeypatch):
    """A model's loss and its gradient, lowered for the TPU without one:
    the head's kernels are ``fused_ce_fwd`` and ``fused_ce_bwd`` alone
    (no ``fused_ce_dh`` / ``fused_ce_dw``)."""
    monkeypatch.setattr(fused_ce, "_resolve_interpret", lambda interpret: False)
    cfg = bloom.BloomConfig(vocab_size=1024, hidden_size=128, n_layer=1,
                            n_head=2, fused_ce=True, dtype=jnp.bfloat16)
    params = jax.eval_shape(lambda: bloom.init_params(
        cfg, jax.random.PRNGKey(0)))
    ids = jax.ShapeDtypeStruct((2, 129), jnp.int32)
    step = jax.grad(lambda p, i: bloom.loss_fn(p, i, None, i, cfg))
    text = jax.jit(step).trace(params, ids).lower(
        lowering_platforms=("tpu",)).as_text()
    assert sorted(re.findall(r'kernel_name = "([^"]*)"', text)) == [
        "fused_ce_bwd", "fused_ce_fwd"]


@pytest.mark.parametrize("case", sorted(CASES))
def test_lowered_kernel_holds_its_name(case):
    """Lowered for the TPU without one (no topology, nothing compiled):
    every kernel of the case is a ``tpu_custom_call`` under its name."""
    fn, shapes = CASES[case]()
    text = jax.jit(fn).trace(*_shapes(shapes)).lower(
        lowering_platforms=("tpu",)).as_text()
    found = set(re.findall(r'kernel_name = "([^"]*)"', text))
    assert found == set(NO_DEVICE_KERNELS.get(case, KERNELS[case]))


# -- the serving engine's pool programs, for their structure ------------------
#
# The pool is 3.2 GB in the benchmark's serving cell and every program
# that touches it is handed it donated. Stored (.., nh, hd) with 64-wide
# heads the compiler kept the PAGES in the lanes: the decode step
# re-laid out two planes a layer and copied the pool once a call, the
# page write copied it four times (PERF.md, PR 27). The mechanism
# engages in the compiled program or not at all, so this is its counter.

POOL_HEADS = {"16x64": (16, 64), "16x128": (16, 128)}    # bloom-560m, -1b7
# 320 pages: a plane's element count is no weight's (those are powers of
# two and three times them), so a plane is known by its size
POOL_L, POOL_PAGES, POOL_SLOTS, POOL_CONTEXT, POOL_BUCKET = 2, 320, 2, 256, 64
POOL_PROGRAMS = ("step", "write", "chunk", "copy", "import")


def _pool_program(one_chip, heads, program, context=POOL_CONTEXT,
                  chunk_tokens=PS):
    """(lowered program, one bank's bytes) of a default engine at small
    depth and pool: 2 slots x 16 table entries reach 32 of 320 pages, so
    nothing a program may legitimately gather is as large as a plane."""
    nh, hd = POOL_HEADS[heads]
    cfg = bloom.BloomConfig(vocab_size=512, hidden_size=nh * hd,
                            n_layer=POOL_L, n_head=nh, dtype=jnp.bfloat16)

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    def vec(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    params = jax.tree_util.tree_map(sds, jax.eval_shape(
        lambda k: bloom.init_params(cfg, k), jax.random.PRNGKey(0)))
    eng = ServingEngine(params, cfg, num_slots=POOL_SLOTS,
                        num_pages=POOL_PAGES, page_size=PS,
                        max_context=context)
    kp, vp = sds(eng.k_pages), sds(eng.v_pages)
    assert kp.shape == (POOL_L, POOL_PAGES, PS, nh * hd)
    slots, width = eng.num_slots, eng.table_width
    if program == "step":
        low = eng._step.lower(params, vec(eng._carry_size), kp, vp)
    elif program == "chunk":
        low = eng._chunk.lower(params, vec(slots, chunk_tokens), kp, vp,
                               vec(slots, width), vec(slots), vec(slots))
    elif program == "write":
        cache = jax.tree_util.tree_map(sds, jax.eval_shape(
            eng._prefill, params, vec(1, POOL_BUCKET), vec(1, POOL_BUCKET))[1])
        low = eng._write.lower(kp, vp, cache, vec(width), vec())
    elif program == "copy":
        low = eng._copy.lower(kp, vp, vec(), vec())
    else:
        slab = jax.ShapeDtypeStruct((POOL_L, 4, PS, nh, hd), jnp.bfloat16,
                                    sharding=one_chip)
        low = jax.jit(import_page_slab, donate_argnums=0).lower(
            kp, slab, vec(4))
    return low, kp.size * kp.dtype.itemsize


def _carry_bytes(context):
    """A decode step's packed inputs (tokens, lengths, table), which it is
    handed donated and hands back as the next step's: int32, in whole
    tiles of 512 bytes."""
    return -(-4 * POOL_SLOTS * (2 + context // PS) // 512) * 512


@pytest.mark.parametrize("heads", sorted(POOL_HEADS))
@pytest.mark.parametrize("program", POOL_PROGRAMS)
def test_pool_program_updates_the_pool_in_place(one_chip, program, heads):
    low, bank_bytes = _pool_program(one_chip, heads, program)
    compiled = low.compile()
    banks = 1 if program == "import" else 2
    ma = compiled.memory_analysis()
    # every bank handed in is the bank handed back (and, beside them, the
    # decode step's packed inputs come back as the next step's: a tile) ...
    carried = _carry_bytes(POOL_CONTEXT) if program == "step" else 0
    assert ma.alias_size_in_bytes == banks * bank_bytes + carried
    # ... no second one is built beside it ...
    assert ma.temp_size_in_bytes < bank_bytes, ma.temp_size_in_bytes
    # ... and none, nor one layer's plane of it, is copied or re-laid out
    plane = bank_bytes // 2 // POOL_L            # elements of a plane
    moved = []
    for m in re.finditer(r"= \w+\[([\d,]+)\]\S* (copy|transpose)\(",
                         compiled.as_text()):
        elements = 1
        for d in m.group(1).split(","):
            elements *= int(d)
        if elements % plane == 0:
            moved.append(m.group(0))
    assert not moved, moved


# The decode read (PERF.md, PR 32). Until PR 32 the step gathered the
# table's full width, widened the view to float32 and split it into
# (.., nh, hd): 36 GB moved a step for ~1,200 live tokens. The read now
# walks the table a chunk of pages at a time and contracts the rows as
# stored; like the layout above, that holds in the compiled program or
# not at all.

# 80 table entries, five chunks of 256 keys: neither the view's element
# count nor a chunk's is a weight's, nor (at 8 tokens a prefill chunk)
# the (slots, tokens x nh, nh x hd) float32 accumulator's
READ_CONTEXT, READ_TOKENS = 1280, 8


@pytest.mark.parametrize("heads", sorted(POOL_HEADS))
@pytest.mark.parametrize("program", ["step", "chunk"])
def test_decode_read_keeps_the_rows_as_stored(one_chip, program, heads):
    """No array of the compiled program has the full gathered view's
    element count (slots x table width x page size x nh x hd), in any
    dtype (the walk gathers a chunk), and none is float32 or split into
    (.., nh, hd) at the size of a chunk either; the pool still rides in
    place and the walk is a loop inside the layer loop."""
    nh, hd = POOL_HEADS[heads]
    low, bank_bytes = _pool_program(one_chip, heads, program,
                                    context=READ_CONTEXT,
                                    chunk_tokens=READ_TOKENS)
    compiled = low.compile()
    carried = _carry_bytes(READ_CONTEXT) if program == "step" else 0
    assert compiled.memory_analysis().alias_size_in_bytes == \
        2 * bank_bytes + carried
    text = compiled.as_text()
    view = POOL_SLOTS * READ_CONTEXT * nh * hd
    chunk = POOL_SLOTS * kv_pool.WALK_KEYS * nh * hd
    assert view == 5 * chunk
    widened, split, whole = [], [], []
    for m in re.finditer(r"= \(?(\w+)\[([\d,]+)\]", text):
        dims = [int(d) for d in m.group(2).split(",")]
        elements = math.prod(dims)
        if elements == view:
            whole.append(m.group(0))
        if elements in (chunk, view):
            if m.group(1) == "f32":
                widened.append(m.group(0))
            if dims[-2:] == [nh, hd]:
                split.append(m.group(0))
    assert not whole, whole[:3]
    assert not widened, widened[:3]
    assert not split, split[:3]
    # a gather of one chunk's rows exists, in the pool's dtype
    assert re.search(r"bf16\[(\d+,)*%d\]\S* (fusion|gather)\(" % (nh * hd),
                     text)
    assert text.count(" while(") == 2


# The state bank (PERF.md, PR 39): a model whose blocks keep a state a
# slot (Falcon-H1's recurrence: 4.2 MB a block a slot at the published
# widths, 1.6 GB over 64 slots and six blocks) hands it to the decode
# step and to the page write donated, beside the pool. The step reads
# and overwrites a few slots' rows a trip; the write puts one slot's
# rows. Like the pool's layout, that holds in the compiled program or
# not at all.

# 24 slots, 3 trips of 8: a block's plane of the bank (24 x 4 x 32 x 256
# float32: whole (8, 128) tiles, as the published 128 x 256 is; 3.1 MB,
# over anything else the step holds) has no weight's element count, nor
# has a trip's rows
BANK_L, BANK_SLOTS, BANK_HEADS, BANK_HEAD, BANK_STATE = 2, 24, 4, 32, 256


@pytest.mark.parametrize("program", ["step", "write"])
def test_state_bank_is_updated_in_place(one_chip, program):
    """The compiled program aliases pool AND bank, holds no temporary of
    a block's plane of the bank, copies or re-lays out none, and (the
    step) walks the bank's slots in a loop inside the layer loop, beside
    the attention's walk over the keys."""
    cfg = falcon_h1.FalconH1Config(
        vocab_size=512, hidden_size=256, intermediate_size=512,
        num_hidden_layers=BANK_L, num_attention_heads=4,
        num_key_value_heads=2, head_dim=128,
        mamba_d_ssm=BANK_HEADS * BANK_HEAD, mamba_n_heads=BANK_HEADS,
        mamba_d_head=BANK_HEAD, mamba_d_state=BANK_STATE, mamba_chunk_size=16,
        dtype=jnp.bfloat16)

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    def vec(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    params = jax.tree_util.tree_map(sds, jax.eval_shape(
        lambda k: falcon_h1.init_params(cfg, k), jax.random.PRNGKey(0)))
    eng = ServingEngine(params, cfg, num_slots=BANK_SLOTS,
                        num_pages=POOL_PAGES, page_size=PS,
                        max_context=POOL_CONTEXT)
    kp, vp = sds(eng.k_pages), sds(eng.v_pages)
    bank = jax.tree_util.tree_map(sds, eng.state)
    assert bank["ssm"].shape == (BANK_L, BANK_SLOTS, BANK_HEADS, BANK_HEAD,
                                 BANK_STATE)
    assert bank["ssm"].dtype == jnp.float32
    assert kv_pool.state_walk_plan(BANK_SLOTS) == (8, 3)
    slots, width = eng.num_slots, eng.table_width
    if program == "step":
        low = eng._step.lower(params, vec(eng._carry_size), kp, vp, bank)
    else:
        cache = jax.tree_util.tree_map(sds, jax.eval_shape(
            eng._prefill, params, vec(1, POOL_BUCKET), vec(1, POOL_BUCKET))[1])
        low = eng._write.lower(kp, vp, cache, vec(width), vec(), vec(), bank,
                               vec())
    compiled = low.compile()
    pool_bytes = 2 * kp.size * kp.dtype.itemsize
    bank_bytes = sum(x.size * x.dtype.itemsize for x in bank.values())
    ma = compiled.memory_analysis()
    # (the convolution's three inputs a slot lie in tiles of four)
    conv_bytes = bank["conv"].size * bank["conv"].dtype.itemsize
    assert 0 <= ma.alias_size_in_bytes - pool_bytes - bank_bytes \
        <= conv_bytes // 3
    plane = bank["ssm"].size // BANK_L           # elements of a block's plane
    assert ma.temp_size_in_bytes < plane * 4, ma.temp_size_in_bytes
    text = compiled.as_text()
    moved, held = [], []
    for m in re.finditer(r"= (\w+)\[([\d,]+)\]\S* ([\w\-]+)\(", text):
        elements = math.prod(int(d) for d in m.group(2).split(","))
        if m.group(1) != "f32":
            continue                    # the bank's planes are float32
        if elements % plane == 0 and m.group(3) in ("copy", "transpose"):
            moved.append(m.group(0))
        # nothing has a plane's size but the bank itself, passed along
        if elements == plane:
            held.append(m.group(0))
    assert not moved, moved
    assert not held, held[:3]
    if program == "step":
        # the layer loop, and inside it the keys' walk and the slots' walk
        assert text.count(" while(") == 3
        # a trip's rows are sliced out of the bank and put back into it
        trip = "f32[1,8,%d,%d,%d]" % (BANK_HEADS, BANK_HEAD, BANK_STATE)
        assert trip in text
        assert re.search(r"f32\[%d,%d,%d,%d,%d\]\S* dynamic-update-slice\("
                         % bank["ssm"].shape, text)


# The latent bank (PERF.md, PR 45): a model with latent attention keeps
# ONE row a token an attention (LongCat-Flash: 512 + 64 lanes, stored in
# five lane tiles) which all of its query heads read in the absorbed
# form; there is no bank of values. Like the pool's layout, that holds in
# the compiled program or not at all: at 576 stored lanes (4.5 tiles) the
# compiler put the pages in the lanes again and copied the pool around
# every step.

# 8 heads at the published head widths; 80 table entries, five chunks of
# 256 keys; 320 pages: a bank layer's plane has no weight's element
# count, nor (at 3 slots) have a chunk's keys or values a head
LATENT_L, LATENT_HEADS, LATENT_CONTEXT, LATENT_SLOTS = 2, 8, 1280, 3


def test_latent_step_reads_one_bank_and_forms_no_keys_or_values(one_chip):
    """The compiled decode step of a latent model aliases its ONE bank
    (and its carry), holds no temporary of a bank layer's plane, copies
    or re-lays out none, walks each attention's chunks in a loop of its
    own, gathers a chunk's rows at their stored width, and has no array
    shaped as keys (.., heads, 192) or values (.., heads, 128) a head at
    a chunk's size."""
    from pipegoose_tpu.models import longcat_flash

    nh = LATENT_HEADS
    cfg = longcat_flash.LongcatFlashConfig(
        vocab_size=512, hidden_size=256, ffn_hidden_size=512,
        expert_ffn_hidden_size=128, num_layers=LATENT_L,
        num_attention_heads=nh, kv_lora_rank=512, q_lora_rank=256,
        qk_rope_head_dim=64, qk_nope_head_dim=128, v_head_dim=128,
        n_routed_experts=8, zero_expert_num=4, moe_topk=3,
        experts_held=(0, 2), dtype=jnp.bfloat16)

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(sds, jax.eval_shape(
        lambda k: longcat_flash.init_params(cfg, k), jax.random.PRNGKey(0)))
    eng = ServingEngine(params, cfg, num_slots=LATENT_SLOTS,
                        num_pages=POOL_PAGES, page_size=PS,
                        max_context=LATENT_CONTEXT)
    assert eng.v_pages is None and eng.model.latent.lanes == 576
    bank = sds(eng.k_pages)
    # two attentions a block: a bank layer each, a row in five lane tiles
    assert bank.shape == (2 * LATENT_L, POOL_PAGES, PS, 640)
    carry = jax.ShapeDtypeStruct((eng._carry_size,), jnp.int32,
                                 sharding=one_chip)
    compiled = eng._step.lower(params, carry, bank).compile()
    bank_bytes = bank.size * bank.dtype.itemsize
    ma = compiled.memory_analysis()
    carried = -(-4 * eng._carry_size // 512) * 512
    assert ma.alias_size_in_bytes == bank_bytes + carried
    plane = bank.size // (2 * LATENT_L)          # elements of a bank layer
    assert ma.temp_size_in_bytes < plane * 2, ma.temp_size_in_bytes
    text = compiled.as_text()
    chunk = LATENT_SLOTS * kv_pool.WALK_KEYS
    moved, heads = [], []
    for m in re.finditer(r"= \(?(\w+)\[([\d,]+)\]\S* ([\w\-]+)\(", text):
        dims = [int(d) for d in m.group(2).split(",")]
        elements = math.prod(dims)
        if elements % plane == 0 and m.group(3) in ("copy", "transpose"):
            moved.append(m.group(0))
        # keys or values a head over a chunk's positions
        if elements in (chunk * nh * 192, chunk * nh * 128) and (
                dims[-2:] in ([nh, 192], [nh, 128])
                or dims[-1] in (192, 128)):
            heads.append(m.group(0))
    assert not moved, moved
    assert not heads, heads[:3]
    # a chunk's rows are gathered at the width they are stored in ...
    assert re.search(r"bf16\[(\d+,)*640\]\S* (fusion|gather)\(", text)
    # ... in a walk of its own an attention (the blocks are traced in line)
    assert text.count(" while(") == 2 * LATENT_L


# ONE attention over two caches (PERF.md, PR 47): a window layer that
# keeps a ring of exact keys and, in global pages, a pooled key and value
# a chunk of every closed window (EvaByte). The step writes the ring's
# row, pools the ring's page and writes ONE summary row, then walks ring
# and summaries under one softmax; the prefill runs EVA over the bucket's
# windows as rows and over the summaries as one more chunk of keys. Like
# the pool's layout, all of it holds in the compiled program or not at
# all.

# 4 heads at the published head width and window; 8,192 positions are
# four windows, 512 summaries: two chunks of the summaries' walk
EVA_L, EVA_HEADS, EVA_WINDOW, EVA_CONTEXT, EVA_SLOTS = 2, 4, 2048, 8192, 3


def _eva_engine(one_chip):
    from pipegoose_tpu.models import evabyte

    cfg = evabyte.EvaByteConfig(
        vocab_size=320, hidden_size=EVA_HEADS * 128, intermediate_size=1024,
        num_hidden_layers=EVA_L, num_attention_heads=EVA_HEADS,
        num_key_value_heads=EVA_HEADS, window_size=EVA_WINDOW, chunk_size=PS,
        use_flash=True, ffn_block_tokens=2048, dtype=jnp.bfloat16)
    params = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        jax.eval_shape(lambda k: evabyte.init_params(cfg, k),
                       jax.random.PRNGKey(0)))
    return params, ServingEngine(
        params, cfg, num_slots=EVA_SLOTS, num_pages=POOL_PAGES, page_size=PS,
        max_context=EVA_CONTEXT)


def test_summarised_step_updates_both_banks_in_place(one_chip):
    """The compiled decode step of a model whose attention reads a ring
    AND summaries aliases all four banks (keys and values of both kinds)
    and its carry, holds no temporary of a bank layer's plane, copies or
    re-lays out none, and walks the ring and the summaries in a loop
    each inside the one layer loop."""
    params, eng = _eva_engine(one_chip)

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    ring = EVA_WINDOW // PS                 # a block window's own pages
    kp = jax.tree_util.tree_map(sds, eng.k_pages)
    vp = jax.tree_util.tree_map(sds, eng.v_pages)
    assert {k: v.shape for k, v in kp.items()} == {
        "global": (EVA_L, POOL_PAGES, PS, EVA_HEADS * 128),
        "window": (EVA_L, EVA_SLOTS * ring + 1, PS, EVA_HEADS * 128)}
    # a global entry a 256 positions, the ring, a token and a length
    assert eng._carry_size == EVA_SLOTS * (2 + EVA_CONTEXT // 256 + ring)
    carry = jax.ShapeDtypeStruct((eng._carry_size,), jnp.int32,
                                 sharding=one_chip)
    compiled = eng._step.lower(params, carry, kp, vp).compile()
    bank_bytes = 2 * sum(x.size * x.dtype.itemsize for x in kp.values())
    ma = compiled.memory_analysis()
    carried = -(-4 * eng._carry_size // 512) * 512
    assert ma.alias_size_in_bytes == bank_bytes + carried
    planes = {x.size // EVA_L for x in kp.values()}
    assert ma.temp_size_in_bytes < min(planes) * 2, ma.temp_size_in_bytes
    text = compiled.as_text()
    moved = []
    for m in re.finditer(r"= \(?(\w+)\[([\d,]+)\]\S* (copy|transpose)\(",
                         text):
        elements = math.prod(int(d) for d in m.group(2).split(","))
        if any(elements % plane == 0 for plane in planes):
            moved.append(m.group(0))
    assert not moved, moved
    # the layer loop, and inside it the ring's walk and the summaries'
    assert text.count(" while(") == 3
    # a chunk's rows are gathered at the width they are stored in
    assert re.search(r"bf16\[(\d+,)*%d\]\S* (fusion|gather)\("
                     % (EVA_HEADS * 128), text)


def test_eva_prefill_compiles_its_kernels_and_forms_no_square_of_scores(
        one_chip, monkeypatch):
    """The prefill of a 4,096-byte bucket (two windows) for the described
    chip, the flash kernels compiled and not interpreted: the window
    part and the summary part are a kernel each, and no array is (..,
    4096, 4096) or has as many elements as heads x 4,096 x 4,096."""
    monkeypatch.setattr(fa, "_resolve_interpret", lambda interpret: False)
    params, eng = _eva_engine(one_chip)
    s = 2 * EVA_WINDOW
    ids = jax.ShapeDtypeStruct((1, s), jnp.int32, sharding=one_chip)
    # the kernels ask the default device for its VMEM while traced (the
    # engine's own buffers are made before: they are real arrays)
    with jax.default_device(next(iter(one_chip.device_set))):
        low = eng._prefill.lower(params, ids, ids)
    lowered = low.as_text()
    assert "flash_fwd" in lowered and "flash_ring_fwd" in lowered
    text = low.compile().as_text()
    assert text.count("tpu_custom_call") >= 2
    square = []
    for m in re.finditer(r"= \(?(\w+)\[([\d,]+)\]", text):
        dims = [int(d) for d in m.group(2).split(",")]
        if dims[-2:] == [s, s] or math.prod(dims) >= EVA_HEADS * s * s:
            square.append(m.group(0))
    assert not square, square[:3]
    cache = jax.eval_shape(eng._prefill, params, ids, ids)[1]
    # what the ring must hold, and a summary a chunk of the bucket
    assert cache["window"]["k"].shape == (EVA_L, 1, EVA_WINDOW, EVA_HEADS, 128)
    assert cache["global"]["k"].shape == (EVA_L, 1, s // PS, EVA_HEADS, 128)


# -- a router before attention, both ring states in a step (PERF.md, PR 52) ----
#
# SmallThinker's decode step routes from the attention's input, walks a
# ring of 257 pages on its window layers and every page on its global
# ones; its 8,192-token prefill is flash attention at a group of 7 with
# the 4,096-key window. As the pool's layout, all of it holds in the
# compiled program or not at all.

# the published heads (28 over 4 of 128), window and expert width; the
# hidden size, the experts' count and the depth (one layer a kind) cut
THINK_SLOTS, THINK_WINDOW, THINK_CONTEXT = 2, 4096, 8192


def _think_engine(one_chip):
    from pipegoose_tpu.models import smallthinker

    cfg = smallthinker.SmallThinkerConfig(
        vocab_size=512, hidden_size=512, num_hidden_layers=2,
        moe_num_primary_experts=8, rope_layout=(0, 1),
        sliding_window_layout=(0, 1), sliding_window_size=THINK_WINDOW,
        use_flash=True, moe_block_tokens=2048, dtype=jnp.bfloat16)
    params = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        jax.eval_shape(lambda k: smallthinker.init_params(cfg, k),
                       jax.random.PRNGKey(0)))
    return params, ServingEngine(
        params, cfg, num_slots=THINK_SLOTS, num_pages=POOL_PAGES,
        page_size=PS, max_context=THINK_CONTEXT)


def test_think_step_updates_both_kinds_banks_in_place(one_chip):
    """The compiled decode step aliases all four banks (keys and values
    of both kinds) and its carry, holds no temporary of a bank layer's
    plane, and copies or re-lays out none."""
    params, eng = _think_engine(one_chip)

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    ring = THINK_WINDOW // PS + 1
    kp = jax.tree_util.tree_map(sds, eng.k_pages)
    vp = jax.tree_util.tree_map(sds, eng.v_pages)
    assert {k: v.shape for k, v in kp.items()} == {
        "global": (1, POOL_PAGES, PS, 4 * 128),
        "window": (1, THINK_SLOTS * ring + 1, PS, 4 * 128)}
    assert eng._carry_size == THINK_SLOTS * (2 + THINK_CONTEXT // PS + ring)
    carry = jax.ShapeDtypeStruct((eng._carry_size,), jnp.int32,
                                 sharding=one_chip)
    compiled = eng._step.lower(params, carry, kp, vp).compile()
    bank_bytes = 2 * sum(x.size * x.dtype.itemsize for x in kp.values())
    ma = compiled.memory_analysis()
    # 1,542 int32: over a tile, so whole tiles of 1,024 elements
    carried = -(-eng._carry_size // 1024) * 4096
    assert ma.alias_size_in_bytes == bank_bytes + carried
    planes = {x.size for x in kp.values()}       # a layer a kind
    assert ma.temp_size_in_bytes < min(planes) * 2, ma.temp_size_in_bytes
    moved = []
    for m in re.finditer(r"= \(?(\w+)\[([\d,]+)\]\S* (copy|transpose)\(",
                         compiled.as_text()):
        elements = math.prod(int(d) for d in m.group(2).split(","))
        if any(elements % plane == 0 for plane in planes):
            moved.append(m.group(0))
    assert not moved, moved


def test_think_prefill_compiles_flash_at_g7_and_forms_no_square_of_scores(
        one_chip, monkeypatch):
    """The prefill of an 8,192-token bucket for the described chip, the
    flash kernel compiled by Mosaic and not interpreted, a call a layer
    (the global one causal, the window one under its 4,096 keys): no
    array is (.., 8192, 8192) or has as many elements as heads x 8,192 x
    8,192."""
    monkeypatch.setattr(fa, "_resolve_interpret", lambda interpret: False)
    params, eng = _think_engine(one_chip)
    s = THINK_CONTEXT
    ids = jax.ShapeDtypeStruct((1, s), jnp.int32, sharding=one_chip)
    with jax.default_device(next(iter(one_chip.device_set))):
        low = eng._prefill.lower(params, ids, ids)
    assert low.as_text().count('kernel_name = "flash_fwd"') == 2
    text = low.compile().as_text()
    called = [ln for ln in text.splitlines()
              if " custom-call(" in ln and "tpu_custom_call" in ln
              and "flash_fwd" in ln.split(" = ")[0]]
    assert len(called) == 2
    # the result the roofline reader tells a call by: heads, length, width
    assert all("bf16[28,8192,128]" in ln.split(" = ")[1] for ln in called)
    square = []
    for m in re.finditer(r"= \(?(\w+)\[([\d,]+)\]", text):
        dims = [int(d) for d in m.group(2).split(",")]
        if dims[-2:] == [s, s] or math.prod(dims) >= 28 * s * s:
            square.append(m.group(0))
    assert not square, square[:3]
    cache = jax.eval_shape(eng._prefill, params, ids, ids)[1]
    assert cache["global"]["k"].shape == cache["window"]["k"].shape \
        == (1, 1, s, 4, 128)
