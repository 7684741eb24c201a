"""The Pallas kernels of the bloom-560m path, compiled for a described
(not attached) TPU v5e at real widths. Nothing runs: the chip's compiler
accepts or refuses each kernel, which interpret-mode tests cannot show
(tile alignment, Mosaic legalization, VMEM). Results and times come from
``chip_smoke.py`` on the chip.

The topology is described inside a fixture of THIS file only: the worker
that runs the file loads the TPU library, every other worker never does.
"""
import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from pipegoose_tpu.nn.sequence_parallel.ring_attention import (
    ring_flash_attention,
)
from pipegoose_tpu.ops.flash_attention import flash_attention
from pipegoose_tpu.ops.fused_ce import fused_ce_sums
from pipegoose_tpu.ops.paged_attention import paged_attention
from pipegoose_tpu.quant.matmul import quantized_matmul

# bloom-560m: hidden 1024, 16 heads x 64, padded vocab 250880; train
# b8 x s1024, decode 8 slots over a 16-row-page pool.
B, S, NH, HD, H, V = 8, 1024, 16, 64, 1024, 250880
PS, W, PAGES = 16, 64, 4096


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


def _flash(grad):
    qkv = [((B, S, NH, HD), jnp.bfloat16)] * 3
    slopes = ((NH,), jnp.float32)

    def fwd(q, k, v, sl):
        return flash_attention(q, k, v, alibi_slopes=sl, interpret=False)

    if not grad:
        return fwd, qkv + [slopes]

    def loss(q, k, v, sl):
        return fwd(q, k, v, sl).astype(jnp.float32).sum()

    return jax.grad(loss, argnums=(0, 1, 2)), qkv + [slopes]


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


def _fused_ce(grad):
    t = B * S
    shapes = [((t, H), jnp.bfloat16), ((V, H), jnp.bfloat16),
              ((t,), jnp.int32), ((t,), jnp.float32)]

    def loss(h, w, tgt, tw):
        tot, cnt = fused_ce_sums(h, w, tgt, tw, interpret=False)
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


def _paged(quantized, ps=PS, c=1, nh=NH, hd=HD):
    w = W * PS // ps
    if quantized:
        bank = {"q": ((PAGES, ps, nh, hd), jnp.int8),
                "scale": ((PAGES, ps, nh), jnp.float32)}
    else:
        bank = ((PAGES, ps, nh, hd), jnp.bfloat16)

    def fn(q, kp, vp, pt, start, sl):
        return paged_attention(q, kp, vp, pt, start, slopes=sl,
                               interpret=False)

    return fn, [((B, c, nh, hd), jnp.bfloat16), bank, bank,
                ((B, w), jnp.int32), ((B,), jnp.int32), ((nh,), jnp.float32)]


CASES = {
    "flash_fwd": lambda: _flash(False),
    "flash_fwd_bwd": lambda: _flash(True),
    "ring_chunk": _ring_chunk,
    "ring_chunk_bwd": lambda: _ring_chunk(True),
    "fused_ce_fwd": lambda: _fused_ce(False),
    "fused_ce_bwd": lambda: _fused_ce(True),
    "matmul_int8": lambda: _quant_matmul(False),
    "matmul_int4": lambda: _quant_matmul(True),
    "paged_fp": lambda: _paged(False),
    "paged_int8": lambda: _paged(True),
    "paged_int8_ps32": lambda: _paged(True, ps=32),
    "paged_fp_chunk": lambda: _paged(False, c=16),
    # what one device of a tp=2 engine sees
    "paged_int8_tp2_local": lambda: _paged(True, nh=NH // 2),
    # head slabs narrower or wider than the 128 lanes they are cut at:
    # an odd local head count at hd 64 (one head per 64-lane slab), and
    # a head_dim that does not divide 128
    "paged_fp_odd_heads": lambda: _paged(False, nh=3),
    "paged_int8_hd96": lambda: _paged(True, nh=4, hd=96),
}


# the kernels each case runs, by the ``name=`` of their ``pallas_call``:
# the name the chip's trace prints for the kernel's ``XLA Ops`` events
KERNELS = {
    "flash_fwd": ["flash_fwd"],
    "flash_fwd_bwd": ["flash_fwd", "flash_dq", "flash_dkv"],
    "ring_chunk": ["flash_ring_fwd"],
    "ring_chunk_bwd": ["flash_ring_fwd", "flash_ring_dq", "flash_ring_dkv"],
    "fused_ce_fwd": ["fused_ce_fwd"],
    "fused_ce_bwd": ["fused_ce_fwd", "fused_ce_dh", "fused_ce_dw"],
    "matmul_int8": ["int8_matmul"],
    "matmul_int4": ["int4_matmul"],
}


def _shapes(shapes, **kw):
    return jax.tree_util.tree_map(
        lambda sd: jax.ShapeDtypeStruct(sd[0], sd[1], **kw),
        shapes, is_leaf=lambda x: isinstance(x, tuple),
    )


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, case):
    fn, shapes = CASES[case]()
    text = jax.jit(fn).lower(*_shapes(shapes, sharding=one_chip)) \
        .compile().as_text()
    assert "tpu_custom_call" in text
    # the compiled instruction, which is the trace's event, is named
    # after the kernel; jax wraps the name in the transforms it went
    # through (``%transpose_jvp_flash_dkv__.1``), so a reader searches
    called = [ln.split(" = ")[0] for ln in text.splitlines()
              if " custom-call(" in ln and "tpu_custom_call" in ln]
    for name in KERNELS.get(case, ["paged_attention"]):
        assert any(name in instruction for instruction in called), \
            (name, called)


@pytest.mark.parametrize("case", sorted(CASES))
def test_lowered_kernel_holds_its_name(case):
    """Lowered for the TPU without one (no topology, nothing compiled):
    every kernel of the case is a ``tpu_custom_call`` under its name."""
    fn, shapes = CASES[case]()
    text = jax.jit(fn).trace(*_shapes(shapes)).lower(
        lowering_platforms=("tpu",)).as_text()
    found = set(re.findall(r'kernel_name = "([^"]*)"', text))
    assert found == set(KERNELS.get(case, ["paged_attention"]))
