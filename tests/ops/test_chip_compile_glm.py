"""What GLM-4.7-Flash's cell asks of the chip's compiler, compiled for
a described (not attached) TPU v5e at the cell's sizes: the flash
kernels (forward, and the one backward) at 4 rows x 20 heads x 256 x 4,096, the fused cross entropy at hidden
2,048 over 19,456 padded rows (19,360 valid), and the expert layer's
grouped products (``lax.ragged_dot`` forward, dx and dw at 65,536 static
rows, 8 experts of 2,048 x 1,536). Nothing runs; times are the chip's
(PERF.md). Same pattern as ``test_chip_compile.py``: the topology is
described inside a fixture of this file only.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from pipegoose_tpu.nn.expert_parallel import swiglu_grouped
from pipegoose_tpu.ops.flash_attention import flash_attention
from pipegoose_tpu.ops.fused_ce import fused_ce_sums

ROWS, S, NH, HD, H, F = 4, 4096, 20, 256, 2048, 1536
TOKENS, PICKS, HELD = 16384, 4, 8
V_PADDED, V_VALID = 19456, 19360


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


def _flash():
    qkv = [((ROWS, S, NH, HD), jnp.bfloat16)] * 3

    def loss(q, k, v):
        out = flash_attention(q, k, v, scale=HD ** -0.5, interpret=False)
        return out.astype(jnp.float32).sum()

    return jax.grad(loss, argnums=(0, 1, 2)), qkv, (
        "flash_fwd", "flash_bwd")


def _fused_ce():
    # the head as the step passes it: twice on ONE weight (main head and
    # MTP module), each pass its own forward and backward kernel
    shapes = [((TOKENS, H), jnp.bfloat16), ((TOKENS, H), jnp.bfloat16),
              ((V_PADDED, H), jnp.bfloat16),
              ((TOKENS,), jnp.int32), ((TOKENS,), jnp.float32)]

    def loss(h, h_mtp, w, tgt, tw):
        def one(x):
            tot, cnt = fused_ce_sums(x, w, tgt, tw, None, V_VALID,
                                     block_v=256, interpret=False)
            return tot / cnt
        return one(h) + 0.3 * one(h_mtp)

    return jax.grad(loss, argnums=(0, 1, 2)), shapes, (
        "fused_ce_fwd", "fused_ce_bwd")


def _grouped():
    kernel = {"kernel": None}
    shapes = [((TOKENS * PICKS, H), jnp.bfloat16),
              ((HELD, H, F), jnp.bfloat16), ((HELD, H, F), jnp.bfloat16),
              ((HELD, F, H), jnp.bfloat16), ((HELD,), jnp.int32)]

    def loss(rows, gate, up, down, sizes):
        ep = {"gate": dict(kernel, kernel=gate), "up": dict(kernel, kernel=up),
              "down": dict(kernel, kernel=down)}
        return swiglu_grouped(ep, rows, sizes).astype(jnp.float32).sum()

    return jax.grad(loss, argnums=(0, 1, 2, 3)), shapes, ("ragged-dot",)


def _kernel_calls(text):
    """The compiled program's lines that call a Pallas kernel."""
    return [ln for ln in text.splitlines()
            if " custom-call(" in ln and "tpu_custom_call" in ln]


CASES = {"flash_256": _flash, "fused_ce_padded": _fused_ce,
         "grouped_products": _grouped}


@pytest.mark.parametrize("case", sorted(CASES))
def test_compiles_for_v5e_at_the_cells_sizes(one_chip, case):
    fn, shapes, names = CASES[case]()
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    # the described chip as the default device while the case is traced:
    # the flash kernels ask that device for its VMEM
    with jax.default_device(next(iter(one_chip.device_set))):
        compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    for name in names:
        assert name in text, f"{name} is not in the compiled program"
    if case == "fused_ce_padded":
        # one backward kernel a head pass, each dlogits tile formed once
        called = [ln.split(" = ")[0] for ln in _kernel_calls(text)]
        assert sorted(c.count("fused_ce_bwd") for c in called) == [0, 0, 1, 1]
        assert sorted(c.count("fused_ce_fwd") for c in called) == [0, 0, 1, 1]
        assert "fused_ce_dh" not in text and "fused_ce_dw" not in text
    if case == "flash_256":
        # one backward kernel, no pair; by their result shapes the
        # accepted roofline reader takes ``flash_bwd`` (dQ, dK, dV) for
        # a dK/dV call and the forward for the forward
        import os

        from benchmark import harness

        classify = harness.load_module(os.path.join(
            os.path.dirname(harness.__file__), "layer_metrics",
            "flash_attn_roofline.train.py")).classify
        called = _kernel_calls(text)
        assert len(called) == 2, called
        kinds = {classify(ln.strip(), (ROWS * NH, S, HD)): ln.split(" = ")[0]
                 for ln in called}
        assert sorted(kinds) == ["dkv", "fwd"], kinds
        assert "flash_fwd" in kinds["fwd"] and "flash_bwd" in kinds["dkv"]
        assert "flash_dq" not in text and "flash_dkv" not in text
    if case == "grouped_products":
        # the chip's own grouped kernel, forward, dx and dw: no dense
        # product over every expert and no loop over the groups
        assert text.count("ragged-dot-metadata") >= 1
        assert " while(" not in text
