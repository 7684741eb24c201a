"""Weight-quantization contracts: the round-trip error every serving
accuracy claim rests on, the pack/unpack nibble convention, target
selection (block kernels only — the embedding doubles as the lm head
and stays fp), the PartitionSpec derivation that keeps tp sharding
unchanged, and the byte census the doctor satellite reports. These are
the bounds; the engine-level parity pins live in
tests/serving/test_quantized.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from pipegoose_tpu.models import bloom
from pipegoose_tpu.quant import (
    QuantSpec,
    dequantize_params,
    dequantize_weight,
    quantize_param_specs,
    quantize_params,
    quantized_weight_bytes,
    unpack_int4,
)
from pipegoose_tpu.quant.weights import pack_int4, validate_tp_compat


@pytest.fixture(scope="module")
def tree():
    cfg = bloom.BloomConfig(vocab_size=64, hidden_size=64, n_layer=2,
                            n_head=4)
    return cfg, bloom.init_params(cfg, jax.random.PRNGKey(0))


# --- round-trip error bounds ------------------------------------------------


def test_int8_round_trip_elementwise_bound(tree):
    """Symmetric rounding error is at most half an int8 step of the
    per-out-channel scale — the bound the accuracy contract quotes."""
    _, params = tree
    qp = quantize_params(params, QuantSpec("int8"))
    for name in ("qkv", "out"):
        leaf = qp["blocks"]["attn"][name]
        deq = dequantize_weight(leaf["q"], leaf["scale"])
        err = jnp.abs(deq - params["blocks"]["attn"][name]["kernel"])
        bound = 0.5 * leaf["scale"][:, None, :] + 1e-7
        assert bool(jnp.all(err <= bound)), f"{name} exceeds scale/2"


def test_int4_round_trip_grouped_bound(tree):
    """int4 buckets are 16x coarser; the grouped scales keep the
    elementwise error at half a 4-bit step of the GROUP's scale."""
    _, params = tree
    g = 16
    qp = quantize_params(params, QuantSpec("int4", group_size=g))
    leaf = qp["blocks"]["mlp"]["up"]
    k = params["blocks"]["mlp"]["up"]["kernel"]
    deq = dequantize_weight(leaf["q"], leaf["scale"])
    err = jnp.abs(deq - k).reshape(k.shape[0], k.shape[1] // g, g, k.shape[2])
    bound = 0.5 * leaf["scale"][:, :, None, :] + 1e-7
    assert bool(jnp.all(err <= bound))


def test_int4_tighter_scales_beat_coarser_groups(tree):
    """Finer groups can only shrink the max-abs scales, hence the
    error — the knob's monotonicity."""
    _, params = tree
    k = params["blocks"]["mlp"]["up"]["kernel"]

    def max_err(g):
        leaf = quantize_params(params, QuantSpec("int4", g))
        leaf = leaf["blocks"]["mlp"]["up"]
        return float(jnp.max(jnp.abs(
            dequantize_weight(leaf["q"], leaf["scale"]) - k
        )))

    assert max_err(8) <= max_err(32) + 1e-7


# --- int4 packing -----------------------------------------------------------


def test_pack_unpack_int4_exact():
    rng = np.random.RandomState(0)
    q4 = jnp.asarray(rng.randint(-8, 8, (3, 10, 5)), jnp.int8)
    packed = pack_int4(q4)
    assert packed.shape == (3, 5, 5) and packed.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(unpack_int4(packed)),
                                  np.asarray(q4))


def test_pack_int4_rejects_odd_contraction_dim():
    with pytest.raises(ValueError, match="even contraction"):
        pack_int4(jnp.zeros((3, 5), jnp.int8))


def test_int4_group_must_divide_contraction_dim(tree):
    _, params = tree
    with pytest.raises(ValueError, match="must divide"):
        quantize_params(params, QuantSpec("int4", group_size=48))


# --- target selection & tree shape ------------------------------------------


def test_quantizes_block_kernels_only(tree):
    """Embedding / layer norms / biases pass through as the SAME
    objects; every block kernel becomes a {q, scale, bias} leaf."""
    _, params = tree
    qp = quantize_params(params, QuantSpec("int8"))
    assert qp["embed"]["weight"] is params["embed"]["weight"]
    assert qp["ln_f"]["scale"] is params["ln_f"]["scale"]
    assert qp["embed_ln"]["bias"] is params["embed_ln"]["bias"]
    for group, name in (("attn", "qkv"), ("attn", "out"),
                        ("mlp", "up"), ("mlp", "down")):
        leaf = qp["blocks"][group][name]
        assert set(leaf) == {"q", "scale", "bias"}
        assert leaf["q"].dtype == jnp.int8
        assert leaf["bias"] is params["blocks"][group][name]["bias"]
    assert qp["blocks"]["ln_1"] is not None  # untouched subtree survives


def test_dequantize_params_restores_kernel_layout(tree):
    _, params = tree
    qp = quantize_params(params, QuantSpec("int8"))
    dq = dequantize_params(qp)
    assert set(dq["blocks"]["mlp"]["up"]) == {"kernel", "bias"}
    assert (dq["blocks"]["mlp"]["up"]["kernel"].shape
            == params["blocks"]["mlp"]["up"]["kernel"].shape)


def test_quantspec_validation():
    with pytest.raises(ValueError, match="weight_dtype"):
        QuantSpec("int2")
    with pytest.raises(ValueError, match="group_size"):
        QuantSpec("int4", group_size=7)


# --- PartitionSpec derivation -----------------------------------------------


def test_param_specs_int8_drops_contraction_entry(tree):
    """q inherits the kernel's spec; per-out-channel scales drop the
    contraction axis so the scale shards WITH its out channels."""
    _, params = tree
    specs = bloom.tp_specs(params)
    qspecs = quantize_param_specs(specs, params, QuantSpec("int8"))
    qkv = qspecs["blocks"]["attn"]["qkv"]
    assert qkv["q"] == specs["blocks"]["attn"]["qkv"]["kernel"]
    assert qkv["q"] == P(None, None, "tensor")     # column: out-sharded
    assert qkv["scale"] == P(None, "tensor")
    out = qspecs["blocks"]["attn"]["out"]
    assert out["q"] == P(None, "tensor", None)     # row: in-sharded
    assert out["scale"] == P(None, None)
    # untouched leaves keep their original spec objects
    assert qspecs["embed"]["weight"] is specs["embed"]["weight"]


def test_param_specs_int4_keeps_grouped_contraction(tree):
    _, params = tree
    specs = bloom.tp_specs(params)
    qspecs = quantize_param_specs(specs, params, QuantSpec("int4", 16))
    out = qspecs["blocks"]["attn"]["out"]
    # grouped scales carry a (sharded) contraction dim like the kernel
    assert out["scale"] == P(None, "tensor", None)


# --- tp compatibility guard -------------------------------------------------


def test_validate_tp_compat_int4_group_vs_shard(tree):
    cfg, _ = tree
    validate_tp_compat(cfg, 2, QuantSpec("int4", 16))   # 64/2=32: ok
    with pytest.raises(ValueError, match="per-shard contraction"):
        validate_tp_compat(cfg, 2, QuantSpec("int4", 48))
    validate_tp_compat(cfg, 2, None)                    # fp: no-op
    validate_tp_compat(cfg, 1, QuantSpec("int4", 48))   # tp=1: no-op


# --- byte census ------------------------------------------------------------


def test_quantized_weight_bytes_by_dtype(tree):
    _, params = tree
    fp = quantized_weight_bytes(params)
    assert set(fp["bytes_by_dtype"]) == {"float32"}
    q8 = quantized_weight_bytes(quantize_params(params, QuantSpec("int8")))
    assert q8["bytes_by_dtype"]["int8"] > 0
    assert q8["total_bytes"] < fp["total_bytes"] / 1.8
    q4 = quantized_weight_bytes(
        quantize_params(params, QuantSpec("int4", 16))
    )
    assert q4["total_bytes"] < q8["total_bytes"]
