"""Dequant-fused matmul: y = x @ dequantize(q, scale), weights int8 in HBM.

The Pallas kernel follows ops/fused_ce.py's tiling idiom: grid
(token_blocks, out_blocks), the full contraction dim per tile (Bloom's
h / 4h fit VMEM comfortably at the block sizes used), fp32 MXU
accumulation via ``preferred_element_type``. The weight tile crosses
HBM -> VMEM as int8 (half/quarter the bytes of the fp kernel — on a
bandwidth-bound decode step that IS the speedup) and is dequantized
in VMEM per tile; a full-precision copy of the weight never exists in
HBM. Per-tile scale rows ride alongside as (1|G, block_o) tiles.

Two numerically identical implementations behind one call:

- ``impl="pallas"`` — the fused kernel (compiled on TPU; interpret
  mode anywhere, the same fallback convention as ops/flash_attention).
- ``impl="xla"`` — a jnp reference with the SAME math and scaling
  order, the default off-TPU so CPU tier-1 pays vectorized-numpy cost
  rather than interpreter cost. Kernel-vs-reference equivalence is
  pinned by tests/quant/test_quant_matmul.py.

int8 applies the per-out-channel scale AFTER the int8-as-fp32 dot
(mathematically the same column scaling, one multiply per output
element instead of per weight); int4 must dequantize before the dot
(scales vary along the contraction dim). Both paths share the
``unpack_int4`` nibble convention of quant/weights.py:pack_int4.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from pipegoose_tpu.ops.fused_ce import _pick_block, _resolve_interpret


def _resolve_impl(impl: Optional[str]) -> str:
    if impl is None:
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl not in ("pallas", "xla"):
        raise ValueError(f"impl must be 'pallas' or 'xla', got {impl!r}")
    return impl


def unpack_int4(packed: jax.Array) -> jax.Array:
    """(..., K//2, N) int8 -> (..., K, N) int8 values in [-8, 7]: the
    low nibble is row 2i, the high nibble row 2i+1 (arithmetic shifts
    sign-extend, matching pack_int4's two's-complement nibbles). The
    shifts run on int32: Mosaic has no shift on int8 vectors."""
    wide = packed.astype(jnp.int32)
    low = jnp.right_shift(jnp.left_shift(wide, 28), 28)
    high = jnp.right_shift(wide, 4)
    inter = jnp.stack([low, high], axis=-2)  # (..., K//2, 2, N)
    return inter.reshape(
        packed.shape[:-2] + (packed.shape[-2] * 2, packed.shape[-1])
    ).astype(jnp.int8)


def dequantize_weight(q: jax.Array, scale: jax.Array) -> jax.Array:
    """Quantized leaf -> fp32 kernel (reference/testing; the fused
    paths never materialize this at model scale). Layout is detected
    from the shapes: int8 per-channel scales have one dim fewer than
    ``q``; int4 grouped scales have the same rank (a grouped
    contraction dim)."""
    if scale.ndim == q.ndim - 1:
        return q.astype(jnp.float32) * scale[..., None, :]
    if scale.ndim != q.ndim:
        raise ValueError(
            f"scale rank {scale.ndim} matches neither int8 (rank "
            f"{q.ndim - 1}) nor int4 (rank {q.ndim}) for q rank {q.ndim}"
        )
    q4 = unpack_int4(q)
    k = q4.shape[-2]
    groups = scale.shape[-2]
    if k % groups:
        raise ValueError(
            f"unpacked contraction dim {k} not divisible by "
            f"{groups} scale groups"
        )
    g = k // groups
    grouped = q4.reshape(q4.shape[:-2] + (groups, g, q4.shape[-1]))
    w = grouped.astype(jnp.float32) * scale[..., None, :]
    return w.reshape(q4.shape)


def _matmul_xla(x32: jax.Array, q: jax.Array, scale: jax.Array,
                int4: bool) -> jax.Array:
    if int4:
        return jnp.dot(x32, dequantize_weight(q, scale),
                       preferred_element_type=jnp.float32)
    y = jnp.dot(x32, q.astype(jnp.float32),
                preferred_element_type=jnp.float32)
    return y * scale[None, :]


def _matmul_int8_pallas(x, q, scale, block_t, block_o, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    t_tot, k = x.shape
    n = q.shape[-1]
    nt, no = t_tot // block_t, n // block_o

    def kernel(x_ref, q_ref, s_ref, o_ref):
        xb = x_ref[...].astype(jnp.float32)          # (BT, K)
        qb = q_ref[...].astype(jnp.float32)          # (K, BO) from int8
        acc = jax.lax.dot_general(
            xb, qb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        o_ref[...] = acc * s_ref[...]                # per-out-channel

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0,
            grid=(nt, no),
            in_specs=[
                pl.BlockSpec((block_t, k), lambda i, j: (i, 0)),
                pl.BlockSpec((k, block_o), lambda i, j: (0, j)),
                pl.BlockSpec((1, block_o), lambda i, j: (0, j)),
            ],
            out_specs=pl.BlockSpec((block_t, block_o),
                                   lambda i, j: (i, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((t_tot, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
        name="int8_matmul",
    )(x, q, scale[None, :])


def _matmul_int4_pallas(x, q, scale, block_t, block_o, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    t_tot, k = x.shape
    kp, n = q.shape
    groups = scale.shape[-2]
    g = k // groups
    nt, no = t_tot // block_t, n // block_o

    def kernel(x_ref, q_ref, s_ref, o_ref):
        xb = x_ref[...].astype(jnp.float32)          # (BT, K)
        q4 = unpack_int4(q_ref[...])                 # (K, BO) int8
        sb = s_ref[...]                              # (G, BO) f32
        w = q4.astype(jnp.float32).reshape(groups, g, block_o)
        w = (w * sb[:, None, :]).reshape(k, block_o)
        o_ref[...] = jax.lax.dot_general(
            xb, w, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0,
            grid=(nt, no),
            in_specs=[
                pl.BlockSpec((block_t, k), lambda i, j: (i, 0)),
                pl.BlockSpec((kp, block_o), lambda i, j: (0, j)),
                pl.BlockSpec((groups, block_o), lambda i, j: (0, j)),
            ],
            out_specs=pl.BlockSpec((block_t, block_o),
                                   lambda i, j: (i, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((t_tot, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
        name="int4_matmul",
    )(x, q, scale)


def quantized_matmul(
    x: jax.Array,       # (..., K) activations (any float dtype)
    q: jax.Array,       # (K, N) int8 | (K//2, N) int4-packed int8
    scale: jax.Array,   # (N,) int8 per-channel | (K//G, N) int4 grouped
    *,
    block_t: int = 128,
    block_o: int = 256,
    impl: Optional[str] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """fp32 ``x @ dequantize(q, scale)`` without an fp weight in HBM.

    Leading dims of ``x`` are batch (flattened through the kernel and
    restored); the int8-vs-int4 layout is detected from the shapes the
    same way as :func:`dequantize_weight`. ``impl=None`` resolves to
    the Pallas kernel on TPU and the XLA reference elsewhere;
    ``interpret`` follows ops/fused_ce's convention (None = compiled
    on TPU, interpreter off-TPU) and only matters for ``"pallas"``.
    Returns fp32 — callers cast, matching the TP layers' convention.
    """
    k_in = x.shape[-1]
    int4 = scale.ndim == q.ndim
    if not int4 and q.shape[-2] != k_in:
        raise ValueError(
            f"int8 weight contraction dim {q.shape[-2]} != x's {k_in}"
        )
    if int4 and q.shape[-2] * 2 != k_in:
        raise ValueError(
            f"int4-packed contraction dim {q.shape[-2]}*2 != x's {k_in}"
        )
    batch = x.shape[:-1]
    x2 = x.reshape((-1, k_in)).astype(jnp.float32)
    n = q.shape[-1]
    impl = _resolve_impl(impl)
    if impl == "xla":
        y = _matmul_xla(x2, q, scale, int4)
        return y.reshape(batch + (n,))
    interpret = _resolve_interpret(interpret)
    t = x2.shape[0]
    # token blocks: largest power of two <= block_t covering t (pad up)
    pow2 = 8
    while pow2 < min(t, block_t):
        pow2 *= 2
    bt = min(pow2, block_t)
    if t % bt:
        x2 = jnp.pad(x2, ((0, bt - t % bt), (0, 0)))
    bo, exact = _pick_block(n, block_o)
    if not exact and not interpret:
        raise ValueError(
            f"quantized matmul: no block size >= 8 among halvings of "
            f"{block_o} divides N={n}; pad the out dim or pass a "
            f"block_o dividing it (compiled TPU runs reject the "
            f"whole-dim fallback tile — same contract as fused CE)"
        )
    fn = _matmul_int4_pallas if int4 else _matmul_int8_pallas
    y = fn(x2, q, scale, bt, bo, interpret)
    return y[:t].reshape(batch + (n,))
