"""Fused flash attention (Pallas, TPU) — forward AND backward.

The hot op of every model here is causal self-attention with an additive
ALiBi bias. XLA's default lowering materializes the (S, S) score matrix
in HBM; these kernels compute softmax(QK^T * scale + bias) V blockwise
in VMEM with the online-softmax recurrence — O(S) memory, MXU matmuls,
one pass over K/V per Q block.

Kernel structure (canonical TPU flash attention):
- forward: grid = (batch*heads, n_q_blocks, n_kv_blocks); the kv
  dimension is sequential ("arbitrary") so the (m, l, acc) scratch
  carries across kv steps for a fixed (bh, q) program. Also emits the
  per-row logsumexp for the backward.
- backward: ONE kernel, ``flash_bwd``, recomputing the probabilities
  from the saved logsumexp (no (S,S) materialization): grid (bh, nk,
  nq), both sequential; a step forms its (BQ, BK) tile of P and dS once
  and feeds dV += P^T dO and dK += dS^T Q (a key block's float32
  scratch, q innermost) and dQ[q block] += dS K (a float32 accumulator
  that holds the whole sequence of one (batch, head) and leaves VMEM
  once a head); five matmuls a tile. Where that accumulator does not
  fit the VMEM budget (``_working_set_bytes("bwd", ..)``: sequences of
  ~64k and more at width 128) the same sums run as two kernels, each
  forming the tile for itself, seven matmuls:
  dq:  grid (bh, nq, nk), kv sequential, accumulates dS @ K;
  dkv: grid (bh, nk, nq), q sequential, accumulates dS^T @ Q and P^T @ dO;
  delta = rowsum(dO * O): plain XLA for these; the paired ``flash_bwd`` forms it itself.
- blocks: ``_pick_blocks(seq, head width, itemsize, kind, vmem limit)``
  gives each kernel the largest (block_q, block_k) whose working set
  fits three quarters of the scoped VMEM it asks for, half the device's
  — 1024 x 1024 at the trained shapes on a v5e: a grid step costs
  ~0.35 us whatever it computes, and a 128 x 512 step held 85 ns of
  matrix work;
- matrix-unit operands stay in the dtype they arrive in (bf16 in the
  models) with float32 accumulation; ``p`` and ``ds`` are rounded to
  that dtype before their products, as the models' plain paths round
  the softmax. Statistics, ``exp``, lse, delta and the accumulators are
  float32;
- per-head ALiBi slope arrives via scalar prefetch (SMEM);
- padding masks are supported via two per-key arrays: ``kv_pos`` (the
  mask-aware ALiBi position, matching BLOOM's (cumsum(mask)-1)*mask)
  and ``kv_neg`` (0 for valid keys, NEG_INF for padded ones). The
  finite NEG_INF keeps fully-masked rows NaN-free (uniform garbage
  probs; those rows are masked out of the loss downstream).
- blocks fully above the causal diagonal (or below the sliding window)
  are skipped with pl.when — ~2x fewer FLOPs for causal attention — and
  fetch nothing: their index maps are clamped to the last block the
  rule keeps, which is already in VMEM.
- layout: operands are flattened ``(batch*heads, seq, head_dim)``, a
  head a tile, EXCEPT where two heads of 64 fill one 128-lane tile:
  there ``flash_fwd`` and ``flash_bwd`` (same names) read and write
  ``(batch, seq, heads * 64)``, a pair of heads a grid row, each head's
  arithmetic unchanged ("two heads a 128-lane tile", below).

The three ``flash_ring_*`` kernels below were copied from these before
PR 30 and keep the old step (128 x 512 blocks, float32 operands, every
block fetched): no benchmark cell runs them (ROADMAP B6c).

Reference framework has no kernels at all (its README advertises "fused
kernels"; grep finds none — SURVEY.md, "Scale/completeness caveat").
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

NEG_INF = -1e9

# What ``_flash_fwd`` leaves for ``_flash_bwd`` carries these names, in
# this order: the kernel's result and its per-row logsumexp. A block's
# ``jax.checkpoint`` that saves them (``remat_policy``) never re-runs
# the forward kernel in backward; one that does not ignores them.
RESIDUAL_NAMES = ("flash_out", "flash_lse")


def remat_policy(extra=None):
    """The ``jax.checkpoint`` policy that keeps the flash kernel's
    residuals, and whatever ``extra`` (another policy) keeps."""
    keep = jax.checkpoint_policies.save_only_these_names(*RESIDUAL_NAMES)
    if extra is None:
        return keep
    return jax.checkpoint_policies.save_from_both_policies(keep, extra)


def _pick_block(n: int, target: int = 128) -> int:
    """Largest power-of-two block <= target dividing n (sequence lengths
    here are powers of two in practice; tiny/odd n fall back to n)."""
    b = target
    while b >= 8:
        if n % b == 0:
            return b
        b //= 2
    return n


# Mosaic's scoped-VMEM limit for a kernel that asks for nothing.
_DEFAULT_VMEM_LIMIT_BYTES = 16 * 2**20
# Past 1024 a side no kernel got faster at any of the measured widths
# (PERF.md, PR 30): the causal skip grows coarser as fast as the grid
# step is amortised.
_MAX_BLOCK = 1024

# What one grid step of each kernel holds, in tiles: pipelined (BQ, hd)
# and (BK, hd) operand/result tiles, float32 (BQ, hd) and (BK, hd)
# scratch, and the (BQ, BK) score tiles its body keeps alive, float32
# ones and copies in the operands' dtype for the matrix unit.
_STEP_TILES = {
    # q o | k v | acc m l | - | s p select | p
    "fwd": (2, 2, 3, 0, 3, 1),
    # q dO dQ | k v | dQ | - | p dp ds select | ds
    "dq": (3, 2, 1, 0, 4, 1),
    # q dO | k v dK dV | - | dK dV | p dp ds select | p ds, each transposed
    "dkv": (2, 4, 0, 2, 4, 4),
    # dK/dV's, and the (BQ, hd) product that goes into dQ's accumulator
    "bwd": (2, 4, 1, 2, 4, 4),
    # two heads a 128-lane tile (``hd`` = 128): m and l a head, and the
    # forward's result beside dO, from which the kernel forms delta
    "fwd_paired": (2, 2, 5, 0, 3, 1),
    "bwd_paired": (3, 4, 1, 2, 4, 4),
}
# What a kernel holds of the WHOLE sequence of one (batch, head),
# whatever its blocks: float32 (seq, hd) scratch, and pipelined (seq,
# hd) results in the operands' dtype.
_SEQ_TILES = {
    # dQ's accumulator | dQ
    "bwd": (1, 1),
    "bwd_paired": (1, 1),
}


def _vmem_limit_bytes() -> int:
    """Scoped VMEM the non-ring kernels ask of the compiler: half
    of what the device's core has (64 MiB of a v5e's 128), and the
    compiler's default where that is no more or the device is not a TPU
    Pallas knows (interpret mode, a lowering with no device)."""
    from jax.experimental.pallas import tpu as pltpu

    try:
        capacity = pltpu.get_tpu_info().vmem_capacity_bytes
    except ValueError:
        return _DEFAULT_VMEM_LIMIT_BYTES
    return max(capacity // 2, _DEFAULT_VMEM_LIMIT_BYTES)


def _working_set_bytes(kind: str, block_q: int, block_k: int, head_dim: int,
                       itemsize: int, seq: int = 0) -> int:
    """VMEM one grid step of kernel ``kind`` ("fwd", "dq", "dkv", "bwd";
    "fwd_paired", "bwd_paired" for the kernels that take two heads of 64
    as ONE tile of ``head_dim`` = 128 lanes, a score tile formed for one
    head at a time) holds, by its own arithmetic: ``_STEP_TILES``'
    pipelined tiles twice (double buffering), the scratch, the score
    tiles, the per-query and per-key float32 rows (two each side,
    pipelined), and what ``_SEQ_TILES`` has the kind keep of a whole
    sequence of ``seq`` positions, which no choice of blocks makes
    smaller. An upper bound: a
    v5e's compiler took each kernel with 1.1-1.5x less at head widths of
    256 and more, where a budget binds, and with 2-4x less at 64
    (PERF.md, PR 30); tests/ops/test_chip_compile.py holds it to that.
    A one-head tile of 64 lanes is counted, as it is stored and moved,
    at the 128 it pads to: what the paired layout fills with a second
    head."""
    q_io, k_io, q_f32, k_f32, s_f32, s_narrow = _STEP_TILES[kind]
    lanes = -(-head_dim // 128) * 128    # a tile's rows pad to 128 lanes
    tiles = (2 * (q_io * block_q + k_io * block_k) * itemsize
             + (q_f32 * block_q + k_f32 * block_k) * 4) * lanes
    rows = 2 * 2 * 8 * (block_q + block_k) * 4    # (1, n): 8 sublanes
    seq_f32, seq_io = _SEQ_TILES.get(kind, (0, 0))
    whole = seq * lanes * (4 * seq_f32 + 2 * itemsize * seq_io)
    return (tiles + rows + whole
            + block_q * block_k * (4 * s_f32 + itemsize * s_narrow))


def _fits(kind: str, block_q: int, block_k: int, head_dim: int,
          itemsize: int, seq: int, vmem_limit_bytes: int) -> bool:
    """Whether kernel ``kind``'s working set fits three quarters of the
    limit (the rest is the compiler's: its temporaries, semaphores,
    alignment)."""
    return (_working_set_bytes(kind, block_q, block_k, head_dim, itemsize, seq)
            <= vmem_limit_bytes * 3 // 4)


def _pick_blocks(seq: int, head_dim: int, itemsize: int, kind: str,
                 vmem_limit_bytes: int):
    """``(block_q, block_k)`` for kernel ``kind`` from the operands'
    shape and the scoped VMEM the kernel will ask for: the largest
    power-of-two blocks (at most ``_MAX_BLOCK`` a side) that divide
    ``seq`` and whose working set, by ``_working_set_bytes``, fits
    (``_fits``); tiny and odd ``seq`` fall back as ``_pick_block`` does.

    A grid step costs ~0.35 us whatever it computes, and what bounds a
    step's body is per-score work outside the matrix unit, so the step
    should hold as many scores as VMEM lets it (v5e, PR 30: 1024 x 1024
    runs the three kernels 2.3-2.7x faster than 128 x 512 at head
    widths 64, 128 and 256). Where the budget binds the larger side is
    halved, the query side on a tie; which side is better kept has not
    been measured (no trained shape binds a v5e's budget)."""
    block_q = block_k = _pick_block(seq, _MAX_BLOCK)
    while not _fits(kind, block_q, block_k, head_dim, itemsize, seq,
                    vmem_limit_bytes):
        if block_q >= block_k and block_q % 16 == 0:
            block_q //= 2
        elif block_k % 16 == 0:
            block_k //= 2
        else:
            break
    return block_q, block_k


def mask_to_kv_bias(attention_mask: jax.Array):
    """(B, S) 1/0 mask -> (kv_pos, kv_neg) f32 kernel bias inputs:
    mask-aware ALiBi position (BLOOM's (cumsum(mask)-1)*mask) and 0 /
    NEG_INF key validity. Single source for the kernel and the models."""
    m = attention_mask.astype(jnp.float32)
    kv_pos = (jnp.cumsum(m, axis=-1) - 1.0) * m
    kv_neg = (1.0 - m) * NEG_INF
    return kv_pos, kv_neg


def _keep_block(q_start, k_start, block_q, block_k, causal, window):
    """Whether the causal / sliding-window rule keeps ANY score of the
    (BQ, BK) block at ``(q_start, k_start)``: blocks fully above the
    diagonal or fully below the window are skipped. Python ``True`` when
    there is no rule, a scalar otherwise."""
    keep = True
    if causal:
        keep = k_start <= q_start + block_q - 1
    if window is not None:
        keep = keep & (k_start + block_k - 1 >= q_start - window + 1)
    return keep


def _kv_block(i, j, block_q, block_k, causal, window):
    """The key block that step ``(i, j)`` of a (bh, nq, nk) grid names:
    ``j`` clamped to the blocks the rule keeps for query block ``i``, so
    a skipped step re-names the block already in VMEM and the pipeline
    issues no copy."""
    if causal:
        j = jnp.minimum(j, (i * block_q + block_q - 1) // block_k)
    if window is not None:
        j = jnp.maximum(j, jnp.maximum(i * block_q - window + 1, 0) // block_k)
    return j


def _kv_index_maps(g, block_q, block_k, causal, window):
    """Index maps of a (bh, nq, nk) grid for the K/V tiles ``(1, BK,
    hd)`` and the per-key rows ``(1, 1, BK)``: kv head ``b // g`` (GQA),
    key block clamped by ``_kv_block``."""
    def kv_map(b, i, j):
        return (b // g, _kv_block(i, j, block_q, block_k, causal, window), 0)

    def kv_row_map(b, i, j):
        return (b // g, 0, _kv_block(i, j, block_q, block_k, causal, window))

    return kv_map, kv_row_map


def _q_block(j, i, block_q, block_k, causal, window, nq):
    """The mirror image for the (bh, nk, nq) grid of dK/dV: query block
    ``i`` clamped to the blocks the rule keeps for key block ``j``."""
    if causal:
        i = jnp.maximum(i, (j * block_k) // block_q)
    if window is not None:
        last = (j * block_k + block_k + window - 2) // block_q
        i = jnp.minimum(i, jnp.minimum(last, nq - 1))
    return i


def _q_inner_in_specs(bh, hd, g, block_q, block_k, causal, window, nq):
    """Operand specs of a (bh, nk, nq) grid, the query block innermost
    (``flash_dkv``, ``flash_bwd``): slopes in SMEM, q, k, v, dO, the lse
    and delta rows, the per-key rows. Query block clamped by
    ``_q_block``, kv head ``b // g`` (GQA)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def q_map(b, j, i):
        return (b, _q_block(j, i, block_q, block_k, causal, window, nq), 0)

    def q_row_map(b, j, i):
        return (b, 0, _q_block(j, i, block_q, block_k, causal, window, nq))

    def kv_map(b, j, i):
        return (b // g, j, 0)

    def kv_row_map(b, j, i):
        return (b // g, 0, j)

    return [
        pl.BlockSpec((bh,), lambda b, j, i: (0,), memory_space=pltpu.SMEM),
        pl.BlockSpec((1, block_q, hd), q_map),
        pl.BlockSpec((1, block_k, hd), kv_map),
        pl.BlockSpec((1, block_k, hd), kv_map),
        pl.BlockSpec((1, block_q, hd), q_map),
        pl.BlockSpec((1, 1, block_q), q_row_map),
        pl.BlockSpec((1, 1, block_q), q_row_map),
        pl.BlockSpec((1, 1, block_k), kv_row_map),
        pl.BlockSpec((1, 1, block_k), kv_row_map),
    ]


def _scores(q, k, slope, kpos_ref, kneg_ref, scale, q_start, k_start,
            causal, window):
    """One (BQ, BK) block of ``q k^T * scale + bias``, float32: the
    per-key row (ALiBi + padding) and the causal / window mask."""
    block_q, block_k = q.shape[0], k.shape[0]
    s_blk = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
    ) * scale
    bias = (slope * kpos_ref[0].astype(jnp.float32)
            + kneg_ref[0].astype(jnp.float32))  # (1, BK)
    if causal or window is not None:
        shape = (block_q, block_k)
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        k_idx = k_start + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        keep = jnp.ones(shape, bool)
        if causal:
            keep = keep & (k_idx <= q_pos)
        if window is not None:
            keep = keep & (q_pos - k_idx < window)
        bias = jnp.where(keep, bias, NEG_INF)
    return s_blk + bias


def _flash_fwd_pallas(q, k, v, slopes, kpos, kneg, scale, causal,
                      block_q, block_k, interpret, g=1, window=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, s, hd = q.shape  # (batch*query_heads, seq, head_dim)
    # GQA: k/v (and their per-key biases) carry batch*kv_heads rows and
    # are shared by g query heads each via the index maps — never
    # repeated in HBM (g=1 is plain MHA)
    nq, nk = s // block_q, s // block_k

    def kernel(slope_ref, q_ref, k_ref, v_ref, kpos_ref, kneg_ref,
               o_ref, lse_ref, m_sc, l_sc, acc_sc):
        qi = pl.program_id(1)
        ki = pl.program_id(2)
        slope = slope_ref[pl.program_id(0)]

        @pl.when(ki == 0)
        def _init():
            m_sc[:] = jnp.full_like(m_sc, NEG_INF)
            l_sc[:] = jnp.zeros_like(l_sc)
            acc_sc[:] = jnp.zeros_like(acc_sc)

        q_start = qi * block_q
        k_start = ki * block_k

        # blocks fully above the causal diagonal or fully below the
        # sliding window are skipped
        @pl.when(_keep_block(q_start, k_start, block_q, block_k, causal,
                             window))
        def _compute():
            vb = v_ref[0]
            s_blk = _scores(q_ref[0], k_ref[0], slope, kpos_ref, kneg_ref,
                            scale, q_start, k_start, causal, window)
            m_prev = m_sc[:]  # (BQ, 1)
            m_new = jnp.maximum(m_prev, s_blk.max(axis=1, keepdims=True))
            p = jnp.exp(s_blk - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_sc[:] = l_sc[:] * alpha + p.sum(axis=1, keepdims=True)
            acc_sc[:] = acc_sc[:] * alpha + jax.lax.dot_general(
                p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m_sc[:] = m_new

        @pl.when(ki == nk - 1)
        def _finish():
            l = jnp.maximum(l_sc[:], 1e-30)
            o_ref[0] = (acc_sc[:] / l).astype(o_ref.dtype)
            lse_ref[0, 0] = (m_sc[:] + jnp.log(l))[:, 0]

    kv_map, kv_row_map = _kv_index_maps(g, block_q, block_k, causal, window)
    grid = (bh, nq, nk)
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bh,), lambda b, i, j: (0,), memory_space=pltpu.SMEM),
                pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((1, block_k, hd), kv_map),
                pl.BlockSpec((1, block_k, hd), kv_map),
                pl.BlockSpec((1, 1, block_k), kv_row_map),
                pl.BlockSpec((1, 1, block_k), kv_row_map),
            ],
            out_specs=[
                pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, hd), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((bh, 1, s), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit_bytes(),
        ),
        interpret=interpret,
        name="flash_fwd",
    )(slopes, q, k, v, kpos[:, None, :], kneg[:, None, :])
    return out, lse[:, 0, :]


def _flash_dq_pallas(q, k, v, do, lse, delta, slopes, kpos, kneg,
                     scale, causal, block_q, block_k, interpret, g=1, window=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, s, hd = q.shape
    nq, nk = s // block_q, s // block_k

    def kernel(slope_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               kpos_ref, kneg_ref, dq_ref, dq_sc):
        qi = pl.program_id(1)
        ki = pl.program_id(2)
        slope = slope_ref[pl.program_id(0)]

        @pl.when(ki == 0)
        def _init():
            dq_sc[:] = jnp.zeros_like(dq_sc)

        q_start = qi * block_q
        k_start = ki * block_k

        @pl.when(_keep_block(q_start, k_start, block_q, block_k, causal,
                             window))
        def _compute():
            kb = k_ref[0]
            s_blk = _scores(q_ref[0], kb, slope, kpos_ref, kneg_ref,
                            scale, q_start, k_start, causal, window)
            p = jnp.exp(s_blk - lse_ref[0, 0][:, None])  # (BQ, BK)
            dp = jax.lax.dot_general(
                do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # (BQ, BK)
            ds = p * (dp - delta_ref[0, 0][:, None])
            dq_sc[:] += scale * jax.lax.dot_general(
                ds.astype(kb.dtype), kb, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

        @pl.when(ki == nk - 1)
        def _finish():
            dq_ref[0] = dq_sc[:].astype(dq_ref.dtype)

    kv_map, kv_row_map = _kv_index_maps(g, block_q, block_k, causal, window)
    grid = (bh, nq, nk)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bh,), lambda b, i, j: (0,), memory_space=pltpu.SMEM),
                pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((1, block_k, hd), kv_map),
                pl.BlockSpec((1, block_k, hd), kv_map),
                pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
                pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
                pl.BlockSpec((1, 1, block_k), kv_row_map),
                pl.BlockSpec((1, 1, block_k), kv_row_map),
            ],
            out_specs=pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
            scratch_shapes=[pltpu.VMEM((block_q, hd), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit_bytes(),
        ),
        interpret=interpret,
        name="flash_dq",
    )(slopes, q, k, v, do, lse[:, None, :], delta[:, None, :],
      kpos[:, None, :], kneg[:, None, :])


def _flash_dkv_pallas(q, k, v, do, lse, delta, slopes, kpos, kneg,
                      scale, causal, block_q, block_k, interpret, g=1, window=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, s, hd = q.shape
    # outputs are PER QUERY HEAD (b*nh rows) even under GQA — the caller
    # sums the g group contributions into the (b*nkv)-row dk/dv (a write
    # race inside the kernel is not expressible; the XLA sum is fused)
    nq, nk = s // block_q, s // block_k

    def kernel(slope_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               kpos_ref, kneg_ref, dk_ref, dv_ref, dk_sc, dv_sc):
        kj = pl.program_id(1)
        qi = pl.program_id(2)
        slope = slope_ref[pl.program_id(0)]

        @pl.when(qi == 0)
        def _init():
            dk_sc[:] = jnp.zeros_like(dk_sc)
            dv_sc[:] = jnp.zeros_like(dv_sc)

        q_start = qi * block_q
        k_start = kj * block_k

        @pl.when(_keep_block(q_start, k_start, block_q, block_k, causal,
                             window))
        def _compute():
            qb = q_ref[0]
            dob = do_ref[0]
            s_blk = _scores(qb, k_ref[0], slope, kpos_ref, kneg_ref,
                            scale, q_start, k_start, causal, window)
            p = jnp.exp(s_blk - lse_ref[0, 0][:, None])  # (BQ, BK)
            dv_sc[:] += jax.lax.dot_general(
                p.astype(dob.dtype), dob, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # P^T @ dO -> (BK, hd)
            dp = jax.lax.dot_general(
                dob, v_ref[0], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            ds = p * (dp - delta_ref[0, 0][:, None])
            dk_sc[:] += scale * jax.lax.dot_general(
                ds.astype(qb.dtype), qb, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # dS^T @ Q -> (BK, hd)

        @pl.when(qi == nq - 1)
        def _finish():
            dk_ref[0] = dk_sc[:].astype(dk_ref.dtype)
            dv_ref[0] = dv_sc[:].astype(dv_ref.dtype)

    grid = (bh, nk, nq)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0,
            grid=grid,
            in_specs=_q_inner_in_specs(bh, hd, g, block_q, block_k, causal,
                                       window, nq),
            out_specs=[
                pl.BlockSpec((1, block_k, hd), lambda b, j, i: (b, j, 0)),
                pl.BlockSpec((1, block_k, hd), lambda b, j, i: (b, j, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_k, hd), jnp.float32),
                pltpu.VMEM((block_k, hd), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, hd), k.dtype),
            jax.ShapeDtypeStruct((bh, s, hd), v.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit_bytes(),
        ),
        interpret=interpret,
        name="flash_dkv",
    )(slopes, q, k, v, do, lse[:, None, :], delta[:, None, :],
      kpos[:, None, :], kneg[:, None, :])


def _flash_bwd_pallas(q, k, v, do, lse, delta, slopes, kpos, kneg,
                      scale, causal, block_q, block_k, interpret, g=1, window=None):
    """dQ, dK and dV from ONE pass over the score tiles: ``flash_dkv``'s
    grid and index maps, and ``flash_dq``'s sum beside its two. Every
    sum runs in the order the two kernels give it (dQ over key blocks
    ascending, dK/dV over query blocks ascending)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, s, hd = q.shape
    # dK/dV are PER QUERY HEAD, as ``_flash_dkv_pallas`` returns them
    nq, nk = s // block_q, s // block_k

    def kernel(slope_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               kpos_ref, kneg_ref, dq_ref, dk_ref, dv_ref, dq_sc, dk_sc, dv_sc):
        kj = pl.program_id(1)
        qi = pl.program_id(2)
        slope = slope_ref[pl.program_id(0)]

        @pl.when((kj == 0) & (qi == 0))
        def _init_head():
            dq_sc[:] = jnp.zeros_like(dq_sc)

        @pl.when(qi == 0)
        def _init():
            dk_sc[:] = jnp.zeros_like(dk_sc)
            dv_sc[:] = jnp.zeros_like(dv_sc)

        q_start = qi * block_q
        k_start = kj * block_k

        @pl.when(_keep_block(q_start, k_start, block_q, block_k, causal,
                             window))
        def _compute():
            qb = q_ref[0]
            kb = k_ref[0]
            dob = do_ref[0]
            s_blk = _scores(qb, kb, slope, kpos_ref, kneg_ref,
                            scale, q_start, k_start, causal, window)
            p = jnp.exp(s_blk - lse_ref[0, 0][:, None])  # (BQ, BK)
            dv_sc[:] += jax.lax.dot_general(
                p.astype(dob.dtype), dob, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # P^T @ dO -> (BK, hd)
            dp = jax.lax.dot_general(
                dob, v_ref[0], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            ds = (p * (dp - delta_ref[0, 0][:, None])).astype(qb.dtype)
            dk_sc[:] += scale * jax.lax.dot_general(
                ds, qb, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # dS^T @ Q -> (BK, hd)
            rows = pl.ds(pl.multiple_of(q_start, block_q), block_q)
            dq_sc[rows, :] += scale * jax.lax.dot_general(
                ds, kb, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # dS @ K -> this query block's rows of (seq, hd)

        @pl.when(qi == nq - 1)
        def _finish():
            dk_ref[0] = dk_sc[:].astype(dk_ref.dtype)
            dv_ref[0] = dv_sc[:].astype(dv_ref.dtype)

        @pl.when((kj == nk - 1) & (qi == nq - 1))
        def _finish_head():
            dq_ref[0] = dq_sc[:].astype(dq_ref.dtype)

    grid = (bh, nk, nq)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0,
            grid=grid,
            in_specs=_q_inner_in_specs(bh, hd, g, block_q, block_k, causal,
                                       window, nq),
            out_specs=[
                # the whole sequence of a head: it leaves VMEM once a head
                pl.BlockSpec((1, s, hd), lambda b, j, i: (b, 0, 0)),
                pl.BlockSpec((1, block_k, hd), lambda b, j, i: (b, j, 0)),
                pl.BlockSpec((1, block_k, hd), lambda b, j, i: (b, j, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((s, hd), jnp.float32),
                pltpu.VMEM((block_k, hd), jnp.float32),
                pltpu.VMEM((block_k, hd), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((bh, s, hd), k.dtype),
            jax.ShapeDtypeStruct((bh, s, hd), v.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem_limit_bytes(),
        ),
        interpret=interpret,
        name="flash_bwd",
    )(slopes, q, k, v, do, lse[:, None, :], delta[:, None, :],
      kpos[:, None, :], kneg[:, None, :])


# -- two heads a 128-lane tile (head width 64) --------------------------------
#
# A Pallas operand is row-major with its last dimension in the lanes, so
# a 64-wide head fills half of every (rows, 128) tile it is stored and
# moved in: q, k, v, the result and the four gradients at twice their
# bytes, behind transposes of heads over positions (PERF.md, PR 49).
# Where two adjacent heads fill one tile the kernels below read q, k, v,
# dO and write the result, dQ, dK, dV as ``(B, S, nh * 64)``, which is
# the reshape of what a model hands over: nothing transposed, nothing
# padded. A grid row is a PAIR of heads; each head's arithmetic is the
# one-head kernels' term for term, a (BQ, BK) score tile formed for one
# head at a time. A head reaches the matrix unit through a lane mask:
# the contraction runs over all 128 lanes against an operand whose other
# head's lanes are zero (the added terms are zeros, and a 64-deep
# contraction already takes a 128-deep pass).

_PAIRED_HEAD_DIM = 64
_PAIR = 2
_PAIR_LANES = _PAIR * _PAIRED_HEAD_DIM


def _lanes_of(h):
    """(1, 128) mask of the lanes that hold a tile's ``h``-th head."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, _PAIR_LANES), 1)
    return (lane >= h * _PAIRED_HEAD_DIM) & (lane < (h + 1) * _PAIRED_HEAD_DIM)


def _own_lanes(x, h):
    """Tile ``x`` (rows, 128) with the other head's lanes zeroed."""
    return jnp.where(_lanes_of(h), x, 0)


def _flash_fwd_paired_pallas(q, k, v, slopes, kpos, kneg, scale, causal,
                             block_q, block_k, interpret, window=None):
    """``_flash_fwd_pallas`` over ``(B, S, nh * 64)`` operands: grid
    (B * nh / 2, nq, nk), slopes ``(B * nh,)``, per-key rows ``(B, S)``
    (one a batch row); returns the result in the operands' layout and
    the logsumexp ``(B * nh, S)``, a row a head."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s, width = q.shape
    pairs = width // _PAIR_LANES
    nq, nk = s // block_q, s // block_k

    def kernel(slope_ref, q_ref, k_ref, v_ref, kpos_ref, kneg_ref,
               o_ref, lse_ref, m_sc, l_sc, acc_sc):
        bp = pl.program_id(0)
        qi = pl.program_id(1)
        ki = pl.program_id(2)

        @pl.when(ki == 0)
        def _init():
            m_sc[:] = jnp.full_like(m_sc, NEG_INF)
            l_sc[:] = jnp.zeros_like(l_sc)
            acc_sc[:] = jnp.zeros_like(acc_sc)

        q_start = qi * block_q
        k_start = ki * block_k

        @pl.when(_keep_block(q_start, k_start, block_q, block_k, causal,
                             window))
        def _compute():
            qb = q_ref[0]
            kb = k_ref[0]
            vb = v_ref[0]
            for h in range(_PAIR):
                s_blk = _scores(_own_lanes(qb, h), kb,
                                slope_ref[_PAIR * bp + h], kpos_ref,
                                kneg_ref, scale, q_start, k_start, causal,
                                window)
                m_prev = m_sc[h]  # (BQ, 1)
                m_new = jnp.maximum(m_prev, s_blk.max(axis=1, keepdims=True))
                p = jnp.exp(s_blk - m_new)
                alpha = jnp.exp(m_prev - m_new)
                l_sc[h] = l_sc[h] * alpha + p.sum(axis=1, keepdims=True)
                # (BQ, 128): this head's lanes hold p v of this head
                pv = jax.lax.dot_general(
                    p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                acc = acc_sc[:]
                acc_sc[:] = jnp.where(_lanes_of(h), acc * alpha + pv, acc)
                m_sc[h] = m_new

        @pl.when(ki == nk - 1)
        def _finish():
            ls = [jnp.maximum(l_sc[h], 1e-30) for h in range(_PAIR)]
            l = jnp.where(_lanes_of(0), ls[0], ls[1])  # (BQ, 128)
            o_ref[0] = (acc_sc[:] / l).astype(o_ref.dtype)
            for h in range(_PAIR):
                lse_ref[h, 0] = (m_sc[h] + jnp.log(ls[h]))[:, 0]

    def q_map(bp, i, j):
        return (bp // pairs, i, bp % pairs)

    def kv_map(bp, i, j):
        return (bp // pairs, _kv_block(i, j, block_q, block_k, causal, window),
                bp % pairs)

    def kv_row_map(bp, i, j):
        return (bp // pairs, 0,
                _kv_block(i, j, block_q, block_k, causal, window))

    out, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0,
            grid=(b * pairs, nq, nk),
            in_specs=[
                pl.BlockSpec((b * pairs * _PAIR,), lambda bp, i, j: (0,),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec((1, block_q, _PAIR_LANES), q_map),
                pl.BlockSpec((1, block_k, _PAIR_LANES), kv_map),
                pl.BlockSpec((1, block_k, _PAIR_LANES), kv_map),
                pl.BlockSpec((1, 1, block_k), kv_row_map),
                pl.BlockSpec((1, 1, block_k), kv_row_map),
            ],
            out_specs=[
                pl.BlockSpec((1, block_q, _PAIR_LANES), q_map),
                pl.BlockSpec((_PAIR, 1, block_q), lambda bp, i, j: (bp, 0, i)),
            ],
            scratch_shapes=[
                pltpu.VMEM((_PAIR, block_q, 1), jnp.float32),
                pltpu.VMEM((_PAIR, block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, _PAIR_LANES), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((b * pairs * _PAIR, 1, s), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit_bytes(),
        ),
        interpret=interpret,
        name="flash_fwd",
    )(slopes, q, k, v, kpos[:, None, :], kneg[:, None, :])
    return out, lse[:, 0, :]


def _flash_bwd_paired_pallas(q, k, v, do, out, lse, slopes, kpos, kneg,
                             scale, causal, block_q, block_k, interpret,
                             window=None):
    """``_flash_bwd_pallas`` over ``(B, S, nh * 64)`` operands and
    results: grid (B * nh / 2, nk, nq); ``lse`` ``(B * nh, S)``, two
    rows a grid row; dQ's whole-sequence float32 accumulator holds the
    pair's 128 lanes. ``delta = rowsum(dO * O)`` is formed HERE, from the
    forward's result ``out``, a head's 64 lanes of the tile at a time
    (float32 products and sum, as the one-head path has them from XLA):
    a sum over half a tile's lanes is what XLA re-lays the whole plane
    out for, sequence-minor, twice a layer (PERF.md, PR 49)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s, width = q.shape
    pairs = width // _PAIR_LANES
    nq, nk = s // block_q, s // block_k

    def kernel(slope_ref, q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
               kpos_ref, kneg_ref, dq_ref, dk_ref, dv_ref, dq_sc, dk_sc, dv_sc):
        bp = pl.program_id(0)
        kj = pl.program_id(1)
        qi = pl.program_id(2)

        @pl.when((kj == 0) & (qi == 0))
        def _init_pair():
            dq_sc[:] = jnp.zeros_like(dq_sc)

        @pl.when(qi == 0)
        def _init():
            dk_sc[:] = jnp.zeros_like(dk_sc)
            dv_sc[:] = jnp.zeros_like(dv_sc)

        q_start = qi * block_q
        k_start = kj * block_k

        @pl.when(_keep_block(q_start, k_start, block_q, block_k, causal,
                             window))
        def _compute():
            kb = k_ref[0]
            vb = v_ref[0]
            rows = pl.ds(pl.multiple_of(q_start, block_q), block_q)
            do_o = do_ref[0].astype(jnp.float32) * o_ref[0].astype(jnp.float32)
            for h in range(_PAIR):
                # every product below is zero outside this head's lanes
                qb = _own_lanes(q_ref[0], h)
                dob = _own_lanes(do_ref[0], h)
                s_blk = _scores(qb, kb, slope_ref[_PAIR * bp + h], kpos_ref,
                                kneg_ref, scale, q_start, k_start, causal,
                                window)
                p = jnp.exp(s_blk - lse_ref[h, 0][:, None])  # (BQ, BK)
                dv_sc[:] += jax.lax.dot_general(
                    p.astype(dob.dtype), dob, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )  # P^T @ dO -> (BK, 128)
                dp = jax.lax.dot_general(
                    dob, vb, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                delta = _own_lanes(do_o, h).sum(axis=1, keepdims=True)  # (BQ, 1)
                ds = (p * (dp - delta)).astype(qb.dtype)
                dk_sc[:] += scale * jax.lax.dot_general(
                    ds, qb, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )  # dS^T @ Q -> (BK, 128)
                dq_sc[rows, :] += scale * jax.lax.dot_general(
                    ds, _own_lanes(kb, h), (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )  # dS @ K -> this query block's rows of (seq, 128)

        @pl.when(qi == nq - 1)
        def _finish():
            dk_ref[0] = dk_sc[:].astype(dk_ref.dtype)
            dv_ref[0] = dv_sc[:].astype(dv_ref.dtype)

        @pl.when((kj == nk - 1) & (qi == nq - 1))
        def _finish_pair():
            dq_ref[0] = dq_sc[:].astype(dq_ref.dtype)

    def q_map(bp, j, i):
        return (bp // pairs, _q_block(j, i, block_q, block_k, causal, window, nq),
                bp % pairs)

    def q_row_map(bp, j, i):
        return (bp, 0, _q_block(j, i, block_q, block_k, causal, window, nq))

    def kv_map(bp, j, i):
        return (bp // pairs, j, bp % pairs)

    def kv_row_map(bp, j, i):
        return (bp // pairs, 0, j)

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0,
            grid=(b * pairs, nk, nq),
            in_specs=[
                pl.BlockSpec((b * pairs * _PAIR,), lambda bp, j, i: (0,),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec((1, block_q, _PAIR_LANES), q_map),
                pl.BlockSpec((1, block_k, _PAIR_LANES), kv_map),
                pl.BlockSpec((1, block_k, _PAIR_LANES), kv_map),
                pl.BlockSpec((1, block_q, _PAIR_LANES), q_map),
                pl.BlockSpec((1, block_q, _PAIR_LANES), q_map),
                pl.BlockSpec((_PAIR, 1, block_q), q_row_map),
                pl.BlockSpec((1, 1, block_k), kv_row_map),
                pl.BlockSpec((1, 1, block_k), kv_row_map),
            ],
            out_specs=[
                # the whole sequence of a pair: it leaves VMEM once a pair
                pl.BlockSpec((1, s, _PAIR_LANES),
                             lambda bp, j, i: (bp // pairs, 0, bp % pairs)),
                pl.BlockSpec((1, block_k, _PAIR_LANES), kv_map),
                pl.BlockSpec((1, block_k, _PAIR_LANES), kv_map),
            ],
            scratch_shapes=[
                pltpu.VMEM((s, _PAIR_LANES), jnp.float32),
                pltpu.VMEM((block_k, _PAIR_LANES), jnp.float32),
                pltpu.VMEM((block_k, _PAIR_LANES), jnp.float32),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in (q, k, v)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem_limit_bytes(),
        ),
        interpret=interpret,
        name="flash_bwd",
    )(slopes, q, k, v, do, out, lse[:, None, :], kpos[:, None, :],
      kneg[:, None, :])


def _flash_chunk_pallas(q, k, v, slopes, qpos, kpos, kneg, m0, l0, acc0,
                        scale, block_q, block_k, interpret, g=1):
    """Stateful flash chunk for ring attention: consume the incoming
    online-softmax state (m, l, acc), attend local Q against ONE K/V
    chunk, and return the updated UNNORMALIZED state. The causal mask is
    value-based (global position arrays ``qpos``/``kpos``), so the same
    kernel serves any ring rotation; normalization happens once after
    the last ring step."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, sq, hd = q.shape
    skv = k.shape[1]
    nq, nk = sq // block_q, skv // block_k

    def kernel(slope_ref, q_ref, k_ref, v_ref, qpos_ref, kpos_ref, kneg_ref,
               m0_ref, l0_ref, acc0_ref, m_ref, l_ref, acc_ref,
               m_sc, l_sc, acc_sc):
        ki = pl.program_id(2)
        slope = slope_ref[pl.program_id(0)]

        @pl.when(ki == 0)
        def _init():
            m_sc[:, 0] = m0_ref[0, 0]
            l_sc[:, 0] = l0_ref[0, 0]
            acc_sc[:] = acc0_ref[0].astype(jnp.float32)

        qp = qpos_ref[0, 0].astype(jnp.float32)  # (BQ,)
        kp = kpos_ref[0, 0].astype(jnp.float32)  # (BK,)

        # value-based causal block skip (positions are dynamic here, so
        # the non-ring kernel's static index skip doesn't apply): a block
        # whose every key is in the future of every query adds NEG_INF
        # everywhere — skip both matmuls, ~2x fewer FLOPs causal
        @pl.when(jnp.min(kp) <= jnp.max(qp))
        def _compute():
            qb = q_ref[0].astype(jnp.float32)
            kb = k_ref[0].astype(jnp.float32)
            vb = v_ref[0].astype(jnp.float32)
            s_blk = jax.lax.dot_general(
                qb, kb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale
            kn = kneg_ref[0, 0].astype(jnp.float32)
            s_blk = s_blk + slope * kp[None, :] + kn[None, :]
            s_blk = s_blk + jnp.where(kp[None, :] <= qp[:, None], 0.0, NEG_INF)

            m_prev = m_sc[:, 0]
            m_new = jnp.maximum(m_prev, s_blk.max(axis=1))
            p = jnp.exp(s_blk - m_new[:, None])
            alpha = jnp.exp(m_prev - m_new)
            l_sc[:, 0] = l_sc[:, 0] * alpha + p.sum(axis=1)
            acc_sc[:] = acc_sc[:] * alpha[:, None] + jax.lax.dot_general(
                p, vb, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m_sc[:, 0] = m_new

        @pl.when(ki == nk - 1)
        def _finish():
            m_ref[0, 0] = m_sc[:, 0]
            l_ref[0, 0] = l_sc[:, 0]
            acc_ref[0] = acc_sc[:]

    grid = (bh, nq, nk)
    m, l, acc = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bh,), lambda b, i, j: (0,), memory_space=pltpu.SMEM),
                pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b // g, j, 0)),
                pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b // g, j, 0)),
                pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
                pl.BlockSpec((1, 1, block_k), lambda b, i, j: (b // g, 0, j)),
                pl.BlockSpec((1, 1, block_k), lambda b, i, j: (b // g, 0, j)),
                pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
                pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
                pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
                pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
                pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, hd), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((bh, 1, sq), jnp.float32),
            jax.ShapeDtypeStruct((bh, 1, sq), jnp.float32),
            jax.ShapeDtypeStruct((bh, sq, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_ring_fwd",
    )(slopes, q, k, v, qpos[:, None, :], kpos[:, None, :], kneg[:, None, :],
      m0[:, None, :], l0[:, None, :], acc0)
    return m[:, 0, :], l[:, 0, :], acc


def _xla_chunk(q, k, v, slopes, qpos, kpos, kneg, m, l, acc, scale):
    """Dense-math mirror of the chunk kernel's online-softmax update —
    the backward of :func:`flash_ring_chunk` differentiates THIS (one
    transient (Sq, Skv) block per ring step, rematerialized)."""
    s = jnp.einsum(
        "bqd,bkd->bqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    s = s + slopes[:, None, None] * kpos[:, None, :] + kneg[:, None, :]
    s = s + jnp.where(kpos[:, None, :] <= qpos[:, :, None], 0.0, NEG_INF)
    m_new = jnp.maximum(m, s.max(axis=-1))
    p = jnp.exp(s - m_new[..., None])
    alpha = jnp.exp(m - m_new)
    l_new = l * alpha + p.sum(axis=-1)
    acc_new = acc * alpha[..., None] + jnp.einsum(
        "bqk,bkd->bqd", p, v.astype(jnp.float32)
    )
    return m_new, l_new, acc_new


def flash_ring_chunk(q, k, v, slopes, qpos, kpos, kneg, m, l, acc,
                     scale, interpret, g=1):
    """One FORWARD ring step of flash attention: fused Pallas update of
    the online-softmax state over the resident K/V chunk (no (Sq, Skv)
    score materialization). NOT differentiable on its own — the ring
    owns the backward (see nn/sequence_parallel/ring_attention.py:
    ring_flash_attention, which runs a second gradient ring using
    flash_chunk_dq / flash_chunk_dkv with the FINAL logsumexp), so no
    per-step residuals are stacked by the forward scan. All arrays are
    in the flattened (batch*heads, seq, head_dim) layout; state is f32."""
    interpret = _resolve_interpret(interpret)
    bq, bk = _pick_block(q.shape[1], 128), _pick_block(k.shape[1], 512)
    return _flash_chunk_pallas(
        q, k, v, slopes, qpos, kpos, kneg, m, l, acc, scale, bq, bk, interpret, g
    )


def _chunk_dq_pallas(q, k, v, do, lse, delta, slopes, qpos, kpos, kneg,
                     scale, block_q, block_k, interpret, g=1):
    """dQ contribution of ONE ring chunk, from the FINAL logsumexp (the
    standard flash backward identity p = exp(s - lse) holds globally, so
    per-chunk contributions just add). Position-array causal mask with a
    value-based fully-future block skip, like the forward chunk."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, sq, hd = q.shape
    skv = k.shape[1]
    nq, nk = sq // block_q, skv // block_k

    def kernel(slope_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               qpos_ref, kpos_ref, kneg_ref, dq_ref, dq_sc):
        ki = pl.program_id(2)
        slope = slope_ref[pl.program_id(0)]

        @pl.when(ki == 0)
        def _init():
            dq_sc[:] = jnp.zeros_like(dq_sc)

        qp = qpos_ref[0, 0].astype(jnp.float32)
        kp = kpos_ref[0, 0].astype(jnp.float32)

        @pl.when(jnp.min(kp) <= jnp.max(qp))
        def _compute():
            qb = q_ref[0].astype(jnp.float32)
            kb = k_ref[0].astype(jnp.float32)
            vb = v_ref[0].astype(jnp.float32)
            dob = do_ref[0].astype(jnp.float32)
            s_blk = jax.lax.dot_general(
                qb, kb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale
            s_blk = s_blk + slope * kp[None, :] + kneg_ref[0, 0][None, :]
            s_blk = s_blk + jnp.where(kp[None, :] <= qp[:, None], 0.0, NEG_INF)
            p = jnp.exp(s_blk - lse_ref[0, 0][:, None])
            dp = jax.lax.dot_general(
                dob, vb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            ds = p * (dp - delta_ref[0, 0][:, None])
            dq_sc[:] += scale * jax.lax.dot_general(
                ds, kb, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

        @pl.when(ki == nk - 1)
        def _finish():
            dq_ref[0] = dq_sc[:]

    grid = (bh, nq, nk)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bh,), lambda b, i, j: (0,), memory_space=pltpu.SMEM),
                pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b // g, j, 0)),
                pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b // g, j, 0)),
                pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
                pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
                pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
                pl.BlockSpec((1, 1, block_k), lambda b, i, j: (b // g, 0, j)),
                pl.BlockSpec((1, 1, block_k), lambda b, i, j: (b // g, 0, j)),
            ],
            out_specs=pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
            scratch_shapes=[pltpu.VMEM((block_q, hd), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((bh, sq, hd), jnp.float32),  # per q-head
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_ring_dq",
    )(slopes, q, k, v, do, lse[:, None, :], delta[:, None, :],
      qpos[:, None, :], kpos[:, None, :], kneg[:, None, :])


def _chunk_dkv_pallas(q, k, v, do, lse, delta, slopes, qpos, kpos, kneg,
                      scale, block_q, block_k, interpret, g=1):
    """dK/dV contributions of ONE ring chunk from THIS rank's queries
    (accumulated into ring-riding gradient carriers by the caller)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, sq, hd = q.shape
    skv = k.shape[1]
    nq, nk = sq // block_q, skv // block_k

    def kernel(slope_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               qpos_ref, kpos_ref, kneg_ref, dk_ref, dv_ref, dk_sc, dv_sc):
        qi = pl.program_id(2)
        slope = slope_ref[pl.program_id(0)]

        @pl.when(qi == 0)
        def _init():
            dk_sc[:] = jnp.zeros_like(dk_sc)
            dv_sc[:] = jnp.zeros_like(dv_sc)

        qp = qpos_ref[0, 0].astype(jnp.float32)
        kp = kpos_ref[0, 0].astype(jnp.float32)

        @pl.when(jnp.min(kp) <= jnp.max(qp))
        def _compute():
            qb = q_ref[0].astype(jnp.float32)
            kb = k_ref[0].astype(jnp.float32)
            vb = v_ref[0].astype(jnp.float32)
            dob = do_ref[0].astype(jnp.float32)
            s_blk = jax.lax.dot_general(
                qb, kb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale
            s_blk = s_blk + slope * kp[None, :] + kneg_ref[0, 0][None, :]
            s_blk = s_blk + jnp.where(kp[None, :] <= qp[:, None], 0.0, NEG_INF)
            p = jnp.exp(s_blk - lse_ref[0, 0][:, None])
            dv_sc[:] += jax.lax.dot_general(
                p, dob, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            dp = jax.lax.dot_general(
                dob, vb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            ds = p * (dp - delta_ref[0, 0][:, None])
            dk_sc[:] += scale * jax.lax.dot_general(
                ds, qb, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

        @pl.when(qi == nq - 1)
        def _finish():
            dk_ref[0] = dk_sc[:]
            dv_ref[0] = dv_sc[:]

    grid = (bh, nk, nq)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bh,), lambda b, j, i: (0,), memory_space=pltpu.SMEM),
                pl.BlockSpec((1, block_q, hd), lambda b, j, i: (b, i, 0)),
                pl.BlockSpec((1, block_k, hd), lambda b, j, i: (b // g, j, 0)),
                pl.BlockSpec((1, block_k, hd), lambda b, j, i: (b // g, j, 0)),
                pl.BlockSpec((1, block_q, hd), lambda b, j, i: (b, i, 0)),
                pl.BlockSpec((1, 1, block_q), lambda b, j, i: (b, 0, i)),
                pl.BlockSpec((1, 1, block_q), lambda b, j, i: (b, 0, i)),
                pl.BlockSpec((1, 1, block_q), lambda b, j, i: (b, 0, i)),
                pl.BlockSpec((1, 1, block_k), lambda b, j, i: (b // g, 0, j)),
                pl.BlockSpec((1, 1, block_k), lambda b, j, i: (b // g, 0, j)),
            ],
            out_specs=[
                pl.BlockSpec((1, block_k, hd), lambda b, j, i: (b, j, 0)),
                pl.BlockSpec((1, block_k, hd), lambda b, j, i: (b, j, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_k, hd), jnp.float32),
                pltpu.VMEM((block_k, hd), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((bh, skv, hd), jnp.float32),
            jax.ShapeDtypeStruct((bh, skv, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_ring_dkv",
    )(slopes, q, k, v, do, lse[:, None, :], delta[:, None, :],
      qpos[:, None, :], kpos[:, None, :], kneg[:, None, :])


def flash_chunk_dq(q, k, v, do, lse, delta, slopes, qpos, kpos, kneg,
                   scale, interpret, g=1):
    interpret = _resolve_interpret(interpret)
    bq, bk = _pick_block(q.shape[1], 128), _pick_block(k.shape[1], 512)
    return _chunk_dq_pallas(q, k, v, do, lse, delta, slopes, qpos, kpos, kneg,
                            scale, bq, bk, interpret, g)


def flash_chunk_dkv(q, k, v, do, lse, delta, slopes, qpos, kpos, kneg,
                    scale, interpret, g=1):
    """dK/dV contributions are PER QUERY HEAD (b*nh rows) even under GQA
    — the caller sums each g-group into the (b*nkv)-row carriers (same
    contract as the non-ring dkv kernel)."""
    interpret = _resolve_interpret(interpret)
    bq, bk = _pick_block(q.shape[1], 128), _pick_block(k.shape[1], 512)
    return _chunk_dkv_pallas(q, k, v, do, lse, delta, slopes, qpos, kpos, kneg,
                             scale, bq, bk, interpret, g)


def _xla_reference(q, k, v, slopes, scale, causal, kpos=None, kneg=None):
    """Plain XLA attention with the same semantics (non-TPU fallback and
    the reference the kernels are tested against)."""
    bh, s, hd = q.shape
    scores = jnp.einsum(
        "bqd,bkd->bqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    if kpos is None:
        kpos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.float32)[None], (bh, s))
    if kneg is None:
        kneg = jnp.zeros((bh, s), jnp.float32)
    scores = scores + slopes[:, None, None] * kpos[:, None, :] + kneg[:, None, :]
    if causal:
        keep = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
        scores = jnp.where(keep[None], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32)).astype(q.dtype)


def _resolve_interpret(interpret):
    # None = choose by platform: compiled on the TPU, the interpreter
    # elsewhere. chip_smoke.py's tpu_custom_call assertions guard that
    # the chip really takes the compiled kernel.
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def _blocks(q, kind, paired=False):
    """``_pick_blocks`` on this device for ``q`` as its kernels take it:
    flattened ``(bh, seq, hd)``, a head a tile ``hd`` wide, or, where
    ``paired``, ``(B, seq, nh * 64)``, two heads a 128-lane tile."""
    if paired:
        return _pick_blocks(q.shape[1], _PAIR_LANES, q.dtype.itemsize,
                            kind + "_paired", _vmem_limit_bytes())
    return _pick_blocks(q.shape[1], q.shape[2], q.dtype.itemsize, kind,
                        _vmem_limit_bytes())


def _pairs_heads(seq, nh, hd, g, itemsize) -> bool:
    """Whether a call takes the paired layout: two heads fill one
    128-lane tile (head width 64, an even number of heads, as many key
    heads as query heads) and the one-kernel backward fits at the paired
    blocks. Decided ONCE a call, from its shapes and the device, for the
    forward, the saved residuals and the backward alike."""
    if hd != _PAIRED_HEAD_DIM or g != 1 or nh % _PAIR:
        return False
    limit = _vmem_limit_bytes()
    blocks = _pick_blocks(seq, _PAIR_LANES, itemsize, "bwd_paired", limit)
    return _fits("bwd_paired", *blocks, _PAIR_LANES, itemsize, seq, limit)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10))
def _flash(q, k, v, slopes, kpos, kneg, scale, causal, interpret, g=1,
           window=None):
    out, _ = _flash_fwd_pallas(
        q, k, v, slopes, kpos, kneg, scale, causal,
        *_blocks(q, "fwd"),
        _resolve_interpret(interpret), g, window,
    )
    return out


def _flash_fwd(q, k, v, slopes, kpos, kneg, scale, causal, interpret, g=1,
               window=None):
    out, lse = _flash_fwd_pallas(
        q, k, v, slopes, kpos, kneg, scale, causal,
        *_blocks(q, "fwd"),
        _resolve_interpret(interpret), g, window,
    )
    # named INSIDE the rule: what backward needs is these two values,
    # and a name outside the custom call would leave lse to recompute
    out, lse = (checkpoint_name(x, name)
                for x, name in zip((out, lse), RESIDUAL_NAMES))
    return out, (q, k, v, slopes, kpos, kneg, out, lse)


def _flash_bwd(scale, causal, interpret, g, window, res, ct):
    q, k, v, slopes, kpos, kneg, out, lse = res
    interpret = _resolve_interpret(interpret)
    delta = (ct.astype(jnp.float32) * out.astype(jnp.float32)).sum(-1)  # (bh, s)
    operands = (q, k, v, ct, lse, delta, slopes, kpos, kneg, scale, causal)
    seq, hd = q.shape[1], q.shape[2]
    blocks = _blocks(q, "bwd")
    if _fits("bwd", *blocks, hd, q.dtype.itemsize, seq, _vmem_limit_bytes()):
        dq, dk, dv = _flash_bwd_pallas(*operands, *blocks, interpret, g, window)
    else:
        # the whole-sequence accumulator of dQ does not fit beside the
        # smallest blocks: the same sums, the score tiles formed twice
        dq = _flash_dq_pallas(*operands, *_blocks(q, "dq"), interpret, g,
                              window)
        dk, dv = _flash_dkv_pallas(*operands, *_blocks(q, "dkv"), interpret,
                                   g, window)
    if g > 1:
        # per-query-head contributions -> shared kv heads (rows ordered
        # so g consecutive query heads share one kv row)
        dk = dk.reshape(-1, g, seq, hd).sum(1).astype(k.dtype)
        dv = dv.reshape(-1, g, seq, hd).sum(1).astype(v.dtype)
    return dq, dk, dv, jnp.zeros_like(slopes), jnp.zeros_like(kpos), jnp.zeros_like(kneg)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9))
def _flash_paired(q, k, v, slopes, kpos, kneg, scale, causal, interpret,
                  window=None):
    """``_flash`` in the paired layout: q, k, v and the result ``(B, S,
    nh * 64)``, slopes ``(B * nh,)``, per-key rows ``(B, S)``."""
    return _flash_paired_fwd(q, k, v, slopes, kpos, kneg, scale, causal,
                             interpret, window)[0]


def _flash_paired_fwd(q, k, v, slopes, kpos, kneg, scale, causal, interpret,
                      window=None):
    out, lse = _flash_fwd_paired_pallas(
        q, k, v, slopes, kpos, kneg, scale, causal,
        *_blocks(q, "fwd", paired=True), _resolve_interpret(interpret), window,
    )
    # named INSIDE the rule, as ``_flash_fwd`` names them
    out, lse = (checkpoint_name(x, name)
                for x, name in zip((out, lse), RESIDUAL_NAMES))
    return out, (q, k, v, slopes, kpos, kneg, out, lse)


def _flash_paired_bwd(scale, causal, interpret, window, res, ct):
    q, k, v, slopes, kpos, kneg, out, lse = res
    dq, dk, dv = _flash_bwd_paired_pallas(
        q, k, v, ct, out, lse, slopes, kpos, kneg, scale, causal,
        *_blocks(q, "bwd", paired=True), _resolve_interpret(interpret), window,
    )
    return dq, dk, dv, jnp.zeros_like(slopes), jnp.zeros_like(kpos), jnp.zeros_like(kneg)


_flash_paired.defvjp(_flash_paired_fwd, _flash_paired_bwd)


def flash_attention(
    q: jax.Array,  # (B, S, nh, hd)
    k: jax.Array,  # (B, S, nh | nkv, hd) — fewer kv heads = native GQA
    v: jax.Array,
    alibi_slopes: Optional[jax.Array] = None,  # (nh,)
    attention_mask: Optional[jax.Array] = None,  # (B, S) 1=keep 0=pad
    kv_pos: Optional[jax.Array] = None,  # (B, S) ALiBi position per key
    kv_neg: Optional[jax.Array] = None,  # (B, S) 0 valid / NEG_INF padded
    causal: bool = True,
    scale: Optional[float] = None,
    interpret: Optional[bool] = None,
    window: Optional[int] = None,  # sliding window (Mistral semantics)
) -> jax.Array:
    """Fused attention. Returns (B, S, nh, hd).

    Padding: pass either ``attention_mask`` (positions derived with
    BLOOM's mask-aware cumsum, matching ``models.bloom.build_alibi``) or
    precomputed ``kv_pos``/``kv_neg`` arrays.

    GQA: when ``k``/``v`` carry fewer heads than ``q`` (``nh = g *
    nkv``, query head h sharing kv head h // g like HF), the kernels
    read the shared K/V directly via grouped index maps — K/V are never
    repeated in HBM, so KV read traffic shrinks by g.

    Layout: one of two, chosen ONCE a call from its shapes and the
    device (``_pairs_heads``) and held for the forward, the saved
    residuals and the backward. Where two heads fill one 128-lane tile
    (``hd == 64``, an even ``nh``, ``nkv == nh``, and the one-kernel
    backward fits VMEM at the paired blocks) the kernels take q, k, v,
    dO and return the result, dQ, dK, dV as ``(B, S, nh * hd)``, the
    reshape of what the caller holds: nothing is transposed and no tile
    is half padding (bloom-560m). Every other call (widths of 128 and
    more, GQA, an odd head count) takes heads over positions,
    ``(B * nh, S, hd)``, a head a tile, through the kernels as they
    were. Counters ``flash.calls`` and ``flash.paired_calls``
    (``telemetry.get_registry()``) count the calls of each once PER
    TRACE (a checkpointed or scanned block is traced more than once a
    compile, and counts each time): which layout a program took, not
    how many kernels it holds.
    """
    b, s, nh, hd = q.shape
    nkv = k.shape[2]
    if nh % nkv:
        raise ValueError(f"n_head={nh} must be a multiple of n_kv_head={nkv}")
    g = nh // nkv
    if scale is None:
        scale = hd**-0.5
    if alibi_slopes is None:
        alibi_slopes = jnp.zeros((nh,), jnp.float32)
    if attention_mask is not None and (kv_pos is None or kv_neg is None):
        # fill only what the caller did not provide (a custom kv_pos may
        # legitimately accompany a mask, e.g. offset decode positions)
        pos, neg = mask_to_kv_bias(attention_mask)
        kv_pos = pos if kv_pos is None else kv_pos
        kv_neg = neg if kv_neg is None else kv_neg
    if kv_pos is None:
        kv_pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.float32)[None], (b, s))
    if kv_neg is None:
        kv_neg = jnp.zeros((b, s), jnp.float32)

    slopes = jnp.broadcast_to(alibi_slopes[None], (b, nh)).reshape(b * nh)
    window = int(window) if window is not None else None

    from pipegoose_tpu.telemetry.registry import get_registry

    # counted per TRACE of this call: the layout is a property of the
    # traced program, not of a step
    registry = get_registry()
    registry.counter("flash.calls").inc(of_trace=True)
    if _pairs_heads(s, nh, hd, g, q.dtype.itemsize):
        registry.counter("flash.paired_calls").inc(of_trace=True)
        out = _flash_paired(
            *(x.reshape(b, s, nh * hd) for x in (q, k, v)),
            slopes.astype(jnp.float32), kv_pos.astype(jnp.float32),
            kv_neg.astype(jnp.float32), float(scale), causal, interpret,
            window,
        )
        return out.reshape(b, s, nh, hd)

    def flat(x):
        h = x.shape[2]
        return x.transpose(0, 2, 1, 3).reshape(b * h, s, hd)

    def flat_bs(x, h):  # (B, S) -> (B*h, S)
        return jnp.broadcast_to(
            x.astype(jnp.float32)[:, None, :], (b, h, s)
        ).reshape(b * h, s)

    out = _flash(
        flat(q), flat(k), flat(v), slopes.astype(jnp.float32),
        flat_bs(kv_pos, nkv), flat_bs(kv_neg, nkv), float(scale), causal,
        interpret, g, window,
    )
    return out.reshape(b, nh, s, hd).transpose(0, 2, 1, 3)
