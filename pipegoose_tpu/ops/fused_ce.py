"""Fused vocab-parallel cross-entropy (Pallas, TPU) — forward AND backward.

The single largest HBM consumer of the bloom-560m train step is the
(B, S, V) fp32 logits buffer (~8 GB at b8 x s1024 x v250880); the
chunked-CE fallback (chunked_ce_sums) bounds it but pays ~7%
throughput for the chunk-boundary logit recompute. This
kernel computes the loss STRAIGHT from (hidden, embedding) with an
online log-sum-exp over vocab tiles — the full logits tensor never
exists in HBM, forward or backward:

- forward: ONE kernel (``fused_ce_fwd``), grid (token super-block,
  vocabulary tile), vocabulary sequential; the super-block's hidden
  states and its per-token online (max, sumexp, target-logit) triple
  stay in VMEM while the vocabulary is walked, a grid step takes the
  super-block a token tile at a time against one weight tile; emits
  per-token local ``lse`` and ``target_logit``. Its tiles come from the
  operands' shapes and the device's VMEM (``_pick_fwd_plan``), not from
  a caller.
- backward: dlogits = softmax - onehot is rematerialized tile-by-tile
  from the saved GLOBAL lse (Megatron's analytic CE backward, reference
  loss.py:71-89, without ever holding more than one (BT, BV) tile), in
  ONE kernel (``fused_ce_bwd``): each tile is formed once and feeds both
  dhidden (dlogits @ W_tile) and dweight (dlogits^T @ h_tile), so a step
  runs four head matmuls (forward 1, backward 3). Grid (token
  super-block, vocab tile, token tile): the super-block's dhidden stays
  in VMEM while the vocabulary is walked; dweight's tile is summed over
  the super-block's tokens in VMEM and carried from one super-block to
  the next through its float32 result in HBM (``_bwd_pallas``). The
  super-block comes from the operands' shapes and the device's VMEM
  (``_pick_super_block``), not from a caller.

Tensor-parallel semantics match ``vocab_parallel_cross_entropy``
(nn/tensor_parallel/layers.py): the kernel works on the LOCAL vocab
shard; the wrapper combines shards with a max+log-sum-exp reduction and
a psum of the (exactly-one-shard-hit) target logit, and the hand-written
VJP psums the hidden cotangent over the axis — the same load-bearing
all-reduce as logits_fn's f-operator (models/bloom.py:366-373), here
fused into the custom backward. Padded vocab slots (pad_vocab) are
masked by GLOBAL column index against ``valid_size``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from pipegoose_tpu.ops import flash_attention

NEG_INF = -1e9


def _resolve_interpret(interpret):
    # None = choose by platform, as in ops/flash_attention.py (guarded
    # by chip_smoke.py's tpu_custom_call assertions)
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def _token_block(tokens: int, cap: int) -> int:
    """Token tile: the least power of two (>= 8) that holds ``tokens``,
    at most ``cap`` (tokens are padded up to it)."""
    block = 8
    while block < min(tokens, cap):
        block *= 2
    return min(block, cap)


def _pick_block(n: int, target: int):
    """Largest halving of ``target`` (>= 8) dividing ``n``. Returns
    ``(block, exact)`` — ``exact=False`` means NO such divisor exists
    and the fallback is the whole dim as one tile, which callers must
    treat as infeasible for compiled TPU runs (a non-8-aligned or
    whole-vocab tile dies in Mosaic; ADVICE r5)."""
    b = target
    while b >= 8:
        if n % b == 0:
            return b, True
        b //= 2
    return n, False


# tokens a matmul tile of the forward: the (tile, vocabulary tile) float32
# logits and what is formed from them are the kernel's temporaries, so
# the tile stays small and the vocabulary tile takes the VMEM
_FWD_BLOCK_T = 256
# tokens the forward aims to hold while the vocabulary is walked: at 256
# the head's reads take as long as the matmul at a v5e's peaks (the
# kernel on both rooflines at once), at 1,024 a quarter of it. Alone on
# the chip the reads hid under the matmul even at 256 (PERF.md, PR 50):
# the mark buys margin, the time comes from the vocabulary tile
_FWD_RESIDENT_TOKENS = 1024
# vocabulary rows a tile at most, and what the picker takes as "enough"
# when it weighs a larger tile against more resident tokens: the cost of
# the per-token columns falls as 1 / tile and is within 1% of its floor
# past ~2,048 (560m shape: 55.0 ms at 1,024 rows, 49.2 at 2,560, 48.8
# at 3,584)
_FWD_MAX_BLOCK_V = 4096
_FWD_ENOUGH_BLOCK_V = 2048
_LANES = 128


def _fwd_vocab_tiles(v_loc: int):
    """Vocabulary tiles the forward may take, ascending: the divisors of
    the shard's rows that are whole lane tiles of the logits (multiples
    of 128) up to ``_FWD_MAX_BLOCK_V``; where there is none, the
    multiples of 8 (a sublane tile of the weight). Empty where 8 does
    not divide the shard: no tile a compiled kernel can take."""
    for unit in (_LANES, 8):
        tiles = [d for d in range(unit, min(v_loc, _FWD_MAX_BLOCK_V) + 1, unit)
                 if v_loc % d == 0]
        if tiles:
            return tiles
    return []


def _fwd_working_set_bytes(super_t: int, block_t: int, block_v: int,
                           hidden: int, itemsize: int) -> int:
    """VMEM the forward kernel holds, by its own arithmetic: the resident
    tokens' ``h`` and the weight tile, both pipelined (twice); a token
    tile's and the weight tile's float32 casts; the (BT, BV) float32
    tiles of logits, ``p`` and the two selects; the per-token columns
    (running max, sum, target logit, target id: a (N, 1) column takes a
    lane tile a sublane) and the three pipelined (1, N) rows (targets
    in, ``lse`` and the target logit out: 8 sublanes each). An upper
    bound, held to the chip's compiler by tests/ops/test_chip_compile.py."""
    return (2 * super_t * hidden * itemsize + 2 * block_v * hidden * itemsize
            + (block_t + block_v) * hidden * 4
            + 4 * block_t * block_v * 4
            + 4 * super_t * _LANES * 4 + 3 * 2 * 8 * super_t * 4)


def _pick_fwd_plan(tokens: int, v_loc: int, hidden: int, itemsize: int,
                   vmem_limit_bytes: int):
    """``(block_t, token tiles resident, super-blocks, block_v)`` of the
    forward from the operands' shape and the scoped VMEM the kernel will
    ask for; where ``_fwd_vocab_tiles`` has no tile for the shard, the
    whole of it is one (the interpreter's alone).

    Two things are paid beside the matmul. The head is read once a
    super-block, so its bytes fall with the RESIDENT tokens (at 256 as
    long as the matmul, at 1,024 a quarter of it; on a v5e they hide
    under the matmul either way). The running max, sum and target logit
    are (N, 1) columns, one lane of 128 in use, touched once a (token,
    vocabulary tile), and a grid step's fixed cost is paid once a
    (super-block, vocabulary tile): both fall with the VOCABULARY tile,
    and they were the whole ~0.8 us a step the caller's 256 x 512 tile
    lost (PERF.md, PR 50). The token tile inside a step stays
    ``_FWD_BLOCK_T``: it sizes the float32 temporaries and buys nothing
    larger. So, from
    one token tile and the smallest vocabulary tile, whichever of the two
    still fits three quarters of the limit (``_fwd_working_set_bytes``)
    grows: the resident tokens by doubling up to ``_MAX_SUPER_TOKENS``,
    the vocabulary tile along ``_fwd_vocab_tiles``; where both fit, the
    one further below its mark (``_FWD_RESIDENT_TOKENS``,
    ``_FWD_ENOUGH_BLOCK_V``). Then the tiles are evened out over the
    super-blocks, so that the tokens are padded by less than a tile
    each."""
    block_t = _token_block(tokens, _FWD_BLOCK_T)
    nt = -(-tokens // block_t)
    tiles = _fwd_vocab_tiles(v_loc)
    if not tiles:  # the whole shard as one tile: the interpreter's
        return block_t, 1, nt, v_loc
    budget = vmem_limit_bytes * 3 // 4

    def fits(ni, bv):
        return _fwd_working_set_bytes(ni * block_t, block_t, bv, hidden,
                                      itemsize) <= budget

    ni, k = 1, 0
    while True:
        more_t = (ni < nt and 2 * ni * block_t <= _MAX_SUPER_TOKENS
                  and fits(2 * ni, tiles[k]))
        more_v = k + 1 < len(tiles) and fits(ni, tiles[k + 1])
        if not (more_t or more_v):
            break
        if more_t and (not more_v or ni * block_t * _FWD_ENOUGH_BLOCK_V
                       < tiles[k] * _FWD_RESIDENT_TOKENS):
            ni *= 2
        else:
            k += 1
    n_super = -(-nt // ni)
    return block_t, -(-nt // n_super), n_super, tiles[k]


def _fwd_pallas(h, w, targets, offset, valid, interpret, vh):
    """Per-token ``(lse, target logit)`` of the LOCAL shard. Grid (token
    super-block, vocabulary tile), vocabulary sequential: the
    super-block's ``h`` and its per-token columns (running max, sum,
    target logit) stay in VMEM while the vocabulary is walked, so the
    head is read once a super-block; inside a grid step a loop takes the
    super-block a token tile at a time against the one weight tile, so
    the float32 logits never pass (block_t, block_v). The plan is the
    kernel's own (``_pick_fwd_plan``)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from pipegoose_tpu.telemetry.registry import get_registry

    t_tot, hd = h.shape
    v_loc = w.shape[0] if vh else w.shape[1]
    limit = flash_attention._vmem_limit_bytes()
    block_t, ni, n_super, block_v = _pick_fwd_plan(
        t_tot, v_loc, hd, h.dtype.itemsize, limit)
    super_t, nv = ni * block_t, v_loc // block_v
    held = _fwd_working_set_bytes(super_t, block_t, block_v, hd,
                                  h.dtype.itemsize)
    # counted per TRACE: the plan is a property of the traced program
    registry = get_registry()
    registry.counter("fused_ce.fwd_calls").inc(of_trace=True)
    registry.gauge("fused_ce.fwd_grid_steps").set(n_super * nv, of_trace=True)
    registry.gauge("fused_ce.fwd_head_walks").set(n_super, of_trace=True)
    registry.gauge("fused_ce.fwd_vmem_bytes").set(held, of_trace=True)
    pad = n_super * super_t - t_tot
    if pad:  # their lse and target logit are cut off below
        h = jnp.pad(h, ((0, pad), (0, 0)))
        targets = jnp.pad(targets, (0, pad))

    def kernel(off_ref, h_ref, w_ref, t_ref, lse_ref, tl_ref,
               m_sc, l_sc, t_sc, id_sc):
        vi = pl.program_id(1)

        @pl.when(vi == 0)
        def _enter_super_block():
            m_sc[...] = jnp.full_like(m_sc, NEG_INF)
            l_sc[...] = jnp.zeros_like(l_sc)
            t_sc[...] = jnp.zeros_like(t_sc)
            id_sc[...] = t_ref[0][:, None]  # targets as a column, once

        # a tile's columns are local: the global column of lane c is
        # first + c, so col < valid is c < valid - first and the target
        # sits at lane target - first
        first = off_ref[0] + vi * block_v
        lane = jax.lax.broadcasted_iota(jnp.int32, (block_t, block_v), 1)

        def token_tile(i, carry):
            rows = pl.ds(pl.multiple_of(i * block_t, block_t), block_t)
            hb = h_ref[rows, :].astype(jnp.float32)  # (BT, H)
            wb = w_ref[...].astype(jnp.float32)      # (BV, H) | (H, BV)
            logits = jax.lax.dot_general(
                hb, wb, (((1,), (1,) if vh else (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # (BT, BV)
            if valid is not None:
                logits = jnp.where(lane < valid - first, logits, NEG_INF)
            hit = lane == id_sc[rows, :] - first
            t_sc[rows, :] += jnp.where(hit, logits, 0.0).sum(
                axis=1, keepdims=True)
            m_prev = m_sc[rows, :]
            m_new = jnp.maximum(m_prev, logits.max(axis=1, keepdims=True))
            p = jnp.exp(logits - m_new)
            l_sc[rows, :] = (l_sc[rows, :] * jnp.exp(m_prev - m_new)
                             + p.sum(axis=1, keepdims=True))
            m_sc[rows, :] = m_new
            return carry

        jax.lax.fori_loop(0, ni, token_tile, 0)

        @pl.when(vi == nv - 1)
        def _leave_super_block():
            lse = m_sc[...] + jnp.log(jnp.maximum(l_sc[...], 1e-30))
            lse_ref[0] = lse[:, 0]
            tl_ref[0] = t_sc[...][:, 0]

    row = pl.BlockSpec((1, super_t), lambda s, j: (0, s))
    column = pltpu.VMEM((super_t, 1), jnp.float32)
    lse, tl = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0,
            grid=(n_super, nv),
            in_specs=[
                pl.BlockSpec((1,), lambda s, j: (0,),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec((super_t, hd), lambda s, j: (s, 0)),
                pl.BlockSpec((block_v, hd), lambda s, j: (j, 0))
                if vh else
                pl.BlockSpec((hd, block_v), lambda s, j: (0, j)),
                row,
            ],
            out_specs=[row, row],
            scratch_shapes=[column, column, column,
                            pltpu.VMEM((super_t, 1), jnp.int32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((1, n_super * super_t), jnp.float32),
            jax.ShapeDtypeStruct((1, n_super * super_t), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            # where even one token tile passes the limit (a device
            # Pallas does not know: the compiler's default), the kernel
            # asks for what that one tile needs
            vmem_limit_bytes=max(limit, held),
        ),
        interpret=interpret,
        name="fused_ce_fwd",
    )(offset, h, w, targets[None, :])
    return lse[0, :t_tot], tl[0, :t_tot]


def _dlogits_tile(hb, wb, tb, lse_b, g_b, off, vi, block_t, block_v, valid,
                  vh=True):
    """One (BT, BV) dlogits tile: g * (softmax - onehot), rebuilt from
    the saved global lse. ``vh``: the weight tile is (BV, H) (tied
    embedding) vs (H, BV) (untied head)."""
    logits = jax.lax.dot_general(
        hb, wb, (((1,), (1,) if vh else (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    col = off + vi * block_v + jax.lax.broadcasted_iota(
        jnp.int32, (block_t, block_v), 1
    )
    if valid is not None:
        logits = jnp.where(col < valid, logits, NEG_INF)
    p = jnp.exp(logits - lse_b[:, None])  # padded cols: exp(-inf) = 0
    hit = tb[:, None] == col
    return g_b[:, None] * (p - jnp.where(hit, 1.0, 0.0))


# tokens a super-block of the backward holds at most: past this the
# carried accumulator's traffic is a few percent of the memory's rate
# and a larger resident dh buys nothing (PERF.md, PR 41)
_MAX_SUPER_TOKENS = 4096


def _bwd_working_set_bytes(super_t: int, block_t: int, block_v: int,
                           hidden: int, itemsize: int) -> int:
    """VMEM the backward kernel holds, by its own arithmetic: the
    super-block's ``h`` and its float32 ``dh``; the pipelined weight
    tile twice; the float32 ``dw`` tile three times (the sum over the
    super-block's tokens, the carried value read, the sum written); a
    grid step's temporaries, float32 all: both operand tiles, both
    products before they are added, and the (BT, BV) tiles of logits,
    ``p``, ``dlogits`` and a select. An upper bound, held to the chip's
    compiler by tests/ops/test_chip_compile.py."""
    w_tile, h_tile = block_v * hidden, block_t * hidden
    return (super_t * hidden * (4 + itemsize)
            + 2 * w_tile * itemsize + 3 * w_tile * 4
            + 2 * (w_tile + h_tile) * 4 + 4 * block_t * block_v * 4)


def _pick_super_block(tokens: int, block_t: int, block_v: int, hidden: int,
                      itemsize: int, vmem_limit_bytes: int):
    """``(token tiles a super-block, super-blocks)`` of the backward
    from the operands' shape and the scoped VMEM the kernel will ask
    for: the most tiles, a power of two, whose working set
    (``_bwd_working_set_bytes``) fits three quarters of the limit, up to
    ``_MAX_SUPER_TOKENS``; then evened out over the super-blocks so that
    the tokens are padded by less than a tile each."""
    nt = -(-tokens // block_t)
    ni = 1
    while (ni < nt and 2 * ni * block_t <= _MAX_SUPER_TOKENS
           and _bwd_working_set_bytes(2 * ni * block_t, block_t, block_v,
                                      hidden, itemsize)
           <= vmem_limit_bytes * 3 // 4):
        ni *= 2
    n_super = -(-nt // ni)
    return -(-nt // n_super), n_super


def _bwd_pallas(h, w, targets, lse, g, offset, valid, block_t, block_v,
                interpret, vh):
    """``(dh, dw)``, both float32, from ONE pass over the (BT, BV) tiles
    of ``dlogits``: each tile is formed once and feeds ``dlogits @ W``
    into ``dh`` and ``dlogits^T @ h`` into ``dw`` (three matmuls of
    2*T*V*H a call where a kernel a product ran four).

    ``dh`` sums over vocabulary tiles and ``dw`` over token tiles, so
    one of them crosses the outer grid axis. Grid (token super-block
    ``s``, vocabulary tile ``j``, token tile ``i``): the super-block's
    ``h`` and float32 ``dh`` stay in VMEM across ``(j, i)``, moved once
    a super-block; ``dw_j`` is summed over ``i`` in VMEM and CARRIED
    over ``s`` through the float32 result in HBM, read, added to and
    written back a tile at a time (first visit: written, not read). The
    copies are the kernel's own: a tile's read starts with its first
    token tile and is waited for with its last; its write is waited for
    a tile later, before the buffer is filled again."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    t_tot, hd = h.shape
    v_loc = w.shape[0] if vh else w.shape[1]
    nv = v_loc // block_v
    limit = flash_attention._vmem_limit_bytes()
    ni, n_super = _pick_super_block(t_tot, block_t, block_v, hd,
                                    h.dtype.itemsize, limit)
    super_t = ni * block_t
    pad = n_super * super_t - t_tot
    if pad:  # weight-0 tokens: g = 0 makes their dlogits 0
        h = jnp.pad(h, ((0, pad), (0, 0)))
        targets, lse, g = (jnp.pad(x, (0, pad)) for x in (targets, lse, g))
    w_tile = (block_v, hd) if vh else (hd, block_v)

    def kernel(off_ref, h_hbm, w_ref, t_ref, lse_ref, g_ref, dh_hbm, dw_hbm,
               h_sc, dh_sc, dw_sc, read_sc, write_sc, sems):
        s, j, i = (pl.program_id(a) for a in range(3))
        first_i, last_i = i == 0, i == ni - 1

        own = pl.ds(s * super_t, super_t)      # the super-block's tokens
        cols = pl.ds(j * block_v, block_v)
        dw_j = dw_hbm.at[cols] if vh else dw_hbm.at[:, cols]
        h_in = pltpu.make_async_copy(h_hbm.at[own], h_sc, sems.at[0])
        dh_out = pltpu.make_async_copy(dh_sc, dh_hbm.at[own], sems.at[1])
        dw_in = pltpu.make_async_copy(dw_j, read_sc, sems.at[2])
        dw_out = pltpu.make_async_copy(write_sc, dw_j, sems.at[3])

        @pl.when(first_i & (j == 0))
        def _enter_super_block():
            h_in.start()
            dh_sc[...] = jnp.zeros_like(dh_sc)
            h_in.wait()

        @pl.when(first_i)
        def _enter_vocab_tile():
            dw_sc[...] = jnp.zeros_like(dw_sc)

        # a single vocabulary tile is read again right after it was
        # written: its write has to land first
        if nv == 1:
            @pl.when(first_i & (s > 0))
            def _land_before_reading():
                dw_out.wait()

        @pl.when(first_i & (s > 0))
        def _read_carried():
            dw_in.start()

        rows = pl.ds(pl.multiple_of(i * block_t, block_t), block_t)
        hb = h_sc[rows, :].astype(jnp.float32)
        wb = w_ref[...].astype(jnp.float32)
        dl = _dlogits_tile(
            hb, wb, t_ref[0], lse_ref[0], g_ref[0],
            off_ref[0], j, block_t, block_v, valid, vh,
        )
        dh_sc[rows, :] += jax.lax.dot_general(
            dl, wb, (((1,), (0,) if vh else (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if vh:
            dw_sc[...] += jax.lax.dot_general(
                dl, hb, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # (BV, H)
        else:
            dw_sc[...] += jax.lax.dot_general(
                hb, dl, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # (H, BV)

        @pl.when(last_i)
        def _leave_vocab_tile():
            if nv > 1:
                @pl.when((s > 0) | (j > 0))
                def _previous_write_landed():
                    dw_out.wait()

            @pl.when(s == 0)
            def _first_visit():
                write_sc[...] = dw_sc[...]

            @pl.when(s > 0)
            def _later_visit():
                dw_in.wait()
                write_sc[...] = read_sc[...] + dw_sc[...]

            dw_out.start()

        @pl.when(last_i & (j == nv - 1))
        def _leave_super_block():
            dh_out.start()
            dh_out.wait()

        @pl.when(last_i & (j == nv - 1) & (s == n_super - 1))
        def _last_write_landed():
            dw_out.wait()

    row = pl.BlockSpec((1, block_t), lambda s, j, i: (0, s * ni + i))
    any_space = pl.BlockSpec(memory_space=pl.ANY)
    dh, dw = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0,
            grid=(n_super, nv, ni),
            in_specs=[
                pl.BlockSpec((1,), lambda s, j, i: (0,),
                             memory_space=pltpu.SMEM),
                any_space,
                pl.BlockSpec(w_tile, lambda s, j, i: (j, 0))
                if vh else
                pl.BlockSpec(w_tile, lambda s, j, i: (0, j)),
                row, row, row,
            ],
            out_specs=[any_space, any_space],
            scratch_shapes=[
                pltpu.VMEM((super_t, hd), h.dtype),
                pltpu.VMEM((super_t, hd), jnp.float32),
                pltpu.VMEM(w_tile, jnp.float32),
                pltpu.VMEM(w_tile, jnp.float32),
                pltpu.VMEM(w_tile, jnp.float32),
                pltpu.SemaphoreType.DMA((4,)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(h.shape, jnp.float32),
            jax.ShapeDtypeStruct(w.shape, jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3,
            # where even one token tile a super-block passes the limit
            # (a device Pallas does not know: the compiler's default),
            # the kernel asks for what that one tile needs
            vmem_limit_bytes=max(limit, _bwd_working_set_bytes(
                super_t, block_t, block_v, hd, h.dtype.itemsize)),
        ),
        interpret=interpret,
        name="fused_ce_bwd",
    )(offset, h, w, targets[None, :], lse[None, :], g[None, :])
    return dh[:t_tot], dw


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9)
)
def _fused_ce(h, w, targets, token_w, axis_name, valid_size, block_t,
              block_v, interpret, vh):
    out, _ = _fused_ce_fwd(
        h, w, targets, token_w, axis_name, valid_size, block_t, block_v,
        interpret, vh,
    )
    return out


def _shard_offset(axis_name, v_local):
    off = jax.lax.axis_index(axis_name) * v_local if axis_name else 0
    return jnp.asarray([off], jnp.int32)


def _combine(lse_l, tl_l, axis_name):
    """Local-shard (lse, target_logit) -> global: max + log-sum-exp over
    shards for lse; the target column lives on exactly one shard (hits
    elsewhere sum to 0), so its psum is the true pick."""
    if not axis_name:
        return lse_l, tl_l
    m = jax.lax.pmax(lse_l, axis_name)
    lse = m + jnp.log(jax.lax.psum(jnp.exp(lse_l - m), axis_name))
    return lse, jax.lax.psum(tl_l, axis_name)


def _fused_ce_fwd(h, w, targets, token_w, axis_name, valid_size, block_t,
                  block_v, interpret, vh):
    offset = _shard_offset(axis_name, w.shape[0] if vh else w.shape[1])
    lse_l, tl_l = _fwd_pallas(h, w, targets, offset, valid_size, interpret,
                              vh)
    lse, tl = _combine(lse_l, tl_l, axis_name)
    loss_sum = ((lse - tl) * token_w).sum()
    return (loss_sum, token_w.sum()), (h, w, targets, token_w, lse)


def _fused_ce_bwd(axis_name, valid_size, block_t, block_v, interpret, vh,
                  res, cts):
    h, w, targets, token_w, lse = res
    ct_loss, _ = cts  # weight_sum is a non-diff count
    g = (ct_loss * token_w).astype(jnp.float32)
    offset = _shard_offset(axis_name, w.shape[0] if vh else w.shape[1])
    dh, dw = _bwd_pallas(
        h, w, targets, lse, g, offset, valid_size, block_t, block_v,
        interpret, vh,
    )
    dh, dw = dh.astype(h.dtype), dw.astype(w.dtype)
    if axis_name:
        # each shard's dh holds only its vocab rows' contribution; the
        # true hidden cotangent is the sum — the f-operator all-reduce
        # (models/bloom.py logits_fn), fused into this backward
        dh = jax.lax.psum(dh, axis_name)
    return dh, dw, None, None


_fused_ce.defvjp(_fused_ce_fwd, _fused_ce_bwd)


def fused_ce_sums(
    hidden: jax.Array,   # (T, H) tokens already aligned with targets
    weight: jax.Array,   # (V_local, H) (tied) embedding shard
    targets: jax.Array,  # (T,) GLOBAL target ids
    token_w: jax.Array,  # (T,) float weights (0 = ignored position)
    axis_name: Optional[str] = None,
    valid_size: Optional[int] = None,
    block_t: int = 256,
    block_v: int = 512,
    interpret: Optional[bool] = None,
    weight_layout: str = "vh",
):
    """(weighted loss sum, weight sum) of the vocab-parallel CE, fused.

    Same contract as chunked_ce_sums' return (callers divide), same TP
    and padded-vocab semantics as vocab_parallel_cross_entropy — but no
    logits buffer and no chunk recompute. Pads T up to the token block
    (weight-0 pad tokens). ``block_t`` and ``block_v`` are the
    BACKWARD's tile (its super-block is its own, ``_pick_super_block``);
    the forward plans every tile of its own (``_pick_fwd_plan``).

    ``weight_layout``: "vh" = (V_local, H) (bloom's tied embedding),
    "hv" = (H, V_local) (llama/mixtral's untied column-parallel head) —
    both read the weight in its native layout, no transpose copy."""
    if weight_layout not in ("vh", "hv"):
        raise ValueError(f"weight_layout must be 'vh' or 'hv', got "
                         f"{weight_layout!r}")
    vh = weight_layout == "vh"
    t = hidden.shape[0]
    # token blocks stay powers of two (pad T up); vocab blocks must
    # divide V_local (pad_vocab guarantees power-of-two-friendly shards)
    block_t = _token_block(t, block_t)
    v_loc = weight.shape[0] if vh else weight.shape[1]
    requested_v = block_v
    block_v, exact_v = _pick_block(v_loc, block_v)
    interpret = _resolve_interpret(interpret)
    if not (exact_v and _fwd_vocab_tiles(v_loc)) and not interpret:
        # _pick_block's fallback (and the forward's, where not even 8
        # divides the shard) is the WHOLE vocab dim as one tile.
        # Whether V_local is larger than the requested block (a
        # (V_local, H) fp32 tile cannot fit VMEM) or merely smaller but
        # not 8-aligned (Mosaic rejects the ragged tile), the compiled
        # run would die with an opaque Mosaic error that interpret-mode
        # tests never see (ADVICE r5) — fail loudly here, but only for
        # compiled runs: the interpreter has no VMEM limit or tile
        # alignment and the whole-vocab tile is valid there.
        raise ValueError(
            f"fused CE: no block size >= 8 among halvings of "
            f"{requested_v} divides V_local={v_loc}, and a single "
            f"(V_local={v_loc}, H) whole-vocab tile is VMEM-infeasible "
            f"(or not 8-aligned) on hardware. Pad the vocab shard to a "
            f"power-of-two-friendly size (pad_for_tp / pad_vocab) or "
            f"pass a block_v dividing it."
        )
    if t % block_t:
        pad = block_t - t % block_t
        hidden = jnp.pad(hidden, ((0, pad), (0, 0)))
        targets = jnp.pad(targets, (0, pad))
        token_w = jnp.pad(token_w, (0, pad))
    return _fused_ce(
        hidden, weight, targets, token_w.astype(jnp.float32), axis_name,
        valid_size, block_t, block_v, interpret, vh,
    )


def fused_ce_shifted_sums(
    hidden: jax.Array,  # (B, S, H) final-LN output
    weight: jax.Array,
    labels: jax.Array,  # (B, S)
    attention_mask,     # (B, S) or None
    axis_name: Optional[str] = None,
    valid_size: Optional[int] = None,
    weight_layout: str = "vh",
):
    """Shift-by-one causal-LM (weighted loss sum, weight sum) via the
    fused kernel — the shared convention for the dense losses AND the
    pipeline heads (which combine per-microbatch sums themselves)."""
    b, s, hd = hidden.shape
    w = (
        attention_mask[:, 1:]
        if attention_mask is not None
        else jnp.ones_like(labels[:, 1:])
    ).astype(jnp.float32)
    return fused_ce_sums(
        hidden[:, :-1].reshape(b * (s - 1), hd), weight,
        labels[:, 1:].reshape(-1), w.reshape(-1),
        axis_name, valid_size, weight_layout=weight_layout,
    )


def fused_ce_shifted_loss(
    hidden: jax.Array,  # (B, S, H) final-LN output
    weight: jax.Array,
    labels: jax.Array,  # (B, S)
    attention_mask,     # (B, S) or None
    axis_name: Optional[str] = None,
    valid_size: Optional[int] = None,
    weight_layout: str = "vh",
) -> jax.Array:
    """Causal-LM mean loss (shift-by-one, mask-weighted) via the fused
    kernel — the single dispatch shared by the bloom/llama/mixtral
    ``config.fused_ce`` paths so the shift/mask/normalize convention
    lives in exactly one place."""
    tot, cnt = fused_ce_shifted_sums(
        hidden, weight, labels, attention_mask, axis_name, valid_size,
        weight_layout,
    )
    return tot / jnp.maximum(cnt, 1)


def fused_ce_masked_sums(
    hidden: jax.Array,   # (B, S, H) — targets ALREADY aligned (no shift)
    weight: jax.Array,
    labels: jax.Array,   # (B, S)
    weights: jax.Array,  # (B, S) float mask
    axis_name: Optional[str] = None,
    valid_size: Optional[int] = None,
    weight_layout: str = "vh",
):
    """(weighted loss sum, weight sum) over pre-aligned positions — the
    sequence-parallel head adapter: under SP the shift-by-one already
    happened globally (nn/sequence_parallel/targets.py), and the local
    (B, S_local, V) logits buffer this replaces is exactly the tensor
    that explodes at the long-context shapes SP exists for."""
    b, s, hd = hidden.shape
    return fused_ce_sums(
        hidden.reshape(b * s, hd), weight, labels.reshape(-1),
        weights.reshape(-1).astype(jnp.float32), axis_name, valid_size,
        weight_layout=weight_layout,
    )
