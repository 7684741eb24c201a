"""Fused paged attention (Pallas, TPU): one HBM pass over the KV pool.

The serving decode step is memory-bound twice over on the gather path:
``serving/kv_pool.py:gather_pages`` materializes every page of a slot's
page table into a contiguous (B, W*ps, nh, hd) KV buffer (dequantizing
int8 to fp in XLA on the way), and ``_attn_core`` then re-reads that
buffer — two full HBM passes at fp precision for a step whose
arithmetic intensity is ~1. This kernel walks the page table directly:

- the page table and per-row start positions ride SCALAR PREFETCH
  (``PrefetchScalarGridSpec``), so each grid step's BlockSpec index map
  returns the PHYSICAL page id ``page_table[b, w]`` — the DMA engine
  fetches raw pages straight out of the pool, no contiguous copy;
- an int8 pool's ``{q, scale}`` planes are DMA'd at WIRE precision
  (1 byte/value + one f32 per (position, head)) and dequantized
  in-register, so the quantized pool's bandwidth saving reaches the
  attention read, not just the storage;
- the ALiBi-over-global-position bias, the causal/validity mask, and
  the online-softmax recurrence (the ops/flash_attention.py idiom:
  m/l/acc scratch carried across the sequential page axis) are fused
  behind the same pass.

Ragged multi-token contract: ``q`` is (B, C, nh, hd) and row ``b``'s
query ``c`` sits at GLOBAL position ``start[b] + c``. A key at logical
position ``w*ps + o`` (independent of which physical page the table
maps it to) is kept iff ``key_pos <= q_pos`` — one mask that subsumes
causality, not-yet-written page offsets, stale tails from a previous
page owner, and NULL-page garbage, exactly mirroring the gather path's
``_paged_bias``. C=1 with ``start=seq_lens`` is the decode step; C>1
serves speculative verify bundles and chunked prefill. Pad queries
(beyond a row's ``n_valid``) produce garbage rows the CALLER zeroes
via its qmask, matching ``_attn_core``'s contract.

Tiles are (page_size, n_head*head_dim) per grid step — the page IS the
block, all local heads of it in the lane axis. That is the pool's own
layout (serving/kv_pool.py:init_pages keeps a position's heads in one
lane-dense row, because rows of a half-filled 128-lane tile make the
compiler put the PAGES in the lanes), so a bank goes to the kernel as
it is stored and one DMA fetches one whole contiguous page.
``check_paged_tile`` is the fused_ce-style feasibility guard: compiled
runs raise loudly when the tile cannot fit VMEM (never a silent
fallback to the gather path); the interpreter is exempt (no VMEM).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e9

# Conservative per-core VMEM working-set budget. v4/v5 cores expose
# ~16 MiB; Mosaic needs headroom for double buffering beyond what the
# estimate below already doubles, so the guard trips at 3/4 of it.
VMEM_BUDGET_BYTES = 12 * 1024 * 1024

_LANE = 128     # last-dim tile width, every dtype
_SUBLANE = {4: 8, 2: 16, 1: 32}   # itemsize -> second-to-last tile height


def _resolve_interpret(interpret):
    # None = choose by platform, as in ops/flash_attention.py (guarded
    # by chip_smoke.py's tpu_custom_call assertions)
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def _is_quantized(pages) -> bool:
    return isinstance(pages, dict)


def _pad_up(n: int, to: int) -> int:
    return -(-n // to) * to


def _head_group(n_head: int, head_dim: int) -> int:
    """Heads the kernel handles per lane-aligned column slab of the
    flattened (nh*hd) axis: as many whole heads as fit 128 lanes (2 at
    head_dim 64), one when head_dim is a multiple of 128. A slab that
    is no multiple of 128 lanes (an odd head count, head_dim 96) is a
    static column slice Mosaic still takes; test_chip_compile.py keeps
    such cases."""
    g = max(1, min(n_head, _LANE // head_dim))
    while n_head % g:
        g -= 1
    return g


def paged_tile_geometry(page_size: int, n_head: int, head_dim: int,
                        n_queries: int, *, quantized: bool) -> dict:
    """Host-side tile report for one kernel instantiation: the
    (page_size, n_head*head_dim) KV tile the page-table walk DMAs per
    grid step — one whole page, every local head — with the VMEM
    working-set estimate the feasibility guard checks. All inputs are
    trace-time constants (array shapes), so this runs once per compiled
    program shape, never per step. The estimate pads every buffer to
    Mosaic's physical tiles ((8|16|32) x 128 by itemsize), counts fp
    pools at f32 (the widest the pool holds) and doubles every operand
    that crosses HBM for double buffering."""
    kv_itemsize = 1 if quantized else 4
    row = _pad_up(n_head * head_dim, _LANE)
    c_pad = _pad_up(n_queries, _SUBLANE[4])
    kv_tile = _pad_up(page_size, _SUBLANE[kv_itemsize]) * row * kv_itemsize
    scale_tile = (_pad_up(page_size, _SUBLANE[4]) * _pad_up(n_head, _LANE) * 4
                  if quantized else 0)
    q_tile = c_pad * row * 4
    piped = 2 * (kv_tile + scale_tile) + 2 * q_tile      # k, v, q, out
    scratch = (
        q_tile                                 # acc
        + 2 * n_head * c_pad * _LANE * 4       # m/l ((nh, C, 1) padded)
    )
    # in-register working set of one head group: dequantized f32 k/v
    # slabs and the (C, slab) q/acc rows
    slab = _pad_up(_head_group(n_head, head_dim) * head_dim, _LANE)
    live = 2 * _pad_up(page_size, _SUBLANE[4]) * slab * 4 + 2 * c_pad * slab * 4
    vmem_bytes = 2 * piped + scratch + live
    return {
        "block_kv": page_size,
        "n_head": n_head,
        "head_dim": head_dim,
        "n_queries": n_queries,
        "quantized": quantized,
        "vmem_bytes": int(vmem_bytes),
        "vmem_budget_bytes": VMEM_BUDGET_BYTES,
        "fits": vmem_bytes <= VMEM_BUDGET_BYTES,
    }


def check_paged_tile(page_size: int, n_head: int, head_dim: int,
                     n_queries: int, *, quantized: bool,
                     interpret: Optional[bool] = None) -> dict:
    """The fused_ce-style loud guard: returns the geometry dict when the
    (page_size, n_head*head_dim) tile fits the VMEM budget, raises
    ValueError for COMPILED runs when it cannot — never a silent
    fallback to the gather path (a half-switched fleet would silently
    lose the perf the config claims). Interpret-mode runs are exempt:
    the interpreter has no VMEM limit, and the CPU test mesh must keep
    covering oversized geometries."""
    geom = paged_tile_geometry(page_size, n_head, head_dim, n_queries,
                               quantized=quantized)
    if not geom["fits"] and not _resolve_interpret(interpret):
        raise ValueError(
            f"paged attention: a (page_size={page_size} x n_head={n_head} "
            f"x head_dim={head_dim}) KV tile with C={n_queries} queries "
            f"needs ~{geom['vmem_bytes']} bytes of VMEM "
            f"(budget {VMEM_BUDGET_BYTES}) on hardware. Shrink "
            f"page_size (the page IS the kernel block) or keep "
            f"attn_kernel='gather' for this geometry — the kernel "
            f"never falls back silently."
        )
    return geom


def _ref_attention(q, keys, vals, start, slopes):
    """Plain-XLA reference over an already-gathered contiguous KV view
    — the gather path's ``_attn_core`` + ``_paged_bias`` math, minus
    the caller-side qmask. Shared by the interpret tests and the parity
    suite so the kernel is always pinned against the exact production
    semantics."""
    b, c, nh, hd = q.shape
    n_keys = keys.shape[1]
    key_pos = jnp.arange(n_keys)
    q_pos = start[:, None] + jnp.arange(c)[None, :]           # (B, C)
    keep = key_pos[None, None, :] <= q_pos[:, :, None]        # (B, C, K)
    bias = slopes[None, :, None, None] * key_pos[None, None, None, :].astype(
        jnp.float32
    )
    bias = bias + jnp.where(keep[:, None, :, :], 0.0, NEG_INF)
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", q, keys, preferred_element_type=jnp.float32
    ) * (hd ** -0.5)
    probs = jax.nn.softmax(scores + bias, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, vals,
                      preferred_element_type=jnp.float32)


def _xla_one_pass(q, k_pages, v_pages, page_table, start, slopes):
    """Compiled one-pass lane for non-TPU backends: the kernel's exact
    algorithm — scan the logical page axis, gather each step's B pages
    through the table, dequantize per page, masked online-softmax
    update — expressed in XLA. Neither the contiguous (B, W*ps) KV view
    nor the dense (B, nh, C, S) score matrix ever exists, so the CPU
    smoke benches the same memory shape the TPU kernel has, minus the
    Pallas interpreter's per-grid-step Python overhead."""
    b, c, nh, hd = q.shape
    w_pages = page_table.shape[1]
    quantized = _is_quantized(k_pages)
    ps = (k_pages["q"] if quantized else k_pages).shape[1]
    qf = q.astype(jnp.float32)
    scale = hd ** -0.5
    slopes = slopes.astype(jnp.float32)
    q_pos = start.astype(jnp.int32)[:, None] + jnp.arange(c)[None, :]

    def dequant(pages, ids):
        rows = (pages["q"] if quantized else pages)[ids].astype(jnp.float32)
        vals = rows.reshape(b, ps, nh, hd)
        if quantized:
            return vals * pages["scale"][ids][..., None]
        return vals

    def step(carry, wi):
        m, l, acc = carry
        ids = jax.lax.dynamic_index_in_dim(page_table, wi, 1, False)
        kb = dequant(k_pages, ids)                       # (B, ps, nh, hd)
        vb = dequant(v_pages, ids)
        s = jnp.einsum("bchd,bkhd->bchk", qf, kb,
                       preferred_element_type=jnp.float32) * scale
        key_pos = wi * ps + jnp.arange(ps)
        bias = slopes[None, None, :, None] * key_pos.astype(jnp.float32)
        keep = key_pos[None, None, :] <= q_pos[:, :, None]
        s = s + bias + jnp.where(keep[:, :, None, :], 0.0, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bchk,bkhd->bchd", p, vb, preferred_element_type=jnp.float32)
        return (m_new, l, acc), None

    init = (jnp.full((b, c, nh), NEG_INF, jnp.float32),
            jnp.zeros((b, c, nh), jnp.float32),
            jnp.zeros((b, c, nh, hd), jnp.float32))
    (_, l, acc), _ = jax.lax.scan(step, init, jnp.arange(w_pages))
    return acc / jnp.maximum(l, 1e-30)[..., None]


def paged_attention_reference(q, k_pages, v_pages, page_table, start, *,
                              slopes):
    """XLA reference implementation (two HBM passes): gather the page
    view, then attend. Used as the parity oracle; returns f32
    (B, C, nh, hd) like the kernel."""
    from pipegoose_tpu.serving.kv_pool import gather_pages

    keys = gather_pages(k_pages, page_table, q.shape[-1])
    vals = gather_pages(v_pages, page_table, q.shape[-1])
    return _ref_attention(q.astype(jnp.float32), keys.astype(jnp.float32),
                          vals.astype(jnp.float32), start, slopes)


def paged_attention(q, k_pages, v_pages, page_table, start, *, slopes,
                    interpret: Optional[bool] = None):
    """Fused one-pass paged attention over a per-layer page bank.

    Args:
      q: (B, C, nh_local, hd) queries (any float dtype; upcast to f32
        in-register). C=1 is a decode step, C>1 a verify bundle or
        prefill chunk.
      k_pages / v_pages: a bank of pages in the pool's layout, a
        position's heads in one row — fp (P, ps, nh_local*hd) or the
        int8 pytree {"q": int8 (P, ps, nh_local*hd), "scale": f32
        (P, ps, nh_local)}. The serving pool passes every layer's pages
        as one bank, the layer folded into the page ids.
      page_table: (B, W) int32 physical page ids; entries beyond a
        row's live prefix must be NULL (0), like everywhere else.
      start: (B,) int32 global position of each row's FIRST query token
        (decode: seq_lens; chunk/verify: the chunk start).
      slopes: (nh_local,) f32 ALiBi slopes for THIS shard's heads.

    Returns f32 (B, C, nh_local, hd) context. Callers cast/reshape and
    apply their pad-query mask, mirroring ``_attn_core``.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, c, nh, hd = q.shape
    _, w_pages = page_table.shape
    quantized = _is_quantized(k_pages)
    ps = (k_pages["q"] if quantized else k_pages).shape[1]
    check_paged_tile(ps, nh, hd, c, quantized=quantized, interpret=interpret)
    if interpret is None and jax.default_backend() != "tpu":
        # auto mode picks by platform: off the TPU it is this XLA lane
        # (same algorithm), on it the compiled kernel. chip_smoke.py's
        # tpu_custom_call assertion guards the choice.
        return _xla_one_pass(q, k_pages, v_pages, page_table,
                             start.astype(jnp.int32), slopes)
    interpret = _resolve_interpret(interpret)
    scale = hd ** -0.5
    page_table = page_table.astype(jnp.int32)
    start = start.astype(jnp.int32)
    # Heads share the lane axis (the pool's rows), so every block's
    # last two dims equal the array's (Mosaic's tiling rule) and one DMA
    # fetches one whole contiguous page. The kernel walks the heads in
    # lane-aligned slabs of `group` heads; within a slab a head is
    # picked by masking q's lanes (the other heads' lanes contribute
    # zero to the contraction).
    group = _head_group(nh, hd)
    slab = group * hd
    row = nh * hd

    def kernel(pt_ref, start_ref, slopes_ref, q_ref, *rest):
        if quantized:
            (kq_ref, ks_ref, vq_ref, vs_ref,
             o_ref, m_sc, l_sc, acc_sc) = rest
        else:
            k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc = rest
        bi = pl.program_id(0)
        wi = pl.program_id(1)
        row_start = start_ref[bi]
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, slab), 1)

        def lanes_of(j):
            """The slab lanes that hold the slab's j-th head."""
            return (lane >= j * hd) & (lane < (j + 1) * hd)

        @pl.when(wi == 0)
        def _init():
            m_sc[...] = jnp.full_like(m_sc, NEG_INF)
            l_sc[...] = jnp.zeros_like(l_sc)
            acc_sc[...] = jnp.zeros_like(acc_sc)

        # pages whose FIRST key position exceeds the row's last query
        # position are fully masked: skip the whole tile. Their table
        # entries are NULL, so consecutive skipped steps revisit block
        # 0 and Pallas elides the redundant DMAs too.
        @pl.when(wi * ps <= row_start + (c - 1))
        def _compute():
            # logical key position = w*ps + offset: the grid's w IS the
            # logical page index — physical indirection lives only in
            # the index maps, so the mask math matches _paged_bias
            key_pos = wi * ps + jax.lax.broadcasted_iota(
                jnp.int32, (c, ps), 1
            )
            q_pos = row_start + jax.lax.broadcasted_iota(
                jnp.int32, (c, ps), 0
            )
            causal = jnp.where(key_pos <= q_pos, 0.0, NEG_INF)
            key_posf = key_pos.astype(jnp.float32)
            for gi in range(nh // group):
                cols = slice(gi * slab, (gi + 1) * slab)
                qb = q_ref[0, :, cols].astype(jnp.float32)   # (C, slab)
                if quantized:
                    kb = kq_ref[0, :, cols].astype(jnp.float32)
                    vb = vq_ref[0, :, cols].astype(jnp.float32)
                    ksb, vsb = ks_ref[0], vs_ref[0]          # (ps, nh)
                else:
                    kb = k_ref[0, :, cols].astype(jnp.float32)
                    vb = v_ref[0, :, cols].astype(jnp.float32)
                acc = acc_sc[:, cols]                        # (C, slab)
                for j in range(group):
                    h = gi * group + j
                    mine = lanes_of(j)
                    if quantized:
                        # per-(position, head) scales: one column of the
                        # (ps, nh) plane, spread over this head's lanes
                        kb = jnp.where(mine, kb * ksb[:, h:h + 1], kb)
                        vb = jnp.where(mine, vb * vsb[:, h:h + 1], vb)
                    s_blk = jax.lax.dot_general(
                        jnp.where(mine, qb, 0.0), kb,
                        (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    ) * scale                                # (C, ps)
                    s_blk = s_blk + slopes_ref[h] * key_posf + causal
                    m_prev = m_sc[h, :, 0]
                    m_new = jnp.maximum(m_prev, s_blk.max(axis=1))
                    p = jnp.exp(s_blk - m_new[:, None])
                    alpha = jnp.exp(m_prev - m_new)
                    l_sc[h, :, 0] = l_sc[h, :, 0] * alpha + p.sum(axis=1)
                    m_sc[h, :, 0] = m_new
                    pv = jax.lax.dot_general(
                        p, vb, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    )                                        # (C, slab)
                    acc = jnp.where(mine, acc * alpha[:, None] + pv, acc)
                acc_sc[:, cols] = acc

        @pl.when(wi == w_pages - 1)
        def _finish():
            for gi in range(nh // group):
                cols = slice(gi * slab, (gi + 1) * slab)
                inv = jnp.zeros((c, slab), jnp.float32)
                for j in range(group):
                    l = jnp.maximum(l_sc[gi * group + j, :, 0], 1e-30)
                    inv = jnp.where(lanes_of(j), (1.0 / l)[:, None], inv)
                o_ref[0, :, cols] = (acc_sc[:, cols] * inv).astype(o_ref.dtype)

    def qidx(bi, wi, pt_ref, start_ref):
        return (bi, 0, 0)

    def kvidx(bi, wi, pt_ref, start_ref):
        return (pt_ref[bi, wi], 0, 0)

    q_spec = pl.BlockSpec((1, c, row), qidx)
    page_spec = pl.BlockSpec((1, ps, row), kvidx)
    slope_spec = pl.BlockSpec((nh,), lambda bi, wi, pt, st: (0,),
                              memory_space=pltpu.SMEM)
    if quantized:
        scale_spec = pl.BlockSpec((1, ps, nh), kvidx)
        in_specs = [slope_spec, q_spec,
                    page_spec, scale_spec, page_spec, scale_spec]
        operands = (k_pages["q"], k_pages["scale"],
                    v_pages["q"], v_pages["scale"])
    else:
        in_specs = [slope_spec, q_spec, page_spec, page_spec]
        operands = (k_pages, v_pages)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, w_pages),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, c, row), qidx),
            scratch_shapes=[
                pltpu.VMEM((nh, c, 1), jnp.float32),
                pltpu.VMEM((nh, c, 1), jnp.float32),
                pltpu.VMEM((c, row), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, c, row), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="paged_attention",
    )(page_table, start, slopes.astype(jnp.float32), q.reshape(b, c, row),
      *operands)
    return out.reshape(b, c, nh, hd)
