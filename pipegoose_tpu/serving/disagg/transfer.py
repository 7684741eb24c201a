"""Page-granular cross-mesh KV transfer at wire precision.

The primitive under disaggregated prefill/decode serving (ROADMAP
item 2, arXiv 2211.05322's cross-mesh resharding as a first-class op):
move finished prefix pages between two differently sharded
``PagePool``s — the prefill pool's mesh (compute-dense, e.g. tp=2) and
the decode pool's (bandwidth-dense, e.g. tp=1) need not match; only
the page GEOMETRY (layer count, page size, heads, head dim) must, the
page COUNTS may differ.

The transfer is host-mediated, which is exactly where the resharding
happens for free:

- **export** — one jitted gather (:func:`~pipegoose_tpu.serving.
  kv_pool.export_page_slab`) pulls the selected pages into a
  contiguous ``(L, W, ps, nh, hd)`` slab ON THE SOURCE MESH (each
  shard contributes its heads), and the host fetch materializes the
  GLOBAL slab — tp_prefill's sharding is gone.
- **wire format** — the slab ships at WIRE precision: an int8 pool's
  ``{"q", "scale"}`` planes go verbatim (quantized pages are NEVER
  dequantized in flight — that would 4x the bytes and re-quantization
  would break the token-exactness contract); fp pools optionally take
  a bf16 wire (``wire_dtype="bf16"``, the distributed/compressed.py
  convention — exact for bf16 pools, lossy for fp32 ones, so the
  default wire is the pool dtype and the token-identity pins run on
  it).
- **import** — one jitted scatter (:func:`~pipegoose_tpu.serving.
  kv_pool.import_page_slab`) writes the slab into the DESTINATION
  pool's pages under its own sharding; pad entries route to the NULL
  page like every other pad write.

Both programs are compiled ONCE per pool pair at a fixed width ``W``
(the prefill chunk's page count — the streaming boundary), with
shorter shipments padded, so a serving run never compiles a new
transfer shape.

``TransferQueue`` is the bounded in-flight buffer between the pools:
the orchestrator stops ticking the prefill engine while it is full
(backpressure — a decode pool that cannot stage reservations must
slow prefill down, not buffer unboundedly). ``set_transfer_fault`` is
the failure seam (checkpoint.py's ``set_io_fault_hook`` convention):
a hook raising :class:`TransferError` during import exercises the
fall-back-to-local-re-prefill path end to end.

Host-side by design (jit-safety allowlisted): the jitted gather/
scatter are the only device programs; everything else is numpy + host
bookkeeping.
"""
from __future__ import annotations

from collections import deque
from dataclasses import dataclass
from typing import Any, Callable, Deque, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from pipegoose_tpu.serving.kv_pool import (
    NULL_PAGE,
    export_page_slab,
    import_page_slab,
)


class TransferError(RuntimeError):
    """A cross-pool page shipment failed (link fault, checksum, test
    injection). The orchestrator's contract: abort the staged transfer
    and fall back to a local re-prefill on the decode pool."""


_fault_hook: Optional[Callable[..., None]] = None


def set_transfer_fault(hook: Optional[Callable[..., None]]):
    """Install a fault-injection hook ``hook(kind, uid, n_pages)``
    called before every import; raise :class:`TransferError` from it to
    fail that shipment. Returns the previous hook (restore it — the
    chaos-harness convention)."""
    global _fault_hook
    prev = _fault_hook
    _fault_hook = hook
    return prev


@dataclass(eq=False)
class PageHandoff:
    """One shipment: ``n_pages`` consecutive logical pages of ``req``'s
    prompt starting at ``page_index``, as host wire slabs. ``final``
    marks the prefill-completion handoff — it carries the first token
    and may legitimately hold zero pages (prompt length a page
    multiple, everything already streamed). Identity equality
    (``eq=False``): records hold numpy slabs and the queue's
    ``remove`` must match THIS record, not a value-equal twin."""

    req: Any
    page_index: int
    n_pages: int
    tokens_end: int                    # materialized positions after import
    k: Any                             # host slab (or None when n_pages=0)
    v: Any
    wire_bytes: int
    final: bool
    first_token: Optional[int]
    t_created: float


class TransferQueue:
    """Bounded FIFO of in-flight :class:`PageHandoff` records. The
    bound is the backpressure valve: ``has_room()`` gates both the
    prefill engine's tick and the streaming exports, so a slow decode
    pool stalls prefill instead of growing host memory. (The final
    handoff of a chunk already mid-tick may overshoot by one record
    per prefill slot — a soft bound, pinned by test.)

    ``max_age_s`` is the stuck-shipment timeout: a record older than
    this when the decode worker services it (a staging-blocked head
    the decode ledger can NEVER cover, a hung link) raises
    :class:`TransferError` into the existing per-shipment fallback —
    the request re-prefills locally — instead of blocking the queue
    until the whole-run stall watchdog gives up. ``None`` (default)
    disables the timeout; backpressure alone bounds the wait."""

    def __init__(self, max_inflight: int = 8,
                 max_age_s: Optional[float] = None):
        if max_inflight < 1:
            raise ValueError(
                f"max_inflight must be >= 1, got {max_inflight}"
            )
        if max_age_s is not None and max_age_s <= 0:
            raise ValueError(
                f"max_age_s must be > 0 (or None), got {max_age_s}"
            )
        self.max_inflight = int(max_inflight)
        self.max_age_s = max_age_s
        self._q: Deque[PageHandoff] = deque()
        self.max_depth = 0             # high-water mark (test + bench)

    def __len__(self) -> int:
        return len(self._q)

    def has_room(self) -> bool:
        return len(self._q) < self.max_inflight

    def push(self, rec: PageHandoff) -> None:
        self._q.append(rec)
        self.max_depth = max(self.max_depth, len(self._q))

    def peek(self) -> PageHandoff:
        return self._q[0]

    def pop(self) -> PageHandoff:
        return self._q.popleft()

    def remove(self, rec: PageHandoff) -> None:
        """Drop one record mid-queue (the decode worker imports
        already-staged requests' records past a staging-blocked head;
        relative order of the rest is untouched)."""
        self._q.remove(rec)

    def reset_depth_mark(self) -> None:
        """Start a fresh high-water measurement (per-run reporting)."""
        self.max_depth = len(self._q)

    def oldest_age(self, now: float) -> float:
        """Age of the oldest queued shipment (0.0 when empty) — the
        ``serving.transfer.queue_age_seconds`` gauge's source."""
        if not self._q:
            return 0.0
        return max(now - self._q[0].t_created, 0.0)

    def expired(self, rec: PageHandoff, now: float) -> bool:
        """Has ``rec`` outlived the stuck-shipment timeout?"""
        return (self.max_age_s is not None
                and now - rec.t_created > self.max_age_s)

    def clear(self) -> List[PageHandoff]:
        """Drop every queued shipment and return the dropped records —
        the POOL-LEVEL failure path (a dead prefill pool's in-flight
        shipments can never complete coherently; the affected requests
        re-prefill locally on the decode pool instead)."""
        dropped = list(self._q)
        self._q.clear()
        return dropped


def _host(slab):
    """Device slab -> host numpy pytree (the wire buffer)."""
    return jax.tree_util.tree_map(np.asarray, slab)


def _slice_pages(slab, n: int):
    return jax.tree_util.tree_map(lambda a: a[:, :n], slab)


def _pad_pages(slab, width: int):
    def pad(a):
        n = a.shape[1]
        if n == width:
            return a
        fill = np.zeros((a.shape[0], width - n) + a.shape[2:], a.dtype)
        return np.concatenate([a, fill], axis=1)

    return jax.tree_util.tree_map(pad, slab)


def slab_nbytes(slab) -> int:
    """Exact wire byte census of a host slab (values + scale planes at
    their wire dtypes — the test that int8 ships q+scale, never fp)."""
    if slab is None:
        return 0
    return int(sum(a.size * a.dtype.itemsize
                   for a in jax.tree_util.tree_leaves(slab)))


class PoolTransfer:
    """The compiled export/import pair between one (prefill, decode)
    engine pair. Validates geometry compatibility once; page counts
    and meshes may differ (that difference IS the feature)."""

    def __init__(self, src_engine, dst_engine, *,
                 wire_dtype: Optional[str] = None,
                 width: Optional[int] = None):
        scfg, dcfg = src_engine.config, dst_engine.config
        for attr in ("n_layer", "n_head", "head_dim"):
            if getattr(scfg, attr) != getattr(dcfg, attr):
                raise ValueError(
                    f"pool geometry mismatch: {attr} "
                    f"{getattr(scfg, attr)} != {getattr(dcfg, attr)}"
                )
        if src_engine.page_size != dst_engine.page_size:
            raise ValueError(
                f"page_size mismatch: {src_engine.page_size} != "
                f"{dst_engine.page_size} (page geometry may differ only "
                f"in COUNT)"
            )
        if src_engine.kv_dtype != dst_engine.kv_dtype:
            raise ValueError(
                f"kv_dtype mismatch: {src_engine.kv_dtype!r} != "
                f"{dst_engine.kv_dtype!r} — the wire format is the "
                f"pools' shared storage format"
            )
        if wire_dtype is not None and src_engine.kv_dtype == "int8":
            raise ValueError(
                "int8 pools define their own wire format (q + scale "
                "planes); wire_dtype applies to fp pools only"
            )
        if width is None and src_engine.prefill_chunk is None:
            raise ValueError(
                "the source engine needs prefill_chunk: the chunk is "
                "the streaming boundary that fixes the transfer width "
                "(or pass width= explicitly — the kv_tier spill/restore "
                "path does, its shipments are page-granular)"
            )
        if width is not None and width < 1:
            raise ValueError(f"width must be >= 1 pages, got {width}")
        self.src = src_engine
        self.dst = dst_engine
        self.wire_dtype = wire_dtype
        self.page_size = src_engine.page_size
        self.width = (int(width) if width is not None
                      else max(1, src_engine.prefill_chunk // self.page_size))

        def _exp(kp, vp, ids):
            return (export_page_slab(kp, ids, scfg.head_dim, wire_dtype),
                    export_page_slab(vp, ids, scfg.head_dim, wire_dtype))

        def _imp(kp, vp, ks, vs, dst_ids):
            return (import_page_slab(kp, ks, dst_ids),
                    import_page_slab(vp, vs, dst_ids))

        self._export_fn = jax.jit(_exp)
        if dst_engine.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            shard = jax.tree_util.tree_map(
                lambda s: NamedSharding(dst_engine.mesh, s),
                dst_engine._pspec,
                is_leaf=lambda x: isinstance(x, P),
            )
            self._import_fn = jax.jit(
                _imp, donate_argnums=(0, 1),
                out_shardings=(shard, shard),
            )
        else:
            self._import_fn = jax.jit(_imp, donate_argnums=(0, 1))
        # the fp-equivalent per-page wire size: what a no-quantization
        # transfer of the same pages would move — the "GB saved" meter
        itemsize = int(np.dtype(scfg.dtype).itemsize)
        self.fp_page_bytes = (2 * scfg.n_layer * self.page_size
                              * scfg.n_head * scfg.head_dim * itemsize)

    def export(self, page_ids: List[int]) -> Tuple[Any, Any, int]:
        """Gather ``page_ids`` from the source pool into host wire
        slabs (sliced to the REAL page count — padding never rides the
        wire census). Returns ``(k_slab, v_slab, wire_bytes)``."""
        n = len(page_ids)
        if n == 0:
            return None, None, 0
        if n > self.width:
            raise ValueError(
                f"shipment of {n} pages exceeds the transfer width "
                f"{self.width} (split at the streaming boundary)"
            )
        ids = np.zeros((self.width,), np.int32)
        ids[:n] = page_ids
        ks, vs = self._export_fn(
            self.src.k_pages, self.src.v_pages, jnp.asarray(ids)
        )
        ks, vs = _slice_pages(_host(ks), n), _slice_pages(_host(vs), n)
        return ks, vs, slab_nbytes(ks) + slab_nbytes(vs)

    def import_(self, rec: PageHandoff, dst_pages: List[int]) -> None:
        """Scatter a shipment into the destination pool's pages
        (``dst_pages``, one per shipped page). The fault seam fires
        FIRST: a failed shipment must not half-write the pool."""
        if _fault_hook is not None:
            _fault_hook("import", rec.req.uid, rec.n_pages)
        if rec.n_pages == 0:
            return
        if len(dst_pages) != rec.n_pages:
            raise ValueError(
                f"shipment has {rec.n_pages} pages but {len(dst_pages)} "
                f"destination pages were provided"
            )
        dst = np.full((self.width,), NULL_PAGE, np.int32)
        dst[:rec.n_pages] = dst_pages
        ks = _pad_pages(rec.k, self.width)
        vs = _pad_pages(rec.v, self.width)
        to_dev = jax.tree_util.tree_map(jnp.asarray, (ks, vs))
        self.dst.k_pages, self.dst.v_pages = self._import_fn(
            self.dst.k_pages, self.dst.v_pages,
            to_dev[0], to_dev[1], jnp.asarray(dst),
        )
