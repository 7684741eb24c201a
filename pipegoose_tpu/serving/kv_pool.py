"""Paged KV-cache pool: fixed-size pages + a page-table attention path.

vLLM-style paging rebuilt for the jit/shard_map stack. The per-call
contiguous cache (models/generate.py:init_cache) allocates
``batch * max_len`` key/value slots whether or not a row ever fills
them; a serving engine multiplexing many requests instead draws from ONE
preallocated pool

    (n_layer, num_pages, page_size, n_head_local * head_dim)

per k and v, where a sequence owns ``ceil(len / page_size)`` pages wired
up by an integer page table. A position's heads share ONE lane-dense
row, heads major (:func:`init_pages` says why), and every program
updates the pool IN PLACE: rows are addressed by (layer, page, offset)
on the donated buffer, never on a slice of it. Values keep
``(.., nh, hd)`` at the edges (:func:`gather_pages`, the wire slabs).

The attention READ (:func:`_attend_rows`) takes the rows as they are
stored: a page table is walked in chunks of whole pages
(:func:`walk_plan`), only as far as the furthest live query of the call
(:func:`walked_chunks`, a trip count on the device), and each gathered
chunk, still ``nh*hd``
lanes of the pool's dtype, is contracted on the matrix unit against a
block-diagonal query under an online softmax. No row is split into
heads or widened in memory, and the pages past the longest live
sequence are not read (PERF.md, PR 32). Four pieces live here:

- :class:`PagePool` — the HOST-side free-list allocator. Allocation is a
  LIFO stack pop, so placement is deterministic given the request/evict
  order (testable invariant); page 0 is reserved as the NULL page that
  absorbs writes from padded slots and pad positions. Pages are
  REFCOUNTED (alloc/share/release) so the prefix cache
  (serving/prefix_cache.py) can point many requests at one physical
  page; :func:`copy_page` is the copy-on-write escape hatch when a
  shared page's tail must be written.
- :func:`paged_prefill_chunk` — forward a C-token chunk per row through
  the page tables (chunked prefill and self-speculative verification
  share this one program shape).
- :func:`paged_decode_step` — one decode step over the ragged active
  batch: each slot's pending token is written through its page table,
  attention reads the pool's rows through it, and invalid key columns
  (beyond ``seq_lens``, stale page tails, null-page garbage) are masked
  to exactly zero softmax weight. Both run ONE layer loop with the SAME
  qkv projection as the contiguous path (models/generate.py:_qkv_proj)
  and the arithmetic of its attention core (``_attn_core``: operands in
  the cache's dtype, float32 scores, softmax and accumulation,
  probabilities rounded before the value product), which the parity
  tests hold it to.
- :func:`write_prompt_pages` — scatter a prefill's contiguous cache
  into the pool, repacking a LEFT-padded prompt to logical positions
  0..len-1 (the unpadded layout the decode bias assumes).

A model whose layers keep a STATE beside their keys and values (a
recurrence's, a short convolution's last inputs: ``serving/blocks.py``,
models/falcon_h1.py) gets a second set of buffers, the state bank
(:func:`init_state`): ``(L, num_slots, ..)`` a leaf, a row a slot a
layer, not paged, because it does not grow with the sequence. It rides
the layer loop's carry beside the pool, is donated by the same programs
and OVERWRITTEN in place: a prefill's result is put in its slot's row
(:func:`write_state`), and a decode step reads and writes the rows of
its slots a few at a time, only as far as the highest live slot
(:func:`update_state_rows`, :func:`walked_state_rows`). A model without
state carries an empty dict, which adds nothing to a program.

A model whose window layers' ONE attention also reads a SUMMARY a chunk
of what left its window (``blocks.Summaries``, models/evabyte.py) keeps
the ring in the ``window`` kind's banks and the summaries, a row a chunk
of positions, in the ``global`` kind's (``PagePool.stride``): a decode
step writes the ring's row, pools the ring's page and writes ONE summary
row in the step that completes its chunk (:func:`_write_summary`),
and reads both under one softmax, the ring's walk handing its (m, denom,
acc) to the summaries' (:func:`_attend_summarised`, :func:`_walk`).

Under TP every function sees the LOCAL head subset (call inside
shard_map with the pool's rows sharded over the tensor axis), and the
engine pairs the local logits with ``global_greedy_pick``.

``init_pages(kv_dtype="int8")`` swaps each bank for an int8 pytree with
a per-page scale plane (one fp32 per layer/page-slot/head): writes
quantize (:func:`quantize_kv`), the attention read applies the
scales to its scores and probabilities (the reconstruction,
:func:`gather_pages`, dequantizes), ``copy_page`` COW-copies values
and scales together, and every signature stays identical — the quantized pool is
a drop-in for the fp one at ~``hd/(hd+4)``x fewer KV bytes per page.
"""
from __future__ import annotations

from collections import deque
from typing import Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from pipegoose_tpu.models.bloom import NEG_INF
from pipegoose_tpu.serving.blocks import (
    BLOCK,
    GLOBAL,
    KINDS,
    SLIDING,
    WINDOW,
    describe,
    ring_pages,
    summaries_seen,
    window_start,
)

NULL_PAGE = 0

KV_DTYPES = (None, "fp", "int8")

# key columns one trip of the read's walk visits, in whole
# pages (:func:`walk_plan`). Measured on the chip at 128 / 256 / 512
# (PERF.md, PR 32).
WALK_KEYS = 256

# slots a trip of a decode step's walk over the state bank reads and
# writes (:func:`state_walk_plan`): at Falcon-H1-34B's 4.2 MB a slot a
# block, 34 MB in flight
STATE_ROWS = 8

# what a decode step over a ring and its summaries counts of ONE layer's
# two walks, in the order of its ``summary_rows`` counter
# (:func:`_summary_counters`)
SUMMARY_COUNTERS = ("rows_live", "window_rows_needed", "window_rows_gathered",
                    "summary_rows_needed", "summary_rows_gathered",
                    "summaries_written")



_KV_INT8_MAX = 127.0


def check_kv_dtype(kv_dtype: Optional[str]) -> Optional[str]:
    if kv_dtype not in KV_DTYPES:
        raise ValueError(f"kv_dtype must be one of {KV_DTYPES}, got "
                         f"{kv_dtype!r}")
    return None if kv_dtype == "fp" else kv_dtype


def quantize_kv(x):
    """fp (..., hd) -> (int8 (..., hd), f32 scale (...,)): symmetric
    max-abs per POSITION per HEAD over the head dim — the quantize-on-
    write half of the int8 pool. Per-(position, head) granularity keeps
    the write shard-local under TP head sharding and makes every write
    deterministic in the token values alone, which is what lets prefix
    sharing, COW, and evict->re-admit stay token-exact under int8."""
    x32 = x.astype(jnp.float32)
    scale = jnp.maximum(
        jnp.max(jnp.abs(x32), axis=-1) / _KV_INT8_MAX,
        jnp.finfo(jnp.float32).tiny,
    )
    q = jnp.clip(
        jnp.round(x32 / scale[..., None]), -_KV_INT8_MAX, _KV_INT8_MAX
    ).astype(jnp.int8)
    return q, scale


def dequantize_kv(q, scale):
    """The dequantize-on-read half (inside the attention gather)."""
    return q.astype(jnp.float32) * scale[..., None]


def _is_quantized(pages) -> bool:
    return isinstance(pages, dict)


class PagePool:
    """Refcounted free-list allocator over ``num_pages`` fixed-size KV pages.

    Page 0 is the NULL page — never handed out; padded slots and the pad
    positions of a bucketed prefill scatter their garbage there. The
    free list is a LIFO stack, so the physical placement of any workload
    is a pure function of the submit/evict order (the determinism
    invariant tests/serving/test_kv_pool.py pins down).

    Pages carry a **refcount** so the prefix cache (serving/
    prefix_cache.py) can share one physical page between many readers:
    ``alloc`` hands out pages at refcount 1, ``share`` adds a reader,
    ``release`` drops one — a page returns to the free list only when
    its last reference is released. ``free`` is an alias for ``release``
    (the pre-sharing API). A shared page is READ-ONLY for everyone but
    its writer-by-construction: the scheduler guarantees write positions
    never land in a page with refcount > 1 (copy-on-write duplicates the
    page first).

    ``history`` keeps the most recent (event, pages, refcount-delta)
    triples for the determinism tests and for debugging fragmentation —
    the delta makes sharing visible (a ``release`` that does NOT free is
    a refcount decrement on a still-shared page). Bounded so a
    long-lived engine never accumulates host memory per request."""

    def __init__(self, num_pages: int, page_size: int,
                 history_limit: int = 1024, window_pages: int = 0,
                 ring: int = 0, stride: int = 1):
        """``window_pages`` > 0 adds the ``"window"`` kind: a second
        free list over pages of the window layers' banks (its own NULL
        page 0), of which a sequence holds at most ``ring``. ``stride``:
        positions a row of a ``global`` page stands for
        (``blocks.PagedModel.stride``: 1, or a summary's chunk)."""
        if num_pages < 2:
            raise ValueError("need >= 2 pages (page 0 is the null page)")
        if page_size < 1:
            raise ValueError(f"page_size must be positive, got {page_size}")
        if history_limit < 1:
            raise ValueError(
                f"history_limit must be >= 1, got {history_limit}")
        if stride < 1:
            raise ValueError(f"stride must be positive, got {stride}")
        self.num_pages = num_pages
        self.page_size = page_size
        self.stride = stride
        self._free: List[int] = list(range(num_pages - 1, 0, -1))
        self._ref: Dict[int, int] = {}   # page -> refcount (allocated only)
        self.history: Deque[Tuple[str, Tuple[int, ...], int]] = deque(
            maxlen=history_limit
        )
        # events the bounded ring has silently evicted — the ring
        # itself must not look lossless once it wraps
        self.history_dropped = 0
        # optional synchronous observer (telemetry/memledger.py): gets
        # every (event, pages) pair history records plus the owner tag
        # the call site declared through ``tag``. None (the default)
        # costs one attribute read + branch per pool event.
        self.ledger = None
        self.tag = None                  # owner tag for the NEXT event
        if window_pages and ring < 1:
            raise ValueError("a window kind needs its ring's length")
        self.ring = ring
        self.window: Optional[PagePool] = (
            PagePool(window_pages, page_size, history_limit)
            if window_pages else None)
        # ring entries taken over by a later logical page, ever
        self.recycled = 0

    @property
    def kinds(self) -> Tuple[str, ...]:
        return (GLOBAL,) if self.window is None else KINDS

    def of(self, kind: str) -> "PagePool":
        """The allocator of one kind's pages (this one for ``global``)."""
        if kind == GLOBAL:
            return self
        if kind != WINDOW or self.window is None:
            raise ValueError(f"the pool has no {kind!r} kind "
                             f"(kinds: {self.kinds})")
        return self.window

    def used_by_kind(self) -> Dict[str, int]:
        return {k: self.of(k).capacity - self.of(k).free_count
                for k in self.kinds}

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def used_count(self) -> int:
        """Pages handed out and not yet back, of EVERY kind: 0 after a
        drained run is the leak invariant."""
        own = self.num_pages - 1 - len(self._free)
        return own + (self.window.used_count if self.window else 0)

    @property
    def capacity(self) -> int:
        """Allocatable pages (the null page is not allocatable)."""
        return self.num_pages - 1

    @property
    def shared_count(self) -> int:
        """Pages currently referenced more than once."""
        return sum(1 for c in self._ref.values() if c > 1)

    def refcount(self, page: int) -> int:
        return self._ref.get(page, 0)

    def pages_for(self, n_tokens: int, kind: str = GLOBAL) -> int:
        """Pages of ``kind`` that hold a sequence of ``n_tokens``: all
        of them for ``global`` (a row every ``stride`` positions), the
        ring at most for ``window``."""
        if kind == GLOBAL:
            return self.logical_pages(-(-n_tokens // self.stride))
        return min(self.logical_pages(n_tokens), self.ring)

    def logical_pages(self, n_tokens: int) -> int:
        """Pages ``n_tokens`` rows fill. A row a position: what a ring
        has held, and the bucket a prompt is forwarded in."""
        return -(-n_tokens // self.page_size)

    def fragmentation(self) -> float:
        """1 - (largest contiguous free run / free pages): 0.0 when the
        free space is one run (or empty). Page-table indirection makes
        fragmentation harmless for correctness; the gauge exists because
        a rising value under sharing means the LIFO stack is being
        diced by mid-stream releases — a debugging signal, not a cost."""
        if not self._free:
            return 0.0
        runs, best = 1, 1
        ordered = sorted(self._free)
        for a, b in zip(ordered, ordered[1:]):
            runs = runs + 1 if b == a + 1 else 1
            best = max(best, runs)
        return 1.0 - best / len(self._free)

    def _record(self, event: str, pages: Tuple[int, ...],
                delta: int) -> None:
        """Ring the event (counting what the bounded ring drops) and
        feed the attached ledger, consuming the one-shot owner tag."""
        h = self.history
        if len(h) == h.maxlen:
            self.history_dropped += 1
        h.append((event, pages, delta))
        led = self.ledger
        if led is not None:
            led.on_pool_event(event, pages, self.tag)
            self.tag = None

    def alloc(self, n: int, kind: str = GLOBAL) -> List[int]:
        if kind != GLOBAL:
            return self.of(kind).alloc(n)
        if n > len(self._free):
            raise RuntimeError(
                f"page pool exhausted: requested {n}, free {len(self._free)} "
                f"of {self.capacity} (admission control should prevent this)"
            )
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            if p == NULL_PAGE or p in self._ref:
                raise RuntimeError(f"allocator invariant broken: page {p} "
                                   f"double-allocated or null")
            self._ref[p] = 1
        self._record("alloc", tuple(pages), +1)
        return pages

    def share(self, pages: List[int]) -> None:
        """Add one reference to each (already allocated) page — the
        prefix-cache hit path: a new reader of an existing page."""
        for p in pages:
            if p not in self._ref:
                raise RuntimeError(f"sharing page {p} that is not allocated")
        for p in pages:
            self._ref[p] += 1
        self._record("share", tuple(pages), +1)

    def release(self, pages: List[int], kind: str = GLOBAL) -> None:
        """Drop one reference per page; pages reaching refcount 0 return
        to the free list (LIFO — placement stays a pure function of the
        event order even under sharing)."""
        if kind != GLOBAL:
            return self.of(kind).release(pages)
        for p in pages:
            if p not in self._ref:
                raise RuntimeError(f"freeing page {p} that is not allocated")
        for p in pages:
            self._ref[p] -= 1
            if self._ref[p] == 0:
                del self._ref[p]
                self._free.append(p)
        self._record("release", tuple(pages), -1)

    # pre-sharing name: release IS free when nothing is shared
    free = release


def init_pages(config, num_pages: int, page_size: int, tp: int = 1,
               kv_dtype: Optional[str] = None, window_pages: int = 0):
    """The pool's device buffers, ``(L, num_pages, page_size, nh*hd)``
    per bank: a position's heads in one row, heads major, so under TP
    each shard holds its nh/tp heads (create the GLOBAL array and shard
    dim 3 over the tensor axis). Kept apart, a ``head_dim`` of 64
    half-fills the TPU's 128 lanes and the compiler puts the PAGES in
    the lanes instead: one page's rows lie strided across its plane and
    every access through a page table re-lays out the plane, or the
    pool (PERF.md, PR 27). ``nh`` is the model's KV heads
    (``serving/blocks.py``): a row of Laguna's is 8 x 128 lanes whatever
    a layer's query heads.

    ``kv_dtype=None`` (or "fp") keeps the fp pool: a bare array pair in
    the model's dtype. ``"int8"`` stores each bank as a PYTREE
    ``{"q": int8 (L, P, ps, nh*hd), "scale": f32 (L, P, ps, nh)}`` —
    the per-page scale plane rides one fp32 scalar per (layer, page
    slot, head), ~hd x 4 bytes lighter than the values it scales. Every
    pool function below dispatches on the structure, so the engine's
    jitted programs, donation, and shard_map specs carry the pair as
    one value either way.

    A model with two cache kinds gets a bank a kind, ``{"global": (L_g,
    num_pages, ..), "window": (L_w, window_pages, ..)}``: the layers of
    a kind stacked in their order in the model.

    A model with a latent row (``PagedModel.latent``) gets ONE bank,
    ``(L, num_pages, page_size, lanes in whole lane tiles)``, a layer an
    attention, and ``None`` where the values' bank would be: the row is
    its own value, and no program of such a model takes a second bank."""
    model = describe(config)
    nh, hd = model.n_kv_head // tp, model.head_dim
    kv_dtype = check_kv_dtype(kv_dtype)
    if model.latent is not None and (kv_dtype, tp) != (None, 1):
        raise ValueError("a latent row is kept whole and in the model's "
                         "dtype: one shard, no int8 bank")

    def bank(layers, pages):
        shape = (layers, pages, page_size, model.row_lanes // tp)
        if kv_dtype is None:
            return jnp.zeros(shape, model.dtype)
        return {"q": jnp.zeros(shape, jnp.int8),
                "scale": jnp.zeros(shape[:-1] + (nh,), jnp.float32)}

    if model.kinds == (GLOBAL,):
        layers = model.layers_of(GLOBAL)
        return bank(layers, num_pages), (
            None if model.latent is not None else bank(layers, num_pages))
    sizes = {GLOBAL: num_pages, WINDOW: window_pages}

    def banks():
        return {k: bank(model.layers_of(k), sizes[k]) for k in model.kinds}

    return banks(), banks()


def by_kind(x) -> dict:
    """A pool bank, a page table or a prefill cache as ``{kind: ..}``:
    a one-kind model's bare value is its ``global`` kind."""
    if isinstance(x, dict) and GLOBAL in x:
        return x
    return {GLOBAL: x}


def _like(x, kinds: dict):
    """``kinds`` back in the form ``x`` came in."""
    return kinds if isinstance(x, dict) and GLOBAL in x else kinds[GLOBAL]


def _rows(x):
    """Values (.., nh, hd) -> the pool's rows (.., nh*hd)."""
    return x.reshape(x.shape[:-2] + (-1,))


def _heads(x, head_dim: int):
    """The pool's rows (.., nh*hd) -> values (.., nh, hd)."""
    return x.reshape(x.shape[:-1] + (-1, head_dim))


def _write_rows(pages, idx, val):
    """Scatter fp values ``val`` (.., nh, hd) into the rows ``idx`` =
    (layer, page, offset) of a WHOLE bank — quantizing on write when the
    bank is int8, value and scale plane in lockstep. All three indices
    on the donated bank keep the update in place: a slice of it
    (``pages[l]``, ``.at[:, page]``) is copied out before it is written."""
    if _is_quantized(pages):
        q, s = quantize_kv(val)
        return {"q": pages["q"].at[idx].set(_rows(q)),
                "scale": pages["scale"].at[idx].set(s)}
    return pages.at[idx].set(_rows(val).astype(pages.dtype))


def _to_lanes(x, lanes: int):
    """``x`` (.., n) with zeros behind it up to ``lanes``."""
    return jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, lanes - x.shape[-1]),))


def _values(pages):
    """A bank's value array (an int8 bank's ``q`` plane)."""
    return pages["q"] if _is_quantized(pages) else pages


def init_state(config, num_slots: int) -> dict:
    """The state bank of ``config``'s model: ``{name: zeros (L,
    num_slots, ..)}`` after ``PagedModel.state``, a row a slot a layer;
    ``{}`` for a model whose layers keep none."""
    model = describe(config)
    return {name: jnp.zeros((model.n_layer, num_slots) + tuple(shape), dtype)
            for name, shape, dtype in model.state}


def write_state(state: dict, new: dict, slot):
    """Put a prefill's state ``new`` ``{name: (L, 1, ..)}`` into row
    ``slot`` of the bank, every layer at once and in place (one
    ``dynamic_update_slice`` a leaf on the donated buffer): whatever the
    slot's last request left there is gone."""
    def put(bank, x):
        start = (0, slot) + (0,) * (bank.ndim - 2)
        return lax.dynamic_update_slice(bank, x.astype(bank.dtype), start)

    return {name: put(bank, new[name]) for name, bank in state.items()}


def state_walk_plan(num_slots: int) -> Tuple[int, int]:
    """How a decode step walks the state bank's ``num_slots`` rows: (rows
    a trip, trips that cover them). A trip takes ``STATE_ROWS`` rows, or
    the largest divisor of ``num_slots`` under it (no trip overhangs)."""
    rows = next(r for r in range(min(STATE_ROWS, num_slots), 0, -1)
                if num_slots % r == 0)
    return rows, num_slots // rows


def walked_state_rows(highest_live, rows: int):
    """Trips the walk makes when the highest live slot is
    ``highest_live`` (-1: none is live): every group of ``rows`` up to
    the one that holds it. The scheduler fills the lowest free slot, so
    the live ones crowd the bank's start. Pure arithmetic, on a traced
    value and on the host's int alike (as :func:`walked_chunks`)."""
    return highest_live // rows + 1


def update_state_rows(state: dict, layer, live, fn, xs):
    """A decode step's read and write of layer ``layer`` of the state
    bank, row ``i`` slot ``i``: ``fn(rows {name: (R, ..)}, xs_rows) ->
    (rows, ys_rows)`` over ``R`` slots a trip (:func:`state_walk_plan`),
    ``xs`` a pytree of (num_slots, ..) arrays cut the same way, as far
    as the highest slot ``live`` (num_slots,) marks
    (:func:`walked_state_rows`). A trip's rows are sliced out of the
    donated bank by (layer, slot) and written back there, so the bank is
    updated in place and what is in flight is a trip's rows, never a
    layer's plane. Returns ``(state, ys (num_slots, ..))``, the rows
    past the walk zeros (they hold no request)."""
    n = live.shape[0]
    rows, n_trips = state_walk_plan(n)
    highest = jnp.max(jnp.where(live, jnp.arange(n), -1))
    trips = jnp.minimum(walked_state_rows(highest, rows), n_trips)

    def at(bank, i):
        return (layer, i * rows) + (0,) * (bank.ndim - 2)

    def one(state, xs, i):
        """``fn`` over trip ``i``'s rows of the bank and of ``xs``."""
        return fn(
            {k: lax.dynamic_slice(b, at(b, i), (1, rows) + b.shape[2:])[0]
             for k, b in state.items()},
            jax.tree_util.tree_map(lambda x: lax.dynamic_slice_in_dim(
                x, i * rows, rows, axis=0), xs))

    def trip(i, carry):
        state, ys = carry
        new, y = one(state, xs, i)
        state = {k: lax.dynamic_update_slice(
            b, new[k][None].astype(b.dtype), at(b, i))
            for k, b in state.items()}
        ys = jax.tree_util.tree_map(
            lambda buf, part: lax.dynamic_update_slice_in_dim(
                buf, part.astype(buf.dtype), i * rows, axis=0), ys, y)
        return state, ys

    like = jax.eval_shape(lambda st, x: one(st, x, 0)[1], state, xs)
    ys = jax.tree_util.tree_map(
        lambda a: jnp.zeros((n,) + a.shape[1:], a.dtype), like)
    return lax.fori_loop(0, trips, trip, (state, ys))


def write_prompt_pages(k_pages, v_pages, cache, phys_pages, pad, page_size,
                      length=None, stride: int = 1):
    """Scatter a prefill's contiguous cache into the pool, in place (the
    L x S_pad rows addressed by layer, page and offset).

    ``cache`` is the prefill's (L, 1, S_pad, nh, hd) pair holding a
    padded prompt: ``pad`` pad slots, then the prompt, then (a
    right-padded prompt, ``length`` its tokens) more padding. Logical
    prompt position p lands in page ``phys_pages[p // page_size]`` at
    offset ``p % page_size`` — the repack drops the padding, so decode
    sees the unpadded 0..len-1 layout. Pad positions route to the NULL
    page. ``phys_pages`` is the slot's full page-table row (fixed width,
    unused tail entries 0) so every bucket shares one compiled program.

    With two cache kinds all three are ``{kind: ..}``; the ``window``
    kind's row is its ring: position p lands in entry ``(p // page_size)
    % ring``, and only the pages the ring still holds at the prompt's
    end are written (the earlier ones would be overwritten anyway).

    A latent model's cache is ``{"rows": (L, 1, S_pad, lanes)}`` and its
    ``v_pages`` None: the one bank is written, ``(bank, None)`` returned.

    ``stride`` > 1 (``blocks.PagedModel.stride``): row ``c`` of the
    ``global`` kind's cache is the summary of positions ``c * stride``
    on, and the first ``length // stride`` of them are written (a chunk
    the prompt ends in has none yet). A kind's cache may carry
    ``"start"``, the position of its first row, where the prefill hands
    over only what the ring must hold.
    """
    if isinstance(cache, dict) and GLOBAL in cache:
        out = {k: _write_prompt(
            k_pages[k], v_pages[k], cache[k], phys_pages[k], pad, page_size,
            length // stride if k == GLOBAL and stride > 1 else length,
            ring=k == WINDOW)
            for k in cache}
        return ({k: o[0] for k, o in out.items()},
                {k: o[1] for k, o in out.items()})
    return _write_prompt(k_pages, v_pages, cache, phys_pages, pad, page_size,
                         length)


def _write_prompt(k_pages, v_pages, cache, phys_pages, pad, page_size,
                  length=None, ring=False):
    if v_pages is None:
        # a latent row is kept in whole lane tiles: zeros behind it
        k_seq = _to_lanes(cache["rows"][:, 0, :, None], k_pages.shape[-1])
    else:
        k_seq, v_seq = cache["k"][:, 0], cache["v"][:, 0]  # (L, S_pad, nh, hd)
    s_pad = k_seq.shape[1]
    pos = jnp.arange(s_pad)
    logical = pos - pad
    if isinstance(cache, dict) and "start" in cache:
        logical = logical + cache["start"]
    valid = logical >= 0
    if length is not None:
        valid = valid & (logical < length)
    lclip = jnp.where(valid, logical, 0)
    page = lclip // page_size
    if ring:
        n = phys_pages.shape[0]
        valid = valid & (page > (length - 1) // page_size - n)
        page = page % n
    dest_page = jnp.where(valid, phys_pages[page], NULL_PAGE)
    dest_off = jnp.where(valid, lclip % page_size, 0)
    layers = jnp.arange(_values(k_pages).shape[0])[:, None]
    idx = (layers, dest_page[None], dest_off[None])
    if v_pages is None:
        return _write_rows(k_pages, idx, k_seq), None
    return _write_rows(k_pages, idx, k_seq), _write_rows(v_pages, idx, v_seq)


def _gather(arr, page_table):
    """Read ``(.., P, ps, X)`` through a (B, W) page table: the table
    dims replace the page dim, then W and ps merge into the contiguous
    (.., B, W*ps, X) view."""
    _, w = page_table.shape
    view = jnp.take(arr, page_table, axis=-3)
    return view.reshape(view.shape[:-3] + (w * arr.shape[-2], arr.shape[-1]))


def gather_pages(pages, page_table, head_dim: int):
    """Read the pool through a page table: (B, W) int32 -> the per-slot
    contiguous view (.., B, W * page_size, nh, hd), the rows split back
    into heads of ``head_dim``, an int8 bank dequantized per (position,
    head). The RECONSTRUCTION of what a table holds: the oracle of the
    parity tests (with :func:`_key_bias` and ``_attn_core``). No program
    reads the pool this way: the decode read is :func:`_attend_rows`,
    over the rows as stored."""
    if _is_quantized(pages):
        q = _heads(_gather(pages["q"], page_table), head_dim)
        return dequantize_kv(q, _gather(pages["scale"], page_table))
    return _heads(_gather(pages, page_table), head_dim)


def page_size_of(pages) -> int:
    """Static page_size of a bank, fp or int8 (the dim before the rows;
    the scale plane shares it)."""
    return _values(pages).shape[-2]


def _key_bias(slopes, q_pos, n_keys):
    """Additive attention bias for queries at GLOBAL positions ``q_pos``
    (B, C) over ``n_keys`` logical key positions: ALiBi over the key
    position + the keep mask ``key_pos <= q_pos`` (causal-by-slot: masks
    not-yet-written offsets, stale page tails from a previous owner, and
    null-page garbage alike). Serving slots hold UNPADDED sequences, so
    plain global positions apply — _decode_bias's for extras=None, with
    a per-row start. Returns (B, nh_local, C, K)."""
    key_pos = jnp.arange(n_keys)
    keep = key_pos[None, None, :] <= q_pos[:, :, None]            # (B, C, K)
    bias = slopes[None, :, None, None] * key_pos.astype(jnp.float32)
    return bias + jnp.where(keep[:, None, :, :], 0.0, NEG_INF)


def walk_plan(page_size: int, table_width: int) -> Tuple[int, int]:
    """How the "gather" read walks a (B, W) page table: (pages a trip,
    trips that cover the table). A trip visits whole pages, about
    ``WALK_KEYS`` key columns; a narrower table is one trip."""
    pages = max(1, min(table_width, WALK_KEYS // page_size))
    return pages, -(-table_width // pages)


def walked_chunks(max_pos, chunk_keys: int):
    """Trips the walk makes when the furthest live query stands at
    position ``max_pos`` (a position inside the table): every chunk up
    to the one that holds it. Pure arithmetic, on a traced value and on
    the host's int alike: the decode program takes its trip count from
    it and the engine its ``decode_key_share``."""
    return max_pos // chunk_keys + 1


def ring_reach(pos, window: int, rule: str):
    """The position whose :func:`walked_chunks` a ring's walk makes for
    rows at ``pos`` (any shape): the furthest row's offset INTO its
    window under the block rule (the ring holds the window's pages in
    order, ``blocks.ring_pages``), its position under the sliding one
    (the walk is then the whole ring once a row is past it). On a traced
    array and on the host's alike."""
    if rule == BLOCK:
        pos = pos % window
    return pos.max()


def walked_rows(n_rows, chunk_keys: int):
    """Trips a walk makes over the first ``n_rows`` rows of a table
    (summaries: a query may see none, and then the walk makes none).
    Arithmetic on a traced value and on the host's int alike."""
    return (n_rows + chunk_keys - 1) // chunk_keys


def _walk_query(q, k_pages, pos, slopes):
    """What the walks of one read share: the block-diagonal query (row
    ``c*nh + h`` holds q[c, h] in the lanes of its KV head ``h // g``
    and zeros elsewhere) in the operands' dtype, which lanes a head
    owns, the queries' positions and ALiBi's slopes in the scores'
    layout, (B, C*nh, 1)."""
    b, c, nh, hd = q.shape
    width = _values(k_pages).shape[-1]
    kv = width // hd
    g = nh // kv
    operand = q.dtype if _is_quantized(k_pages) else k_pages.dtype
    # own[h, r]: lane r of a row belongs to head h's KV head
    own = jnp.arange(width)[None, :] // hd == jnp.arange(nh)[:, None] // g
    lanes = _rows(q) if g == 1 else jnp.tile(q, (1, 1, 1, kv))
    q_bd = jnp.where(own, lanes[:, :, None, :] if g == 1 else lanes, 0)
    q_bd = q_bd.reshape(b, c * nh, width).astype(operand)
    q_pos = jnp.repeat(pos, nh, axis=1)[:, :, None]          # (B, C*nh, 1)
    slope = (None if slopes is None
             else jnp.tile(slopes, c)[None, :, None])        # (1, C*nh, 1)
    return q_bd, own, q_pos, slope


def _walk_table(page_table, page_size: int):
    """(the table as a walk takes it, the walk's chunks): a last chunk
    that overhangs the table reads NULL pages, whose key positions lie
    past every query's."""
    width = page_table.shape[1]
    pages, n_chunks = walk_plan(page_size, width)
    return jnp.pad(page_table, ((0, 0), (0, pages * n_chunks - width)),
                   constant_values=NULL_PAGE), n_chunks


def _walk(carry, q_bd, k_pages, v_pages, layer, table, trips, mask,
          heads, slope=None, guard=False):
    """The online softmax ``carry`` = (m, denom, acc) of the queries
    ``q_bd`` (:func:`_walk_query`) taken over the first ``trips`` chunks
    of ``table`` (B, W: :func:`_walk_table`'s) in layer ``layer``, and
    handed on UNDIVIDED: a read over two tables (a ring, then summaries)
    divides once, after both. ``carry`` None: no key seen yet. ``trips``
    counts chunks of :func:`walk_plan`, at most the table's; ``mask(i)
    -> (keep, key_pos)`` says which of chunk ``i``'s key columns a query
    keeps, (B | 1, C*nh | 1, K), and where they stand (``slope`` reads
    it). ``heads`` = (C,
    nh, hd), the queries' layout. ``guard``: a chunk may hold no key of
    a row at all while its ``m`` is still at the floor, so masked
    columns are zeroed and not left to the exponent."""
    b, n, width = q_bd.shape
    c, nh, hd = heads
    ps = page_size_of(k_pages)
    pages = walk_plan(ps, table.shape[1])[0]
    chunk_keys = pages * ps
    quantized = _is_quantized(k_pages)
    operand = q_bd.dtype

    def rows_of(bank, ids):
        return _values(bank)[layer, ids].reshape(
            b, chunk_keys, width).astype(operand)

    def scales_of(bank, ids):
        """(B, K, nh) -> the scores' layout (B, C*nh, K)."""
        s = bank["scale"][layer, ids].reshape(b, chunk_keys, nh)
        return jnp.tile(jnp.swapaxes(s, 1, 2), (1, c, 1))

    def chunk(i, carry):
        m, denom, acc = carry
        ids = lax.dynamic_slice_in_dim(table, i * pages, pages, axis=1)
        keep, key_pos = mask(i)
        s = jnp.einsum("bnr,bkr->bnk", q_bd, rows_of(k_pages, ids),
                       preferred_element_type=jnp.float32)
        if quantized:
            s = s * scales_of(k_pages, ids)
        s = s * (hd ** -0.5)
        if slope is not None:
            s = s + slope * key_pos.astype(jnp.float32)
        s = s + jnp.where(keep, 0.0, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        if guard:
            p = jnp.where(keep, p, 0.0)
        alpha = jnp.exp(m - m_new)
        denom = denom * alpha + p.sum(-1)
        if quantized:
            p = p * scales_of(v_pages, ids)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bnk,bkr->bnr", p.astype(operand), rows_of(v_pages, ids),
            preferred_element_type=jnp.float32)
        return m_new, denom, acc

    if carry is None:
        carry = (jnp.full((b, n), NEG_INF, jnp.float32),
                 jnp.zeros((b, n), jnp.float32),
                 jnp.zeros((b, n, width), jnp.float32))
    return lax.fori_loop(0, trips, chunk, carry)


def _walk_context(carry, own, heads, qmask, out_dtype):
    """The ONE division of a read: (m, denom, acc) -> the context (B, C,
    nh*hd) in ``out_dtype``, every head keeping its KV head's lanes, pad
    queries zero."""
    _, denom, acc = carry
    c, nh, hd = heads
    b, width = acc.shape[0], acc.shape[-1]
    kv = width // hd
    g = nh // kv
    # a query is its own key (written before the read) and a dead slot
    # reads key 0 of the NULL page, so denom >= 1
    ctx = (acc / denom[..., None]).reshape(b, c, nh, width)
    if g == 1:
        ctx = jnp.sum(jnp.where(own, ctx, 0.0), axis=2)      # (B, C, nh*hd)
    else:
        # head h keeps the hd lanes of KV head h // g
        mine = jnp.arange(kv)[None, :] == jnp.arange(nh)[:, None] // g
        ctx = jnp.sum(jnp.where(mine[:, :, None],
                                ctx.reshape(b, c, nh, kv, hd), 0.0), axis=3)
        ctx = ctx.reshape(b, c, nh * hd)
    if qmask is not None:
        # pad-query context is ZERO in every attention path
        ctx = ctx * qmask[:, :, None].astype(ctx.dtype)
    return ctx.astype(out_dtype)


def _ring_mask(pos, q_pos, ring, ps, window, rule):
    """``mask`` of :func:`_walk` over a window layer's RING: entry ``r``
    holds the newest logical page ``j <= pos // page_size`` with ``j %
    ring == r``, so a key's position is read off the query's own. Kept:
    the keys from ``blocks.window_start`` of the rule to the query."""
    if rule == BLOCK and ring * ps != window:
        raise ValueError(f"a block window's ring is its own pages in "
                         f"order: {ring} pages of {ps} are not a window "
                         f"of {window}")
    b = pos.shape[0]
    pages = walk_plan(ps, ring)[0]
    cur = pos[:, :1] // ps                                   # (B, 1)

    def mask(i):
        entry = i * pages + jnp.arange(pages)                # (pages,)
        page = cur - (cur - entry[None, :]) % ring           # (B, pages)
        key_pos = (page[:, :, None] * ps
                   + jnp.arange(ps)).reshape(b, 1, pages * ps)
        held = jnp.repeat((entry < ring)[None, :] & (page >= 0), ps,
                          axis=1)[:, None, :]
        keep = held & (key_pos <= q_pos)
        if rule == SLIDING:
            return keep & (key_pos > q_pos - window), key_pos
        return keep & (key_pos >= window_start(q_pos, window, rule)), key_pos

    return mask


def _attend_rows(q, k_pages, v_pages, layer, page_table, pos, qmask, slopes,
                 out_dtype, window: Optional[int] = None,
                 rule: str = SLIDING):
    """Softmax attention of ``q`` (B, C, nh, hd) at global positions
    ``pos`` (B, C) over layer ``layer`` of the pool, read through
    ``page_table`` (B, W) AS STORED: a gathered row keeps its
    ``kv*hd`` lanes and the pool's dtype, and is never split into heads
    or widened in memory.

    All heads' scores come from one matrix-unit contraction of the rows
    against a block-diagonal query (row ``c*nh + h`` holds q[c, h] in
    the lanes of its KV head ``h // g`` and zeros elsewhere, so the
    other heads' lanes add exact zeros; ``g = nh / kv`` query heads
    share a KV head, 1 for BLOOM); the context product gives every
    (query, head) row all ``kv*hd`` lanes, of which the head keeps its
    KV head's. Accumulation and softmax are float32; the probabilities
    are rounded to the operands' dtype before the value product, as
    :func:`_attn_core` rounds them. An int8 bank's per-(position, head)
    scales multiply the scores and the probabilities, never a
    dequantized copy of the rows. ``slopes`` (nh,) is ALiBi's bias on
    the key position, ``None`` for none.

    The keys are visited in chunks of whole pages (:func:`walk_plan`)
    under an online softmax (:func:`_walk`), and only as far as the
    furthest live query: :func:`walked_chunks` of the largest position
    among the queries ``qmask`` keeps. Chunks beyond are not gathered;
    inside the walk the bias masks what ``_key_bias`` masks (columns
    past a query's own position: unwritten offsets, stale tails,
    NULL-page garbage).

    ``window``: the layer keeps the keys with ``0 <= q_pos - k_pos <
    window`` (``rule`` "sliding") or those from the last multiple of
    ``window`` on ("block"), and ``page_table`` is a RING
    (``blocks.ring_pages``, :func:`_ring_mask`); the walk is the ring's
    few chunks however long the sequence (one query a row), and under
    the block rule only as far as the furthest row stands into its
    window (:func:`ring_reach`).
    Returns (B, C, nh*hd) in ``out_dtype``, pad queries zero."""
    b, c, nh, hd = q.shape
    ps = page_size_of(k_pages)
    ring = page_table.shape[1]
    if window is not None and c != 1:
        raise ValueError("a window layer's ring is read a query a row")
    chunk_keys = walk_plan(ps, ring)[0] * ps
    table, n_chunks = _walk_table(page_table, ps)
    q_bd, own, q_pos, slope = _walk_query(q, k_pages, pos, slopes)
    live = pos if qmask is None else jnp.where(qmask, pos, 0)
    reach = (jnp.max(live) if window is None
             else ring_reach(live, window, rule))
    trips = jnp.minimum(walked_chunks(reach, chunk_keys), n_chunks)
    if window is None:
        def mask(i):
            key_pos = i * chunk_keys + jnp.arange(chunk_keys)
            return key_pos <= q_pos, key_pos
    else:
        mask = _ring_mask(pos, q_pos, ring, ps, window, rule)
    carry = _walk(None, q_bd, k_pages, v_pages, layer, table, trips, mask,
                  (c, nh, hd), slope, guard=window is not None)
    return _walk_context(carry, own, (c, nh, hd), qmask, out_dtype)


def _attend_summarised(q, k_pages, v_pages, layers, tables, pos, out_dtype,
                       window: int, rule: str, chunk: int):
    """ONE softmax of ``q`` (B, 1, nh, hd) at positions ``pos`` (B, 1)
    over two caches (``blocks.Summaries``), both read as stored: the
    ring of the window's exact keys and values (``k_pages[WINDOW]``
    through ``tables[WINDOW]``, under ``rule``), then the summaries of
    what the window has left (``k_pages[GLOBAL]``, a row a ``chunk`` of
    positions, the first ``blocks.summaries_seen`` of them), as far as
    the furthest row sees any. The second walk takes the first's (m,
    denom, acc); the division comes after both. ``layers`` =
    {kind: the bank layer}. Returns (B, 1, nh*hd)."""
    b, c, nh, hd = q.shape
    if c != 1:
        raise ValueError("a ring and its summaries are read a query a row")
    heads = (c, nh, hd)
    kw, kg = k_pages[WINDOW], k_pages[GLOBAL]
    ps = page_size_of(kw)
    ring = tables[WINDOW].shape[1]
    q_bd, own, q_pos, _ = _walk_query(q, kw, pos, None)
    with jax.named_scope("eva.read.window"):
        table, n_chunks = _walk_table(tables[WINDOW], ps)
        trips = walked_chunks(ring_reach(pos, window, rule),
                              walk_plan(ps, ring)[0] * ps)
        carry = _walk(
            None, q_bd, kw, v_pages[WINDOW], layers[WINDOW], table,
            jnp.minimum(trips, n_chunks),
            _ring_mask(pos, q_pos, ring, ps, window, rule), heads,
            guard=True)
    seen = summaries_seen(q_pos, window, chunk, rule)        # (B, nh, 1)
    chunk_keys = walk_plan(ps, tables[GLOBAL].shape[1])[0] * ps

    def mask(i):
        row = i * chunk_keys + jnp.arange(chunk_keys)
        return row < seen, row

    with jax.named_scope("eva.read.summary"):
        table, n_chunks = _walk_table(tables[GLOBAL], ps)
        carry = _walk(
            carry, q_bd, kg, v_pages[GLOBAL], layers[GLOBAL], table,
            jnp.minimum(walked_rows(jnp.max(seen), chunk_keys), n_chunks),
            mask, heads, guard=True)
    return _walk_context(carry, own, heads, None, out_dtype)


def _attend_latent(q, pages, layer, page_table, pos, qmask, out_dtype, row):
    """Softmax attention of ``q`` (B, C, H, lanes) at global positions
    ``pos`` (B, C) over layer ``layer`` of a LATENT bank (``row``:
    ``blocks.LatentRow``), read through ``page_table`` (B, W) as stored:
    every head's query meets the same rows, so a chunk of them is
    gathered ONCE and used for both products on the matrix unit, the
    scores over all of its (stored) lanes, ``(B, C*H, lanes) x (B, K,
    lanes)``, and the context over its first ``value_lanes``, ``(B, C*H,
    K) x (B, K, value_lanes)``. No key and no value a head exists at any point.
    Float32 scores and accumulation, the probabilities rounded to the
    bank's dtype, the walk as far as the furthest live query and the
    mask as :func:`_attend_rows` has them. Returns (B, C, H *
    value_lanes) in ``out_dtype``, pad queries zero."""
    b, c, nh, _ = q.shape
    ps, lanes = page_size_of(pages), pages.shape[-1]
    n, vl = c * nh, row.value_lanes
    width = page_table.shape[1]
    n_pages, n_chunks = walk_plan(ps, width)
    chunk_keys = n_pages * ps
    table = jnp.pad(page_table, ((0, 0), (0, n_pages * n_chunks - width)),
                    constant_values=NULL_PAGE)
    # the bank keeps a row in whole lane tiles, zeros behind it: zeros
    # behind the query too, which add nothing to a score
    q2 = _to_lanes(q.reshape(b, n, -1), lanes).astype(pages.dtype)
    q_pos = jnp.repeat(pos, nh, axis=1)[:, :, None]          # (B, C*H, 1)
    live = pos if qmask is None else jnp.where(qmask, pos, 0)
    trips = jnp.minimum(walked_chunks(jnp.max(live), chunk_keys), n_chunks)

    def chunk(i, carry):
        m, denom, acc = carry
        ids = lax.dynamic_slice_in_dim(table, i * n_pages, n_pages, axis=1)
        rows = pages[layer, ids].reshape(b, chunk_keys, lanes)
        keep = i * chunk_keys + jnp.arange(chunk_keys) <= q_pos
        s = jnp.einsum("bnr,bkr->bnk", q2, rows,
                       preferred_element_type=jnp.float32) * row.scale
        s = s + jnp.where(keep, 0.0, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        denom = denom * alpha + p.sum(-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bnk,bkr->bnr", p.astype(pages.dtype), rows[..., :vl],
            preferred_element_type=jnp.float32)
        return m_new, denom, acc

    _, denom, acc = lax.fori_loop(0, trips, chunk, (
        jnp.full((b, n), NEG_INF, jnp.float32),
        jnp.zeros((b, n), jnp.float32),
        jnp.zeros((b, n, vl), jnp.float32)))
    ctx = (acc / denom[..., None]).reshape(b, c, nh * vl)
    if qmask is not None:
        ctx = ctx * qmask[:, :, None].astype(ctx.dtype)
    return ctx.astype(out_dtype)


def _paged_forward(params, tokens, k_pages, v_pages, page_table, pos,
                   dest_page, dest_off, qmask, config, tp_axis,
                   n_layers=None, live=None, state=None):
    """The forward both paged programs share: ``tokens`` (B, C) at
    global positions ``pos`` (B, C) through the model's blocks
    (``serving/blocks.py``: the first ``n_layers`` of them, all by
    default) and its final norm. The pool rides the layer loop's CARRY:
    layer ``l`` writes its B x C rows at (l, dest_page, dest_off) and
    attention reads through gathers addressed by (l, page)
    (:func:`_attend_rows`), so the donated pool is updated in place —
    scanned in and stacked out (xs/ys) it is copied once a call and
    each layer's plane twice more.

    A group of stacked layers is one ``fori_loop``; a group of one
    unstacked layer is traced in line (its shapes are its own). With
    two cache kinds ``k_pages``, ``v_pages``, ``page_table``,
    ``dest_page``, ``dest_off`` are ``{kind: ..}`` and a layer's bank
    index counts the layers of its kind before it. A layer whose ONE
    attention keeps a ring and summaries (``blocks.Summaries``) writes
    both kinds' banks (:func:`_write_summary`) and reads both under one
    softmax (:func:`_attend_summarised`). ``live`` (B, C) bool says which
    positions are real, for a block that sends rows somewhere (the
    experts) or keeps a state a slot. ``state`` is the state bank
    (:func:`init_state`; ``{}`` or None for a model without): it rides
    the same carry, and a group's ``mix`` reads and overwrites its rows
    of the layer between ``qkv`` and ``finish`` (one query a row, row
    ``i`` slot ``i``: a decode step). A layer that attends more than
    once (``LayerGroup.more``) writes and reads a bank layer an
    attention, ``l``, ``l + 1``, ..; over a latent row ``v_pages`` is
    None, the one bank is written once an attention and read by
    :func:`_attend_latent`. Returns (hidden, k_pages, v_pages, counters,
    state): what the blocks' ``finish`` brought out, stacked over the
    layers that bring any, ``{}`` for a model with none."""
    model = describe(config, tp_axis)
    c = tokens.shape[1]
    state = state or {}
    if model.state and (not state or c != 1 or live is None):
        raise ValueError("a model with a state a slot runs the paged "
                         "forward as a decode step over its state bank: "
                         "one query a row, row i slot i")
    kp, vp = by_kind(k_pages), by_kind(v_pages)
    tables, dest = by_kind(page_table), by_kind(dest_page)
    offs = by_kind(dest_off)

    x = model.embed(params, tokens)
    seen = dict.fromkeys(model.kinds, 0)      # layers of a kind so far
    # by default every layer the banks hold (a pool cut to fewer layers
    # than the model runs the first of them)
    left = (sum(_values(kp[k]).shape[0] for k in model.kinds)
            if n_layers is None else n_layers)
    brought = []

    for grp in model.groups:
        a = grp.attends                       # bank layers a layer fills
        take = min(grp.n, left // a)
        if take <= 0:
            break
        left -= take * a
        kind, base = grp.kind, seen[grp.kind]
        sm = grp.summaries
        # the banks the group's layers write: its kind's and, where its
        # attention keeps summaries, the global one beside it, whose
        # layer is the ring's moved by what came before the group
        fills = (kind,) if sm is None else (kind, GLOBAL)
        shift = 0 if sm is None else seen[GLOBAL] - base
        for k in fills:
            seen[k] += grp.n * a
        window = model.window if kind == WINDOW else None
        slopes = grp.slopes() if grp.slopes is not None else None
        blocks = grp.params(params)
        halves = ((grp.qkv, grp.finish),) + grp.more

        def layer(l, h, kps, vps, st, blk):
            """Layer whose first attention is bank layer ``l``; ``kps``,
            ``vps``: {kind: bank} of the kinds it writes."""
            kps, vps = dict(kps), dict(vps)
            for j, (qkv, finish) in enumerate(halves):
                lj = l + j if j else l        # (no ``+ 0`` in a program)
                q, k, v, saved = qkv(blk, h, pos)
                if model.latent is not None:
                    # the row in the bank's whole lane tiles
                    k = _to_lanes(k, kps[kind].shape[-1])
                at = (lj, dest[kind], offs[kind])
                kps[kind] = _write_rows(kps[kind], at, k)
                if vps[kind] is not None:
                    vps[kind] = _write_rows(vps[kind], at, v)
                kpk, vpk = kps[kind], vps[kind]
                # between a layer's attentions ``h`` may hold more than
                # the hidden state, which comes first
                dtype = jax.tree_util.tree_leaves(h)[0].dtype
                if sm is not None:
                    lay = {kind: lj, GLOBAL: lj + shift if shift else lj}
                    kps[GLOBAL], vps[GLOBAL] = _write_summary(
                        sm, blk, kps, vps, lay, dest, offs, model.head_dim)
                    ctx = _attend_summarised(
                        q, kps, vps, lay, tables, pos, dtype, window,
                        model.window_rule, sm.chunk)
                elif model.latent is not None:
                    ctx = _attend_latent(q, kpk, lj, tables[kind], pos,
                                         qmask, dtype, model.latent)
                else:
                    ctx = _attend_rows(q, kpk, vpk, lj, tables[kind], pos,
                                       qmask, slopes, dtype, window,
                                       model.window_rule)
                if grp.mix is not None:
                    saved, st = grp.mix(blk, saved, st, l, live[:, 0])
                h, out = finish(blk, h, ctx, saved, live)
            return h, kps, vps, st, out

        carry = (x, {k: kp[k] for k in fills}, {k: vp[k] for k in fills},
                 state)
        if grp.stacked:
            if a != 1:
                raise ValueError("a layer that attends more than once is "
                                 "traced in line: its group is not stacked")

            def body(l, carry):
                # l counts the kind's layers, the stack the group's own
                own = l - base if base else l
                blk = jax.tree_util.tree_map(
                    lambda a: lax.dynamic_index_in_dim(a, own, 0,
                                                       keepdims=False),
                    blocks)
                return layer(l, *carry, blk)[:4]

            x, kps, vps, state = lax.fori_loop(base, base + take, body, carry)
        else:
            x, kps, vps, state, out = layer(base, *carry, blocks)
            if out is not None:
                brought.append(out)
        kp.update(kps)
        vp.update(vps)
    x = model.final(params, x)
    counters = {}
    if brought and model.counters:
        # a layer brings an array, under the model's one name, or a dict
        # of named ones: stacked over the layers either way
        counters = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *brought)
        if not isinstance(counters, dict):
            counters = {model.counters: counters}
    if model.summaries is not None and live is not None:
        counters = dict(counters, summary_rows=_summary_counters(
            model, pos[:, 0], live[:, 0], tables, page_size_of(kp[GLOBAL])))
    return x, _like(k_pages, kp), _like(v_pages, vp), counters, state


def _write_summary(sm, blk, kps, vps, layers, dest, offs, head_dim: int):
    """The summary a decode step's layer makes (``sm``:
    ``blocks.Summaries``), after its key and value are in the ring: the
    ring's page that the row's position lies in, pooled (``pool`` hook:
    a summary is its own chunk's rows and nothing else), ONE row a slot
    written to the summary bank at ``dest[GLOBAL]``, which is the NULL
    page but in the step whose position completes the chunk
    (:func:`paged_decode_step`). ``layers``: {kind: the bank layer}.
    Returns the ``global`` kind's (k bank, v bank)."""
    kw, vw = kps[WINDOW], vps[WINDOW]
    if sm.chunk != page_size_of(kw):
        raise ValueError(
            f"a summary is pooled from ONE page of the ring: the chunk "
            f"({sm.chunk}) has to be the page size ({page_size_of(kw)})")
    if dest[WINDOW].shape[1] != 1:
        raise ValueError("a ring and its summaries take a query a row: "
                         "a decode step")
    with jax.named_scope("eva.pool"):
        page = dest[WINDOW][:, 0]
        lw = layers[WINDOW]
        k_sum, v_sum = sm.pool(blk, _heads(kw[lw, page], head_dim),
                               _heads(vw[lw, page], head_dim))
    with jax.named_scope("eva.summary_write"):
        to = (layers[GLOBAL], dest[GLOBAL], offs[GLOBAL])
        return (_write_rows(kps[GLOBAL], to, k_sum[:, None]),
                _write_rows(vps[GLOBAL], to, v_sum[:, None]))


def _summary_counters(model, pos, live, tables, page_size: int):
    """What the two walks of ONE layer's summarised read needed and
    gathered in a decode step, over the live rows ``live`` (B,) at
    ``pos`` (B,): how many they are, the keys the softmax has (the
    window's exact ones, the summaries seen), the rows the walks brought
    in for them (every live row's table is walked as far as the furthest
    row's), and the summaries this step wrote. ONE int32 vector in
    ``SUMMARY_COUNTERS``' order: the host fetches it in one transfer."""
    sm, window, rule = model.summaries, model.window, model.window_rule
    seen = summaries_seen(pos, window, sm.chunk, rule)
    start = window_start(pos, window, rule)
    alive = live.sum()
    reach = ring_reach(pos, window, rule)

    def gathered(table, trips):
        pages, n_chunks = walk_plan(page_size, table.shape[1])
        keys = pages * page_size
        return alive * jnp.minimum(trips(keys), n_chunks) * keys

    counted = {
        "rows_live": alive,
        "window_rows_needed": jnp.where(
            live, pos - (start > 0) * start + 1, 0).sum(),
        "window_rows_gathered": gathered(
            tables[WINDOW], lambda keys: walked_chunks(reach, keys)),
        "summary_rows_needed": jnp.where(live, seen, 0).sum(),
        "summary_rows_gathered": gathered(
            tables[GLOBAL], lambda keys: walked_rows(jnp.max(seen), keys)),
        "summaries_written": (live & ((pos + 1) % sm.chunk == 0)).sum(),
    }
    return jnp.stack([counted[name].astype(jnp.int32)
                      for name in SUMMARY_COUNTERS])


def _dest(page_table, page_idx, ring: bool):
    """The physical page of logical page ``page_idx`` (B, C) in a (B, W)
    table, or in a ring of W entries."""
    if ring:
        page_idx = page_idx % page_table.shape[1]
    return jnp.take_along_axis(page_table, page_idx, axis=1)


def paged_decode_step(params, tokens, k_pages, v_pages, page_table, seq_lens,
                      config, tp_axis=None, write_ok=None,
                      draft_layers: Optional[int] = None,
                      with_counters: bool = False, state=None):
    """One decode step for every slot of the ragged active batch.

    ``tokens`` (B,) are the pending tokens (each slot's last emitted
    token), ``seq_lens`` (B,) the number of tokens already cached per
    slot — the pending token's position. Each slot's k/v row is written
    in place through its ``page_table`` (B, W) row at page ``seq_len //
    ps``, offset ``seq_len % ps``; attention reads the pool's rows
    through it (the loop is :func:`_paged_forward`'s). Padded slots must
    point every table entry at the NULL page (their writes and reads
    are garbage-in/garbage-out, masked by the bias and discarded by the
    scheduler). ``config`` is the model's config or its
    ``blocks.PagedModel``; with two cache kinds ``k_pages``,
    ``v_pages`` and ``page_table`` are ``{kind: ..}``, the ``window``
    kind's table a ring.

    ``write_ok`` (B,) bool routes a row's k/v write to the NULL page
    when False — the self-speculative draft loop uses it to cap
    per-slot draft depth inside one compiled program. ``draft_layers``
    (static) runs only the FIRST k transformer blocks before the final
    LN and lm head — the shallow-exit draft model that shares every
    weight with the verifier; it bounds the layer loop, so its k/v
    writes land in the pool's first k layers and no deeper layer is
    touched (the verification pass later overwrites them with
    byte-identical values, since layer i's k/v depend only on the token
    sequence and layers < i).

    Over a latent row (``PagedModel.latent``) ``k_pages`` is the one
    bank and ``v_pages`` None, there as in the result.

    ``state``: the state bank of a model whose layers keep one
    (:func:`init_state`), row ``i`` slot ``i`` of the batch: a row with
    ``seq_lens`` > 0 has its state read and overwritten, any other keeps
    what it holds.

    Returns (logits (B, V_local), k_pages, v_pages), with
    ``with_counters`` a fourth: the blocks' counters (a slot with
    ``seq_lens`` 0 holds no request and is sent to no expert), and where
    ``state`` is given the bank last. Under
    ``tp_axis`` the logits are the LOCAL vocab shard — pair with
    ``_decode.global_greedy_pick`` like the sharded generate driver.
    """
    model = describe(config, tp_axis)
    ps = page_size_of(by_kind(k_pages)[GLOBAL])
    page_idx = (seq_lens // ps)[:, None]
    off = seq_lens % ps
    summarised = model.stride > 1
    phys = {k: _dest(t, page_idx, k == WINDOW)[:, 0]
            for k, t in by_kind(page_table).items()
            if not (summarised and k == GLOBAL)}
    if write_ok is not None:
        if summarised:
            raise ValueError("write_ok caps a draft, which is not built "
                             "over a ring and its summaries")
        phys = {k: jnp.where(write_ok, p, NULL_PAGE) for k, p in phys.items()}
        off = jnp.where(write_ok, off, 0)
    if summarised:
        # a row of the global bank is a SUMMARY, a chunk of positions:
        # the step whose position completes the chunk writes it, at the
        # chunk's index; any other step's goes to the NULL page
        row = seq_lens // model.stride
        done = (seq_lens + 1) % model.stride == 0
        phys[GLOBAL] = jnp.where(done, _dest(
            page_table[GLOBAL], (row // ps)[:, None], False)[:, 0], NULL_PAGE)
        row_off = jnp.where(done, row % ps, 0)
    # (B,) -> the forward's (B, 1); the kinds write at one offset, a
    # summary at its own
    tokens, pos = tokens[:, None], seq_lens[:, None]
    dest_page = {k: p[:, None] for k, p in phys.items()}
    dest_off = dict.fromkeys(phys, off[:, None])
    if summarised:
        dest_off[GLOBAL] = row_off[:, None]
    x, k_pages, v_pages, counters, new_state = _paged_forward(
        params, tokens, k_pages, v_pages, page_table, pos,
        _like(page_table, dest_page), _like(page_table, dest_off), None,
        model, tp_axis, n_layers=draft_layers,
        live=((seq_lens > 0)[:, None]
              if model.counters or model.state or summarised else None),
        state=state)
    logits = model.logits(params, x)[:, 0]  # (B, V_local)
    out = (logits, k_pages, v_pages) + ((counters,) if with_counters else ())
    return out if state is None else out + (new_state,)


def export_page_slab(pages, page_ids, head_dim: int, wire_dtype=None):
    """Page EXPORT view for cross-pool KV streaming (serving/disagg/):
    gather ``page_ids`` (W,) int32 out of one bank into a contiguous
    slab ``(L, W, ps, nh, hd)`` at WIRE precision (the small slab is
    split into heads, never the pool). An int8 bank ships its ``{"q",
    "scale"}`` planes verbatim — quantized pages are NEVER dequantized
    in flight; an fp bank optionally down-casts to ``wire_dtype="bf16"``
    (the distributed/compressed.py convention — exact when the pool is
    already bf16, lossy for an fp32 pool). Pure jax: jit it on the
    source pool's mesh and the gather resolves this shard's heads; the
    host fetch of the result is the resharding point."""
    if _is_quantized(pages):
        if wire_dtype is not None:
            raise ValueError(
                "int8 pools define their own wire format (q + scale); "
                f"wire_dtype={wire_dtype!r} does not apply"
            )
        return {"q": _heads(jnp.take(pages["q"], page_ids, axis=1), head_dim),
                "scale": jnp.take(pages["scale"], page_ids, axis=1)}
    slab = _heads(jnp.take(pages, page_ids, axis=1), head_dim)
    if wire_dtype == "bf16":
        return slab.astype(jnp.bfloat16)
    if wire_dtype is not None:
        raise ValueError(f"unknown wire_dtype {wire_dtype!r} "
                         f"(fp pools support None or 'bf16')")
    return slab


def import_page_slab(pages, slab, dst_ids):
    """Page IMPORT view: scatter a wire slab ``(L, W, ps, nh, hd)`` into
    ``dst_ids`` (W,) of one bank, in place (pages addressed by layer and
    page). The quantized layout lands q and scale planes together (still
    never dequantized — the decode pool's gather does that, per read);
    a bf16 wire slab up-casts to the pool dtype here. Padding entries
    route to the NULL page, the same sink every other pad write uses."""
    idx = (jnp.arange(_values(pages).shape[0])[:, None], dst_ids[None])
    if _is_quantized(pages):
        return {"q": pages["q"].at[idx].set(_rows(slab["q"])),
                "scale": pages["scale"].at[idx].set(slab["scale"])}
    return pages.at[idx].set(_rows(slab).astype(pages.dtype))


def copy_page(k_pages, v_pages, src, dst):
    """Copy-on-write duplication: device-copy one physical page (every
    layer's k and v planes) from ``src`` to ``dst``. The prefix cache
    uses it when a request's unique tail begins MID-page of a shared
    page — the new owner gets a private copy of the shared tokens' KV
    and writes its tail there, while readers of ``src`` are untouched.
    ``src``/``dst`` are runtime scalars: one compiled program covers
    every copy. An int8 bank copies its scale plane WITH the page —
    COW'd quantized values stay exactly the values the readers of
    ``src`` dequantize."""

    def cp(plane):
        return plane.at[:, dst].set(jnp.take(plane, src, axis=1))

    return (
        jax.tree_util.tree_map(cp, k_pages),
        jax.tree_util.tree_map(cp, v_pages),
    )


def paged_prefill_chunk(params, tokens, k_pages, v_pages, page_table, start,
                        n_valid, config, tp_axis=None, all_logits=False):
    """Forward one CHUNK of C tokens per row straight through the pool.

    The prefill half of a chunked-prefill mixed step: ``tokens`` (B, C)
    are each row's next prompt tokens, ``start`` (B,) the logical
    position of the row's first chunk token (= tokens already cached,
    whether written by earlier chunks or SHARED from the prefix cache),
    ``n_valid`` (B,) how many of the C are real. Each valid token's k/v
    is written through the row's page table; pad tails route writes to
    the NULL page and get zero context. Attention is causal over the
    global position — every cached position plus the chunk's own
    earlier tokens — with the same ALiBi-over-global-position bias as
    the decode step (:func:`_paged_forward` serves both), so chunk
    boundaries are invisible in the math.

    Returns (logits, k_pages, v_pages): logits at each row's LAST VALID
    position, (B, V_local) — the next-token distribution chunked
    prefill needs — or at EVERY chunk position, (B, C, V_local), with
    ``all_logits=True`` (self-speculative verification scores the whole
    draft bundle in one pass through this same paged path).
    """
    model = describe(config, tp_axis)
    if model.kinds != (GLOBAL,):
        raise ValueError("a prefill chunk reads one cache kind: a window "
                         "layer's ring holds one query a row")
    if model.state:
        raise ValueError("a prefill chunk is not built for a model with a "
                         "state a slot: its prefill is the model's own")
    c = tokens.shape[1]
    ps = page_size_of(k_pages)
    pos = start[:, None] + jnp.arange(c)[None, :]             # (B, C)
    valid = jnp.arange(c)[None, :] < n_valid[:, None]         # (B, C)
    dest_page = jnp.where(
        valid, jnp.take_along_axis(page_table, pos // ps, axis=1), NULL_PAGE
    )
    dest_off = jnp.where(valid, pos % ps, 0)
    x, k_pages, v_pages, _, _ = _paged_forward(
        params, tokens, k_pages, v_pages, page_table, pos, dest_page,
        dest_off, valid, model, tp_axis)
    if all_logits:
        return model.logits(params, x), k_pages, v_pages        # (B, C, V)
    last = jnp.take_along_axis(x, (n_valid - 1)[:, None, None], axis=1)
    logits = model.logits(params, last)[:, 0]                   # (B, V_local)
    return logits, k_pages, v_pages
