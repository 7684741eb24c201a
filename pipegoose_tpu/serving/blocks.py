"""What the paged programs and the engine know of a model: ONE
description of its blocks and of its cache.

``serving/kv_pool.py`` owns the pool, the page tables, the writes and
the attention read; a model says, through a :class:`PagedModel`, how a
token becomes a hidden state, what a layer computes before attention
(``qkv``: norm, projections, rotary) and after it (``finish``: gate or
none, output projection, feed-forward), and how a hidden state becomes
logits. Layers come in :class:`LayerGroup` s: ``n`` layers of one shape
stacked on a leading axis and walked by one ``fori_loop`` (BLOOM: one
group), or a single unstacked layer where shapes differ (Laguna: a group
a layer, 48 or 72 query heads, dense or sparse).

A group's ``kind`` names the cache of keys and values its layers'
attention keeps, which is APPENDED to, a row a position:

* ``"global"``: every page of a sequence, through a page table as wide
  as ``max_context``;
* ``"window"``: the last ``window + page_size`` keys in a ring of
  ``ring_pages(window, page_size)`` pages, logical page ``j`` in ring
  entry ``j % ring``; the read masks what has left the window.

A layer may keep a second cache beside it, which is OVERWRITTEN: a
``state`` of fixed size a sequence (a recurrence's, a short
convolution's last inputs), held a slot a layer in a bank beside the
pool (``kv_pool.init_state``) and not paged. The model gives its shapes
(``PagedModel.state``), its group a ``mix`` hook that reads and returns
the bank in a decode step, and its ``prefill`` the state after the last
real token of a bucketed prompt, which the engine puts in the slot at
admission.

What a cached row IS belongs to the description too. By default it is
``n_kv_head x head_dim`` lanes of keys in one bank and as many of values
in a second. A model with LATENT attention names a :class:`LatentRow`
instead: ONE row a token in ONE bank, which every query head reads, the
row's first lanes being the value as well; no second bank exists
(``kv_pool.init_pages``, ``_attend_latent``).

A layer may attend MORE THAN ONCE (``LayerGroup.more``): each attention
has a row of its own a token, so a layer of ``a`` attentions fills ``a``
bank layers, attention ``j`` of layer ``l`` bank layer ``a * l + j``.

ONE attention may read TWO caches under one softmax
(``LayerGroup.summaries``, a :class:`Summaries`): the ring of its
window's keys and values, and beside it, in ``global`` pages, one POOLED
key and value a ``chunk`` of positions, made from the ring's own rows
(the ``pool`` hook) in the step that completes the chunk. Which exact
keys a query keeps is the model's window rule (``PagedModel.
window_rule``): ``"sliding"``, the last ``window`` positions, or
``"block"``, the positions since the last multiple of ``window``; which
summaries it sees follows from it (:func:`summaries_seen`: the chunks
that lie wholly behind what the rule keeps exact). A row of such a
model's ``global`` bank stands for ``chunk`` positions
(``PagedModel.stride``), and its pages are counted so.

BLOOM is the first instance (:func:`bloom_model`), Laguna the second
(``models/laguna.py:paged_model``: two kinds), Falcon-H1 the third
(``models/falcon_h1.py:paged_model``: global pages and a state in every
block), LongCat-Flash the fourth (``models/longcat_flash.py:paged_model``:
a latent row, two attentions a block), EvaByte the fifth
(``models/evabyte.py:paged_model``: a block window's ring and a summary
a chunk under one softmax), SmallThinker the sixth
(``models/smallthinker.py:paged_model``: Laguna's two kinds, a ring of
257 pages; ``qkv`` makes the router's picks from the attention's input
and hands them to ``finish`` as ``saved``). A config object that has a ``paged_model(tp_axis)`` method
describes itself; any other is taken for a BLOOM (:func:`describe`).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Optional, Tuple

GLOBAL, WINDOW = "global", "window"
KINDS = (GLOBAL, WINDOW)
SLIDING, BLOCK = "sliding", "block"
WINDOW_RULES = (SLIDING, BLOCK)
LANE_TILE = 128               # lanes of the chip's vector registers


@dataclass(frozen=True)
class LatentRow:
    """A cached row that is neither a key nor a value but what both are
    made from: one a token, read by every query head."""
    lanes: int                # lanes a row holds
    value_lanes: int          # its first lanes, which are the value too
    q_heads: int              # query heads that read the one row
    scale: float              # on the scores: the model's own, not lanes ** -.5

    @property
    def stored(self) -> int:
        """Lanes the bank keeps a row in: whole lane tiles, zeros behind
        the row. A row of 4.5 tiles (576) has the compiler put the PAGES
        in the lanes and re-lay out the pool around every program
        (PERF.md, PRs 27 and 45)."""
        return -(-self.lanes // LANE_TILE) * LANE_TILE


@dataclass(frozen=True)
class Summaries:
    """What a window layer's attention keeps of the positions its window
    has left: one pooled key and one pooled value a ``chunk`` of them, a
    row a chunk in the ``global`` bank, read in the same softmax as the
    ring's exact keys."""
    chunk: int                # positions a summary stands for
    # (blk, k (.., chunk, KV, hd), v (.., chunk, KV, hd)) -> (k~, v~)
    # (.., KV, hd): the summary of one chunk from its own rows alone
    pool: Callable


@dataclass(frozen=True)
class LayerGroup:
    kind: str                 # the cache kind of the layers' keys and values
    n: int                    # layers in the group
    stacked: bool             # params carry a leading (n,) axis
    params: Callable          # params -> the group's subtree
    # (blk, h (B, C, hidden), pos (B, C)) -> (q (B, C, H, hd),
    #  k, v (B, C, KV, hd), saved): all before attention. Over a latent
    # row: q (B, C, H, lanes) as it meets the row, k (B, C, 1, lanes)
    # the row itself, v None
    qkv: Callable
    # (blk, h, ctx (B, C, H * hd), saved, live (B, C) bool | None)
    #  -> (h, counters | None): all after it. Over a latent row ctx is
    # (B, C, H * value_lanes): the probabilities over the rows' values
    finish: Callable
    # () -> (H,) ALiBi slopes of this shard's heads, called inside the
    # program; None: no position bias on the scores (rotary models)
    slopes: Optional[Callable] = None
    # a mixer that keeps a state a slot beside the keys and values, run
    # between ``qkv`` and ``finish`` of a decode step (row i is slot i):
    # (blk, saved, bank {name: (L, slots, ..)}, layer, live (B,) bool)
    #  -> (saved, bank), the bank's rows of ``layer`` read and overwritten
    # (``kv_pool.update_state_rows``); None: the layers keep none
    mix: Optional[Callable] = None
    # the layer's further attentions after its first, ``((qkv, finish),
    # ..)`` with the signatures above, each with its own row a token in
    # the bank. What one attention's ``finish`` returns as ``h`` is the
    # next one's and stays inside the layer, so it may hold more than
    # the hidden state (a result that lands later in the block); the
    # last returns the hidden state alone, and the layer's counters
    # (an array under the model's one name, or ``{name: array}``). Such
    # a layer is traced in line: its group is not stacked.
    more: Tuple[Tuple[Callable, Callable], ...] = ()
    # a ``window`` group whose one attention also reads a summary a
    # chunk of what left its window (a row a chunk in the ``global``
    # bank); None: the ring alone
    summaries: Optional[Summaries] = None

    @property
    def attends(self) -> int:
        return 1 + len(self.more)


@dataclass(frozen=True)
class PagedModel:
    n_kv_head: int            # heads a cached row holds, all shards
    head_dim: int
    dtype: Any
    groups: Tuple[LayerGroup, ...]
    embed: Callable           # (params, tokens (B, C)) -> h (B, C, hidden)
    final: Callable           # (params, h) -> the final norm's output
    logits: Callable          # (params, h (B, C, hidden)) -> (B, C, V_local)
    # the model's own forward over one bucketed prompt:
    # (params, ids (1, S), mask (1, S)) -> (logits (1, V_local), cache)
    # with cache {"k", "v"} (L, 1, S, KV, hd) for a one-kind model and
    # {kind: {"k", "v"}} for a two-kind one; a model with ``state`` adds
    # "state": {name: (L, 1, ..)} after the prompt's last REAL token; a
    # latent model's cache is {"rows": (L, 1, S, lanes)}, a layer an
    # attention
    prefill: Callable
    left_pad: bool = True     # the side a bucketed prompt is padded on
    window: Optional[int] = None          # keys a window layer keeps
    # which: the last ``window`` positions ("sliding") or those since
    # the last multiple of ``window`` ("block")
    window_rule: str = SLIDING
    # the name of what the groups' ``finish`` brings out of a decode
    # step (stacked over the layers that bring any); None: nothing
    counters: Optional[str] = None
    # what a sequence leaves in a layer beside its keys and values, a
    # slot: ((name, shape, dtype), ..); (): nothing
    state: Tuple[tuple, ...] = ()
    # the cached row where it is not keys and values of ``n_kv_head x
    # head_dim`` lanes in two banks (then ``n_kv_head`` is 1 and
    # ``head_dim`` the row's lanes)
    latent: Optional[LatentRow] = None

    @property
    def banks(self) -> int:
        """Banks the pool holds: keys and values, or the one of a
        latent row."""
        return 1 if self.latent is not None else 2

    @property
    def row_lanes(self) -> int:
        """Lanes a bank keeps a cached row in, all shards."""
        if self.latent is not None:
            return self.latent.stored
        return self.n_kv_head * self.head_dim

    def __post_init__(self):
        if self.window_rule not in WINDOW_RULES:
            raise ValueError(f"window_rule must be one of {WINDOW_RULES}, "
                             f"got {self.window_rule!r}")
        for g in self.groups:
            if g.summaries is not None and (
                    g.kind != WINDOW or g.more or self.latent is not None):
                raise ValueError(
                    "summaries stand beside a window layer's ring of keys "
                    "and values, one attention a layer")
        self.stride                       # the groups agree on it

    def _fills(self, g: LayerGroup, kind: str) -> bool:
        """Whether group ``g`` keeps rows in ``kind``'s bank."""
        return g.kind == kind or (kind == GLOBAL and g.summaries is not None)

    @property
    def kinds(self) -> Tuple[str, ...]:
        return tuple(k for k in KINDS
                     if any(self._fills(g, k) for g in self.groups))

    def layers_of(self, kind: str) -> int:
        """Layers of a kind's bank: a row a token (a summary a chunk) an
        ATTENTION."""
        return sum(g.n * g.attends for g in self.groups
                   if self._fills(g, kind))

    @property
    def summaries(self) -> Optional[Summaries]:
        """The summaries its window layers keep (the first such
        group's: they agree on the chunk); None: none keeps any."""
        return next((g.summaries for g in self.groups
                     if g.summaries is not None), None)

    @property
    def stride(self) -> int:
        """Positions a row of the ``global`` bank stands for: 1 where it
        is a position's keys and values, the chunk where it is a
        summary. One bank, one stride."""
        strides = {1 if g.summaries is None else g.summaries.chunk
                   for g in self.groups if self._fills(g, GLOBAL)}
        if len(strides) > 1:
            raise ValueError(
                f"the global bank holds a row every {sorted(strides)} "
                f"positions: global layers beside summaries of another "
                f"stride are not built")
        return strides.pop() if strides else 1

    @property
    def n_layer(self) -> int:
        return sum(g.n for g in self.groups)


def window_start(pos, window: int, rule: str = SLIDING):
    """The first position a query at ``pos`` keeps exact under ``rule``
    (may be negative under "sliding": every position so far). Pure
    arithmetic, on a traced value and on the host's int alike."""
    if rule == BLOCK:
        return pos // window * window
    return pos - window + 1


def summaries_seen(pos, window: int, chunk: int, rule: str = SLIDING):
    """Summaries a query at ``pos`` sees: the chunks that lie wholly
    before :func:`window_start`, which are the first so many rows of the
    summary bank. Under "block" every chunk of every CLOSED window."""
    start = window_start(pos, window, rule)
    return (start > 0) * start // chunk


def ring_pages(window: int, page_size: int, rule: str = SLIDING) -> int:
    """Pages in a window layer's ring: a sliding window's keys can
    straddle one page more than they fill. A block window is a whole
    number of pages: it starts on a page and never holds more than its
    own, so entry ``r`` of its ring is the window's page ``r`` (positions
    ``r * page_size`` on from the window's start) and nothing else, and
    a read walks it only as far as the furthest row stands INTO its
    window."""
    pages = -(-window // page_size)
    if rule != BLOCK:
        return pages + 1
    if window % page_size:
        raise ValueError(
            f"a block window is a whole number of pages: {window} "
            f"positions are not, at {page_size} a page")
    return pages


def describe(config, tp_axis=None) -> PagedModel:
    """The description of ``config``'s model: its own where the config
    object gives one, BLOOM's otherwise. A :class:`PagedModel` passes
    through."""
    if isinstance(config, PagedModel):
        return config
    own = getattr(config, "paged_model", None)
    return own(tp_axis) if own is not None else bloom_model(config, tp_axis)


def bloom_model(config, tp_axis=None) -> PagedModel:
    """BLOOM's block, as the paged programs have always run it: the
    contiguous path's fused qkv projection, ALiBi on the scores, the
    tanh GELU MLP; one group of ``n_layer`` stacked layers."""
    import jax.numpy as jnp
    from jax import lax

    from pipegoose_tpu.models.bloom import (
        alibi_slopes,
        bloom_gelu,
        layer_norm,
        logits_fn,
    )
    from pipegoose_tpu.models.generate import (
        _qkv_proj,
        forward_cached,
        init_cache,
    )
    from pipegoose_tpu.nn.tensor_parallel.layers import (
        column_parallel_linear,
        row_parallel_linear,
        vocab_parallel_embedding,
    )

    eps = config.layer_norm_epsilon

    def slopes():
        """This shard's ALiBi slope subset (all heads when unsharded)."""
        tp = lax.axis_size(tp_axis) if tp_axis else 1
        nh = config.n_head // tp
        s = jnp.asarray(alibi_slopes(config.n_head))
        if tp_axis:
            s = lax.dynamic_slice_in_dim(s, lax.axis_index(tp_axis) * nh,
                                         nh, 0)
        return s

    def embed(params, tokens):
        x = vocab_parallel_embedding(params["embed"], tokens, tp_axis)
        return layer_norm(params["embed_ln"], x.astype(config.dtype), eps)

    def qkv(blk, h, pos):
        ln1 = layer_norm(blk["ln_1"], h, eps)
        return _qkv_proj({"qkv": blk["attn"]["qkv"]}, ln1, config,
                         tp_axis) + (None,)

    def finish(blk, h, ctx, saved, live):
        h = h + row_parallel_linear(blk["attn"]["out"], ctx, tp_axis)
        ln2 = layer_norm(blk["ln_2"], h, eps)
        up = column_parallel_linear(blk["mlp"]["up"], ln2, tp_axis)
        return h + row_parallel_linear(blk["mlp"]["down"], bloom_gelu(up),
                                       tp_axis), None

    def prefill(params, ids, mask):
        tp = lax.axis_size(tp_axis) if tp_axis else 1
        cache = init_cache(config, 1, ids.shape[1], tp)
        return forward_cached(params, ids, cache, 0, config, tp_axis,
                              extras={"mask": mask})

    return PagedModel(
        n_kv_head=config.n_head, head_dim=config.head_dim,
        dtype=config.dtype,
        groups=(LayerGroup(
            kind=GLOBAL, n=config.n_layer, stacked=True,
            params=lambda p: p["blocks"], qkv=qkv, finish=finish,
            slopes=slopes),),
        embed=embed,
        final=lambda p, h: layer_norm(p["ln_f"], h, eps),
        logits=lambda p, h: logits_fn(p, h, tp_axis),
        prefill=prefill, left_pad=True)
