"""EvaByte (``model_type: evabyte``): a byte-level decoder whose
attention is EVA (Zheng et al., ICLR 2023, "Efficient Attention via
Control Variates", in the chunked form EvaByte's ``eva.py`` runs): ONE
softmax over two sets of keys, the exact keys of the query's own window
and one pooled key and value a chunk of every window before it.

Every size is a published config key
(https://huggingface.co/EvaByte/EvaByte/blob/main/config.json). RMSNorm
with a unit offset (``norm_add_unit_offset``: the scale is ``1 + g``),
pre-norm residuals in float32 (``fp32_skip_add``), no bias, an untied
head of ``num_pred_heads`` x ``vocab_size`` columns, float32 logits
(``fp32_logits``). One layer, input ``h``, position ``t``::

    x  = RMS1(h; g_1)
    q, k, v = x W_q, x W_k, x W_v          # heads x head_dim
    q, k = RoPE(q, t), RoPE(k, t)          # theta rope_theta, rotate-half
    h  = h + EVA(q, k, v) W_o
    y  = RMS1(h; g_2)
    h' = h + (silu(y W_g) * (y W_u)) W_d

``EVA``, a head, ``W = window_size``, ``C = chunk_size``, ``s =
head_dim^-0.5``; chunk ``c`` holds positions ``C c .. C c + C - 1``;
``phi`` and ``mu`` are learned vectors a head::

    a_cj = softmax_j(s k_{Cc+j} . phi)                  # the chunk's C keys
    k~_c = sum_j a_cj k_{Cc+j} + mu      v~_c = sum_j a_cj v_{Cc+j}
    query t, w = t // W:
      e_m = s q_t . k_m    for m in [w W, t]            # the window, exact
      r_c = s q_t . k~_c   for c <  w (W / C)           # closed windows
      out_t = (sum_m exp(e_m) v_m + sum_c exp(r_c) v~_c)
              / (sum_m exp(e_m) + sum_c exp(r_c))       # ONE normaliser

A summary is its own chunk's keys and values and nothing else, so WHEN
it is made is the program's choice; which ones a query sees is not.
Scores, softmax and accumulation are float32 over operands in the
config's dtype (``mixedp_attn``). Head ``i`` of the output, columns
``[V i, V (i + 1))``, predicts the byte ``i + 1`` ahead; serving reads
head 0, the next byte.

Not in ``config.json`` and set from EvaByte's code (the benchmark's
configuration file lists them under ``assumed``): the rotate-half
pairing, ``phi`` and ``mu`` (``adaptive_phi``, ``adaptive_mu_k``), what
``mixedp_attn`` means, the head's column layout.

Two paths share the layer functions: the full forward (:func:`forward`,
:func:`prefill`: the window part as causal attention over the bucket's
windows as rows, the summary part one more chunk of keys whose
"positions" are the first position of the NEXT window, so that a
value-based causal mask IS the visibility rule; no ``(S, S)`` array on
either lane), and the paged programs of ``serving/`` through
:func:`paged_model` (``serving/blocks.py``): a ring under the ``block``
window rule and a summary a chunk in ``global`` pages, both read by one
softmax.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp

from pipegoose_tpu.models.laguna import apply_rotary, rope_frequencies
from pipegoose_tpu.models.mixtral import NEG_INF

# heads a whole sequence's attention takes at a time, one group after
# the other (each with its own projections, rotary, pooling and
# kernels): a 24k-byte prompt's queries, keys, values and float32 carry
# exist 8 heads at a time (2.66 GB of temporaries at the published
# widths, 5.06 GB with all 32 at once, which does not fit beside the
# weights and the banks of a served chip)
ATTN_HEADS_AT_ONCE = 8


@dataclasses.dataclass(frozen=True)
class EvaByteConfig:
    # published keys (defaults: EvaByte 6.5B)
    vocab_size: int = 320
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    num_pred_heads: int = 8
    window_size: int = 2048
    chunk_size: int = 16
    num_chunks: Optional[int] = None
    rope_theta: float = 100000.0
    rope_scaling: Optional[dict] = None
    rms_norm_eps: float = 1e-5
    norm_add_unit_offset: bool = True
    fp32_skip_add: bool = True
    fp32_logits: bool = True
    fp32_ln: bool = False
    mixedp_attn: bool = True
    attention_bias: bool = False
    attention_class: str = "eva"
    hidden_act: str = "silu"
    tie_word_embeddings: bool = False
    init_std: float = 0.01275
    init_fn: str = "v2"
    init_cutoff_factor: Optional[float] = None
    lazy_init: bool = True
    max_position_embeddings: int = 32768
    max_seq_length: int = 32768
    model_type: str = "evabyte"
    # the program's own
    use_flash: bool = False
    # tokens a call of the feed-forward: a longer sequence (a multiple
    # of it) goes through in blocks, one after the other. None: never
    ffn_block_tokens: Optional[int] = None
    dtype: Any = jnp.float32

    def __post_init__(self):
        built = {"attention_class": "eva", "hidden_act": "silu",
                 "attention_bias": False, "rope_scaling": None,
                 "num_chunks": None, "tie_word_embeddings": False,
                 "norm_add_unit_offset": True, "fp32_skip_add": True,
                 "fp32_logits": True, "mixedp_attn": True}
        for key, value in built.items():
            if getattr(self, key) != value:
                raise ValueError(f"{key}={getattr(self, key)!r} is not "
                                 f"built: EvaByte publishes {value!r}")
        if self.num_key_value_heads != self.num_attention_heads:
            raise ValueError("a summary is pooled a head: one key-value "
                             "head a query head")
        if self.hidden_size % self.num_attention_heads \
                or self.window_size % self.chunk_size:
            raise ValueError("heads divide the hidden size and chunks the "
                             "window")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    def paged_model(self, tp_axis=None):
        """The description ``ServingEngine`` serves this model by."""
        return paged_model(self, tp_axis)


# -- init ------------------------------------------------------------------

def param_shapes(c: EvaByteConfig) -> dict:
    """The parameter tree as shapes. Every layer is alike: the blocks
    are stacked on a leading axis."""
    h, f, n = c.hidden_size, c.intermediate_size, c.num_hidden_layers
    nh, hd = c.num_attention_heads, c.head_dim
    return {
        "embed": {"weight": (c.vocab_size, h)},
        "blocks": {
            "ln_1": {"scale": (n, h)},
            "attn": {**{k: {"kernel": (n, h, h)} for k in "qkvo"},
                     "phi": (n, nh, hd), "mu": (n, nh, hd)},
            "ln_2": {"scale": (n, h)},
            "mlp": {"gate": {"kernel": (n, h, f)}, "up": {"kernel": (n, h, f)},
                    "down": {"kernel": (n, f, h)}},
        },
        "ln_f": {"scale": (h,)},
        "lm_head": {"weight": (h, c.num_pred_heads * c.vocab_size)},
    }


def init_params(config: EvaByteConfig, key: jax.Array) -> dict:
    """N(0, init_std) matrices and per-head vectors; the norms' offsets
    ``g`` zero (a scale of one)."""
    shapes, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(config), is_leaf=lambda x: isinstance(x, tuple))
    out = []
    for i, (path, shape) in enumerate(shapes):
        if "scale" in jax.tree_util.keystr(path):
            x = jnp.zeros(shape, config.dtype)
        else:
            x = (jax.random.normal(jax.random.fold_in(key, i), shape)
                 * config.init_std).astype(config.dtype)
        out.append(x)
    return jax.tree_util.tree_unflatten(treedef, out)


# -- layers ----------------------------------------------------------------

def rms1(g, x, eps: float, dtype):
    """RMSNorm with a unit offset, ``x / rms(x) * (1 + g)``: float32
    inside (the residual stream is), the result in ``dtype``."""
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt((xf * xf).mean(-1, keepdims=True) + eps)
    return (y * (1.0 + g["scale"].astype(jnp.float32))).astype(dtype)


def _dot32(x, w):
    """``x W`` accumulated, and kept, in float32: what goes into the
    residual stream."""
    return jnp.dot(x, w["kernel"], preferred_element_type=jnp.float32)


def project(attn, x, pos, config: EvaByteConfig):
    """``(q, k, v (B, S, heads, hd))`` of the normed input ``x`` at
    positions ``pos`` (B, S) by ``attn``'s matrices (all heads', or a
    group's columns of them), rotary applied to queries and keys."""
    c = config
    b, s, _ = x.shape
    q, k, v = (_dot32(x, attn[n]).astype(c.dtype).reshape(
        b, s, -1, c.head_dim) for n in "qkv")
    with jax.named_scope("attn.rope"):
        freqs = rope_frequencies({"rope_theta": c.rope_theta}, c.head_dim)
        q, k = apply_rotary(q, pos, freqs), apply_rotary(k, pos, freqs)
    return q, k, v


def qkv(blk, h, pos, config: EvaByteConfig):
    """``(q, k, v (B, S, heads, hd), None)`` of one layer at positions
    ``pos`` (B, S): the norm, then :func:`project`."""
    x = rms1(blk["ln_1"], h, config.rms_norm_eps, config.dtype)
    return project(blk["attn"], x, pos, config) + (None,)


def pool(blk, k, v):
    """The summary of each chunk: ``k``, ``v`` (.., C, heads, hd), a
    chunk's ROTATED keys and its values -> ``(k~, v~)`` (.., heads, hd).
    The weights are a softmax over the chunk's ``C`` keys of ``s k .
    phi``; ``mu`` is added to the pooled key and not to the value.
    ``blk["attn"]`` holds ``phi`` and ``mu`` of the heads given."""
    hd = k.shape[-1]
    kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
    phi = blk["attn"]["phi"].astype(jnp.float32)
    a = jax.nn.softmax(
        jnp.einsum("...chd,hd->...ch", kf, phi) * hd ** -0.5, axis=-2)
    k_sum = jnp.einsum("...ch,...chd->...hd", a, kf) \
        + blk["attn"]["mu"].astype(jnp.float32)
    v_sum = jnp.einsum("...ch,...chd->...hd", a, vf)
    return k_sum.astype(k.dtype), v_sum.astype(v.dtype)


def _swiglu32(blk, y):
    gate = _dot32(y, blk["gate"]).astype(y.dtype)
    up = _dot32(y, blk["up"]).astype(y.dtype)
    return _dot32(jax.nn.silu(gate) * up, blk["down"])


def finish(blk, h, ctx, config: EvaByteConfig):
    """The rest of a layer once attention has given ``ctx`` (B, S, heads
    * hd): output projection and feed-forward, each added to the float32
    residual stream as a float32 product."""
    c = config
    h = h + _dot32(ctx.astype(c.dtype), blk["attn"]["o"])
    y = rms1(blk["ln_2"], h, c.rms_norm_eps, c.dtype)
    flat = y.reshape(-1, y.shape[-1])
    n, block = flat.shape[0], c.ffn_block_tokens
    if block and n > block and n % block == 0:
        # the (tokens, intermediate_size) pair a block of tokens at a time
        out = jax.lax.map(lambda r: _swiglu32(blk["mlp"], r),
                          flat.reshape(-1, block, flat.shape[1]))
        return h + out.reshape(h.shape)
    return h + _swiglu32(blk["mlp"], y)


def _windows(s: int, window: int) -> tuple:
    """(windows, positions a window) a sequence of ``s`` is attended as:
    one partial window, or whole ones."""
    if s <= window:
        return 1, s
    if s % window:
        raise ValueError(f"{s} positions are neither one window of "
                         f"{window} nor whole ones")
    return s // window, window


def eva_attention(q, k, v, blk, config: EvaByteConfig):
    """EVA over one whole sequence from position 0: ``q``, ``k``, ``v``
    (1, S, heads, hd), ``S`` one partial window or whole windows.
    Returns ``(ctx (1, S, heads * hd), k~, v~ (1, S / C, heads, hd))``,
    the summary of every chunk of the sequence.

    The window part is causal attention over the windows as rows; the
    summary part attends every query to all ``S / C`` summaries, chunk
    ``c`` standing at the first position of the window after its own,
    so that "key position <= query position" keeps exactly the chunks
    of closed windows. The two are merged as ONE online softmax: the
    window part's normalised result and log-sum-exp ARE a carry ``(m,
    l, acc) = (lse, 1, out)``, which the summary part's chunk kernel
    takes on and divides once at the end (two log-sum-exps would have
    both parts divide, then weigh and add: one pass more over the
    result, and one kernel that cannot take a carry)."""
    c = config
    _, s, nh, hd = q.shape
    nw, wl = _windows(s, c.window_size)
    scale = hd ** -0.5
    k_sum, v_sum = pool(blk, k.reshape(1, s // c.chunk_size, c.chunk_size,
                                       nh, hd),
                        v.reshape(1, s // c.chunk_size, c.chunk_size, nh, hd))
    n_sum = s // c.chunk_size
    # a chunk becomes visible at the first position of the next window
    sum_pos = (jnp.arange(n_sum) * c.chunk_size // c.window_size + 1) \
        * c.window_size
    pos = jnp.arange(s)
    with jax.named_scope("eva.attn"):
        if c.use_flash:
            from pipegoose_tpu.ops.flash_attention import (
                _flash_fwd,
                flash_ring_chunk,
            )

            def heads_first(x):          # (1, S', nh, hd) -> (nh, S', hd)
                return x[0].transpose(1, 0, 2)

            qh, kh, vh = heads_first(q), heads_first(k), heads_first(v)
            rows = nh * nw               # (head, window) pairs, head major
            zeros = jnp.zeros((rows, wl), jnp.float32)
            out, res = _flash_fwd(
                qh.reshape(rows, wl, hd), kh.reshape(rows, wl, hd),
                vh.reshape(rows, wl, hd), jnp.zeros((rows,), jnp.float32),
                zeros + jnp.arange(wl, dtype=jnp.float32), zeros, scale,
                True, None)
            lse = res[-1]
            ctx = out.reshape(nh, s, hd)
            if nw > 1:
                _, denom, acc = flash_ring_chunk(
                    qh, heads_first(k_sum), heads_first(v_sum),
                    jnp.zeros((nh,), jnp.float32),
                    jnp.broadcast_to(pos.astype(jnp.float32), (nh, s)),
                    jnp.broadcast_to(sum_pos.astype(jnp.float32),
                                     (nh, n_sum)),
                    jnp.zeros((nh, n_sum), jnp.float32),
                    lse.reshape(nh, s), jnp.ones((nh, s), jnp.float32),
                    ctx.astype(jnp.float32), scale, None)
                ctx = acc / denom[..., None]
            ctx = ctx.transpose(1, 0, 2)
        else:
            def rows(x):                 # (1, S, nh, hd) -> (nw, wl, nh, hd)
                return x[0].reshape(nw, wl, nh, hd)

            exact = jnp.einsum("wqhd,wkhd->whqk", rows(q), rows(k),
                               preferred_element_type=jnp.float32) * scale
            exact = jnp.where(jnp.arange(wl)[None, :] <= jnp.arange(wl)[:, None],
                              exact, NEG_INF)
            pooled = jnp.einsum("wqhd,chd->whqc", rows(q), k_sum[0],
                                preferred_element_type=jnp.float32) * scale
            seen = sum_pos[None, None, :] <= pos.reshape(nw, 1, wl, 1)
            pooled = jnp.where(seen, pooled, NEG_INF)
            # a query is its own key, so the masked columns' NEG_INF
            # leaves them exactly nothing
            probs = jax.nn.softmax(
                jnp.concatenate([exact, pooled], axis=-1),
                axis=-1).astype(q.dtype)
            ctx = jnp.einsum("whqk,wkhd->wqhd", probs[..., :wl], rows(v),
                             preferred_element_type=jnp.float32) \
                + jnp.einsum("whqc,chd->wqhd", probs[..., wl:], v_sum[0],
                             preferred_element_type=jnp.float32)
            ctx = ctx.reshape(s, nh, hd)
    return ctx.astype(q.dtype).reshape(1, s, nh * hd), k_sum, v_sum


def _padded(s: int, window: int) -> int:
    """The length a sequence of ``s`` is attended at: itself inside one
    window, whole windows past it."""
    return s if s <= window else -(-s // window) * window


def _trunk(params, input_ids, config: EvaByteConfig, keep=None):
    """Embedding and every layer over ONE whole sequence (1, S) from
    position 0, the layers scanned. Returns the final norm's output (1,
    S, H) and, a layer, what ``keep(k, v, k~, v~)`` kept of its rotated
    keys, values and summaries (None: nothing)."""
    c = config
    b, s = input_ids.shape
    if b != 1:
        raise ValueError("the whole-sequence forward takes one sequence")
    full = _padded(s, c.window_size)
    ids = jnp.pad(input_ids, ((0, 0), (0, full - s)))
    x = jnp.take(params["embed"]["weight"], ids, axis=0).astype(jnp.float32)
    pos = jnp.arange(full)[None]

    def attend(attn, x):
        """Attention of the heads whose matrices ``attn`` holds."""
        q, k, v = project(attn, x, pos, c)
        ctx, k_sum, v_sum = eva_attention(q, k, v, {"attn": attn}, c)
        return ctx, None if keep is None else keep(k, v, k_sum, v_sum)

    # a group of heads at a time (a divisor of the heads)
    groups = c.num_attention_heads // math.gcd(c.num_attention_heads,
                                               ATTN_HEADS_AT_ONCE)

    def layer(h, blk):
        x = rms1(blk["ln_1"], h, c.rms_norm_eps, c.dtype)
        if groups > 1:
            # the columns of W_q, W_k, W_v and the rows of phi and mu
            # that are the group's
            def cut(name, w):
                if name in ("phi", "mu"):
                    return w.reshape((groups, -1) + w.shape[1:])
                w = w["kernel"]
                return {"kernel": jnp.moveaxis(
                    w.reshape(w.shape[0], groups, -1), 1, 0)}

            ctx, kept = jax.lax.map(
                lambda attn: attend(attn, x),
                {n: cut(n, w) for n, w in blk["attn"].items() if n != "o"})
            # (G, 1, S, heads / G ..) -> (1, S, heads ..), heads in order
            ctx = jnp.moveaxis(ctx, 0, 2).reshape(x.shape)
            kept = jax.tree_util.tree_map(
                lambda a: jnp.moveaxis(a, 0, 2).reshape(
                    a.shape[1:3] + (-1,) + a.shape[4:]), kept)
        else:
            ctx, kept = attend(blk["attn"], x)
        return finish(blk, h, ctx, c), kept

    x, kept = jax.lax.scan(layer, x, params["blocks"])
    return rms1(params["ln_f"], x, c.rms_norm_eps, jnp.float32)[:, :s], kept


def logits_fn(params, hidden, config: EvaByteConfig, heads: int = None):
    """(.., heads * V) float32 logits of the first ``heads`` output heads
    (all by default), in float32 from float32 operands
    (``fp32_logits``)."""
    n = (heads or config.num_pred_heads) * config.vocab_size
    w = params["lm_head"]["weight"][:, :n].astype(jnp.float32)
    return jnp.dot(hidden.astype(jnp.float32), w,
                   precision=jax.lax.Precision.HIGHEST)


def forward(params, input_ids, config: EvaByteConfig):
    """(1, S) byte ids -> (1, S, num_pred_heads, V) float32 logits: head
    ``i`` predicts the byte ``i + 1`` ahead."""
    hidden, _ = _trunk(params, input_ids, config)
    return logits_fn(params, hidden, config).reshape(
        hidden.shape[:2] + (config.num_pred_heads, config.vocab_size))


def prefill(params, ids, mask, config: EvaByteConfig):
    """The serving prefill: one RIGHT-padded prompt ``ids`` (1, S_pad)
    with ``mask`` (1, S_pad) 1 on its ``n`` bytes. Returns head 0's
    logits after the last real byte (1, V) and the cache a layer::

        {"window": {"k", "v" (L, 1, min(W, S'), heads, hd), "start"},
         "global": {"k", "v" (L, 1, S' / C, heads, hd)}}

    ``window``: the rows from position ``start = (n // W) * W`` on, what
    the ring must hold for the next byte's query (its own window's
    keys; none where the prompt ends on a window's last byte).
    ``global``: the summary of every chunk of the bucket, of which the
    page write keeps the ``n // C`` complete chunks of real bytes. The
    padding behind the prompt changes no real position: attention is
    causal and a summary that holds padding is seen by padding alone."""
    c = config
    s = ids.shape[1]
    n = mask.sum(axis=1).astype(jnp.int32)
    full = _padded(s, c.window_size)
    held = min(c.window_size, full)
    start = n[0] // c.window_size * c.window_size

    def keep(k, v, k_sum, v_sum):
        # a slice past the bucket's end is clamped, and then holds no
        # position under n: the page write keeps none of it
        return (jax.lax.dynamic_slice_in_dim(k, start, held, axis=1),
                jax.lax.dynamic_slice_in_dim(v, start, held, axis=1),
                k_sum, v_sum)

    hidden, (k, v, k_sum, v_sum) = _trunk(params, ids, c, keep)
    last = jnp.take_along_axis(hidden, (n - 1)[:, None, None], axis=1)
    cache = {"window": {"k": k, "v": v, "start": start},
             "global": {"k": k_sum, "v": v_sum}}
    return logits_fn(params, last, c, heads=1)[:, 0], cache


# -- the description the paged programs take ---------------------------------

def paged_model(config: EvaByteConfig, tp_axis=None):
    """EvaByte as ``serving/blocks.PagedModel``: one group of stacked
    layers on the ``window`` kind under the ``block`` rule, each keeping
    a summary a chunk in ``global`` pages beside its ring."""
    from pipegoose_tpu.serving.blocks import (
        BLOCK,
        LayerGroup,
        PagedModel,
        Summaries,
    )

    if tp_axis is not None:
        raise ValueError("evabyte is served on one device: a mesh is not "
                         "built for a model with two cache kinds")
    c = config
    return PagedModel(
        n_kv_head=c.num_key_value_heads, head_dim=c.head_dim, dtype=c.dtype,
        window=c.window_size, window_rule=BLOCK,
        groups=(LayerGroup(
            kind="window", n=c.num_hidden_layers, stacked=True,
            params=lambda p: p["blocks"],
            qkv=lambda blk, h, pos: qkv(blk, h, pos, c),
            finish=lambda blk, h, ctx, saved, live: (
                finish(blk, h, ctx, c), None),
            summaries=Summaries(chunk=c.chunk_size, pool=pool)),),
        embed=lambda p, tokens: jnp.take(
            p["embed"]["weight"], tokens, axis=0).astype(jnp.float32),
        final=lambda p, h: rms1(p["ln_f"], h, c.rms_norm_eps, jnp.float32),
        # a step serves head 0, the next byte
        logits=lambda p, h: logits_fn(p, h, c, heads=1),
        prefill=lambda p, ids, mask: prefill(p, ids, mask, c),
        left_pad=False,
    )
