"""LongCat-Flash's language model (the text path of LongCat-Flash-Chat
and of LongCat-Flash-Omni): two latent attentions and a shortcut-connected
expert layer in every block, softmax top-k routing over real experts AND
zero-compute ones.

Every size is a published config key
(https://huggingface.co/meituan-longcat/LongCat-Flash-Omni/blob/main/config.json);
the equations are HF ``modeling_longcat_flash.py``'s and the LongCat-Flash
report's. RMSNorm, no bias, an untied head. One block, input ``h``::

    a0 = h  + MLA_0(RMS(h))          x0 = RMS(a0)
    s  = MoE(x0)                     # the shortcut: lands at the block's end
    b0 = a0 + FFN_0(x0)              # dense SwiGLU, ffn_hidden_size wide
    a1 = b0 + MLA_1(RMS(b0))         x1 = RMS(a1)
    h' = a1 + FFN_1(x1) + s

* Latent attention, ``H`` heads: ``cq = RMS(x W_qa)``; ``q = s_q (cq
  W_qb)`` in ``(H, nope | rope)``, ``s_q = sqrt(hidden / q_lora_rank)``
  (``mla_scale_q_lora``); ``[c | kr] = x W_kva``; ``c = s_kv RMS(c)``,
  ``s_kv = sqrt(hidden / kv_lora_rank)`` (``mla_scale_kv_lora``); RoPE
  (interleaved pairs, DeepSeek's) on ``q``'s rotary part and on ``kr``,
  ONE ``kr`` for all heads. Two forms of one mathematics:

  - EXPANDED (:func:`prefill`, :func:`forward`): ``[k_nope | v] = c
    W_kvb`` a head, ``k = [k_nope | kr]``, causal softmax(``q k / sqrt(nope
    + rope)``) ``v``, through ``ops/flash_attention.py`` where
    ``use_flash`` (the kernels take one head width: ``v`` is padded from
    ``v_head_dim`` to the keys' width and the padding dropped after; 192
    lanes, a tile and a half, compile and run on a v5e).
  - ABSORBED (the paged decode step): the cached row is ``r = [c | kr]``
    (``kv_lora_rank + rope`` lanes, what ``W_kvb`` multiplies), a head's
    query ``q~ = [W_kvb^K q_nope | q_rope]``, scores ``q~ . r``, ``u =
    sum p r[:kv_lora_rank]``, context ``(W_kvb^V)^T u``. No key and no
    value a head is ever formed.

* Expert layer: ``z = softmax(x W_r)`` in float32 over ``n_routed_experts
  + zero_expert_num`` outputs; the ``moe_topk`` picks are the largest of
  ``z + b`` (``e_score_correction_bias``, a buffer); ``w_j =
  routed_scaling_factor * z[e_j]``, not renormalised; ``y = sum_{e_j <
  n_routed} w_j SwiGLU_{e_j}(x) + (sum_{e_j >= n_routed} w_j) x``: a
  zero-compute expert of type ``identity`` returns its input.

A chip's share of a layer, as ``glm4_moe_lite``: ``experts_held =
(first, count)`` of the routed experts; the router keeps its width, the
picks on held experts are computed (``grouped_experts``), what absent
ones would add is left out. The identity picks cost nothing and run where
the token is: in full, here.

Serving only: :func:`paged_model` is the description ``ServingEngine``
takes (``serving/blocks.py``): a latent row a token an attention, two
attentions a block.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from pipegoose_tpu.models.laguna import (
    _dot,
    _swiglu,
    _swiglu_shapes,
    logits_fn,
)
from pipegoose_tpu.models.mixtral import NEG_INF, rms_norm
from pipegoose_tpu.nn.expert_parallel.experts import grouped_experts
from pipegoose_tpu.nn.expert_parallel.routers import SoftmaxTopKRouter


@dataclasses.dataclass(frozen=True)
class LongcatFlashConfig:
    # published keys (defaults: LongCat-Flash-Omni's language model)
    vocab_size: int = 131072
    hidden_size: int = 6144
    ffn_hidden_size: int = 12288
    expert_ffn_hidden_size: int = 2048
    num_layers: int = 28
    num_attention_heads: int = 64
    kv_lora_rank: int = 512
    q_lora_rank: int = 1536
    qk_rope_head_dim: int = 64
    qk_nope_head_dim: int = 128
    v_head_dim: int = 128
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    routed_scaling_factor: float = 6.0
    n_routed_experts: int = 512
    zero_expert_num: int = 256
    zero_expert_type: str = "identity"
    moe_topk: int = 12
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e7
    # absent from config.json: HF's default
    norm_topk_prob: bool = False
    initializer_range: float = 0.02
    # the share of a layer held here: (first, count) of the routed
    # experts; None = all of them
    experts_held: Optional[tuple] = None
    use_flash: bool = False
    # tokens a call of a block's feed-forwards in the prefill (a longer
    # prompt, a multiple of it, goes through in blocks); None: never
    ffn_block_tokens: Optional[int] = None
    dtype: Any = jnp.float32

    def __post_init__(self):
        if self.zero_expert_type != "identity":
            raise ValueError("zero-compute experts of type 'identity' are "
                             "what is built")
        first, count = self.held
        if first < 0 or count < 1 or first + count > self.n_routed_experts:
            raise ValueError(f"experts_held {self.experts_held} lies "
                             f"outside 0..{self.n_routed_experts}")

    @property
    def held(self) -> tuple:
        return tuple(self.experts_held or (0, self.n_routed_experts))

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def row_lanes(self) -> int:
        """Lanes of the cached latent row: ``[c | kr]``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def router_outputs(self) -> int:
        return self.n_routed_experts + self.zero_expert_num

    @property
    def scale_q(self) -> float:
        return ((self.hidden_size / self.q_lora_rank) ** 0.5
                if self.mla_scale_q_lora else 1.0)

    @property
    def scale_kv(self) -> float:
        return ((self.hidden_size / self.kv_lora_rank) ** 0.5
                if self.mla_scale_kv_lora else 1.0)

    def router(self) -> SoftmaxTopKRouter:
        return SoftmaxTopKRouter(
            self.router_outputs, self.moe_topk,
            scaling=self.routed_scaling_factor,
            normalize=self.norm_topk_prob)

    def paged_model(self, tp_axis=None):
        """The description ``ServingEngine`` serves this model by."""
        return paged_model(self, tp_axis)


# -- init ------------------------------------------------------------------

def _half_shapes(c: LongcatFlashConfig) -> dict:
    """One attention with its two norms and its dense feed-forward: a
    block holds two."""
    h, nh = c.hidden_size, c.num_attention_heads
    return {
        "ln_in": {"scale": (h,)},
        "attn": {
            "q_a": {"kernel": (h, c.q_lora_rank)},
            "q_a_norm": {"scale": (c.q_lora_rank,)},
            "q_b": {"kernel": (c.q_lora_rank, nh * c.qk_head_dim)},
            "kv_a": {"kernel": (h, c.row_lanes)},
            "kv_a_norm": {"scale": (c.kv_lora_rank,)},
            "kv_b": {"kernel": (c.kv_lora_rank,
                                nh * (c.qk_nope_head_dim + c.v_head_dim))},
            "o": {"kernel": (nh * c.v_head_dim, h)},
        },
        "ln_post": {"scale": (h,)},
        "mlp": _swiglu_shapes(h, c.ffn_hidden_size),
    }


def param_shapes(c: LongcatFlashConfig) -> dict:
    """The parameter tree as shapes. The blocks are a list, a tree a
    block, not a stack with a leading axis: a block's held experts cut
    out of a stack by a traced index are COPIED on their way into the
    grouped product (three matrices of 0.4 GB a block a decode step at
    the published widths: compiled for a v5e, PERF.md PR 45)."""
    h, v = c.hidden_size, c.vocab_size
    block = {
        "half0": _half_shapes(c), "half1": _half_shapes(c),
        "router": {"gate": {"kernel": (h, c.router_outputs)},
                   "bias": (c.router_outputs,)},
        "experts": _swiglu_shapes(h, c.expert_ffn_hidden_size,
                                  (c.held[1],)),
    }
    return {
        "embed": {"weight": (v, h)},
        "layers": [block for _ in range(c.num_layers)],
        "ln_f": {"scale": (h,)},
        "lm_head": {"weight": (v, h)},
    }


def init_params(config: LongcatFlashConfig, key: jax.Array) -> dict:
    """N(0, initializer_range) matrices, unit norms, a zero router bias."""
    shapes, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(config), is_leaf=lambda x: isinstance(x, tuple))
    out = []
    for i, (path, shape) in enumerate(shapes):
        name = jax.tree_util.keystr(path)
        if "scale" in name:
            x = jnp.ones(shape, config.dtype)
        elif name.endswith("['bias']"):
            x = jnp.zeros(shape, jnp.float32)
        else:
            x = (jax.random.normal(jax.random.fold_in(key, i), shape)
                 * config.initializer_range).astype(config.dtype)
        out.append(x)
    return jax.tree_util.tree_unflatten(treedef, out)


# -- latent attention --------------------------------------------------------

def _rope(x, pos, theta: float):
    """DeepSeek's interleaved rotary on ``x`` (B, S, heads, rot) at
    positions ``pos`` (B, S): the pairs ``(x[2i], x[2i + 1])`` are first
    laid out as ``[evens | odds]`` and turned as rotate-half turns them
    (HF ``apply_rotary_pos_emb_interleave``). Queries and the shared key
    get the same layout, which is the one the cache keeps. Float32
    inside, the result in ``x``'s dtype."""
    rot = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    ang = pos.astype(jnp.float32)[..., None, None] * inv       # (B, S, 1, r/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., 0::2], xf[..., 1::2]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def project(at, x, pos, config: LongcatFlashConfig):
    """Both low-rank projections of one attention on ``x`` (B, S, hidden)
    at positions ``pos`` (B, S). Returns ``(q (B, S, H, nope + rope),
    row (B, S, kv_lora_rank + rope))``: the scaled, rotated queries, and
    the latent row ``[c | kr]``, normed, scaled and rotated: what the
    cache keeps and what ``W_kvb`` multiplies."""
    c = config
    b, s, _ = x.shape
    with jax.named_scope("mla.proj"):
        cq = rms_norm(at["q_a_norm"], _dot(x, at["q_a"]), c.rms_norm_eps)
        q = (_dot(cq, at["q_b"]).astype(jnp.float32) * c.scale_q).astype(
            x.dtype).reshape(b, s, c.num_attention_heads, c.qk_head_dim)
        ckv = _dot(x, at["kv_a"])
        lat = rms_norm(at["kv_a_norm"], ckv[..., :c.kv_lora_rank],
                       c.rms_norm_eps)
        lat = (lat.astype(jnp.float32) * c.scale_kv).astype(x.dtype)
        dn = c.qk_nope_head_dim
        q = jnp.concatenate(
            [q[..., :dn], _rope(q[..., dn:], pos, c.rope_theta)], axis=-1)
        kr = _rope(ckv[..., None, c.kv_lora_rank:], pos, c.rope_theta)[:, :, 0]
        return q, jnp.concatenate([lat, kr], axis=-1)


def _kv_b(at, config: LongcatFlashConfig):
    """``W_kvb`` as (kv_lora_rank, H, nope + v)."""
    c = config
    return at["kv_b"]["kernel"].reshape(
        c.kv_lora_rank, c.num_attention_heads,
        c.qk_nope_head_dim + c.v_head_dim)


def absorb_query(at, q, config: LongcatFlashConfig):
    """``q`` (B, S, H, nope + rope) as it meets a latent row: ``q~_h =
    [W_kvb,h^K q_nope,h | q_rope,h]`` (B, S, H, kv_lora_rank + rope)."""
    dn = config.qk_nope_head_dim
    with jax.named_scope("mla.absorb"):
        wk = _kv_b(at, config)[..., :dn]
        lat = jnp.einsum("bshd,rhd->bshr", q[..., :dn], wk,
                         preferred_element_type=jnp.float32).astype(q.dtype)
        return jnp.concatenate([lat, q[..., dn:]], axis=-1)


def attend_out(at, u, config: LongcatFlashConfig):
    """The attention's output from ``u`` (B, S, H * kv_lora_rank), the
    probabilities over the rows' latent part: ``W_kvb^V`` a head, then
    ``W_o``."""
    c = config
    b, s, _ = u.shape
    with jax.named_scope("mla.absorb"):
        wv = _kv_b(at, c)[..., c.qk_nope_head_dim:]
        ctx = jnp.einsum(
            "bshr,rhd->bshd", u.reshape(b, s, -1, c.kv_lora_rank), wv,
            preferred_element_type=jnp.float32).astype(u.dtype)
    with jax.named_scope("mla.proj"):
        return _dot(ctx.reshape(b, s, -1), at["o"])


def attend_expanded(at, q, row, config: LongcatFlashConfig):
    """The EXPANDED form over whole sequences from position 0: keys and
    values a head out of the latent rows, causal attention, ``W_o``.
    Returns (B, S, hidden)."""
    c = config
    b, s, nh, _ = q.shape
    dn, dv, dq = c.qk_nope_head_dim, c.v_head_dim, c.qk_head_dim
    with jax.named_scope("mla.proj"):
        kv = _dot(row[..., :c.kv_lora_rank], at["kv_b"]).reshape(
            b, s, nh, dn + dv)
        kr = jnp.broadcast_to(row[:, :, None, c.kv_lora_rank:],
                              (b, s, nh, c.qk_rope_head_dim))
        k = jnp.concatenate([kv[..., :dn], kr], axis=-1)
        v = kv[..., dn:]
    with jax.named_scope("mla.attn"):
        if c.use_flash:
            from pipegoose_tpu.ops.flash_attention import flash_attention

            # one head width for q, k and v: zeros behind the values give
            # columns that are dropped
            v = jnp.pad(v, ((0, 0),) * 3 + ((0, dq - dv),))
            ctx = flash_attention(q, k, v, alibi_slopes=None, causal=True,
                                  scale=dq ** -0.5)[..., :dv]
        else:
            scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                                preferred_element_type=jnp.float32)
            pos = jnp.arange(s)
            scores = scores * dq ** -0.5 + jnp.where(
                pos[None, :] <= pos[:, None], 0.0, NEG_INF)
            probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
            ctx = jnp.einsum("bhqk,bkhd->bqhd", probs, v,
                             preferred_element_type=jnp.float32)
        ctx = ctx.astype(q.dtype).reshape(b, s, nh * dv)
    with jax.named_scope("mla.proj"):
        return _dot(ctx, at["o"])


# -- the expert layer ----------------------------------------------------------

def moe_parts(blk, x, config: LongcatFlashConfig, live=None):
    """The expert layer on ``x`` (T, hidden), its two parts apart:
    ``(routed, zero, counters)``: the held experts' part of the routed
    sum; the identity picks' ``(sum w) x``; ``rows_per_expert`` (held,),
    ``zero_picks`` and ``picks`` (scalars: picks on zero-compute experts,
    and all picks, of the rows ``live`` (T,) keeps). A row that is not
    live is sent nowhere and adds nothing."""
    c = config
    with jax.named_scope("moe.route"):
        routing = c.router()(blk["router"], x)
        if live is not None:
            # past every id: no held expert, no zero-compute one
            routing = routing._replace(experts=jnp.where(
                live[:, None], routing.experts, c.router_outputs))
    routed, rows = grouped_experts(blk["experts"], x, routing, c.held)
    with jax.named_scope("moe.zero"):
        # ids n_routed .. n_routed + zero_expert_num - 1 return their
        # input: the masked sum of a token's weights, times the token
        is_zero = (routing.experts >= c.n_routed_experts) \
            & (routing.experts < c.router_outputs)
        wz = jnp.where(is_zero, routing.weights, 0.0).sum(-1, keepdims=True)
        zero = (x.astype(jnp.float32) * wz).astype(x.dtype)
    counters = {
        "rows_per_expert": rows,
        "zero_picks": is_zero.sum().astype(jnp.int32),
        "picks": (routing.experts < c.router_outputs).sum().astype(jnp.int32),
    }
    return routed, zero, counters


def moe(blk, x, config: LongcatFlashConfig, live=None):
    """``(y, counters)`` of the expert layer on ``x`` (B, S, hidden)."""
    flat = x.reshape(-1, x.shape[-1])
    routed, zero, counters = moe_parts(
        blk, flat, config, None if live is None else live.reshape(-1))
    return (routed + zero).reshape(x.shape), counters


# -- the block, in the halves the paged programs run it by ---------------------

def first_half(blk, h, attn_out, config: LongcatFlashConfig, live=None):
    """From the first attention's output to the second one's input:
    ``(b0, s, counters)`` with ``s`` the expert layer's result, which
    leaves here and lands at the block's end."""
    c = config
    half = blk["half0"]
    a0 = h + attn_out
    x0 = rms_norm(half["ln_post"], a0, c.rms_norm_eps)
    s, counters = moe(blk, x0, c, live)
    return a0 + _swiglu(half["mlp"], x0), s, counters


def second_half(blk, b0, s, attn_out, config: LongcatFlashConfig):
    """The block's output from the second attention's."""
    half = blk["half1"]
    a1 = b0 + attn_out
    x1 = rms_norm(half["ln_post"], a1, config.rms_norm_eps)
    y = a1 + _swiglu(half["mlp"], x1)
    with jax.named_scope("scmoe.shortcut"):
        return y + s


def _in_blocks(fn, xs, block):
    """``fn`` over ``block``-token pieces of the (1, S, ..) arrays
    ``xs``, one piece after the other, where S is a multiple of ``block``
    past it; whole otherwise. Array results lead with (1, block), scalar
    ones (counters) are summed over the pieces, vectors too."""
    s = xs[0].shape[1]
    if not block or s <= block or s % block:
        return fn(xs)
    cut = tuple(x.reshape((s // block, 1, block) + x.shape[2:]) for x in xs)
    out = jax.lax.map(fn, cut)
    return jax.tree_util.tree_map(
        lambda o: (o.reshape((1, s) + o.shape[3:])
                   if o.ndim >= 3 and o.shape[1:3] == (1, block)
                   else o.sum(axis=0)), out)


def _trunk(params, input_ids, config: LongcatFlashConfig, live=None):
    """Embedding and every block over ONE sequence (1, S) from position
    0, attention in the expanded form. Returns the final norm's output,
    the latent rows an attention ``(2 * num_layers, 1, S, lanes)`` and
    the counters a block."""
    c = config
    b, s = input_ids.shape
    x = jnp.take(params["embed"]["weight"], input_ids, axis=0).astype(c.dtype)
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    keep = jnp.ones((b, s), bool) if live is None else live
    rows, counters = [], []
    for blk in params["layers"]:
        h0, h1 = blk["half0"], blk["half1"]
        q, row = project(h0["attn"], rms_norm(h0["ln_in"], x, c.rms_norm_eps),
                         pos, c)
        rows.append(row)
        a0 = attend_expanded(h0["attn"], q, row, c)

        x, sc, cnt = _in_blocks(
            lambda args, blk=blk: first_half(blk, args[0], args[1], c,
                                             args[2]),
            (x, a0, keep), c.ffn_block_tokens)
        counters.append(cnt)
        q, row = project(h1["attn"], rms_norm(h1["ln_in"], x, c.rms_norm_eps),
                         pos, c)
        rows.append(row)
        a1 = attend_expanded(h1["attn"], q, row, c)
        x = _in_blocks(
            lambda args, blk=blk: second_half(blk, args[0], args[1], args[2],
                                              c),
            (x, sc, a1), c.ffn_block_tokens)
    counters = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *counters)
    return (rms_norm(params["ln_f"], x, c.rms_norm_eps), jnp.stack(rows),
            counters)


def forward(params, input_ids, config: LongcatFlashConfig):
    """(1, S) token ids -> (1, S, V) float32 logits."""
    return logits_fn(params, _trunk(params, input_ids, config)[0])


def prefill(params, ids, mask, config: LongcatFlashConfig):
    """The serving prefill: one RIGHT-padded prompt ``ids`` (1, S_pad)
    with ``mask`` (1, S_pad) 1 on its tokens, through the model's own
    forward in the expanded form. Returns the logits after the last real
    token (1, V) and the cache ``{"rows": (2 * num_layers, 1, S_pad,
    lanes)}``: the latent rows an attention and nothing else. Attention
    is causal, so the padding behind the prompt changes no real position;
    its picks go to no expert."""
    hidden, rows, _ = _trunk(params, ids, config, live=mask > 0)
    n = mask.sum(axis=1).astype(jnp.int32)
    last = jnp.take_along_axis(hidden, (n - 1)[:, None, None], axis=1)
    return logits_fn(params, last)[:, 0], {"rows": rows}


# -- the description the paged programs take ---------------------------------

def paged_model(config: LongcatFlashConfig, tp_axis=None):
    """LongCat-Flash as ``serving/blocks.PagedModel``: a group a block
    (see :func:`param_shapes`) on the ``global`` cache kind, a LATENT row
    a token, two attentions a block (bank layers ``2l`` and ``2l + 1``).
    Between the two, what passes is ``(b0, s, counters)``: the expert
    layer's result and its counters stay inside the block until its
    end."""
    from pipegoose_tpu.serving.blocks import (
        GLOBAL,
        LatentRow,
        LayerGroup,
        PagedModel,
    )

    if tp_axis is not None:
        raise ValueError("longcat_flash is served on one device: a mesh is "
                         "not built for a model with a latent row")
    c = config

    def qkv(half):
        def fn(blk, h, pos):
            x = jax.tree_util.tree_leaves(h)[0]
            at = blk[half]["attn"]
            q, row = project(at, rms_norm(blk[half]["ln_in"], x,
                                          c.rms_norm_eps), pos, c)
            return absorb_query(at, q, c), row[:, :, None, :], None, None
        return fn

    def finish0(blk, h, u, saved, live):
        out = attend_out(blk["half0"]["attn"], u, c)
        return first_half(blk, h, out, c, live), None

    def finish1(blk, h, u, saved, live):
        b0, s, counters = h
        out = attend_out(blk["half1"]["attn"], u, c)
        return second_half(blk, b0, s, out, c), counters

    return PagedModel(
        n_kv_head=1, head_dim=c.row_lanes, dtype=c.dtype,
        latent=LatentRow(lanes=c.row_lanes, value_lanes=c.kv_lora_rank,
                         q_heads=c.num_attention_heads,
                         scale=c.qk_head_dim ** -0.5),
        groups=tuple(LayerGroup(
            kind=GLOBAL, n=1, stacked=False,
            params=lambda p, i=i: p["layers"][i],
            qkv=qkv("half0"), finish=finish0,
            more=((qkv("half1"), finish1),))
            for i in range(c.num_layers)),
        embed=lambda p, tokens: jnp.take(
            p["embed"]["weight"], tokens, axis=0).astype(c.dtype),
        final=lambda p, h: rms_norm(p["ln_f"], h, c.rms_norm_eps),
        logits=lambda p, h: logits_fn(p, h),
        prefill=lambda p, ids, mask: prefill(p, ids, mask, c),
        left_pad=False,
        counters="rows_per_expert",
    )
