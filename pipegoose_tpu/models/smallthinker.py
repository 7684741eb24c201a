"""SmallThinker-21BA3B-Instruct (``model_type: smallthinker``): sparse
ReGLU experts picked by a router that reads the ATTENTION's input,
global layers with no position encoding beside rotary layers under a
sliding window.

Every size is a published config key
(https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct/blob/main/config.json;
arXiv 2507.20984). RMSNorm, pre-norm residual blocks, no bias, an untied
head, every layer sparse. T tokens, layer ``l``:

* ``x = RMSNorm_1(h)``. The router reads ``x``: ``z = x W_r`` over
  ``moe_num_primary_experts`` outputs in float32, the
  ``moe_num_active_primary_experts`` largest, their weights a softmax
  over the chosen logits (``moe_primary_router_apply_softmax`` with
  ``norm_topk_prob``: the softmax over all of them, renormalised over
  the chosen). A server knows a layer's experts while its attention
  still runs.
* Attention on the same ``x``: ``q`` in ``(T, num_attention_heads,
  hd)``, ``k``, ``v`` in ``(T, KV, hd)``, query head ``i`` reading KV
  head ``i // g``. Where ``rope_layout[l]`` is 1, plain RoPE
  (``rope_theta``) on all of ``hd``; where it is 0, NO position encoding
  (the causal mask alone orders the keys). Scores ``q k^T / sqrt(hd)``,
  causal; where ``sliding_window_layout[l]`` is 1 a query keeps the keys
  with ``0 <= q_pos - k_pos < sliding_window_size``. ``h += ctx W_o``.
* ``y = RMSNorm_2(h)``; ``h += sum_j w_j down_{e_j}(relu(gate_{e_j} y)
  * up_{e_j} y)``: ReGLU experts ``moe_ffn_hidden_size`` wide, the picks
  those made before attention.

Not in the config, set by the model's description (the benchmark's
configuration file lists them under ``assumed``): the router's input,
ReGLU, no dense layer, no secondary experts, no attention bias, no
QK-norm, rotate-half pairing.

``experts_held = (first, count)`` says which experts this parameter tree
holds, as ``laguna``: the router keeps its published width, the picks on
held experts are computed. The served cut holds them all.

Norm, rotary, the projections' product, the whole-sequence attention and
the head are ``laguna``'s, imported, not copied. Two paths share the
layer functions: the full forward (:func:`forward`, :func:`prefill`) and
the paged programs of ``serving/`` through :func:`paged_model`: window
layers keep a ring of pages, global layers every page.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from pipegoose_tpu.models.laguna import (
    _attend,
    _dot,
    apply_rotary,
    logits_fn,
    rope_frequencies,
)
from pipegoose_tpu.models.mixtral import rms_norm
from pipegoose_tpu.nn.expert_parallel.experts import (
    grouped_experts,
    reglu_grouped,
)
from pipegoose_tpu.nn.expert_parallel.routers import (
    SoftmaxTopKRouter,
    TopKRouting,
)


@dataclasses.dataclass(frozen=True)
class SmallThinkerConfig:
    # published keys (defaults: SmallThinker-21BA3B-Instruct)
    vocab_size: int = 151936
    hidden_size: int = 2560
    num_hidden_layers: int = 52
    num_attention_heads: int = 28
    num_key_value_heads: int = 4
    head_dim: int = 128
    rms_norm_eps: float = 1e-6
    moe_ffn_hidden_size: int = 768
    moe_num_primary_experts: int = 64
    moe_num_active_primary_experts: int = 6
    moe_primary_router_apply_softmax: bool = True
    norm_topk_prob: bool = True
    # per layer, 1: rotary / a sliding window; the published pattern is
    # one global layer without position encoding in four
    rope_layout: tuple = (0, 1, 1, 1) * 13
    sliding_window_layout: tuple = (0, 1, 1, 1) * 13
    sliding_window_size: int = 4096
    rope_theta: float = 1500000.0
    rope_scaling: Optional[Any] = None
    max_position_embeddings: int = 16384
    tie_word_embeddings: bool = False
    initializer_range: float = 0.02
    # the share of a layer held here: (first, count) of the experts;
    # None = all of them
    experts_held: Optional[tuple] = None
    use_flash: bool = False
    # tokens a call of the experts: a longer sequence (a multiple of it)
    # goes through in blocks, one after the other, so that the sorted
    # picks of an 8k prompt do not all exist at once. None: never
    moe_block_tokens: Optional[int] = None
    dtype: Any = jnp.float32

    def __post_init__(self):
        n = self.num_hidden_layers
        if len(self.rope_layout) != n or len(self.sliding_window_layout) != n:
            raise ValueError(f"rope_layout and sliding_window_layout need "
                             f"one entry for each of {n} layers")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f"{self.num_attention_heads} query heads do not divide "
                f"over {self.num_key_value_heads} KV heads")
        for key, built in (("moe_primary_router_apply_softmax", True),
                           ("rope_scaling", None),
                           ("tie_word_embeddings", False)):
            if getattr(self, key) != built:
                raise ValueError(f"{key}={getattr(self, key)!r} is not "
                                 f"built: only {built!r} is")
        first, count = self.held
        if first < 0 or count < 1 \
                or first + count > self.moe_num_primary_experts:
            raise ValueError(f"experts_held {self.experts_held} lies "
                             f"outside 0..{self.moe_num_primary_experts}")

    @property
    def held(self) -> tuple:
        return tuple(self.experts_held or (0, self.moe_num_primary_experts))

    def window_of(self, layer: int) -> Optional[int]:
        return (self.sliding_window_size
                if self.sliding_window_layout[layer] else None)

    def router(self) -> SoftmaxTopKRouter:
        return SoftmaxTopKRouter(
            self.moe_num_primary_experts,
            self.moe_num_active_primary_experts,
            normalize=self.norm_topk_prob)

    def paged_model(self, tp_axis=None):
        """The description ``ServingEngine`` serves this model by."""
        return paged_model(self, tp_axis)


# -- init ------------------------------------------------------------------

def param_shapes(c: SmallThinkerConfig) -> dict:
    """The parameter tree as shapes: a list of layers, all of one
    shape."""
    h, hd, v = c.hidden_size, c.head_dim, c.vocab_size
    nh, kv, f = c.num_attention_heads, c.num_key_value_heads, \
        c.moe_ffn_hidden_size
    lead = (c.held[1],)
    layer = {
        "ln_1": {"scale": (h,)},
        "router": {"gate": {"kernel": (h, c.moe_num_primary_experts)}},
        "attn": {"q": {"kernel": (h, nh * hd)}, "k": {"kernel": (h, kv * hd)},
                 "v": {"kernel": (h, kv * hd)}, "o": {"kernel": (nh * hd, h)}},
        "ln_2": {"scale": (h,)},
        "experts": {"gate": {"kernel": lead + (h, f)},
                    "up": {"kernel": lead + (h, f)},
                    "down": {"kernel": lead + (f, h)}},
    }
    return {
        "embed": {"weight": (v, h)},
        "layers": [layer for _ in range(c.num_hidden_layers)],
        "ln_f": {"scale": (h,)},
        "lm_head": {"weight": (v, h)},
    }


def init_params(config: SmallThinkerConfig, key: jax.Array) -> dict:
    """N(0, initializer_range) matrices, unit norms."""
    shapes, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(config), is_leaf=lambda x: isinstance(x, tuple))
    out = []
    for i, (path, shape) in enumerate(shapes):
        if "scale" in jax.tree_util.keystr(path):
            x = jnp.ones(shape, config.dtype)
        else:
            x = (jax.random.normal(jax.random.fold_in(key, i), shape)
                 * config.initializer_range).astype(config.dtype)
        out.append(x)
    return jax.tree_util.tree_unflatten(treedef, out)


# -- layers ----------------------------------------------------------------

def qkv(blk, h, pos, config: SmallThinkerConfig, layer: int):
    """``(q (B, S, H, hd), k, v (B, S, KV, hd), routing)`` of one layer
    at positions ``pos`` (B, S): everything that reads the first norm's
    output, the router among it; rotary where the layer has any.
    ``routing`` is over the B * S flat tokens."""
    c = config
    b, s, _ = h.shape
    a = rms_norm(blk["ln_1"], h, c.rms_norm_eps)
    with jax.named_scope("moe.route"):
        routing = c.router()(
            {"gate": blk["router"]["gate"],
             "bias": jnp.zeros((c.moe_num_primary_experts,))},
            a.reshape(b * s, -1))
    at = blk["attn"]
    q = _dot(a, at["q"]).reshape(b, s, -1, c.head_dim)
    k = _dot(a, at["k"]).reshape(b, s, -1, c.head_dim)
    v = _dot(a, at["v"]).reshape(b, s, -1, c.head_dim)
    if c.rope_layout[layer]:
        with jax.named_scope("attn.rope"):
            freqs = rope_frequencies({"rope_theta": c.rope_theta},
                                     c.head_dim)
            q, k = apply_rotary(q, pos, freqs), apply_rotary(k, pos, freqs)
    return q, k, v, routing


def moe(blk, x, routing: TopKRouting, config: SmallThinkerConfig, live=None):
    """The held experts' part of the routed sum on ``x`` (B, S, H), the
    picks as ``routing`` (over the B * S flat tokens) gives them.
    ``live`` (B, S) bool: the picks of the other positions (padding,
    empty slots) are sent to no expert. Returns ``(y, rows on each held
    expert)``."""
    c = config
    flat = x.reshape(-1, x.shape[-1])
    if live is not None:
        routing = routing._replace(experts=jnp.where(
            live.reshape(-1, 1), routing.experts, c.moe_num_primary_experts))

    def rows_of(args):
        return grouped_experts(blk["experts"], *args, c.held,
                               mlp_fn=reglu_grouped)

    n, block = flat.shape[0], c.moe_block_tokens
    if block and n > block and n % block == 0:
        y, rows = jax.lax.map(rows_of, jax.tree_util.tree_map(
            lambda a: a.reshape((-1, block) + a.shape[1:]), (flat, routing)))
        return y.reshape(x.shape), rows.sum(axis=0)
    y, rows = rows_of((flat, routing))
    return y.reshape(x.shape), rows


def finish(blk, h, ctx, routing, config: SmallThinkerConfig, live=None):
    """The rest of a layer once attention has given ``ctx`` (B, S, H *
    hd): output projection, then the experts ``routing`` picked before
    attention, on the second norm's output. Returns ``(h, rows on each
    held expert)``."""
    h = h + _dot(ctx, blk["attn"]["o"])
    y, rows = moe(blk, rms_norm(blk["ln_2"], h, config.rms_norm_eps),
                  routing, config, live)
    return h + y, rows


def _trunk(params, input_ids, config: SmallThinkerConfig, live=None):
    """Embedding and every layer over whole sequences from position 0.
    Returns the final norm's output, each layer's keys and values
    (rotated where the layer rotates) and the rows on each held expert
    per layer."""
    c = config
    b, s = input_ids.shape
    x = jnp.take(params["embed"]["weight"], input_ids, axis=0).astype(c.dtype)
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    kvs, rows = [], []
    for i, blk in enumerate(params["layers"]):
        q, k, v, routing = qkv(blk, x, pos, c, i)
        ctx = _attend(q, k, v, c, c.window_of(i))
        x, r = finish(blk, x, ctx, routing, c, live)
        kvs.append((k, v))
        rows.append(r)
    return rms_norm(params["ln_f"], x, c.rms_norm_eps), kvs, rows


def forward(params, input_ids, config: SmallThinkerConfig):
    """(B, S) token ids -> (B, S, V) float32 logits."""
    return logits_fn(params, _trunk(params, input_ids, config)[0])


def prefill(params, ids, mask, config: SmallThinkerConfig):
    """The serving prefill: one RIGHT-padded prompt ``ids`` (1, S_pad)
    with ``mask`` (1, S_pad) 1 on its tokens, through the model's own
    forward. Returns the logits after the last real token (1, V) and the
    cache ``{kind: {"k", "v"}}``, each (layers of the kind, 1, S_pad, KV,
    hd). Attention is causal, so the padding behind the prompt changes
    no real position; its picks go to no expert."""
    hidden, kvs, _ = _trunk(params, ids, config, live=mask > 0)
    n = mask.sum(axis=1).astype(jnp.int32)
    last = jnp.take_along_axis(hidden, (n - 1)[:, None, None], axis=1)
    cache = {}
    for name, windowed in (("global", False), ("window", True)):
        mine = [kv for kv, w in zip(kvs, config.sliding_window_layout)
                if bool(w) is windowed]
        if mine:
            cache[name] = {"k": jnp.stack([k for k, _ in mine]),
                           "v": jnp.stack([v for _, v in mine])}
    return logits_fn(params, last)[:, 0], cache


# -- the description the paged programs take ---------------------------------

def paged_model(config: SmallThinkerConfig, tp_axis=None):
    """SmallThinker as ``serving/blocks.PagedModel``: a group a layer as
    Laguna's, global layers on the ``global`` cache kind, sliding layers
    on the ``window`` kind; what ``qkv`` saves for ``finish`` is the
    routing."""
    from pipegoose_tpu.serving.blocks import LayerGroup, PagedModel

    if tp_axis is not None:
        raise ValueError("smallthinker is served on one device: a mesh is "
                         "not built for a model with two cache kinds")
    c = config

    def group(i):
        return LayerGroup(
            kind="window" if c.sliding_window_layout[i] else "global",
            n=1, stacked=False,
            params=lambda p, i=i: p["layers"][i],
            qkv=lambda blk, h, pos, i=i: qkv(blk, h, pos, c, i),
            finish=lambda blk, h, ctx, routing, live: finish(
                blk, h, ctx, routing, c, live),
            slopes=None)

    return PagedModel(
        n_kv_head=c.num_key_value_heads, head_dim=c.head_dim, dtype=c.dtype,
        window=c.sliding_window_size,
        groups=tuple(group(i) for i in range(c.num_hidden_layers)),
        embed=lambda p, tokens: jnp.take(
            p["embed"]["weight"], tokens, axis=0).astype(c.dtype),
        final=lambda p, h: rms_norm(p["ln_f"], h, c.rms_norm_eps),
        logits=lambda p, h: logits_fn(p, h),
        prefill=lambda p, ids, mask: prefill(p, ids, mask, c),
        left_pad=False,
        counters="rows_per_expert",
    )
