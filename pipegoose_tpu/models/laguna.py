"""Laguna-S-2.1 (``model_type: laguna``): window and full attention
layers of unlike head counts over shared KV heads, a per-head output
gate, sigmoid top-k experts with a shared expert.

Every size is a published config key
(https://huggingface.co/poolside/Laguna-S-2.1/blob/main/config.json).
RMSNorm, pre-norm residual blocks, no bias, an untied head. T tokens:

* Attention, layer ``l``: ``a = RMSNorm(h)``; ``q = a W_q`` in ``(T,
  H_l, hd)`` with ``H_l = num_attention_heads_per_layer[l]`` (48 on
  ``full_attention`` layers, 72 on ``sliding_attention`` ones); ``k``,
  ``v`` in ``(T, KV, hd)``, each KV head serving ``H_l / KV`` query
  heads (query head ``h`` reads KV head ``h // g``). Rotary by kind
  (``rope_parameters``): full layers YaRN on the first
  ``partial_rotary_factor * hd`` dims (cos and sin scaled by
  ``attention_factor``), the rest pass through; sliding layers plain
  RoPE on all of ``hd``. Scores ``q k^T / sqrt(hd)``, causal; a sliding
  layer keeps the keys with ``0 <= q_pos - k_pos < sliding_window``.
  Gate (``gating: per-head``): ``g = sigmoid(a W_g)`` in ``(T, H_l)``,
  one scalar a head; ``h += concat_h(g_h * o_h) W_o``.
* FFN: the layers of ``mlp_only_layers`` a SwiGLU of width
  ``intermediate_size``. Every other layer ``shared(m) + sum_j w_j
  expert_{e_j}(m)``: ``s = sigmoid(m W_r)`` over ``num_experts``, the
  ``num_experts_per_tok`` largest, their weights renormalised to sum 1
  (``norm_topk_prob``) times ``moe_routed_scaling_factor``, applied to
  the experts' outputs; every expert ``moe_intermediate_size`` wide.

Not in the config, set by the family's convention (the benchmark's
configuration file lists them under ``assumed``): ``silu`` SwiGLU; the
router's score function (sigmoid, DeepSeek-V3's, whose ``norm_topk_prob``
+ scaling pairing the config repeats; no selection bias); the gate's
input (the normed hidden state) and place (on each head's output,
before ``W_o``); no QK-norm; rotate-half pairing.

A chip's share of a layer, as ``glm4_moe_lite``: ``experts_held =
(first, count)`` says which routed experts this parameter tree holds.
The router keeps its published width and picks over all of them; the
picks that fall on held experts are computed, what the absent ones
would add is left out. A sliced vocabulary is a smaller vocabulary.

Two paths share the layer functions: the full forward (:func:`forward`,
:func:`prefill`: flash attention with ``window`` and a GQA group where
``use_flash``), and the paged programs of ``serving/`` through
:func:`paged_model`, the description ``ServingEngine`` takes
(``serving/blocks.py``): window layers keep a ring of pages, global
layers every page.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from pipegoose_tpu.models.mixtral import NEG_INF, rms_norm
from pipegoose_tpu.nn.expert_parallel.experts import grouped_experts
from pipegoose_tpu.nn.expert_parallel.routers import SigmoidTopKRouter

FULL, SLIDING = "full_attention", "sliding_attention"

# Laguna-S-2.1's two rotary sets, as ``rope_parameters`` gives them
ROPE_S_2_1 = (
    (FULL, (("rope_type", "yarn"), ("rope_theta", 500000.0),
            ("factor", 128.0), ("original_max_position_embeddings", 8192),
            ("beta_fast", 32.0), ("beta_slow", 1.0),
            ("attention_factor", 1.4852030263919618),
            ("partial_rotary_factor", 0.5))),
    (SLIDING, (("rope_type", "default"), ("rope_theta", 10000.0),
               ("partial_rotary_factor", 1.0))),
)


def freeze_rope(rope_parameters: dict) -> tuple:
    """The config's nested ``rope_parameters`` as a hashable tuple (a
    config is a jit static argument)."""
    return tuple((kind, tuple(sorted(p.items())))
                 for kind, p in sorted(rope_parameters.items()))


@dataclasses.dataclass(frozen=True)
class LagunaConfig:
    # published keys (defaults: Laguna-S-2.1)
    vocab_size: int = 100352
    hidden_size: int = 3072
    intermediate_size: int = 12288
    num_hidden_layers: int = 48
    num_key_value_heads: int = 8
    head_dim: int = 128
    rms_norm_eps: float = 1e-6
    num_experts: int = 256
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 1024
    shared_expert_intermediate_size: int = 1024
    norm_topk_prob: bool = True
    moe_routed_scaling_factor: float = 2.5
    mlp_only_layers: tuple = (0,)
    sliding_window: int = 512
    # per layer; the published pattern is one full layer in four
    layer_types: tuple = (FULL, SLIDING, SLIDING, SLIDING) * 12
    num_attention_heads_per_layer: tuple = (48, 72, 72, 72) * 12
    rope_parameters: tuple = ROPE_S_2_1
    initializer_range: float = 0.02
    # the share of a layer held here: (first, count) of the routed
    # experts; None = all of them
    experts_held: Optional[tuple] = None
    # real rows of a padded vocabulary
    valid_vocab_size: Optional[int] = None
    use_flash: bool = False
    # tokens a call of a sparse layer's feed-forward: a longer sequence
    # (a multiple of it) goes through in blocks, one after the other, so
    # that the sorted picks (tokens x experts_per_tok rows, three times
    # over) of an 8k prompt do not all exist at once. None: never
    moe_block_tokens: Optional[int] = None
    dtype: Any = jnp.float32

    def __post_init__(self):
        n = self.num_hidden_layers
        if len(self.layer_types) != n or \
                len(self.num_attention_heads_per_layer) != n:
            raise ValueError(f"layer_types and num_attention_heads_per_layer "
                             f"need one entry for each of {n} layers")
        for kind in self.layer_types:
            if kind not in (FULL, SLIDING):
                raise ValueError(f"unknown layer type {kind!r}")
        for h in self.num_attention_heads_per_layer:
            if h % self.num_key_value_heads:
                raise ValueError(f"{h} query heads do not divide over "
                                 f"{self.num_key_value_heads} KV heads")
        first, count = self.held
        if first < 0 or count < 1 or first + count > self.num_experts:
            raise ValueError(f"experts_held {self.experts_held} lies "
                             f"outside 0..{self.num_experts}")

    @property
    def held(self) -> tuple:
        return tuple(self.experts_held or (0, self.num_experts))

    def is_dense(self, layer: int) -> bool:
        return layer in self.mlp_only_layers

    def window_of(self, layer: int) -> Optional[int]:
        return (self.sliding_window
                if self.layer_types[layer] == SLIDING else None)

    def rope_of(self, layer: int) -> dict:
        return dict(dict(self.rope_parameters)[self.layer_types[layer]])

    def router(self) -> SigmoidTopKRouter:
        return SigmoidTopKRouter(
            self.num_experts, self.num_experts_per_tok,
            scaling=self.moe_routed_scaling_factor,
            normalize=self.norm_topk_prob)

    def paged_model(self, tp_axis=None):
        """The description ``ServingEngine`` serves this model by."""
        return paged_model(self, tp_axis)


# -- init ------------------------------------------------------------------

def _swiglu_shapes(h: int, f: int, lead: tuple = ()) -> dict:
    return {"gate": {"kernel": lead + (h, f)}, "up": {"kernel": lead + (h, f)},
            "down": {"kernel": lead + (f, h)}}


def _layer_shapes(c: LagunaConfig, layer: int) -> dict:
    h, hd, kv = c.hidden_size, c.head_dim, c.num_key_value_heads
    nh = c.num_attention_heads_per_layer[layer]
    out = {
        "ln_1": {"scale": (h,)},
        "attn": {"q": {"kernel": (h, nh * hd)}, "k": {"kernel": (h, kv * hd)},
                 "v": {"kernel": (h, kv * hd)},
                 "gate": {"kernel": (h, nh)}, "o": {"kernel": (nh * hd, h)}},
        "ln_2": {"scale": (h,)},
    }
    if c.is_dense(layer):
        out["mlp"] = _swiglu_shapes(h, c.intermediate_size)
    else:
        out["router"] = {"gate": {"kernel": (h, c.num_experts)}}
        out["shared"] = _swiglu_shapes(h, c.shared_expert_intermediate_size)
        out["experts"] = _swiglu_shapes(h, c.moe_intermediate_size,
                                        (c.held[1],))
    return out


def param_shapes(c: LagunaConfig) -> dict:
    """The parameter tree as shapes. Layers differ in shape (head
    counts, dense or sparse), so they are a list, not a stack."""
    h, v = c.hidden_size, c.vocab_size
    return {
        "embed": {"weight": (v, h)},
        "layers": [_layer_shapes(c, i) for i in range(c.num_hidden_layers)],
        "ln_f": {"scale": (h,)},
        "lm_head": {"weight": (v, h)},
    }


def init_params(config: LagunaConfig, key: jax.Array) -> dict:
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


# -- rotary ----------------------------------------------------------------

def rope_frequencies(rope: dict, head_dim: int) -> tuple:
    """``(inv_freq (rot / 2,), scale, rot)`` of one rotary set: the
    first ``rot = partial_rotary_factor * head_dim`` dims turn, cos and
    sin are multiplied by ``scale``. ``default``: ``theta^(-2i / rot)``.
    ``yarn`` (Peng et al. 2023, as HF ``_compute_yarn_parameters``):
    each frequency a blend of itself and itself over ``factor``, by a
    linear ramp between the dims that make ``beta_fast`` and
    ``beta_slow`` turns over the original context."""
    rot = int(head_dim * rope.get("partial_rotary_factor", 1.0))
    theta = float(rope["rope_theta"])
    freqs = theta ** (np.arange(0, rot, 2, dtype=np.float64) / rot)
    if rope.get("rope_type", "default") == "default":
        return (1.0 / freqs).astype(np.float32), 1.0, rot
    if rope["rope_type"] != "yarn":
        raise ValueError(f"unknown rope_type {rope['rope_type']!r}")
    factor = float(rope["factor"])
    orig = rope["original_max_position_embeddings"]

    def turns_dim(n_turns):
        return rot * math.log(orig / (n_turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(turns_dim(rope["beta_fast"])), 0)
    high = min(math.ceil(turns_dim(rope["beta_slow"])), rot - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(rot // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp            # 1: the frequency as it is (fast dims)
    inv = (1.0 / (factor * freqs)) * (1.0 - keep) + (1.0 / freqs) * keep
    scale = rope.get("attention_factor")
    if scale is None:
        scale = 0.1 * math.log(factor) + 1.0
    return inv.astype(np.float32), float(scale), rot


def apply_rotary(x, pos, freqs):
    """Rotate-half rotary on the first ``rot`` dims of ``x`` (..., S,
    heads, hd) at positions ``pos`` (..., S); float32 inside, the
    result in ``x``'s dtype."""
    inv, scale, rot = freqs
    ang = pos.astype(jnp.float32)[..., None] * jnp.asarray(inv)
    ang = jnp.concatenate([ang, ang], axis=-1)[..., None, :]   # (.., S, 1, rot)
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    xf = x.astype(jnp.float32)
    turn, rest = xf[..., :rot], xf[..., rot:]
    x1, x2 = turn[..., :rot // 2], turn[..., rot // 2:]
    turned = turn * cos + jnp.concatenate([-x2, x1], axis=-1) * sin
    return jnp.concatenate([turned, rest], axis=-1).astype(x.dtype)


# -- layers ----------------------------------------------------------------

def _dot(x, w):
    return jnp.dot(x, w["kernel"],
                   preferred_element_type=jnp.float32).astype(x.dtype)


def _swiglu(blk, x):
    return _dot(jax.nn.silu(_dot(x, blk["gate"])) * _dot(x, blk["up"]),
                blk["down"])


def qkv(blk, h, pos, config: LagunaConfig, layer: int):
    """``(q (B, S, H_l, hd), k, v (B, S, KV, hd), a)`` of one layer at
    positions ``pos`` (B, S), rotary applied; ``a`` the normed input,
    which the gate reads too."""
    c = config
    b, s, _ = h.shape
    a = rms_norm(blk["ln_1"], h, c.rms_norm_eps)
    at = blk["attn"]
    q = _dot(a, at["q"]).reshape(b, s, -1, c.head_dim)
    k = _dot(a, at["k"]).reshape(b, s, -1, c.head_dim)
    v = _dot(a, at["v"]).reshape(b, s, -1, c.head_dim)
    with jax.named_scope("attn.rope"):
        freqs = rope_frequencies(c.rope_of(layer), c.head_dim)
        q, k = apply_rotary(q, pos, freqs), apply_rotary(k, pos, freqs)
    return q, k, v, a


def moe(blk, x, config: LagunaConfig, live=None):
    """One sparse layer's feed-forward on ``x`` (B, S, H): the shared
    expert plus the held experts' part of the routed sum. ``live`` (B,
    S) bool: the picks of the other positions (padding, empty slots)
    are sent to no expert. Returns ``(y, rows on each held expert)``."""
    c = config
    flat = x.reshape(-1, x.shape[-1])
    keep = (jnp.ones(flat.shape[:1], bool) if live is None
            else live.reshape(-1))

    def rows_of(flat, keep):
        with jax.named_scope("moe.route"):
            routing = c.router()({"gate": blk["router"]["gate"],
                                  "bias": jnp.zeros((c.num_experts,))}, flat)
            routing = routing._replace(experts=jnp.where(
                keep[:, None], routing.experts, c.num_experts))
        routed, rows = grouped_experts(blk["experts"], flat, routing, c.held)
        with jax.named_scope("moe.shared"):
            shared = _swiglu(blk["shared"], flat)
        return shared + routed, rows

    n, block = flat.shape[0], c.moe_block_tokens
    if block and n > block and n % block == 0:
        y, rows = jax.lax.map(
            lambda args: rows_of(*args),
            (flat.reshape(-1, block, flat.shape[1]), keep.reshape(-1, block)))
        return y.reshape(x.shape), rows.sum(axis=0)
    y, rows = rows_of(flat, keep)
    return y.reshape(x.shape), rows


def finish(blk, h, ctx, a, config: LagunaConfig, live=None):
    """The rest of a layer once attention has given ``ctx`` (B, S, H_l
    * hd): gate, output projection, feed-forward. Returns ``(h, rows on
    each held expert)``, the rows ``None`` for a dense layer."""
    c = config
    b, s, _ = h.shape
    with jax.named_scope("attn.gate"):
        g = jax.nn.sigmoid(jnp.dot(a, blk["attn"]["gate"]["kernel"],
                                   preferred_element_type=jnp.float32))
        ctx = (ctx.reshape(b, s, -1, c.head_dim).astype(jnp.float32)
               * g[..., None]).astype(h.dtype).reshape(b, s, -1)
    h = h + _dot(ctx, blk["attn"]["o"])
    m = rms_norm(blk["ln_2"], h, c.rms_norm_eps)
    if "mlp" in blk:
        flat = m.reshape(-1, m.shape[-1])
        n, block = flat.shape[0], c.moe_block_tokens
        if block and n > block and n % block == 0:
            # the dense layer's (tokens, intermediate_size) pair as well
            y = jax.lax.map(lambda r: _swiglu(blk["mlp"], r),
                            flat.reshape(-1, block, flat.shape[1]))
            return h + y.reshape(m.shape), None
        return h + _swiglu(blk["mlp"], m), None
    y, rows = moe(blk, m, c, live)
    return h + y, rows


def _attend(q, k, v, config: LagunaConfig, window: Optional[int]):
    """Causal (and windowed) attention over whole sequences: the flash
    kernels with their GQA group and window block skipping, or dense
    masked scores. Returns (B, S, H_l * hd)."""
    b, s, nh, hd = q.shape
    if config.use_flash:
        from pipegoose_tpu.ops.flash_attention import flash_attention

        ctx = flash_attention(q, k, v, alibi_slopes=None, causal=True,
                              scale=hd ** -0.5, window=window)
        return ctx.astype(q.dtype).reshape(b, s, nh * hd)
    g = nh // k.shape[2]
    qg = q.reshape(b, s, -1, g, hd)
    scores = jnp.einsum("bqkgd,bnkd->bkgqn", qg, k,
                        preferred_element_type=jnp.float32) * hd ** -0.5
    pos = jnp.arange(s)
    keep = pos[None, :] <= pos[:, None]
    if window is not None:
        keep = keep & (pos[:, None] - pos[None, :] < window)
    probs = jax.nn.softmax(jnp.where(keep, scores, NEG_INF), axis=-1)
    ctx = jnp.einsum("bkgqn,bnkd->bqkgd", probs.astype(q.dtype), v,
                     preferred_element_type=jnp.float32)
    return ctx.astype(q.dtype).reshape(b, s, nh * hd)


def _trunk(params, input_ids, config: LagunaConfig, live=None):
    """Embedding and every layer over whole sequences from position 0.
    Returns the final norm's output, each layer's rotated keys and
    values, and the rows on each held expert per sparse layer."""
    c = config
    b, s = input_ids.shape
    x = jnp.take(params["embed"]["weight"], input_ids, axis=0).astype(c.dtype)
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    kvs, rows = [], []
    for i, blk in enumerate(params["layers"]):
        q, k, v, a = qkv(blk, x, pos, c, i)
        ctx = _attend(q, k, v, c, c.window_of(i))
        x, r = finish(blk, x, ctx, a, c, live)
        kvs.append((k, v))
        if r is not None:
            rows.append(r)
    return rms_norm(params["ln_f"], x, c.rms_norm_eps), kvs, rows


def logits_fn(params, hidden):
    """(.., V) float32 over the head's rows."""
    return jnp.einsum("...h,vh->...v", hidden, params["lm_head"]["weight"],
                      preferred_element_type=jnp.float32)


def forward(params, input_ids, config: LagunaConfig):
    """(B, S) token ids -> (B, S, V) float32 logits."""
    return logits_fn(params, _trunk(params, input_ids, config)[0])


def prefill(params, ids, mask, config: LagunaConfig):
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
    for name, kind in (("global", FULL), ("window", SLIDING)):
        mine = [kv for kv, t in zip(kvs, config.layer_types) if t == kind]
        if mine:
            cache[name] = {"k": jnp.stack([k for k, _ in mine]),
                           "v": jnp.stack([v for _, v in mine])}
    return logits_fn(params, last)[:, 0], cache


# -- the description the paged programs take ---------------------------------

def paged_model(config: LagunaConfig, tp_axis=None):
    """Laguna as ``serving/blocks.PagedModel``: a group a layer (their
    shapes differ), full layers on the ``global`` cache kind, sliding
    layers on the ``window`` kind."""
    from pipegoose_tpu.serving.blocks import LayerGroup, PagedModel

    if tp_axis is not None:
        raise ValueError("laguna is served on one device: a mesh is not "
                         "built for a model with two cache kinds")
    c = config

    def group(i):
        return LayerGroup(
            kind="window" if c.layer_types[i] == SLIDING else "global",
            n=1, stacked=False,
            params=lambda p, i=i: p["layers"][i],
            qkv=lambda blk, h, pos, i=i: qkv(blk, h, pos, c, i),
            finish=lambda blk, h, ctx, a, live: finish(
                blk, h, ctx, a, c, live),
            slopes=None)

    return PagedModel(
        n_kv_head=c.num_key_value_heads, head_dim=c.head_dim, dtype=c.dtype,
        window=c.sliding_window,
        groups=tuple(group(i) for i in range(c.num_hidden_layers)),
        embed=lambda p, tokens: jnp.take(
            p["embed"]["weight"], tokens, axis=0).astype(c.dtype),
        final=lambda p, h: rms_norm(p["ln_f"], h, c.rms_norm_eps),
        logits=lambda p, h: logits_fn(p, h),
        prefill=lambda p, ids, mask: prefill(p, ids, mask, c),
        left_pad=False,
        counters="rows_per_expert",
    )
