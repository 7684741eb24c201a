"""Mixtral: sparse-MoE transformer (RMSNorm, RoPE, GQA, SwiGLU experts).

Second model family, targeting BASELINE.json config 5 (Mixtral-8x7B 4D
TP x PP x DP x EP + DiLoCo). The reference supports only BLOOM
(README.md:19); this model is built on the same framework primitives —
stacked-layer scan, TP layer functions, static-shape MoE dispatch — so
every parallel form (TP/DP/EP/ZeRO/PP-ready stacked layout) applies.

Semantics match HF ``modeling_mixtral`` for checkpoint parity:
- RMSNorm (no bias, f32 stats), rotate-half RoPE (theta from config),
  GQA via kv-head repetition, scaling = head_dim**-0.5;
- SwiGLU experts: w2(silu(w1(x)) * w3(x)); router = softmax over f32
  logits -> top-k -> renormalize (HF MixtralSparseMoeBlock:112-118) —
  exactly our TopKRouter with normalize_gates=True and ample capacity.
Parity is tested against HF in tests/models/test_mixtral.py.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from pipegoose_tpu.nn.expert_parallel.experts import moe_layer
from pipegoose_tpu.nn.expert_parallel.loss import ExpertLoss
from pipegoose_tpu.nn.expert_parallel.routers import SwitchNoisePolicy, TopKRouter
from pipegoose_tpu.nn.tensor_parallel.layers import (
    column_parallel_linear,
    row_parallel_linear,
    vocab_parallel_cross_entropy,
    vocab_parallel_embedding,
)
from pipegoose_tpu.ops.flash_attention import remat_policy

NEG_INF = -1e9


@dataclasses.dataclass(frozen=True)
class MixtralConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 14336
    n_layer: int = 32
    n_head: int = 32
    n_kv_head: int = 8
    num_experts: int = 8
    top_k: int = 2
    rope_theta: float = 1e6
    rms_eps: float = 1e-5
    initializer_range: float = 0.02
    router_jitter: float = 0.0
    aux_loss_weight: float = 0.001  # HF MixtralConfig router_aux_loss_coef default
    z_loss_weight: float = 0.0
    # None -> no-drop capacity (= num_experts/top_k, i.e. C = n_tokens):
    # HF's MixtralSparseMoeBlock never drops, so checkpoint parity needs
    # this; set a real factor (e.g. 1.25-2.0) for capacity-bound training
    capacity_factor: Optional[float] = None
    dtype: Any = jnp.float32
    remat: bool = False
    # fused Pallas flash attention (ops/flash_attention.py): applied
    # after RoPE, zero ALiBi slopes, padding via the kernel's kv_neg
    # bias input; GQA served natively (grouped K/V index maps, no head
    # repetition)
    use_flash: bool = False
    # fused Pallas CE (ops/fused_ce.py): no logits buffer in HBM
    fused_ce: bool = False
    # set when the embedding/head was padded for TP divisibility: the
    # true vocab size; padded logit slots are masked out of CE + decode
    valid_vocab_size: Optional[int] = None
    # Mistral-style sliding-window attention: each query attends keys
    # within `sliding_window` positions behind it (None = full causal;
    # HF Mixtral-8x7B configs disable it)
    sliding_window: Optional[int] = None

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.n_head

    @classmethod
    def mixtral_8x7b(cls, **kw) -> "MixtralConfig":
        return cls(vocab_size=32000, hidden_size=4096, intermediate_size=14336,
                   n_layer=32, n_head=32, n_kv_head=8, **kw)

    def router(self) -> TopKRouter:
        noise = SwitchNoisePolicy(self.router_jitter) if self.router_jitter else None
        cf = (
            self.capacity_factor
            if self.capacity_factor is not None
            else self.num_experts / self.top_k  # C = n_tokens: no drops
        )
        return TopKRouter(
            num_experts=self.num_experts,
            top_k=self.top_k,
            capacity_factor=cf,
            noise=noise,
            normalize_gates=True,
        )


# -- init ------------------------------------------------------------------

def init_params(config: MixtralConfig, key: jax.Array) -> dict:
    h, v, L = config.hidden_size, config.vocab_size, config.n_layer
    hd, nh, nkv = config.head_dim, config.n_head, config.n_kv_head
    f, E = config.intermediate_size, config.num_experts
    std, dt = config.initializer_range, config.dtype
    ks = jax.random.split(key, 10)

    def dense(k, shape):
        return (jax.random.normal(k, shape) * std).astype(dt)

    def rms_stack():
        return {"scale": jnp.ones((L, h), dt)}

    return {
        "embed": {"weight": dense(ks[0], (v, h))},
        "blocks": {
            "ln_1": rms_stack(),
            "attn": {
                "q": {"kernel": dense(ks[1], (L, h, nh * hd))},
                "k": {"kernel": dense(ks[2], (L, h, nkv * hd))},
                "v": {"kernel": dense(ks[3], (L, h, nkv * hd))},
                "o": {"kernel": dense(ks[4], (L, nh * hd, h))},
            },
            "ln_2": rms_stack(),
            "router": {"gate": {"kernel": dense(ks[5], (L, h, E))}},
            "moe": {
                "w1": {"kernel": dense(ks[6], (L, E, h, f))},  # gate proj
                "w3": {"kernel": dense(ks[7], (L, E, h, f))},  # up proj
                "w2": {"kernel": dense(ks[8], (L, E, f, h))},  # down proj
            },
        },
        "ln_f": {"scale": jnp.ones(h, dt)},
        "lm_head": {"kernel": dense(ks[9], (h, v))},
    }


# -- ops -------------------------------------------------------------------

def rms_norm(params: dict, x: jax.Array, eps: float) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt((xf * xf).mean(-1, keepdims=True) + eps)
    return (y * params["scale"]).astype(dt)


@dataclasses.dataclass(frozen=True)
class RopeScaling:
    """HF ``rope_scaling`` semantics (transformers modeling_rope_utils):
    ``linear`` divides positions by ``factor``; ``dynamic`` is NTK theta
    rescaling past the original context; ``llama3`` is the per-frequency
    interpolation of Llama-3.1+ checkpoints. Frozen dataclass (not the
    raw HF dict) so configs stay hashable for jit static args."""

    rope_type: str  # "linear" | "dynamic" | "llama3"
    factor: float = 1.0
    low_freq_factor: float = 1.0
    high_freq_factor: float = 4.0
    original_max_position_embeddings: int = 8192

    @classmethod
    def from_hf(cls, d, default_original_max: int = 8192) -> Optional["RopeScaling"]:
        if d is None:
            return None
        rope_type = d.get("rope_type", d.get("type", "default"))
        if rope_type == "default":
            return None
        if rope_type not in ("linear", "dynamic", "llama3"):
            raise NotImplementedError(
                f"rope_scaling type {rope_type!r} not supported "
                "(linear, dynamic, llama3 are)"
            )
        return cls(
            rope_type=rope_type,
            factor=float(d.get("factor", 1.0)),
            low_freq_factor=float(d.get("low_freq_factor", 1.0)),
            high_freq_factor=float(d.get("high_freq_factor", 4.0)),
            original_max_position_embeddings=int(
                d.get("original_max_position_embeddings", default_original_max)
            ),
        )


def _scaled_inv_freq(inv: jax.Array, seq: int, head_dim: int, theta: float,
                     scaling: RopeScaling) -> jax.Array:
    """Apply one RopeScaling variant to the base inverse frequencies."""
    if scaling.rope_type == "linear":
        return inv / scaling.factor
    if scaling.rope_type == "dynamic":
        orig = scaling.original_max_position_embeddings
        if seq <= orig:  # static shape — resolved at trace time
            return inv
        theta = theta * (
            (scaling.factor * seq / orig) - (scaling.factor - 1)
        ) ** (head_dim / (head_dim - 2))
        return 1.0 / (
            theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
        )
    if scaling.rope_type == "llama3":
        orig = scaling.original_max_position_embeddings
        low_wl = orig / scaling.low_freq_factor
        high_wl = orig / scaling.high_freq_factor
        wavelen = 2.0 * jnp.pi / inv
        inv_lo = jnp.where(wavelen > low_wl, inv / scaling.factor, inv)
        smooth = (orig / wavelen - scaling.low_freq_factor) / (
            scaling.high_freq_factor - scaling.low_freq_factor
        )
        smoothed = (1.0 - smooth) * inv / scaling.factor + smooth * inv
        mid = (wavelen >= high_wl) & (wavelen <= low_wl)
        return jnp.where(mid, smoothed, inv_lo)
    raise NotImplementedError(scaling.rope_type)


def rope_cos_sin(seq: int, head_dim: int, theta: float,
                 scaling: Optional[RopeScaling] = None):
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    if scaling is not None:
        inv = _scaled_inv_freq(inv, seq, head_dim, theta, scaling)
    t = jnp.arange(seq, dtype=jnp.float32)
    freqs = jnp.outer(t, inv)  # (S, hd/2)
    emb = jnp.concatenate([freqs, freqs], axis=-1)  # (S, hd)
    return jnp.cos(emb), jnp.sin(emb)


def _rotate_half(x):
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-x2, x1], axis=-1)


def apply_rope(q, k, cos, sin):
    """q,k: (B, S, h, hd); cos/sin: (S, hd)."""
    cos = cos[None, :, None, :]
    sin = sin[None, :, None, :]
    return q * cos + _rotate_half(q) * sin, k * cos + _rotate_half(k) * sin


def causal_mask_bias(
    attention_mask: jax.Array, window: Optional[int] = None
) -> jax.Array:
    """Combined causal + padding (+ optional sliding window) additive
    bias (B, 1, S, S) — shared by the Mixtral and Llama families
    (absolute positions; RoPE models carry no ALiBi term)."""
    s = attention_mask.shape[-1]
    keep = jnp.tril(jnp.ones((s, s), bool))
    if window is not None:
        pos = jnp.arange(s)
        keep = keep & (pos[:, None] - pos[None, :] < window)
    keep = keep[None, None] & (attention_mask[:, None, None, :] > 0)
    return jnp.where(keep, 0.0, NEG_INF).astype(jnp.float32)


def rope_attention_bias(attention_mask: jax.Array, config) -> dict:
    """Bias inputs in the form the configured attention path consumes
    (shared by Mixtral and Llama): flash gets the O(S) per-key validity
    bias ``kv_neg`` (the causal mask lives inside the kernel); the
    standard path gets the dense (B, 1, S, S) ``mask_bias``."""
    if config.use_flash:
        from pipegoose_tpu.ops.flash_attention import mask_to_kv_bias

        # the sliding window (if any) is applied inside the kernel
        return {"kv_neg": mask_to_kv_bias(attention_mask)[1]}
    return {"mask_bias": causal_mask_bias(
        attention_mask, getattr(config, "sliding_window", None)
    )}


def _swiglu_experts(moe_params: dict, x: jax.Array, tp_axis: Optional[str]) -> jax.Array:
    """(E_local, C, H) -> (E_local, C, H): w2(silu(w1 x) * w3 x), with the
    FFN dim Megatron-sharded over tensor (w1/w3 column, w2 row+reduce)."""
    from pipegoose_tpu.distributed.functional import (
        copy_to_tensor_group,
        reduce_from_tensor_group,
    )

    if tp_axis is not None:
        # f-operator (see expert_mlp): completes the input cotangent's
        # psum across tensor ranks in backward
        x = copy_to_tensor_group(x, tp_axis)
    g = jnp.einsum("ech,ehf->ecf", x, moe_params["w1"]["kernel"],
                   preferred_element_type=jnp.float32).astype(x.dtype)
    u = jnp.einsum("ech,ehf->ecf", x, moe_params["w3"]["kernel"],
                   preferred_element_type=jnp.float32).astype(x.dtype)
    h = jax.nn.silu(g) * u
    out = jnp.einsum("ecf,efh->ech", h, moe_params["w2"]["kernel"],
                     preferred_element_type=jnp.float32).astype(x.dtype)
    if tp_axis is not None:
        out = reduce_from_tensor_group(out, tp_axis)
    return out


def _attention(blk, x, cos, sin, bias, config, tp_axis):
    """RoPE + GQA attention; ``bias`` is the dict from
    :func:`rope_attention_bias` (dense mask_bias OR flash kv_neg)."""
    b, s, _ = x.shape
    hd = config.head_dim
    tp = jax.lax.axis_size(tp_axis) if tp_axis else 1
    if config.n_head % tp or config.n_kv_head % tp:
        raise ValueError(
            f"n_head={config.n_head}/n_kv_head={config.n_kv_head} must divide "
            f"tensor axis size {tp}"
        )
    nh_l, nkv_l = config.n_head // tp, config.n_kv_head // tp
    groups = nh_l // nkv_l

    q = column_parallel_linear(blk["q"], x, tp_axis).reshape(b, s, nh_l, hd)
    k = column_parallel_linear(blk["k"], x, tp_axis).reshape(b, s, nkv_l, hd)
    v = column_parallel_linear(blk["v"], x, tp_axis).reshape(b, s, nkv_l, hd)
    q, k = apply_rope(q, k, cos, sin)

    if config.use_flash:
        from pipegoose_tpu.ops.flash_attention import flash_attention

        # native GQA: the kernel reads the nkv-wide K/V via grouped
        # index maps — no head repetition, g x less KV traffic
        ctx = flash_attention(
            q, k, v, alibi_slopes=None,  # RoPE: no ALiBi term
            kv_neg=bias["kv_neg"], causal=True,
            window=getattr(config, "sliding_window", None),
        )
        ctx = ctx.astype(x.dtype).reshape(b, s, nh_l * hd)
        return row_parallel_linear(blk["o"], ctx, tp_axis)

    # GQA: repeat kv heads for the dense einsum path
    k = jnp.repeat(k, groups, axis=2)
    v = jnp.repeat(v, groups, axis=2)

    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
    scores = scores * (hd**-0.5) + bias["mask_bias"]
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(x.dtype)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", probs, v, preferred_element_type=jnp.float32)
    ctx = ctx.astype(x.dtype).reshape(b, s, nh_l * hd)
    return row_parallel_linear(blk["o"], ctx, tp_axis)


def _block(blk, x, cos, sin, bias, key, config, tp_axis, ep_axis, train):
    h = rms_norm(blk["ln_1"], x, config.rms_eps)
    x = x + _attention(blk["attn"], h, cos, sin, bias, config, tp_axis)
    h = rms_norm(blk["ln_2"], x, config.rms_eps)

    router = config.router()
    flat = h.reshape(-1, h.shape[-1])
    routing = router(blk["router"], flat, key=key, train=train)
    y = moe_layer(
        blk["moe"], h, routing, axis_name=ep_axis,
        tp_axis=tp_axis, act=None, mlp_fn=_swiglu_experts,
    )
    return x + y, routing.aux_loss, routing.z_loss


def forward_hidden(
    params, input_ids, attention_mask, config,
    tp_axis=None, ep_axis=None, rng=None, train=False,
):
    b, s = input_ids.shape
    if attention_mask is None:
        attention_mask = jnp.ones((b, s), jnp.int32)
    x = vocab_parallel_embedding(params["embed"], input_ids, tp_axis).astype(config.dtype)

    cos, sin = rope_cos_sin(s, config.head_dim, config.rope_theta)
    bias = rope_attention_bias(attention_mask, config)

    if rng is None:
        if train and config.router_jitter:
            raise ValueError("train=True with router jitter needs an explicit rng")
        rng = jax.random.PRNGKey(0)
    layer_keys = jax.random.split(rng, config.n_layer)

    def scan_fn(carry, blk_and_key):
        blk, key = blk_and_key
        out, aux, z = _block(
            blk, carry, cos, sin, bias, key, config, tp_axis, ep_axis, train
        )
        return out, (aux, z)

    step = (jax.checkpoint(scan_fn, policy=remat_policy())
            if config.remat else scan_fn)
    x, (aux, z) = jax.lax.scan(step, x, (params["blocks"], layer_keys))
    return rms_norm(params["ln_f"], x, config.rms_eps), aux, z


def forward(params, input_ids, attention_mask, config,
            tp_axis=None, ep_axis=None, rng=None, train=False):
    """Logits (B, S, V/tp) — lm_head is column-parallel over tensor."""
    hidden, aux, z = forward_hidden(
        params, input_ids, attention_mask, config, tp_axis, ep_axis, rng, train
    )
    return column_parallel_linear(params["lm_head"], hidden, tp_axis), aux, z


def loss_fn(params, input_ids, attention_mask, labels, config,
            tp_axis=None, ep_axis=None, rng=None, train=True):
    if config.fused_ce:
        # fused Pallas CE on the (H, V/tp) column head in its native
        # layout (ops/fused_ce.py, weight_layout="hv") — no logits
        # buffer; the f-operator psum lives in the kernel's VJP
        from pipegoose_tpu.ops.fused_ce import fused_ce_shifted_loss

        hidden, aux, z = forward_hidden(
            params, input_ids, attention_mask, config, tp_axis, ep_axis,
            rng, train,
        )
        task = fused_ce_shifted_loss(
            hidden, params["lm_head"]["kernel"], labels, attention_mask,
            tp_axis, config.valid_vocab_size, weight_layout="hv",
        )
        return ExpertLoss(config.aux_loss_weight, config.z_loss_weight)(
            task, aux.mean(), z.mean()
        )
    logits, aux, z = forward(
        params, input_ids, attention_mask, config, tp_axis, ep_axis, rng, train
    )
    per_tok = vocab_parallel_cross_entropy(
        logits[:, :-1], labels[:, 1:], tp_axis, valid_size=config.valid_vocab_size
    )
    if attention_mask is not None:
        w = attention_mask[:, 1:].astype(per_tok.dtype)
        task = (per_tok * w).sum() / jnp.maximum(w.sum(), 1)
    else:
        task = per_tok.mean()
    # HF computes ONE load-balancing loss over all layers' gates jointly
    # (~O(1) when balanced); our scan yields per-layer losses, so take the
    # layer MEAN to keep router_aux_loss_coef on HF's scale
    return ExpertLoss(config.aux_loss_weight, config.z_loss_weight)(
        task, aux.mean(), z.mean()
    )


def _pp_prologue(
    input_ids, attention_mask, labels, config, n_microbatches, pipe_axis, rng,
    train, stage_layer_counts=None,
):
    """Shared pipeline setup for the GPipe and 1F1B Mixtral losses:
    validates the stage split, derives THIS stage's slice of the same
    L-layer router keys the dense path uses, splits microbatches, and
    builds the RoPE tables + per-microbatch attention bias (M-leading,
    ready as gpipe/1F1B side inputs).

    ``stage_layer_counts``: UNEVEN stages — the keys for stage p's live
    slots are ``layer_keys[offset_p : offset_p + n_p]`` (layer ORDER as
    in ``repartition_blocks``), padded to L_max; pad-slot keys are
    zeros and never reach a router (the masked scan skips the block)."""
    from pipegoose_tpu.nn.pipeline_parallel import microbatch as mb

    b, s = input_ids.shape
    if attention_mask is None:
        attention_mask = jnp.ones((b, s), jnp.int32)

    P_pipe = jax.lax.axis_size(pipe_axis)
    L = config.n_layer
    stage = jax.lax.axis_index(pipe_axis)

    if rng is None:
        if train and config.router_jitter:
            raise ValueError("train=True with router jitter needs an explicit rng")
        rng = jax.random.PRNGKey(0)
    layer_keys = jax.random.split(rng, L)  # (L, 2) — same keys as dense

    from pipegoose_tpu.nn.pipeline_parallel.partitioner import stage_n_valid

    n_valid = None
    if stage_layer_counts is not None:
        n_valid = stage_n_valid(stage_layer_counts, L, pipe_axis)  # validates
        counts_np = np.asarray(stage_layer_counts, np.int64)
        L_max = int(counts_np.max())
        offsets = jnp.asarray(
            np.concatenate([[0], np.cumsum(counts_np)[:-1]]), jnp.int32
        )
        keys_padded = jnp.concatenate(
            [layer_keys, jnp.zeros((L_max,) + layer_keys.shape[1:], layer_keys.dtype)]
        )
        local_keys = jax.lax.dynamic_slice_in_dim(
            keys_padded, offsets[stage], L_max, 0
        )
    else:
        if L % P_pipe:
            raise ValueError(
                f"n_layer={L} must be divisible by the pipe axis size {P_pipe}"
            )
        L_local = L // P_pipe
        local_keys = jax.lax.dynamic_slice_in_dim(
            layer_keys, stage * L_local, L_local, 0
        )

    mbs = mb.split(
        {"ids": input_ids, "mask": attention_mask, "labels": labels}, n_microbatches
    )
    cos, sin = rope_cos_sin(s, config.head_dim, config.rope_theta)
    side = {"bias": jax.vmap(lambda m: rope_attention_bias(m, config))(mbs["mask"])}
    return attention_mask, mbs, cos, sin, local_keys, L, side, n_valid


def _stage_scan(blocks, keys, h, bias, cos, sin, config, tp_axis, ep_axis,
                train, n_valid=None):
    """Scan this stage's local layer slice; returns (h, aux (L_local,),
    z (L_local,)). Shared by the GPipe and 1F1B stage functions.

    ``n_valid`` (runtime scalar): UNEVEN stages — slots >= n_valid are
    pad layers, genuinely skipped by ``lax.cond`` (zero aux/z, h passes
    through). Collective-safe for the same reason as
    ``masked_stage_scan``: the predicate varies only over the pipe
    axis, so all tensor/expert peers of a stage take the same branch."""

    def live(blk, key, hh):
        out, aux, z = _block(
            blk, hh, cos, sin, bias, key, config, tp_axis, ep_axis, train
        )
        return out, (aux.astype(jnp.float32), z.astype(jnp.float32))

    if n_valid is None:
        def scan_fn(carry, blk_key):
            blk, key = blk_key
            return live(blk, key, carry)

        h, (aux, z) = jax.lax.scan(scan_fn, h, (blocks, keys))
        return h, aux, z

    L_max = jax.tree_util.tree_leaves(blocks)[0].shape[0]

    def scan_fn(carry, xs):
        blk, key, i = xs
        return jax.lax.cond(
            i < n_valid,
            lambda hh: live(blk, key, hh),
            lambda hh: (hh, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32))),
            carry,
        )

    h, (aux, z) = jax.lax.scan(scan_fn, h, (blocks, keys, jnp.arange(L_max)))
    return h, aux, z


def loss_fn_pp(
    params: dict,
    input_ids: jax.Array,
    attention_mask: Optional[jax.Array],
    labels: jax.Array,
    config: MixtralConfig,
    n_microbatches: int,
    tp_axis: Optional[str] = None,
    pipe_axis: str = "pipe",
    ep_axis: Optional[str] = None,
    rng: Optional[jax.Array] = None,
    train: bool = True,
    stage_layer_counts=None,
) -> jax.Array:
    """Pipeline-parallel Mixtral loss: the 4D TP x PP x DP x EP
    composition (BASELINE config 5 shape; the reference's group layout
    supports it at parallel_context.py:173-198 but never demonstrates it
    end-to-end).

    ``stage_layer_counts``: UNEVEN stages exactly as in
    ``bloom.loss_fn_pp`` — ``params["blocks"]`` must carry the padded
    ``repartition_blocks`` layout; router keys follow the same layer
    order (see ``_pp_prologue``).

    Structure mirrors bloom.loss_fn_pp (vectorized embed -> compiled
    GPipe over the pipe-sharded block stack -> vectorized head) plus the
    MoE-specific parts:
    - per-stage router aux/z losses ride gpipe's ``with_aux``
      accumulator (valid microbatches only) and are combined across the
      pipe axis with an identity-backward psum — each rank's router
      gradients stay local;
    - per-layer router RNG: every rank derives the full L-layer key
      array from ``rng`` and slices its own stage's rows, so routing
      matches the dense path exactly regardless of pp size;
    - aux/z are averaged over layers x microbatches, keeping
      ``router_aux_loss_coef`` on HF's scale (dense ``loss_fn`` takes
      the layer mean; with M=1 the two coincide exactly).
    """
    from pipegoose_tpu.distributed.functional import reduce_from_tensor_group
    from pipegoose_tpu.nn.pipeline_parallel.pipeline import gpipe, last_stage_value

    M = n_microbatches
    attention_mask, mbs, cos, sin, local_keys, L, side, n_valid = _pp_prologue(
        input_ids, attention_mask, labels, config, M, pipe_axis, rng, train,
        stage_layer_counts,
    )

    h0 = jax.vmap(
        lambda ids: vocab_parallel_embedding(params["embed"], ids, tp_axis).astype(
            config.dtype
        )
    )(mbs["ids"])

    def stage_fn(blocks_and_keys, h, side):
        blocks, keys = blocks_and_keys
        h, aux, z = _stage_scan(
            blocks, keys, h, side["bias"], cos, sin, config, tp_axis, ep_axis,
            train, n_valid,
        )
        return h, (aux.sum(), z.sum())

    outs, (aux_sum, z_sum) = gpipe(
        stage_fn,
        (params["blocks"], local_keys),
        h0,
        side_inputs=side,
        axis_name=pipe_axis,
        remat=config.remat,
        with_aux=True,
    )

    def head_one(h, mask, labels):
        h = rms_norm(params["ln_f"], h, config.rms_eps)
        if config.fused_ce:
            from pipegoose_tpu.ops.fused_ce import fused_ce_shifted_sums

            return fused_ce_shifted_sums(
                h, params["lm_head"]["kernel"], labels, mask, tp_axis,
                config.valid_vocab_size, weight_layout="hv",
            )
        logits = column_parallel_linear(params["lm_head"], h, tp_axis)
        per_tok = vocab_parallel_cross_entropy(
            logits[:, :-1], labels[:, 1:], tp_axis, valid_size=config.valid_vocab_size
        )
        w = mask[:, 1:].astype(per_tok.dtype)
        return (per_tok * w).sum(), w.sum()

    tot, cnt = jax.vmap(head_one)(outs, mbs["mask"], mbs["labels"])
    task = last_stage_value(tot.sum() / jnp.maximum(cnt.sum(), 1), pipe_axis)

    # identity-backward psum over pipe: forward-replicated totals, local
    # gradients per rank (the psum-transpose hazard)
    aux_mean = reduce_from_tensor_group(aux_sum, pipe_axis) / (L * M)
    z_mean = reduce_from_tensor_group(z_sum, pipe_axis) / (L * M)
    return ExpertLoss(config.aux_loss_weight, config.z_loss_weight)(
        task, aux_mean, z_mean
    )


def specs(params: dict, tp_axis: str = "tensor", ep_axis: str = "expert") -> dict:
    """4D PartitionSpecs: attention q/k/v column + o row over tensor,
    experts over expert with FFN over tensor, lm_head column, embedding
    vocab-sharded; stacked n_layer dim free for the pipe axis."""
    from jax.sharding import PartitionSpec as P

    from pipegoose_tpu.nn.parallel import spec_tree

    t, e = tp_axis, ep_axis

    def spec_fn(path, x):
        if "attn/q" in path or "attn/k" in path or "attn/v" in path:
            return P(None, None, t)
        if "attn/o" in path:
            return P(None, t, None)
        if "moe/w1" in path or "moe/w3" in path:
            return P(None, e, None, t)
        if "moe/w2" in path:
            return P(None, e, t, None)
        if "router" in path:
            return P()
        if "embed/weight" in path:
            return P(t, None)
        if "lm_head" in path:
            return P(None, t)
        return P()

    return spec_tree(params, spec_fn)


def loss_fn_1f1b(
    params: dict,
    input_ids: jax.Array,
    attention_mask: Optional[jax.Array],
    labels: jax.Array,
    config: MixtralConfig,
    n_microbatches: int,
    tp_axis: Optional[str] = None,
    pipe_axis: str = "pipe",
    ep_axis: Optional[str] = None,
    rng: Optional[jax.Array] = None,
    train: bool = True,
    stage_layer_counts=None,
) -> jax.Array:
    """Mixtral pipeline loss on the 1F1B runtime: same value/gradients
    as :func:`loss_fn_pp` with O(stages) activation memory. Router aux/z
    losses ride ``one_f_one_b``'s ``with_aux`` channel: each stage's
    pre-weighted aux scalar seeds its OWN backward, so router gradients
    never cross stages, and the per-rank loss sums combine with one
    psum over the pipe axis. ``stage_layer_counts``: UNEVEN stages as in
    :func:`loss_fn_pp`."""
    from pipegoose_tpu.nn.pipeline_parallel.pipeline import (
        manual_grads_loss,
        one_f_one_b,
    )

    M = n_microbatches
    attention_mask, mbs, cos, sin, local_keys, L, side, n_valid = _pp_prologue(
        input_ids, attention_mask, labels, config, M, pipe_axis, rng, train,
        stage_layer_counts,
    )
    side = {**side, "labels": mbs["labels"], "mask": mbs["mask"]}
    inv_count = 1.0 / jnp.maximum(attention_mask[:, 1:].sum().astype(jnp.float32), 1)

    def stage_fn(blocks, h, side):
        # local_keys is closed over (constant for AD): integer key
        # arrays must not enter the differentiated stage_params pytree
        h, aux, z = _stage_scan(
            blocks, local_keys, h, side["bias"], cos, sin,
            config, tp_axis, ep_axis, train, n_valid,
        )
        aux_scalar = (
            config.aux_loss_weight * aux.sum() + config.z_loss_weight * z.sum()
        ) / (L * M)
        return h, aux_scalar.astype(jnp.float32)

    def head_fn(hp, h, side):
        h = rms_norm(hp["ln_f"], h, config.rms_eps)
        if config.fused_ce:
            from pipegoose_tpu.ops.fused_ce import fused_ce_shifted_sums

            tot, _ = fused_ce_shifted_sums(
                h, hp["lm_head"]["kernel"], side["labels"], side["mask"],
                tp_axis, config.valid_vocab_size, weight_layout="hv",
            )
            return (tot * inv_count).astype(jnp.float32)
        logits = column_parallel_linear(hp["lm_head"], h, tp_axis)
        per_tok = vocab_parallel_cross_entropy(
            logits[:, :-1], side["labels"][:, 1:], tp_axis,
            valid_size=config.valid_vocab_size,
        )
        w = side["mask"][:, 1:].astype(per_tok.dtype)
        return ((per_tok * w).sum() * inv_count).astype(jnp.float32)

    def run(params):
        h0, embed_vjp = jax.vjp(
            lambda ep: jax.vmap(
                lambda ids: vocab_parallel_embedding(ep, ids, tp_axis).astype(
                    config.dtype
                )
            )(mbs["ids"]),
            params["embed"],
        )
        head_params = {"ln_f": params["ln_f"], "lm_head": params["lm_head"]}
        loss_local, dh0, d_blocks, d_head = one_f_one_b(
            stage_fn, params["blocks"], head_fn, head_params,
            h0, side, pipe_axis, with_aux=True,
        )
        (d_embed,) = embed_vjp(dh0)
        # every rank's aux rode its local loss sum; the task part lives
        # on the last rank — one psum combines both
        loss = jax.lax.psum(loss_local, pipe_axis)
        grads = {
            "embed": d_embed,
            "blocks": d_blocks,
            "ln_f": d_head["ln_f"],
            "lm_head": d_head["lm_head"],
        }
        return loss, grads

    return manual_grads_loss(run, params)


def upcycle_from_llama(
    llama_params: dict,
    llama_config,
    num_experts: int,
    top_k: int = 2,
    key: Optional[jax.Array] = None,
    jitter: float = 0.0,
    **config_overrides,
):
    """Sparse-upcycle a dense Llama into a Mixtral-style MoE: every
    expert starts as a copy of the dense SwiGLU MLP (gate/up/down map
    exactly onto w1/w3/w2), plus a fresh router gate.

    This is the "turn this model into MoE" capability beyond the
    framework's own BLOOM (the reference's Experts wraps arbitrary HF
    MLP modules, experts.py:55-68; its ExpertParallel swaps dense MLPs
    for expert copies, expert_parallel.py:53-80). With ``jitter=0`` the
    upcycled model's FORWARD equals the dense Llama exactly — identical
    experts and normalized top-k gates make routing irrelevant — which
    the test pins; ``jitter`` perturbs experts so they diverge in
    training.

    Returns (MixtralConfig, params) ready for every Mixtral parallel
    form (TP/EP/PP/ZeRO, generation).
    """
    cfg = MixtralConfig(
        vocab_size=llama_config.vocab_size,
        hidden_size=llama_config.hidden_size,
        intermediate_size=llama_config.intermediate_size,
        n_layer=llama_config.n_layer,
        n_head=llama_config.n_head,
        n_kv_head=llama_config.n_kv_head,
        rope_theta=llama_config.rope_theta,
        rms_eps=llama_config.rms_eps,
        num_experts=num_experts,
        top_k=top_k,
        dtype=llama_config.dtype,
        remat=llama_config.remat,
        use_flash=llama_config.use_flash,
        valid_vocab_size=llama_config.valid_vocab_size,
        **config_overrides,
    )
    if key is None:
        key = jax.random.PRNGKey(0)
    kj, kr = jax.random.split(key)

    blocks = dict(llama_params["blocks"])
    mlp = blocks.pop("mlp")
    E = num_experts

    def tile(x):
        return jnp.broadcast_to(x[:, None], (x.shape[0], E) + x.shape[1:])

    moe = {
        "w1": {"kernel": tile(mlp["gate"]["kernel"])},
        "w3": {"kernel": tile(mlp["up"]["kernel"])},
        "w2": {"kernel": tile(mlp["down"]["kernel"])},
    }
    if jitter:
        leaves, treedef = jax.tree_util.tree_flatten(moe)
        keys = jax.random.split(kj, len(leaves))
        leaves = [
            x * (1 + jitter * jax.random.normal(k, x.shape, x.dtype))
            for x, k in zip(leaves, keys)
        ]
        moe = jax.tree_util.tree_unflatten(treedef, leaves)
    blocks["moe"] = moe
    blocks["router"] = {
        "gate": {
            "kernel": (
                jax.random.normal(kr, (cfg.n_layer, cfg.hidden_size, E)) * 0.02
            ).astype(cfg.dtype)
        }
    }

    lm_head = llama_params.get("lm_head")
    if lm_head is None:  # tied checkpoint: materialize the head
        lm_head = {"kernel": llama_params["embed"]["weight"].T}
    params = {
        "embed": llama_params["embed"],
        "blocks": blocks,
        "ln_f": llama_params["ln_f"],
        "lm_head": lm_head,
    }
    return cfg, params


def pp_specs(
    params: dict,
    tp_axis: str = "tensor",
    ep_axis: str = "expert",
    pipe_axis: str = "pipe",
) -> dict:
    """4D specs with the stacked n_layer dim of blocks sharded over the
    pipe axis (stage assignment as a PartitionSpec, like bloom.pp_specs)."""
    from pipegoose_tpu.nn.pipeline_parallel.pipeline import pipe_stage_specs

    sp = specs(params, tp_axis, ep_axis)
    sp["blocks"] = pipe_stage_specs(sp["blocks"], pipe_axis)
    return sp


# -- sequence-parallel composition ------------------------------------------

def _attention_sp(blk, x, config, tp_axis, sp_axis, pad_mask_local,
                  variant: str = "ring"):
    """RoPE/GQA attention with the sequence sharded over ``sp_axis``,
    heads over ``tp_axis``. RoPE is applied at GLOBAL positions — each
    rank slices the full cos/sin tables at its chunk offset
    (rope_scaling honored via the shared rope_cos_sin) — BEFORE any
    head exchange, since RoPE travels with tokens, not heads.

    ``variant="ring"``: K/V rotate over the sp ring. GQA is NATIVE on
    both ring paths: the nkv-headed K/V ride the ring — the flash chunk
    kernels read them via grouped index maps, the dense-math ring
    (sliding-window configs, or use_flash=False) via a grouped einsum.
    Hop bytes shrink by g either way.

    ``variant="ulysses"``: two all_to_alls re-shard seq -> heads so each
    device runs FULL-sequence attention on nh_l/sp query heads and
    nkv_l/sp kv heads (the grouped-head mapping stays consistent because
    nh_l = g * nkv_l splits uniformly); needs both head counts divisible
    by the sp size — use ring otherwise (it has no such constraint).

    Shared by Mixtral and Llama (llama.loss_fn_sp imports this)."""
    from pipegoose_tpu.nn.sequence_parallel.ring_attention import (
        make_causal_alibi_bias_fn,
        ring_attention,
        ring_flash_attention,
    )

    if variant not in ("ring", "ulysses"):
        raise ValueError(f"unknown SP variant {variant!r} (ring, ulysses)")
    b, s_local, _ = x.shape
    hd = config.head_dim
    tp = jax.lax.axis_size(tp_axis) if tp_axis else 1
    nh_l, nkv_l = config.n_head // tp, config.n_kv_head // tp

    q = column_parallel_linear(blk["q"], x, tp_axis).reshape(b, s_local, nh_l, hd)
    k = column_parallel_linear(blk["k"], x, tp_axis).reshape(b, s_local, nkv_l, hd)
    v = column_parallel_linear(blk["v"], x, tp_axis).reshape(b, s_local, nkv_l, hd)

    sp = jax.lax.axis_size(sp_axis) if sp_axis else 1
    rank = jax.lax.axis_index(sp_axis) if sp_axis else 0
    cos_f, sin_f = rope_cos_sin(
        sp * s_local, hd, config.rope_theta,
        getattr(config, "rope_scaling", None),
    )
    cos = jax.lax.dynamic_slice_in_dim(cos_f, rank * s_local, s_local, 0)
    sin = jax.lax.dynamic_slice_in_dim(sin_f, rank * s_local, s_local, 0)
    q, k = apply_rope(q, k, cos, sin)

    window = getattr(config, "sliding_window", None)
    if variant == "ulysses":
        from pipegoose_tpu.nn.sequence_parallel.ulysses import (
            ulysses_causal_attention,
        )

        ctx = ulysses_causal_attention(
            q, k, v, sp_axis, pad_mask_local,
            window=window, use_flash=config.use_flash,
        )
    elif config.use_flash and window is None:
        # native GQA: nkv-headed K/V ride the ring
        ctx = ring_flash_attention(
            q, k, v, sp_axis, alibi_slopes=None, kv_side=pad_mask_local
        )
    else:
        # no ALiBi term (RoPE carries position in q/k); window is a
        # value-based position mask in the shared block bias
        bias_fn = make_causal_alibi_bias_fn(s_local, sp_axis, window=window)
        ctx = ring_attention(q, k, v, sp_axis, bias_fn, kv_side=pad_mask_local)
    ctx = ctx.astype(x.dtype).reshape(b, s_local, nh_l * hd)
    return row_parallel_linear(blk["o"], ctx, tp_axis)


def _sp_block(blk, x, key, config, tp_axis, ep_axis, sp_axis,
              pad_mask_local, train, variant="ring"):
    h = rms_norm(blk["ln_1"], x, config.rms_eps)
    x = x + _attention_sp(
        blk["attn"], h, config, tp_axis, sp_axis, pad_mask_local, variant
    )
    h = rms_norm(blk["ln_2"], x, config.rms_eps)

    router = config.router()
    flat = h.reshape(-1, h.shape[-1])
    routing = router(blk["router"], flat, key=key, train=train)
    y = moe_layer(
        blk["moe"], h, routing, axis_name=ep_axis,
        tp_axis=tp_axis, act=None, mlp_fn=_swiglu_experts,
    )
    return x + y, routing.aux_loss, routing.z_loss


def loss_fn_sp(
    params: dict,
    input_ids: jax.Array,  # (B, S_local) — sequence sharded over sp_axis
    attention_mask: Optional[jax.Array],
    labels: jax.Array,
    config: MixtralConfig,
    tp_axis: Optional[str] = None,
    ep_axis: Optional[str] = None,
    sp_axis: str = "seq",
    rng=None,
    train: bool = True,
    variant: str = "ring",
) -> jax.Array:
    """Sequence-parallel Mixtral loss: ring (or, with
    ``variant="ulysses"``, all_to_all head-exchange) attention over
    ``sp_axis`` with RoPE at global positions; MoE routing/dispatch
    stays on each rank's local tokens (composes with ``ep_axis``
    all_to_all as usual).
    This is the long-context path for the RoPE/GQA families — the ring
    machinery previously served only BLOOM (VERDICT r2 weak #4).

    Loss terms: the task CE uses the cross-chunk target shift
    (nn/sequence_parallel/targets.py); z-loss is a per-token mean, so the
    rank average IS the dense value (equal chunks); the router aux loss
    is nonlinear in the token split — the rank average is the standard
    Megatron-style approximation (zero-weight it for strict equivalence
    tests, same policy as loss_fn_pp with M>1).

    Grad sync for replicated params: ``grad_sync_axes=(("seq","sum"),)``.
    """
    from pipegoose_tpu.distributed.functional import reduce_from_tensor_group
    from pipegoose_tpu.nn.sequence_parallel.targets import sp_shifted_targets

    b, s_local = input_ids.shape
    if attention_mask is None:
        attention_mask = jnp.ones((b, s_local), jnp.int32)

    x = vocab_parallel_embedding(params["embed"], input_ids, tp_axis).astype(
        config.dtype
    )
    if rng is None:
        if train and config.router_jitter:
            raise ValueError("train=True with router jitter needs an explicit rng")
        rng = jax.random.PRNGKey(0)
    layer_keys = jax.random.split(rng, config.n_layer)

    def scan_fn(carry, blk_key):
        blk, key = blk_key
        out, aux, z = _sp_block(
            blk, carry, key, config, tp_axis, ep_axis, sp_axis,
            attention_mask, train, variant,
        )
        return out, (aux, z)

    step = (jax.checkpoint(scan_fn, policy=remat_policy())
            if config.remat else scan_fn)
    x, (aux, z) = jax.lax.scan(step, x, (params["blocks"], layer_keys))

    x = rms_norm(params["ln_f"], x, config.rms_eps)
    shifted_labels, shifted_w = sp_shifted_targets(
        labels, attention_mask, sp_axis
    )
    if config.fused_ce:
        from pipegoose_tpu.ops.fused_ce import fused_ce_masked_sums

        tot, cnt = fused_ce_masked_sums(
            x, params["lm_head"]["kernel"], shifted_labels, shifted_w,
            tp_axis, config.valid_vocab_size, weight_layout="hv",
        )
    else:
        logits = column_parallel_linear(params["lm_head"], x, tp_axis)
        per_tok = vocab_parallel_cross_entropy(
            logits, shifted_labels, tp_axis,
            valid_size=config.valid_vocab_size,
        )
        w = shifted_w.astype(per_tok.dtype)
        tot, cnt = (per_tok * w).sum(), w.sum()
    count = jax.lax.psum(cnt, sp_axis)
    # identity-backward combines: values become global means, gradients
    # stay local (summed later by grad_sync_axes)
    task = reduce_from_tensor_group(
        tot / jnp.maximum(count, 1), sp_axis
    )
    sp = jax.lax.axis_size(sp_axis)
    aux_t = reduce_from_tensor_group(aux.mean() / sp, sp_axis)
    z_t = reduce_from_tensor_group(z.mean() / sp, sp_axis)
    return ExpertLoss(config.aux_loss_weight, config.z_loss_weight)(
        task, aux_t, z_t
    )


def loss_fn_pp_sp(
    params: dict,
    input_ids: jax.Array,  # (B, S_local) — sequence sharded over sp_axis
    attention_mask: Optional[jax.Array],
    labels: jax.Array,
    config: MixtralConfig,
    n_microbatches: int,
    tp_axis: Optional[str] = None,
    ep_axis: Optional[str] = None,
    pipe_axis: str = "pipe",
    sp_axis: str = "seq",
    rng=None,
    train: bool = True,
) -> jax.Array:
    """Pipeline x sequence parallel Mixtral: ring attention (RoPE at
    global positions) runs INSIDE compiled GPipe stages, with MoE
    routing on each rank's local tokens — the long-context + deep-model
    composition for the RoPE/GQA/MoE family (bloom.loss_fn_pp_sp is the
    ALiBi analog). All sp peers of a stage advance in lockstep (uniform
    SPMD), so the ring's ppermutes and the pipeline's ppermutes compose
    without any scheduling interaction.

    Loss terms follow loss_fn_sp: cross-chunk target shift; z is exact
    (per-token mean over equal chunks); aux is the Megatron-style rank/
    microbatch average — zero-weight it for strict equivalence tests.

    Grad sync: ``grad_sync_axes=(("pipe","sum"), ("seq","sum"))`` (+
    ``("expert","mean")`` when expert-data replicas carry different
    tokens)."""
    from pipegoose_tpu.distributed.functional import reduce_from_tensor_group
    from pipegoose_tpu.nn.pipeline_parallel import microbatch as mb
    from pipegoose_tpu.nn.pipeline_parallel.pipeline import gpipe, last_stage_value
    from pipegoose_tpu.nn.sequence_parallel.targets import sp_shifted_targets

    M = n_microbatches
    b, s_local = input_ids.shape
    if attention_mask is None:
        attention_mask = jnp.ones((b, s_local), jnp.int32)

    P_pipe = jax.lax.axis_size(pipe_axis)
    L = config.n_layer
    if L % P_pipe:
        raise ValueError(
            f"n_layer={L} must be divisible by the pipe axis size {P_pipe}"
        )
    L_local = L // P_pipe
    stage = jax.lax.axis_index(pipe_axis)
    if rng is None:
        if train and config.router_jitter:
            raise ValueError("train=True with router jitter needs an explicit rng")
        rng = jax.random.PRNGKey(0)
    layer_keys = jax.random.split(rng, L)
    local_keys = jax.lax.dynamic_slice_in_dim(layer_keys, stage * L_local, L_local, 0)

    mbs = mb.split(
        {"ids": input_ids, "mask": attention_mask, "labels": labels}, M
    )
    h0 = jax.vmap(
        lambda ids: vocab_parallel_embedding(params["embed"], ids, tp_axis).astype(
            config.dtype
        )
    )(mbs["ids"])
    side = {"mask": mbs["mask"]}

    def stage_fn(blocks_and_keys, h, side):
        blocks, keys = blocks_and_keys

        def scan_fn(carry, blk_key):
            blk, key = blk_key
            out, aux, z = _sp_block(
                blk, carry, key, config, tp_axis, ep_axis, sp_axis,
                side["mask"], train,
            )
            return out, (aux, z)

        h, (aux, z) = jax.lax.scan(scan_fn, h, (blocks, keys))
        return h, (aux.sum(), z.sum())

    outs, (aux_sum, z_sum) = gpipe(
        stage_fn,
        (params["blocks"], local_keys),
        h0,
        side_inputs=side,
        axis_name=pipe_axis,
        remat=config.remat,
        with_aux=True,
    )

    def head_one(h, mask_mb, labels_mb):
        h = rms_norm(params["ln_f"], h, config.rms_eps)
        sl, sw = sp_shifted_targets(labels_mb, mask_mb, sp_axis)
        if config.fused_ce:
            from pipegoose_tpu.ops.fused_ce import fused_ce_masked_sums

            return fused_ce_masked_sums(
                h, params["lm_head"]["kernel"], sl, sw, tp_axis,
                config.valid_vocab_size, weight_layout="hv",
            )
        logits = column_parallel_linear(params["lm_head"], h, tp_axis)
        per_tok = vocab_parallel_cross_entropy(
            logits, sl, tp_axis, valid_size=config.valid_vocab_size
        )
        w = sw.astype(per_tok.dtype)
        return (per_tok * w).sum(), w.sum()

    tot, cnt = jax.vmap(head_one)(outs, mbs["mask"], mbs["labels"])
    count = jax.lax.psum(cnt.sum(), sp_axis)
    task_local = reduce_from_tensor_group(
        tot.sum() / jnp.maximum(count, 1), sp_axis
    )
    task = last_stage_value(task_local, pipe_axis)

    sp = jax.lax.axis_size(sp_axis)
    aux_mean = reduce_from_tensor_group(
        reduce_from_tensor_group(aux_sum, pipe_axis), sp_axis
    ) / (L * M * sp)
    z_mean = reduce_from_tensor_group(
        reduce_from_tensor_group(z_sum, pipe_axis), sp_axis
    ) / (L * M * sp)
    return ExpertLoss(config.aux_loss_weight, config.z_loss_weight)(
        task, aux_mean, z_mean
    )


# -- generation (KV cache) ---------------------------------------------------

def init_cache(config: MixtralConfig, batch: int, max_len: int) -> dict:
    L, nkv, hd = config.n_layer, config.n_kv_head, config.head_dim
    shape = (L, batch, max_len, nkv, hd)
    return {"k": jnp.zeros(shape, config.dtype), "v": jnp.zeros(shape, config.dtype)}


def _attn_cached(blk, x, k_cache, v_cache, start, cos_full, sin_full, config):
    """S new tokens against cache[:start]+selves (GQA, RoPE at absolute
    positions). Returns (out, k_cache, v_cache)."""
    b, s, _ = x.shape
    hd = config.head_dim
    nh, nkv = config.n_head, config.n_kv_head
    groups = nh // nkv
    max_len = k_cache.shape[1]

    q = column_parallel_linear(blk["q"], x, None).reshape(b, s, nh, hd)
    k = column_parallel_linear(blk["k"], x, None).reshape(b, s, nkv, hd)
    v = column_parallel_linear(blk["v"], x, None).reshape(b, s, nkv, hd)

    cos = jax.lax.dynamic_slice_in_dim(cos_full, start, s, 0)
    sin = jax.lax.dynamic_slice_in_dim(sin_full, start, s, 0)
    q, k = apply_rope(q, k, cos, sin)

    k_cache = jax.lax.dynamic_update_slice(k_cache, k.astype(k_cache.dtype), (0, start, 0, 0))
    v_cache = jax.lax.dynamic_update_slice(v_cache, v.astype(v_cache.dtype), (0, start, 0, 0))

    key_pos = jnp.arange(max_len)
    q_pos = start + jnp.arange(s)
    keep = key_pos[None, :] <= q_pos[:, None]
    window = getattr(config, "sliding_window", None)  # shared with Llama decode
    if window is not None:
        keep = keep & (q_pos[:, None] - key_pos[None, :] < window)
    bias = jnp.where(keep[None, None, None], 0.0, NEG_INF)  # (1,1,1,S,max_len)

    # grouped einsum against the nkv-wide cache: no group-repeated K/V
    # copies in the decode hot loop (GQA's whole point)
    qg = q.reshape(b, s, nkv, groups, hd)
    scores = jnp.einsum("bqkgd,bmkd->bkgqm", qg, k_cache,
                        preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(scores * (hd**-0.5) + bias, axis=-1).astype(x.dtype)
    ctx = jnp.einsum("bkgqm,bmkd->bqkgd", probs, v_cache,
                     preferred_element_type=jnp.float32)
    ctx = ctx.astype(x.dtype).reshape(b, s, nh * hd)
    return row_parallel_linear(blk["o"], ctx, None), k_cache, v_cache


def forward_cached(params, ids, cache, start, config):
    """(logits at last position, new cache); deterministic routing
    (no-drop capacity, no jitter — inference)."""
    x = vocab_parallel_embedding(params["embed"], ids, None).astype(config.dtype)
    max_len = cache["k"].shape[2]
    cos_full, sin_full = rope_cos_sin(max_len, config.head_dim, config.rope_theta)

    def scan_fn(carry, blk_and_cache):
        h = carry
        blk, kc, vc = blk_and_cache
        ln1 = rms_norm(blk["ln_1"], h, config.rms_eps)
        attn, kc, vc = _attn_cached(
            blk["attn"], ln1, kc, vc, start, cos_full, sin_full, config
        )
        h = h + attn
        ln2 = rms_norm(blk["ln_2"], h, config.rms_eps)
        router = config.router()
        flat = ln2.reshape(-1, ln2.shape[-1])
        routing = router(blk["router"], flat, train=False)
        h = h + moe_layer(blk["moe"], ln2, routing, axis_name=None,
                          tp_axis=None, mlp_fn=_swiglu_experts)
        return h, (kc, vc)

    x, (k_new, v_new) = jax.lax.scan(scan_fn, x, (params["blocks"], cache["k"], cache["v"]))
    x = rms_norm(params["ln_f"], x, config.rms_eps)
    logits = column_parallel_linear(params["lm_head"], x[:, -1:], None)[:, 0]
    return logits, {"k": k_new, "v": v_new}


def generate(
    params: dict,
    input_ids: jax.Array,
    config: MixtralConfig,
    max_new_tokens: int,
    temperature: float = 0.0,
    rng=None,
    eos_token_id=None,
) -> jax.Array:
    """Greedy/sampled decoding with a GQA KV cache — shared decode
    driver (models/_decode.py), same EOS semantics as BLOOM's generate."""
    from pipegoose_tpu.models._decode import autoregressive_generate, vocab_mask_for

    return autoregressive_generate(
        forward_cached, init_cache, params, input_ids, config,
        max_new_tokens, temperature, rng, eos_token_id,
        logits_mask=vocab_mask_for(config),
    )
