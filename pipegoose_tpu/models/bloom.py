"""BLOOM, TPU-native.

The reference wraps HuggingFace's torch ``BloomForCausalLM`` and rewrites
its modules in place (pipegoose/nn/tensor_parallel/tensor_parallel.py:18-82);
BLOOM is its supported model family (reference README.md:19). Here BLOOM
is implemented from scratch in pure JAX, designed for the MXU and for
4D sharding:

- per-layer params are STACKED on a leading ``n_layer`` dim and the
  forward scans over them (``lax.scan`` + optional ``jax.checkpoint``):
  one compiled block regardless of depth, and pipeline stages slice the
  leading dim instead of torch.fx graph surgery
  (vs reference partitioner.py:29-219).
- attention/MLP use the tensor-parallel layer functions, so the same
  code runs single-device (``tp_axis=None``) or inside ``shard_map``
  with head- and vocab-sharded params.
- matmuls accumulate in fp32 (``preferred_element_type``), activations
  can be bf16; softmax and layernorm stats are always fp32.

Semantics match HF ``modeling_bloom`` (gelu-tanh MLP, fused qkv in
[n_head, 3, head_dim] layout, alibi from mask positions, fp32 softmax,
pre-LN residuals with ``apply_residual_connection_post_layernorm=False``)
so HF checkpoints load exactly; parity is tested against the torch
implementation in tests/models/test_bloom.py.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from pipegoose_tpu.nn.parallel_mapping import (
    Column,
    ParallelMapping,
    Row,
    Vocab,
)
from pipegoose_tpu.nn.tensor_parallel.layers import (
    column_parallel_linear,
    layer_norm,
    row_parallel_linear,
    vocab_parallel_cross_entropy,
    vocab_parallel_embedding,
)
from pipegoose_tpu.ops.flash_attention import remat_policy

NEG_INF = -1e9


@dataclasses.dataclass(frozen=True)
class BloomConfig:
    vocab_size: int = 250880
    hidden_size: int = 64
    n_layer: int = 2
    n_head: int = 8
    layer_norm_epsilon: float = 1e-5
    initializer_range: float = 0.02
    # dtype of activations/params at run time; f32 for parity tests,
    # bf16 for TPU throughput
    dtype: Any = jnp.float32
    # rematerialize each block's activations in backward (HBM for FLOPs)
    remat: bool = False
    # selective-remat policy under remat=True: None saves the block's
    # input and nothing it computes; "dots" saves matmul outputs except
    # batch-dim ones
    # (jax.checkpoint_policies.dots_with_no_batch_dims_saveable);
    # "attn" saves only the per-block attention outputs
    # (checkpoint_name "attn_out", present on every attention variant)
    # so backward never re-runs attention — between full remat (slow,
    # tiny HBM) and no remat (fast, 2x HBM). Under all three a block
    # also keeps the flash kernel's result and logsumexp where it ran
    # one (_remat_wrap), so backward never re-runs THAT forward
    remat_policy: Optional[str] = None
    # fused Pallas flash attention (ops/flash_attention.py): causal+alibi,
    # padding masks supported via the kernel's kv_pos/kv_neg bias inputs
    use_flash: bool = False
    # set when the embedding was padded for TP divisibility (pad_for_tp):
    # the true vocab size; padded logit slots are masked out of the CE
    valid_vocab_size: Optional[int] = None
    # chunk the loss over the sequence so the (B, S, V) fp32 logits
    # buffer (8 GB at bench shapes) never materializes — backward
    # rematerializes per chunk (nn/tensor_parallel/layers.py:
    # chunked_ce_sums). None = plain full-logits path.
    ce_chunks: Optional[int] = None
    # fused Pallas CE (ops/fused_ce.py): the logits buffer never exists
    # in HBM at all, forward or backward, with no chunk recompute —
    # strictly dominates ce_chunks when the kernel is available; takes
    # precedence over it
    fused_ce: bool = False
    # ring collective-matmul overlap (nn/tensor_parallel/overlap.py):
    # the dense/hybrid train path keeps activations TOKEN-SHARDED over
    # the tensor axis between blocks and decomposes the column gather /
    # row reduce into ppermute steps interleaved with partial matmuls,
    # so TP comm hides behind compute (and activations shrink by 1/tp).
    # Training-path flag: generate/serving and the PP/SP compositions
    # ignore it. Requires seq % tp == 0.
    overlap_tp: bool = False

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.n_head

    @classmethod
    def bloom_560m(cls, **kw) -> "BloomConfig":
        return cls(vocab_size=250880, hidden_size=1024, n_layer=24, n_head=16, **kw)


def _remat_wrap(fn, config):
    """``jax.checkpoint`` honoring ``config.remat_policy`` (caller gates
    on ``config.remat``). Under every policy the block keeps what the
    flash kernel left for its backward (``flash_attention.
    RESIDUAL_NAMES``: its result and logsumexp, ~(B,S,H) a layer), so
    backward never re-runs the forward kernel; a block that calls no
    flash kernel has nothing by those names."""
    policies = jax.checkpoint_policies
    extra = {
        "dots": policies.dots_with_no_batch_dims_saveable,
        # the attention outputs as well (checkpoint_name "attn_out", set
        # on every _attention/_attention_sp branch): backward recomputes
        # the cheap elementwise/matmul parts but never re-runs attention
        # of any kind — for ~(B,S,H) x n_layer extra HBM
        "attn": policies.save_only_these_names("attn_out"),
    }.get(getattr(config, "remat_policy", None))
    return jax.checkpoint(fn, policy=remat_policy(extra))


# -- init ------------------------------------------------------------------

def init_params(config: BloomConfig, key: jax.Array) -> dict:
    """Random init matching HF's scheme (normal(0, initializer_range) for
    dense/embedding, zeros bias, ones/zeros layernorm)."""
    h, v, L = config.hidden_size, config.vocab_size, config.n_layer
    std = config.initializer_range
    dt = config.dtype
    ks = jax.random.split(key, 6)

    def dense(k, shape):
        return (jax.random.normal(k, shape) * std).astype(dt)

    def ln():
        return {"scale": jnp.ones(h, dt), "bias": jnp.zeros(h, dt)}

    def ln_stack():
        return {"scale": jnp.ones((L, h), dt), "bias": jnp.zeros((L, h), dt)}

    return {
        "embed": {"weight": dense(ks[0], (v, h))},
        "embed_ln": ln(),
        "blocks": {
            "ln_1": ln_stack(),
            "attn": {
                "qkv": {
                    "kernel": dense(ks[1], (L, h, 3 * h)),
                    "bias": jnp.zeros((L, 3 * h), dt),
                },
                "out": {
                    "kernel": dense(ks[2], (L, h, h)),
                    "bias": jnp.zeros((L, h), dt),
                },
            },
            "ln_2": ln_stack(),
            "mlp": {
                "up": {
                    "kernel": dense(ks[3], (L, h, 4 * h)),
                    "bias": jnp.zeros((L, 4 * h), dt),
                },
                "down": {
                    "kernel": dense(ks[4], (L, 4 * h, h)),
                    "bias": jnp.zeros((L, h), dt),
                },
            },
        },
        "ln_f": {"scale": jnp.ones(h, dt), "bias": jnp.zeros(h, dt)},
    }


# -- alibi -----------------------------------------------------------------

def alibi_slopes(n_head: int) -> np.ndarray:
    """Per-head slopes from the ALiBi paper's geometric recipe (matches
    HF build_alibi_tensor's closest-power-of-2 construction)."""
    closest = 2 ** math.floor(math.log2(n_head))
    base = 2.0 ** (-(2.0 ** -(math.log2(closest) - 3)))
    slopes = [base ** i for i in range(1, closest + 1)]
    if closest != n_head:
        extra_base = 2.0 ** (-(2.0 ** -(math.log2(2 * closest) - 3)))
        n_extra = min(closest, n_head - closest)
        slopes += [extra_base ** i for i in range(1, 2 * n_extra, 2)]
    return np.asarray(slopes, dtype=np.float32)


def build_alibi(attention_mask: jax.Array, n_head: int) -> jax.Array:
    """(B, n_head, 1, S) bias: slope * key position, where position is the
    mask-aware index ``(cumsum(mask)-1)*mask``. Constant per query row, so
    softmax translation-invariance makes it equivalent to relative bias
    under the causal mask."""
    slopes = jnp.asarray(alibi_slopes(n_head))
    pos = (jnp.cumsum(attention_mask, axis=-1) - 1) * attention_mask  # (B,S)
    return slopes[None, :, None, None] * pos[:, None, None, :].astype(jnp.float32)


def bloom_gelu(x: jax.Array) -> jax.Array:
    """Megatron-style tanh gelu. Deliberately uses HF's truncated constant
    0.79788456 (not jax.nn.gelu's full-precision sqrt(2/pi)) so logits
    match HF bit-for-bit in the parity tests."""
    return x * 0.5 * (1.0 + jnp.tanh(0.79788456 * x * (1.0 + 0.044715 * x * x)))


# -- forward ---------------------------------------------------------------

def _local_heads(config: BloomConfig, tp: int) -> int:
    if config.n_head % tp != 0:
        raise ValueError(
            f"n_head={config.n_head} must be divisible by the tensor axis "
            f"size {tp} (whole heads per shard)"
        )
    return config.n_head // tp


def _mlp(
    blk: dict, x: jax.Array, config: BloomConfig, tp_axis, overlap: bool = False
) -> jax.Array:
    """ln_2 -> column up -> gelu -> row down (single source for the
    dense, pipeline, and sequence-parallel block paths).

    ``overlap``: ``x`` is this rank's token chunk; the up-projection
    ring-gathers tokens while it projects and the down-projection
    ring-reduces while it projects (nn/tensor_parallel/overlap.py), so
    the block maps token shard -> token shard with the comm hidden.
    ``ln_2`` then sees only local tokens, so its params route through
    the f-operator for exact full-sequence grads."""
    ln2_p = blk["ln_2"]
    if overlap:
        from pipegoose_tpu.nn.tensor_parallel.overlap import replicated_for_overlap

        ln2_p = replicated_for_overlap(ln2_p, tp_axis)
    ln2 = layer_norm(ln2_p, x, config.layer_norm_epsilon)
    h = column_parallel_linear(blk["mlp"]["up"], ln2, tp_axis, overlap=overlap)
    return row_parallel_linear(
        blk["mlp"]["down"], bloom_gelu(h), tp_axis, overlap=overlap
    )


def _columns_by_kind(params: dict, local_heads: int, hd: int) -> dict:
    """The fused projection's parameters with their columns (every
    leaf's LAST dimension: kernel, bias, a quantized ``q`` and its
    ``scale``) permuted from HF's ``[head][q|k|v][hd]`` to ``[q|k|v]
    [head][hd]``: one reshape-transpose-reshape a leaf, whose cotangent
    is the inverse transpose of the same few MB."""

    def by_kind(a: jax.Array) -> jax.Array:
        lead = a.shape[:-1]
        a = a.reshape(*lead, local_heads, 3, hd).swapaxes(-3, -2)
        return a.reshape(*lead, 3 * local_heads * hd)

    return jax.tree_util.tree_map(by_kind, params)


def _project_qkv(
    blk: dict,
    x: jax.Array,
    config: BloomConfig,
    tp_axis: Optional[str],
    overlap: bool = False,
) -> tuple:
    """q, k, v ``(B, S, local heads, head_dim)`` of the fused projection.

    The stored projection keeps HF's column order ``[head][q|k|v]
    [head_dim]``. Where a head is not whole 128-lane tiles
    (``head_dim % 128``: bloom-560m's 64) a slice of the
    ``(B, S, nh, 3, hd)`` view is part of a tile, and the chip's
    compiler copies a ``(B, S, H)`` plane at every use of q, k or v;
    there the projection's columns (6 MB a layer, not 100 MB of
    activations) are regrouped by kind in the graph,
    :func:`_columns_by_kind`, and q, k, v are three lane-aligned thirds
    of its result. Each column is the same dot product either way. The
    test is of the projection's layout alone: ``flash_attention``'s
    ``_pairs_heads`` asks its own question (do two heads' blocks fit
    VMEM) and neither calls the other, because ``(B, S, nh, hd)``
    reshapes into either kernel layout for nothing."""
    from pipegoose_tpu.telemetry.registry import get_registry

    b = x.shape[0]
    hd = config.head_dim
    tp = jax.lax.axis_size(tp_axis) if tp_axis else 1
    local_heads = _local_heads(config, tp)
    # counted per TRACE, as flash.calls / flash.paired_calls are
    registry = get_registry()
    registry.counter("bloom.qkv_projections").inc(of_trace=True)
    if hd % 128 == 0:
        fused = column_parallel_linear(
            blk["qkv"], x, tp_axis, overlap=overlap
        )  # (B,S,3H/tp) — full-token either way
        fused = fused.reshape(b, fused.shape[1], local_heads, 3, hd)
        return fused[..., 0, :], fused[..., 1, :], fused[..., 2, :]
    registry.counter("bloom.qkv_by_kind").inc(of_trace=True)
    fused = column_parallel_linear(
        _columns_by_kind(blk["qkv"], local_heads, hd), x, tp_axis,
        overlap=overlap,
    )  # (B,S,3H/tp) as [q|k|v][head][hd]
    # the three slices' cotangents leave the chip's compiler as ONE bf16
    # plane written a third at a time, no pad and no add
    # (tests/ops/test_chip_compile.py holds it to that)
    return tuple(
        t.reshape(b, fused.shape[1], local_heads, hd)
        for t in jnp.split(fused, 3, axis=-1)
    )


def _attention(
    blk: dict,
    x: jax.Array,
    bias: dict,
    config: BloomConfig,
    tp_axis: Optional[str],
    overlap: bool = False,
) -> jax.Array:
    """Self-attention with heads sharded over ``tp_axis``. qkv is
    column-parallel, the output projection row-parallel — the Megatron
    pattern the reference applies by module surgery
    (tensor_parallel/parallel_mapping.py:23-31). ``bias`` is the dict
    from :func:`attention_bias`.

    ``overlap``: ``x`` is this rank's token chunk; the qkv projection
    ring-gathers the sequence while it projects (attention itself needs
    every key anyway), the attention core runs full-sequence exactly as
    the monolithic path, and the output projection ring-reduce-scatters
    back to the token chunk."""
    hd = config.head_dim
    q, k, v = _project_qkv(blk, x, config, tp_axis, overlap)
    b, s, local_heads = q.shape[:3]

    if config.use_flash:
        # fused kernel path: alibi from static slopes; causal + padding
        # masks applied inside the kernel via kv_pos/kv_neg
        from pipegoose_tpu.ops.flash_attention import flash_attention

        slopes = jnp.asarray(alibi_slopes(config.n_head))
        if tp_axis:
            h0 = jax.lax.axis_index(tp_axis) * local_heads
            slopes = jax.lax.dynamic_slice_in_dim(slopes, h0, local_heads, 0)
        ctx = flash_attention(
            q, k, v, slopes,
            kv_pos=bias["kv_pos"], kv_neg=bias["kv_neg"], causal=True,
        )
        # zero pad-query rows (see the XLA branch below: every attention
        # path defines pad-query context as zero)
        ctx = ctx * bias["qmask"][:, :, None, None].astype(ctx.dtype)
        ctx = checkpoint_name(ctx, "attn_out")  # for remat_policy="attn"
        ctx = ctx.astype(x.dtype).reshape(b, s, local_heads * hd)
        return row_parallel_linear(blk["out"], ctx, tp_axis, overlap=overlap)

    # local head slice of the alibi bias
    alibi = bias["alibi"]
    if tp_axis:
        h0 = jax.lax.axis_index(tp_axis) * local_heads
        alibi = jax.lax.dynamic_slice_in_dim(alibi, h0, local_heads, axis=1)

    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
    scores = scores * (1.0 / math.sqrt(hd)) + alibi + bias["mask_bias"]
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(x.dtype)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", probs, v, preferred_element_type=jnp.float32)
    # fully-masked query rows (pad queries under LEFT padding attend
    # nothing): softmax of an all-NEG_INF row is an accidental uniform
    # average over keys. Define the context as ZERO there instead — the
    # flash kernel's natural value — so the XLA, flash, ring, and
    # Ulysses paths agree bit-for-bit. No loss-carrying position is
    # affected under the reference's right-padded protocol (a valid
    # target implies a valid query there).
    ctx = ctx * bias["qmask"][:, :, None, None].astype(ctx.dtype)
    ctx = checkpoint_name(ctx, "attn_out")
    ctx = ctx.astype(x.dtype).reshape(b, s, local_heads * hd)
    return row_parallel_linear(blk["out"], ctx, tp_axis, overlap=overlap)


def _block(
    blk: dict,
    x: jax.Array,
    bias: dict,
    config: BloomConfig,
    tp_axis: Optional[str],
    overlap: bool = False,
) -> jax.Array:
    """One transformer block, HF BloomBlock ordering (pre-LN, residual
    from the un-normalized stream).

    ``overlap``: the ring collective-matmul path — ``x`` is this rank's
    token chunk of the residual stream; the dense/hybrid forward sets
    it from ``config.overlap_tp``, the PP/SP compositions keep the
    monolithic path (their stream is already sharded differently)."""
    eps = config.layer_norm_epsilon
    ln1_p = blk["ln_1"]
    if overlap:
        from pipegoose_tpu.nn.tensor_parallel.overlap import replicated_for_overlap

        ln1_p = replicated_for_overlap(ln1_p, tp_axis)
    ln1 = layer_norm(ln1_p, x, eps)
    x = x + _attention(blk["attn"], ln1, bias, config, tp_axis, overlap=overlap)
    return x + _mlp(blk, x, config, tp_axis, overlap=overlap)


def embed_tokens(
    params: dict, input_ids: jax.Array, config: BloomConfig, tp_axis: Optional[str]
) -> jax.Array:
    """Embedding lookup + embedding layernorm (single source for the
    plain and pipeline forward paths)."""
    x = vocab_parallel_embedding(params["embed"], input_ids, tp_axis)
    x = x.astype(config.dtype)
    return layer_norm(params["embed_ln"], x, config.layer_norm_epsilon)


def attention_bias(attention_mask: jax.Array, config: BloomConfig) -> dict:
    """Attention bias inputs, in the form the configured attention path
    consumes (single source for the plain, pipeline, and 1F1B paths):
    - flash (``config.use_flash``): O(S) per-key mask-aware ALiBi
      position ``kv_pos`` and validity bias ``kv_neg`` — the dense
      (B, 1, S, S) tensors are never materialized;
    - standard: per-head ``alibi`` plus the dense causal/padding
      ``mask_bias``."""
    if config.use_flash:
        from pipegoose_tpu.ops.flash_attention import mask_to_kv_bias

        kv_pos, kv_neg = mask_to_kv_bias(attention_mask)
        return {"kv_pos": kv_pos, "kv_neg": kv_neg, "qmask": attention_mask}

    s = attention_mask.shape[-1]
    alibi = build_alibi(attention_mask, config.n_head)
    causal = jnp.tril(jnp.ones((s, s), dtype=bool))
    keep = causal[None, None] & (attention_mask[:, None, None, :] > 0)
    return {
        "alibi": alibi,
        "mask_bias": jnp.where(keep, 0.0, NEG_INF).astype(jnp.float32),
        "qmask": attention_mask,
    }


def forward_hidden(
    params: dict,
    input_ids: jax.Array,
    attention_mask: Optional[jax.Array],
    config: BloomConfig,
    tp_axis: Optional[str] = None,
) -> jax.Array:
    """Embedding -> scanned blocks -> final LN. Returns (B, S, H).

    With ``config.overlap_tp`` (and a tensor axis) the residual stream
    between blocks is TOKEN-SHARDED over ``tp_axis``: one f/g scatter
    after the (replicated) embedding, ring collective-matmuls inside
    every block, one f/g gather before the final LN — the hidden the
    caller sees is identical (fp32 allclose) to the monolithic path."""
    b, s = input_ids.shape
    if attention_mask is None:
        attention_mask = jnp.ones((b, s), dtype=jnp.int32)

    x = embed_tokens(params, input_ids, config, tp_axis)
    bias = attention_bias(attention_mask, config)

    overlap = bool(getattr(config, "overlap_tp", False)) and tp_axis is not None
    if overlap:
        from pipegoose_tpu.distributed.functional import scatter_to_tensor_group

        tp = jax.lax.axis_size(tp_axis)
        if s % tp:
            raise ValueError(
                f"overlap_tp: sequence length {s} must be divisible by "
                f"the tensor axis size {tp} (token chunks ride the ring)"
            )
        x = scatter_to_tensor_group(x, tp_axis, dim=1)

    block = partial(_block, config=config, tp_axis=tp_axis, overlap=overlap)
    if config.remat:
        block = _remat_wrap(block, config)

    def scan_fn(carry, blk):
        return block(blk, carry, bias), None

    x, _ = jax.lax.scan(scan_fn, x, params["blocks"])
    if overlap:
        from pipegoose_tpu.distributed.functional import gather_from_tensor_group

        x = gather_from_tensor_group(x, tp_axis, dim=1)
    return layer_norm(params["ln_f"], x, config.layer_norm_epsilon)


def logits_fn(
    params: dict,
    hidden: jax.Array,
    tp_axis: Optional[str] = None,
) -> jax.Array:
    """LM head tied to the (vocab-sharded) embedding: local logits are the
    local vocab shard — exactly what vocab_parallel_cross_entropy expects.
    Mirrors the reference's tied LMHead handling (parallelizer.py:205-211).

    The f-operator (copy_to_tensor_group) on ``hidden`` is load-bearing:
    in backward, each rank's hidden cotangent is only the partial sum over
    its local vocab shard, and the f-operator's all-reduce completes it —
    without it every gradient upstream of the LM head is wrong under TP."""
    from pipegoose_tpu.distributed.functional import copy_to_tensor_group

    if tp_axis:
        hidden = copy_to_tensor_group(hidden, tp_axis)
    w = params["embed"]["weight"]  # (V/tp, H) under TP
    out = jnp.einsum("bsh,vh->bsv", hidden, w, preferred_element_type=jnp.float32)
    return out


def forward(
    params: dict,
    input_ids: jax.Array,
    attention_mask: Optional[jax.Array],
    config: BloomConfig,
    tp_axis: Optional[str] = None,
) -> jax.Array:
    """Full causal-LM forward -> local-vocab-shard logits (B, S, V/tp)."""
    hidden = forward_hidden(params, input_ids, attention_mask, config, tp_axis)
    return logits_fn(params, hidden, tp_axis)


def loss_fn(
    params: dict,
    input_ids: jax.Array,
    attention_mask: Optional[jax.Array],
    labels: jax.Array,
    config: BloomConfig,
    tp_axis: Optional[str] = None,
) -> jax.Array:
    """Next-token cross entropy (shift-by-one), masked by attention_mask,
    vocab-parallel over ``tp_axis``. With ``config.ce_chunks`` the loss
    is computed chunk-by-chunk over the sequence (the full logits buffer
    never exists — see chunked_ce_sums)."""
    if config.fused_ce:
        from pipegoose_tpu.ops.fused_ce import fused_ce_shifted_loss

        # final-LN output -> kernel; the tied embedding is the LM head
        # (logits_fn without the materialized einsum)
        hidden = forward_hidden(params, input_ids, attention_mask, config, tp_axis)
        return fused_ce_shifted_loss(
            hidden, params["embed"]["weight"], labels, attention_mask,
            tp_axis, config.valid_vocab_size,
        )
    if config.ce_chunks:
        from pipegoose_tpu.nn.tensor_parallel.layers import chunked_ce_sums

        hidden = forward_hidden(params, input_ids, attention_mask, config, tp_axis)
        w = (
            attention_mask[:, 1:]
            if attention_mask is not None
            else jnp.ones_like(labels[:, 1:])
        ).astype(jnp.float32)
        tot, cnt = chunked_ce_sums(
            hidden[:, :-1], labels[:, 1:], w,
            lambda h: logits_fn(params, h, tp_axis),
            tp_axis, config.valid_vocab_size, config.ce_chunks,
        )
        return tot / jnp.maximum(cnt, 1)
    logits = forward(params, input_ids, attention_mask, config, tp_axis)
    shift_logits = logits[:, :-1]
    shift_labels = labels[:, 1:]
    per_tok = vocab_parallel_cross_entropy(
        shift_logits, shift_labels, tp_axis, valid_size=config.valid_vocab_size
    )
    if attention_mask is not None:
        w = attention_mask[:, 1:].astype(per_tok.dtype)
        return (per_tok * w).sum() / jnp.maximum(w.sum(), 1)
    return per_tok.mean()


# -- TP policy -------------------------------------------------------------

def pad_for_tp(params: dict, config: BloomConfig, tp: int):
    """Pad the (tied) embedding so vocab divides the tensor axis —
    returns (params, config) with ``valid_vocab_size`` recording the true
    vocab so the CE masks padded slots (reference
    EmbeddingParallelizer._resize_vocab_size semantics,
    parallelizer.py:125-141, plus the loss masking it lacked)."""
    import dataclasses as _dc

    from pipegoose_tpu.nn.tensor_parallel.tensor_parallel import pad_vocab

    v = params["embed"]["weight"].shape[0]
    padded = pad_vocab(params["embed"]["weight"], tp)
    if padded.shape[0] == v:
        return params, config
    params = dict(params)
    params["embed"] = {"weight": padded}
    config = _dc.replace(
        config, vocab_size=padded.shape[0], valid_vocab_size=config.valid_vocab_size or v
    )
    return params, config


def tp_mapping(axis: str = "tensor") -> ParallelMapping:
    """Partition policy for the BLOOM params tree — the analog of the
    reference's per-model __MAPPING__ table
    (tensor_parallel/parallel_mapping.py:16-52): qkv/up column, out/down
    row, embedding vocab-sharded (head-contiguous qkv layout keeps whole
    heads per shard; requires n_head % tp == 0)."""
    return ParallelMapping(
        [
            (r"blocks/attn/qkv", Column(axis)),
            (r"blocks/attn/out", Row(axis)),
            (r"blocks/mlp/up", Column(axis)),
            (r"blocks/mlp/down", Row(axis)),
            (r"embed/weight", Vocab(axis)),
        ]
    )


def tp_specs(params: dict, axis: str = "tensor") -> dict:
    """PartitionSpec pytree for the stacked-layer params layout. The
    stacked leading n_layer dim shifts every kernel spec right by one."""
    from jax.sharding import PartitionSpec as P

    from pipegoose_tpu.nn.parallel import spec_tree

    mapping = tp_mapping(axis)

    def spec_fn(path, x):
        if "blocks" in path:
            base = mapping.spec_for(path, x.ndim - 1)
            return P(None, *base)
        return mapping.spec_for(path, x.ndim)

    return spec_tree(params, spec_fn)


# -- pipeline-parallel composition ------------------------------------------

def loss_fn_pp(
    params: dict,
    input_ids: jax.Array,
    attention_mask: Optional[jax.Array],
    labels: jax.Array,
    config: BloomConfig,
    n_microbatches: int,
    tp_axis: Optional[str] = None,
    pipe_axis: str = "pipe",
    stage_layer_counts=None,
) -> jax.Array:
    """Pipeline-parallel loss: embed (vectorized over all microbatches on
    every rank — replicated compute off the critical path), GPipe over
    the pipe-sharded block stack, then vectorized LN/LM-head/CE, with the
    scalar combined from the last stage.

    Replaces the reference's PipelineEngine.run + scheduled backward
    (pipeline_engine.py:60-134, _job/creator.py:182-277) with one
    differentiable program.

    ``stage_layer_counts`` (len-P ints): UNEVEN stages — ``params`` must
    carry the padded block layout from ``repartition_blocks`` (driven by
    the cost-DP ``partition_costs``); each stage runs only its own live
    layers (lax.cond skip — see nn/pipeline_parallel/partitioner.py).
    The analog of the reference's cost-balanced partitioning incl. its
    embedding/head exclusions (reference partitioner.py:73-144).
    """
    from pipegoose_tpu.nn.pipeline_parallel import microbatch as mb
    from pipegoose_tpu.nn.pipeline_parallel.partitioner import (
        masked_stage_scan,
        stage_n_valid,
    )
    from pipegoose_tpu.nn.pipeline_parallel.pipeline import gpipe, last_stage_value

    b, s = input_ids.shape
    if attention_mask is None:
        attention_mask = jnp.ones((b, s), dtype=jnp.int32)

    mbs = mb.split(
        {"ids": input_ids, "mask": attention_mask, "labels": labels}, n_microbatches
    )

    # pipeline-entry activations for ALL microbatches (vmapped embed);
    # shared helpers keep PP/non-PP loss parity by construction
    h0 = jax.vmap(lambda ids: embed_tokens(params, ids, config, tp_axis))(mbs["ids"])

    # per-microbatch side inputs: alibi + combined mask bias
    side = jax.vmap(lambda m: attention_bias(m, config))(mbs["mask"])

    # with a selective remat_policy, checkpoint PER BLOCK (the policy's
    # named values live inside _block) instead of letting gpipe wrap the
    # whole stage — same semantics as the dense/1F1B paths
    def block_call(blk, hh, side):
        return _block(blk, hh, side, config, tp_axis)

    if config.remat and getattr(config, "remat_policy", None):
        block_call = _remat_wrap(block_call, config)
        gpipe_remat = False
    else:
        gpipe_remat = config.remat

    if stage_layer_counts is not None:
        n_valid = stage_n_valid(stage_layer_counts, config.n_layer, pipe_axis)

        def stage_fn(blocks, h, side):
            return masked_stage_scan(
                lambda blk, hh: block_call(blk, hh, side), blocks, h, n_valid
            )
    else:
        def stage_fn(blocks, h, side):
            def scan_fn(carry, blk):
                return block_call(blk, carry, side), None

            h, _ = jax.lax.scan(scan_fn, h, blocks)
            return h

    outs = gpipe(
        stage_fn,
        params["blocks"],
        h0,
        side_inputs=side,
        axis_name=pipe_axis,
        remat=gpipe_remat,
    )  # (M, mb, S, H), valid on last stage

    # vectorized head over all microbatches
    def head_one(h, ids, mask, labels):
        h = layer_norm(params["ln_f"], h, config.layer_norm_epsilon)
        if config.fused_ce:
            # the LAST stage's per-microbatch logits buffer is the PP
            # step's largest tensor — the fused kernel never builds it
            from pipegoose_tpu.ops.fused_ce import fused_ce_shifted_sums

            return fused_ce_shifted_sums(
                h, params["embed"]["weight"], labels, mask, tp_axis,
                config.valid_vocab_size,
            )
        logits = logits_fn(params, h, tp_axis)
        per_tok = vocab_parallel_cross_entropy(
            logits[:, :-1], labels[:, 1:], tp_axis, valid_size=config.valid_vocab_size
        )
        w = mask[:, 1:].astype(per_tok.dtype)
        return (per_tok * w).sum(), w.sum()

    tot, cnt = jax.vmap(head_one)(outs, mbs["ids"], mbs["mask"], mbs["labels"])
    loss_local = tot.sum() / jnp.maximum(cnt.sum(), 1)
    return last_stage_value(loss_local, pipe_axis)


def loss_fn_1f1b(
    params: dict,
    input_ids: jax.Array,
    attention_mask: Optional[jax.Array],
    labels: jax.Array,
    config: BloomConfig,
    n_microbatches: int,
    tp_axis: Optional[str] = None,
    pipe_axis: str = "pipe",
    stage_layer_counts=None,
) -> jax.Array:
    """Pipeline-parallel loss with the 1F1B (PipeDream-flush) runtime:
    same semantics as :func:`loss_fn_pp` (identical loss value and
    gradients) but peak activation memory bounded by the STAGE count
    instead of the microbatch count — each microbatch's backward starts
    as soon as its forward clears the last stage
    (nn/pipeline_parallel/pipeline.py:one_f_one_b).

    ``stage_layer_counts``: UNEVEN stages exactly as in :func:`loss_fn_pp`
    — ``params["blocks"]`` must carry the padded ``repartition_blocks``
    layout; pad slots are lax.cond-skipped in both the forward and the
    rematerialized backward of each stage.

    Implemented as a ``jax.custom_vjp`` whose forward runs the fused
    forward+backward pipeline and stashes the parameter gradients as
    residuals, so ``jax.value_and_grad(loss_fn_1f1b)`` plugs into
    ``make_hybrid_train_step`` unchanged (grad_sync_axes=("pipe","sum")
    completes the replicated embed/ln_f grads across stages, exactly as
    for loss_fn_pp)."""
    from functools import partial as _partial

    from pipegoose_tpu.nn.pipeline_parallel import microbatch as mb
    from pipegoose_tpu.nn.pipeline_parallel.pipeline import (
        manual_grads_loss,
        one_f_one_b,
    )

    b, s = input_ids.shape
    if attention_mask is None:
        attention_mask = jnp.ones((b, s), dtype=jnp.int32)

    mbs = mb.split(
        {"ids": input_ids, "mask": attention_mask, "labels": labels}, n_microbatches
    )
    side = jax.vmap(lambda m: attention_bias(m, config))(mbs["mask"])
    side = {**side, "labels": mbs["labels"], "mask": mbs["mask"]}

    # per-microbatch head losses are pre-normalized by the LOCAL total
    # token count so their plain sum equals loss_fn_pp's tot/cnt
    inv_count = 1.0 / jnp.maximum(attention_mask[:, 1:].sum().astype(jnp.float32), 1)

    block = _partial(_block, config=config, tp_axis=tp_axis)
    if config.remat:
        block = _remat_wrap(block, config)

    if stage_layer_counts is not None:
        from pipegoose_tpu.nn.pipeline_parallel.partitioner import (
            masked_stage_scan,
            stage_n_valid,
        )

        n_valid = stage_n_valid(stage_layer_counts, config.n_layer, pipe_axis)

        def stage_fn(blocks, h, side):
            return masked_stage_scan(
                lambda blk, hh: block(blk, hh, side), blocks, h, n_valid
            )
    else:
        def stage_fn(blocks, h, side):
            def scan_fn(carry, blk):
                return block(blk, carry, side), None

            h, _ = jax.lax.scan(scan_fn, h, blocks)
            return h

    def head_fn(hp, h, side):
        h = layer_norm(hp["ln_f"], h, config.layer_norm_epsilon)
        if config.fused_ce:
            from pipegoose_tpu.ops.fused_ce import fused_ce_shifted_sums

            tot, _ = fused_ce_shifted_sums(
                h, hp["embed"]["weight"], side["labels"], side["mask"],
                tp_axis, config.valid_vocab_size,
            )
            return (tot * inv_count).astype(jnp.float32)
        logits = logits_fn({"embed": hp["embed"]}, h, tp_axis)
        per_tok = vocab_parallel_cross_entropy(
            logits[:, :-1], side["labels"][:, 1:], tp_axis,
            valid_size=config.valid_vocab_size,
        )
        w = side["mask"][:, 1:].astype(per_tok.dtype)
        return ((per_tok * w).sum() * inv_count).astype(jnp.float32)

    def run(params):
        embed_params = {"embed": params["embed"], "embed_ln": params["embed_ln"]}
        h0, embed_vjp = jax.vjp(
            lambda ep: jax.vmap(
                lambda ids: embed_tokens(ep, ids, config, tp_axis)
            )(mbs["ids"]),
            embed_params,
        )
        head_params = {"ln_f": params["ln_f"], "embed": params["embed"]}
        loss_local, dh0, d_blocks, d_head = one_f_one_b(
            stage_fn, params["blocks"], head_fn, head_params, h0, side, pipe_axis
        )
        (d_embed,) = embed_vjp(dh0)
        P = jax.lax.axis_size(pipe_axis)
        is_last = jax.lax.axis_index(pipe_axis) == P - 1
        loss = jax.lax.psum(jnp.where(is_last, loss_local, 0.0), pipe_axis)
        grads = {
            "embed": {
                "weight": d_embed["embed"]["weight"] + d_head["embed"]["weight"]
            },
            "embed_ln": d_embed["embed_ln"],
            "blocks": d_blocks,
            "ln_f": d_head["ln_f"],
        }
        return loss, grads

    return manual_grads_loss(run, params)


def pp_specs(params: dict, tp_axis: str = "tensor", pipe_axis: str = "pipe") -> dict:
    """tp_specs with the stacked n_layer dim of blocks sharded over the
    pipe axis — stage assignment as a PartitionSpec (vs the reference's
    torch.fx partitioner, partitioner.py:29-219)."""
    from pipegoose_tpu.nn.pipeline_parallel.pipeline import pipe_stage_specs

    specs = tp_specs(params, tp_axis)
    specs["blocks"] = pipe_stage_specs(specs["blocks"], pipe_axis)
    return specs


# -- sequence-parallel composition ------------------------------------------

def _sp_alibi_pos(pad_mask_local: jax.Array, sp_axis: str) -> jax.Array:
    """GLOBAL mask-aware ALiBi key positions for this sequence chunk:
    BLOOM's ``(cumsum(mask)-1)*mask`` over the FULL sequence (HF
    build_alibi_tensor semantics — matches :func:`build_alibi`), under
    sequence sharding. One tiny all_gather of per-chunk mask counts
    gives every rank the global prefix for its chunk; for unpadded or
    right-padded batches the result equals plain global positions, for
    LEFT-padded batches it is what HF computes and plain positions are
    not. Compute ONCE per step and thread through the blocks."""
    m = pad_mask_local.astype(jnp.float32)
    counts = jax.lax.all_gather(m.sum(-1), sp_axis)  # (sp, B)
    sp = jax.lax.axis_size(sp_axis)
    rank = jax.lax.axis_index(sp_axis)
    prefix = jnp.where(
        jnp.arange(sp)[:, None] < rank, counts, 0.0
    ).sum(0)  # (B,) non-pad tokens on earlier chunks
    return (prefix[:, None] + jnp.cumsum(m, axis=-1) - 1.0) * m


def _attention_sp(
    blk: dict,
    x: jax.Array,  # (B, S_local, H)
    config: BloomConfig,
    tp_axis: Optional[str],
    sp_axis: str,
    pad_mask_local: jax.Array,  # (B, S_local)
    variant: str = "ring",
    alibi_pos: Optional[jax.Array] = None,  # (B, S_local) global positions
) -> jax.Array:
    """BLOOM attention with the sequence sharded over ``sp_axis`` and
    heads over ``tp_axis``. ALiBi positions come from ``alibi_pos``
    (mask-aware global positions, HF semantics under ANY padding —
    _sp_alibi_pos); when None, plain global key positions are used,
    identical for unpadded or right-padded batches.

    ``variant``:
    - ``"ring"``: K/V blocks rotate over the sp ring (flash chunk
      kernels when config.use_flash) — O(S_local^2) score working set,
      comm = K+V once around, best for very long sequences;
    - ``"ulysses"``: two all_to_all ops re-shard seq -> heads so each
      device runs FULL-sequence attention on local_heads/sp heads
      (flash kernel inside when config.use_flash), then one all_to_all
      restores sequence sharding — 4 collectives/layer, best when
      heads >= sp and the ring's per-hop latency dominates.
    Both are exact; gradient flows through the collectives' AD."""
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
    local_heads = _local_heads(config, tp)

    fused = column_parallel_linear(blk["qkv"], x, tp_axis)
    fused = fused.reshape(b, s_local, local_heads, 3, hd)
    q, k, v = fused[..., 0, :], fused[..., 1, :], fused[..., 2, :]

    slopes = jnp.asarray(alibi_slopes(config.n_head))
    if tp_axis:
        h0 = jax.lax.axis_index(tp_axis) * local_heads
        slopes = jax.lax.dynamic_slice_in_dim(slopes, h0, local_heads, 0)

    if variant == "ulysses":
        from pipegoose_tpu.nn.sequence_parallel.ulysses import (
            ulysses_causal_attention,
        )

        # per-head slopes follow the heads through the all_to_all —
        # device r serves the sp_rank-th subset (sliced inside)
        ctx = ulysses_causal_attention(
            q, k, v, sp_axis, pad_mask_local,
            alibi_slopes=slopes, use_flash=config.use_flash,
            alibi_pos_local=alibi_pos,
        )
    elif config.use_flash:
        # fused chunk kernel per ring step — no (S_local, S_local) score
        # materialization in the forward
        ctx = ring_flash_attention(
            q, k, v, sp_axis, alibi_slopes=slopes, kv_side=pad_mask_local,
            alibi_pos=alibi_pos,
        )
    else:
        bias_fn = make_causal_alibi_bias_fn(s_local, sp_axis, alibi_slopes=slopes)
        side = (
            (pad_mask_local, alibi_pos)
            if alibi_pos is not None else pad_mask_local
        )
        ctx = ring_attention(q, k, v, sp_axis, bias_fn, kv_side=side)
    # pad-query context is ZERO in every attention path (see _attention)
    ctx = ctx * pad_mask_local[:, :, None, None].astype(ctx.dtype)
    ctx = checkpoint_name(ctx, "attn_out")
    ctx = ctx.astype(x.dtype).reshape(b, s_local, local_heads * hd)
    return row_parallel_linear(blk["out"], ctx, tp_axis)


def loss_fn_sp(
    params: dict,
    input_ids: jax.Array,  # (B, S_local) — sequence sharded over sp_axis
    attention_mask: Optional[jax.Array],
    labels: jax.Array,
    config: BloomConfig,
    tp_axis: Optional[str] = None,
    sp_axis: str = "seq",
    variant: str = "ring",
) -> jax.Array:
    """Sequence-parallel causal-LM loss: every activation tensor lives
    sequence-sharded; attention is the ring (or Ulysses all_to_all with
    ``variant="ulysses"`` — see _attention_sp); the next-token target at
    each chunk boundary arrives by one ppermute of the label chunk.
    Gradients of (seq-replicated) params are partial per rank — sum them
    over ``sp_axis`` (grad_sync_axes=(("seq","sum"),))."""
    from pipegoose_tpu.distributed.functional import reduce_from_tensor_group

    b, s_local = input_ids.shape
    if attention_mask is None:
        attention_mask = jnp.ones((b, s_local), dtype=jnp.int32)

    x = embed_tokens(params, input_ids, config, tp_axis)
    # global mask-aware ALiBi positions, once per step (HF semantics
    # under any padding — left-padded batches included)
    apos = _sp_alibi_pos(attention_mask, sp_axis)

    def scan_fn(carry, blk):
        return _sp_block(
            blk, carry, config, tp_axis, sp_axis, attention_mask, variant,
            alibi_pos=apos,
        ), None

    step = _remat_wrap(scan_fn, config) if config.remat else scan_fn
    x, _ = jax.lax.scan(step, x, params["blocks"])

    total, w_sum = _sp_head_sums(
        params, x, attention_mask, labels, config, tp_axis, sp_axis
    )
    count = jax.lax.psum(w_sum, sp_axis)
    # identity-backward combine: each rank's grads stay local and are
    # psum'd over sp by the train step
    return reduce_from_tensor_group(total / jnp.maximum(count, 1), sp_axis)


def _sp_block(blk, h, config, tp_axis, sp_axis, pad_mask_local,
              variant: str = "ring", alibi_pos=None):
    """One transformer block on sequence-sharded activations (shared by
    the plain SP and the PP x SP compositions)."""
    ln1 = layer_norm(blk["ln_1"], h, config.layer_norm_epsilon)
    attn_blk = {"qkv": blk["attn"]["qkv"], "out": blk["attn"]["out"]}
    h = h + _attention_sp(
        attn_blk, ln1, config, tp_axis, sp_axis, pad_mask_local, variant,
        alibi_pos=alibi_pos,
    )
    return h + _mlp(blk, h, config, tp_axis)


def _sp_head_sums(params, x, attention_mask, labels, config, tp_axis, sp_axis):
    """Final LN -> logits -> SP-shifted CE sums. Returns the LOCAL
    (weighted-loss sum, weight sum) for this sequence shard.

    Global shift-by-one on a sharded sequence: see
    nn/sequence_parallel/targets.py (shared by all families)."""
    from pipegoose_tpu.nn.sequence_parallel.targets import sp_shifted_targets

    x = layer_norm(params["ln_f"], x, config.layer_norm_epsilon)
    shifted_labels, shifted_w = sp_shifted_targets(
        labels, attention_mask, sp_axis
    )
    if config.fused_ce:
        # the local (B, S_local, V) fp32 logits buffer is the tensor
        # that explodes at exactly the long-context shapes SP serves —
        # the fused kernel never materializes it
        from pipegoose_tpu.ops.fused_ce import fused_ce_masked_sums

        return fused_ce_masked_sums(
            x, params["embed"]["weight"], shifted_labels, shifted_w,
            tp_axis, config.valid_vocab_size,
        )
    logits = logits_fn(params, x, tp_axis)  # (B, S_local, V/tp)
    per_tok = vocab_parallel_cross_entropy(
        logits, shifted_labels, tp_axis, valid_size=config.valid_vocab_size
    )
    w = shifted_w.astype(per_tok.dtype)
    return (per_tok * w).sum(), w.sum()


def loss_fn_pp_sp(
    params: dict,
    input_ids: jax.Array,  # (B, S_local) — sequence sharded over sp_axis
    attention_mask: Optional[jax.Array],
    labels: jax.Array,
    config: BloomConfig,
    n_microbatches: int,
    tp_axis: Optional[str] = None,
    pipe_axis: str = "pipe",
    sp_axis: str = "seq",
) -> jax.Array:
    """Pipeline x sequence parallel composition: sequence-sharded
    activations flow through the compiled GPipe schedule, with ring
    attention running over the ``seq`` axis INSIDE each pipeline stage
    (all sp peers of a stage advance in lockstep — uniform SPMD). This
    is the long-context + deep-model shape neither axis covers alone.

    Gradient sync for the hybrid step: ``grad_sync_axes=(("pipe","sum"),
    ("seq","sum"))`` — replicated params get partial grads from both the
    stage split and the sequence split."""
    from pipegoose_tpu.distributed.functional import reduce_from_tensor_group
    from pipegoose_tpu.nn.pipeline_parallel import microbatch as mb
    from pipegoose_tpu.nn.pipeline_parallel.pipeline import gpipe, last_stage_value

    b, s_local = input_ids.shape
    if attention_mask is None:
        attention_mask = jnp.ones((b, s_local), dtype=jnp.int32)

    mbs = mb.split(
        {"ids": input_ids, "mask": attention_mask, "labels": labels}, n_microbatches
    )
    h0 = jax.vmap(lambda ids: embed_tokens(params, ids, config, tp_axis))(mbs["ids"])
    # mask-aware global ALiBi positions per microbatch (HF semantics
    # under any padding), computed once and fed as a pipeline side input
    apos = jax.vmap(lambda m: _sp_alibi_pos(m, sp_axis))(mbs["mask"])
    side = {"mask": mbs["mask"], "apos": apos}

    def stage_fn(blocks, h, side):
        def scan_fn(carry, blk):
            return _sp_block(
                blk, carry, config, tp_axis, sp_axis, side["mask"],
                alibi_pos=side["apos"],
            ), None

        h, _ = jax.lax.scan(scan_fn, h, blocks)
        return h

    outs = gpipe(
        stage_fn, params["blocks"], h0, side_inputs=side,
        axis_name=pipe_axis, remat=config.remat,
    )

    tot, cnt = jax.vmap(
        lambda h, m, l: _sp_head_sums(params, h, m, l, config, tp_axis, sp_axis)
    )(outs, mbs["mask"], mbs["labels"])
    count = jax.lax.psum(cnt.sum(), sp_axis)
    loss_local = reduce_from_tensor_group(
        tot.sum() / jnp.maximum(count, 1), sp_axis
    )
    return last_stage_value(loss_local, pipe_axis)
