"""Llama: dense decoder family (RMSNorm, RoPE, GQA, SwiGLU MLP).

Third model family. The reference's policy registry carries exactly two
architectures (bloom + albert, reference
nn/tensor_parallel/parallel_mapping.py:16-52); this framework's
equivalent registry (models/convert.py RULES tables) gains the Llama
decoder line (Llama 2/3, TinyLlama, and any llama-type HF checkpoint).

Built on the same primitives as Mixtral — the attention stack (RoPE,
GQA, column/row TP projections) is literally Mixtral's; only the MLP
differs (dense SwiGLU instead of routed experts), so every parallel
form (TP/DP/PP/ZeRO, stacked-layer scan, KV-cache generation) applies.
Semantics match HF ``modeling_llama`` for checkpoint parity (tested in
tests/models/test_llama.py).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp

from pipegoose_tpu.models.mixtral import (
    RopeScaling,
    _attention,
    rms_norm,
    rope_attention_bias,
    rope_cos_sin,
)
from pipegoose_tpu.nn.tensor_parallel.layers import (
    column_parallel_linear,
    row_parallel_linear,
    vocab_parallel_cross_entropy,
    vocab_parallel_embedding,
)
from pipegoose_tpu.ops.flash_attention import remat_policy


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    n_layer: int = 32
    n_head: int = 32
    n_kv_head: int = 32
    rope_theta: float = 1e4
    # HF rope_scaling (linear / dynamic / llama3) — None = plain RoPE
    rope_scaling: Optional["RopeScaling"] = None
    rms_eps: float = 1e-5
    initializer_range: float = 0.02
    tie_word_embeddings: bool = False
    dtype: Any = jnp.float32
    remat: bool = False
    # fused Pallas flash attention after RoPE; GQA served natively by
    # the kernel's grouped K/V index maps (no head repetition)
    use_flash: bool = False
    # fused Pallas CE (ops/fused_ce.py): no logits buffer in HBM
    fused_ce: bool = False
    valid_vocab_size: Optional[int] = None

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.n_head

    @classmethod
    def llama2_7b(cls, **kw) -> "LlamaConfig":
        return cls(**kw)

    @classmethod
    def llama3_8b(cls, **kw) -> "LlamaConfig":
        return cls(
            vocab_size=128256, hidden_size=4096, intermediate_size=14336,
            n_layer=32, n_head=32, n_kv_head=8, rope_theta=5e5, **kw,
        )


# -- init ------------------------------------------------------------------

def init_params(config: LlamaConfig, key: jax.Array) -> dict:
    h, v, L = config.hidden_size, config.vocab_size, config.n_layer
    hd, nh, nkv = config.head_dim, config.n_head, config.n_kv_head
    f = config.intermediate_size
    std, dt = config.initializer_range, config.dtype
    ks = jax.random.split(key, 9)

    def dense(k, shape):
        return (jax.random.normal(k, shape) * std).astype(dt)

    def rms_stack():
        return {"scale": jnp.ones((L, h), dt)}

    params = {
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
            "mlp": {
                "gate": {"kernel": dense(ks[5], (L, h, f))},
                "up": {"kernel": dense(ks[6], (L, h, f))},
                "down": {"kernel": dense(ks[7], (L, f, h))},
            },
        },
        "ln_f": {"scale": jnp.ones(h, dt)},
    }
    if not config.tie_word_embeddings:
        params["lm_head"] = {"kernel": dense(ks[8], (h, v))}
    return params


# -- forward ---------------------------------------------------------------

def _mlp(blk: dict, x: jax.Array, tp_axis: Optional[str]) -> jax.Array:
    """SwiGLU: down(silu(gate x) * up x), gate/up column, down row."""
    g = column_parallel_linear(blk["gate"], x, tp_axis)
    u = column_parallel_linear(blk["up"], x, tp_axis)
    return row_parallel_linear(blk["down"], jax.nn.silu(g) * u, tp_axis)


def _block(blk, x, cos, sin, bias, config, tp_axis):
    h = rms_norm(blk["ln_1"], x, config.rms_eps)
    x = x + _attention(blk["attn"], h, cos, sin, bias, config, tp_axis)
    h = rms_norm(blk["ln_2"], x, config.rms_eps)
    return x + _mlp(blk["mlp"], h, tp_axis)


def forward_hidden(
    params, input_ids, attention_mask, config, tp_axis: Optional[str] = None
):
    b, s = input_ids.shape
    if attention_mask is None:
        attention_mask = jnp.ones((b, s), jnp.int32)
    x = vocab_parallel_embedding(params["embed"], input_ids, tp_axis).astype(
        config.dtype
    )
    cos, sin = rope_cos_sin(
        s, config.head_dim, config.rope_theta, config.rope_scaling
    )
    bias = rope_attention_bias(attention_mask, config)

    block = partial(_block, config=config, tp_axis=tp_axis)
    if config.remat:
        block = jax.checkpoint(block, policy=remat_policy())

    def scan_fn(carry, blk):
        return block(blk, carry, cos, sin, bias), None

    x, _ = jax.lax.scan(scan_fn, x, params["blocks"])
    return rms_norm(params["ln_f"], x, config.rms_eps)


def logits_fn(params, hidden, config, tp_axis: Optional[str] = None):
    """lm_head column-parallel; tied checkpoints reuse the (vocab-
    sharded) embedding like BLOOM (reference parallelizer.py:205-211)."""
    if config.tie_word_embeddings:
        from pipegoose_tpu.distributed.functional import copy_to_tensor_group

        if tp_axis:
            hidden = copy_to_tensor_group(hidden, tp_axis)
        w = params["embed"]["weight"]  # (V/tp, H) under TP
        return jnp.einsum(
            "bsh,vh->bsv", hidden, w, preferred_element_type=jnp.float32
        )
    return column_parallel_linear(params["lm_head"], hidden, tp_axis)


def forward(params, input_ids, attention_mask, config, tp_axis=None):
    hidden = forward_hidden(params, input_ids, attention_mask, config, tp_axis)
    return logits_fn(params, hidden, config, tp_axis)


def _head_weight_layout(params, config):
    """(weight, fused-CE layout) of the LM head in its native form:
    tied = the (V/tp, H) vocab-sharded embedding, untied = the
    (H, V/tp) column-parallel kernel."""
    return (
        (params["embed"]["weight"], "vh")
        if config.tie_word_embeddings
        else (params["lm_head"]["kernel"], "hv")
    )


def loss_fn(params, input_ids, attention_mask, labels, config, tp_axis=None):
    if config.fused_ce:
        # fused Pallas CE: loss straight from (hidden, head weight) in
        # its NATIVE layout — tied = (V/tp, H) embedding, untied =
        # (H, V/tp) column head — no logits buffer, no transpose copy
        # (ops/fused_ce.py; the f-operator psum lives in its VJP)
        from pipegoose_tpu.ops.fused_ce import fused_ce_shifted_loss

        hidden = forward_hidden(
            params, input_ids, attention_mask, config, tp_axis
        )
        weight, layout = _head_weight_layout(params, config)
        return fused_ce_shifted_loss(
            hidden, weight, labels, attention_mask, tp_axis,
            config.valid_vocab_size, weight_layout=layout,
        )
    logits = forward(params, input_ids, attention_mask, config, tp_axis)
    per_tok = vocab_parallel_cross_entropy(
        logits[:, :-1], labels[:, 1:], tp_axis, valid_size=config.valid_vocab_size
    )
    if attention_mask is not None:
        w = attention_mask[:, 1:].astype(per_tok.dtype)
        return (per_tok * w).sum() / jnp.maximum(w.sum(), 1)
    return per_tok.mean()


# -- pipeline-parallel composition ------------------------------------------

def loss_fn_pp(
    params, input_ids, attention_mask, labels, config, n_microbatches,
    tp_axis: Optional[str] = None, pipe_axis: str = "pipe",
    stage_layer_counts=None,
):
    """GPipe composition, structured like bloom.loss_fn_pp.
    ``stage_layer_counts``: UNEVEN stages exactly as there (padded
    ``repartition_blocks`` layout, lax.cond slot skip)."""
    from pipegoose_tpu.nn.pipeline_parallel import microbatch as mb
    from pipegoose_tpu.nn.pipeline_parallel.partitioner import (
        masked_stage_scan,
        stage_n_valid,
    )
    from pipegoose_tpu.nn.pipeline_parallel.pipeline import gpipe, last_stage_value

    b, s = input_ids.shape
    if attention_mask is None:
        attention_mask = jnp.ones((b, s), jnp.int32)
    mbs = mb.split(
        {"ids": input_ids, "mask": attention_mask, "labels": labels}, n_microbatches
    )
    h0 = jax.vmap(
        lambda ids: vocab_parallel_embedding(params["embed"], ids, tp_axis).astype(
            config.dtype
        )
    )(mbs["ids"])
    cos, sin = rope_cos_sin(
        s, config.head_dim, config.rope_theta, config.rope_scaling
    )
    side = {"bias": jax.vmap(lambda m: rope_attention_bias(m, config))(mbs["mask"])}

    if stage_layer_counts is not None:
        n_valid = stage_n_valid(stage_layer_counts, config.n_layer, pipe_axis)

        def stage_fn(blocks, h, side):
            return masked_stage_scan(
                lambda blk, hh: _block(blk, hh, cos, sin, side["bias"], config, tp_axis),
                blocks, h, n_valid,
            )
    else:
        def stage_fn(blocks, h, side):
            def scan_fn(carry, blk):
                return _block(blk, carry, cos, sin, side["bias"], config, tp_axis), None

            h, _ = jax.lax.scan(scan_fn, h, blocks)
            return h

    outs = gpipe(
        stage_fn, params["blocks"], h0, side_inputs=side,
        axis_name=pipe_axis, remat=config.remat,
    )

    def head_one(h, mask, labels):
        h = rms_norm(params["ln_f"], h, config.rms_eps)
        if config.fused_ce:
            from pipegoose_tpu.ops.fused_ce import fused_ce_shifted_sums

            weight, layout = _head_weight_layout(params, config)
            return fused_ce_shifted_sums(
                h, weight, labels, mask, tp_axis,
                config.valid_vocab_size, weight_layout=layout,
            )
        logits = logits_fn(params, h, config, tp_axis)
        per_tok = vocab_parallel_cross_entropy(
            logits[:, :-1], labels[:, 1:], tp_axis, valid_size=config.valid_vocab_size
        )
        w = mask[:, 1:].astype(per_tok.dtype)
        return (per_tok * w).sum(), w.sum()

    tot, cnt = jax.vmap(head_one)(outs, mbs["mask"], mbs["labels"])
    return last_stage_value(tot.sum() / jnp.maximum(cnt.sum(), 1), pipe_axis)


def loss_fn_1f1b(
    params, input_ids, attention_mask, labels, config, n_microbatches,
    tp_axis: Optional[str] = None, pipe_axis: str = "pipe",
    stage_layer_counts=None,
):
    """Llama on the 1F1B (PipeDream-flush) runtime: same value/gradients
    as :func:`loss_fn_pp` with O(stages) activation memory — the same
    custom-vjp manual-gradient wrapper as ``bloom.loss_fn_1f1b``.
    Handles both tied and untied heads (tied: the embedding gets input
    AND head gradient contributions)."""
    from pipegoose_tpu.nn.pipeline_parallel import microbatch as mb
    from pipegoose_tpu.nn.pipeline_parallel.pipeline import (
        manual_grads_loss,
        one_f_one_b,
    )

    b, s = input_ids.shape
    if attention_mask is None:
        attention_mask = jnp.ones((b, s), jnp.int32)
    mbs = mb.split(
        {"ids": input_ids, "mask": attention_mask, "labels": labels}, n_microbatches
    )
    cos, sin = rope_cos_sin(
        s, config.head_dim, config.rope_theta, config.rope_scaling
    )
    side = {
        "bias": jax.vmap(lambda m: rope_attention_bias(m, config))(mbs["mask"]),
        "labels": mbs["labels"],
        "mask": mbs["mask"],
    }
    inv_count = 1.0 / jnp.maximum(attention_mask[:, 1:].sum().astype(jnp.float32), 1)

    block = partial(_block, config=config, tp_axis=tp_axis)
    if config.remat:
        block = jax.checkpoint(block, policy=remat_policy())

    if stage_layer_counts is not None:
        from pipegoose_tpu.nn.pipeline_parallel.partitioner import (
            masked_stage_scan,
            stage_n_valid,
        )

        n_valid = stage_n_valid(stage_layer_counts, config.n_layer, pipe_axis)

        def stage_fn(blocks, h, side):
            return masked_stage_scan(
                lambda blk, hh: block(blk, hh, cos, sin, side["bias"]),
                blocks, h, n_valid,
            )
    else:
        def stage_fn(blocks, h, side):
            def scan_fn(carry, blk):
                return block(blk, carry, cos, sin, side["bias"]), None

            h, _ = jax.lax.scan(scan_fn, h, blocks)
            return h

    tied = config.tie_word_embeddings

    def head_fn(hp, h, side):
        h = rms_norm(hp["ln_f"], h, config.rms_eps)
        if config.fused_ce:
            from pipegoose_tpu.ops.fused_ce import fused_ce_shifted_sums

            weight, layout = _head_weight_layout(hp, config)
            tot, _ = fused_ce_shifted_sums(
                h, weight, side["labels"], side["mask"], tp_axis,
                config.valid_vocab_size, weight_layout=layout,
            )
            return (tot * inv_count).astype(jnp.float32)
        logits = logits_fn(hp, h, config, tp_axis)
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
        head_params = {"ln_f": params["ln_f"]}
        if tied:
            head_params["embed"] = params["embed"]
        else:
            head_params["lm_head"] = params["lm_head"]
        loss_local, dh0, d_blocks, d_head = one_f_one_b(
            stage_fn, params["blocks"], head_fn, head_params, h0, side, pipe_axis
        )
        (d_embed,) = embed_vjp(dh0)
        P = jax.lax.axis_size(pipe_axis)
        is_last = jax.lax.axis_index(pipe_axis) == P - 1
        loss = jax.lax.psum(jnp.where(is_last, loss_local, 0.0), pipe_axis)
        if tied:
            d_embed = {
                "weight": d_embed["weight"] + d_head["embed"]["weight"]
            }
        grads = {
            "embed": d_embed,
            "blocks": d_blocks,
            "ln_f": d_head["ln_f"],
        }
        if not tied:
            grads["lm_head"] = d_head["lm_head"]
        return loss, grads

    return manual_grads_loss(run, params)


# -- TP/PP policy -----------------------------------------------------------

def specs(params: dict, tp_axis: str = "tensor") -> dict:
    """PartitionSpecs: q/k/v/gate/up column, o/down row, embedding
    vocab-sharded, lm_head column; stacked n_layer dim free for pipe."""
    from jax.sharding import PartitionSpec as P

    from pipegoose_tpu.nn.parallel import spec_tree

    t = tp_axis

    def spec_fn(path, x):
        if any(k in path for k in ("attn/q", "attn/k", "attn/v", "mlp/gate", "mlp/up")):
            return P(None, None, t)
        if "attn/o" in path or "mlp/down" in path:
            return P(None, t, None)
        if "embed/weight" in path:
            return P(t, None)
        if "lm_head" in path:
            return P(None, t)
        return P()

    return spec_tree(params, spec_fn)


# -- sequence-parallel composition ------------------------------------------

def loss_fn_sp(
    params: dict,
    input_ids: jax.Array,  # (B, S_local) — sequence sharded over sp_axis
    attention_mask: Optional[jax.Array],
    labels: jax.Array,
    config: LlamaConfig,
    tp_axis: Optional[str] = None,
    sp_axis: str = "seq",
    variant: str = "ring",
) -> jax.Array:
    """Sequence-parallel Llama loss: ring (or ``variant="ulysses"``)
    attention over ``sp_axis`` with RoPE at global positions
    (rope_scaling honored). Shares mixtral._attention_sp — the RoPE/GQA
    SP paths are family-agnostic; only the dense SwiGLU block body
    differs from Mixtral's MoE.

    Grad sync for replicated params: ``grad_sync_axes=(("seq","sum"),)``.
    """
    from pipegoose_tpu.distributed.functional import reduce_from_tensor_group
    from pipegoose_tpu.models.mixtral import _attention_sp
    from pipegoose_tpu.nn.sequence_parallel.targets import sp_shifted_targets

    b, s_local = input_ids.shape
    if attention_mask is None:
        attention_mask = jnp.ones((b, s_local), jnp.int32)

    x = vocab_parallel_embedding(params["embed"], input_ids, tp_axis).astype(
        config.dtype
    )

    def block(blk, h):
        ln1 = rms_norm(blk["ln_1"], h, config.rms_eps)
        h = h + _attention_sp(
            blk["attn"], ln1, config, tp_axis, sp_axis, attention_mask, variant
        )
        ln2 = rms_norm(blk["ln_2"], h, config.rms_eps)
        return h + _mlp(blk["mlp"], ln2, tp_axis)

    def scan_fn(carry, blk):
        return block(blk, carry), None

    step = (jax.checkpoint(scan_fn, policy=remat_policy())
            if config.remat else scan_fn)
    x, _ = jax.lax.scan(step, x, params["blocks"])

    x = rms_norm(params["ln_f"], x, config.rms_eps)
    shifted_labels, shifted_w = sp_shifted_targets(
        labels, attention_mask, sp_axis
    )
    if config.fused_ce:
        from pipegoose_tpu.ops.fused_ce import fused_ce_masked_sums

        weight, layout = _head_weight_layout(params, config)
        tot, cnt = fused_ce_masked_sums(
            x, weight, shifted_labels, shifted_w, tp_axis,
            config.valid_vocab_size, weight_layout=layout,
        )
    else:
        logits = logits_fn(params, x, config, tp_axis)
        per_tok = vocab_parallel_cross_entropy(
            logits, shifted_labels, tp_axis,
            valid_size=config.valid_vocab_size,
        )
        w = shifted_w.astype(per_tok.dtype)
        tot, cnt = (per_tok * w).sum(), w.sum()
    count = jax.lax.psum(cnt, sp_axis)
    return reduce_from_tensor_group(
        tot / jnp.maximum(count, 1), sp_axis
    )


def pp_specs(params: dict, tp_axis: str = "tensor", pipe_axis: str = "pipe") -> dict:
    from pipegoose_tpu.nn.pipeline_parallel.pipeline import pipe_stage_specs

    sp = specs(params, tp_axis)
    sp["blocks"] = pipe_stage_specs(sp["blocks"], pipe_axis)
    return sp


# -- generation (KV cache) ---------------------------------------------------

def init_cache(config: LlamaConfig, batch: int, max_len: int) -> dict:
    L, nkv, hd = config.n_layer, config.n_kv_head, config.head_dim
    shape = (L, batch, max_len, nkv, hd)
    return {"k": jnp.zeros(shape, config.dtype), "v": jnp.zeros(shape, config.dtype)}


def forward_cached(params, ids, cache, start, config):
    """(logits at last position, new cache) — shares Mixtral's grouped-GQA
    cached attention; the per-layer body swaps the MoE for dense SwiGLU."""
    from pipegoose_tpu.models.mixtral import _attn_cached

    x = vocab_parallel_embedding(params["embed"], ids, None).astype(config.dtype)
    max_len = cache["k"].shape[2]
    if config.rope_scaling is not None and config.rope_scaling.rope_type == "dynamic":
        # dynamic NTK makes inv_freq a function of the CURRENT length;
        # precomputing at cache capacity would rescale short prompts HF
        # leaves unscaled — reject rather than silently diverge
        raise NotImplementedError(
            "rope_scaling type 'dynamic' is not supported in the KV-cache "
            "decode path (length-dependent frequencies)"
        )
    cos_full, sin_full = rope_cos_sin(
        max_len, config.head_dim, config.rope_theta, config.rope_scaling
    )

    def scan_fn(carry, blk_and_cache):
        h = carry
        blk, kc, vc = blk_and_cache
        ln1 = rms_norm(blk["ln_1"], h, config.rms_eps)
        attn, kc, vc = _attn_cached(
            blk["attn"], ln1, kc, vc, start, cos_full, sin_full, config
        )
        h = h + attn
        ln2 = rms_norm(blk["ln_2"], h, config.rms_eps)
        return h + _mlp(blk["mlp"], ln2, None), (kc, vc)

    x, (k_new, v_new) = jax.lax.scan(
        scan_fn, x, (params["blocks"], cache["k"], cache["v"])
    )
    x = rms_norm(params["ln_f"], x, config.rms_eps)
    logits = logits_fn(params, x[:, -1:], config, None)[:, 0]
    return logits, {"k": k_new, "v": v_new}


def generate(
    params, input_ids, config, max_new_tokens,
    temperature: float = 0.0, rng=None, eos_token_id=None,
) -> jax.Array:
    from pipegoose_tpu.models._decode import autoregressive_generate, vocab_mask_for

    return autoregressive_generate(
        forward_cached, init_cache, params, input_ids, config,
        max_new_tokens, temperature, rng, eos_token_id,
        logits_mask=vocab_mask_for(config),
    )
