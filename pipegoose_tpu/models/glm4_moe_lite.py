"""GLM-4.7-Flash (``glm4_moe_lite``): latent attention, sigmoid top-k
experts with a shared expert, a multi-token-prediction module.

The layer equations are DeepSeek-V3's (arXiv 2412.19437), which HF's
``glm4_moe_lite`` follows; every size is a published config key
(https://huggingface.co/zai-org/GLM-4.7-Flash/blob/main/config.json).
RMSNorm, pre-norm residuals, no biases.

* Latent attention (every layer): ``c_q = RMSNorm(x W_qa)``; ``q = c_q
  W_qb`` -> heads x (nope | rope); ``[c_kv | k_r] = x W_kva``; ``c_kv =
  RMSNorm(c_kv)``; ``[k_nope | v] = c_kv W_kvb`` per head; ``k_r`` is
  ONE rotary key shared by all heads. RoPE (rotate-half) on ``q``'s
  rotary part and on ``k_r``. Per head ``q = [q_nope | q_rope]``, ``k =
  [k_nope | k_r]``; causal softmax(q k^T / sqrt(nope + rope)) v.
* The first ``first_k_dense_replace`` layers: SwiGLU of width
  ``intermediate_size``. The others: ``shared(x) + sum_e w_e
  expert_e(x)`` with the router of ``nn/expert_parallel/routers.py``
  (``SigmoidTopKRouter``) and the grouped expert layer
  (``grouped_experts``), each expert a SwiGLU of width
  ``moe_intermediate_size``. No auxiliary loss.
* MTP module (training objective; ``num_nextn_predict_layers`` 1),
  position i predicting token i+2: ``h' = W_eh [RMSNorm_e(Emb(t_{i+1}))
  ; RMSNorm_h(h_i)]`` with ``h_i`` the last layer's output before the
  final norm and the embedding shared; one whole expert layer; its own
  final RMSNorm; the main head's weight.
  ``loss = CE(main, t_{i+1}) + mtp_loss_weight * CE(MTP, t_{i+2})``.

A chip's share of a layer: ``experts_held = (first, count)`` says which
routed experts this parameter tree holds. The router keeps its
published width and picks over all of them; the picks that fall on
held experts are computed, what the absent ones would add is left out
(no exchange on one chip, nothing stands in for the other chips). A
sliced vocabulary is a smaller vocabulary: ``vocab_size`` rows are
stored (padded so the fused CE's blocks divide them),
``valid_vocab_size`` of them are real.

Training path; serving is not built (ROADMAP.md: a paged latent cache,
experts through the decode step, MTP as the engine's draft).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from pipegoose_tpu.models.mixtral import (
    apply_rope,
    rms_norm,
    rope_attention_bias,
    rope_cos_sin,
)
from pipegoose_tpu.nn.expert_parallel.experts import grouped_experts
from pipegoose_tpu.nn.expert_parallel.routers import SigmoidTopKRouter
from pipegoose_tpu.nn.tensor_parallel.layers import (
    column_parallel_linear,
    row_parallel_linear,
    vocab_parallel_cross_entropy,
    vocab_parallel_embedding,
)
from pipegoose_tpu.ops.flash_attention import remat_policy


# vocabulary rows a tile of the fused CE. Chosen when the backward had a
# dw kernel whose default 512-row tile took 16.6 MB of VMEM against the
# compiler's 16 MB at hidden 2048; the one backward kernel plans its own
# VMEM and the forward compiles at 512 too, but the cell's times were
# measured at 256 and 512 is no faster in the backward (PERF.md, PR 41)
_CE_BLOCK_V = 256


@dataclasses.dataclass(frozen=True)
class Glm4MoeLiteConfig:
    # published keys (defaults: GLM-4.7-Flash)
    vocab_size: int = 154880
    hidden_size: int = 2048
    intermediate_size: int = 10240
    moe_intermediate_size: int = 1536
    num_hidden_layers: int = 47
    num_attention_heads: int = 20
    num_key_value_heads: int = 20
    n_routed_experts: int = 64
    n_shared_experts: int = 1
    num_experts_per_tok: int = 4
    routed_scaling_factor: float = 1.8
    norm_topk_prob: bool = True
    n_group: int = 1
    topk_group: int = 1
    first_k_dense_replace: int = 1
    num_nextn_predict_layers: int = 1
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    rope_theta: float = 1e6
    rms_norm_eps: float = 1e-5
    tie_word_embeddings: bool = False
    initializer_range: float = 0.02
    # the share of a layer held here: (first, count) of the routed
    # experts; None = all of them
    experts_held: Optional[tuple] = None
    # real rows of a padded (or sliced and padded) vocabulary
    valid_vocab_size: Optional[int] = None
    mtp_loss_weight: float = 0.3
    remat: bool = False
    use_flash: bool = False
    fused_ce: bool = False
    dtype: Any = jnp.float32

    def __post_init__(self):
        if self.n_group != 1 or self.topk_group != 1:
            raise ValueError("group-limited routing is not built "
                             "(n_group and topk_group must be 1)")
        if self.num_key_value_heads != self.num_attention_heads:
            raise ValueError("latent attention has one key per head")
        if self.tie_word_embeddings:
            raise ValueError("the head is untied from the embedding")
        if self.num_nextn_predict_layers not in (0, 1):
            raise ValueError("one MTP module at most")
        if self.first_k_dense_replace != 1 or self.num_hidden_layers < 2:
            raise ValueError("one leading dense layer, then expert layers, "
                             "is what is built")
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
    def n_moe_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    def router(self) -> SigmoidTopKRouter:
        return SigmoidTopKRouter(
            self.n_routed_experts, self.num_experts_per_tok,
            scaling=self.routed_scaling_factor, normalize=self.norm_topk_prob,
        )


# -- init ------------------------------------------------------------------

def _attn_shapes(c: Glm4MoeLiteConfig) -> dict:
    h, nh = c.hidden_size, c.num_attention_heads
    return {
        "q_a": {"kernel": (h, c.q_lora_rank)},
        "q_a_norm": {"scale": (c.q_lora_rank,)},
        "q_b": {"kernel": (c.q_lora_rank, nh * c.qk_head_dim)},
        "kv_a": {"kernel": (h, c.kv_lora_rank + c.qk_rope_head_dim)},
        "kv_a_norm": {"scale": (c.kv_lora_rank,)},
        "kv_b": {"kernel": (c.kv_lora_rank,
                            nh * (c.qk_nope_head_dim + c.v_head_dim))},
        "o": {"kernel": (nh * c.v_head_dim, h)},
    }


def _swiglu_shapes(h: int, f: int, lead: tuple = ()) -> dict:
    return {"gate": {"kernel": lead + (h, f)}, "up": {"kernel": lead + (h, f)},
            "down": {"kernel": lead + (f, h)}}


def _moe_block_shapes(c: Glm4MoeLiteConfig) -> dict:
    h = c.hidden_size
    return {
        "ln_1": {"scale": (h,)}, "attn": _attn_shapes(c),
        "ln_2": {"scale": (h,)},
        "router": {"gate": {"kernel": (h, c.n_routed_experts)},
                   "bias": (c.n_routed_experts,)},
        "shared": _swiglu_shapes(
            h, c.moe_intermediate_size * c.n_shared_experts),
        "experts": _swiglu_shapes(h, c.moe_intermediate_size,
                                  (c.held[1],)),
    }


def param_shapes(c: Glm4MoeLiteConfig) -> dict:
    """The parameter tree as shapes. Layers past the dense ones are
    stacked on a leading axis (``blocks``) and scanned."""
    h, v = c.hidden_size, c.vocab_size
    stack = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda s: (c.n_moe_layers,) + s, t,
        is_leaf=lambda x: isinstance(x, tuple))
    tree = {
        "embed": {"weight": (v, h)},
        "dense": {"ln_1": {"scale": (h,)}, "attn": _attn_shapes(c),
                  "ln_2": {"scale": (h,)},
                  "mlp": _swiglu_shapes(h, c.intermediate_size)},
        "blocks": stack(_moe_block_shapes(c)),
        "ln_f": {"scale": (h,)},
        "lm_head": {"weight": (v, h)},
    }
    if c.num_nextn_predict_layers:
        tree["mtp"] = {
            "enorm": {"scale": (h,)}, "hnorm": {"scale": (h,)},
            "eh_proj": {"kernel": (2 * h, h)},
            "block": _moe_block_shapes(c),
            "norm": {"scale": (h,)},
        }
    return tree


def init_params(config: Glm4MoeLiteConfig, key: jax.Array) -> dict:
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


# the counters of ``loss_and_counters`` -> registry metrics, for
# ``telemetry.AuxRecorder(names=COUNTER_METRICS)``
COUNTER_METRICS = {
    "rows_per_expert": ("histogram", "moe.rows_per_expert"),
    "local_pick_share": ("gauge", "moe.local_pick_share"),
    "loss_main": ("gauge", "train.loss.main"),
    "loss_mtp": ("gauge", "train.loss.mtp"),
}


def frozen_leaves(params: dict) -> dict:
    """True for the leaves that take no gradient and no optimizer state
    (``Trainer(frozen=...)``): the routers' selection bias."""
    return jax.tree_util.tree_map_with_path(
        lambda path, _: jax.tree_util.keystr(path).endswith(
            "['router']['bias']"), params)


# -- layers ----------------------------------------------------------------

def _mla(blk, x, cos, sin, bias, config, tp_axis):
    from pipegoose_tpu.distributed.functional import copy_to_tensor_group

    c = config
    b, s, _ = x.shape
    tp = jax.lax.axis_size(tp_axis) if tp_axis else 1
    if c.num_attention_heads % tp:
        raise ValueError(f"{c.num_attention_heads} heads do not divide "
                         f"over tensor axis size {tp}")
    nh = c.num_attention_heads // tp
    dn, dr, dv = c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim
    with jax.named_scope("mla.proj"):
        cq = jnp.dot(x, blk["q_a"]["kernel"],
                     preferred_element_type=jnp.float32).astype(x.dtype)
        cq = rms_norm(blk["q_a_norm"], cq, c.rms_norm_eps)
        q = column_parallel_linear(blk["q_b"], cq, tp_axis)
        q = q.reshape(b, s, nh, dn + dr)
        ckv = jnp.dot(x, blk["kv_a"]["kernel"],
                      preferred_element_type=jnp.float32).astype(x.dtype)
        ckv, k_r = ckv[..., :c.kv_lora_rank], ckv[..., c.kv_lora_rank:]
        ckv = rms_norm(blk["kv_a_norm"], ckv, c.rms_norm_eps)
        kv = column_parallel_linear(blk["kv_b"], ckv, tp_axis)
        kv = kv.reshape(b, s, nh, dn + dv)
        if tp_axis:
            # one key for all heads, used here by this rank's heads only
            k_r = copy_to_tensor_group(k_r, tp_axis)
        q_r, k_r = (t.astype(x.dtype) for t in apply_rope(
            q[..., dn:], k_r[:, :, None, :], cos, sin))
        q = jnp.concatenate([q[..., :dn], q_r], axis=-1)
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_r, (b, s, nh, dr))], axis=-1)
        v = kv[..., dn:]
    scale = (dn + dr) ** -0.5
    with jax.named_scope("mla.attn"):
        if c.use_flash:
            from pipegoose_tpu.ops.flash_attention import flash_attention

            if dv != dn + dr:
                raise ValueError("the flash kernels take one head width "
                                 "for q, k and v")
            ctx = flash_attention(q, k, v, alibi_slopes=None,
                                  kv_neg=bias["kv_neg"], causal=True,
                                  scale=scale)
        else:
            scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                                preferred_element_type=jnp.float32)
            scores = scores * scale + bias["mask_bias"]
            probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
            ctx = jnp.einsum("bhqk,bkhd->bqhd", probs, v,
                             preferred_element_type=jnp.float32)
        ctx = ctx.astype(x.dtype).reshape(b, s, nh * dv)
    with jax.named_scope("mla.proj"):
        return row_parallel_linear(blk["o"], ctx, tp_axis)


def _swiglu(blk, x, tp_axis):
    g = column_parallel_linear(blk["gate"], x, tp_axis)
    u = column_parallel_linear(blk["up"], x, tp_axis)
    return row_parallel_linear(blk["down"], jax.nn.silu(g) * u, tp_axis)


def moe(blk, x, config, tp_axis=None):
    """One expert layer's feed-forward on ``x`` (B, S, H): the shared
    expert plus the held experts' part of the routed sum. Returns
    ``(y, rows_per_held_expert)``."""
    flat = x.reshape(-1, x.shape[-1])
    with jax.named_scope("moe.route"):
        routing = config.router()(blk["router"], flat)
    routed, rows = grouped_experts(
        blk["experts"], flat, routing, config.held, tp_axis=tp_axis)
    with jax.named_scope("moe.shared"):
        shared = _swiglu(blk["shared"], x, tp_axis)
    return shared + routed.reshape(x.shape), rows


def _block(blk, x, cos, sin, bias, config, tp_axis):
    """One layer: latent attention, then the SwiGLU of a dense layer
    (``mlp`` in ``blk``) or the expert layer. Returns ``(x, rows on
    each held expert)``, the rows ``None`` for a dense layer."""
    h = rms_norm(blk["ln_1"], x, config.rms_norm_eps)
    x = x + _mla(blk["attn"], h, cos, sin, bias, config, tp_axis)
    h = rms_norm(blk["ln_2"], x, config.rms_norm_eps)
    if "mlp" in blk:
        return x + _swiglu(blk["mlp"], h, tp_axis), None
    y, rows = moe(blk, h, config, tp_axis)
    return x + y, rows


def _trunk(params, input_ids, attention_mask, config, tp_axis):
    """Embedding and every layer. Returns the last layer's output BEFORE
    the final norm, what the blocks share (cos, sin, bias) and the rows
    on each held expert, per expert layer."""
    c = config
    b, s = input_ids.shape
    if attention_mask is None:
        attention_mask = jnp.ones((b, s), jnp.int32)
    x = vocab_parallel_embedding(params["embed"], input_ids, tp_axis)
    x = x.astype(c.dtype)
    cos, sin = rope_cos_sin(s, c.qk_rope_head_dim, c.rope_theta)
    bias = rope_attention_bias(attention_mask, c)
    block = _block
    if c.remat:
        # the block's input and what the flash kernel left for its
        # backward: nothing else is kept, and no forward kernel re-runs
        block = jax.checkpoint(block, static_argnums=(5, 6),
                               policy=remat_policy())
    x, _ = block(params["dense"], x, cos, sin, bias, c, tp_axis)
    x, rows = jax.lax.scan(
        lambda carry, blk: block(blk, carry, cos, sin, bias, c, tp_axis),
        x, params["blocks"])
    return x, (cos, sin, bias, block), rows


def _mtp_hidden(params, h_last, input_ids, shared, config, tp_axis):
    """The MTP module's final-norm output (B, S, H): position i has seen
    tokens <= i+1 and predicts token i+2. The last position's embedding
    wraps round; attention is causal, so only that position's own
    (masked) prediction sees it."""
    c = config
    cos, sin, bias, block = shared
    mtp = params["mtp"]
    with jax.named_scope("mtp"):
        nxt = vocab_parallel_embedding(
            params["embed"], jnp.roll(input_ids, -1, axis=1), tp_axis)
        both = jnp.concatenate(
            [rms_norm(mtp["enorm"], nxt.astype(c.dtype), c.rms_norm_eps),
             rms_norm(mtp["hnorm"], h_last, c.rms_norm_eps)], axis=-1)
        h = jnp.dot(both, mtp["eh_proj"]["kernel"],
                    preferred_element_type=jnp.float32).astype(c.dtype)
        h, rows = block(mtp["block"], h, cos, sin, bias, c, tp_axis)
        return rms_norm(mtp["norm"], h, c.rms_norm_eps), rows


def forward_hidden(params, input_ids, attention_mask, config, tp_axis=None):
    x, _, _ = _trunk(params, input_ids, attention_mask, config, tp_axis)
    return rms_norm(params["ln_f"], x, config.rms_norm_eps)


def logits_fn(params, hidden, config, tp_axis=None):
    """(B, S, V_local) float32, the head's rows over the tensor axis."""
    from pipegoose_tpu.distributed.functional import copy_to_tensor_group

    if tp_axis:
        hidden = copy_to_tensor_group(hidden, tp_axis)
    return jnp.einsum("bsh,vh->bsv", hidden, params["lm_head"]["weight"],
                      preferred_element_type=jnp.float32)


def forward(params, input_ids, attention_mask, config, tp_axis=None):
    hidden = forward_hidden(params, input_ids, attention_mask, config, tp_axis)
    return logits_fn(params, hidden, config, tp_axis)


def _ce_sums(params, hidden, labels, weights, config, tp_axis):
    """(weighted loss sum, weight sum) of one head pass; ``labels`` are
    already aligned with ``hidden``."""
    with jax.named_scope("head"):
        if config.fused_ce:
            from pipegoose_tpu.ops.fused_ce import fused_ce_sums

            return fused_ce_sums(
                hidden.reshape(-1, hidden.shape[-1]),
                params["lm_head"]["weight"], labels.reshape(-1),
                weights.reshape(-1).astype(jnp.float32), tp_axis,
                config.valid_vocab_size, block_v=_CE_BLOCK_V,
                weight_layout="vh")
        per_tok = vocab_parallel_cross_entropy(
            logits_fn(params, hidden, config, tp_axis), labels, tp_axis,
            valid_size=config.valid_vocab_size)
        w = weights.astype(per_tok.dtype)
        return (per_tok * w).sum(), w.sum()


def loss_and_counters(params, input_ids, attention_mask, labels, config,
                      tp_axis=None):
    """``(loss, counters)``: the two-term loss, and a small pytree of
    float32 counters for the step's counter channel
    (``Trainer(has_aux=True)``): the two terms apart, the rows on each
    held expert per expert layer (the MTP module's last), and the share
    of all picks that fell on held experts."""
    c = config
    b, s = input_ids.shape
    x, shared, rows = _trunk(params, input_ids, attention_mask, c, tp_axis)
    live = (jnp.ones((b, s), jnp.float32) if attention_mask is None
            else attention_mask.astype(jnp.float32))
    pos = jnp.arange(s)

    def shifted(n):
        """Targets n ahead, the last n positions masked."""
        w = jnp.roll(live, -n, axis=1) * (pos < s - n)[None, :]
        return jnp.roll(labels, -n, axis=1), w

    hidden = rms_norm(params["ln_f"], x, c.rms_norm_eps)
    tot, cnt = _ce_sums(params, hidden, *shifted(1), c, tp_axis)
    main = tot / jnp.maximum(cnt, 1)
    loss, mtp = main, jnp.zeros((), jnp.float32)
    if c.num_nextn_predict_layers:
        hidden, mtp_rows = _mtp_hidden(params, x, input_ids, shared, c,
                                       tp_axis)
        tot, cnt = _ce_sums(params, hidden, *shifted(2), c, tp_axis)
        mtp = tot / jnp.maximum(cnt, 1)
        loss = main + c.mtp_loss_weight * mtp
        rows = jnp.concatenate([rows, mtp_rows[None]], axis=0)
    rows = rows.astype(jnp.float32)
    picks = b * s * c.num_experts_per_tok * rows.shape[0]
    counters = {
        "loss_main": main.astype(jnp.float32),
        "loss_mtp": mtp.astype(jnp.float32),
        "rows_per_expert": rows,
        "local_pick_share": rows.sum() / picks,
    }
    return loss, counters


def loss_fn(params, input_ids, attention_mask, labels, config, tp_axis=None):
    return loss_and_counters(params, input_ids, attention_mask, labels,
                             config, tp_axis)[0]


# -- TP policy -------------------------------------------------------------

def tp_specs(params: dict, tp_axis: str = "tensor") -> dict:
    """PartitionSpecs: heads over the tensor axis (q_b, kv_b column, o
    row), the down-projections' inputs (q_a, kv_a) and the router
    replicated, every SwiGLU's inner width (gate/up column, down row),
    the embedding's and the head's rows."""
    from jax.sharding import PartitionSpec as P

    from pipegoose_tpu.nn.parallel import spec_tree

    t = tp_axis

    def spec_fn(path, x):
        lead = (None,) * (x.ndim - 2)
        if any(k in path for k in ("attn/q_b", "attn/kv_b", "/gate/kernel",
                                   "/up/kernel")) and "router" not in path:
            return P(*lead, None, t)
        if "attn/o/" in path or "/down/kernel" in path:
            return P(*lead, t, None)
        if "embed/weight" in path or "lm_head/weight" in path:
            return P(t, None)
        return P()

    return spec_tree(params, spec_fn)
