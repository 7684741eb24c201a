"""BLOOM-MoE: BLOOM with Switch/Mixtral-style MoE MLPs.

The reference's MoE path wraps BLOOM and swaps chosen ``mlp`` modules
for ExpertLayers (expert_parallel.py:53-80, convergence test
tests/convergence/run_ep.py). Here the MoE variant is a first-class
model sharing BLOOM's attention/embedding/LN code: every block's MLP is
a routed expert layer (the Switch-Transformer layout; a Mixtral-style
config is this model with top_k=2), dispatched with static shapes over
the ``expert`` mesh axis and optionally Megatron-sharded over ``tensor``
inside each expert.

Router aux/z losses are returned functionally (summed over layers by the
scan), not via a process singleton (vs expert_context.py:7-32).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from pipegoose_tpu.models import bloom as _bloom
from pipegoose_tpu.models.bloom import (
    BloomConfig,
    attention_bias,
    embed_tokens,
    layer_norm,
    logits_fn,
)
from pipegoose_tpu.nn.expert_parallel.experts import moe_layer
from pipegoose_tpu.nn.expert_parallel.loss import ExpertLoss
from pipegoose_tpu.nn.expert_parallel.routers import TopKRouter
from pipegoose_tpu.nn.tensor_parallel.layers import vocab_parallel_cross_entropy
from pipegoose_tpu.ops.flash_attention import remat_policy


@dataclasses.dataclass(frozen=True)
class BloomMoEConfig(BloomConfig):
    num_experts: int = 8
    top_k: int = 1
    capacity_factor: float = 1.25
    router_noise_eps: float = 0.1
    aux_loss_weight: float = 0.01
    z_loss_weight: float = 0.001
    ffn_mult: int = 4

    def router(self) -> TopKRouter:
        from pipegoose_tpu.nn.expert_parallel.routers import SwitchNoisePolicy

        noise = SwitchNoisePolicy(self.router_noise_eps) if self.router_noise_eps else None
        return TopKRouter(
            num_experts=self.num_experts,
            top_k=self.top_k,
            capacity_factor=self.capacity_factor,
            noise=noise,
        )


def init_params(config: BloomMoEConfig, key: jax.Array) -> dict:
    """Fresh MoE init: BLOOM trunk + independently-drawn expert stacks +
    router gate. (To *upcycle* an existing dense model into MoE with the
    dense MLP as every expert's template — the reference's semantics —
    use ExpertParallel.from_dense.)"""
    kd, ke, kr = jax.random.split(key, 3)
    params = _bloom.init_params(config, kd)
    h, L, E, F = (
        config.hidden_size,
        config.n_layer,
        config.num_experts,
        config.ffn_mult * config.hidden_size,
    )
    std, dt = config.initializer_range, config.dtype
    k1, k2 = jax.random.split(ke)
    params["blocks"]["moe"] = {
        "up": {
            "kernel": (jax.random.normal(k1, (L, E, h, F)) * std).astype(dt),
            "bias": jnp.zeros((L, E, F), dt),
        },
        "down": {
            "kernel": (jax.random.normal(k2, (L, E, F, h)) * std).astype(dt),
            "bias": jnp.zeros((L, E, h), dt),
        },
    }
    params["blocks"]["router"] = {
        "gate": {"kernel": (jax.random.normal(kr, (L, h, E)) * std).astype(dt)}
    }
    del params["blocks"]["mlp"]
    return params


def _moe_block(
    blk: dict,
    x: jax.Array,
    bias: dict,
    key: Optional[jax.Array],
    config: BloomMoEConfig,
    tp_axis: Optional[str],
    ep_axis: Optional[str],
    train: bool,
):
    eps = config.layer_norm_epsilon
    ln1 = layer_norm(blk["ln_1"], x, eps)
    x = x + _bloom._attention(blk["attn"], ln1, bias, config, tp_axis)
    ln2 = layer_norm(blk["ln_2"], x, eps)

    router = config.router()
    flat = ln2.reshape(-1, ln2.shape[-1])
    routing = router(blk["router"], flat, key=key, train=train)
    y = moe_layer(
        blk["moe"],
        ln2,
        routing,
        axis_name=ep_axis,
        act=_bloom.bloom_gelu,
        tp_axis=tp_axis,
    )
    return x + y, routing.aux_loss, routing.z_loss


def forward_hidden(
    params: dict,
    input_ids: jax.Array,
    attention_mask: Optional[jax.Array],
    config: BloomMoEConfig,
    tp_axis: Optional[str] = None,
    ep_axis: Optional[str] = None,
    rng: Optional[jax.Array] = None,
    train: bool = False,
):
    """Returns (hidden (B,S,H), aux_losses (L,), z_losses (L,))."""
    b, s = input_ids.shape
    if attention_mask is None:
        attention_mask = jnp.ones((b, s), dtype=jnp.int32)
    x = embed_tokens(params, input_ids, config, tp_axis)
    bias = attention_bias(attention_mask, config)

    if rng is None:
        if train and config.router_noise_eps:
            raise ValueError(
                "train=True with router noise needs an explicit rng (fold in "
                "the step count and data/expert axis indices); a fixed "
                "default key would apply the SAME perturbation every step"
            )
        rng = jax.random.PRNGKey(0)  # inert: noise disabled on this path
    layer_keys = jax.random.split(rng, config.n_layer)

    def scan_fn(carry, blk_and_key):
        blk, key = blk_and_key
        out, aux, z = _moe_block(
            blk, carry, bias, key, config, tp_axis, ep_axis, train,
        )
        return out, (aux, z)

    step = (jax.checkpoint(scan_fn, policy=remat_policy())
            if config.remat else scan_fn)
    x, (aux, z) = jax.lax.scan(step, x, (params["blocks"], layer_keys))
    return layer_norm(params["ln_f"], x, config.layer_norm_epsilon), aux, z


def loss_fn(
    params: dict,
    input_ids: jax.Array,
    attention_mask: Optional[jax.Array],
    labels: jax.Array,
    config: BloomMoEConfig,
    tp_axis: Optional[str] = None,
    ep_axis: Optional[str] = None,
    rng: Optional[jax.Array] = None,
    train: bool = True,
) -> jax.Array:
    hidden, aux, z = forward_hidden(
        params, input_ids, attention_mask, config, tp_axis, ep_axis, rng, train
    )
    logits = logits_fn(params, hidden, tp_axis)
    per_tok = vocab_parallel_cross_entropy(
        logits[:, :-1], labels[:, 1:], tp_axis, valid_size=config.valid_vocab_size
    )
    if attention_mask is not None:
        w = attention_mask[:, 1:].astype(per_tok.dtype)
        task = (per_tok * w).sum() / jnp.maximum(w.sum(), 1)
    else:
        task = per_tok.mean()
    return ExpertLoss(config.aux_loss_weight, config.z_loss_weight)(task, aux, z)


def moe_specs(
    params: dict, tp_axis: str = "tensor", ep_axis: str = "expert"
) -> dict:
    """tp_specs for the shared trunk + expert/router specs: experts over
    the expert axis, expert FFN over tensor, router gate replicated."""
    from jax.sharding import PartitionSpec as P

    from pipegoose_tpu.nn.expert_parallel.experts import expert_mlp_specs
    from pipegoose_tpu.nn.parallel import spec_tree

    base_mapping = _bloom.tp_mapping(tp_axis)
    especs = expert_mlp_specs(ep_axis, tp_axis)

    def spec_fn(path, x):
        if "blocks/moe" in path:
            proj = "up" if "/up/" in path else "down"
            kind = "kernel" if path.endswith("kernel") else "bias"
            return especs[proj][kind]
        if "blocks/router" in path:
            return P()
        if "blocks" in path:
            base = base_mapping.spec_for(path, x.ndim - 1)
            return P(None, *base)
        return base_mapping.spec_for(path, x.ndim)

    return spec_tree(params, spec_fn)
