"""MoE token routers.

TPU-native analog of the reference's router stack
(pipegoose/nn/expert_parallel/routers.py:18-189): gate projection,
Switch-style multiplicative training noise (SwitchNoisePolicy,
routers.py:18-34), softmax, top-k selection, Switch aux load-balancing
loss (:73-89), ST-MoE router z-loss (:91-97), and expert-capacity
truncation (:133-143).

Two output forms, both with static shapes (the reference returns a
dynamic dispatching order consumed by index_select loops,
experts.py:99-102, which cannot be jit-compiled):

- ``SigmoidTopKRouter`` and ``SoftmaxTopKRouter`` (the score function
  is the difference; the second counts a model's zero-compute experts
  among its outputs) return ``(T, k)`` expert ids and combine
  weights (``TopKRouting``) and nothing else: no capacity, no dropped
  token. ``experts.grouped_experts`` sorts the picks by expert and runs
  a grouped matrix product over the groups, so memory and work grow
  with ``T * k`` rows. This is the form the expert models of the
  benchmark run (models/glm4_moe_lite.py, laguna.py,
  longcat_flash.py).
- ``TopKRouter`` returns dense one-hot dispatch/combine tensors of
  shape ``(T, E, C)`` (``RouterOutput``; the Mesh-TensorFlow/GShard
  formulation), consumed by two einsums around an ``all_to_all`` in
  ``experts.moe_layer``. With no drops ``C`` is ``T`` and that tensor
  is quadratic in the tokens: right for the tiny sizes of tests and the
  convergence demos (bloom_moe, mixtral), and the reference the grouped
  form is tested against.

Losses are returned functionally in ``RouterOutput`` (no process-global
ExpertContext singleton, expert_context.py:7-32).
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp


class RouterOutput(NamedTuple):
    dispatch: jax.Array  # (T, E, C) one-hot: token t -> slot c of expert e
    combine: jax.Array  # (T, E, C) gate-weighted dispatch
    aux_loss: jax.Array  # scalar, Switch load-balancing loss
    z_loss: jax.Array  # scalar, ST-MoE router z-loss


class TopKRouting(NamedTuple):
    experts: jax.Array  # (T, k) int32 ids over ALL experts, best first
    weights: jax.Array  # (T, k) float32 combine weights
    scores: jax.Array  # (T, E) float32 router scores (counters, tests)


def _pick(router, scores, bias) -> TopKRouting:
    """The ``top_k`` largest of ``scores + bias`` with their weights
    from the scores alone: what the two score functions below share."""
    choice = scores + jax.lax.stop_gradient(bias.astype(jnp.float32))
    _, experts = jax.lax.top_k(choice, router.top_k)
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    if router.normalize:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    return TopKRouting(experts.astype(jnp.int32),
                       weights * router.scaling, scores)


@dataclasses.dataclass(frozen=True)
class SigmoidTopKRouter:
    """Sigmoid scores, selection on score + bias, weights from the
    scores alone (DeepSeek-V3's ``noaux_tc`` with one group; HF
    ``glm4_moe_lite``/``deepseek_v3`` gate): ``s = sigmoid(x W_g)`` in
    float32; the ``top_k`` experts are the largest of ``s + b``; their
    weights are ``s[chosen]``, renormalised over the chosen
    (``normalize``), times ``scaling``. The bias ``b`` moves the
    SELECTION only: it gets no gradient (its update is a training
    recipe's, outside the loss) and the weights never see it. No
    capacity and no drop: every token keeps its ``top_k`` picks."""

    num_experts: int
    top_k: int
    scaling: float = 1.0
    normalize: bool = True

    def __call__(self, params: dict, x: jax.Array) -> TopKRouting:
        """``params``: ``{"gate": {"kernel": (H, E)}, "bias": (E,)}``;
        ``x``: (T, H) flat tokens."""
        scores = jax.nn.sigmoid(jnp.dot(
            x, params["gate"]["kernel"], preferred_element_type=jnp.float32
        ))
        return _pick(self, scores, params["bias"])


@dataclasses.dataclass(frozen=True)
class SoftmaxTopKRouter:
    """Softmax scores over ALL outputs, selection on score + bias,
    weights from the scores alone (HF ``longcat_flash``'s
    ``LongcatFlashTopkRouter``): ``z = softmax(x W_g)`` in float32 over
    ``num_experts`` outputs, which count the zero-compute experts a
    model routes to beside its real ones; the ``top_k`` picks are the
    largest of ``z + b``; their weights are ``z[chosen]`` times
    ``scaling``, renormalised over the chosen only where ``normalize``
    (LongCat-Flash: not). The bias moves the SELECTION only, as
    :class:`SigmoidTopKRouter`'s. Same :class:`TopKRouting` out."""

    num_experts: int
    top_k: int
    scaling: float = 1.0
    normalize: bool = False

    def __call__(self, params: dict, x: jax.Array) -> TopKRouting:
        """``params``: ``{"gate": {"kernel": (H, E)}, "bias": (E,)}``;
        ``x``: (T, H) flat tokens."""
        scores = jax.nn.softmax(jnp.dot(
            x, params["gate"]["kernel"], preferred_element_type=jnp.float32
        ), axis=-1)
        return _pick(self, scores, params["bias"])


@dataclasses.dataclass(frozen=True)
class SwitchNoisePolicy:
    """Multiplicative jitter on router logits during training (reference
    routers.py:18-34): logits *= U[1-eps, 1+eps]."""

    eps: float = 0.1

    def apply(self, key: jax.Array, logits: jax.Array) -> jax.Array:
        noise = jax.random.uniform(
            key, logits.shape, logits.dtype, 1.0 - self.eps, 1.0 + self.eps
        )
        return logits * noise


@dataclasses.dataclass(frozen=True)
class TopKRouter:
    """k-choice router with capacity (reference _TopKRouter,
    routers.py:49-147). Call with the gate params and flat tokens."""

    num_experts: int
    top_k: int = 1
    capacity_factor: float = 1.25
    noise: Optional[SwitchNoisePolicy] = SwitchNoisePolicy()
    normalize_gates: bool = True  # for k > 1, renormalize kept gates

    def capacity(self, n_tokens: int) -> int:
        # ceil, per the GShard/Switch convention — floor would drop tokens
        # under perfectly balanced routing despite the headroom factor
        import math

        return max(1, math.ceil(n_tokens * self.top_k * self.capacity_factor / self.num_experts))

    def __call__(
        self,
        params: dict,
        x: jax.Array,  # (T, H) flat tokens
        key: Optional[jax.Array] = None,
        train: bool = False,
        capacity: Optional[int] = None,
    ) -> RouterOutput:
        T = x.shape[0]
        E, k = self.num_experts, self.top_k
        C = capacity if capacity is not None else self.capacity(T)

        logits = jnp.dot(
            x, params["gate"]["kernel"], preferred_element_type=jnp.float32
        )
        if "bias" in params["gate"]:
            logits = logits + params["gate"]["bias"]
        if train and self.noise is not None:
            if key is None:
                raise ValueError("train-time routing needs a PRNG key for noise")
            logits = self.noise.apply(key, logits)

        probs = jax.nn.softmax(logits, axis=-1)  # (T, E)

        # z-loss on the pre-softmax logits (reference routers.py:91-97)
        z = jax.nn.logsumexp(logits, axis=-1)
        z_loss = jnp.mean(z**2)

        # top-k expert choices per token, by decreasing priority
        gates, idx = jax.lax.top_k(probs, k)  # (T, k)
        masks = jax.nn.one_hot(idx, E, dtype=jnp.float32)  # (T, k, E)

        # Switch aux loss: E * sum_e f_e * P_e, f_e = fraction of tokens
        # whose (any-priority) choice is e, P_e = mean router prob
        # (reference routers.py:73-89)
        f = masks.sum(axis=1).mean(axis=0) / k  # (E,)
        p = probs.mean(axis=0)  # (E,)
        aux_loss = E * jnp.sum(f * p)

        # capacity assignment: priority j slots come after all j' < j
        # (reference's cumsum-position truncation, routers.py:133-143)
        dispatch = jnp.zeros((T, E, C), dtype=jnp.float32)
        combine = jnp.zeros((T, E, C), dtype=jnp.float32)
        offset = jnp.zeros((E,), dtype=jnp.float32)
        kept_gates = []
        kept_slots = []
        for j in range(k):
            m = masks[:, j]  # (T, E)
            pos = jnp.cumsum(m, axis=0) - m + offset[None, :]  # (T, E)
            keep = (pos < C) * m  # (T, E)
            slot = jax.nn.one_hot(
                jnp.sum(pos * m, axis=-1).astype(jnp.int32), C, dtype=jnp.float32
            )  # (T, C) slot index of this token's choice
            d_j = keep[:, :, None] * slot[:, None, :]  # (T, E, C)
            dispatch = dispatch + d_j
            kept_gates.append(gates[:, j] * keep.sum(axis=-1))
            kept_slots.append(d_j)
            offset = offset + m.sum(axis=0)

        g = jnp.stack(kept_gates, axis=1)  # (T, k), zeros where dropped
        if self.normalize_gates and k > 1:
            g = g / jnp.maximum(g.sum(axis=1, keepdims=True), 1e-9)
        for j in range(k):
            combine = combine + g[:, j][:, None, None] * kept_slots[j]

        return RouterOutput(dispatch, combine, aux_loss, z_loss)


def Top1Router(num_experts: int, **kw) -> TopKRouter:
    """Switch-Transformer router (reference Top1Router, routers.py:150-168)."""
    return TopKRouter(num_experts=num_experts, top_k=1, **kw)


def Top2Router(num_experts: int, **kw) -> TopKRouter:
    """GShard-style 2-choice router (reference Top2Router, routers.py:171-189)."""
    return TopKRouter(num_experts=num_experts, top_k=2, **kw)
