"""Expert computation: grouped over the experts held here, or dense
one-hot dispatch around an ``all_to_all``.

TPU-native analog of the reference's ``Experts``/``ExpertLayer``
(pipegoose/nn/expert_parallel/experts.py:15-102, layers.py:26-48). The
reference holds num_experts/tp experts per rank and dispatches by
boolean ``nonzero`` index-selects followed by an all_reduce combine
(experts.py:41-80) — dynamic shapes, and every rank ships every token.

``grouped_experts`` is the form the benchmark's expert model runs. The
layer is told which experts it holds (``first``, ``count``) and is
given the router's ``(T, k)`` picks over ALL experts:

    picks on held experts, sorted by expert   (sizes are data;
    rows gathered in that order -> (T*k, H)    the shape is static)
    grouped matrix products over the groups   (``lax.ragged_dot``)
    rows back in pick order, weighted, summed over k -> (T, H)

No capacity and no dropped token; no tensor grows with tokens x experts.
What absent experts would add is left out: the caller's share of the
layer. On one chip the layer runs without an exchange.

``moe_layer`` is the GShard dataflow with static shapes:

    local tokens --einsum dispatch--> (E, C, H)
    all_to_all over the expert axis  -> (E_local, ep*C, H)
    per-expert MLP (one batched einsum on the MXU)
    all_to_all back                  -> (E, C, H)
    --einsum combine--> local tokens

Its one-hot ``(T, E, C)`` tensors are quadratic in the tokens when
nothing may be dropped, so it serves the small models (bloom_moe,
mixtral) and is the reference ``grouped_experts`` is tested against.
Expert grads stay local to the owning rank (the reference's
``is_expert``/EXPERT_DATA bookkeeping, experts.py:35-39 +
data_parallel.py:35-43, falls out of the sharding specs instead).
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from pipegoose_tpu.distributed.functional import all_to_all
from pipegoose_tpu.nn.expert_parallel.routers import (
    RouterOutput,
    TopKRouter,
    TopKRouting,
)


def init_experts(
    key: jax.Array,
    num_local_experts: int,
    hidden: int,
    ffn: int,
    dtype=jnp.float32,
    std: float = 0.02,
) -> dict:
    """Expert-stacked MLP params: leading dim = local experts."""
    k1, k2 = jax.random.split(key)
    return {
        "up": {
            "kernel": (jax.random.normal(k1, (num_local_experts, hidden, ffn)) * std).astype(dtype),
            "bias": jnp.zeros((num_local_experts, ffn), dtype),
        },
        "down": {
            "kernel": (jax.random.normal(k2, (num_local_experts, ffn, hidden)) * std).astype(dtype),
            "bias": jnp.zeros((num_local_experts, hidden), dtype),
        },
    }


def expert_mlp_specs(expert_axis: str = "expert", tensor_axis: Optional[str] = "tensor"):
    """PartitionSpecs for stacked expert MLP params (L, E, in, out):
    experts over the expert axis, FFN dim Megatron-sharded over tensor.
    Single source consumed by bloom_moe.moe_specs and ExpertParallel."""
    from jax.sharding import PartitionSpec as P

    t = tensor_axis
    e = expert_axis
    return {
        "up": {"kernel": P(None, e, None, t), "bias": P(None, e, t)},
        "down": {"kernel": P(None, e, t, None), "bias": P(None, e, None)},
    }


def expert_mlp(
    params: dict,
    x: jax.Array,
    act: Callable = jax.nn.gelu,
    tp_axis: Optional[str] = None,
) -> jax.Array:
    """(E_local, S, H) -> (E_local, S, H), one batched einsum per matmul.

    With ``tp_axis``, each expert's FFN dim is additionally Megatron-
    sharded over the tensor axis (up column / down row + reduce) — the
    4D interaction the reference only gestures at via its
    num_experts % tp == 0 assert (expert_parallel.py:34)."""
    from pipegoose_tpu.distributed.functional import (
        copy_to_tensor_group,
        reduce_from_tensor_group,
    )

    if tp_axis is not None:
        # f-operator: identity fwd, psum bwd — without it each tensor
        # rank's input cotangent is only its local FFN-shard partial and
        # every grad upstream of the MoE layer de-syncs across ranks
        x = copy_to_tensor_group(x, tp_axis)
    h = jnp.einsum("esh,ehf->esf", x, params["up"]["kernel"],
                   preferred_element_type=jnp.float32).astype(x.dtype)
    h = act(h + params["up"]["bias"][:, None, :])
    out = jnp.einsum("esf,efh->esh", h, params["down"]["kernel"],
                     preferred_element_type=jnp.float32).astype(x.dtype)
    if tp_axis is not None:
        out = reduce_from_tensor_group(out, tp_axis)
    return out + params["down"]["bias"][:, None, :]


def moe_layer(
    expert_params: dict,
    x: jax.Array,  # (..., H) local tokens
    routing: RouterOutput,
    axis_name: Optional[str],
    act: Optional[Callable] = jax.nn.gelu,
    tp_axis: Optional[str] = None,
    mlp_fn: Optional[Callable] = None,
) -> jax.Array:
    """Dispatch -> expert MLP -> combine. ``expert_params`` hold this
    rank's E_local experts (stacked leading dim); ``routing`` covers the
    E = E_local * ep global experts."""
    orig_shape = x.shape
    h = x.reshape(-1, orig_shape[-1])  # (T, H)
    dispatch, combine = routing.dispatch, routing.combine
    E = dispatch.shape[1]
    e_local = jax.tree_util.tree_leaves(expert_params)[0].shape[0]
    ep = 1 if axis_name is None else jax.lax.axis_size(axis_name)
    if e_local * ep != E:
        raise ValueError(
            f"router has {E} experts but params hold {e_local} x ep={ep}"
        )

    # (T,H) -> (E, C, H): capacity-bucketed expert inputs
    buckets = jnp.einsum("tec,th->ech", dispatch.astype(h.dtype), h)
    if axis_name is not None and ep > 1:
        # each rank keeps its E_local experts, gains every rank's C slots
        buckets = all_to_all(buckets, axis_name, split_dim=0, concat_dim=1)
    if mlp_fn is not None:
        # custom per-expert computation, e.g. Mixtral's SwiGLU
        # (models/mixtral.py:_swiglu_experts)
        out = mlp_fn(expert_params, buckets, tp_axis)
    else:
        out = expert_mlp(expert_params, buckets, act, tp_axis=tp_axis)
    if axis_name is not None and ep > 1:
        out = all_to_all(out, axis_name, split_dim=1, concat_dim=0)
    # (E, C, H) -> (T, H), gate-weighted
    y = jnp.einsum("tec,ech->th", combine.astype(out.dtype), out)
    return y.reshape(orig_shape)


# --------------------------------------------------------------------------
# grouped form: sort the picks by expert, grouped matrix products
# --------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _pick_rows(x: jax.Array, order: jax.Array, inv: jax.Array, k: int):
    """Token rows in sorted pick order: ``y[i] = x[order[i] // k]``, pick
    ``p`` being choice ``p % k`` of token ``p // k`` and ``order`` a
    permutation of the picks with inverse ``inv``. The transpose of a
    gather is a scatter-add; here it is the gather by ``inv`` and a sum
    over each token's ``k`` rows, which is what the backward runs."""
    return jnp.take(x, order // k, axis=0)


def _pick_rows_fwd(x, order, inv, k):
    return jnp.take(x, order // k, axis=0), inv


def _pick_rows_bwd(k, inv, g):
    back = jnp.take(g, inv, axis=0)
    return back.reshape(-1, k, g.shape[-1]).sum(axis=1), None, None


_pick_rows.defvjp(_pick_rows_fwd, _pick_rows_bwd)


@jax.custom_vjp
def _unsort_rows(x: jax.Array, order: jax.Array, inv: jax.Array):
    """Sorted rows back in pick order: ``y[p] = x[inv[p]]``; backward
    is the gather by ``order``."""
    return jnp.take(x, inv, axis=0)


def _unsort_rows_fwd(x, order, inv):
    return jnp.take(x, inv, axis=0), order


def _unsort_rows_bwd(order, g):
    return jnp.take(g, order, axis=0), None, None


_unsort_rows.defvjp(_unsort_rows_fwd, _unsort_rows_bwd)


def _glu_grouped(act: Callable, expert_params: dict, rows: jax.Array,
                 sizes: jax.Array) -> jax.Array:
    """down(act(gate x) * up x) for rows sorted by expert: row block
    ``g`` (``sizes[g]`` rows) meets expert ``g``'s matrices. Kernels are
    stacked ``(E_held, in, out)``. Rows past ``sizes.sum()`` belong to
    no group and their result is unspecified."""

    def mm(x, w):
        # the result in the rows' dtype straight from the product (the
        # chip's grouped kernel accumulates in float32 either way): no
        # float32 (T*k, width) buffer and no pass to narrow it
        return jax.lax.ragged_dot(x, w, sizes, preferred_element_type=x.dtype)

    g = mm(rows, expert_params["gate"]["kernel"])
    u = mm(rows, expert_params["up"]["kernel"])
    return mm(act(g) * u, expert_params["down"]["kernel"])


def swiglu_grouped(
    expert_params: dict, rows: jax.Array, sizes: jax.Array
) -> jax.Array:
    """SwiGLU experts over rows sorted by expert: ``down(silu(gate x) *
    up x)`` (:func:`_glu_grouped`)."""
    return _glu_grouped(jax.nn.silu, expert_params, rows, sizes)


def reglu_grouped(
    expert_params: dict, rows: jax.Array, sizes: jax.Array
) -> jax.Array:
    """ReGLU experts over rows sorted by expert: ``down(relu(gate x) *
    up x)``, the same three grouped products (:func:`_glu_grouped`)."""
    return _glu_grouped(jax.nn.relu, expert_params, rows, sizes)


def grouped_experts(
    expert_params: dict,
    x: jax.Array,  # (T, H) flat tokens
    routing: TopKRouting,
    held: tuple,  # (first, count): the experts whose params these are
    mlp_fn: Callable = swiglu_grouped,
    tp_axis: Optional[str] = None,
):
    """The held experts' part of ``sum_j w[t, j] * expert[e[t, j]](x[t])``.
    Returns ``(y (T, H), rows_per_expert (count,) int32)``.

    Shapes are static at ``T * k`` rows, the most that can fall on the
    held experts; how many do, and on which, is data (``sizes``). With
    ``tp_axis`` each expert's inner width is sharded over the tensor
    axis (gate/up column, down row + reduce)."""
    from pipegoose_tpu.distributed.functional import (
        copy_to_tensor_group,
        reduce_from_tensor_group,
    )

    first, count = held
    t, k = routing.experts.shape
    n = t * k
    with jax.named_scope("moe.dispatch"):
        local = routing.experts.reshape(n) - first
        here = (local >= 0) & (local < count)
        # picks on absent experts sort behind every group
        key = jnp.where(here, local, count)
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        inv = jnp.argsort(order).astype(jnp.int32)
        sizes = jnp.bincount(key, length=count + 1)[:count].astype(jnp.int32)
        live = (jnp.arange(n) < sizes.sum())[:, None]
        # rows of no group: zero in, so nothing unspecified flows back
        rows = jnp.where(live, _pick_rows(x, order, inv, k), 0)
    with jax.named_scope("moe.experts"):
        if tp_axis is not None:
            rows = copy_to_tensor_group(rows, tp_axis)
        out = mlp_fn(expert_params, rows, sizes)
        if tp_axis is not None:
            out = reduce_from_tensor_group(out, tp_axis)
        out = jnp.where(live, out, 0)
    with jax.named_scope("moe.combine"):
        back = _unsort_rows(out, order, inv).reshape(t, k, -1)
        w = jnp.where(here.reshape(t, k), routing.weights, 0.0)
        y = (back.astype(jnp.float32) * w[:, :, None]).sum(axis=1)
    return y.astype(x.dtype), sizes
