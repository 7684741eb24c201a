"""Megatron-style 1D tensor-parallel layers as pure functions.

TPU-native analog of the reference's module surgery
(pipegoose/nn/tensor_parallel/linear.py:17-82, embedding.py:11-42,
layer_norm.py:8-25). Instead of re-classing ``nn.Linear`` in place, a
layer here is a pure function over a params dict, designed to run inside
``shard_map`` with the weight already sharded along the ``tensor`` mesh
axis. Passing ``axis_name=None`` gives the single-device path (the
reference's world-size-1 short-circuit).

Shape conventions (JAX style): kernels are (in_features, out_features) —
transposed from torch. Column parallelism shards the OUT dim, row
parallelism the IN dim, exactly mirroring the reference's dim-0/dim-1
weight slicing (parallelizer.py:105-112).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from pipegoose_tpu.distributed.functional import (
    all_reduce,
    copy_to_tensor_group,
    gather_from_tensor_group,
    reduce_from_tensor_group,
    scatter_to_tensor_group,
)


def _kernel_matmul(params: dict, x: jax.Array) -> jax.Array:
    """The local matmul both parallel linears share, dispatching on the
    leaf layout: ``{"kernel": fp}`` runs the plain dot; a quantized
    leaf ``{"q", "scale"}`` (quant/weights.py) runs the dequant-fused
    matmul so the fp kernel never materializes in HBM. Bias and the
    surrounding collectives are identical either way, which is what
    lets ``quantize_params`` drop into every serving forward —
    prefill, paged decode, and generate() references alike — without
    touching a call site."""
    if "q" in params:
        from pipegoose_tpu.quant.matmul import quantized_matmul

        y = quantized_matmul(x, params["q"], params["scale"])
    else:
        y = jnp.dot(x, params["kernel"], preferred_element_type=jnp.float32)
    return y.astype(x.dtype)


def column_parallel_linear(
    params: dict,
    x: jax.Array,
    axis_name: Optional[str],
    gather_output: bool = False,
    overlap: bool = False,
) -> jax.Array:
    """Y = X @ W[:, shard] (+ b[shard]).

    Reference ColumnParallelLinear.forward (linear.py:40-50): broadcast
    input (f-operator) -> local matmul -> optional all-gather of the
    output's last dim.

    ``overlap=True``: ``x`` is this rank's TOKEN CHUNK of the sequence
    (dim -2 sharded over ``axis_name``) and the gather back to full
    tokens is decomposed into ring ppermute steps interleaved with
    partial matmuls (nn/tensor_parallel/overlap.py) — comm hides behind
    compute, forward and backward. Output is full-token, OUT-sharded,
    numerically equal (fp32 allclose) to the monolithic path on the
    gathered input.
    """
    if overlap:
        if gather_output:
            raise ValueError(
                "column_parallel_linear(overlap=True) keeps the output "
                "OUT-sharded; gather_output is not supported"
            )
        if "q" in params:
            raise ValueError(
                "overlap=True is a training-path option; quantized "
                "(serving) kernels use the monolithic dequant matmul"
            )
        from pipegoose_tpu.nn.tensor_parallel.overlap import (
            column_parallel_linear_overlap,
        )

        return column_parallel_linear_overlap(params, x, axis_name)
    x = copy_to_tensor_group(x, axis_name) if axis_name else x
    y = _kernel_matmul(params, x)
    if "bias" in params and params["bias"] is not None:
        y = y + params["bias"]
    if gather_output and axis_name:
        y = gather_from_tensor_group(y, axis_name, dim=-1)
    return y


def row_parallel_linear(
    params: dict,
    x: jax.Array,
    axis_name: Optional[str],
    input_is_parallel: bool = True,
    overlap: bool = False,
) -> jax.Array:
    """Y = psum_over_shards(X[shard] @ W[shard, :]) + b.

    Reference RowParallelLinear.forward (linear.py:74-82): scatter input
    last dim -> local matmul -> all-reduce (g-operator) -> add full bias.

    ``overlap=True``: the output reduce is decomposed into a ring
    matmul-reduce-scatter (nn/tensor_parallel/overlap.py) — each rank
    returns its TOKEN CHUNK (dim -2) of the fully reduced output, the
    reduce's transfers hidden behind the partial matmuls, forward and
    backward. Equal (fp32 allclose) to the monolithic psum path's rows
    for this chunk.
    """
    if overlap:
        if not input_is_parallel:
            raise ValueError(
                "row_parallel_linear(overlap=True) requires the input "
                "already feature-sharded (input_is_parallel=True)"
            )
        if "q" in params:
            raise ValueError(
                "overlap=True is a training-path option; quantized "
                "(serving) kernels use the monolithic dequant matmul"
            )
        from pipegoose_tpu.nn.tensor_parallel.overlap import (
            row_parallel_linear_overlap,
        )

        return row_parallel_linear_overlap(params, x, axis_name)
    if axis_name and not input_is_parallel:
        x = scatter_to_tensor_group(x, axis_name, dim=-1)
    y = _kernel_matmul(params, x)
    if axis_name:
        y = reduce_from_tensor_group(y, axis_name)
    if "bias" in params and params["bias"] is not None:
        y = y + params["bias"]
    return y


def vocab_parallel_embedding(
    params: dict,
    ids: jax.Array,
    axis_name: Optional[str],
) -> jax.Array:
    """Vocab-sharded embedding lookup.

    Reference ParallelEmbedding.forward (embedding.py:26-42): mask ids
    outside this shard's [start, end) range, look up locally, zero the
    masked rows, all-reduce to combine. Shard range math mirrors
    VocabUtility (_utils.py:4-14).
    """
    weight = params["weight"]
    if not axis_name:
        return jnp.take(weight, ids, axis=0)
    per_shard = weight.shape[0]
    rank = jax.lax.axis_index(axis_name)
    start = rank * per_shard
    in_range = (ids >= start) & (ids < start + per_shard)
    local_ids = jnp.where(in_range, ids - start, 0)
    out = jnp.take(weight, local_ids, axis=0)
    out = jnp.where(in_range[..., None], out, 0)
    # reduce_from (identity backward): with the loss replicated across the
    # tensor axis, a plain psum would transpose to psum and scale weight
    # grads by the TP degree — same hazard the CE below avoids.
    return reduce_from_tensor_group(out, axis_name)


def layer_norm(params: dict, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    """Replicated LayerNorm (reference layer_norm.py:8-25). Stats in f32
    regardless of activation dtype — MXU-friendly bf16 activations keep
    full-precision normalization."""
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    mean = xf.mean(axis=-1, keepdims=True)
    var = xf.var(axis=-1, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    y = y * params["scale"] + params["bias"]
    return y.astype(dtype)


def vocab_parallel_cross_entropy(
    logits: jax.Array,
    targets: jax.Array,
    axis_name: Optional[str],
    valid_size: Optional[int] = None,
) -> jax.Array:
    """Cross-entropy over vocab-sharded logits, per token.

    Reference VocabParallelCrossEntropy (loss.py:14-89): all-reduce(MAX)
    normalization, masked predicted-logit all-reduce(SUM), log-sum-exp
    all-reduce(SUM). Like the reference (and Megatron-LM, credited at
    loss.py:71-73) the backward is analytic — softmax minus one-hot on
    the local shard — via ``custom_vjp``. This both avoids any backward
    collective and sidesteps psum's psum-transpose, which would scale
    grads by the TP degree when the (replicated) loss is differentiated
    on every rank.

    Returns per-token losses; callers take the mean (the reference's
    module wrapper divides by len(targets), loss.py:92-103).

    ``valid_size``: when the vocab was padded for divisibility
    (``pad_vocab``), the true vocab size — padded slots are excluded from
    the log-sum-exp so the loss matches the unpadded model.
    """
    if valid_size is not None:
        logits = mask_padded_vocab(logits, axis_name, valid_size)
    if not axis_name:
        logits = logits.astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        pred = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
        return lse - pred
    return _vp_ce(logits, targets, axis_name)


def chunked_ce_sums(
    hidden: jax.Array,   # (B, T, H) — already shifted to align with labels
    labels: jax.Array,   # (B, T)
    weights: jax.Array,  # (B, T) float mask
    logits_fn,           # (B, C, H) -> (B, C, V/tp) local-shard logits
    axis_name: Optional[str],
    valid_size: Optional[int],
    n_chunks: int,
):
    """(weighted loss sum, weight sum) without ever materializing the
    (B, T, V) logits: scan over T/n_chunks sequence chunks, computing
    each chunk's logits + CE inside ``jax.checkpoint`` so backward
    rematerializes them chunk by chunk. Bounds the logits working set to
    1/n_chunks — at bloom-560m bench shapes the full fp32 buffer is
    ~8 GB (b8 x s1024 x v250880), the single largest HBM consumer of
    the train step.

    The reference computes full logits then its VocabParallelCrossEntropy
    (loss.py:14-89); chunking composes with the same vocab-parallel CE,
    so the TP semantics (incl. padded-vocab masking) are unchanged."""
    b, t, h = hidden.shape
    if t % n_chunks:
        pad = n_chunks - t % n_chunks
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
        weights = jnp.pad(weights, ((0, 0), (0, pad)))  # pad weight 0
        t += pad
    c = t // n_chunks
    hs = hidden.reshape(b, n_chunks, c, h).transpose(1, 0, 2, 3)
    ls = labels.reshape(b, n_chunks, c).transpose(1, 0, 2)
    ws = weights.reshape(b, n_chunks, c).transpose(1, 0, 2)

    def chunk(carry, xs):
        tot, cnt = carry
        h_c, l_c, w_c = xs
        logits = logits_fn(h_c)
        per_tok = vocab_parallel_cross_entropy(
            logits, l_c, axis_name, valid_size=valid_size
        )
        w_c = w_c.astype(per_tok.dtype)
        return (tot + (per_tok * w_c).sum(), cnt + w_c.sum()), None

    zero = jnp.zeros((), jnp.float32)
    (tot, cnt), _ = jax.lax.scan(
        jax.checkpoint(chunk), (zero, zero), (hs, ls, ws)
    )
    return tot, cnt


def mask_padded_vocab(
    logits: jax.Array, axis_name: Optional[str], valid_size: int
) -> jax.Array:
    """Set logits of vocab slots >= valid_size to a large negative, so
    padded slots (zero rows from ``pad_vocab``) can never win a softmax
    or shift the log-sum-exp."""
    shard_v = logits.shape[-1]
    start = jax.lax.axis_index(axis_name) * shard_v if axis_name else 0
    slot = start + jnp.arange(shard_v)
    return jnp.where(slot < valid_size, logits, -1e9)


from functools import partial  # noqa: E402
import numpy as np  # noqa: E402


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def _vp_ce(logits, targets, axis_name):
    return _vp_ce_fwd(logits, targets, axis_name)[0]


def _vp_ce_fwd(logits, targets, axis_name):
    in_dtype = logits.dtype
    logits = logits.astype(jnp.float32)
    shard_v = logits.shape[-1]
    start = jax.lax.axis_index(axis_name) * shard_v

    # numeric stabilization: global max over the sharded vocab dim
    global_max = all_reduce(logits.max(axis=-1), axis_name, op="max")
    shifted = logits - global_max[..., None]

    # log-sum-exp across shards
    exp = jnp.exp(shifted)
    sumexp = all_reduce(exp.sum(axis=-1), axis_name)
    lse = jnp.log(sumexp)

    # predicted logit: only the owning shard contributes
    in_range = (targets >= start) & (targets < start + shard_v)
    local_t = jnp.where(in_range, targets - start, 0)
    pred_local = jnp.take_along_axis(shifted, local_t[..., None], axis=-1)[..., 0]
    pred = all_reduce(jnp.where(in_range, pred_local, 0.0), axis_name)

    softmax_local = exp / sumexp[..., None]
    # dtype carried as a 0-size array (residuals must be JAX types)
    dtype_token = jnp.zeros((0,), dtype=in_dtype)
    return lse - pred, (softmax_local, in_range, local_t, dtype_token)


def _vp_ce_bwd(axis_name, res, g):
    softmax_local, in_range, local_t, dtype_token = res
    shard_v = softmax_local.shape[-1]
    onehot = jax.nn.one_hot(local_t, shard_v, dtype=softmax_local.dtype)
    onehot = onehot * in_range[..., None]
    grad = g[..., None] * (softmax_local - onehot)
    # integer targets carry no tangent
    t_zero = np.zeros(local_t.shape, dtype=jax.dtypes.float0)
    return grad.astype(dtype_token.dtype), t_zero


_vp_ce.defvjp(_vp_ce_fwd, _vp_ce_bwd)
