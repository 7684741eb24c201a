"""Plain BLOOM: forward, loss, gradients and three Adam steps in
straightforward ``jax.numpy`` float32 at matmul precision "highest".

Follows the published model (BigScience BLOOM, arXiv 2211.05100; HF
``modeling_bloom.py``): word embedding -> embedding layer norm -> L
pre-LN blocks (fused query_key_value with columns ordered (head, 3,
head_dim), ALiBi added to the scaled scores, float32 softmax, dense;
MLP h->4h, tanh GELU, 4h->h; residuals from the un-normalised stream)
-> final layer norm -> head tied to the embedding. No kernels, cache,
batching or sharding; imports nothing of the program.

``precision`` "float32" is the reference. "fp8" is the CONTROL: the
same mathematics with every matmul operand rounded to an 8-bit float
(e4m3) under a per-tensor scale — the nearest step below the bfloat16 the
configurations state, and what a later PR would be tempted by.
Departures from HF, each without effect on the values: layers are
stacked on a leading axis and walked with ``lax.scan``; the loss is
taken in chunks of positions so the (S, V) logits never exist at once;
``jax.checkpoint`` per block bounds activation memory.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F8_BITS = (4, 3)       # e4m3: 4 exponent bits, 3 mantissa bits
F8_MAX = 240.0         # its largest finite value


def alibi_slopes(n_head: int) -> np.ndarray:
    """Press et al. 2021: a geometric sequence from 2**(-8/n) for a
    power-of-two head count, interleaved extras otherwise."""
    def pow2(n):
        start = 2.0 ** (-(2.0 ** -(math.log2(n) - 3)))
        return [start ** (i + 1) for i in range(n)]

    if math.log2(n_head).is_integer():
        return np.asarray(pow2(n_head), np.float32)
    closest = 2 ** math.floor(math.log2(n_head))
    extra = pow2(2 * closest)[0::2][: n_head - closest]
    return np.asarray(pow2(closest) + extra, np.float32)


def _round_fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    # reduce_precision, not astype(float8).astype(float32): XLA may drop
    # such a round trip on the TPU
    q = jax.lax.reduce_precision(x / scale, *F8_BITS) * scale
    # straight-through: backward multiplies by the rounded operands too
    return x + jax.lax.stop_gradient(q - x)


def _mm(spec, a, b, precision):
    if precision == "fp8":
        a, b = _round_fp8(a), _round_fp8(b)
    elif precision != "float32":
        raise ValueError(f"unknown reference precision {precision!r}")
    return jnp.einsum(spec, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def _layer_norm(x, scale, bias, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def _gelu(x):
    return x * 0.5 * (1.0 + jnp.tanh(0.79788456 * x * (1.0 + 0.044715 * x * x)))


def _block(x, lw, sizes, precision):
    b, s, h = x.shape
    nh = sizes["n_head"]
    hd = h // nh
    eps = sizes["layer_norm_epsilon"]
    ln1 = _layer_norm(x, lw["ln1_scale"], lw["ln1_bias"], eps)
    fused = _mm("bsh,hk->bsk", ln1, lw["qkv_w"], precision) + lw["qkv_b"]
    fused = fused.reshape(b, s, nh, 3, hd)
    q, k, v = fused[..., 0, :], fused[..., 1, :], fused[..., 2, :]
    scores = _mm("bqnd,bknd->bnqk", q, k, precision) / math.sqrt(hd)
    pos = jnp.arange(s, dtype=jnp.float32)
    scores = scores + jnp.asarray(alibi_slopes(nh))[None, :, None, None] \
        * pos[None, None, None, :]
    causal = pos[None, :] <= pos[:, None]
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = _mm("bnqk,bknd->bqnd", probs, v, precision).reshape(b, s, h)
    x = x + _mm("bsh,hk->bsk", ctx, lw["out_w"], precision) + lw["out_b"]
    ln2 = _layer_norm(x, lw["ln2_scale"], lw["ln2_bias"], eps)
    up = _gelu(_mm("bsh,hk->bsk", ln2, lw["up_w"], precision) + lw["up_b"])
    return x + _mm("bsk,kh->bsh", up, lw["down_w"], precision) + lw["down_b"]


_LAYER_LEAVES = ("ln1_scale", "ln1_bias", "qkv_w", "qkv_b", "out_w", "out_b",
                 "ln2_scale", "ln2_bias", "up_w", "up_b", "down_w", "down_b")


def hidden(w, ids, sizes, precision="float32"):
    """(B, S) token ids -> (B, S, H) final-layer-norm output."""
    eps = sizes["layer_norm_epsilon"]
    x = _layer_norm(w["embed"][ids], w["embed_ln_scale"], w["embed_ln_bias"],
                    eps)
    block = jax.checkpoint(partial(_block, sizes=sizes, precision=precision))
    x, _ = jax.lax.scan(lambda c, lw: (block(c, lw), None), x,
                        {n: w[n] for n in _LAYER_LEAVES})
    return _layer_norm(x, w["lnf_scale"], w["lnf_bias"], eps)


def logits(w, hid, precision="float32"):
    return _mm("...h,vh->...v", hid, w["embed"], precision)


def loss_sum(w, ids, sizes, precision="float32", chunk=512):
    """Sum over rows and positions of the next-token cross entropy."""
    hid = hidden(w, ids, sizes, precision)[:, :-1]
    tgt = ids[:, 1:]
    rows, n = tgt.shape
    pad = (-n) % chunk
    steps = (n + pad) // chunk
    hid = jnp.pad(hid, ((0, 0), (0, pad), (0, 0)))
    tgt = jnp.pad(tgt, ((0, 0), (0, pad)))
    live = jnp.pad(jnp.ones((n,), jnp.float32), (0, pad))

    @jax.checkpoint
    def one(args):
        hc, tc, lc = args               # (rows, chunk, H), (rows, chunk), (chunk,)
        lg = logits(w, hc, precision)
        lse = jax.nn.logsumexp(lg, axis=-1)
        picked = jnp.take_along_axis(lg, tc[..., None], axis=-1)[..., 0]
        return ((lse - picked) * lc).sum()

    return jax.lax.map(one, (
        hid.reshape(rows, steps, chunk, -1).swapaxes(0, 1),
        tgt.reshape(rows, steps, chunk).swapaxes(0, 1),
        live.reshape(steps, chunk))).sum()


def next_token_scores(w, tokens, picks, sizes, precision="float32"):
    """One sequence (1-D, prompt then generated tokens; right padding is
    harmless because attention is causal). For every position i, over
    the logits of the token that follows it: how far the logit of
    ``picks[i]`` lies below the best one, and which token is best."""
    hid = hidden(w, tokens[None], sizes, precision)[0]
    lg = logits(w, hid, precision)                    # (S, V)
    own = jnp.take_along_axis(lg, picks[:, None], axis=-1)[:, 0]
    return lg.max(-1) - own, lg.argmax(-1)


def leaf_norms(tree: dict) -> dict:
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


def adam_steps(make_w0, batches, sizes, lr, precision="float32", rows_per_call=1,
               b1=0.9, b2=0.999, eps=1e-8, store_dtype=None, place=None):
    """Follow ``len(batches)`` Adam steps (Kingma & Ba, bias-corrected,
    as ``optax.adam``) from float32 copies of the weights ``make_w0()``
    returns (called again at the end, so no second copy stays alive). Gradients are
    accumulated over blocks of ``rows_per_call`` rows so one block's
    activations are all that is alive. ``store_dtype``: the dtype the
    configuration keeps its parameters in — each update's result is
    rounded to it (bfloat16 parameters near 1.0 do not move under a
    3e-4 step; that is the configuration, not a fault), all arithmetic
    staying float32. Returns the loss of each step,
    the per-leaf norm of the first step's gradient, and the per-leaf
    norm of the parameters' change after the last step."""
    place = place or (lambda t: t)
    n_rows, seq = batches[0].shape
    n_tok = n_rows * (seq - 1)

    @jax.jit
    def grad_all(w, batch):
        # one block of rows at a time, sequentially: the backward pass of
        # the map adds each block's gradient into one accumulator
        def total(p):
            one = jax.checkpoint(
                lambda rows: loss_sum(p, rows, sizes, precision))
            return jax.lax.map(one, batch.reshape(
                n_rows // rows_per_call, rows_per_call, seq)).sum() / n_tok
        return jax.value_and_grad(total)(w)

    @partial(jax.jit, donate_argnums=(0, 1, 2))
    def update(w, mu, nu, g, t):
        def one(p, m, n, gg):
            m = b1 * m + (1 - b1) * gg
            n = b2 * n + (1 - b2) * gg * gg
            mhat = m / (1 - b1 ** t)
            nhat = n / (1 - b2 ** t)
            p = p - lr * mhat / (jnp.sqrt(nhat) + eps)
            if store_dtype is not None:
                # not astype().astype(): XLA may drop that round trip
                info = jnp.finfo(store_dtype)
                p = jax.lax.reduce_precision(p, info.nexp, info.nmant)
            return p, m, n
        out = {k: one(w[k], mu[k], nu[k], g[k]) for k in w}
        return tuple({k: o[i] for k, o in out.items()} for i in range(3))

    # x * 0, not zeros_like: the moments keep the gradient's placement
    # where the reference is spread over several chips
    zeros = jax.jit(lambda t: jax.tree_util.tree_map(lambda x: x * 0.0, t))
    w = jax.jit(lambda t: {k: v.astype(jnp.float32)
                           for k, v in t.items()})(place(make_w0()))
    mu = nu = None
    losses, grad_norm = [], None
    for t, batch in enumerate(batches, start=1):
        val, g = grad_all(w, jnp.asarray(batch))
        losses.append(float(val))
        if grad_norm is None:
            grad_norm = {k: float(v) for k, v in
                         jax.jit(leaf_norms)(g).items()}
            mu, nu = zeros(g), zeros(g)
        w, mu, nu = update(w, mu, nu, g, jnp.float32(t))
        del g
    delta = jax.jit(lambda a, b: leaf_norms(
        {k: a[k] - b[k].astype(jnp.float32) for k in a}))(w, place(make_w0()))
    return {"losses": losses, "grad_norm": grad_norm,
            "delta_norm": {k: float(v) for k, v in delta.items()}}
