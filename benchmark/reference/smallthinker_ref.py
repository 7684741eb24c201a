"""Plain SmallThinker-21BA3B-Instruct (``model_type: smallthinker``):
the forward pass in straightforward ``jax.numpy`` float32 at matmul
precision "highest". No kernel, no cache, no paging, no batching, no
sort or gather of rows by expert; imports nothing of the program.

Follows the published config
(https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct/blob/main/config.json)
and the model's description (arXiv 2507.20984); RMSNorm, pre-norm
residuals, no bias, an untied head, every layer sparse. One sequence of
S tokens at a time, layer ``l``, positions ``t`` = 0..S-1:

    x      = RMS(h; g_1)
    z      = x W_r                              (S, 64) router logits
    e      = top6(z);  w = softmax(z[e])        the router reads the
                                                ATTENTION's input
    q, k, v = x W_q, x W_k, x W_v               28 / 4 / 4 heads of 128
    if rope_layout[l] == 1: q, k = RoPE(q, t), RoPE(k, t)
                                                layout 0: NO position
                                                encoding at all
    keep   = k_pos <= q_pos and (sliding_window_layout[l] == 0
                                 or q_pos - k_pos < sliding_window_size)
    a      = softmax(q k^T / sqrt(128) + mask(keep)) v
                                                query head i reads KV
                                                head i // 7
    h      = h + a W_o
    y      = RMS(h; g_2)
    h      = h + sum_j w_j (relu(y W_gate[e_j]) * (y W_up[e_j])) W_down[e_j]

then ``logits = RMS(h_L; g_f) W_head``. The six chosen experts are those
of which fewer than six others have a larger logit (a count, not a
sort); the mask is built densely.

``precision`` "float32" is the reference. "fp8" is the CONTROL: the same
mathematics with every matmul operand rounded to an 8-bit float (e4m3)
under a per-tensor scale, the nearest step below the bfloat16 the
configuration states (``bloom_ref._mm``, shared with that reference).

Departures from the published description, none of which changes a
value: the weights arrive in the configuration's dtype and are widened
to float32 where they are used, a layer, and within a layer an expert,
at a time (one layer's 64 experts are 1.5 GB in float32, the twelve
18 GB); the loop over a token's six picks is a loop over all 64 experts,
EVERY expert applied to EVERY token and multiplied by its ``w`` (zero
where it was not chosen), so that nothing is gathered by expert;
attention is taken over blocks of queries, the experts and the head
over blocks of rows, so neither the (heads, S, S) scores nor the (S, V)
logits exist at once and 9,728 positions fit. Not in the config and set
by the description (the configuration file lists them under
``assumed``): the router's input, ReGLU, no dense layer, no secondary
experts, no bias, no QK-norm, rotate-half pairing.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

# the float32 "highest" product with its fp8 control: the other
# reference's, shared so that both controls round alike
from benchmark.reference.bloom_ref import _mm

Q_BLOCK = 256          # queries a block of attention
ROW_BLOCK = 1024       # rows a block of the experts and of the head


def _f32(x):
    return x.astype(jnp.float32)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) \
        * _f32(scale)


def _rope(x, theta: float):
    """Plain rotary on every dim of x (S, heads, hd), positions 0..S-1,
    rotate-half pairing."""
    hd = x.shape[-1]
    inv = (1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
           ).astype(np.float32)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]      # (S, 1, hd)
    half = jnp.concatenate([-x[..., hd // 2:], x[..., :hd // 2]], -1)
    return x * jnp.cos(ang) + half * jnp.sin(ang)


def _blocks(fn, xs, block):
    """``fn`` over blocks of ``block`` rows of every array of the tuple
    ``xs`` (each (S, ..)), one block at a time."""
    s = xs[0].shape[0]
    pad = (-s) % block
    cut = tuple(jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1)).reshape(
        (-1, block) + x.shape[1:]) for x in xs)
    out = jax.lax.map(lambda args: fn(*args), cut)
    return jax.tree_util.tree_map(
        lambda o: o.reshape((-1,) + o.shape[2:])[:s], out)


def routing_weights(x, router_w, sizes, precision="float32"):
    """(S, E) float32: an expert's combine weight for each token, zero
    where the token did not choose it; ``x`` the FIRST norm's output."""
    k = sizes["moe_num_active_primary_experts"]
    z = _mm("sh,he->se", x, _f32(router_w), precision)
    larger = (z[:, None, :] > z[:, :, None]).sum(-1)             # (S, E)
    chosen = larger < k
    # softmax over the chosen logits (= the softmax over all of them,
    # renormalised over the chosen: norm_topk_prob)
    e = jnp.where(chosen, jnp.exp(z - z.max(-1, keepdims=True)), 0.0)
    return e / e.sum(-1, keepdims=True)


def _attention(a, w, i, sizes, precision):
    """Attention of layer ``i`` on the normed input ``a`` (S, H)."""
    s = a.shape[0]
    hd, kv = sizes["head_dim"], sizes["num_key_value_heads"]
    nh = sizes["num_attention_heads"]
    q = _mm("sh,hk->sk", a, _f32(w[f"l{i}_q"]), precision).reshape(s, nh, hd)
    k = _mm("sh,hk->sk", a, _f32(w[f"l{i}_k"]), precision).reshape(s, kv, hd)
    v = _mm("sh,hk->sk", a, _f32(w[f"l{i}_v"]), precision).reshape(s, kv, hd)
    if sizes["rope_layout"][i]:
        q, k = _rope(q, sizes["rope_theta"]), _rope(k, sizes["rope_theta"])
    windowed = bool(sizes["sliding_window_layout"][i])
    # every query head beside its KV head: (S, KV, g, hd)
    q = q.reshape(s, kv, nh // kv, hd)
    k_pos = jnp.arange(s)

    def block(qb, q_pos):
        sc = _mm("qkgd,nkd->kgqn", qb, k, precision) / math.sqrt(hd)
        keep = k_pos[None, :] <= q_pos[:, None]
        if windowed:
            keep = keep & (q_pos[:, None] - k_pos[None, :]
                           < sizes["sliding_window"])
        sc = jnp.where(keep, sc, -jnp.inf)
        return _mm("kgqn,nkd->qkgd", jax.nn.softmax(sc, axis=-1), v,
                   precision)

    # a padded query stands at position 0 and sees key 0, so its softmax
    # is finite; it is cut off
    ctx = _blocks(block, (q, k_pos), Q_BLOCK).reshape(s, nh * hd)
    return _mm("sk,kh->sh", ctx, _f32(w[f"l{i}_o"]), precision)


def _reglu(x, gate, up, down, precision):
    g = _mm("sh,hf->sf", x, _f32(gate), precision)
    u = _mm("sh,hf->sf", x, _f32(up), precision)
    return _mm("sf,fh->sh", jax.nn.relu(g) * u, _f32(down), precision)


def moe(y, rw, w, i, sizes, precision="float32"):
    """The routed sum of layer ``i`` on ``y`` (S, H) under the combine
    weights ``rw`` (S, E) over ALL experts: the part the held experts
    give (all of them, in the served cut)."""
    first, count = sizes["experts_held"]

    def one(acc, ex):
        gate, up, down, we = ex
        return acc + we[:, None] * _reglu(y, gate, up, down, precision), None

    out, _ = jax.lax.scan(
        one, jnp.zeros_like(y),
        (w[f"l{i}_ex_gate"], w[f"l{i}_ex_up"], w[f"l{i}_ex_down"],
         rw[:, first:first + count].T))
    return out


def _layer(x, w, i, sizes, precision):
    eps = sizes["rms_norm_eps"]
    a = _rms(x, w[f"l{i}_ln1"], eps)
    # the picks are made HERE, from the attention's input
    rw = routing_weights(a, w[f"l{i}_router"], sizes, precision)
    x = x + _attention(a, w, i, sizes, precision)

    def ffn(rows, rw_rows):
        return moe(_rms(rows, w[f"l{i}_ln2"], eps), rw_rows, w, i, sizes,
                   precision)

    return x + _blocks(ffn, (x, rw), ROW_BLOCK)


def hidden(w, ids, sizes, precision="float32"):
    """(S,) token ids -> (S, H) final-norm output."""
    x = _f32(w["embed"][ids])
    for i in range(sizes["num_hidden_layers"]):
        x = _layer(x, w, i, sizes, precision)
    return _rms(x, w["lnf"], sizes["rms_norm_eps"])


def logits(w, hid, precision="float32"):
    return _mm("sh,vh->sv", hid, _f32(w["head"]), precision)


def next_token_scores(w, tokens, picks, sizes, precision="float32"):
    """One sequence (1-D, prompt then generated tokens; right padding is
    harmless because attention is causal). For every position i, over
    the logits of the token that follows it: how far the logit of
    ``picks[i]`` lies below the best one, and which token is best."""
    hid = hidden(w, tokens, sizes, precision)

    def rows(h, p):
        lg = logits(w, h, precision)
        own = jnp.take_along_axis(lg, p[:, None], axis=-1)[:, 0]
        return lg.max(-1) - own, lg.argmax(-1)

    return _blocks(rows, (hid, picks), ROW_BLOCK)
