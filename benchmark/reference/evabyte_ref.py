"""Plain EvaByte (``model_type: evabyte``): the forward pass in
straightforward ``jax.numpy`` float32 at matmul precision "highest". No
kernel, no cache, no paging, no batching, no online softmax; imports
nothing of the program.

Follows the published config
(https://huggingface.co/EvaByte/EvaByte/blob/main/config.json), EVA
(Zheng et al., ICLR 2023, "Efficient Attention via Control Variates")
in the chunked form of EvaByte's ``eva.py`` / ``modeling_evabyte.py``.
``RMS1(x; g) = x / sqrt(mean(x^2) + eps) * (1 + g)``
(``norm_add_unit_offset``), no bias, an untied head of ``num_pred_heads``
x ``vocab_size`` columns. One sequence of S bytes at a time; one layer,
input ``h`` (float32 throughout: ``fp32_skip_add`` asks no less)::

    x  = RMS1(h; g_1)
    q, k, v = x W_q, x W_k, x W_v          # heads x head_dim
    q, k = RoPE(q, t), RoPE(k, t)          # theta rope_theta, rotate-half
    h  = h + EVA(q, k, v) W_o
    y  = RMS1(h; g_2)
    h' = h + (silu(y W_g) * (y W_u)) W_d

``EVA`` a head, ``W = window_size``, ``C = chunk_size``, ``s =
head_dim^-0.5``, ``phi`` and ``mu`` a head:

* the summary of chunk ``c`` (positions ``C c .. C c + C - 1``): ``a_j =
  softmax_j(s k_j . phi)`` over the chunk's ``C`` rotated keys; ``k~_c =
  sum_j a_j k_j + mu``; ``v~_c = sum_j a_j v_j``;
* query ``t`` in window ``w = t // W``: its scores on the keys of its
  own window from ``w W`` to ``t`` (a dense causal block) and, beside
  them, its scores on the summaries of EVERY chunk of EVERY earlier
  window (``c < w W / C``), concatenated; ONE softmax over the
  concatenation; the probabilities times the values and the pooled
  values.

After the last layer ``RMS1(h; g_f)`` and the head; head ``i``, columns
``[V i, V (i + 1))``, predicts the byte ``i + 1`` ahead.

``precision`` "float32" is the reference. "fp8" is the CONTROL: the same
mathematics with every matmul operand rounded to an 8-bit float (e4m3)
under a per-tensor scale, the nearest step below the bfloat16 the
configuration states (``bloom_ref._mm``, shared with that reference).

Departures from the published code, none of which changes a value: the
weights arrive in the configuration's dtype, stacked over layers, and
are widened to float32 where they are used, a layer at a time; a
query's softmax is taken a block of queries at a time (each with its
whole window and every summary before it), the feed-forward and the head
over blocks of rows, so that 26,624 positions fit; every summary is
computed up front, its chunk complete or not (one that holds a later
position than a query is never among that query's keys); the published
code runs the attention's products in the model's dtype under
``mixedp_attn`` and this one in float32. Not in ``config.json`` (the
configuration file lists them under ``assumed``): the rotate-half
pairing, ``phi`` and ``mu``, the head's column layout.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# the float32 "highest" product with its fp8 control: the other
# references', shared so that every control rounds alike
from benchmark.reference.bloom_ref import _mm
# widening, rotate-half rotary and the walk over blocks of rows: the
# Laguna reference's, as the other references take them
from benchmark.reference.laguna_ref import (
    Q_BLOCK,
    ROW_BLOCK,
    _blocks,
    _f32,
    _rope,
)


def _rms1(x, g, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) \
        * (1.0 + _f32(g))


def summaries(k, v, phi, mu, sizes, precision="float32"):
    """``(k~, v~)`` (S / C, heads, hd) of every chunk of ``k``, ``v`` (S,
    heads, hd), S a multiple of the chunk."""
    c = sizes["chunk_size"]
    s, nh, hd = k.shape
    kc, vc = k.reshape(s // c, c, nh, hd), v.reshape(s // c, c, nh, hd)
    a = jax.nn.softmax(
        _mm("cjhd,hd->cjh", kc, phi, precision) * hd ** -0.5, axis=1)
    return (_mm("cjh,cjhd->chd", a, kc, precision) + mu,
            _mm("cjh,cjhd->chd", a, vc, precision))


def eva(q, k, v, phi, mu, sizes, precision="float32"):
    """EVA on q, k, v (S, heads, hd) of one sequence from position 0.
    Returns (S, heads * hd)."""
    w, c = sizes["window_size"], sizes["chunk_size"]
    s, nh, hd = q.shape
    block = min(Q_BLOCK, w)
    # whole windows (and so whole chunks and whole blocks of queries):
    # a position past the sequence is no earlier query's key
    pad = (-s) % w
    q, k, v = (jnp.pad(x, ((0, pad), (0, 0), (0, 0))) for x in (q, k, v))
    k_sum, v_sum = summaries(k, v, phi, mu, sizes, precision)
    chunk_window = jnp.arange(k_sum.shape[0]) * c // w

    def one(args):
        qb, first = args                         # (block, heads, hd), its pos
        win = first // w
        kw = jax.lax.dynamic_slice_in_dim(k, win * w, w, axis=0)
        vw = jax.lax.dynamic_slice_in_dim(v, win * w, w, axis=0)
        q_pos = first + jnp.arange(block)
        exact = _mm("qhd,nhd->hqn", qb, kw, precision) * hd ** -0.5
        exact = jnp.where(win * w + jnp.arange(w)[None, :] <= q_pos[:, None],
                          exact, -jnp.inf)
        pooled = _mm("qhd,chd->hqc", qb, k_sum, precision) * hd ** -0.5
        pooled = jnp.where(chunk_window < win, pooled, -jnp.inf)
        # ONE softmax over the window's keys and the summaries
        p = jax.nn.softmax(jnp.concatenate([exact, pooled], axis=-1), axis=-1)
        return _mm("hqn,nhd->qhd", p[..., :w], vw, precision) \
            + _mm("hqc,chd->qhd", p[..., w:], v_sum, precision)

    n = q.shape[0] // block
    ctx = jax.lax.map(one, (q.reshape(n, block, nh, hd),
                            jnp.arange(n) * block))
    return ctx.reshape(-1, nh * hd)[:s]


def block(x, w, i, sizes, precision="float32"):
    """Layer ``i`` on x (S, hidden)."""
    s, hid = x.shape
    nh = sizes["num_attention_heads"]
    eps = sizes["rms_norm_eps"]
    rope = {"rope_theta": sizes["rope_theta"]}
    a = _rms1(x, w["ln1"][i], eps)
    q, k, v = (_mm("sh,hk->sk", a, _f32(w[n][i]), precision).reshape(
        s, nh, hid // nh) for n in "qkv")
    ctx = eva(_rope(q, rope), _rope(k, rope), v, _f32(w["phi"][i]),
              _f32(w["mu"][i]), sizes, precision)
    x = x + _mm("sk,kh->sh", ctx, _f32(w["o"][i]), precision)

    def ffn(rows):
        y = _rms1(rows, w["ln2"][i], eps)
        gate = _mm("sh,hf->sf", y, _f32(w["gate"][i]), precision)
        up = _mm("sh,hf->sf", y, _f32(w["up"][i]), precision)
        return rows + _mm("sf,fh->sh", jax.nn.silu(gate) * up,
                          _f32(w["down"][i]), precision)

    return _blocks(ffn, x, ROW_BLOCK)


def hidden(w, ids, sizes, precision="float32"):
    """(S,) byte ids -> (S, H) final-norm output."""
    x = _f32(w["embed"][ids])
    for i in range(sizes["num_hidden_layers"]):
        x = block(x, w, i, sizes, precision)
    return _rms1(x, w["lnf"], sizes["rms_norm_eps"])


def logits(w, hid, sizes, precision="float32", heads=None):
    """(.., heads x V) logits of the first ``heads`` output heads, all
    ``num_pred_heads`` by default."""
    n = (heads or sizes["num_pred_heads"]) * sizes["vocab_size"]
    return _mm("sh,hv->sv", hid, _f32(w["head"][:, :n]), precision)


def forward(w, ids, sizes, precision="float32"):
    """(S,) byte ids -> (S, num_pred_heads, V) logits."""
    out = logits(w, hidden(w, ids, sizes, precision), sizes, precision)
    return out.reshape(ids.shape[0], sizes["num_pred_heads"], -1)


def next_token_scores(w, tokens, picks, sizes, precision="float32"):
    """One sequence (1-D, prompt then generated bytes; right padding is
    harmless: no earlier query sees a later position or a summary that
    holds one). For every position i, over head 0's logits of the byte
    that follows it (what a decode step serves): how far the logit of
    ``picks[i]`` lies below the best one, and which byte is best."""
    lg = logits(w, hidden(w, tokens, sizes, precision), sizes, precision,
                heads=1)
    own = jnp.take_along_axis(lg, picks[:, None], axis=-1)[:, 0]
    return lg.max(-1) - own, lg.argmax(-1)
