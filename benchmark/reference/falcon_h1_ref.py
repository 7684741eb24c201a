"""Plain Falcon-H1 (``model_type: falcon_h1``): the forward pass in
straightforward ``jax.numpy`` float32 at matmul precision "highest". No
kernel, no cache, no paging, no batching, no chunked form of the
recurrence; imports nothing of the program.

Follows the published config
(https://huggingface.co/tiiuae/Falcon-H1-34B-Instruct/blob/main/config.json)
and ``transformers``' ``modeling_falcon_h1.py``; RMSNorm, pre-norm
residuals, no bias but the convolution's, an untied head. One sequence
of S tokens at a time, ``x = embed[ids] * embedding_multiplier``, and in
every block, with ``u = RMSNorm(x)``:

* attention: ``q = (u * attention_in_multiplier) W_q`` in ``(S, H,
  hd)``; ``k = (..) W_k * key_multiplier``, ``v = (..) W_v`` in ``(S,
  KV, hd)``; query head ``h`` reads KV head ``h // (H / KV)``;
  rotate-half RoPE at ``rope_theta`` over all of ``hd``; causal softmax
  over ``q k^T / sqrt(hd)``, the mask built densely; ``a = (ctx W_o) *
  attention_out_multiplier``;
* the state-space mixer (Mamba-2): ``p = ((u * ssm_in_multiplier) W_in)
  * mup_vector``, split z (d_ssm) | xBC (d_ssm + 2 groups x d_state) |
  dt (heads), ``mup_vector`` holding ``ssm_multipliers[0..4]`` on z | x
  | B | C | dt; ``xBC_t = silu(conv_b + sum_j conv_w[j] *
  xBC_{t-3+j})``, zeros before the sequence; ``dt_t = softplus(dt_t +
  dt_bias)``, ``A = -exp(A_log)``; the recurrence a plain ``lax.scan`` A
  TOKEN AT A TIME over ``H`` (heads, d_head, d_state), head ``h`` using
  group ``h // (heads / groups)`` of B and C:

      H_t = exp(dt_t A) H_{t-1} + dt_t x_t B_t^T;  y_t = H_t C_t + D x_t

  ``y = weight * RMSNorm over each group of d_ssm / groups of (y *
  silu(z))``; ``m = (y W_out) * ssm_out_multiplier``;
* ``x = x + a + m``; ``f = RMSNorm(x)``; ``x = x + (silu((f W_gate) *
  mlp_multipliers[0]) * (f W_up)) W_down * mlp_multipliers[1]``;

and ``logits = (RMSNorm(x) W_head) * lm_head_multiplier``.

``precision`` "float32" is the reference. "fp8" is the CONTROL: the same
mathematics with every matmul operand rounded to an 8-bit float (e4m3)
under a per-tensor scale, the nearest step below the bfloat16 the
configuration states (``bloom_ref._mm``, shared with the other
references); the recurrence's inputs ``x``, ``B`` and ``C``, which the
program hands its matrix unit in bfloat16, are rounded the same way.

Departures from the published description, none of which changes a
value: the weights arrive in the configuration's dtype, stacked over the
layers, and are widened to float32 a block at a time (a scan over the
blocks); attention is taken over blocks of queries; the head is taken a
block of its ROWS at a time with a running best (whole in float32 it is
5.3 GB beside 10.5 GB of bfloat16 weights), so the (S, V) logits never
exist at once. Not in the catalog's config and set by the family's
convention (the configuration file lists them under ``assumed``): the
weights' distribution.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

# the float32 "highest" product with its fp8 control: the other
# references', shared so that all controls round alike
from benchmark.reference.bloom_ref import _mm, _round_fp8
from benchmark.reference.laguna_ref import _f32, _rms

Q_BLOCK = 256          # queries a block of attention
HEAD_ROWS = 32768      # rows of the head a block, at most

BLOCK_LEAVES = ("ln1", "ln2", "q", "k", "v", "o", "in_proj", "conv_w",
                "conv_b", "dt_bias", "A_log", "D", "ssm_norm", "out_proj",
                "gate", "up", "down")


def _rope(x, theta: float):
    """Rotate-half rotary on x (S, heads, hd), positions 0..S-1."""
    hd = x.shape[-1]
    inv = (1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
           ).astype(np.float32)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]     # (S, 1, hd)
    half = jnp.concatenate([-x[..., hd // 2:], x[..., :hd // 2]], -1)
    return x * jnp.cos(ang) + half * jnp.sin(ang)


def _attention(u, w, sizes, precision):
    s = u.shape[0]
    hd, kv, nh = sizes["head_dim"], sizes["num_key_value_heads"], \
        sizes["num_attention_heads"]
    ua = u * sizes["attention_in_multiplier"]
    q = _mm("sh,hk->sk", ua, _f32(w["q"]), precision).reshape(s, nh, hd)
    k = (_mm("sh,hk->sk", ua, _f32(w["k"]), precision)
         * sizes["key_multiplier"]).reshape(s, kv, hd)
    v = _mm("sh,hk->sk", ua, _f32(w["v"]), precision).reshape(s, kv, hd)
    q, k = _rope(q, sizes["rope_theta"]), _rope(k, sizes["rope_theta"])
    # every query head beside its KV head: (S, KV, g, hd)
    q = q.reshape(s, kv, nh // kv, hd)
    k_pos = jnp.arange(s)

    def block(args):
        qb, q_pos = args
        sc = _mm("qkgd,nkd->kgqn", qb, k, precision) / math.sqrt(hd)
        sc = jnp.where(k_pos[None, :] <= q_pos[:, None], sc, -jnp.inf)
        return _mm("kgqn,nkd->qkgd", jax.nn.softmax(sc, axis=-1), v,
                   precision)

    pad = (-s) % Q_BLOCK
    qs = jnp.pad(q, ((0, pad), (0, 0), (0, 0), (0, 0)))
    # a padded query sees key 0, so its softmax is finite; it is cut off
    pos = jnp.pad(k_pos, (0, pad))
    ctx = jax.lax.map(block, (qs.reshape((-1, Q_BLOCK) + q.shape[1:]),
                              pos.reshape(-1, Q_BLOCK)))
    ctx = ctx.reshape(-1, nh * hd)[:s]
    return _mm("sk,kh->sh", ctx, _f32(w["o"]), precision) \
        * sizes["attention_out_multiplier"]


def mup_vector(sizes: dict) -> np.ndarray:
    d, gn = sizes["mamba_d_ssm"], \
        sizes["mamba_n_groups"] * sizes["mamba_d_state"]
    out = np.ones((2 * d + 2 * gn + sizes["mamba_n_heads"],), np.float32)
    edges = np.cumsum([0, d, d, gn, gn, sizes["mamba_n_heads"]])
    for lo, hi, mult in zip(edges, edges[1:], sizes["ssm_multipliers"]):
        out[lo:hi] *= mult
    return out


def recurrence(x, dt, a, bm, cm):
    """``H_t = exp(dt_t a) H_{t-1} + dt_t x_t B_t^T``, ``y_t = H_t C_t``,
    a token at a time: x (S, heads, P), dt (S, heads), a (heads,), bm, cm
    (S, groups, N). Returns (y (S, heads, P), H_S (heads, P, N))."""
    heads, p = x.shape[1:]
    g, n = bm.shape[1:]
    rep = heads // g

    def step(h, xs):
        x_t, dt_t, b_t, c_t = xs
        b_t, c_t = jnp.repeat(b_t, rep, axis=0), jnp.repeat(c_t, rep, axis=0)
        h = jnp.exp(dt_t * a)[:, None, None] * h \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return h, (h * c_t[:, None, :]).sum(-1)

    last, y = jax.lax.scan(step, jnp.zeros((heads, p, n), jnp.float32),
                           (x, dt, bm, cm))
    return y, last


def _mixer(u, w, sizes, precision):
    s = u.shape[0]
    d, nh = sizes["mamba_d_ssm"], sizes["mamba_n_heads"]
    g, n, kw = sizes["mamba_n_groups"], sizes["mamba_d_state"], \
        sizes["mamba_d_conv"]
    p = _mm("sh,hk->sk", u * sizes["ssm_in_multiplier"], _f32(w["in_proj"]),
            precision) * mup_vector(sizes)
    z, xbc, dt = p[:, :d], p[:, d:d + d + 2 * g * n], p[:, 2 * d + 2 * g * n:]
    # causal depthwise convolution, zeros before the sequence
    padded = jnp.pad(xbc, ((kw - 1, 0), (0, 0)))
    conv = _f32(w["conv_b"]) + sum(
        _f32(w["conv_w"])[j] * padded[j:j + s] for j in range(kw))
    xbc = jax.nn.silu(conv)
    x, bm, cm = (xbc[:, :d].reshape(s, nh, d // nh),
                 xbc[:, d:d + g * n].reshape(s, g, n),
                 xbc[:, d + g * n:].reshape(s, g, n))
    if precision == "fp8":
        x, bm, cm = _round_fp8(x), _round_fp8(bm), _round_fp8(cm)
    dt = jax.nn.softplus(dt + _f32(w["dt_bias"]))
    y, _ = recurrence(x, dt, -jnp.exp(_f32(w["A_log"])), bm, cm)
    y = (y + _f32(w["D"])[:, None] * x).reshape(s, d)
    y = (y * jax.nn.silu(z)).reshape(s, g, d // g)
    y = y * jax.lax.rsqrt((y * y).mean(-1, keepdims=True)
                          + sizes["rms_norm_eps"])
    y = y.reshape(s, d) * _f32(w["ssm_norm"])
    return _mm("sk,kh->sh", y, _f32(w["out_proj"]), precision) \
        * sizes["ssm_out_multiplier"]


def _block(x, w, sizes, precision):
    eps = sizes["rms_norm_eps"]
    u = _rms(x, w["ln1"], eps)
    x = x + _attention(u, w, sizes, precision) + _mixer(u, w, sizes, precision)
    f = _rms(x, w["ln2"], eps)
    gate = jax.nn.silu(_mm("sh,hf->sf", f, _f32(w["gate"]), precision)
                       * sizes["mlp_multipliers"][0])
    up = _mm("sh,hf->sf", f, _f32(w["up"]), precision)
    return x + _mm("sf,fh->sh", gate * up, _f32(w["down"]), precision) \
        * sizes["mlp_multipliers"][1]


def hidden(w, ids, sizes, precision="float32"):
    """(S,) token ids -> (S, H) final-norm output; a block's weights are
    widened inside the scan's step."""
    x = _f32(w["embed"][ids]) * sizes["embedding_multiplier"]
    x, _ = jax.lax.scan(
        lambda x, blk: (_block(x, blk, sizes, precision), None), x,
        {k: w[k] for k in BLOCK_LEAVES})
    return _rms(x, w["lnf"], sizes["rms_norm_eps"])


def logits(w, hid, sizes, precision="float32"):
    """(S, V), whole: for the tests' small vocabularies."""
    return _mm("sh,vh->sv", hid, _f32(w["head"]), precision) \
        * sizes["lm_head_multiplier"]


def next_token_scores(w, tokens, picks, sizes, precision="float32"):
    """One sequence (1-D, prompt then generated tokens; right padding is
    harmless because attention is causal and the recurrence runs
    forward). For every position i, over the logits of the token that
    follows it: how far the logit of ``picks[i]`` lies below the best
    one, and which token is best. The head a block of rows at a time."""
    hid = hidden(w, tokens, sizes, precision)
    v = w["head"].shape[0]
    parts = next(d for d in range(1, v + 1)
                 if v % d == 0 and v // d <= HEAD_ROWS)
    rows = v // parts

    def part(carry, xs):
        best, arg, own = carry
        head, first = xs
        lg = logits({"head": head}, hid, sizes, precision)        # (S, rows)
        top = lg.max(-1)
        arg = jnp.where(top > best, first + lg.argmax(-1), arg)
        mine = jnp.take_along_axis(
            lg, jnp.clip(picks - first, 0, rows - 1)[:, None], axis=-1)[:, 0]
        own = jnp.where((picks >= first) & (picks < first + rows), mine, own)
        return (jnp.maximum(best, top), arg, own), None

    s = hid.shape[0]
    (best, arg, own), _ = jax.lax.scan(
        part, (jnp.full((s,), -jnp.inf), jnp.zeros((s,), jnp.int32),
               jnp.zeros((s,))),
        (w["head"].reshape(parts, rows, -1), jnp.arange(parts) * rows))
    return best - own, arg
