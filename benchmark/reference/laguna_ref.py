"""Plain Laguna-S-2.1 (``model_type: laguna``): the forward pass in
straightforward ``jax.numpy`` float32 at matmul precision "highest". No
kernel, no cache, no paging, no batching, no sort or gather of rows by
expert; imports nothing of the program.

Follows the published config
(https://huggingface.co/poolside/Laguna-S-2.1/blob/main/config.json);
RMSNorm, pre-norm residuals, no bias, an untied head. One sequence of S
tokens at a time:

* attention, layer ``l``: ``a = RMSNorm(h)``; ``q = a W_q`` in ``(S,
  H_l, hd)``, ``H_l`` = ``num_attention_heads_per_layer[l]``; ``k``,
  ``v`` in ``(S, KV, hd)``; query head ``h`` reads KV head ``h // (H_l
  / KV)``. Rotary by the layer's kind (``rope_parameters``): full
  layers YaRN on the first ``partial_rotary_factor * hd`` dims with cos
  and sin scaled by ``attention_factor``, sliding layers plain RoPE on
  all dims; rotate-half pairing. Masked softmax over ``q k^T /
  sqrt(hd)``: causal, and on a sliding layer the keys with ``0 <= q_pos
  - k_pos < sliding_window``; the mask is built densely. ``g =
  sigmoid(a W_g)`` in ``(S, H_l)``; ``h += concat_h(g_h o_h) W_o``;
* FFN: a layer of ``mlp_only_layers`` a SwiGLU; every other ``shared(m)
  + sum_e w_e expert_e(m)`` with ``s = sigmoid(m W_r)``, the chosen
  experts those of which fewer than ``k`` others have a larger ``s`` (a
  count, not a sort), ``w = s[chosen] / sum * scaling``. EVERY held
  expert is applied to EVERY token and multiplied by its ``w`` (zero
  where it was not chosen). The reference is given the same share as
  the program: the held experts' matrices and the held rows of the
  vocabulary; what absent experts would add is left out, here as there.

``precision`` "float32" is the reference. "fp8" is the CONTROL: the same
mathematics with every matmul operand rounded to an 8-bit float (e4m3)
under a per-tensor scale, the nearest step below the bfloat16 the
configuration states (``bloom_ref._mm``, shared with that reference).

Departures from the published description, none of which changes a
value: the weights arrive in the configuration's dtype and are widened
to float32 where they are used, a layer, and within a sparse layer an
expert, at a time (one sparse layer's held experts are 4.8 GB in
float32: a whole float32 copy does not fit beside anything on a 16 GB
chip); attention is taken over blocks of queries, the feed-forward and
the head over blocks of rows, so neither the (heads, S, S) scores nor
the (S, V) logits exist at once. Not in the config and set by the
family's convention (the configuration file lists them under
``assumed``): ``silu``, the router's sigmoid, the gate's input and
place, no QK-norm, rotate-half.
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
ROW_BLOCK = 1024       # rows a block of the feed-forward and of the head
SLIDING = "sliding_attention"


def _f32(x):
    return x.astype(jnp.float32)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) \
        * _f32(scale)


def rope_frequencies(rope: dict, head_dim: int):
    """(inverse frequencies (rot / 2,), factor on cos and sin, rot) of
    one entry of ``rope_parameters``. ``default``: theta^(-2i / rot).
    ``yarn`` (Peng et al. 2023; HF ``_compute_yarn_parameters``): a
    blend of each frequency with itself over ``factor``, by a linear
    ramp between the dims that turn ``beta_fast`` and ``beta_slow``
    times over the original context."""
    rot = int(head_dim * rope.get("partial_rotary_factor", 1.0))
    theta = float(rope["rope_theta"])
    freqs = theta ** (np.arange(0, rot, 2, dtype=np.float64) / rot)
    if rope.get("rope_type", "default") == "default":
        return (1.0 / freqs).astype(np.float32), 1.0, rot
    factor, orig = float(rope["factor"]), \
        rope["original_max_position_embeddings"]

    def dim_of(turns):
        return rot * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(dim_of(rope["beta_fast"])), 0)
    high = min(math.ceil(dim_of(rope["beta_slow"])), rot - 1)
    ramp = np.clip((np.arange(rot // 2, dtype=np.float64) - low)
                   / ((high if high != low else high + 0.001) - low), 0, 1)
    inv = ramp / (factor * freqs) + (1.0 - ramp) / freqs
    return inv.astype(np.float32), float(rope["attention_factor"]), rot


def _rope(x, rope: dict):
    """Rotary on x (S, heads, hd), positions 0..S-1."""
    inv, scale, rot = rope_frequencies(rope, x.shape[-1])
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]     # (S, 1, rot)
    turn, rest = x[..., :rot], x[..., rot:]
    half = jnp.concatenate([-turn[..., rot // 2:], turn[..., :rot // 2]], -1)
    return jnp.concatenate(
        [turn * jnp.cos(ang) * scale + half * jnp.sin(ang) * scale, rest], -1)


def _blocks(fn, x, block):
    """``fn`` over blocks of ``block`` rows of x (S, ..), one at a time."""
    s = x.shape[0]
    pad = (-s) % block
    xs = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
    out = jax.lax.map(fn, xs.reshape((-1, block) + x.shape[1:]))
    return jax.tree_util.tree_map(
        lambda o: o.reshape((-1,) + o.shape[2:])[:s], out)


def _attention(x, w, i, sizes, precision):
    s = x.shape[0]
    hd, kv = sizes["head_dim"], sizes["num_key_value_heads"]
    nh = sizes["num_attention_heads_per_layer"][i]
    kind = sizes["layer_types"][i]
    rope = sizes["rope_parameters"][kind]
    a = _rms(x, w[f"l{i}_ln1"], sizes["rms_norm_eps"])
    q = _mm("sh,hk->sk", a, _f32(w[f"l{i}_q"]), precision).reshape(s, nh, hd)
    k = _mm("sh,hk->sk", a, _f32(w[f"l{i}_k"]), precision).reshape(s, kv, hd)
    v = _mm("sh,hk->sk", a, _f32(w[f"l{i}_v"]), precision).reshape(s, kv, hd)
    q, k = _rope(q, rope), _rope(k, rope)
    # every query head beside its KV head: (S, KV, g, hd)
    q = q.reshape(s, kv, nh // kv, hd)
    k_pos = jnp.arange(s)

    def block(args):
        qb, q_pos = args
        sc = _mm("qkgd,nkd->kgqn", qb, k, precision) / math.sqrt(hd)
        keep = k_pos[None, :] <= q_pos[:, None]
        if kind == SLIDING:
            keep = keep & (q_pos[:, None] - k_pos[None, :]
                           < sizes["sliding_window"])
        sc = jnp.where(keep, sc, -jnp.inf)
        return _mm("kgqn,nkd->qkgd", jax.nn.softmax(sc, axis=-1), v,
                   precision)

    pad = (-s) % Q_BLOCK
    qs = jnp.pad(q, ((0, pad), (0, 0), (0, 0), (0, 0)))
    # a padded query sees key 0, so its softmax is finite; it is cut off
    pos = jnp.pad(k_pos, (0, pad))
    ctx = jax.lax.map(block, (qs.reshape((-1, Q_BLOCK) + q.shape[1:]),
                              pos.reshape(-1, Q_BLOCK)))
    ctx = ctx.reshape((-1, nh, hd))[:s]
    gate = jax.nn.sigmoid(_mm("sh,hn->sn", a, _f32(w[f"l{i}_g"]), precision))
    ctx = (ctx * gate[:, :, None]).reshape(s, nh * hd)
    return _mm("sk,kh->sh", ctx, _f32(w[f"l{i}_o"]), precision)


def _swiglu(x, gate, up, down, precision):
    g = _mm("sh,hf->sf", x, _f32(gate), precision)
    u = _mm("sh,hf->sf", x, _f32(up), precision)
    return _mm("sf,fh->sh", jax.nn.silu(g) * u, _f32(down), precision)


def routing_weights(x, router_w, sizes, precision="float32"):
    """(S, E) float32: an expert's combine weight for each token, zero
    where the token did not choose it."""
    k = sizes["num_experts_per_tok"]
    s = jax.nn.sigmoid(_mm("sh,he->se", x, _f32(router_w), precision))
    larger = (s[:, None, :] > s[:, :, None]).sum(-1)             # (S, E)
    w = s * (larger < k).astype(jnp.float32)
    if sizes.get("norm_topk_prob", True):
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return w * sizes["moe_routed_scaling_factor"]


def moe(x, w, i, sizes, precision="float32", held=None):
    """A sparse layer's feed-forward on x (S, H): the shared expert plus
    the part of the routed sum that the experts ``held`` = (first,
    count) give (default: the configuration's share, whose matrices are
    the ones ``w`` holds)."""
    first, count = held or sizes["experts_held"]
    rw = routing_weights(x, w[f"l{i}_router"], sizes, precision)
    rw = rw[:, first:first + count]

    def one(acc, ex):
        gate, up, down, we = ex
        return acc + we[:, None] * _swiglu(x, gate, up, down, precision), None

    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(x),
        (w[f"l{i}_ex_gate"], w[f"l{i}_ex_up"], w[f"l{i}_ex_down"], rw.T))
    return routed + _swiglu(x, w[f"l{i}_sh_gate"], w[f"l{i}_sh_up"],
                            w[f"l{i}_sh_down"], precision)


def _layer(x, w, i, sizes, precision):
    x = x + _attention(x, w, i, sizes, precision)

    def ffn(rows):
        m = _rms(rows, w[f"l{i}_ln2"], sizes["rms_norm_eps"])
        if i in sizes["mlp_only_layers"]:
            return _swiglu(m, w[f"l{i}_gate"], w[f"l{i}_up"],
                           w[f"l{i}_down"], precision)
        return moe(m, w, i, sizes, precision)

    return x + _blocks(ffn, x, ROW_BLOCK)


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

    def rows(args):
        h, p = args
        lg = logits(w, h, precision)
        own = jnp.take_along_axis(lg, p[:, None], axis=-1)[:, 0]
        return lg.max(-1) - own, lg.argmax(-1)

    s = hid.shape[0]
    pad = (-s) % ROW_BLOCK
    gap, best = jax.lax.map(rows, (
        jnp.pad(hid, ((0, pad), (0, 0))).reshape(-1, ROW_BLOCK, hid.shape[1]),
        jnp.pad(picks, (0, pad)).reshape(-1, ROW_BLOCK)))
    return gap.reshape(-1)[:s], best.reshape(-1)[:s]
