"""Plain LongCat-Flash (the language model of LongCat-Flash-Omni): the
forward pass in straightforward ``jax.numpy`` float32 at matmul precision
"highest". No kernel, no cache, no paging, no batching, no sort or gather
of rows by expert, no absorbed attention; imports nothing of the program.

Follows the published config
(https://huggingface.co/meituan-longcat/LongCat-Flash-Omni/blob/main/config.json),
HF ``modeling_longcat_flash.py`` and the LongCat-Flash report. RMSNorm
(eps ``rms_norm_eps``), no bias, an untied head. One sequence of S tokens
at a time; one block, input ``h``::

    a0 = h  + MLA_0(RMS(h))          x0 = RMS(a0)
    s  = MoE(x0)                     # the shortcut
    b0 = a0 + FFN_0(x0)              # dense SwiGLU
    a1 = b0 + MLA_1(RMS(b0))         x1 = RMS(a1)
    h' = a1 + FFN_1(x1) + s

* ``MLA(x)``, H heads, in the EXPANDED form: ``cq = RMS(x W_qa)``; ``q =
  s_q (cq W_qb)`` as (H, nope | rope), ``s_q = sqrt(hidden / q_lora_rank)``;
  ``[c | kr] = x W_kva``; ``c = s_kv RMS(c)``, ``s_kv = sqrt(hidden /
  kv_lora_rank)``; rotary (theta ``rope_theta``) on ``q``'s last ``rope``
  dims a head and on ``kr``, pair ``(2i, 2i + 1)`` turned by ``pos *
  theta^(-2i / rope)``; ``[k_nope | v] = c W_kvb`` a head; ``k = [k_nope |
  kr]`` with the ONE ``kr`` for every head; scores ``q . k / sqrt(nope +
  rope)``, a dense causal mask, softmax, ``sum p v``, ``W_o``.
* ``MoE(x)``: ``z = softmax(x W_r)`` over ``router_experts +
  zero_expert_num`` outputs; an expert is chosen where fewer than
  ``moe_topk`` others have a larger ``z + b`` (a count, not a sort);
  ``w = routed_scaling_factor * z`` there and 0 elsewhere, not
  renormalised; ``y = sum_{e < router_experts} w_e SwiGLU_e(x) +
  (sum_{e >= router_experts} w_e) x``. EVERY held expert is applied to
  EVERY token and multiplied by its ``w``. ``held = (first, count)``: the
  experts whose matrices ``w`` holds (the configuration's share by
  default; all ``router_experts`` of them is the uncut layer); what
  absent experts would add is left out, as in the program. The identity
  part needs no weights and is always whole.

``precision`` "float32" is the reference. "fp8" is the CONTROL: the same
mathematics with every matmul operand rounded to an 8-bit float (e4m3)
under a per-tensor scale, the nearest step below the bfloat16 the
configuration states (``bloom_ref._mm``, shared with that reference).

Departures from the published code, none of which changes a value: the
pairs of the rotary are turned where they stand (HF first lays them out
as ``[evens | odds]``: a permutation of the rotary dims, the same for
``q`` and ``kr``, which no score sees); the weights arrive in the
configuration's dtype and are widened to float32 where they are used,
a matrix and an expert at a time; attention
is taken over blocks of queries, the feed-forwards and the head over
blocks of rows. Not in ``config.json`` (the configuration file lists
them under ``assumed``): ``norm_topk_prob`` false, where the two scales
multiply, silu.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# the float32 "highest" product with its fp8 control: the other
# references', shared so that every control rounds alike
from benchmark.reference.bloom_ref import _mm
# widening, RMSNorm, a SwiGLU and the walk over blocks of rows: the
# Laguna reference's, as the Falcon-H1 reference takes them
from benchmark.reference.laguna_ref import (
    Q_BLOCK,
    ROW_BLOCK,
    _blocks,
    _f32,
    _rms,
    _swiglu,
)


def _rope(x, theta):
    """Rotary on x (S, heads, rot), positions 0..S-1, the pairs (2i, 2i
    + 1) turned in place."""
    s, _, rot = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def mla(x, w, i, j, sizes, precision="float32"):
    """Attention ``j`` of block ``i`` on x (S, hidden), expanded."""
    s, hid = x.shape
    nh = sizes["num_attention_heads"]
    dn, dr, dv = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"], \
        sizes["v_head_dim"]
    rq, rkv = sizes["q_lora_rank"], sizes["kv_lora_rank"]
    eps, theta = sizes["rms_norm_eps"], sizes["rope_theta"]
    p = f"l{i}_h{j}_"
    s_q = math.sqrt(hid / rq) if sizes["mla_scale_q_lora"] else 1.0
    s_kv = math.sqrt(hid / rkv) if sizes["mla_scale_kv_lora"] else 1.0
    cq = _rms(_mm("sh,hr->sr", x, _f32(w[p + "qa"]), precision),
              w[p + "qa_norm"], eps)
    q = s_q * _mm("sr,rk->sk", cq, _f32(w[p + "qb"]), precision)
    q = q.reshape(s, nh, dn + dr)
    ckv = _mm("sh,hr->sr", x, _f32(w[p + "kva"]), precision)
    c = s_kv * _rms(ckv[:, :rkv], w[p + "kva_norm"], eps)
    kr = _rope(ckv[:, None, rkv:], theta)                       # (S, 1, dr)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], theta)], axis=-1)
    kv = _mm("sr,rk->sk", c, _f32(w[p + "kvb"]), precision)
    kv = kv.reshape(s, nh, dn + dv)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(kr, (s, nh, dr))],
                        axis=-1)
    v = kv[..., dn:]
    k_pos = jnp.arange(s)

    def block(args):
        qb, q_pos = args
        sc = _mm("qhd,nhd->hqn", qb, k, precision) / math.sqrt(dn + dr)
        sc = jnp.where(k_pos[None, :] <= q_pos[:, None], sc, -jnp.inf)
        return _mm("hqn,nhd->qhd", jax.nn.softmax(sc, axis=-1), v, precision)

    pad = (-s) % Q_BLOCK
    qs = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
    # a padded query sees key 0, so its softmax is finite; it is cut off
    pos = jnp.pad(k_pos, (0, pad))
    ctx = jax.lax.map(block, (qs.reshape((-1, Q_BLOCK) + q.shape[1:]),
                              pos.reshape(-1, Q_BLOCK)))
    ctx = ctx.reshape(-1, nh * dv)[:s]
    return _mm("sk,kh->sh", ctx, _f32(w[p + "o"]), precision)


def routing_weights(x, w, i, sizes, precision="float32"):
    """(S, router_experts + zero_expert_num) float32: an output's combine
    weight for each token, zero where the token did not choose it."""
    z = jax.nn.softmax(
        _mm("sh,he->se", x, _f32(w[f"l{i}_router"]), precision), axis=-1)
    choice = z + _f32(w[f"l{i}_bias"])
    larger = (choice[:, None, :] > choice[:, :, None]).sum(-1)   # (S, E)
    out = z * (larger < sizes["moe_topk"]).astype(jnp.float32)
    if sizes.get("norm_topk_prob", False):
        out = out / (out.sum(-1, keepdims=True) + 1e-20)
    return out * sizes["routed_scaling_factor"]


def moe_routed(x, w, i, sizes, precision="float32", held=None):
    """The part of the routed sum that the experts ``held`` = (first,
    count) give on x (S, hidden); their matrices are the ones ``w``
    holds, in order. ``held=None``: the configuration's share."""
    first, count = held or sizes["experts_held"]
    rw = routing_weights(x, w, i, sizes, precision)[:, first:first + count]

    def one(acc, ex):
        gate, up, down, we = ex
        return acc + we[:, None] * _swiglu(x, gate, up, down, precision), None

    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(x),
        (w[f"l{i}_ex_gate"], w[f"l{i}_ex_up"], w[f"l{i}_ex_down"], rw.T))
    return routed


def moe_identity(x, w, i, sizes, precision="float32"):
    """The zero-compute experts' part: the summed weight of a token's
    picks past the real experts, times the token. Whole on every share."""
    rw = routing_weights(x, w, i, sizes, precision)
    return rw[:, sizes["router_experts"]:].sum(-1, keepdims=True) * x


def moe(x, w, i, sizes, precision="float32", held=None):
    return moe_routed(x, w, i, sizes, precision, held) \
        + moe_identity(x, w, i, sizes, precision)


def _ffn(x, w, i, j, precision):
    p = f"l{i}_h{j}_"
    return _swiglu(x, w[p + "gate"], w[p + "up"], w[p + "down"], precision)


def block(x, w, i, sizes, precision="float32", held=None):
    """Block ``i`` on x (S, hidden)."""
    eps = sizes["rms_norm_eps"]
    a0 = x + mla(_rms(x, w[f"l{i}_h0_ln_in"], eps), w, i, 0, sizes, precision)

    def first(rows):
        x0 = _rms(rows, w[f"l{i}_h0_ln_post"], eps)
        # the shortcut leaves here, from the first half's normed input
        return (rows + _ffn(x0, w, i, 0, precision),
                moe(x0, w, i, sizes, precision, held))

    b0, s = _blocks(first, a0, ROW_BLOCK)
    a1 = b0 + mla(_rms(b0, w[f"l{i}_h1_ln_in"], eps), w, i, 1, sizes, precision)

    def second(rows):
        x1 = _rms(rows, w[f"l{i}_h1_ln_post"], eps)
        return rows + _ffn(x1, w, i, 1, precision)

    # and lands here, after the second half's feed-forward
    return _blocks(second, a1, ROW_BLOCK) + s


def hidden(w, ids, sizes, precision="float32", held=None):
    """(S,) token ids -> (S, H) final-norm output."""
    x = _f32(w["embed"][ids])
    for i in range(sizes["num_layers"]):
        x = block(x, w, i, sizes, precision, held)
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
