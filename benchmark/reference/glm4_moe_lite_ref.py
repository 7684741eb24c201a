"""Plain GLM-4.7-Flash (``glm4_moe_lite``): forward, the two-term loss,
gradients and Adam steps in straightforward ``jax.numpy`` float32 at
matmul precision "highest". No kernel, no sort, no gather of rows by
expert; imports nothing of the program.

Follows DeepSeek-V3's equations (arXiv 2412.19437), which the published
``glm4_moe_lite`` config describes; RMSNorm, pre-norm residuals, no
biases. One ROW (one sequence) at a time:

* latent attention: ``c_q = RMSNorm(x W_qa)``, ``q = c_q W_qb`` per head
  (nope | rope); ``[c_kv | k_r] = x W_kva``, ``c_kv = RMSNorm(c_kv)``,
  ``[k_nope | v] = c_kv W_kvb`` per head; rotate-half RoPE on q's rotary
  part and on the one shared ``k_r``; masked softmax over
  ``q k^T / sqrt(nope + rope)``, taken over blocks of queries so the
  (heads, S, S) scores never exist at once;
* layer 0 a SwiGLU; the others ``shared(x) + sum_e w_e expert_e(x)``
  with ``s = sigmoid(x W_g)``, the chosen experts those of which fewer
  than ``k`` others have a larger ``s + b`` (a count, not a sort),
  ``w = s[chosen] / (sum + 1e-20) * scaling``. EVERY held expert is
  applied to EVERY token and multiplied by its ``w`` (zero where it was
  not chosen). The reference is given the same share as the program:
  the held experts' matrices and the held rows of the vocabulary; what
  absent experts would add is left out, here as there;
* MTP: positions 0..S-2 of ``W_eh [RMSNorm_e(Emb(t_{i+1})) ;
  RMSNorm_h(h_i)]`` through one expert layer and a final norm, the main
  head's weight, targets ``t_{i+2}`` (so S-2 of them); the row's loss is
  ``sum CE_main / (S-1) + lambda * sum CE_mtp / (S-2)``.

``precision`` "float32" is the reference. "fp8" is the CONTROL: the same
mathematics with every matmul operand rounded to an 8-bit float (e4m3)
under a per-tensor scale, the nearest step below the bfloat16 the
configuration states (``bloom_ref._mm``, shared with that reference).

Economies, none of which changes a value: stacked layers under
``lax.scan``, ``jax.checkpoint`` per layer, per block of queries and per
expert, the loss over chunks of positions, one block of rows a call with
the gradients summed, donation in the Adam update. ONE departure from a
float32 Adam, forced by the chip: 706 M parameters with float32 weights,
gradients and both moments are 11.3 GB and leave no room for a row's
backward pass beside them on a 15.75 GB chip (compiled for a described
v5e: see PERF.md), so ``moment_dtype`` lets the caller keep Adam's two
moments in the configuration's dtype, as the program's optimizer does
(``optax.adam`` on bfloat16 parameters). All arithmetic stays float32.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

# the float32 "highest" product with its fp8 control, and per-leaf norms:
# the other reference's, shared so that both controls round alike
from benchmark.reference.bloom_ref import _mm, leaf_norms  # noqa: F401

Q_BLOCK = 512          # queries a block of attention
CE_CHUNK = 512         # positions a chunk of the loss

ATTN = ("ln1", "qa", "qa_norm", "qb", "kva", "kva_norm", "kvb", "o", "ln2")
MOE = ("router_w", "router_b", "sh_gate", "sh_up", "sh_down",
       "ex_gate", "ex_up", "ex_down")


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """Rotate-half RoPE over the whole last axis of x (S, ..., d)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (d,))
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], -1) * jnp.sin(ang)


def _attention(x, lw, sizes, precision):
    """x (S, H) -> (S, H)."""
    s = x.shape[0]
    nh = sizes["num_attention_heads"]
    dn, dr = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    dv, r = sizes["v_head_dim"], sizes["kv_lora_rank"]
    eps, theta = sizes["rms_norm_eps"], float(sizes["rope_theta"])
    pos = jnp.arange(s, dtype=jnp.float32)
    cq = _rms(_mm("sh,hr->sr", x, lw["qa"], precision), lw["qa_norm"], eps)
    q = _mm("sr,rk->sk", cq, lw["qb"], precision).reshape(s, nh, dn + dr)
    ckv = _mm("sh,hr->sr", x, lw["kva"], precision)
    k_r = _rope(ckv[:, r:], pos, theta)                       # (S, dr)
    c = _rms(ckv[:, :r], lw["kva_norm"], eps)
    kv = _mm("sr,rk->sk", c, lw["kvb"], precision).reshape(s, nh, dn + dv)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], pos, theta)], -1)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_r[:, None, :], (s, nh, dr))], -1)
    v = kv[..., dn:]
    block = min(Q_BLOCK, s)
    pad = (-s) % block

    @jax.checkpoint
    def some_queries(args):
        qb, qpos = args                                  # (block, nh, d)
        sc = _mm("qnd,knd->nqk", qb, k, precision) / (dn + dr) ** 0.5
        sc = jnp.where((pos[None, :] <= qpos[:, None])[None], sc, -jnp.inf)
        return _mm("nqk,knd->qnd", jax.nn.softmax(sc, axis=-1), v, precision)

    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
    pp = jnp.pad(pos, (0, pad), constant_values=float(s))  # sees every key
    ctx = jax.lax.map(some_queries, (
        qp.reshape(-1, block, nh, dn + dr), pp.reshape(-1, block)))
    ctx = ctx.reshape(-1, nh * dv)[:s]
    return _mm("sk,kh->sh", ctx, lw["o"], precision)


def _swiglu(x, gate, up, down, precision):
    g = _mm("sh,hf->sf", x, gate, precision)
    u = _mm("sh,hf->sf", x, up, precision)
    return _mm("sf,fh->sh", jax.nn.silu(g) * u, down, precision)


def routing_weights(x, router_w, router_b, sizes, precision="float32"):
    """(S, E) float32: an expert's combine weight for each token, zero
    where the token did not choose it."""
    k = sizes["num_experts_per_tok"]
    s = jax.nn.sigmoid(_mm("sh,he->se", x, router_w, precision))
    choice = jax.lax.stop_gradient(s + router_b)
    larger = (choice[:, None, :] > choice[:, :, None]).sum(-1)   # (S, E)
    chosen = (larger < k).astype(jnp.float32)
    w = s * chosen
    if sizes.get("norm_topk_prob", True):
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return w * sizes["routed_scaling_factor"]


def moe(x, lw, sizes, precision="float32"):
    """The expert layer's feed-forward on x (S, H): shared expert plus
    the held experts' part of the routed sum."""
    first, count = sizes["experts_held"]
    w = routing_weights(x, lw["router_w"], lw["router_b"], sizes, precision)
    w = w[:, first:first + count]

    @jax.checkpoint
    def one(acc, ex):
        gate, up, down, we = ex
        return acc + we[:, None] * _swiglu(x, gate, up, down, precision), None

    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(x),
        (lw["ex_gate"], lw["ex_up"], lw["ex_down"], w.T))
    return routed + _swiglu(x, lw["sh_gate"], lw["sh_up"], lw["sh_down"],
                            precision)


def _moe_block(x, lw, sizes, precision):
    eps = sizes["rms_norm_eps"]
    x = x + _attention(_rms(x, lw["ln1"], eps), lw, sizes, precision)
    return x + moe(_rms(x, lw["ln2"], eps), lw, sizes, precision)


def _dense_block(x, lw, sizes, precision):
    eps = sizes["rms_norm_eps"]
    x = x + _attention(_rms(x, lw["ln1"], eps), lw, sizes, precision)
    return x + _swiglu(_rms(x, lw["ln2"], eps), lw["gate"], lw["up"],
                       lw["down"], precision)


def _sub(w, prefix, names):
    return {n: w[prefix + n] for n in names}


def trunk(w, ids, sizes, precision="float32"):
    """(S,) token ids -> (S, H): the last layer's output before the
    final norm."""
    x = w["embed"][ids]
    dense = jax.checkpoint(partial(_dense_block, sizes=sizes,
                                   precision=precision))
    block = jax.checkpoint(partial(_moe_block, sizes=sizes,
                                   precision=precision))
    x = dense(x, _sub(w, "l0_", ATTN + ("gate", "up", "down")))
    x, _ = jax.lax.scan(lambda c, lw: (block(c, lw), None), x,
                        _sub(w, "moe_", ATTN + MOE))
    return x


def mtp_hidden(w, h_last, ids, sizes, precision="float32"):
    """(S-1, H): the MTP module's final-norm output at positions
    0..S-2; position i has seen tokens <= i+1."""
    eps = sizes["rms_norm_eps"]
    both = jnp.concatenate(
        [_rms(w["embed"][ids[1:]], w["mtp_enorm"], eps),
         _rms(h_last[:-1], w["mtp_hnorm"], eps)], axis=-1)
    h = _mm("sk,kh->sh", both, w["mtp_eh"], precision)
    block = jax.checkpoint(partial(_moe_block, sizes=sizes,
                                   precision=precision))
    return _rms(block(h, _sub(w, "mtp_", ATTN + MOE)), w["mtp_norm"], eps)


def _ce_sum(w, hid, tgt, precision):
    """Sum over positions of the cross entropy of hid (N, H) against
    tgt (N,), the (N, V) logits taken a chunk of positions at a time."""
    n = tgt.shape[0]
    chunk = min(CE_CHUNK, n)
    pad = (-n) % chunk
    hid = jnp.pad(hid, ((0, pad), (0, 0)))
    tgt = jnp.pad(tgt, (0, pad))
    live = jnp.pad(jnp.ones((n,), jnp.float32), (0, pad))

    @jax.checkpoint
    def one(args):
        hc, tc, lc = args
        lg = _mm("sh,vh->sv", hc, w["head"], precision)
        lse = jax.nn.logsumexp(lg, axis=-1)
        picked = jnp.take_along_axis(lg, tc[:, None], axis=-1)[:, 0]
        return ((lse - picked) * lc).sum()

    return jax.lax.map(one, (hid.reshape(-1, chunk, hid.shape[-1]),
                             tgt.reshape(-1, chunk),
                             live.reshape(-1, chunk))).sum()


def row_losses(w, ids, sizes, precision="float32"):
    """(main, mtp): one row's mean cross entropy of the main head
    (S-1 targets) and of the MTP module (S-2 targets)."""
    s = ids.shape[0]
    eps = sizes["rms_norm_eps"]
    h_last = trunk(w, ids, sizes, precision)
    main = _ce_sum(w, _rms(h_last, w["lnf"], eps)[:-1], ids[1:],
                   precision) / (s - 1)
    hid = mtp_hidden(w, h_last, ids, sizes, precision)
    mtp = _ce_sum(w, hid[:-1], ids[2:], precision) / (s - 2)
    return main, mtp


def row_loss(w, ids, sizes, precision="float32"):
    main, mtp = row_losses(w, ids, sizes, precision)
    return main + sizes["mtp_loss_weight"] * mtp


def adam_steps(make_w0, batches, sizes, lr, precision="float32",
               rows_per_call=1, b1=0.9, b2=0.999, eps=1e-8, store_dtype=None,
               place=None, moment_dtype=None):
    """Follow ``len(batches)`` Adam steps (Kingma & Ba, bias-corrected,
    as ``optax.adam``) from float32 copies of the weights ``make_w0()``
    returns (called again at the end, so no second copy stays alive).
    One call takes the gradient of ``rows_per_call`` rows and adds it
    into the (donated) sum, so one block's activations are all that is
    alive. ``store_dtype``: the dtype the configuration keeps its
    parameters in; each update's result is rounded to it, all arithmetic
    staying float32. ``moment_dtype``: see the header. Returns the loss
    of each step, the per-leaf norm of the first step's gradient, and
    the per-leaf norm of the parameters' change after the last step."""
    place = place or (lambda t: t)
    n_rows, seq = batches[0].shape

    def block_loss(w, rows):
        return jax.lax.map(
            lambda r: row_loss(w, r, sizes, precision), rows).sum() / n_rows

    @partial(jax.jit, donate_argnums=(1, 2))
    def add_grad(w, acc, total, rows):
        val, g = jax.value_and_grad(block_loss)(w, rows)
        return {k: acc[k] + g[k] for k in acc}, total + val

    def rounded(x, dtype):
        if dtype is None:
            return x
        # not astype().astype(): XLA may drop that round trip
        info = jnp.finfo(dtype)
        return jax.lax.reduce_precision(x, info.nexp, info.nmant)

    mdt = jnp.dtype(moment_dtype or jnp.float32)

    @partial(jax.jit, donate_argnums=(0, 1, 2))
    def update(w, mu, nu, g, t):
        def one(p, m, n, gg):
            m = b1 * m.astype(jnp.float32) + (1 - b1) * gg
            n = b2 * n.astype(jnp.float32) + (1 - b2) * gg * gg
            m, n = rounded(m, moment_dtype), rounded(n, moment_dtype)
            mhat = m / (1 - b1 ** t)
            nhat = n / (1 - b2 ** t)
            p = rounded(p - lr * mhat / (jnp.sqrt(nhat) + eps), store_dtype)
            return p, m.astype(mdt), n.astype(mdt)
        out = {k: one(w[k], mu[k], nu[k], g[k]) for k in w}
        return tuple({k: o[i] for k, o in out.items()} for i in range(3))

    zeros = jax.jit(lambda t, dt: jax.tree_util.tree_map(
        lambda x: (x * 0.0).astype(dt), t), static_argnums=1)
    w = jax.jit(lambda t: {k: v.astype(jnp.float32)
                           for k, v in t.items()})(place(make_w0()))
    mu, nu = zeros(w, mdt), zeros(w, mdt)
    losses, grad_norm = [], None
    for t, batch in enumerate(batches, start=1):
        g, val = zeros(w, jnp.dtype(jnp.float32)), jnp.zeros((), jnp.float32)
        rows = jnp.asarray(batch).reshape(-1, rows_per_call, seq)
        for i in range(rows.shape[0]):
            g, val = add_grad(w, g, val, rows[i])
        losses.append(float(val))
        if grad_norm is None:
            grad_norm = {k: float(v) for k, v in
                         jax.jit(leaf_norms)(g).items()}
        w, mu, nu = update(w, mu, nu, g, jnp.float32(t))
        del g
    del mu, nu
    delta = jax.jit(lambda a, b: leaf_norms(
        {k: a[k] - b[k].astype(jnp.float32) for k in a}))(w, place(make_w0()))
    return {"losses": losses, "grad_norm": grad_norm,
            "delta_norm": {k: float(v) for k, v in delta.items()}}
