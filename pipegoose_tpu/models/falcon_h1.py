"""Falcon-H1 (``model_type: falcon_h1``): in EVERY block a Mamba-2
state-space mixer and a grouped-query attention read one normed input
side by side and are summed, then a SwiGLU MLP; muP multipliers on the
embedding, both mixers' inputs and outputs, the keys, the MLP and the
head.

Every size is a published config key
(https://huggingface.co/tiiuae/Falcon-H1-34B-Instruct/blob/main/config.json),
the equations those of ``transformers``' ``modeling_falcon_h1.py``.
RMSNorm, pre-norm residual blocks, no bias but the convolution's, an
untied head. With ``x`` the residual stream (``e = embed[ids] *
embedding_multiplier``), a block:

* ``u = RMSNorm(x)``, read by both mixers.
* Attention: ``q = (u * attention_in_multiplier) W_q``, ``k = (..) W_k *
  key_multiplier``, ``v = (..) W_v``; ``num_attention_heads`` query
  heads over ``num_key_value_heads`` KV heads (query head ``h`` reads KV
  head ``h // g``); rotate-half RoPE at ``rope_theta`` on all of
  ``head_dim``; causal softmax of ``q k^T / sqrt(head_dim)``;
  ``a = (ctx W_o) * attention_out_multiplier``.
* State-space mixer (Mamba-2; ``d_ssm = mamba_n_heads x mamba_d_head``,
  ``mamba_n_groups`` groups of ``mamba_d_state``): ``p = ((u *
  ssm_in_multiplier) W_in) * mup_vector`` splits into ``z`` (d_ssm) |
  ``xBC`` (d_ssm + 2 groups x d_state) | ``dt`` (heads), ``mup_vector``
  holding ``ssm_multipliers[0..4]`` on z | x | B | C | dt. A causal
  depthwise convolution of width ``mamba_d_conv`` and SiLU on ``xBC``
  (zeros before the sequence), then ``dt = softplus(dt + dt_bias)``,
  ``A = -exp(A_log)`` a head and, with ``H`` (d_head x d_state) a head
  in float32 (head ``h`` uses group ``h // (heads / groups)``),

      H_t = exp(dt_t A) H_{t-1} + dt_t x_t B_t^T
      y_t = H_t C_t + D x_t

  ``y = weight * RMSNorm over each group of d_ssm / groups of (y *
  silu(z))`` (``mamba_rms_norm``, ``mamba_norm_before_gate`` false),
  ``m = (y W_out) * ssm_out_multiplier``.
* ``x = x + a + m``; ``f = RMSNorm(x)``; ``x = x + (silu((f W_gate) *
  mlp_multipliers[0]) * (f W_up)) W_down * mlp_multipliers[1]``.

``logits = (RMSNorm(x) W_head) * lm_head_multiplier``.

The recurrence has two forms here. Over a whole sequence
(:func:`ssd_chunked`: the forward and the serving prefill) the chunked
matrix form at ``mamba_chunk_size``: inside a chunk ``(C B^T * L) X``
with ``L`` the decay mask, between chunks the state carried. A token at
a time (:func:`ssm_step`: the serving decode) the two lines above over
the rows of a step. What a sequence leaves behind is a STATE, not keys
that grow: ``H`` of every block and the convolution's last
``mamba_d_conv - 1`` inputs, which ``serving/`` keeps a slot
(``serving/kv_pool.py``: the state bank) beside the paged keys and
values of the attention. :func:`paged_model` is the description
``ServingEngine`` takes.

Not built: the variants the published 34B config does not use (a bias
on a projection, ``mamba_rms_norm`` false, ``mamba_norm_before_gate``
true, ``rope_scaling``), and training (the chunked scan's backward).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from pipegoose_tpu.models.laguna import _attend, apply_rotary, rope_frequencies
from pipegoose_tpu.models.mixtral import rms_norm


@dataclasses.dataclass(frozen=True)
class FalconH1Config:
    # published keys (defaults: Falcon-H1-34B-Instruct)
    vocab_size: int = 261120
    hidden_size: int = 5120
    intermediate_size: int = 21504
    num_hidden_layers: int = 72
    num_attention_heads: int = 20
    num_key_value_heads: int = 4
    head_dim: int = 128
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e11
    mamba_d_ssm: int = 4096
    mamba_n_heads: int = 32
    mamba_d_head: int = 128
    mamba_n_groups: int = 2
    mamba_d_state: int = 256
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 128
    embedding_multiplier: float = 5.656854249492381
    lm_head_multiplier: float = 0.0078125
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 0.0375
    key_multiplier: float = 0.011048543456039804
    ssm_in_multiplier: float = 0.25
    ssm_out_multiplier: float = 0.08838834764831845
    # on z | x | B | C | dt of the mixer's input projection
    ssm_multipliers: tuple = (0.3535533905932738, 0.25, 0.1767766952966369,
                              0.5, 0.3535533905932738)
    # on the gate's pre-activation, on the down projection's output
    mlp_multipliers: tuple = (0.1767766952966369, 0.011160714285714284)
    initializer_range: float = 0.02
    use_flash: bool = False
    # the recurrence's state: multiplied by a decay every token, so a
    # rounding a step accumulates over the hundreds of tokens it lives
    state_dtype: Any = jnp.float32
    dtype: Any = jnp.float32

    def __post_init__(self):
        if self.mamba_d_ssm != self.mamba_n_heads * self.mamba_d_head:
            raise ValueError("mamba_d_ssm is mamba_n_heads x mamba_d_head")
        if self.mamba_n_heads % self.mamba_n_groups \
                or self.mamba_d_ssm % self.mamba_n_groups:
            raise ValueError("the state-space heads divide over the groups")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("the query heads divide over the KV heads")
        if len(self.ssm_multipliers) != 5 or len(self.mlp_multipliers) != 2:
            raise ValueError("ssm_multipliers has 5 entries (z, x, B, C, "
                             "dt), mlp_multipliers 2 (gate, down)")

    @property
    def conv_dim(self) -> int:
        """Channels the convolution runs over: x | B | C."""
        return self.mamba_d_ssm + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def in_proj_dim(self) -> int:
        return self.mamba_d_ssm + self.conv_dim + self.mamba_n_heads

    def mup_vector(self) -> np.ndarray:
        """``ssm_multipliers`` spread over the input projection's
        columns z | x | B | C | dt."""
        gn = self.mamba_n_groups * self.mamba_d_state
        widths = (self.mamba_d_ssm, self.mamba_d_ssm, gn, gn,
                  self.mamba_n_heads)
        return np.concatenate([np.full((w,), m, np.float32)
                               for w, m in zip(widths, self.ssm_multipliers)])

    def state_shapes(self) -> tuple:
        """What a sequence leaves in a block, a slot: (name, shape,
        dtype) of the recurrence's ``H`` and of the convolution's last
        inputs."""
        return (
            ("ssm", (self.mamba_n_heads, self.mamba_d_head,
                     self.mamba_d_state), self.state_dtype),
            ("conv", (self.mamba_d_conv - 1, self.conv_dim), self.dtype),
        )

    def paged_model(self, tp_axis=None):
        """The description ``ServingEngine`` serves this model by."""
        return paged_model(self, tp_axis)


# -- init ------------------------------------------------------------------

def param_shapes(c: FalconH1Config) -> dict:
    """The parameter tree as shapes: the blocks are alike, so every leaf
    of theirs carries a leading ``(num_hidden_layers,)`` axis. The
    convolution's weight is ``(mamba_d_conv, channels)``: tap ``j``
    multiplies the input ``mamba_d_conv - 1 - j`` tokens back."""
    n, h, v = c.num_hidden_layers, c.hidden_size, c.vocab_size
    q, kv = c.num_attention_heads * c.head_dim, \
        c.num_key_value_heads * c.head_dim
    f, nh = c.intermediate_size, c.mamba_n_heads
    return {
        "embed": {"weight": (v, h)},
        "blocks": {
            "ln_1": {"scale": (n, h)},
            "attn": {"q": {"kernel": (n, h, q)}, "k": {"kernel": (n, h, kv)},
                     "v": {"kernel": (n, h, kv)}, "o": {"kernel": (n, q, h)}},
            "ssm": {"in_proj": {"kernel": (n, h, c.in_proj_dim)},
                    "conv": {"weight": (n, c.mamba_d_conv, c.conv_dim),
                             "bias": (n, c.conv_dim)},
                    "dt_bias": (n, nh), "A_log": (n, nh), "D": (n, nh),
                    "norm": {"scale": (n, c.mamba_d_ssm)},
                    "out_proj": {"kernel": (n, c.mamba_d_ssm, h)}},
            "ln_2": {"scale": (n, h)},
            "mlp": {"gate": {"kernel": (n, h, f)}, "up": {"kernel": (n, h, f)},
                    "down": {"kernel": (n, f, h)}},
        },
        "ln_f": {"scale": (h,)},
        "lm_head": {"weight": (v, h)},
    }


def init_params(config: FalconH1Config, key: jax.Array) -> dict:
    """N(0, initializer_range) matrices and convolution taps, unit norms,
    and the state-space leaves as Mamba-2 initialises them: ``dt_bias``
    the inverse softplus of a ``dt`` log-uniform in [0.001, 0.1], ``A_log
    = log(uniform(1, 16))``, ``D`` 1. ``dt_bias``, ``A_log`` and ``D``
    stay float32 (they shape a decay)."""
    c = config
    shapes, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(c), is_leaf=lambda x: isinstance(x, tuple))
    out = []
    for i, (path, shape) in enumerate(shapes):
        name, k = jax.tree_util.keystr(path), jax.random.fold_in(key, i)
        if "scale" in name:
            x = jnp.ones(shape, c.dtype)
        elif "dt_bias" in name:
            dt = jnp.exp(jax.random.uniform(
                k, shape, minval=np.log(1e-3), maxval=np.log(1e-1)))
            x = dt + jnp.log(-jnp.expm1(-dt))
        elif "A_log" in name:
            x = jnp.log(jax.random.uniform(k, shape, minval=1.0, maxval=16.0))
        elif name.endswith("['D']"):
            x = jnp.ones(shape, jnp.float32)
        elif "bias" in name:
            x = jnp.zeros(shape, c.dtype)
        else:
            x = (jax.random.normal(k, shape)
                 * c.initializer_range).astype(c.dtype)
        out.append(x)
    return jax.tree_util.tree_unflatten(treedef, out)


# -- layers ----------------------------------------------------------------

def _dot32(x, w):
    """x @ kernel, accumulated and returned in float32 (a multiplier
    follows before the result is rounded)."""
    return jnp.dot(x, w["kernel"], preferred_element_type=jnp.float32)


def _dot(x, w):
    return _dot32(x, w).astype(x.dtype)


def qkv(blk, h, pos, config: FalconH1Config):
    """``(q (B, S, H, hd), k, v (B, S, KV, hd), u)`` of one block at
    positions ``pos`` (B, S), rotary applied; ``u`` the normed input,
    which the state-space mixer reads too."""
    c = config
    b, s, _ = h.shape
    u = rms_norm(blk["ln_1"], h, c.rms_norm_eps)
    ua = (u.astype(jnp.float32) * c.attention_in_multiplier).astype(u.dtype)
    at = blk["attn"]
    q = _dot(ua, at["q"]).reshape(b, s, -1, c.head_dim)
    k = (_dot32(ua, at["k"]) * c.key_multiplier).astype(u.dtype).reshape(
        b, s, -1, c.head_dim)
    v = _dot(ua, at["v"]).reshape(b, s, -1, c.head_dim)
    with jax.named_scope("attn.rope"):
        freqs = rope_frequencies({"rope_theta": c.rope_theta}, c.head_dim)
        q, k = apply_rotary(q, pos, freqs), apply_rotary(k, pos, freqs)
    return q, k, v, u


def attend(q, k, v, config: FalconH1Config):
    """Causal attention over whole sequences (B, S, H * hd): the flash
    kernels with their GQA group where ``use_flash`` and the length is
    whole 128-lane tiles (their blocks are), dense masked scores for a
    shorter or ragged bucket (64, 192)."""
    flash = config.use_flash and q.shape[1] % 128 == 0
    return _attend(q, k, v, dataclasses.replace(config, use_flash=flash),
                   None)


def ssm_in(blk, u, config: FalconH1Config):
    """The mixer's input projection of ``u`` (.., hidden): ``z`` (..,
    d_ssm), ``xBC`` (.., conv_dim) before the convolution, ``dt`` (..,
    heads) float32 before its bias."""
    c = config
    with jax.named_scope("ssm.in_proj"):
        us = (u.astype(jnp.float32) * c.ssm_in_multiplier).astype(u.dtype)
        p = _dot32(us, blk["ssm"]["in_proj"]) * jnp.asarray(c.mup_vector())
    d = c.mamba_d_ssm
    return (p[..., :d].astype(u.dtype),
            p[..., d:d + c.conv_dim].astype(u.dtype), p[..., d + c.conv_dim:])


def _conv_taps(blk, window, config: FalconH1Config):
    """silu(bias + sum_j weight[j] * window[.., j, :]) over a window of
    ``mamba_d_conv`` inputs, oldest first: (.., d_conv, C) -> (.., C),
    float32 inside, the result in the model's dtype."""
    w = blk["ssm"]["conv"]
    acc = w["bias"].astype(jnp.float32) + jnp.sum(
        w["weight"].astype(jnp.float32) * window.astype(jnp.float32), axis=-2)
    return jax.nn.silu(acc).astype(config.dtype)


def _split_xbc(xbc, config: FalconH1Config):
    """Convolved ``xBC`` (.., conv_dim) -> x (.., heads, d_head), B, C
    (.., groups, d_state)."""
    c = config
    d, gn = c.mamba_d_ssm, c.mamba_n_groups * c.mamba_d_state
    lead = xbc.shape[:-1]
    return (xbc[..., :d].reshape(lead + (c.mamba_n_heads, c.mamba_d_head)),
            xbc[..., d:d + gn].reshape(
                lead + (c.mamba_n_groups, c.mamba_d_state)),
            xbc[..., d + gn:].reshape(
                lead + (c.mamba_n_groups, c.mamba_d_state)))


def _decay(blk, dt_raw):
    """``(dt, A)``: softplus(dt + dt_bias) and -exp(A_log), float32."""
    s = blk["ssm"]
    return (jax.nn.softplus(dt_raw + s["dt_bias"].astype(jnp.float32)),
            -jnp.exp(s["A_log"].astype(jnp.float32)))


def ssd_chunked(x, dt, a, bm, cm, chunk: int):
    """The recurrence ``H_t = exp(dt_t A) H_{t-1} + dt_t x_t B_t^T``,
    ``y_t = H_t C_t`` over whole sequences, in the chunked matrix form
    (Dao & Gu 2024, "state space duality"): inside a chunk of ``chunk``
    tokens ``Y = (C B^T * L) X`` with ``L[i, j] = exp(sum_{j<k<=i} dt_k
    A)`` for ``j <= i``, a chunk's own state ``sum_j L[last, j] dt_j x_j
    B_j^T``, and between chunks the state carried by a scan over the
    chunks (S / chunk steps).

    ``x`` (B, S, H, P), ``dt`` (B, S, H) float32 (0 where a position is
    padding: decay 1, input 0, so the state passes through), ``a`` (H,)
    float32, ``bm``, ``cm`` (B, S, G, N); ``S`` any length (padded here
    to whole chunks). Matrix-unit operands stay in ``x``'s dtype, sums
    and decays float32. Returns ``(y (B, S, H, P) float32, H_S (B, H, P,
    N) float32)``."""
    b, s, h, p = x.shape
    g, n = bm.shape[2:]
    hg = h // g
    pad = (-s) % chunk
    if pad:
        x, dt, bm, cm = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (x, dt, bm, cm))
    nc = (s + pad) // chunk
    xc = x.reshape(b, nc, chunk, g, hg, p)
    bc, cc = (t.reshape(b, nc, chunk, g, n) for t in (bm, cm))
    # heads before tokens: the (token, token) masks keep tokens minor
    dtc = jnp.moveaxis(dt.reshape(b, nc, chunk, g, hg), 2, -1)  # (b,c,g,hg,j)
    # a_cs[.., i]: the log decay from the chunk's start through token i
    a_cs = jnp.cumsum(dtc * a.reshape(g, hg, 1), axis=-1)
    # inside a chunk
    tri = jnp.tril(jnp.ones((chunk, chunk), bool))
    seg = a_cs[..., :, None] - a_cs[..., None, :]            # (b,c,g,hg,i,j)
    decay = jnp.where(tri, jnp.exp(jnp.where(tri, seg, 0.0)), 0.0)
    scores = jnp.einsum("bcign,bcjgn->bcgij", cc, bc,
                        preferred_element_type=jnp.float32)
    m = scores[:, :, :, None] * decay * dtc[..., None, :]
    y = jnp.einsum("bcghij,bcjghp->bcighp", m.astype(x.dtype), xc,
                   preferred_element_type=jnp.float32)
    # a chunk's own state, and the states the chunks hand on
    to_end = jnp.exp(a_cs[..., -1:] - a_cs) * dtc            # (b,c,g,hg,j)
    weighted = (xc.astype(jnp.float32)
                * jnp.moveaxis(to_end, -1, 2)[..., None]).astype(x.dtype)
    own = jnp.einsum("bcjghp,bcjgn->bcghpn", weighted, bc,
                     preferred_element_type=jnp.float32)
    whole = jnp.exp(a_cs[..., -1])                           # (b,c,g,hg)
    def carry(state, xs):
        own_c, whole_c = xs
        return whole_c[..., None, None] * state + own_c, state

    last, before = jax.lax.scan(
        carry, jnp.zeros((b, g, hg, p, n), jnp.float32),
        (jnp.moveaxis(own, 1, 0), jnp.moveaxis(whole, 1, 0)))
    before = jnp.moveaxis(before, 0, 1)                      # (b,c,g,hg,p,n)
    y = y + jnp.einsum("bcign,bcghpn->bcighp", cc, before.astype(x.dtype),
                       preferred_element_type=jnp.float32) \
        * jnp.moveaxis(jnp.exp(a_cs), -1, 2)[..., None]
    return y.reshape(b, s + pad, h, p)[:, :s], last.reshape(b, h, p, n)


def ssm_step(blk, state, xbc, dt_raw, config: FalconH1Config, live=None):
    """One token of the mixer's recurrence over R rows: ``state``
    ``{"ssm": (R, H, P, N) float32, "conv": (R, d_conv - 1, C)}``, the
    rows' projected ``xbc`` (R, C) and ``dt_raw`` (R, H). ``y`` is taken
    from the OLD state (``exp(dt A) (H C) + dt x (B . C)``, the same
    number) so that the new one is written once and read by nothing
    here. ``live`` (R,) bool: a row that holds no request keeps its
    state. Returns ``(state, y (R, H, P) float32)`` with ``D x`` added."""
    c = config
    with jax.named_scope("ssm.conv"):
        window = jnp.concatenate([state["conv"], xbc[:, None]], axis=1)
        x, bm, cm = _split_xbc(_conv_taps(blk, window, c), c)
        conv = window[:, 1:]
    with jax.named_scope("ssm.scan"):
        dt, a = _decay(blk, dt_raw)
        g, hg = c.mamba_n_groups, c.mamba_n_heads // c.mamba_n_groups
        r = x.shape[0]
        xf = x.astype(jnp.float32).reshape(r, g, hg, -1)       # (R,g,hg,P)
        bf, cf = bm.astype(jnp.float32), cm.astype(jnp.float32)
        da = jnp.exp(dt * a).reshape(r, g, hg)
        dtx = dt.reshape(r, g, hg)[..., None] * xf             # (R,g,hg,P)
        h0 = state["ssm"].astype(jnp.float32).reshape(
            r, g, hg, c.mamba_d_head, c.mamba_d_state)
        y = da[..., None] * jnp.sum(h0 * cf[:, :, None, None, :], axis=-1) \
            + dtx * jnp.sum(bf * cf, axis=-1)[:, :, None, None] \
            + blk["ssm"]["D"].astype(jnp.float32).reshape(g, hg)[..., None] * xf
        h1 = da[..., None, None] * h0 + dtx[..., None] * bf[:, :, None, None, :]
        ssm = h1.reshape(state["ssm"].shape).astype(state["ssm"].dtype)
    if live is not None:
        ssm = jnp.where(live[:, None, None, None], ssm, state["ssm"])
        conv = jnp.where(live[:, None, None], conv, state["conv"])
    return {"ssm": ssm, "conv": conv}, y.reshape(r, c.mamba_n_heads, -1)


def ssm_out(blk, y, z, config: FalconH1Config):
    """``y`` (.., d_ssm) float32 gated by ``z``, normed a group at a
    time, projected back to the hidden size and scaled."""
    c = config
    with jax.named_scope("ssm.gate_norm"):
        gated = y * jax.nn.silu(z.astype(jnp.float32))
        grouped = gated.reshape(gated.shape[:-1] + (c.mamba_n_groups, -1))
        grouped = grouped * jax.lax.rsqrt(
            (grouped * grouped).mean(-1, keepdims=True) + c.rms_norm_eps)
        normed = (grouped.reshape(gated.shape)
                  * blk["ssm"]["norm"]["scale"].astype(jnp.float32)
                  ).astype(c.dtype)
    with jax.named_scope("ssm.out_proj"):
        return (_dot32(normed, blk["ssm"]["out_proj"])
                * c.ssm_out_multiplier).astype(c.dtype)


def mixer(blk, u, config: FalconH1Config, mask=None):
    """The state-space mixer over whole sequences ``u`` (B, S, hidden)
    from an empty state. ``mask`` (B, S) 1 on real tokens of a
    RIGHT-padded sequence: a padded position leaves the state as it was.
    Returns ``(m (B, S, hidden), state)``: the state after the last real
    token, ``{"ssm": (B, H, P, N), "conv": (B, d_conv - 1, C)}``, the
    convolution's inputs zeros where the sequence has fewer."""
    c = config
    b, s, _ = u.shape
    k = c.mamba_d_conv
    z, xbc, dt_raw = ssm_in(blk, u, c)
    with jax.named_scope("ssm.conv"):
        padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
        window = jnp.stack([padded[:, j:j + s] for j in range(k)], axis=2)
        x, bm, cm = _split_xbc(_conv_taps(blk, window, c), c)
        # the last k - 1 REAL inputs: positions n - k + 1 .. n - 1 of the
        # sequence are n .. n + k - 2 of the padded one
        n = (jnp.full((b,), s, jnp.int32) if mask is None
             else mask.sum(axis=1).astype(jnp.int32))
        conv = jax.vmap(lambda row, at: jax.lax.dynamic_slice_in_dim(
            row, at, k - 1, axis=0))(padded, n)
    with jax.named_scope("ssm.scan"):
        dt, a = _decay(blk, dt_raw)
        if mask is not None:
            dt = dt * (mask > 0)[..., None]
        y, ssm = ssd_chunked(x, dt, a, bm, cm, c.mamba_chunk_size)
        y = y + blk["ssm"]["D"].astype(jnp.float32)[:, None] \
            * x.astype(jnp.float32)
    m = ssm_out(blk, y.reshape(b, s, -1), z, c)
    return m, {"ssm": ssm.astype(c.state_dtype), "conv": conv}


def finish(blk, h, ctx, m, config: FalconH1Config):
    """The rest of a block once attention has given ``ctx`` (B, S, H *
    hd) and the mixer ``m``: output projection, both added, the MLP."""
    c = config
    a = (_dot32(ctx, blk["attn"]["o"])
         * c.attention_out_multiplier).astype(h.dtype)
    h = h + a + m
    f = rms_norm(blk["ln_2"], h, c.rms_norm_eps)
    mlp = blk["mlp"]
    gate = jax.nn.silu(_dot32(f, mlp["gate"]) * c.mlp_multipliers[0])
    act = (gate * _dot32(f, mlp["up"])).astype(h.dtype)
    return h + (_dot32(act, mlp["down"]) * c.mlp_multipliers[1]).astype(h.dtype)


def embed(params, tokens, config: FalconH1Config):
    e = jnp.take(params["embed"]["weight"], tokens, axis=0)
    return (e.astype(jnp.float32)
            * config.embedding_multiplier).astype(config.dtype)


def logits_fn(params, hidden, config: FalconH1Config):
    """(.., V) float32 over the head's rows."""
    return jnp.einsum("...h,vh->...v", hidden, params["lm_head"]["weight"],
                      preferred_element_type=jnp.float32) \
        * config.lm_head_multiplier


def _trunk(params, input_ids, config: FalconH1Config, mask=None):
    """Embedding and every block over whole sequences from position 0
    and an empty state. Returns the final norm's output, each block's
    rotated keys and values, each block's state after the last real
    token."""
    c = config
    b, s = input_ids.shape
    x = embed(params, input_ids, c)
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))

    def block(x, blk):
        q, k, v, u = qkv(blk, x, pos, c)
        ctx = attend(q, k, v, c)
        m, state = mixer(blk, u, c, mask)
        return finish(blk, x, ctx, m, c), (k, v, state)

    x, (ks, vs, states) = jax.lax.scan(block, x, params["blocks"])
    return rms_norm(params["ln_f"], x, c.rms_norm_eps), ks, vs, states


def forward(params, input_ids, config: FalconH1Config):
    """(B, S) token ids -> (B, S, V) float32 logits."""
    return logits_fn(params, _trunk(params, input_ids, config)[0], config)


def prefill(params, ids, mask, config: FalconH1Config):
    """The serving prefill: one RIGHT-padded prompt ``ids`` (1, S_pad)
    with ``mask`` (1, S_pad) 1 on its tokens, through the model's own
    forward. Returns the logits after the last real token (1, V) and the
    cache: ``"k"``, ``"v"`` (L, 1, S_pad, KV, hd), and ``"state"``:
    every block's ``{"ssm": (L, 1, H, P, N), "conv": (L, 1, d_conv - 1,
    C)}`` after the last REAL token (attention is causal and a padded
    position leaves the state as it was, so the padding changes
    nothing)."""
    hidden, ks, vs, states = _trunk(params, ids, config, mask)
    n = mask.sum(axis=1).astype(jnp.int32)
    last = jnp.take_along_axis(hidden, (n - 1)[:, None, None], axis=1)
    return (logits_fn(params, last, config)[:, 0],
            {"k": ks, "v": vs, "state": states})


# -- the description the paged programs take ---------------------------------

def paged_model(config: FalconH1Config, tp_axis=None):
    """Falcon-H1 as ``serving/blocks.PagedModel``: one group of stacked
    blocks on the ``global`` cache kind for the attention's keys and
    values, and a state a slot a block for the mixer (``state``), which
    a decode step reads and overwrites through the group's ``mix``."""
    from pipegoose_tpu.serving.blocks import GLOBAL, LayerGroup, PagedModel
    from pipegoose_tpu.serving.kv_pool import update_state_rows

    if tp_axis is not None:
        raise ValueError("falcon_h1 is served on one device: a mesh is not "
                         "built for a model with a state a slot")
    c = config

    def mix(blk, u, bank, layer, live):
        """A decode step's mixer: ``u`` (B, 1, hidden), row ``i`` slot
        ``i`` of ``bank``."""
        z, xbc, dt_raw = ssm_in(blk, u[:, 0], c)
        bank, y = update_state_rows(
            bank, layer, live,
            lambda rows, xs: ssm_step(blk, rows, xs[0], xs[1], c, xs[2]),
            (xbc, dt_raw, live))
        return ssm_out(blk, y.reshape(y.shape[0], -1), z, c)[:, None], bank

    return PagedModel(
        n_kv_head=c.num_key_value_heads, head_dim=c.head_dim, dtype=c.dtype,
        groups=(LayerGroup(
            kind=GLOBAL, n=c.num_hidden_layers, stacked=True,
            params=lambda p: p["blocks"],
            qkv=lambda blk, h, pos: qkv(blk, h, pos, c),
            finish=lambda blk, h, ctx, m, live: (finish(blk, h, ctx, m, c),
                                                 None),
            mix=mix),),
        embed=lambda p, tokens: embed(p, tokens, c),
        final=lambda p, h: rms_norm(p["ln_f"], h, c.rms_norm_eps),
        logits=lambda p, h: logits_fn(p, h, c),
        prefill=lambda p, ids, mask: prefill(p, ids, mask, c),
        left_pad=False,
        state=c.state_shapes(),
    )
