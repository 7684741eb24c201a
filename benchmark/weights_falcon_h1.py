"""The benchmark's own seeded weights for a Falcon-H1-shaped model.

One flat dict of named arrays in the dtype the model is served in, as
``weights.py`` makes BLOOM's: the adapter maps the names onto the
program's tree; the reference takes the same dict (and nothing the
program made). The blocks are alike, so a block leaf is stacked over the
layers on a leading axis (``gate``: (L, hidden, intermediate)), as the
program holds them; the reference takes a layer's slice at a time.

Every leaf is random, the norms' scales too (centred on 1), so a path
that drops one of them changes the result. Matrices are N(0,
``initializer_range``), the convolution's taps N(0, ``conv_std``) and its
bias N(0, ``initializer_range``). The state-space leaves follow Mamba-2's
own initialisation, so that a head's decay a token ``exp(dt A)`` lies
between 0.2 and 0.999 and a state handed over wrongly, or an update
dropped, is still in the logits hundreds of tokens later (N(0, 0.02)
everywhere would give ``dt`` ~ 0.7 and ``A`` ~ -1: a decay of ~0.5 a
token, which forgets any mistake in a dozen tokens): ``dt_bias`` the
inverse softplus of a ``dt`` log-uniform in [0.001, 0.1], ``A_log =
log(uniform(1, 16))``, ``D = 1 + N(0, initializer_range)``.

Made LEAF BY LEAF, and a large leaf a block of its leading axis at a
time inside its call: the head is 2.7 GB in bfloat16 and 5.3 GB as the
float32 normals it is rounded from, which does not fit beside 8 GB of
leaves already made.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from benchmark.weights import seed_key  # noqa: F401  (drivers take it here)

BLOCK_ELEMENTS = 1 << 28      # float32 normals a block of a leaf: 1 GiB

NORMAL, NORM, DT_BIAS, A_LOG = "normal", "norm", "dt_bias", "A_log"
# kinds that are N(0, sizes[kind]) with a spread of their own
IN_PROJ, CONV = "in_proj_std", "conv_std"


def leaf_shapes(sizes: dict) -> dict:
    """name -> (shape, kind)."""
    h, v, n = sizes["hidden_size"], sizes["vocab_size"], \
        sizes["num_hidden_layers"]
    hd = sizes["head_dim"]
    q, kv = sizes["num_attention_heads"] * hd, \
        sizes["num_key_value_heads"] * hd
    f = sizes["intermediate_size"]
    d, nh = sizes["mamba_d_ssm"], sizes["mamba_n_heads"]
    conv = d + 2 * sizes["mamba_n_groups"] * sizes["mamba_d_state"]
    return {
        "embed": ((v, h), NORMAL), "head": ((v, h), NORMAL),
        "lnf": ((h,), NORM),
        "ln1": ((n, h), NORM), "ln2": ((n, h), NORM),
        "q": ((n, h, q), NORMAL), "k": ((n, h, kv), NORMAL),
        "v": ((n, h, kv), NORMAL), "o": ((n, q, h), NORMAL),
        "in_proj": ((n, h, d + conv + nh), IN_PROJ),
        # tap j multiplies the input mamba_d_conv - 1 - j tokens back
        "conv_w": ((n, sizes["mamba_d_conv"], conv), CONV),
        "conv_b": ((n, conv), NORMAL),
        "dt_bias": ((n, nh), DT_BIAS), "A_log": ((n, nh), A_LOG),
        "D": ((n, nh), NORM), "ssm_norm": ((n, d), NORM),
        "out_proj": ((n, d, h), NORMAL),
        "gate": ((n, h, f), NORMAL), "up": ((n, h, f), NORMAL),
        "down": ((n, f, h), NORMAL),
    }


def n_params(sizes: dict) -> int:
    return sum(math.prod(shape) for shape, _ in leaf_shapes(sizes).values())


def block_params(sizes: dict) -> int:
    """Parameters of one block: every leaf but embedding, head and the
    final norm is stacked over the layers."""
    return sum(math.prod(shape[1:]) for name, (shape, _) in
               leaf_shapes(sizes).items()
               if name not in ("embed", "head", "lnf"))


def _draw(key, shape, kind, std):
    if kind == DT_BIAS:
        dt = jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    if kind == A_LOG:
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    x = jax.random.normal(key, shape, jnp.float32) * std
    return x + 1.0 if kind == NORM else x


@partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _leaf(key, shape, kind, std, dtype):
    info = jnp.finfo(dtype)

    def block(k, shape):
        # round by an operation XLA may not drop (see weights.py)
        return jax.lax.reduce_precision(
            _draw(k, shape, kind, std), info.nexp,
            info.nmant).astype(dtype)

    parts = next(d for d in range(1, shape[0] + 1) if shape[0] % d == 0
                 and math.prod(shape) // d <= BLOCK_ELEMENTS)
    if parts == 1:
        return block(key, shape)
    rows = (shape[0] // parts,) + shape[1:]
    out = jax.lax.map(lambda k: block(k, rows), jax.random.split(key, parts))
    return out.reshape(shape)


def make(key: jax.Array, sizes: dict, dtype=jnp.bfloat16) -> dict:
    """All leaves from ``key`` (see ``leaf_shapes``); a jitted call a
    leaf."""
    std = float(sizes["initializer_range"])
    dtype = jnp.dtype(dtype)
    return {name: _leaf(jax.random.fold_in(key, i), shape, kind,
                        float(sizes[kind]) if kind in (IN_PROJ, CONV)
                        else std, dtype)
            for i, (name, (shape, kind)) in enumerate(
                sorted(leaf_shapes(sizes).items()))}
