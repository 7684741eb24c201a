"""Under ``remat`` a block's checkpoint keeps what the flash kernel left
for its backward (``ops/flash_attention.py:RESIDUAL_NAMES``), so a train
step runs ``flash_fwd`` once an attention layer and not twice; it keeps
nothing else the block computes unless ``remat_policy`` asks for it.
Both are facts of the gradient's jaxpr, read here with no chip."""
from functools import partial

import jax
import jax.numpy as jnp
import pytest

from pipegoose_tpu.models import bloom
from pipegoose_tpu.models import glm4_moe_lite as glm
from pipegoose_tpu.testing import kernel_calls, saved_residuals

B, S = 2, 64


def _elements(shape_text):
    dims = shape_text[shape_text.index("[") + 1:-1]
    n = 1
    for d in dims.split(","):
        n *= int(d) if d else 1
    return n


@pytest.mark.parametrize("policy", [None, "dots", "attn"])
def test_bloom_runs_flash_fwd_once_a_layer_under_every_remat_policy(policy):
    cfg = bloom.BloomConfig(vocab_size=96, hidden_size=64, n_layer=3, n_head=2,
                            remat=True, remat_policy=policy, use_flash=True)
    params = bloom.init_params(cfg, jax.random.PRNGKey(0))
    ids = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, 96)

    step = jax.make_jaxpr(jax.grad(
        lambda p: bloom.loss_fn(p, ids, None, ids, cfg)))(params)
    assert kernel_calls(step, "flash_fwd") == cfg.n_layer
    # the backward is one kernel a layer
    assert kernel_calls(step, "flash_bwd") == cfg.n_layer
    assert kernel_calls(step, "flash_dq") == 0
    assert kernel_calls(step, "flash_dkv") == 0

    # one block, as ``forward_hidden`` wraps it
    block = bloom._remat_wrap(
        partial(bloom._block, config=cfg, tp_axis=None), cfg)
    blk = jax.tree_util.tree_map(lambda a: a[0], params["blocks"])
    x = jnp.ones((B, S, cfg.hidden_size))
    bias = bloom.attention_bias(jnp.ones((B, S), jnp.int32), cfg)
    kept = saved_residuals(lambda blk, x: block(blk, x, bias).sum(), blk, x)
    heads = B * cfg.n_head
    out, lse = f"f32[{heads},{S},{cfg.head_dim}]", f"f32[{heads},{S}]"
    assert (lse, "named 'flash_lse'") in kept
    assert out in {shape for shape, _ in kept}
    if policy is None:
        # the kernel's two residuals and nothing else the block computes
        assert [shape for shape, _ in kept] == sorted([out, lse])
    else:
        # what the policy asked for is still kept, beside them
        big = [s for s, _ in kept if _elements(s) >= B * S * cfg.hidden_size]
        assert len(big) > 1


def test_glm_runs_flash_fwd_once_a_layer_mtp_included():
    from tests.models.test_glm4_moe_lite import TINY, _setup

    _, cfg, _, tree, ids = _setup(
        {"remat": True, "use_flash": True, "fused_ce": False})
    layers = TINY["num_hidden_layers"] + TINY["num_nextn_predict_layers"]
    step = jax.make_jaxpr(jax.grad(
        lambda p: glm.loss_fn(p, ids, None, ids, cfg)))(tree)
    assert kernel_calls(step, "flash_fwd") == layers
    assert kernel_calls(step, "flash_bwd") == layers
    assert kernel_calls(step, "flash_dq") == 0
    assert kernel_calls(step, "flash_dkv") == 0

    # the block as ``_trunk`` wraps it (the MTP module runs the same one)
    x, (cos, sin, bias, block), _ = glm._trunk(tree, ids, None, cfg, None)
    kept = saved_residuals(
        lambda blk, x: block(blk, x, cos, sin, bias, cfg, None)[0].sum(),
        tree["dense"], x)
    b, s = ids.shape
    heads, width = b * cfg.num_attention_heads, cfg.v_head_dim
    assert [shape for shape, _ in kept] == sorted(
        [f"f32[{heads},{s},{width}]", f"f32[{heads},{s}]"])
    assert (f"f32[{heads},{s}]", "named 'flash_lse'") in kept
