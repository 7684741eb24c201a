"""The compiled step's counter channel (``has_aux``) and leaves without
gradient (``frozen``): both resolved at build time, so BLOOM's step,
which uses neither, lowers to the program it lowered to before they
existed; on, the counters come back replicated, and a frozen leaf has
no gradient, no optimizer state and no change."""
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

from pipegoose_tpu.distributed import ParallelContext
from pipegoose_tpu.distributed.compat import shard_map
from pipegoose_tpu.models import bloom
from pipegoose_tpu.optim.zero import DistributedOptimizer
from pipegoose_tpu.parallel import make_hybrid_train_step
from pipegoose_tpu.parallel.hybrid import zero_state_spec
from pipegoose_tpu.telemetry import AuxRecorder, MetricsRegistry
from pipegoose_tpu.trainer import Trainer


@pytest.fixture()
def parts(devices):
    cfg = bloom.BloomConfig(vocab_size=64, hidden_size=32, n_layer=2, n_head=2)
    params = bloom.init_params(cfg, jax.random.PRNGKey(0))
    ctx = ParallelContext(tensor_parallel_size=2, data_parallel_size=4)
    yield cfg, params, ctx
    ctx.destroy()


def _ids(batch=8, seq=8, seed=0):
    return jnp.asarray(np.random.RandomState(seed).randint(0, 64, (batch, seq)))


def test_blooms_step_lowers_to_the_program_it_was_before_the_channel(parts):
    """The step as it was written before ``has_aux``/``frozen`` existed,
    built by hand here, against ``make_hybrid_train_step`` with both
    off: the same lowered text, byte for byte."""
    cfg, params, ctx = parts

    def loss_fn(p, ids):
        return bloom.loss_fn(p, ids, None, ids, cfg, tp_axis="tensor")

    specs = bloom.tp_specs(params)
    opt = DistributedOptimizer(optax.adam(1e-3), axis_name="data")
    init_fn, make_step = make_hybrid_train_step(loss_fn, specs, opt, ctx)
    opt_state = jax.eval_shape(init_fn, params)
    now = make_step(params).lower(params, opt_state, _ids()).as_text()

    def _step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        new_params, new_state = opt.step(grads, opt_state, params)
        loss = lax.pmean(loss, "data")
        return new_params, new_state, loss

    spec = zero_state_spec(opt, params, specs, ctx.mesh)
    before = jax.jit(shard_map(
        _step, mesh=ctx.mesh, in_specs=(specs, spec, P("data")),
        out_specs=(specs, spec, P()), check_vma=False),
        donate_argnums=(0, 1))
    assert before.lower(params, opt_state, _ids()).as_text() == now


def test_counters_come_back_beside_the_loss(parts):
    cfg, params, ctx = parts

    def loss_fn(p, ids):
        loss = bloom.loss_fn(p, ids, None, ids, cfg, tp_axis="tensor")
        return loss, {"twice": 2.0 * loss,
                      "rows": jnp.asarray(ids.shape[0], jnp.float32)}

    specs = bloom.tp_specs(params)
    opt = DistributedOptimizer(optax.adam(1e-3), axis_name="data")
    init_fn, make_step = make_hybrid_train_step(loss_fn, specs, opt, ctx,
                                                has_aux=True)
    out = make_step(params)(jax.tree_util.tree_map(jnp.copy, params),
                            init_fn(params), _ids())
    assert len(out) == 4
    loss, aux = out[2], out[3]
    np.testing.assert_allclose(float(aux["twice"]), 2.0 * float(loss),
                               rtol=1e-6)
    assert float(aux["rows"]) == 2.0      # a data replica's rows, averaged
    with pytest.raises(ValueError, match="n_accum"):
        make_hybrid_train_step(loss_fn, specs, opt, ctx, has_aux=True,
                               n_accum=2)


def test_a_frozen_leaf_has_no_gradient_no_state_and_no_change(parts):
    cfg, params, ctx = parts
    frozen = jax.tree_util.tree_map(lambda _: False, params)
    frozen["ln_f"]["bias"] = True
    params["ln_f"]["bias"] = params["ln_f"]["bias"] + 0.25
    registry = MetricsRegistry(enabled=True)
    recorder = AuxRecorder(registry=registry,
                           names={"loss_again": ("gauge", "test.loss")})

    def loss_fn(p, ids):
        loss = bloom.loss_fn(p, ids, None, ids, cfg, tp_axis="tensor")
        return loss, {"loss_again": loss}

    trainer = Trainer(
        loss_fn=loss_fn, params=params, param_specs=bloom.tp_specs(params),
        optimizer=DistributedOptimizer(optax.adam(1e-2), axis_name="data"),
        parallel_context=ctx, has_aux=True, frozen=frozen,
        callbacks=[recorder])
    trainer.fit([_ids(seed=i) for i in range(3)])
    seen = recorder.take()
    # no change, though the loss depends on it and every other leaf moved
    np.testing.assert_array_equal(np.asarray(trainer.params["ln_f"]["bias"]),
                                  np.asarray(params["ln_f"]["bias"]))
    assert float(jnp.abs(trainer.params["ln_f"]["scale"]
                         - params["ln_f"]["scale"]).max()) > 0
    # no optimizer state: Adam's moments lack the leaf
    mu = trainer.opt_state.inner[0].mu
    assert mu["ln_f"]["bias"] is None and mu["ln_f"]["scale"] is not None
    n_state = len(jax.tree_util.tree_leaves(mu))
    assert n_state == len(jax.tree_util.tree_leaves(params)) - 1
    # the recorder has each step's counters and the registry the newest
    assert len(seen) == 3 and recorder.take() == []
    np.testing.assert_allclose(
        [float(c["loss_again"]) for c in seen],
        [float(x) for x in trainer.state.losses], rtol=1e-6)
    assert registry.gauge("test.loss").value == pytest.approx(
        float(seen[-1]["loss_again"]))
    # evaluate() runs the same loss_fn and takes the loss alone
    assert np.isfinite(trainer.evaluate([_ids(seed=9)]))
