"""Driver ``train_model``: driver ``train``'s procedure for any model
whose configuration file names its own adapter, weights and reference
(``program.adapter`` / ``.weights`` / ``.reference``, files beside
``program_bloom.py``), so that another architecture adds files and no
driver.

As ``train``: ONE ``Trainer`` on a tensor x data mesh with ZeRO-1 Adam;
set-up drives it from the seed through its first steps (``check.steps``)
through the window's own feed and call; the window; the peak; the
program's state freed; the plain reference follows the same steps;
``compare``. ``_feed``, ``compare``, ``_spread`` and ``ADAM_B1`` are
``drivers/train.py``'s own, loaded from that file.

What differs: the adapter says what ``Trainer`` is given (the loss on
the program's normal path, a counter channel, leaves without gradient);
a leaf with no gradient reads 0 for the program; the step's counters
(``telemetry.AuxRecorder``) are left in ``facts["counters"]``, one
entry a window step.
"""
from __future__ import annotations

import gc
import json
import math
import os
import time

from benchmark import harness, traffic

_train = harness.load_module(os.path.join(harness.HERE, "drivers", "train.py"))
ADAM_B1 = _train.ADAM_B1


def _parts(config: dict) -> tuple:
    """(adapter, weights, reference) modules, by the names the
    configuration file gives."""
    names = config["program"]
    return tuple(
        harness.load_module(os.path.join(harness.HERE, names[k] + ".py"))
        for k in ("adapter", "weights", "reference"))


def _reference_steps(ctx, precision: str):
    import jax
    import jax.numpy as jnp

    adapter, weights, reference = _parts(ctx.config)
    w = ctx.workload
    sizes = adapter.sizes(ctx.config)
    dtype = jnp.dtype(ctx.config["dtype"])
    key = weights.seed_key(ctx.seed)
    more = {}
    if ctx.config["program"].get("reference_moment_dtype"):
        more["moment_dtype"] = jnp.dtype(
            ctx.config["program"]["reference_moment_dtype"])
    rows = [traffic.token_batch(sizes["vocab_size"], ctx.seed, s,
                                w["global_batch"], w["seq"])
            for s in range(w["check"]["steps"])]
    return reference.adam_steps(
        jax.jit(lambda: weights.make(key, sizes, dtype)), rows, sizes,
        w["learning_rate"], precision=precision,
        rows_per_call=w["check"]["reference_rows_per_call"], b1=ADAM_B1,
        store_dtype=dtype, place=_train._spread(ctx.devices), **more)


def run(ctx):
    t_run = time.perf_counter()
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    adapter, weights, reference = _parts(ctx.config)
    cfg = adapter.make_config(ctx.config, ctx.config.get("model_options"))

    from pipegoose_tpu import ParallelContext
    from pipegoose_tpu.optim.zero import DistributedOptimizer
    from pipegoose_tpu.telemetry import AuxRecorder
    from pipegoose_tpu.trainer import Callback, Trainer

    t_imported = time.perf_counter()
    w = ctx.workload
    sizes = adapter.sizes(ctx.config)
    vocab = sizes["vocab_size"]
    tp, dp = w["mesh"]["tensor"], w["mesh"]["data"]
    batch, seq, lr = w["global_batch"], w["seq"], w["learning_rate"]
    dtype = jnp.dtype(ctx.config["dtype"])
    key = weights.seed_key(ctx.seed)
    first_steps = w["check"]["steps"]

    pctx = ParallelContext(tensor_parallel_size=tp, data_parallel_size=dp,
                           devices=ctx.devices)
    mesh = pctx.mesh

    def fresh_tree(k):
        return adapter.to_tree(weights.make(k, sizes, dtype), ctx.config)

    shapes = jax.eval_shape(fresh_tree, key)
    specs = adapter.specs(shapes)
    shard = jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), specs,
                                   is_leaf=lambda x: isinstance(x, P))
    params = jax.block_until_ready(
        jax.jit(fresh_tree, out_shardings=shard)(key))
    t_weights = time.perf_counter()

    def delta(p, k):
        now = adapter.from_tree(p, ctx.config)
        start = weights.make(k, sizes, dtype)
        return reference.leaf_norms(
            {n: now[n].astype(jnp.float32) - start[n].astype(jnp.float32)
             for n in now})

    class Probe(Callback):
        """After step 1: the norm of each leaf of the gradient, as the
        optimizer got it (Adam's first moment after one step is
        (1 - b1) * g). After the last followed step: the norm of each
        leaf's change against the seeded weights, made again in the jit."""

        def __init__(self):
            self.grad_norm = self.delta_norm = None
            self.step_ends = []      # host clock, the followed steps
            self._norms = jax.jit(lambda mu: reference.leaf_norms(
                adapter.from_tree(mu, ctx.config)))
            self._delta = jax.jit(delta)

        def on_step_end(self, trainer, step, loss):
            if step <= first_steps:
                self.step_ends.append(time.perf_counter())
            if step == 1:
                self.grad_norm = self._norms(trainer.opt_state.inner[0].mu)
            if step == first_steps:
                self.delta_norm = self._delta(trainer.params, key)

    class StepClock(Callback):
        """Per-step wall time with the device drained at both ends
        (traced run only: the fence costs the overlap it measures)."""

        def __init__(self):
            self.seconds, self._span = [], None

        def on_step_start(self, trainer, step):
            jax.block_until_ready(trainer.params)
            self._span = harness.annotate("train.step")
            self._span.__enter__()
            self._t0 = time.perf_counter()

        def on_step_end(self, trainer, step, loss):
            jax.block_until_ready((loss, trainer.params))
            self.seconds.append(time.perf_counter() - self._t0)
            self._span.__exit__(None, None, None)

    probe, clock = Probe(), StepClock()
    counters = AuxRecorder(names=adapter.counter_metrics())
    given = adapter.trainer_kwargs(cfg, shapes)
    trainer = Trainer(
        params=params, param_specs=specs,
        optimizer=DistributedOptimizer(optax.adam(lr, b1=ADAM_B1),
                                       axis_name="data"),
        parallel_context=pctx,
        callbacks=[probe] + ([counters] if given.get("has_aux") else [])
        + ([clock] if ctx.trace else []), **given)
    del params

    # the first steps: compile, warm up, and what the reference follows
    t_built = time.perf_counter()
    trainer.fit(_train._feed(trainer, vocab, ctx.seed, batch, seq, 0,
                             n=first_steps))
    first_losses = [float(x) for x in trainer.state.losses[:first_steps]]
    # a leaf with no gradient has no moment: its gradient reads 0
    got_grad = {k: 0.0 for k in weights.leaf_shapes(sizes)}
    got_grad.update({k: float(v) for k, v in probe.grad_norm.items()})
    got_delta = {k: float(v) for k, v in probe.delta_norm.items()}
    clock.seconds.clear()
    first_counters = counters.take()
    print("setup " + json.dumps(_train.setup_facts(
        ctx, t_run, t_imported, t_weights, t_built, probe.step_ends,
        first_steps)), flush=True)

    compiles = ctx.watch.count
    step0 = trainer.state.step
    with harness.traced_window(ctx):
        t_window = time.perf_counter()
        trainer.fit(_train._feed(trainer, vocab, ctx.seed, batch, seq, step0,
                                 deadline=t_window + ctx.seconds))
        jax.block_until_ready(trainer.params)
        window_losses = [float(x) for x in trainer.state.losses[step0:]]
        wall = time.perf_counter() - t_window
    harness.refuse_compiles(ctx, compiles)
    steps = trainer.state.step - step0
    bad = sum(1 for x in window_losses if not math.isfinite(x))
    tokens_per_s = steps * batch * seq / wall
    peak = harness.memory_peak_bytes(ctx.devices)
    window_counters = [
        {k: v.tolist() for k, v in c.items()} for c in counters.take()]
    print("train " + json.dumps({
        "steps": steps, "wall_s": wall, "first_losses": first_losses,
        "last_loss": window_losses[-1] if window_losses else None,
        "first_counters": [{k: v.tolist() for k, v in c.items()}
                           for c in first_counters[:1]],
        "last_counters": window_counters[-1:]}),
        flush=True)

    # free the program's state, then let the reference follow
    del trainer, probe
    pctx.destroy()
    gc.collect()
    t_ref = time.perf_counter()
    ref = _reference_steps(ctx, "float32")
    print(f"reference: {time.perf_counter() - t_ref:.1f} s", flush=True)
    ctx.reference = ref
    _train.compare(ctx.checks, first_losses, got_grad, got_delta, ref,
                   w["check"])

    return harness.Result(
        end_to_end={"train_tokens_per_s": tokens_per_s},
        attempted=steps, failed=bad, t_window_start=t_window,
        memory_peak_bytes=peak,
        facts={"step_s": list(clock.seconds),
               "window_wall_s": wall, "steps": steps,
               "tokens_per_step": batch * seq, "seq": seq, "batch": batch,
               "rows_per_replica": batch // dp, "tensor": tp,
               "chips": tp * dp, "sizes": sizes, "peaks": ctx.peaks,
               "dtype": ctx.config["dtype"], "memory_peak_bytes": peak,
               "counters": window_counters})


def control(ctx):
    """The reference in the program's place, one precision below the
    configuration's: the same steps with every matmul operand rounded
    to fp8, held to the same comparison. Needs ``run`` first."""
    low = _reference_steps(ctx, "fp8")
    checks = harness.Checks()
    _train.compare(
        checks, low["losses"],
        {k: v * (1.0 - ADAM_B1) for k, v in low["grad_norm"].items()},
        low["delta_norm"], ctx.reference, ctx.workload["check"])
    return checks
