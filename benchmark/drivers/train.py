"""Driver ``train``: ``Trainer.fit`` over fresh seeded batches on a
tensor x data mesh, with ZeRO-1 Adam.

Set-up builds ONE trainer, drives it from the seed through its first
steps (``check.steps`` of the workload) through the window's own feed and call, and hands that same
object to the window. After the window the program's state is freed and
the plain reference follows the same steps; see ``check`` in the
workload's file for the limits.
"""
from __future__ import annotations

import gc
import json
import math
import time

import numpy as np

from benchmark import harness, program_bloom, traffic, weights
from benchmark.reference import bloom_ref

ADAM_B1 = 0.9


def _feed(trainer, vocab, seed, batch, seq, first_step, n=None, deadline=None):
    """Fresh rows for every step, made on the host. At most two steps
    are in flight: before step k is handed over, step k-2's loss is
    waited for, so the device never idles and the host never runs a
    window's worth of steps ahead of it. Stops after ``n`` batches or
    once ``deadline`` has passed."""
    import jax

    k = 0
    while (n is None or k < n) and (
            deadline is None or time.perf_counter() < deadline):
        with harness.annotate("train.make_batch"):
            rows = traffic.token_batch(vocab, seed, first_step + k, batch, seq)
        losses = trainer.state.losses
        if len(losses) >= 2:
            with harness.annotate("train.wait_two_back"):
                jax.block_until_ready(losses[-2])
        yield rows
        k += 1


def run(ctx):
    t_run = time.perf_counter()
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from pipegoose_tpu import ParallelContext
    from pipegoose_tpu.models import bloom
    from pipegoose_tpu.optim.zero import DistributedOptimizer
    from pipegoose_tpu.trainer import Callback, Trainer

    t_imported = time.perf_counter()
    w = ctx.workload
    sizes = ctx.config["sizes"]
    vocab = sizes["vocab_size"]
    tp, dp = w["mesh"]["tensor"], w["mesh"]["data"]
    batch, seq, lr = w["global_batch"], w["seq"], w["learning_rate"]
    dtype = jnp.dtype(ctx.config["dtype"])
    key = weights.seed_key(ctx.seed)
    first_steps = w["check"]["steps"]

    pctx = ParallelContext(tensor_parallel_size=tp, data_parallel_size=dp,
                           devices=ctx.devices)
    mesh = pctx.mesh
    cfg = program_bloom.make_config(ctx.config,
                                    ctx.config.get("model_options"))

    def fresh_tree(k):
        return program_bloom.to_tree(weights.make(k, sizes, dtype))

    if vocab % tp:
        raise SystemExit(f"benchmark: vocabulary {vocab} does not divide "
                         f"over tensor={tp}; this driver pads nothing")
    shapes = jax.eval_shape(fresh_tree, key)
    specs = bloom.tp_specs(shapes)
    shard = jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), specs,
                                   is_leaf=lambda x: isinstance(x, P))
    params = jax.block_until_ready(
        jax.jit(fresh_tree, out_shardings=shard)(key))
    t_weights = time.perf_counter()

    def delta(p, k):
        now = program_bloom.from_tree(p)
        start = weights.make(k, sizes, dtype)
        return bloom_ref.leaf_norms(
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
            self._norms = jax.jit(lambda mu: bloom_ref.leaf_norms(
                program_bloom.from_tree(mu)))
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
    trainer = Trainer(
        loss_fn=lambda p, ids: bloom.loss_fn(p, ids, None, ids, cfg,
                                             tp_axis="tensor"),
        params=params, param_specs=specs,
        optimizer=DistributedOptimizer(optax.adam(lr, b1=ADAM_B1),
                                       axis_name="data"),
        parallel_context=pctx,
        callbacks=[probe] + ([clock] if ctx.trace else []))
    del params

    # the first steps: compile, warm up, and what the reference follows
    t_built = time.perf_counter()
    trainer.fit(_feed(trainer, vocab, ctx.seed, batch, seq, 0, n=first_steps))
    first_losses = [float(x) for x in trainer.state.losses[:first_steps]]
    got_grad = {k: float(v) for k, v in probe.grad_norm.items()}
    got_delta = {k: float(v) for k, v in probe.delta_norm.items()}
    clock.seconds.clear()
    print("setup " + json.dumps(setup_facts(
        ctx, t_run, t_imported, t_weights, t_built, probe.step_ends,
        first_steps)), flush=True)

    compiles = ctx.watch.count
    step0 = trainer.state.step
    with harness.traced_window(ctx):
        t_window = time.perf_counter()
        trainer.fit(_feed(trainer, vocab, ctx.seed, batch, seq, step0,
                          deadline=t_window + ctx.seconds))
        jax.block_until_ready(trainer.params)
        window_losses = [float(x) for x in trainer.state.losses[step0:]]
        wall = time.perf_counter() - t_window
    harness.refuse_compiles(ctx, compiles)
    steps = trainer.state.step - step0
    bad = sum(1 for x in window_losses if not math.isfinite(x))
    tokens_per_s = steps * batch * seq / wall
    peak = harness.memory_peak_bytes(ctx.devices)
    print("train " + json.dumps({
        "steps": steps, "wall_s": wall, "first_losses": first_losses,
        "last_loss": window_losses[-1] if window_losses else None}),
        flush=True)

    # free the program's state, then let the reference follow
    del trainer, probe
    pctx.destroy()
    gc.collect()
    check = w["check"]
    t_ref = time.perf_counter()
    make_w0 = jax.jit(lambda: weights.make(key, sizes, dtype))
    rows = [traffic.token_batch(vocab, ctx.seed, s, batch, seq)
            for s in range(first_steps)]
    ref = bloom_ref.adam_steps(
        make_w0, rows, sizes, lr, precision="float32",
        rows_per_call=check["reference_rows_per_call"], b1=ADAM_B1,
        store_dtype=dtype, place=_spread(ctx.devices))
    print(f"reference: {time.perf_counter() - t_ref:.1f} s", flush=True)
    ctx.reference = ref
    compare(ctx.checks, first_losses, got_grad, got_delta, ref, check)

    return harness.Result(
        end_to_end={"train_tokens_per_s": tokens_per_s},
        attempted=steps, failed=bad, t_window_start=t_window,
        memory_peak_bytes=peak,
        facts={"step_s": list(clock.seconds),
               "window_wall_s": wall, "steps": steps,
               "tokens_per_step": batch * seq, "seq": seq, "batch": batch,
               "rows_per_replica": batch // dp, "tensor": tp,
               "chips": tp * dp, "sizes": sizes, "peaks": ctx.peaks,
               "dtype": ctx.config["dtype"], "memory_peak_bytes": peak})


def setup_facts(ctx, t_run, t_imported, t_weights, t_built, step_ends,
                first_steps) -> dict:
    """Where a training cell's ``setup_s`` goes, by the host's clock:
    the library's import, the seeded weights (waited for), the
    ``Trainer``'s build, the first step to its hand-back (the compile or
    the cache's read, then its dispatch), and all the steps the
    reference follows with the fetch of their losses and norms."""
    now = time.perf_counter()
    return {"chip_to_driver_s": t_run - ctx.t_chip,
            "import_s": t_imported - t_run,
            "weights_s": t_weights - t_imported,
            "trainer_build_s": t_built - t_weights,
            "first_step_s": step_ends[0] - t_built if step_ends else None,
            "followed_steps_s": now - t_built,
            "steps_followed": first_steps,
            "lowerings_and_compiles": ctx.watch.count}


SPREAD_MIN = 1024     # leaves with no axis this long stay replicated


def _spread(devices):
    """Where the reference does not fit one chip it is spread over the
    cell's chips: each large leaf cut along its last axis that divides,
    the rest replicated; ``jit`` partitions the plain program itself."""
    import jax
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    n = len(devices)
    if n == 1:
        return None
    mesh = Mesh(np.asarray(devices), ("ref",))

    def put(x):
        axes = [i for i in range(x.ndim) if x.shape[i] % n == 0
                and x.shape[i] >= SPREAD_MIN]
        spec = [None] * x.ndim
        if axes:
            spec[axes[-1]] = "ref"
        return jax.device_put(x, NamedSharding(mesh, P(*spec)))

    return lambda tree: jax.tree_util.tree_map(put, tree)


def control(ctx):
    """The reference in the program's place, one precision below the
    configuration's: the same three steps with every matmul operand
    rounded to fp8, held to the same comparison. Needs ``run`` first."""
    import jax
    import jax.numpy as jnp

    w = ctx.workload
    sizes = ctx.config["sizes"]
    dtype = jnp.dtype(ctx.config["dtype"])
    key = weights.seed_key(ctx.seed)
    make_w0 = jax.jit(lambda: weights.make(key, sizes, dtype))
    rows = [traffic.token_batch(sizes["vocab_size"], ctx.seed, s,
                                w["global_batch"], w["seq"])
            for s in range(w["check"]["steps"])]
    low = bloom_ref.adam_steps(
        make_w0, rows, sizes, w["learning_rate"], precision="fp8",
        rows_per_call=w["check"]["reference_rows_per_call"], b1=ADAM_B1,
        store_dtype=dtype, place=_spread(ctx.devices))
    checks = harness.Checks()
    compare(checks, low["losses"],
            {k: v * (1.0 - ADAM_B1) for k, v in low["grad_norm"].items()},
            low["delta_norm"], ctx.reference, w["check"])
    return checks


def compare(checks, first_losses, got_grad, got_delta, ref, check):
    """The program's first three steps against the reference's."""
    loss_gap = max(abs(a - b) / abs(b)
                   for a, b in zip(first_losses, ref["losses"]))
    checks.add("loss_rel_gap_max", loss_gap, check["loss_rel_gap_max"],
               note=f"program {first_losses} reference {ref['losses']}")
    g, leaf = harness.worst_leaf_gap(
        {k: v / (1.0 - ADAM_B1) for k, v in got_grad.items()},
        ref["grad_norm"])
    checks.add("grad_norm_gap_worst_leaf", g, check["grad_norm_gap_max"],
               note=f"worst leaf {leaf}: program "
                    f"{got_grad[leaf] / (1.0 - ADAM_B1):.6g} reference "
                    f"{ref['grad_norm'][leaf]:.6g}")
    d, leaf = harness.worst_leaf_gap(got_delta, ref["delta_norm"])
    checks.add("param_change_gap_worst_leaf", d,
               check["param_change_gap_max"],
               note=f"worst leaf {leaf}: program {got_delta[leaf]:.6g} "
                    f"reference {ref['delta_norm'][leaf]:.6g}")
