"""Trainer: the end-user training loop.

Real implementation of the reference's empty ``Trainer`` stub
(pipegoose/trainer/trainer.py:13-35). One object wires together the
hybrid-parallel compiled train step (parallel/hybrid.py), the ZeRO-1
optimizer, callbacks, logging, and checkpoint/resume — the composition
the reference's examples hand-roll (examples/hybrid_parallelism.py).
"""
from __future__ import annotations

from typing import Any, Callable, Iterable, Optional, Sequence

import jax
from jax.sharding import PartitionSpec as P

from pipegoose_tpu.distributed.parallel_context import ParallelContext
from pipegoose_tpu.optim.zero import DistributedOptimizer
from pipegoose_tpu.telemetry.spans import span
from pipegoose_tpu.trainer.callback import Callback
from pipegoose_tpu.trainer.logger import DistributedLogger
from pipegoose_tpu.trainer.state import TrainerState, TrainerStatus


class Trainer:
    def __init__(
        self,
        loss_fn: Callable[..., jax.Array],
        params: Any,
        param_specs: Any,
        optimizer: DistributedOptimizer,
        parallel_context: Optional[ParallelContext] = None,
        batch_spec: P = P("data"),
        loss_axis: Any = "data",
        grad_sync_axes: tuple = (),
        with_rng: bool = False,
        n_accum: int = 1,
        with_health: bool = False,
        has_aux: bool = False,
        frozen: Any = None,
        callbacks: Sequence[Callback] = (),
        logger: Optional[DistributedLogger] = None,
        resume_dir: Optional[str] = None,
    ):
        self.parallel_context = parallel_context or ParallelContext.get_context()
        self.logger = logger or DistributedLogger()
        self.callbacks = sorted(callbacks, key=lambda c: c.order)
        self.state = TrainerState()
        self.with_rng = with_rng
        # with_health: the compiled step also returns the in-graph
        # health pytree (telemetry/health.py), kept on-device in
        # state.last_health for callbacks (FlightRecorder) to consume
        self.with_health = with_health
        # has_aux: loss_fn returns (loss, counters) and the compiled
        # step hands the counters back (parallel/hybrid.py), kept
        # on-device in state.last_aux for callbacks (AuxRecorder)
        self.has_aux = has_aux
        # frozen: pytree of bools, True = a leaf with no gradient and no
        # optimizer state (e.g. glm4_moe_lite.frozen_leaves)
        self.frozen = frozen
        self.tokens_per_step = 0  # updated from batch shapes each step
        # TelemetryCallback's cost-probe input: valid only DURING the
        # step-end callback round, cleared right after so the trainer
        # never pins a batch past its step
        self.last_batch: Any = None
        # refreshed by profile() — the ops server's /debug/profile
        # provider (lambda: trainer.last_step_profile)
        self.last_step_profile: Any = None

        from pipegoose_tpu.parallel.hybrid import (
            build_hybrid_train_step,
            hybrid_build_config,
        )

        # the step-rebuild hook (parallel/hybrid.py): everything the
        # compiled step was built from, minus the context — an elastic
        # mesh change (trainer/elastic.py) re-lowers the SAME config on
        # the surviving-device context via rebuild()
        self._hybrid_config = hybrid_build_config(
            loss_fn,
            param_specs,
            optimizer,
            batch_spec=batch_spec,
            loss_axis=loss_axis,
            grad_sync_axes=grad_sync_axes,
            with_rng=with_rng,
            n_accum=n_accum,
            with_health=with_health,
            has_aux=has_aux,
            frozen=frozen,
        )
        init_fn, make_step = build_hybrid_train_step(
            self._hybrid_config, self.parallel_context
        )
        self._init_fn = init_fn
        self.param_specs = param_specs
        self.optimizer = optimizer
        # place params on the mesh in FRESH buffers: the jitted step
        # donates its params argument, and donating the caller's arrays
        # would invalidate them (device_put can alias, a jitted identity
        # can't)
        from jax.sharding import NamedSharding

        out_shardings = jax.tree_util.tree_map(
            lambda s: NamedSharding(self.parallel_context.mesh, s),
            param_specs,
            is_leaf=lambda x: isinstance(x, P),
        )
        self.params = jax.jit(lambda t: t, out_shardings=out_shardings)(params)
        self._step_fn = make_step(params)

        # lazily-built jitted eval step (loss only, no grads/update);
        # evaluate() must run the SAME accumulated loss as training —
        # n_accum exists because the un-microbatched forward doesn't fit
        if n_accum > 1:
            from pipegoose_tpu.core.accumulation import make_accumulating_loss

            self._loss_fn = make_accumulating_loss(loss_fn, n_accum)
        else:
            self._loss_fn = loss_fn
        self._batch_spec = batch_spec
        self._loss_axis = loss_axis
        self._eval_fn = None

        resumed = False
        if resume_dir is not None:
            # shapes only — materializing a full ZeRO state just to
            # overwrite it from the checkpoint would waste a compile +
            # the whole optimizer memory
            state_shapes = jax.eval_shape(init_fn, params)
            resumed = self._try_resume(resume_dir, state_shapes)
        if not resumed:
            self.opt_state = init_fn(params)

    def _try_resume(self, directory: str, opt_state_shapes) -> bool:
        from pipegoose_tpu.utils.checkpoint import latest_step

        step = latest_step(directory)
        if step is None:
            self.logger.info(f"no checkpoint under {directory}; starting fresh")
            return False
        self._restore(directory, step, opt_state_shapes)
        self.logger.info(f"resumed from {directory} at step {step}")
        return True

    def restore_from(self, directory: str, step: Optional[int] = None) -> int:
        """Restore params + optimizer state from a checkpoint into the
        LIVE trainer (used by ``AutoRecovery`` to roll back a diverged
        run mid-fit; also usable interactively). Rewinds
        ``state.step``; returns the restored step. Raises
        ``FileNotFoundError`` when the directory holds no checkpoint."""
        from pipegoose_tpu.utils.checkpoint import latest_step

        if step is None:
            step = latest_step(directory)
        if step is None:
            raise FileNotFoundError(f"no checkpoint under {directory!r}")
        # shapes from the CURRENT init_fn, not the live opt_state: after
        # an elastic rebuild() the live state still has the OLD mesh's
        # ZeRO padding (global dim0 = ceil(d/dp)*dp depends on dp), and
        # the restore must target what the rebuilt step expects
        self._restore(directory, step, jax.eval_shape(self._init_fn, self.params))
        return step

    def _restore(self, directory: str, step: int, opt_state_like) -> None:
        from pipegoose_tpu.parallel.hybrid import trainable, zero_state_spec
        from pipegoose_tpu.utils.checkpoint import restore_train_state

        like = {"params": self.params, "opt_state": opt_state_like}
        # restore SHARDED onto this mesh — without specs every leaf (incl.
        # the ZeRO state, which exists precisely because it can't live
        # replicated) would materialize on all devices
        specs = {
            "params": self.param_specs,
            "opt_state": zero_state_spec(
                self.optimizer, trainable(self.params, self.frozen),
                trainable(self.param_specs, self.frozen),
                self.parallel_context.mesh,
            ),
        }
        restored = restore_train_state(
            directory, step, like, specs, self.parallel_context
        )
        self.params = restored["params"]
        self.opt_state = restored["opt_state"]
        self.state.step = step

    def rebuild(self, parallel_context: ParallelContext) -> None:
        """Recompile the hybrid train step on a NEW ``ParallelContext``
        — the elastic-recovery entry point (``trainer/elastic.py``):
        after a device loss shrinks the cluster, the same stored build
        config (``parallel/hybrid.py`` ``hybrid_build_config``) is
        re-lowered on the surviving-device mesh. Params and optimizer
        state are NOT migrated here (they still live on the old mesh's
        buffers); follow with :meth:`restore_from`, whose cross-mesh
        orbax restore places the checkpointed state sharded onto the
        new mesh."""
        from pipegoose_tpu.parallel.hybrid import build_hybrid_train_step

        self.parallel_context = parallel_context
        init_fn, make_step = build_hybrid_train_step(
            self._hybrid_config, parallel_context
        )
        self._init_fn = init_fn
        # current params serve as a shape/dtype source only: make_step
        # reads them through eval_shape (state specs) and size
        # arithmetic (comm gauges) — planner precedent, bloom_builder
        # passes pure SDS trees through the same path
        self._step_fn = make_step(self.params)
        self._eval_fn = None  # compiled for the OLD mesh; rebuild lazily

    def evaluate(
        self,
        batches: Iterable[Any],
        rng: Optional[jax.Array] = None,
        weight_fn: Optional[Any] = None,
    ) -> float:
        """Mean loss over ``batches`` with the CURRENT params — no
        gradients, no optimizer update (the eval half the reference's
        Trainer stub never got, trainer.py:13-35). Runs the same
        sharded loss_fn as training, jitted once.

        ``weight_fn(batch) -> float`` weights each batch's (internally
        normalized) loss in the running mean. For ragged eval sets pass
        the batch's valid-token count — e.g.
        ``lambda b: float(b["attention_mask"][:, 1:].sum())`` — and the
        result is the corpus TOKEN-weighted mean, the number eval
        reports should quote. Default: equal batch weights (exact when
        every batch carries the same token count)."""
        if self._eval_fn is None:
            from pipegoose_tpu.parallel.hybrid import shard_map

            in_specs = (self.param_specs, self._batch_spec) + (
                (P(),) if self.with_rng else ()
            )

            def eval_step(params, batch, *rng):
                loss = self._loss_fn(params, batch, *rng)
                if self.has_aux:
                    loss = loss[0]
                axes = (
                    self._loss_axis
                    if isinstance(self._loss_axis, tuple)
                    else (self._loss_axis,)
                )
                for ax in axes:
                    loss = jax.lax.pmean(loss, ax)
                return loss

            self._eval_fn = jax.jit(
                shard_map(
                    eval_step,
                    mesh=self.parallel_context.mesh,
                    in_specs=in_specs,
                    out_specs=P(),
                    check_vma=False,
                )
            )
        if rng is None:
            rng = jax.random.PRNGKey(0)
        total, n = 0.0, 0.0
        for i, batch in enumerate(batches):
            args = (self.params, batch)
            if self.with_rng:
                args = args + (jax.random.fold_in(rng, i),)
            w = float(weight_fn(batch)) if weight_fn is not None else 1.0
            total += w * float(self._eval_fn(*args))
            n += w
        if n == 0:
            raise ValueError(
                "evaluate() received no batches (an exhausted generator?) or "
                "all batch weights were zero — 0.0 would be "
                "indistinguishable from perfect convergence"
            )
        return total / n

    def doctor(
        self,
        batch: Any,
        large_bytes: int = 1 << 20,
        registry: Any = None,
    ):
        """Mesh-doctor report (telemetry/doctor.py) for THIS trainer's
        compiled train step: actual vs intended shardings of every
        param/optimizer-state/batch leaf, the collective schedule split
        into intentional vs partitioner-inserted traffic, and the
        per-device HBM budget. ``batch`` only provides shapes — a
        ``jax.ShapeDtypeStruct`` pytree works; nothing executes.
        Headline numbers land as ``doctor.*`` gauges on ``registry``
        (default: the global one, only if enabled)."""
        from pipegoose_tpu.parallel.hybrid import train_step_intended_specs
        from pipegoose_tpu.telemetry.doctor import diagnose, set_doctor_gauges

        args = (self.params, self.opt_state, batch)
        labels = ["params", "opt_state", "batch"]
        intended = train_step_intended_specs(
            self.optimizer, self.params, self.param_specs,
            self.parallel_context.mesh, batch_spec=self._batch_spec,
            with_rng=self.with_rng, frozen=self.frozen,
        )
        if self.with_rng:
            args = args + (jax.random.PRNGKey(0),)
            labels.append("rng")
        report = diagnose(
            self._step_fn, *args,
            intended=intended, labels=labels,
            mesh=self.parallel_context.mesh, large_bytes=large_bytes,
        )
        set_doctor_gauges(report, registry=registry)
        return report

    def profile(
        self,
        batch: Any,
        steps: int = 3,
        warmup: int = 2,
        trace_dir: Optional[str] = None,
        registry: Any = None,
    ):
        """Measured device-time attribution (telemetry/xprof.py) of
        THIS trainer's compiled train step — the runtime twin of
        :meth:`doctor`: runs the real step ``warmup + steps`` times
        under the XLA profiler on ``batch`` (REAL arrays — unlike the
        doctor, the step executes) and returns the
        :class:`~pipegoose_tpu.telemetry.xprof.StepProfile` splitting
        each fenced step into compute / per-mesh-axis collectives /
        idle, with measured MFU.

        The profiled steps are REAL optimizer steps: params and
        optimizer state advance (the step donates its buffers, so the
        trainer adopts the final ones), exactly as ``fit`` over the
        same batches would — ``state.step`` is not bumped, since no
        callbacks ran. The result is cached on ``last_step_profile``
        (the ops server's ``/debug/profile`` provider)."""
        from pipegoose_tpu.telemetry.xprof import profile_step

        args: tuple = (self.params, self.opt_state, batch)
        if self.with_rng:
            args = args + (jax.random.PRNGKey(0),)
        final: dict = {}

        def update(out, cur):
            # out = (params, opt_state, loss[, health][, aux]); batch and rng
            # (when present) repeat — profiling measures the step, not
            # the data pipeline
            final["params"], final["opt_state"] = out[0], out[1]
            return (out[0], out[1]) + tuple(cur[2:])

        try:
            profile = profile_step(
                self._step_fn, *args, steps=steps, warmup=warmup,
                update_args=update, mesh=self.parallel_context.mesh,
                trace_dir=trace_dir, registry=registry,
            )
        finally:
            # the compiled step DONATED the params/opt-state buffers on
            # every call: adopt the final generation — even when trace
            # parsing raises mid-profile — or the trainer's next step
            # would touch deleted arrays
            if final:
                self.params = final["params"]
                self.opt_state = final["opt_state"]
        self.last_step_profile = profile
        return profile

    def fit(
        self,
        batches: Iterable[Any],
        max_steps: Optional[int] = None,
        rng: Optional[jax.Array] = None,
        profiler_trace_dir: Optional[str] = None,
    ) -> TrainerState:
        """Run the training loop (reference Trainer.fit stub,
        trainer.py:18-30). ``batches`` yields pytrees matching the
        batch_spec; with ``with_rng`` a fresh folded key goes to every
        step. ``profiler_trace_dir``: wrap the whole fit in
        ``jax.profiler.trace(dir)`` so an XLA timeline
        (TensorBoard/Perfetto viewable) is one flag away."""
        if profiler_trace_dir is not None:
            from pipegoose_tpu.utils.profiler import trace

            with trace(profiler_trace_dir):
                return self._fit(batches, max_steps, rng)
        return self._fit(batches, max_steps, rng)

    def _fire_fit_abort(self, exc: BaseException) -> None:
        """Teardown hooks for the failure path — a callback holding
        process-global state (the chaos checkpoint-fault seam) must get
        a chance to release it when fit raises. Best-effort and
        getattr-guarded: duck-typed callbacks predating the hook keep
        working, and a teardown error never masks the original."""
        for cb in self.callbacks:
            hook = getattr(cb, "on_fit_abort", None)
            if hook is None:
                continue
            try:
                hook(self, exc)
            except Exception as cleanup_err:  # noqa: BLE001
                self.logger.warning(
                    f"on_fit_abort of {type(cb).__name__} raised "
                    f"{type(cleanup_err).__name__}: {cleanup_err} "
                    "(suppressed; original error propagates)"
                )

    def _fit(
        self,
        batches: Iterable[Any],
        max_steps: Optional[int] = None,
        rng: Optional[jax.Array] = None,
    ) -> TrainerState:
        self.state.status = TrainerStatus.RUNNING
        for cb in self.callbacks:
            cb.on_fit_start(self)
        if rng is None:
            rng = jax.random.PRNGKey(0)
        it = iter(batches)
        try:
            while True:
                # check BEFORE pulling: a pull consumes the caller's
                # iterator (and may tokenize a whole batch) for nothing
                if max_steps is not None and self.state.step >= max_steps:
                    break
                try:
                    # train.data / train.step: profiler annotations always
                    # (inert without a session); with the registry enabled
                    # they also split host-side data time from step
                    # dispatch in the JSONL stream (telemetry/spans.py)
                    with span("train.data"):
                        batch = next(it)
                except StopIteration:
                    break
                step = self.state.step
                for cb in self.callbacks:
                    cb.on_step_start(self, step)
                leaves = jax.tree_util.tree_leaves(batch)
                self.tokens_per_step = int(leaves[0].size) if leaves else 0
                self.last_batch = batch
                args = (self.params, self.opt_state, batch)
                if self.with_rng:
                    args = args + (jax.random.fold_in(rng, step),)
                # UNFENCED: measures dispatch; in steady state the queue
                # backpressures to device step time. TelemetryCallback
                # (fence=True) gives exact per-step device attribution.
                with span("train.step"):
                    self.params, self.opt_state, loss, *extra = (
                        self._step_fn(*args)
                    )
                    # device pytrees, same async-dispatch rule as the
                    # loss: consumers fetch when they actually look
                    if self.with_health:
                        self.state.last_health = extra[0]
                    if self.has_aux:
                        self.state.last_aux = extra[-1]
                # keep loss as a device array: float() here would block the
                # host every step and kill JAX's async dispatch; callbacks
                # convert only when they actually log
                self.state.step = step + 1
                self.state.last_loss = loss
                self.state.losses.append(loss)
                for cb in self.callbacks:
                    cb.on_step_end(self, self.state.step, loss)
                self.last_batch = None  # don't pin the batch past its step
        except KeyboardInterrupt as e:
            self.state.status = TrainerStatus.INTERRUPTED
            self.logger.warning("interrupted")
            self._fire_fit_abort(e)
            raise
        except Exception as e:
            # a divergence abort (TrainingDiverged from a callback) or any
            # other mid-fit error must not leave state.status at RUNNING —
            # callers inspect trainer.state after fit() raises
            self.state.status = TrainerStatus.FAILED
            self._fire_fit_abort(e)
            raise
        finally:
            # the per-iteration clear misses aborted steps (an OOM raise
            # or interrupt between assignment and clear would pin the
            # batch for the trainer's lifetime)
            self.last_batch = None
        self.state.status = TrainerStatus.FINISHED
        for cb in self.callbacks:
            cb.on_fit_end(self)
        return self.state
