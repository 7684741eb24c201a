"""Trainer callbacks.

Real implementation of the reference's no-op ``Callback``
(pipegoose/trainer/callback.py:4-14). Hooks mirror and extend its
on_fit_start/on_fit_end surface with per-step and checkpoint events.
"""
from __future__ import annotations

from typing import Any, Optional


def _host_scalar(x: Any) -> float:
    """Scalar (possibly multi-host sharded) -> host float.

    ``float()``/``bool()`` raise on non-fully-addressable arrays, which
    a multihost trainer produces (tests/distributed/test_multihost.py);
    fall back to a replicated all-gather in that case (advisor r4).
    """
    try:
        return float(x)
    except RuntimeError:
        from jax.experimental import multihost_utils

        import numpy as np

        return float(np.asarray(multihost_utils.process_allgather(x)).reshape(-1)[0])


def _all_finite(tree: Any) -> Any:
    """Is every floating-point leaf of ``tree`` finite? (Traced.)"""
    import functools

    import jax
    import jax.numpy as jnp

    return functools.reduce(
        jnp.logical_and,
        [
            jnp.isfinite(leaf).all()
            for leaf in jax.tree_util.tree_leaves(tree)
            if jnp.issubdtype(leaf.dtype, jnp.floating)
        ],
        jnp.asarray(True),
    )


class Callback:
    order: int = 0

    def on_fit_start(self, trainer: Any) -> None: ...

    def on_fit_end(self, trainer: Any) -> None: ...

    # teardown on the FAILURE path: on_fit_end only runs when fit
    # finishes, so process-global state a callback armed (e.g. the
    # chaos checkpoint-fault seam) needs a hook that fires when fit
    # raises. Called best-effort; exceptions here never mask the
    # original one.
    def on_fit_abort(self, trainer: Any, exc: BaseException) -> None: ...

    def on_step_start(self, trainer: Any, step: int) -> None: ...

    def on_step_end(self, trainer: Any, step: int, loss: float) -> None: ...

    def on_checkpoint(self, trainer: Any, step: int, path: str) -> None: ...


class LossLoggerCallback(Callback):
    """Periodic loss/throughput logging via the trainer's logger."""

    def __init__(self, every: int = 10):
        self.every = every
        self._t0: Optional[float] = None
        self._tokens = 0

    def on_step_end(self, trainer: Any, step: int, loss: float) -> None:
        import time

        self._tokens += trainer.tokens_per_step
        if self._t0 is None:
            self._t0 = time.perf_counter()
            self._tokens = 0
            return
        if step % self.every == 0:
            dt = time.perf_counter() - self._t0
            tps = self._tokens / dt if dt > 0 else float("nan")
            trainer.logger.info(
                f"step {step} loss {_host_scalar(loss):.4f} tokens/s {tps:,.0f}"
            )
            self._t0 = time.perf_counter()
            self._tokens = 0


class CheckpointCallback(Callback):
    """Periodic sharded checkpointing of the full train state."""

    def __init__(self, directory: str, every: int = 1000, save_final: bool = True):
        self.directory = directory
        self.every = every
        self.save_final = save_final
        self._last_saved = -1

    def _save(self, trainer: Any, step: int) -> None:
        import math

        import jax

        from pipegoose_tpu.utils.checkpoint import (
            available_steps,
            save_train_state,
        )

        # a COMPLETE checkpoint for this step already on disk means the
        # state came FROM it (recovery rolled back and restored it —
        # the only path that revisits a step number): re-saving would
        # hit save_pretrained's exists-check and kill the run. Quick
        # dir listing, only on steps that passed the `every` gate.
        if step in available_steps(self.directory):
            self._last_saved = max(self._last_saved, step)
            return

        # persisting non-finite params would poison every later restore
        # (AutoRecovery would loop restoring the poisoned checkpoint
        # until max_restores). Two guards:
        # 1. the last recorded loss — catches divergence that happened on
        #    an earlier step (e.g. slipped past a FailureDetector with
        #    check_every > 1) at zero extra device work;
        if trainer.state.last_loss is not None:
            last_loss = _host_scalar(trainer.state.last_loss)
            if not math.isfinite(last_loss):
                trainer.logger.warning(
                    f"step {step}: refusing to checkpoint non-finite state "
                    f"(loss {last_loss})"
                )
                return
        # 2. the params AND optimizer state — the loss canary is computed
        #    from PRE-update params, so a step whose optimizer update
        #    itself overflowed (finite loss, NaN update) would slip past
        #    it; and opt_state (e.g. overflowed Adam moments under still-
        #    finite params) is restored too, so a poisoned moment would
        #    re-poison training on resume (advisor r4). One fused
        #    reduction per checkpoint; negligible next to the write.
        #    ONE program, not one per leaf: each is a launch with
        #    collectives on every device, and dozens in flight at once
        #    starve XLA:CPU's thread pool of the threads a rendezvous
        #    needs (8 virtual devices on 8 busy cores: a 40 s
        #    rendezvous timeout that aborts the process).
        finite = jax.jit(_all_finite)((trainer.params, trainer.opt_state))
        if not _host_scalar(finite):
            trainer.logger.warning(
                f"step {step}: refusing to checkpoint non-finite params/opt_state"
            )
            return
        path = save_train_state(self.directory, step, trainer.params, trainer.opt_state)
        self._last_saved = step
        trainer.logger.info(f"checkpointed step {step} -> {path}")
        for cb in trainer.callbacks:
            cb.on_checkpoint(trainer, step, path)

    def on_step_end(self, trainer: Any, step: int, loss: float) -> None:
        # trust the TRAINER's step, not the argument: AutoRecovery (which
        # runs earlier in this callback round, order=-10) may have rolled
        # state.step back — saving the restored old state under the
        # failing step's label would poison later restores, and saving
        # the already-on-disk step again would collide
        step = trainer.state.step
        if step > 0 and step % self.every == 0 and step > self._last_saved:
            self._save(trainer, step)

    def on_fit_end(self, trainer: Any) -> None:
        # short runs would otherwise end with NO checkpoint despite the
        # user configuring a checkpoint directory
        from pipegoose_tpu.utils.checkpoint import latest_step

        existing = latest_step(self.directory)
        already = max(self._last_saved, existing if existing is not None else -1)
        if self.save_final and trainer.state.step > already:
            self._save(trainer, trainer.state.step)
