"""Trainer lifecycle state.

Analog of the reference's ``TrainerStatus``/``TrainerState`` enums
(pipegoose/trainer/state.py:4-19), extended with the actual mutable
run-state (step, last loss, loss history) the reference never filled in.
"""
from __future__ import annotations

import dataclasses
import enum
from typing import Any, Optional


class TrainerStatus(str, enum.Enum):
    INITIALIZING = "initializing"
    RUNNING = "running"
    FINISHED = "finished"
    INTERRUPTED = "interrupted"
    FAILED = "failed"


class LossHistory(list):
    """Bounded per-step loss record.

    ``fit`` appends the step's loss as a DEVICE array (fetching it
    would stall JAX's async dispatch every step), so an unbounded list
    pins one live device buffer per step for the whole run. This list
    subclass keeps the plain-list API the consumers rely on
    (``losses[-1]``, ``del losses[k:]`` in AutoRecovery's rollback,
    iteration in plots/early-stopping) while:

    - keeping at most ``maxlen`` entries (ring semantics: oldest
      dropped on append), and
    - opportunistically converting the entry ``sync_lag`` steps behind
      the head to a host float on each append — by then that step's
      device work has long retired, so the ``float()`` doesn't block,
      and the ring holds device handles only for the most recent
      ``sync_lag`` steps.
    """

    def __init__(self, iterable=(), maxlen: int = 4096, sync_lag: int = 16):
        super().__init__(iterable)
        if maxlen < 1:
            raise ValueError(f"maxlen must be >= 1, got {maxlen}")
        self.maxlen = maxlen
        self.sync_lag = max(int(sync_lag), 0)

    def append(self, value) -> None:
        super().append(value)
        if len(self) > self.maxlen:
            del self[: len(self) - self.maxlen]
        i = len(self) - 1 - self.sync_lag
        if i >= 0 and not isinstance(self[i], float):
            try:
                self[i] = float(self[i])
            except (TypeError, RuntimeError):
                # non-fully-addressable multihost scalar (float() raises)
                # or a non-numeric entry: keep the original object
                pass


@dataclasses.dataclass
class TrainerState:
    status: TrainerStatus = TrainerStatus.INITIALIZING
    step: int = 0
    last_loss: Optional[float] = None
    losses: LossHistory = dataclasses.field(default_factory=LossHistory)
    # most recent in-graph health pytree (device scalars) when the
    # trainer runs with with_health=True; None otherwise
    last_health: Optional[Any] = None
    # most recent counters pytree (device arrays) the loss_fn returned
    # beside the loss when the trainer runs with has_aux=True
    last_aux: Optional[Any] = None
