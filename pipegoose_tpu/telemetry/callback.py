"""TelemetryCallback: per-step trainer metrics into the registry.

The trainer-side instrumentation lives in a callback (not the fit loop)
so the cost profile is opt-in: the loop itself only carries disabled-
registry spans. Adding this callback turns on:

- ``train.step_seconds`` histogram + ``train.tokens_per_s`` gauge per
  step (tokens from ``trainer.tokens_per_step``, same source the
  ``LossLoggerCallback`` uses);
- ``train.tokens_total`` / ``train.steps_total`` counters;
- ``train.mfu`` gauge — from an explicit ``flops_per_step`` or, with
  ``auto_cost=True``, a ONE-TIME lower+compile cost probe of the
  trainer's jitted step (``telemetry.derived.compiled_step_stats``: XLA
  flops + per-collective comm bytes). The probe compiles a second
  executable, so it is off by default — enable it for small models or
  pass ``flops_per_step`` measured offline for big ones. XLA reports
  the PER-DEVICE SPMD program's flops, and the peak table is per chip,
  so the resulting MFU is per-device;
- ``train.comm_bytes_per_step`` gauge from the same probe;
- ``train.hbm_utilization`` gauge every ``hbm_every`` steps (0 = off;
  CPU backends report no memory stats and the gauge stays unset);
- a ``"train.step"`` JSONL event every ``every`` steps.

**Timing semantics.** The trainer deliberately never blocks on the loss
(async dispatch); with ``fence=False`` (default) a step's measured wall
time is dispatch-to-dispatch, which in steady state equals device step
time (the dispatch queue backpressures) but mis-attributes the first
few steps. ``fence=True`` blocks on the loss every step — exact
per-step times, at the cost of draining the pipeline each step.
"""
from __future__ import annotations

import time
from typing import Any, Optional, Union

import jax

from pipegoose_tpu.telemetry import derived
from pipegoose_tpu.telemetry.exporters import (
    JSONLExporter,
    PrometheusTextfileExporter,
)
from pipegoose_tpu.telemetry.registry import MetricsRegistry, get_registry
from pipegoose_tpu.trainer.callback import Callback


class TelemetryCallback(Callback):
    order = 5  # after recovery (-10) / default (0) callbacks

    def __init__(
        self,
        registry: Optional[MetricsRegistry] = None,
        jsonl: Union[str, JSONLExporter, None] = None,
        prom: Union[str, PrometheusTextfileExporter, None] = None,
        every: int = 1,
        flops_per_step: Optional[float] = None,
        auto_cost: bool = False,
        hbm_every: int = 0,
        fence: bool = False,
        device_kind: Optional[str] = None,
    ):
        self.registry = registry
        self.every = max(int(every), 1)
        self.flops_per_step = flops_per_step
        self.auto_cost = auto_cost
        self.hbm_every = int(hbm_every)
        self.fence = fence
        self.device_kind = device_kind
        self._jsonl = jsonl
        self._prom = prom
        self._t0: Optional[float] = None
        self._peak: Optional[float] = None
        self._cost_probed = flops_per_step is not None
        self._comm_bytes: Optional[int] = None

    # -- lifecycle ---------------------------------------------------------

    def on_fit_start(self, trainer: Any) -> None:
        reg = self.registry or get_registry()
        self.registry = reg
        reg.enable()  # adding the callback IS the opt-in
        if isinstance(self._jsonl, str):
            self._jsonl = JSONLExporter(self._jsonl, registry=reg)
        elif self._jsonl is not None:
            reg.attach(self._jsonl)
        if isinstance(self._prom, str):
            self._prom = PrometheusTextfileExporter(self._prom)
        if self._peak is None:
            self._peak = derived.peak_flops_for(self.device_kind)
        reg.event("train.fit_start")

    def on_step_start(self, trainer: Any, step: int) -> None:
        self._t0 = time.perf_counter()

    def on_step_end(self, trainer: Any, step: int, loss: Any) -> None:
        if self._t0 is None:
            return
        if self.fence:
            jax.block_until_ready(loss)
        dt = time.perf_counter() - self._t0
        reg = self.registry
        reg.histogram("train.step_seconds").observe(dt)
        reg.counter("train.steps_total").inc()
        tokens = getattr(trainer, "tokens_per_step", 0)
        tps = derived.tokens_per_second(tokens, dt)
        if tokens:
            reg.counter("train.tokens_total").inc(tokens)
            reg.gauge("train.tokens_per_s").set(tps)
        if not self._cost_probed and self.auto_cost:
            self._probe_cost(trainer)
        step_mfu = None
        if self.flops_per_step:
            step_mfu = derived.mfu(self.flops_per_step, dt, peak=self._peak)
            reg.gauge("train.mfu").set(step_mfu)
        if self.hbm_every and step % self.hbm_every == 0:
            hbm = derived.hbm_utilization()
            if "utilization" in hbm:
                reg.gauge("train.hbm_utilization").set(hbm["utilization"])
            if "bytes_in_use" in hbm:
                reg.gauge("train.hbm_bytes_in_use").set(hbm["bytes_in_use"])
        if step % self.every == 0:
            ev = {"step": step, "dur_s": dt, "tokens_per_s": tps}
            if step_mfu is not None:
                ev["mfu"] = step_mfu
            reg.event("train.step", **ev)

    def on_fit_end(self, trainer: Any) -> None:
        reg = self.registry
        if reg is None:
            return
        reg.event("train.fit_end")
        if isinstance(self._jsonl, JSONLExporter):
            self._jsonl.export_snapshot(reg)
        if isinstance(self._prom, PrometheusTextfileExporter):
            self._prom.write(reg)

    # -- cost probe --------------------------------------------------------

    def _probe_cost(self, trainer: Any) -> None:
        """One lower+compile of the trainer's jitted step at the live
        arg shapes -> flops + comm bytes. Failure (exotic step fn, no
        batch seen yet) downgrades to 'no MFU gauge', never breaks the
        fit loop."""
        self._cost_probed = True  # one attempt, success or not
        batch = getattr(trainer, "last_batch", None)
        step_fn = getattr(trainer, "_step_fn", None)
        if batch is None or step_fn is None:
            return
        try:
            args = (trainer.params, trainer.opt_state, batch)
            if getattr(trainer, "with_rng", False):
                args = args + (jax.random.PRNGKey(0),)
            stats = derived.compiled_step_stats(step_fn, *args)
        except Exception:  # noqa: BLE001
            return
        if stats["flops"]:
            self.flops_per_step = stats["flops"]
            self.registry.gauge("train.flops_per_step").set(stats["flops"])
        self._comm_bytes = stats["comm_bytes"]
        self.registry.gauge("train.comm_bytes_per_step").set(
            stats["comm_bytes"]
        )
        self.registry.event("train.cost_probe", **{
            "flops": stats["flops"],
            "bytes_accessed": stats["bytes_accessed"],
            "comm_bytes": stats["comm_bytes"],
            "comm_by_op": stats["comm_by_op"],
        })


class AuxRecorder(Callback):
    """Keeps what the step's counter channel returned (``Trainer(
    has_aux=True)``: ``state.last_aux``), one device pytree a step, and
    fetches a step's counters only ``LAG`` steps later, when its device
    work has long retired: a fetch of the newest would stall the
    dispatch queue. ``take()`` hands over every step recorded so far as
    host values (and blocks for the newest).

    With the registry enabled, each fetched step also lands there by
    name: ``names`` maps a counter's key to ``("gauge" | "histogram",
    metric name)`` (e.g. ``glm4_moe_lite.COUNTER_METRICS``); a histogram
    observes every element of an array (rows per expert), a gauge takes
    a scalar."""

    order = 6
    LAG = 2        # steps behind the newest that a fetch stays
    KEEP = 4096    # fetched steps kept until ``take()``

    def __init__(self, registry: Optional[MetricsRegistry] = None,
                 names: Optional[dict] = None):
        self.registry = registry if registry is not None else get_registry()
        self.names = dict(names or {})
        self._pending: list = []    # device pytrees, oldest first
        self._host: list = []       # fetched, oldest first

    def on_step_end(self, trainer: Any, step: int, loss: Any) -> None:
        aux = trainer.state.last_aux
        if aux is None:
            return
        self._pending.append(aux)
        while len(self._pending) > self.LAG:
            self._fetch_oldest()

    def _fetch_oldest(self) -> None:
        import numpy as np

        host = jax.tree_util.tree_map(np.asarray, self._pending.pop(0))
        self._host.append(host)
        del self._host[:-self.KEEP]
        if not self.registry.enabled or not isinstance(host, dict):
            return
        for key, (kind, name) in self.names.items():
            if key not in host:
                continue
            if kind == "histogram":
                # counts, not seconds: powers of two up to 2**20
                h = self.registry.histogram(
                    name, buckets=[float(2 ** i) for i in range(21)])
                for v in np.ravel(host[key]):
                    h.observe(float(v))
            else:
                self.registry.gauge(name).set(float(host[key]))

    def take(self) -> list:
        """Every recorded step's counters as host values, oldest first;
        the recorder starts empty again."""
        while self._pending:
            self._fetch_oldest()
        out, self._host = self._host, []
        return out
