"""Perf-regression sentinel: rolling-baseline watch over measured runs.

The flight recorder (telemetry/flightrec.py) catches CORRECTNESS
anomalies — NaNs, loss spikes, decode stalls. Nothing watches for the
quieter failure: the run still converges, the tokens still stream, but
a component got slower — a PartitionSpec regression re-routed a
collective, a new allocation pattern doubled dispatch time, a noisy
neighbor stole the fabric. The sentinel is the measured-performance
twin of the doctor's compile-time guards:

- :meth:`PerfSentinel.observe` takes one run's measurement — a
  ``telemetry.xprof.StepProfile``, or any flat component dict
  (``tokens_per_s`` plus ``*_s`` time components, e.g. the serving
  engine's per-run decode-step/idle split) — and compares each
  component against the rolling median of the last ``window`` healthy
  runs;
- a component at ``>= ratio_threshold`` x its baseline (or tokens/s at
  ``<= drop_threshold`` x) fires ONE ``perf_regression`` black box
  through the attached ``FlightRecorder`` whose reason NAMES the
  regressed component — "tensor-axis collective time 2.1x baseline" —
  with every component's ratio in the details;
- regressed runs do NOT enter the baseline (the flightrec convention:
  a spike must not poison the median it is judged against);
- every observation exports the ``perf.{compute,comm,idle}_fraction``
  gauges (when the run carries a profile) and ``perf.tokens_per_s``.

Everything is opt-in and host-side: nothing observes unless a caller
(``ServingEngine(sentinel=...)``) passes a sentinel, and the disabled
cost is one attribute read + branch (guard-tested < 5 µs, the
established contract).
"""
from __future__ import annotations

from collections import deque
from typing import Any, Dict, List, Optional

# component key -> human label for the trigger reason
_LABELS = {
    "tokens_per_s": "tokens/s",
    "compute_s": "compute time",
    "idle_s": "idle time",
    "decode_step_s": "decode-step time",
    "wall_step_s": "step wall time",
}


def _label(key: str) -> str:
    if key in _LABELS:
        return _LABELS[key]
    if key.startswith("comm[") and key.endswith("]_s"):
        return f"{key[5:-3]}-axis collective time"
    return key


def _components_of(run: Any) -> Dict[str, float]:
    """Flatten one observation into comparable components: a
    ``StepProfile`` contributes its attribution components + derived
    tokens/s only if the caller added one; a dict passes through
    (``profile`` sub-dicts flattened the same way)."""
    if hasattr(run, "components"):  # StepProfile
        return dict(run.components())
    out: Dict[str, float] = {}
    for k, v in dict(run).items():
        if k == "profile" and isinstance(v, dict):
            out["compute_s"] = float(v.get("compute_s", 0.0))
            out["idle_s"] = float(v.get("idle_s", 0.0))
            for axes, t in (v.get("comm_by_axes") or {}).items():
                out[f"comm[{axes}]_s"] = float(t)
            continue
        if isinstance(v, (int, float)) and (k.endswith("_s")
                                            or k == "tokens_per_s"):
            out[k] = float(v)
    return out


class PerfSentinel:
    """Rolling-baseline perf-regression watch (module docstring).

    ``recorder``: optional ``FlightRecorder`` — regressions dump a
    ``perf_regression`` black box through it (without one they are
    still returned + counted). ``window``: healthy runs the rolling
    median spans. ``min_baseline``: observations required before any
    verdict (a 1-run "baseline" would page on startup noise).
    ``ratio_threshold``: a time component this many times its baseline
    median regresses. ``drop_threshold``: tokens/s at or below this
    fraction of its baseline regresses.
    """

    def __init__(
        self,
        recorder: Any = None,
        registry: Any = None,
        window: int = 8,
        min_baseline: int = 2,
        ratio_threshold: float = 1.5,
        drop_threshold: float = 0.7,
    ):
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
        if min_baseline < 1:
            raise ValueError(f"min_baseline must be >= 1, got {min_baseline}")
        if ratio_threshold <= 1.0:
            raise ValueError(
                f"ratio_threshold must be > 1, got {ratio_threshold}")
        if not 0.0 < drop_threshold < 1.0:
            raise ValueError(
                f"drop_threshold must be in (0, 1), got {drop_threshold}")
        self.recorder = recorder
        self.registry = registry
        self.window = window
        self.min_baseline = min_baseline
        self.ratio_threshold = ratio_threshold
        self.drop_threshold = drop_threshold
        self._hist: deque = deque(maxlen=window)
        self.regressions = 0
        self.last_verdict: Optional[Dict[str, Any]] = None

    @property
    def baseline_size(self) -> int:
        return len(self._hist)

    def baseline(self) -> Dict[str, float]:
        """{component -> rolling median} over the healthy window."""
        import statistics

        keys = set()
        for comps in self._hist:
            keys.update(comps)
        out = {}
        for k in keys:
            vals = [c[k] for c in self._hist if k in c]
            if vals:
                out[k] = statistics.median(vals)
        return out

    def _gauges(self, run: Any, comps: Dict[str, float]) -> None:
        from pipegoose_tpu.telemetry.registry import get_registry

        reg = self.registry if self.registry is not None else get_registry()
        if not reg.enabled:
            return
        prof = run if hasattr(run, "compute_fraction") else None
        if prof is None and isinstance(run, dict) \
                and isinstance(run.get("profile"), dict):
            p = run["profile"]
            wall = float(p.get("wall_step_s") or 0.0)
            if wall > 0:
                reg.gauge("perf.compute_fraction").set(
                    float(p.get("compute_s", 0.0)) / wall)
                reg.gauge("perf.comm_fraction").set(
                    float(p.get("comm_s", 0.0)) / wall)
                reg.gauge("perf.idle_fraction").set(
                    float(p.get("idle_s", 0.0)) / wall)
        elif prof is not None:
            reg.gauge("perf.compute_fraction").set(prof.compute_fraction)
            reg.gauge("perf.comm_fraction").set(prof.comm_fraction)
            reg.gauge("perf.idle_fraction").set(prof.idle_fraction)
        if "tokens_per_s" in comps:
            reg.gauge(
                "perf.tokens_per_s",
                help="last observed run throughput (perf sentinel)",
            ).set(comps["tokens_per_s"])
        reg.gauge(
            "perf.regressions_total",
            help="perf_regression verdicts fired by the sentinel",
        ).set(float(self.regressions))

    def observe(
        self,
        run: Any,
        step: int = 0,
        tokens_per_s: Optional[float] = None,
        context: Optional[dict] = None,
    ) -> Optional[Any]:
        """Compare one run against the rolling baseline; returns the
        fired ``TriggerEvent`` (or a verdict dict when no recorder is
        attached) on regression, else None. ``run``: a ``StepProfile``
        or flat component dict; ``tokens_per_s`` merges into the
        components when the run object does not carry one."""
        comps = _components_of(run)
        if tokens_per_s is not None:
            comps["tokens_per_s"] = float(tokens_per_s)
        self._gauges(run, comps)
        if not comps:
            return None
        regressions: List[Dict[str, Any]] = []
        if len(self._hist) >= self.min_baseline:
            base = self.baseline()
            for k, v in comps.items():
                b = base.get(k)
                if b is None or b <= 0:
                    continue
                ratio = v / b
                if k == "tokens_per_s":
                    if ratio <= self.drop_threshold:
                        regressions.append(
                            {"component": k, "ratio": ratio, "baseline": b,
                             "value": v,
                             "reason": f"{_label(k)} {ratio:.2f}x baseline "
                                       f"({v:.1f} vs {b:.1f})"})
                elif ratio >= self.ratio_threshold:
                    regressions.append(
                        {"component": k, "ratio": ratio, "baseline": b,
                         "value": v,
                         "reason": f"{_label(k)} {ratio:.1f}x baseline "
                                   f"({v * 1e3:.2f}ms vs {b * 1e3:.2f}ms)"})
        if not regressions:
            self._hist.append(comps)
            self.last_verdict = None
            return None
        # worst offender names the trigger; tokens/s drops sort by
        # severity of the drop, time components by the blowup
        def severity(r: Dict[str, Any]) -> float:
            return (1.0 / r["ratio"] if r["component"] == "tokens_per_s"
                    else r["ratio"])

        regressions.sort(key=severity, reverse=True)
        worst = regressions[0]
        self.regressions += 1
        verdict = {
            "reason": worst["reason"],
            "regressions": regressions,
            "components": comps,
            "baseline_size": len(self._hist),
        }
        self.last_verdict = verdict
        self._gauges(run, comps)  # refresh the regressions_total gauge
        if self.recorder is not None:
            return self.recorder.fire_trigger(
                "perf_regression", worst["reason"], step,
                context=context,
                details={
                    "regressions": regressions,
                    "components": comps,
                    "baseline": self.baseline(),
                },
            )
        return verdict
