"""Static cost model: compiled program -> predicted step time.

Everything here is computed from artifacts a shape-only lower+compile
already produced (telemetry/doctor.py) plus the per-chip spec tables
next to ``PEAK_FLOPS`` (telemetry/derived.py) — no hardware, no
execution:

- compute seconds: XLA cost-analysis FLOPs of the per-device SPMD
  program over the chip's peak;
- comm seconds: the doctor's per-collective wire-byte estimates
  (``estimated_wire_bytes`` — payload conventions normalized per op)
  grouped by the mesh axes each collective spans, divided by the
  fabric bandwidth those axes ride (ICI inside a slice, DCI for
  cross-slice axes like the DiLoCo outer loop); the ring-overlap path
  hides a configured fraction of the tensor-axis traffic behind the
  partial matmuls;
- pipeline bubble: the analytic idle fraction from the schedulers
  (``GPipeScheduler``/``OneFOneBScheduler.bubble_fraction``) inflates
  the busy time;
- HBM feasibility: the doctor's per-device peak vs the chip budget —
  an infeasible candidate is pruned with the numbers in the reason.

The model ranks layouts; it does not promise wall-clock accuracy
(docs/planner.md).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

from pipegoose_tpu.telemetry.derived import (
    DCI_AXES,
    dci_bytes_per_s_for,
    hbm_bytes_for,
    ici_bytes_per_s_for,
    peak_flops_for,
)
from pipegoose_tpu.telemetry.doctor import wire_bytes_by_axes


@dataclasses.dataclass
class CostModel:
    """Per-chip budgets + scoring knobs for one target device kind."""

    device_kind: str = "cpu"
    peak_flops: float = 1e12
    ici_bytes_per_s: float = 10e9
    dci_bytes_per_s: float = 1e9
    hbm_bytes: float = 16 * 1024**3
    # mesh axes that ride the data-center network instead of ICI (the
    # shared definition lives in telemetry/derived.py next to the
    # bandwidth tables; override per model for custom topologies)
    dci_axes: Tuple[str, ...] = DCI_AXES
    # fraction of tensor-axis wire time the ring collective-matmul
    # overlap hides behind partial matmuls (docs/comm.md measured the
    # hops interleaving with tp-1 partial matmuls; 0.75 is the planner's
    # deliberately conservative default — calibrate() replaces it with
    # the MEASURED value)
    overlap_hidden_fraction: float = 0.75
    # fixed cost per collective INSTRUCTION (launch/dispatch latency) —
    # 0.0 in the uncalibrated spec-table model (bandwidth-only), fit by
    # calibrate() from measured profiles: small collectives are
    # launch-bound, and a model that prices them at bytes/bandwidth
    # alone calls a 40-instruction schedule free
    collective_launch_s: float = 0.0
    # fixed per-step time outside compute+comm (host dispatch, gaps) —
    # 0.0 uncalibrated, fit from the measured idle component
    step_overhead_s: float = 0.0
    # per-HLO-instruction dispatch/thunk cost — 0.0 uncalibrated, fit
    # jointly with step_overhead_s from (instruction count, idle)
    # samples: on a dispatch-bound backend (the CPU smoke) the step
    # wall ranks by instruction count, and a model blind to it cannot
    # reproduce the measured ranking
    dispatch_s_per_instruction: float = 0.0
    # provenance of a calibrated model: the fitted efficiencies + the
    # sample counts they rest on (None = uncalibrated spec tables)
    calibration: Optional[Dict[str, Any]] = None

    @classmethod
    def for_device(
        cls,
        device_kind: Optional[str] = None,
        hbm_bytes: Optional[float] = None,
    ) -> "CostModel":
        """Budgets from the spec tables (telemetry/derived.py) for a
        device-kind string; defaults to the first visible device.
        ``hbm_bytes`` overrides the table (plan for a chip you don't
        have)."""
        if device_kind is None:
            import jax

            dev = jax.devices()[0]
            device_kind = getattr(dev, "device_kind", dev.platform)
        return cls(
            device_kind=device_kind,
            peak_flops=peak_flops_for(device_kind),
            ici_bytes_per_s=ici_bytes_per_s_for(device_kind),
            dci_bytes_per_s=dci_bytes_per_s_for(device_kind),
            hbm_bytes=(float(hbm_bytes) if hbm_bytes is not None
                       else hbm_bytes_for(device_kind)),
        )

    def to_json(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, d: dict) -> "CostModel":
        base = cls()
        return cls(
            device_kind=str(d.get("device_kind", base.device_kind)),
            peak_flops=float(d.get("peak_flops", base.peak_flops)),
            ici_bytes_per_s=float(d.get("ici_bytes_per_s",
                                        base.ici_bytes_per_s)),
            dci_bytes_per_s=float(d.get("dci_bytes_per_s",
                                        base.dci_bytes_per_s)),
            hbm_bytes=float(d.get("hbm_bytes", base.hbm_bytes)),
            dci_axes=tuple(d.get("dci_axes", base.dci_axes)),
            overlap_hidden_fraction=float(
                d.get("overlap_hidden_fraction",
                      base.overlap_hidden_fraction)),
            collective_launch_s=float(
                d.get("collective_launch_s", base.collective_launch_s)),
            step_overhead_s=float(
                d.get("step_overhead_s", base.step_overhead_s)),
            dispatch_s_per_instruction=float(
                d.get("dispatch_s_per_instruction",
                      base.dispatch_s_per_instruction)),
            calibration=(dict(d["calibration"])
                         if d.get("calibration") else None),
        )

    def bandwidth_for_axes(self, axes: Tuple[str, ...]) -> float:
        if any(ax in self.dci_axes for ax in axes):
            return self.dci_bytes_per_s
        return self.ici_bytes_per_s

    def fabric_for_axes(self, axes: Tuple[str, ...]) -> str:
        return "dci" if any(ax in self.dci_axes for ax in axes) else "ici"

    # -- measured-delta calibration ----------------------------------------

    def calibrate(self, observations: Any) -> "CostModel":
        """Fit the model's constants to MEASURED step profiles and
        return the calibrated copy (self untouched).

        ``observations``: iterable of dicts, one per profiled
        candidate —

        - ``"profile"``: a ``telemetry.xprof.StepProfile`` (or its
          ``to_json()`` dict) of the candidate's real compiled step;
        - ``"breakdown"``: that candidate's STATIC score anatomy
          (``score_breakdown`` output: ``wire_bytes_by_axes``,
          ``collective_counts_by_axes``, ``flops_per_device``);
        - ``"overlap_tp"``: optional bool (default False) — overlap
          candidates' tensor-axis buckets feed the hidden-fraction fit,
          not the bandwidth fit (their measured time is post-overlap).

        Fits, in order (each falls back to the current constant when no
        sample supports it, recorded in ``calibration``):

        1. **flops efficiency** — median of achieved FLOP/s
           (``flops_per_device / compute_s``) over the spec-table peak;
           scales ``peak_flops``.
        2. **per-fabric bandwidth + launch cost** — least squares of
           measured bucket seconds against ``n_instructions * launch +
           bytes / bandwidth`` over every non-overlapped axes bucket;
           scales ``ici_bytes_per_s`` / ``dci_bytes_per_s`` and sets
           ``collective_launch_s`` (small collectives are launch-bound;
           a bytes-only model cannot rank schedules that differ mostly
           in instruction count).
        3. **measured overlap_hidden_fraction** — 1 - measured/expected
           un-overlapped tensor-axis time on overlap candidates,
           medianed and clamped to [0, 0.95].
        4. **step overhead** — median measured idle component (host
           dispatch + gaps the busy-time model never sees).
        """
        import statistics

        obs = []
        for o in observations:
            prof = o.get("profile")
            if prof is not None and hasattr(prof, "to_json"):
                prof = prof.to_json()
            if not prof:
                continue
            obs.append({
                "profile": prof,
                "breakdown": dict(o.get("breakdown") or {}),
                "overlap_tp": bool(o.get("overlap_tp", False)),
            })
        cal: Dict[str, Any] = {"observations": len(obs)}
        if not obs:
            return dataclasses.replace(self, calibration=cal)

        # 1) flops efficiency
        eff_samples = []
        for o in obs:
            flops = o["breakdown"].get("flops_per_device") \
                or o["profile"].get("flops_per_device")
            comp = float(o["profile"].get("compute_s") or 0.0)
            if flops and comp > 0:
                eff_samples.append(float(flops) / comp / self.peak_flops)
        flops_eff = (statistics.median(eff_samples)
                     if eff_samples else 1.0)
        cal["flops_efficiency"] = flops_eff
        cal["flops_samples"] = len(eff_samples)

        # 2) per-fabric bandwidth + launch: samples are (n, bytes, secs)
        per_fabric: Dict[str, list] = {"ici": [], "dci": []}
        overlap_samples = []  # (n, bytes, secs) of overlap tensor buckets
        for o in obs:
            wire = o["breakdown"].get("wire_bytes_by_axes") or {}
            counts = o["breakdown"].get("collective_counts_by_axes") or {}
            measured = o["profile"].get("comm_by_axes") or {}
            for key, secs in measured.items():
                nbytes = float(wire.get(key, 0.0))
                n = float(counts.get(key, 0.0))
                if secs <= 0 or (nbytes <= 0 and n <= 0):
                    continue
                axes = tuple(key.split("+")) if key != "?" else ()
                if o["overlap_tp"] and axes == ("tensor",):
                    overlap_samples.append((n, nbytes, float(secs)))
                    continue
                per_fabric[self.fabric_for_axes(axes)].append(
                    (n, nbytes, float(secs))
                )
        bw = {"ici": self.ici_bytes_per_s, "dci": self.dci_bytes_per_s}
        launch_samples = []
        for fabric, samples in per_fabric.items():
            if not samples:
                continue
            import numpy as np

            a = np.array([[n, b] for n, b, _ in samples], dtype=float)
            y = np.array([s for _, _, s in samples], dtype=float)
            launch = inv_bw = None
            if len(samples) >= 2 and np.linalg.matrix_rank(a) == 2:
                sol, *_ = np.linalg.lstsq(a, y, rcond=None)
                launch, inv_bw = float(sol[0]), float(sol[1])
            if launch is None or launch < 0 or inv_bw is None or inv_bw <= 0:
                # degenerate fit (few buckets, uniform bytes, or a
                # negative coefficient): split the aggregate measured
                # time evenly between the two terms — but only when
                # BOTH exist (counts absent in a pre-calibration
                # artifact must not halve the fitted bandwidth; bytes
                # absent must not zero the launch cost)
                tot_n = sum(n for n, _, _ in samples)
                tot_b = sum(b for _, b, _ in samples)
                tot_s = sum(s for _, _, s in samples)
                if tot_b > 0 and tot_n > 0:
                    inv_bw = tot_s / tot_b / 2.0
                    launch = tot_s / 2.0 / tot_n
                elif tot_b > 0:
                    inv_bw = tot_s / tot_b
                    launch = 0.0
                else:
                    inv_bw = 1.0 / bw[fabric]
                    launch = (tot_s / tot_n) if tot_n else 0.0
            bw[fabric] = 1.0 / inv_bw
            launch_samples.append(launch)
            cal[f"{fabric}_bandwidth_efficiency"] = (
                bw[fabric] / (self.ici_bytes_per_s if fabric == "ici"
                              else self.dci_bytes_per_s)
            )
            cal[f"{fabric}_samples"] = len(samples)
        launch_s = (statistics.median(launch_samples)
                    if launch_samples else self.collective_launch_s)
        launch_s = max(float(launch_s), 0.0)
        cal["collective_launch_s"] = launch_s

        # 3) measured overlap hidden fraction
        hidden = self.overlap_hidden_fraction
        if overlap_samples:
            hs = []
            for n, nbytes, secs in overlap_samples:
                expected = n * launch_s + nbytes / bw["ici"]
                if expected > 0:
                    hs.append(1.0 - secs / expected)
            if hs:
                hidden = min(max(statistics.median(hs), 0.0), 0.95)
        cal["overlap_hidden_fraction"] = hidden
        cal["overlap_samples"] = len(overlap_samples)

        # 4) per-step overhead from the measured idle component: joint
        # (base, per-instruction) fit over (n_instr, idle) samples —
        # idle on a dispatch-bound backend scales with the instruction
        # count (static, per candidate), so a flat median would erase
        # exactly the differences the re-scored ranking needs
        import numpy as np

        idle_samples = []
        for o in obs:
            idle = float(o["profile"].get("idle_s") or 0.0)
            n = (o["breakdown"].get("hlo_instructions")
                 or o["profile"].get("hlo_instructions"))
            idle_samples.append((float(n) if n else 0.0, idle))
        overhead = dispatch = 0.0
        if idle_samples:
            ns = {n for n, _ in idle_samples}
            if len(ns) >= 2:
                a = np.array([[1.0, n] for n, _ in idle_samples])
                y = np.array([i for _, i in idle_samples])
                sol, *_ = np.linalg.lstsq(a, y, rcond=None)
                # a base within float noise of zero is zero, not a
                # reason to throw the fit away
                overhead = max(float(sol[0]), 0.0)
                dispatch = float(sol[1])
            if dispatch <= 0:
                overhead = statistics.median([i for _, i in idle_samples])
                dispatch = 0.0
        cal["step_overhead_s"] = overhead
        cal["dispatch_s_per_instruction"] = dispatch

        return dataclasses.replace(
            self,
            peak_flops=self.peak_flops * flops_eff,
            ici_bytes_per_s=bw["ici"],
            dci_bytes_per_s=bw["dci"],
            overlap_hidden_fraction=hidden,
            collective_launch_s=launch_s,
            step_overhead_s=overhead,
            dispatch_s_per_instruction=dispatch,
            calibration=cal,
        )


def hbm_check(report: Any, cost_model: CostModel) -> Optional[str]:
    """None when the compiled program fits the chip, else the prune
    reason with the numbers. The live backend ``bytes_limit`` wins
    where the doctor saw one (a real TPU); the spec-table budget covers
    fake-device planning."""
    from pipegoose_tpu.telemetry.doctor import _fmt_bytes

    budget = float(report.memory.hbm_limit or cost_model.hbm_bytes)
    peak = float(report.memory.peak_bytes)
    if peak > budget:
        return (f"HBM-infeasible: per-device peak {_fmt_bytes(int(peak))} "
                f"> budget {_fmt_bytes(int(budget))} "
                f"({cost_model.device_kind})")
    return None


def score_breakdown(
    candidate: Any,
    report: Any,
    cost_model: CostModel,
    tokens_per_step: int,
    bubble_fraction: float = 0.0,
) -> Dict[str, Any]:
    """The per-candidate score anatomy (docs/planner.md):

    {"score" (predicted global tokens/s — the ranking key),
     "step_seconds", "compute_seconds", "comm_seconds",
     "comm_seconds_by_axes", "wire_bytes_by_axes", "bubble_fraction",
     "flops_per_device", "hbm_peak_bytes", "hbm_budget_bytes",
     "tokens_per_step"}.

    All candidates score the SAME global batch, so the tokens/s ranking
    is exactly the inverse step-time ranking.
    """
    # a backend without AOT cost analysis yields cost_flops=None
    # (doctor.py treats it as advisory): the ranking then rests on comm
    # time alone — carried as an explicit compute_modeled=False marker
    # in the breakdown, and run_plan logs it, never a silent zero
    compute_modeled = report.cost_flops is not None
    flops = float(report.cost_flops or 0.0)
    compute_s = flops / cost_model.peak_flops
    wire = wire_bytes_by_axes(report)
    # instruction counts per axes bucket: the launch-cost numerator (a
    # calibrated model prices dispatch-bound small collectives by
    # count, not bytes) and the calibration fit's sample shape
    sharding = getattr(report, "sharding", report)
    counts: Dict[str, int] = {}
    for c in sharding.collectives:
        key = "+".join(c.mesh_axes) if c.mesh_axes else "?"
        counts[key] = counts.get(key, 0) + 1
    comm_by_axes: Dict[str, float] = {}
    wire_by_axes: Dict[str, int] = {}
    overlap_on = bool(getattr(candidate, "overlap_tp", False))
    for axes, nbytes in sorted(wire.items()):
        key = "+".join(axes) if axes else "?"
        t = (nbytes / cost_model.bandwidth_for_axes(axes)
             + counts.get(key, 0) * cost_model.collective_launch_s)
        if overlap_on and axes == ("tensor",):
            t *= 1.0 - cost_model.overlap_hidden_fraction
        comm_by_axes[key] = comm_by_axes.get(key, 0.0) + t
        wire_by_axes[key] = wire_by_axes.get(key, 0) + int(nbytes)
    comm_s = sum(comm_by_axes.values())
    busy_s = compute_s + comm_s
    bubble = min(max(float(bubble_fraction), 0.0), 0.99)
    step_s = busy_s / (1.0 - bubble) if busy_s > 0 else 0.0
    n_instr = int(getattr(report, "hlo_instructions", None) or 0)
    overhead_s = (cost_model.step_overhead_s
                  + cost_model.dispatch_s_per_instruction * n_instr)
    step_s += overhead_s
    score = tokens_per_step / step_s if step_s > 0 else 0.0
    return {
        "score": score,
        "step_seconds": step_s,
        "compute_modeled": compute_modeled,
        "compute_seconds": compute_s,
        "comm_seconds": comm_s,
        "comm_seconds_by_axes": comm_by_axes,
        "wire_bytes_by_axes": wire_by_axes,
        "collective_counts_by_axes": counts,
        "hlo_instructions": n_instr or None,
        "overhead_seconds": overhead_s,
        "bubble_fraction": bubble,
        "flops_per_device": flops,
        "hbm_peak_bytes": int(report.memory.peak_bytes),
        "hbm_budget_bytes": int(report.memory.hbm_limit
                                or cost_model.hbm_bytes),
        "tokens_per_step": int(tokens_per_step),
    }
