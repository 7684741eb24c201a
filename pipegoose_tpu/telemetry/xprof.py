"""Measured step attribution from XLA profiler traces.

Everything the stack measured so far is either host-side wall clock
(fenced ``span()``s, request tracing) or a compile-time estimate (the
mesh doctor's wire bytes, the planner's static cost model). This module
closes the gap GSPMD-lineage systems (arxiv 2105.04663, 2211.05322)
close with profiler feedback: run the REAL compiled step under
``jax.profiler.trace(..., create_perfetto_trace=True)``, parse the
emitted ``*.trace.json.gz``, and attribute the measured device time of
one step to

- **compute** — every HLO instruction that is not a collective;
- **per-mesh-axis collectives** — trace op events joined against the
  mesh doctor's :class:`~pipegoose_tpu.telemetry.doctor.CollectiveInfo`
  schedule by HLO instruction name, so each measured collective lands
  on the axes its replica groups span (``derived.py``'s fabric tables
  then turn bytes/seconds into utilization);
- **idle** — the fenced step wall time not covered by either (host
  gaps between dispatches, dispatch latency, pipeline bubbles).

The join works because the trace's op events carry
``args = {"hlo_module": <module>, "hlo_op": <instruction name>}`` —
the same instruction names ``compiled.as_text()`` prints, which is what
``parse_collective_schedule`` tables. On backends whose trace carries
no op events at all, :func:`profile_step` degrades to a HOST-CLOCK
fallback (``source="host_clock"``): the fenced wall time is attributed
wholesale to compute, so CI on exotic platforms still gets a finite,
clearly-labelled profile instead of a crash.

Attribution arithmetic: every instruction executes once per device per
step (loop bodies more often — their repeats still sum into the same
instruction bucket), so dividing an instruction's summed trace duration
by ``steps x n_devices`` yields its mean per-device per-step seconds.
Per-device op execution is serial, so ``compute + comm <= wall`` and
``idle`` is the (clamped) residual; the raw residual is kept on the
profile so over-attribution is visible, never silently absorbed.

Everything is opt-in: nothing here runs unless a caller invokes
:func:`profile_step` (or the ``Trainer.profile`` /
``ServingEngine.profile`` fronts), and the profiled function pays the
profiler's own overhead only for the measured steps.
"""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import gzip
import json
import os
import re
import tempfile
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import numpy as np

from pipegoose_tpu.telemetry.derived import (
    DCI_AXES,
    dci_bytes_per_s_for,
    ici_bytes_per_s_for,
    peak_flops_for,
)
from pipegoose_tpu.telemetry.doctor import (
    CollectiveInfo,
    estimated_wire_bytes,
    hlo_instruction_names,
    parse_collective_schedule,
)

# trace-event names that are HLO collectives, including the async
# "-start"/"-done" halves real TPU schedules split them into
_COLLECTIVE_PREFIXES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute",
)

_HLO_MODULE_RE = re.compile(r"^HloModule\s+([^\s,]+)", re.MULTILINE)



def _is_collective_name(name: str) -> bool:
    return name.startswith(_COLLECTIVE_PREFIXES)


def _base_collective_name(name: str) -> str:
    """Strip the async suffix: ``all-gather-start.1`` and
    ``all-gather-done.1`` both attribute to the schedule's
    ``all-gather-start.1``-or-plain row by its stem."""
    return re.sub(r"-(start|done)(?=\.|$)", "", name)


@dataclasses.dataclass
class StepProfile:
    """Measured device-time attribution of one compiled step.

    ``compute_s`` / ``comm_s`` / ``idle_s`` are mean per-device
    per-step seconds and sum to ``wall_step_s`` (the fenced host wall
    time per step) up to ``residual_s`` — the raw un-clamped residual,
    negative exactly when attribution over-counted. ``comm_by_axes``
    buckets the collective time by the mesh axes each instruction's
    replica groups span (``"?"`` = unattributed). ``collectives`` keeps
    the per-instruction rows (name, op, axes, seconds, schedule bytes)
    — the op-for-op join against the doctor's schedule the acceptance
    tests pin. ``source`` is ``"device_trace"`` when op events were
    found, ``"host_clock"`` for the wall-time-only fallback.
    """

    steps: int
    n_devices: int
    wall_step_s: float
    compute_s: float
    comm_s: float
    idle_s: float
    residual_s: float
    comm_by_axes: Dict[str, float]
    collectives: List[Dict[str, Any]]
    source: str
    device_kind: str
    module_name: str = ""
    # distinct HLO instructions of the compiled module — the dispatch-
    # cost driver the calibrated planner model (planner/cost.py) fits
    # its per-instruction overhead term against
    hlo_instructions: Optional[int] = None
    flops_per_device: Optional[float] = None
    mfu: Optional[float] = None
    # axes-bucket -> measured fraction of the fabric's peak bandwidth
    # (estimated wire bytes / measured bucket seconds / peak B/s)
    fabric_utilization: Dict[str, float] = dataclasses.field(
        default_factory=dict
    )
    top_ops: List[Dict[str, Any]] = dataclasses.field(default_factory=list)
    wall_steps_s: List[float] = dataclasses.field(default_factory=list)

    # -- views -------------------------------------------------------------

    @property
    def attributed_s(self) -> float:
        return self.compute_s + self.comm_s + self.idle_s

    @property
    def compute_fraction(self) -> float:
        return self.compute_s / self.wall_step_s if self.wall_step_s > 0 else 0.0

    @property
    def comm_fraction(self) -> float:
        return self.comm_s / self.wall_step_s if self.wall_step_s > 0 else 0.0

    @property
    def idle_fraction(self) -> float:
        return self.idle_s / self.wall_step_s if self.wall_step_s > 0 else 0.0

    def components(self) -> Dict[str, float]:
        """Flat component dict — the perf sentinel's comparison unit:
        ``{"compute_s", "idle_s", "comm[<axes>]_s"...}``."""
        out = {"compute_s": self.compute_s, "idle_s": self.idle_s}
        for axes, t in self.comm_by_axes.items():
            out[f"comm[{axes}]_s"] = t
        return out

    # -- serialization -----------------------------------------------------

    def to_json(self) -> dict:
        d = dataclasses.asdict(self)
        d["compute_fraction"] = self.compute_fraction
        d["comm_fraction"] = self.comm_fraction
        d["idle_fraction"] = self.idle_fraction
        return d

    @classmethod
    def from_json(cls, d: dict) -> "StepProfile":
        # forward compat: pick known keys only (doctor/report convention)
        return cls(
            steps=int(d["steps"]),
            n_devices=int(d["n_devices"]),
            wall_step_s=float(d["wall_step_s"]),
            compute_s=float(d["compute_s"]),
            comm_s=float(d["comm_s"]),
            idle_s=float(d["idle_s"]),
            residual_s=float(d.get("residual_s", 0.0)),
            comm_by_axes={str(k): float(v)
                          for k, v in (d.get("comm_by_axes") or {}).items()},
            collectives=[dict(c) for c in d.get("collectives", [])],
            source=str(d.get("source", "device_trace")),
            device_kind=str(d.get("device_kind", "?")),
            module_name=str(d.get("module_name", "")),
            hlo_instructions=(None if d.get("hlo_instructions") is None
                              else int(d["hlo_instructions"])),
            flops_per_device=(None if d.get("flops_per_device") is None
                              else float(d["flops_per_device"])),
            mfu=(None if d.get("mfu") is None else float(d["mfu"])),
            fabric_utilization={
                str(k): float(v)
                for k, v in (d.get("fabric_utilization") or {}).items()
            },
            top_ops=[dict(t) for t in d.get("top_ops", [])],
            wall_steps_s=[float(x) for x in d.get("wall_steps_s", [])],
        )

    def format_table(self, max_ops: int = 8) -> str:
        from pipegoose_tpu.telemetry.doctor import _align

        def ms(x: float) -> str:
            return f"{x * 1e3:.3f}ms"

        lines = [
            f"step profile ({self.source}): {self.steps} step(s) x "
            f"{self.n_devices} device(s), wall {ms(self.wall_step_s)}/step",
            "",
        ]
        rows = [("component", "per-step", "fraction")]
        rows.append(("compute", ms(self.compute_s),
                     f"{self.compute_fraction:6.1%}"))
        for axes, t in sorted(self.comm_by_axes.items()):
            frac = t / self.wall_step_s if self.wall_step_s > 0 else 0.0
            rows.append((f"comm[{axes}]", ms(t), f"{frac:6.1%}"))
        rows.append(("idle", ms(self.idle_s), f"{self.idle_fraction:6.1%}"))
        lines += _align(rows)
        if self.mfu is not None:
            lines += ["", f"measured MFU {self.mfu:.4f} "
                          f"({self.device_kind})"]
        for axes, u in sorted(self.fabric_utilization.items()):
            lines.append(f"fabric[{axes}] utilization {u:.1%}")
        if self.collectives:
            lines += ["", "collectives (measured vs schedule):"]
            lines += _align([("name", "op", "axes", "per-step", "bytes")] + [
                (c["name"] or "?", c["op"],
                 ",".join(c["axes"]) if c.get("axes") else "?",
                 ms(float(c["seconds"])), str(c.get("bytes", 0)))
                for c in self.collectives
            ])
        if self.top_ops:
            lines += ["", "largest compute ops:"]
            lines += _align([("name", "per-step")] + [
                (t["name"], ms(float(t["seconds"])))
                for t in self.top_ops[:max_ops]
            ])
        if self.residual_s < 0:
            lines += ["", f"WARNING: attribution exceeds wall by "
                          f"{ms(-self.residual_s)} (concurrent thunks)"]
        return "\n".join(lines)


def set_profile_gauges(profile: StepProfile, registry: Any = None) -> None:
    """Land the profile's headline fractions as gauges next to MFU:
    ``perf.compute_fraction`` / ``perf.comm_fraction`` /
    ``perf.idle_fraction`` (+ ``perf.measured_mfu`` when modeled)."""
    from pipegoose_tpu.telemetry.registry import get_registry

    reg = registry if registry is not None else get_registry()
    reg.gauge(
        "perf.compute_fraction",
        help="measured compute share of the fenced step wall time",
    ).set(float(profile.compute_fraction))
    reg.gauge(
        "perf.comm_fraction",
        help="measured collective share of the fenced step wall time",
    ).set(float(profile.comm_fraction))
    reg.gauge(
        "perf.idle_fraction",
        help="measured idle share of the fenced step wall time",
    ).set(float(profile.idle_fraction))
    if profile.mfu is not None:
        reg.gauge(
            "perf.measured_mfu",
            help="XLA cost-analysis FLOPs over measured step wall x peak",
        ).set(float(profile.mfu))


# -- trace parsing ---------------------------------------------------------


def find_trace_file(logdir: str) -> Optional[str]:
    """Newest ``*.trace.json.gz`` under a ``jax.profiler.trace`` logdir
    (the profiler writes ``plugins/profile/<run>/<host>.trace.json.gz``)."""
    paths = glob.glob(
        os.path.join(logdir, "plugins", "profile", "*", "*.trace.json.gz")
    ) + glob.glob(os.path.join(logdir, "*.trace.json.gz"))
    # the perfetto conversion of the same run is not the event stream
    paths = [p for p in paths
             if not os.path.basename(p).startswith("perfetto")]
    return max(paths, key=os.path.getmtime) if paths else None


def load_trace_events(path: str) -> List[dict]:
    """The ``traceEvents`` list of a (gzipped) Chrome-trace JSON."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        data = json.load(f)
    return data["traceEvents"] if isinstance(data, dict) else data


def op_events(
    events: Sequence[dict],
    module_name: str,
    instruction_names: Optional[set] = None,
) -> List[dict]:
    """Complete ("X") events that are HLO op executions of
    ``module_name``: primary match on ``args.hlo_module`` (what the TSL
    profiler stamps on op events); fallback — for traces whose op
    events carry no args — on the event name being one of the module's
    instruction names."""
    primary = [
        e for e in events
        if e.get("ph") == "X"
        and isinstance(e.get("args"), dict)
        and e["args"].get("hlo_module") == module_name
    ]
    if primary or not instruction_names:
        return primary
    return [
        e for e in events
        if e.get("ph") == "X" and not e.get("args")
        and e.get("name") in instruction_names
    ]


def attribute_op_times(
    events: Sequence[dict],
    steps: int,
    n_devices: int,
    schedule: Sequence[CollectiveInfo] = (),
) -> Dict[str, Any]:
    """Aggregate op events into per-device per-step seconds.

    Returns ``{"compute_s", "comm_s", "comm_by_axes", "collectives",
    "top_ops", "per_op"}`` where every seconds value is
    ``sum(dur) / (steps * n_devices)``. Collective events join the
    doctor ``schedule`` by HLO instruction name (async start/done halves
    by stem) to inherit mesh axes + payload bytes; unmatched collectives
    land in the ``"?"`` bucket with ``bytes=0``. A ``while``,
    ``conditional`` or ``call`` is not counted: its event spans its
    body's ops, which are events of their own.
    """
    totals: Dict[str, float] = {}
    for e in events:
        name = e.get("name")
        if not name:
            continue
        op = (e.get("args") or {}).get("hlo_op") or name
        if op.split(".")[0] in ("while", "conditional", "call"):
            continue
        totals[op] = totals.get(op, 0.0) + float(e.get("dur", 0.0)) * 1e-6
    denom = max(steps, 1) * max(n_devices, 1)
    per_op = {k: v / denom for k, v in totals.items()}

    by_name: Dict[str, CollectiveInfo] = {}
    for c in schedule:
        if c.name:
            by_name[c.name] = c
    compute_s = 0.0
    comm_by_axes: Dict[str, float] = {}
    collectives: List[Dict[str, Any]] = []
    top_ops: List[Dict[str, Any]] = []
    for name, secs in per_op.items():
        # XLA names an instruction after the op that made it
        # (``psum.14`` is an all-reduce), so the schedule — parsed from
        # the HLO by op kind — says what is a collective; the name
        # prefix only catches those the schedule does not list
        info = by_name.get(name) or by_name.get(_base_collective_name(name))
        if info is None and not _is_collective_name(name):
            compute_s += secs
            top_ops.append({"name": name, "seconds": secs})
            continue
        axes = tuple(info.mesh_axes) if info is not None and info.mesh_axes \
            else None
        key = "+".join(axes) if axes else "?"
        comm_by_axes[key] = comm_by_axes.get(key, 0.0) + secs
        collectives.append({
            "name": name,
            "op": (info.op if info is not None
                   else _base_collective_name(name).rsplit(".", 1)[0]),
            "axes": list(axes) if axes else None,
            "seconds": secs,
            "bytes": int(info.bytes) if info is not None else 0,
            "intentional": (bool(info.intentional)
                            if info is not None else None),
        })
    top_ops.sort(key=lambda t: -t["seconds"])
    collectives.sort(key=lambda c: -c["seconds"])
    return {
        "compute_s": compute_s,
        "comm_s": sum(comm_by_axes.values()),
        "comm_by_axes": comm_by_axes,
        "collectives": collectives,
        "top_ops": top_ops[:16],
        "per_op": per_op,
    }


# -- the profiler ----------------------------------------------------------


@contextlib.contextmanager
def _trace_session(logdir: str, create_perfetto_trace: bool = False):
    """A profiler session with the PYTHON tracer disabled.

    ``jax.profiler.trace`` defaults to ``python_tracer_level=1``, which
    wraps every Python call in a TraceMe — measured ~25x dispatch
    inflation on the CPU smoke, enough to invert the step-time ranking
    being profiled. The XLA op events this module consumes come from
    the host/device tracers, so the Python tracer is pure observer
    effect here. Falls back to plain ``jax.profiler.trace`` when the
    (private) session API is unavailable.
    """
    try:
        from jax._src.lib import xla_client

        opts = xla_client.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        # level 1 keeps the XLA op events (the payload here) at about
        # half the per-event recording overhead of the default 2
        opts.host_tracer_level = 1
        sess = xla_client.profiler.ProfilerSession(opts)
    except Exception:  # noqa: BLE001 - private API; degrade gracefully
        with jax.profiler.trace(logdir,
                                create_perfetto_trace=create_perfetto_trace):
            yield
        return
    try:
        yield
    finally:
        sess.export(sess.stop(), str(logdir))
        if create_perfetto_trace:
            try:
                from jax._src.profiler import _write_perfetto_trace_file

                _write_perfetto_trace_file(logdir)
            except Exception:  # noqa: BLE001 - the conversion is a
                pass           # convenience; the parsed trace exists


def _mesh_axes_of(compiled: Any, mesh: Any) -> Dict[str, int]:
    if mesh is None:
        from jax.sharding import NamedSharding

        try:
            leaves = (
                list(jax.tree_util.tree_leaves(compiled.input_shardings[0]))
                + list(jax.tree_util.tree_leaves(compiled.output_shardings))
            )
        except Exception:  # noqa: BLE001 - shardings are advisory
            leaves = []
        for s in leaves:
            if isinstance(s, NamedSharding):
                mesh = s.mesh
                break
    if mesh is None:
        return {}
    return {str(k): int(v) for k, v in dict(mesh.shape).items()}


def profile_step(
    fn: Callable,
    *args: Any,
    steps: int = 3,
    warmup: int = 2,
    update_args: Optional[Callable] = None,
    mesh: Any = None,
    device_kind: Optional[str] = None,
    trace_dir: Optional[str] = None,
    registry: Any = None,
) -> StepProfile:
    """Run the real compiled step under the XLA profiler and return its
    measured :class:`StepProfile`.

    ``fn`` may be jitted (donation settings kept) or a plain callable
    (wrapped in ``jax.jit``). ``args`` are REAL arrays — unlike the
    mesh doctor, the step EXECUTES (``warmup + steps`` times: warmup
    outside the trace so compile/cache effects never pollute the
    measured events; the default ``warmup=2`` matters — the FIRST call
    compiles and the SECOND settles donated-buffer layouts, measured at
    ~50x a steady step on CPU, so a 1-warmup profile would bake that
    one-off into every component). ``update_args(out, args) -> args`` threads one
    step's outputs into the next call — REQUIRED when the step donates
    inputs (the hybrid train step donates params/opt state; the paged
    decode step donates its KV pages), otherwise the second call would
    touch deleted buffers. Each measured step is individually fenced
    (``block_until_ready``) and host-timed; the fenced mean is the
    profile's wall denominator.

    ``trace_dir``: keep the profiler artifact there (TensorBoard /
    ui.perfetto.dev viewable — ``create_perfetto_trace=True`` also
    writes the perfetto conversion); default is a temp dir parsed and
    discarded. Fractions land as ``perf.*`` gauges on ``registry``
    (default: the global one; disabled registries cost one branch).
    """
    if steps < 1:
        raise ValueError(f"steps must be >= 1, got {steps}")
    if warmup < 0:
        raise ValueError(f"warmup must be >= 0, got {warmup}")
    jfn = fn if hasattr(fn, "lower") else jax.jit(fn)

    # ONE AOT lower+compile for the compile-time side: module name,
    # instruction set, the collective schedule (axes + bytes), FLOPs
    lowered = jfn.lower(*args)
    compiled = lowered.compile()
    try:
        hlo = compiled.as_text()
    except Exception:  # noqa: BLE001 - backends without HLO text export
        hlo = ""
    m = _HLO_MODULE_RE.search(hlo)
    module_name = m.group(1) if m else ""
    # the SAME counting rule the doctor/planner side uses — the
    # calibration fit joins the two counts
    instruction_names = hlo_instruction_names(hlo)
    mesh_axes = _mesh_axes_of(compiled, mesh)
    n_devices = int(np.prod(list(mesh_axes.values()))) if mesh_axes else 1
    schedule = parse_collective_schedule(hlo, mesh_axes)
    cost_flops = None
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        f = dict(ca or {}).get("flops")
        cost_flops = float(f) if f is not None else None
    except Exception:  # noqa: BLE001 - cost analysis is advisory
        pass

    if device_kind is None:
        dev = jax.devices()[0]
        device_kind = getattr(dev, "device_kind", dev.platform)

    def one(step_args):
        out = jfn(*step_args)
        jax.block_until_ready(out)
        return out, (update_args(out, step_args) if update_args is not None
                     else step_args)

    cur = tuple(args)
    for _ in range(warmup):
        _, cur = one(cur)

    logdir = trace_dir
    tmp = None
    if logdir is None:
        tmp = tempfile.TemporaryDirectory(prefix="pipegoose_xprof_")
        logdir = tmp.name
    walls: List[float] = []
    try:
        # perfetto conversion only when the caller keeps the artifact —
        # a parsed-and-discarded temp dir doesn't need the copy
        with _trace_session(logdir,
                            create_perfetto_trace=trace_dir is not None):
            for _ in range(steps):
                t0 = time.perf_counter()
                _, cur = one(cur)
                walls.append(time.perf_counter() - t0)
        trace_path = find_trace_file(logdir)
        events = load_trace_events(trace_path) if trace_path else []
    finally:
        if tmp is not None:
            tmp.cleanup()

    wall_step_s = float(sum(walls) / len(walls))
    ops = op_events(events, module_name, instruction_names)
    if ops:
        att = attribute_op_times(ops, steps, n_devices, schedule)
        compute_s = att["compute_s"]
        comm_s = att["comm_s"]
        comm_by_axes = att["comm_by_axes"]
        collectives = att["collectives"]
        top_ops = att["top_ops"]
        source = "device_trace"
    else:
        # host-clock fallback: no op events in the trace (backend
        # without op-level profiling) — the fenced wall IS the only
        # measurement; attribute it to compute, loudly labelled
        compute_s, comm_s = wall_step_s, 0.0
        comm_by_axes, collectives, top_ops = {}, [], []
        source = "host_clock"
    residual_s = wall_step_s - compute_s - comm_s
    idle_s = max(residual_s, 0.0)

    flops_per_device = cost_flops
    mfu = None
    if flops_per_device is not None and wall_step_s > 0:
        mfu = flops_per_device / wall_step_s / peak_flops_for(device_kind)

    # measured fabric utilization: estimated wire bytes of each axes
    # bucket over its measured seconds, vs the fabric's peak B/s
    wire_by_key: Dict[str, int] = {}
    for c in schedule:
        if not c.mesh_axes:
            continue
        key = "+".join(c.mesh_axes)
        wire_by_key[key] = (wire_by_key.get(key, 0)
                            + estimated_wire_bytes(c, mesh_axes))
    fabric_utilization: Dict[str, float] = {}
    for key, secs in comm_by_axes.items():
        nbytes = wire_by_key.get(key)
        if not nbytes or secs <= 0:
            continue
        peak_bw = (dci_bytes_per_s_for(device_kind)
                   if any(ax in DCI_AXES for ax in key.split("+"))
                   else ici_bytes_per_s_for(device_kind))
        fabric_utilization[key] = (nbytes / secs) / peak_bw

    profile = StepProfile(
        steps=steps, n_devices=n_devices, wall_step_s=wall_step_s,
        compute_s=compute_s, comm_s=comm_s, idle_s=idle_s,
        residual_s=residual_s, comm_by_axes=comm_by_axes,
        collectives=collectives, source=source, device_kind=str(device_kind),
        module_name=module_name,
        hlo_instructions=len(instruction_names) or None,
        flops_per_device=flops_per_device,
        mfu=mfu, fabric_utilization=fabric_utilization,
        top_ops=top_ops, wall_steps_s=[float(w) for w in walls],
    )
    set_profile_gauges(profile, registry=registry)
    return profile
