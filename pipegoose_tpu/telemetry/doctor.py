"""Mesh doctor: compile-time sharding & memory inspection of pjit /
shard_map programs, with CI regression guards.

The runtime telemetry layer (registry/spans/derived, health, flight
recorder) measures what a step DID; this module inspects what the
partitioner COMPILED — the artifact Megatron-LM and Alpa-style systems
treat as first-class and the reference stack never exposes. A silently
replicated weight or a GSPMD-inserted all-gather on the hot path shows
up here as a named table row at compile time, not as a mysteriously
slow (or OOMing) step on hardware. Three views, all from ONE
``jax.jit(fn).lower(*args).compile()``:

- :class:`ShardingReport` — the ACTUAL sharding of every input leaf
  (params, optimizer state, batch, KV pages ...) and output buffer from
  ``compiled.input_shardings`` / ``output_shardings``, diffed against
  the INTENDED ``PartitionSpec`` trees (``parallel/auto.py`` /
  ``parallel/hybrid.py``), with the parameter's module path on every
  flag; plus the per-collective schedule the compiler actually emitted
  (bytes, mesh axes recovered from replica groups, source op), split
  into *intentional* traffic (an HLO collective whose metadata names a
  user-level jax collective primitive — psum, pmean, all_gather,
  psum_scatter, ppermute, all_to_all) and *resharding* traffic (GSPMD
  inserted it; no collective primitive in the metadata).
- :class:`MemoryReport` — a per-device HBM budget: bytes per argument
  group (params / opt state / batch / ...), outputs, XLA's own
  temp/peak numbers from ``compiled.memory_analysis()`` where the
  backend reports them (shape-walk fallback otherwise), and the
  largest buffers ranked — an OOM becomes a table, not a crash.
- Guards — :func:`assert_no_resharding` /
  :func:`assert_fully_sharded` / :func:`assert_matches_intended` raise
  :class:`ShardingRegressionError` with the offending rows, so tier-1
  tests pin a step's partitioning plan and a future PR that breaks a
  PartitionSpec fails at compile time on a host-device mesh, not in a
  TPU bench.

Reports serialize (``to_json``/``from_json``), pretty-print
(``format_table``), and land as telemetry gauges
(``doctor.replicated_bytes``, ``doctor.resharding_bytes``,
``doctor.hbm_peak_bytes`` — :func:`set_doctor_gauges`) next to MFU.
Entry points: :func:`diagnose` (any jitted/plain callable),
``Trainer.doctor()``, ``ServingEngine.doctor()`` and the
``scripts/mesh_doctor.py`` CLI. See docs/observability.md ("Mesh
doctor").
"""
from __future__ import annotations

import dataclasses
import itertools
import json
import re
from fnmatch import fnmatch
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from pipegoose_tpu.telemetry.derived import iter_collectives

# jax collective primitives a user writes explicitly (inside shard_map
# or via lax.*): an HLO collective whose metadata op_name ends in one of
# these is the user's own traffic, anything else was inserted by the
# partitioner (resharding / partial-sum reduction of a sharded matmul).
INTENTIONAL_PRIMITIVES = frozenset({
    "psum", "pmean", "pmax", "pmin", "all_gather", "all_gather_invariant",
    "all_to_all", "ppermute", "pshuffle", "psum_scatter", "reduce_scatter",
})

_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
_INSTR_NAME_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([A-Za-z0-9_.-]+)\s*=", re.MULTILINE
)
_RG_IOTA_RE = re.compile(
    r"replica_groups=\[([0-9,]+)\]<=\[([0-9,]+)\](?:T\(([0-9,]+)\))?"
)
_RG_EXPLICIT_RE = re.compile(r"replica_groups=\{(\{[0-9,{} ]*\})\}")
_STP_RE = re.compile(r"source_target_pairs=\{(\{[0-9,{} ]*\})\}")


# -- dataclasses -----------------------------------------------------------


@dataclasses.dataclass
class BufferInfo:
    """One input/output leaf of the compiled program."""

    path: str                 # e.g. "params/transformer/h_0/attn/qkv/w"
    shape: Tuple[int, ...]
    dtype: str
    actual: str               # actual sharding spec (str(PartitionSpec))
    intended: Optional[str]   # intended spec string, None = no intent given
    global_bytes: int
    per_device_bytes: int
    replicated: bool          # fully replicated across a >1-device mesh
    role: str = "input"       # "input" | "donated input" | "output"
    flags: List[str] = dataclasses.field(default_factory=list)
    # flags: "mismatch" (intended != actual), "replicated_large"
    # (intended sharded, actual replicated), "unsharded_large" (large
    # and replicated with no/replicated intent — likely a missing spec)


@dataclasses.dataclass
class CollectiveInfo:
    """One collective instruction of the compiled program."""

    op: str                           # "all-gather", "all-reduce", ...
    bytes: int                        # output-payload bytes (wire proxy)
    mesh_axes: Optional[Tuple[str, ...]]  # axes the groups span, if resolvable
    source: str                       # last metadata op_name component, "" if none
    intentional: bool                 # user collective primitive vs GSPMD-inserted
    # HLO instruction name ("all-reduce.2") — the key the measured
    # profiler attribution (telemetry/xprof.py) joins trace events on,
    # so a profiled collective's device time lands on THIS schedule row.
    # "" on reports from artifacts written before the field existed.
    name: str = ""


@dataclasses.dataclass
class ShardingReport:
    """Actual-vs-intended shardings + the emitted collective schedule."""

    mesh_axes: Dict[str, int]
    n_devices: int
    buffers: List[BufferInfo]
    collectives: List[CollectiveInfo]

    @property
    def replicated_bytes(self) -> int:
        """Per-device bytes pinned by fully replicated buffers (inputs
        only — outputs usually alias donated inputs)."""
        return sum(b.per_device_bytes for b in self.buffers
                   if b.replicated and b.role != "output")

    @property
    def resharding_bytes(self) -> int:
        return sum(c.bytes for c in self.collectives if not c.intentional)

    @property
    def intentional_bytes(self) -> int:
        return sum(c.bytes for c in self.collectives if c.intentional)

    @property
    def resharding_collectives(self) -> List[CollectiveInfo]:
        return [c for c in self.collectives if not c.intentional]

    def mismatches(self) -> List[BufferInfo]:
        return [b for b in self.buffers if "mismatch" in b.flags]

    def flagged(self) -> List[BufferInfo]:
        return [b for b in self.buffers if b.flags]

    def to_json(self) -> dict:
        return {
            "mesh_axes": dict(self.mesh_axes),
            "n_devices": self.n_devices,
            "buffers": [dataclasses.asdict(b) for b in self.buffers],
            "collectives": [dataclasses.asdict(c) for c in self.collectives],
            "replicated_bytes": self.replicated_bytes,
            "resharding_bytes": self.resharding_bytes,
            "intentional_bytes": self.intentional_bytes,
        }

    @classmethod
    def from_json(cls, d: dict) -> "ShardingReport":
        return cls(
            mesh_axes=dict(d["mesh_axes"]),
            n_devices=int(d["n_devices"]),
            buffers=[BufferInfo(
                path=b["path"], shape=tuple(b["shape"]), dtype=b["dtype"],
                actual=b["actual"], intended=b.get("intended"),
                global_bytes=int(b["global_bytes"]),
                per_device_bytes=int(b["per_device_bytes"]),
                replicated=bool(b["replicated"]),
                role=b.get("role", "input"), flags=list(b.get("flags", [])),
            ) for b in d["buffers"]],
            collectives=[CollectiveInfo(
                op=c["op"], bytes=int(c["bytes"]),
                mesh_axes=tuple(c["mesh_axes"]) if c.get("mesh_axes") else None,
                source=c.get("source", ""),
                intentional=bool(c["intentional"]),
                name=c.get("name", ""),
            ) for c in d["collectives"]],
        )

    def format_table(self, max_rows: int = 32) -> str:
        mesh = " ".join(f"{k}={v}" for k, v in self.mesh_axes.items()) or "-"
        lines = [f"mesh: {mesh} ({self.n_devices} devices)", "", "buffers:"]
        # flagged rows always shown, then the largest of the rest
        flagged = self.flagged()
        rest = sorted((b for b in self.buffers if not b.flags),
                      key=lambda b: -b.global_bytes)
        rows = flagged + rest[:max(0, max_rows - len(flagged))]
        header = ("path", "shape", "dtype", "intended", "actual",
                  "global", "per-dev", "flags")
        table = [header] + [
            (b.path, "x".join(map(str, b.shape)) or "()", b.dtype,
             b.intended if b.intended is not None else "-", b.actual,
             _fmt_bytes(b.global_bytes), _fmt_bytes(b.per_device_bytes),
             ",".join(b.flags) or ("replicated" if b.replicated else "-"))
            for b in rows
        ]
        lines += _align(table)
        hidden = len(self.buffers) - len(rows)
        if hidden > 0:
            lines.append(f"  ... {hidden} more unflagged buffer(s)")
        lines += ["", "collectives:"]
        if self.collectives:
            ctable = [("op", "bytes", "axes", "source", "class")] + [
                (c.op, _fmt_bytes(c.bytes),
                 ",".join(c.mesh_axes) if c.mesh_axes else "?",
                 c.source or "-",
                 "intentional" if c.intentional else "RESHARDING")
                for c in self.collectives
            ]
            lines += _align(ctable)
        else:
            lines.append("  (none)")
        lines += ["", (
            f"replicated={_fmt_bytes(self.replicated_bytes)}/dev  "
            f"intentional-comm={_fmt_bytes(self.intentional_bytes)}  "
            f"resharding-comm={_fmt_bytes(self.resharding_bytes)}  "
            f"mismatches={len(self.mismatches())}"
        )]
        return "\n".join(lines)


@dataclasses.dataclass
class MemoryReport:
    """Per-device HBM budget of one compiled program."""

    groups: Dict[str, int]        # arg-group label -> per-device bytes
    output_bytes: int             # per-device
    temp_bytes: Optional[int]     # XLA temp (activations/workspace), per-device
    peak_bytes: int               # per-device peak estimate
    source: str                   # "memory_analysis" | "shape_walk"
    hbm_limit: Optional[int]      # device bytes_limit where the backend reports it
    top: List[dict]               # largest buffers: {path, per_device_bytes, role}
    # arg-group label -> {dtype string -> per-device bytes}: the dtype
    # split of each group, so a quantized serving engine's weight and
    # KV-page drop reads straight off /debug/doctor (None on reports
    # from older artifacts)
    by_dtype: Optional[Dict[str, Dict[str, int]]] = None

    def to_json(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, d: dict) -> "MemoryReport":
        by_dtype = d.get("by_dtype")
        return cls(
            groups=dict(d["groups"]), output_bytes=int(d["output_bytes"]),
            temp_bytes=(None if d.get("temp_bytes") is None
                        else int(d["temp_bytes"])),
            peak_bytes=int(d["peak_bytes"]), source=d["source"],
            hbm_limit=(None if d.get("hbm_limit") is None
                       else int(d["hbm_limit"])),
            top=[dict(t) for t in d.get("top", [])],
            by_dtype=(None if by_dtype is None else {
                str(g): {str(k): int(v) for k, v in dd.items()}
                for g, dd in by_dtype.items()
            }),
        )

    def format_table(self) -> str:
        rows = [("group", "per-device", "of peak")]
        denom = max(self.peak_bytes, 1)
        for k, v in self.groups.items():
            label = k
            if self.by_dtype and len(self.by_dtype.get(k, {})) > 0:
                label = k + " (" + " + ".join(
                    f"{dt}:{_fmt_bytes(b)}"
                    for dt, b in sorted(self.by_dtype[k].items())
                ) + ")"
            rows.append((label, _fmt_bytes(v), f"{v / denom:6.1%}"))
        rows.append(("outputs", _fmt_bytes(self.output_bytes),
                     f"{self.output_bytes / denom:6.1%}"))
        if self.temp_bytes is not None:
            rows.append(("temp (XLA)", _fmt_bytes(self.temp_bytes),
                         f"{self.temp_bytes / denom:6.1%}"))
        lines = [f"memory budget per device ({self.source}):"]
        lines += _align(rows)
        peak = f"peak ~= {_fmt_bytes(self.peak_bytes)}/dev"
        if self.hbm_limit:
            peak += (f"  (HBM limit {_fmt_bytes(self.hbm_limit)}, "
                     f"{self.peak_bytes / self.hbm_limit:.1%})")
        lines += ["", peak, "", "largest buffers:"]
        lines += _align([("path", "per-dev", "role")] + [
            (t["path"], _fmt_bytes(t["per_device_bytes"]), t["role"])
            for t in self.top
        ])
        return "\n".join(lines)


@dataclasses.dataclass
class DoctorReport:
    """The full mesh-doctor result for one compiled program."""

    sharding: ShardingReport
    memory: MemoryReport
    # XLA cost-analysis FLOPs of the compiled (per-device, SPMD)
    # program — the planner's compute-time numerator. None where the
    # backend reports no cost analysis, and on reports deserialized
    # from artifacts written before the field existed.
    cost_flops: Optional[float] = None
    # distinct HLO instructions of the compiled module — the static
    # driver of per-step dispatch cost (a calibrated planner cost model
    # prices host/thunk dispatch per instruction; telemetry/xprof.py
    # measures the same count from its own HLO parse). None on older
    # artifacts and backends without HLO text export.
    hlo_instructions: Optional[int] = None

    def to_json(self) -> dict:
        return {"sharding": self.sharding.to_json(),
                "memory": self.memory.to_json(),
                "cost_flops": self.cost_flops,
                "hlo_instructions": self.hlo_instructions}

    @classmethod
    def from_json(cls, d: dict) -> "DoctorReport":
        # forward compat: pick known keys only — a plan/doctor artifact
        # written by a NEWER version (extra fields at any level) must
        # still load here, e.g. in the CLI's --check mode
        return cls(sharding=ShardingReport.from_json(d["sharding"]),
                   memory=MemoryReport.from_json(d["memory"]),
                   cost_flops=(None if d.get("cost_flops") is None
                               else float(d["cost_flops"])),
                   hlo_instructions=(
                       None if d.get("hlo_instructions") is None
                       else int(d["hlo_instructions"])))

    def format_table(self, max_rows: int = 32) -> str:
        return (self.sharding.format_table(max_rows=max_rows)
                + "\n\n" + self.memory.format_table())


# -- formatting helpers ----------------------------------------------------


def _fmt_bytes(n: int) -> str:
    f = float(n)
    for unit in ("B", "KiB", "MiB", "GiB"):
        if abs(f) < 1024 or unit == "GiB":
            return f"{f:.1f}{unit}" if unit != "B" else f"{int(f)}B"
        f /= 1024
    return f"{int(n)}B"


def _align(rows: Sequence[Tuple[str, ...]]) -> List[str]:
    widths = [max(len(str(r[i])) for r in rows) for i in range(len(rows[0]))]
    return ["  " + "  ".join(str(c).ljust(w) for c, w in zip(r, widths)).rstrip()
            for r in rows]


def _path_str(path) -> str:
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        elif hasattr(k, "name"):
            parts.append(str(k.name))
        else:
            parts.append(str(k))
    return "/".join(parts)


# -- spec normalization / sharding introspection ---------------------------


def _norm_spec(spec: Optional[P]) -> tuple:
    """Canonical comparable form of a PartitionSpec: single-name tuples
    unwrapped, trailing None entries stripped (``P(None, 'tensor')`` ==
    ``P(None, ('tensor',))``, ``P('data')`` == ``P('data', None)``)."""
    if spec is None:
        return ()
    entries: list = []
    for e in tuple(spec):
        if isinstance(e, (tuple, list)):
            e = tuple(e)
            if len(e) == 1:
                e = e[0]
        entries.append(e)
    while entries and entries[-1] is None:
        entries.pop()
    return tuple(entries)


def _spec_str(spec: Optional[P]) -> str:
    if spec is None:
        return "?"
    return "P(" + ", ".join(
        repr(e) if not isinstance(e, (tuple, list)) else repr(tuple(e))
        for e in _norm_spec(spec)
    ) + ")"


def _gspmd_to_spec(sharding, mesh) -> Optional[P]:
    """PartitionSpec of a compiled-program GSPMDSharding on ``mesh``
    (jit-of-shard_map programs report their input shardings in GSPMD
    form, not as NamedShardings). Best effort — None when the tiling
    doesn't decompose over the mesh."""
    try:
        from jax._src.sharding_impls import parse_flatten_op_sharding

        hlo = getattr(sharding, "_hlo_sharding", None)
        if hlo is None:
            hlo = sharding._op_sharding
        parsed = parse_flatten_op_sharding(hlo, mesh)
        return parsed[0].get_partition_spec() if parsed else None
    except Exception:  # noqa: BLE001 - private API; degrade to repr
        return None


def _sharding_info(sharding, shape, mesh=None) -> Tuple[str, Optional[P], int]:
    """(spec string, PartitionSpec or None, per-device nbytes-divisor).

    Returns the shard-count divisor instead of bytes so callers can
    apply it to the leaf's own itemsize."""
    if sharding is None:
        return "?", None, 1
    spec = getattr(sharding, "spec", None)
    if spec is None and mesh is not None:
        spec = _gspmd_to_spec(sharding, mesh)
    try:
        shard_shape = sharding.shard_shape(tuple(shape))
        denom = max(1, int(np.prod(shape)) // max(1, int(np.prod(shard_shape))))
    except Exception:  # noqa: BLE001 - uneven shapes / exotic shardings
        denom = 1
    if spec is not None:
        return _spec_str(spec), spec, denom
    name = type(sharding).__name__
    if name == "SingleDeviceSharding":
        return "single-device", None, 1
    return name, None, denom


def _equivalent(sharding, mesh, spec: P, ndim: int) -> bool:
    """Whether a compiled sharding is layout-equivalent to the intended
    PartitionSpec (catches specs that normalize differently but place
    bytes identically). False on any API failure — the spec-string
    comparison then governs."""
    try:
        return bool(sharding.is_equivalent_to(NamedSharding(mesh, spec), ndim))
    except Exception:  # noqa: BLE001
        return False


# -- collective schedule parsing -------------------------------------------


def _parse_groups(line: str) -> Optional[List[List[int]]]:
    """Device-id groups of one collective line: explicit
    ``replica_groups={{0,1},{2,3}}``, iota ``[4,2]<=[8]`` (optionally
    ``T(perm)``), or ``source_target_pairs`` (connected components of
    the permutation graph)."""
    m = _RG_EXPLICIT_RE.search(line)
    if m:
        # whitespace-tolerant: pretty-printed dumps write "{0,1}, {2,3}"
        return [
            [int(x) for x in re.findall(r"\d+", g)]
            for g in re.split(r"\}\s*,\s*\{", m.group(1).strip("{}"))
        ]
    m = _RG_IOTA_RE.search(line)
    if m:
        gshape = [int(x) for x in m.group(1).split(",")]
        dims = [int(x) for x in m.group(2).split(",")]
        ids = np.arange(int(np.prod(dims))).reshape(dims)
        if m.group(3):
            perm = [int(x) for x in m.group(3).split(",")]
            ids = ids.transpose(perm)
        return [list(map(int, row)) for row in ids.reshape(gshape)]
    m = _STP_RE.search(line)
    if m:
        pairs = [
            tuple(int(x) for x in re.findall(r"\d+", g))
            for g in re.split(r"\}\s*,\s*\{", m.group(1).strip("{}"))
        ]
        if not pairs or any(len(p) != 2 for p in pairs):
            return None
        # union-find over permutation edges: each connected component is
        # the device set the permute cycles within (= its "group")
        parent: Dict[int, int] = {}

        def find(a: int) -> int:
            parent.setdefault(a, a)
            while parent[a] != a:
                parent[a] = parent[parent[a]]
                a = parent[a]
            return a

        for a, b in pairs:
            parent[find(a)] = find(b)
        comps: Dict[int, List[int]] = {}
        for a in parent:
            comps.setdefault(find(a), []).append(a)
        return [sorted(v) for v in comps.values()]
    return None


def _groups_to_axes(
    groups: Optional[List[List[int]]], mesh_axes: Dict[str, int]
) -> Optional[Tuple[str, ...]]:
    """Smallest mesh-axis subset whose induced device partition matches
    ``groups``. Device ids are logical positions in the mesh's flat
    device order (row-major over the axis sizes), which is how
    jit-on-a-mesh numbers replica groups."""
    if not groups or not mesh_axes:
        return None
    names = list(mesh_axes)
    sizes = [mesh_axes[n] for n in names]
    n = int(np.prod(sizes))
    if max(max(g) for g in groups) >= n:
        return None
    target = {frozenset(g) for g in groups}
    coords = np.stack(np.unravel_index(np.arange(n), sizes), axis=1)
    for r in range(1, len(names) + 1):  # smallest subset wins
        for sub in itertools.combinations(range(len(names)), r):
            keep = [a for a in range(len(names)) if a not in sub]
            part: Dict[tuple, set] = {}
            for i in range(n):
                part.setdefault(tuple(coords[i, keep]), set()).add(i)
            if {frozenset(v) for v in part.values()} == target:
                return tuple(names[a] for a in sub)
    return None


def hlo_instruction_names(hlo_text: str) -> set:
    """Distinct HLO instruction names of a module's text — the join
    key between the compiled schedule and profiler trace op events
    (telemetry/xprof.py), and the static dispatch-cost driver
    (``DoctorReport.hlo_instructions``). ONE definition: the profiler
    and the planner must count with the same rule."""
    return set(_INSTR_NAME_RE.findall(hlo_text))


def _source_primitive(line: str) -> str:
    m = _OP_NAME_RE.search(line)
    if not m:
        return ""
    tail = m.group(1).split("/")[-1]
    return tail.split("[")[0].strip()


def parse_collective_schedule(
    hlo_text: str, mesh_axes: Optional[Dict[str, int]] = None
) -> List[CollectiveInfo]:
    """Per-instruction collective table of an HLO module: op, payload
    bytes, the mesh axes its replica groups span (when resolvable
    against ``mesh_axes``), the source jax primitive from the metadata,
    and the intentional/resharding classification."""
    out = []
    for c in iter_collectives(hlo_text):
        src = _source_primitive(c["line"])
        try:  # axes are advisory — a malformed group never aborts the run
            axes = _groups_to_axes(_parse_groups(c["line"]), mesh_axes or {})
        except (ValueError, IndexError):
            axes = None
        nm = _INSTR_NAME_RE.match(c["line"])
        out.append(CollectiveInfo(
            op=c["op"],
            bytes=c["bytes"],
            mesh_axes=axes,
            source=src,
            intentional=src in INTENTIONAL_PRIMITIVES,
            name=nm.group(1) if nm else "",
        ))
    return out


# -- intended-spec alignment -----------------------------------------------


def _intended_by_path(args: tuple, intended: Optional[tuple]) -> Dict[str, P]:
    """{leaf path -> intended PartitionSpec} for the args tuple.

    ``intended`` aligns positionally with ``args``; each entry is None
    (no intent), a single PartitionSpec (broadcast over every leaf of
    that arg), or a pytree of PartitionSpecs structurally matching the
    arg (leaf paths are matched individually, so a partial tree simply
    leaves the unmatched leaves un-diffed)."""
    out: Dict[str, P] = {}
    if intended is None:
        return out
    for i, spec_i in enumerate(intended):
        if spec_i is None:
            continue
        if isinstance(spec_i, P):
            for path, _ in jax.tree_util.tree_leaves_with_path(args[i]):
                out[f"{i}/{_path_str(path)}".rstrip("/")] = spec_i
            continue
        for path, leaf in jax.tree_util.tree_leaves_with_path(
            spec_i, is_leaf=lambda x: isinstance(x, P)
        ):
            if isinstance(leaf, P):
                out[f"{i}/{_path_str(path)}".rstrip("/")] = leaf
    return out


# -- the inspector ---------------------------------------------------------


def diagnose(
    fn: Any,
    *args: Any,
    intended: Optional[tuple] = None,
    labels: Optional[Sequence[str]] = None,
    mesh: Any = None,
    large_bytes: int = 1 << 20,
) -> DoctorReport:
    """Lower+compile ``fn`` at these arg shapes (ShapeDtypeStructs are
    fine — nothing executes) and inspect the compiled partitioning plan.

    ``fn`` may be a jitted function (its donation/sharding settings are
    kept) or a plain callable (wrapped in ``jax.jit``). ``intended``
    aligns with ``args`` (see :func:`_intended_by_path`); ``labels``
    names each positional arg in report paths (default ``arg0``...).
    ``large_bytes`` is the threshold above which a replicated buffer is
    flagged as a problem rather than noise."""
    jfn = fn if hasattr(fn, "lower") else jax.jit(fn)
    lowered = jfn.lower(*args)
    compiled = lowered.compile()

    # one entry per positional arg, each a PYTREE of shardings (bare
    # arrays give a flat tuple, container args give containers): flatten
    # the whole structure — sharding objects are pytree leaves
    in_shardings = jax.tree_util.tree_leaves(compiled.input_shardings[0])
    out_sh_leaves = jax.tree_util.tree_leaves(compiled.output_shardings)
    arg_leaves = jax.tree_util.tree_leaves_with_path(args)
    labels = list(labels) if labels is not None else [
        f"arg{i}" for i in range(len(args))
    ]

    # donated flags, leaf-aligned with args (best effort across versions)
    donated: List[bool] = []
    try:
        donated = [bool(getattr(a, "donated", False))
                   for a in jax.tree_util.tree_leaves(
                       lowered.args_info,
                       is_leaf=lambda x: hasattr(x, "donated"))]
    except Exception:  # noqa: BLE001
        donated = []
    if len(donated) != len(arg_leaves):
        donated = [False] * len(arg_leaves)

    # mesh: explicit > first NamedSharding seen (outputs included —
    # jit-of-shard_map reports GSPMD input shardings but Named outputs)
    if mesh is None:
        for s in list(in_shardings) + list(out_sh_leaves):
            if isinstance(s, NamedSharding):
                mesh = s.mesh
                break
    mesh_axes = (
        {str(k): int(v) for k, v in dict(mesh.shape).items()}
        if mesh is not None else {}
    )
    n_devices = int(np.prod(list(mesh_axes.values()))) if mesh_axes else 1

    intent = _intended_by_path(args, intended)
    aligned = len(in_shardings) == len(arg_leaves)

    def _leaf_path(i, path) -> str:
        if not aligned:
            return f"input[{i}]"
        first = path[0]
        idx = getattr(first, "idx", None)
        prefix = labels[idx] if idx is not None and idx < len(labels) else str(idx)
        rest = _path_str(path[1:])
        return f"{prefix}/{rest}" if rest else prefix

    buffers: List[BufferInfo] = []
    for i, (path, leaf) in enumerate(arg_leaves):
        sharding = in_shardings[i] if i < len(in_shardings) else None
        shape = tuple(getattr(leaf, "shape", ()))
        dtype = np.dtype(getattr(leaf, "dtype", np.float32))
        gbytes = int(np.prod(shape)) * dtype.itemsize if shape else dtype.itemsize
        actual_str, actual_spec, denom = _sharding_info(sharding, shape, mesh)
        pbytes = gbytes // max(denom, 1)
        replicated = n_devices > 1 and (
            bool(getattr(sharding, "is_fully_replicated", denom == 1))
            if sharding is not None else denom == 1
        )
        spec_want = None
        if aligned and hasattr(path[0], "idx"):
            key = f"{path[0].idx}/{_path_str(path[1:])}".rstrip("/")
            spec_want = intent.get(key)
        flags: List[str] = []
        if spec_want is not None and sharding is not None:
            differs = (actual_spec is None
                       or _norm_spec(spec_want) != _norm_spec(actual_spec))
            if differs and (mesh is None or not _equivalent(
                    sharding, mesh, spec_want, len(shape))):
                flags.append("mismatch")
        if replicated and gbytes >= large_bytes:
            if spec_want is not None and _norm_spec(spec_want) != ():
                flags.append("replicated_large")
            else:
                flags.append("unsharded_large")
        buffers.append(BufferInfo(
            path=_leaf_path(i, path), shape=shape, dtype=str(dtype),
            actual=actual_str,
            intended=_spec_str(spec_want) if spec_want is not None else None,
            global_bytes=gbytes, per_device_bytes=pbytes,
            replicated=replicated,
            role="donated input" if donated[i] else "input",
            flags=flags,
        ))

    # outputs: shardings from the compiled object; avals from the
    # lowering (out_info), falling back to a re-trace only when the jax
    # version lacks it — diagnose stays ONE trace+compile
    out_bytes_per_device = 0
    try:
        out_avals = getattr(lowered, "out_info", None)
        if out_avals is None:
            out_avals = jax.eval_shape(jfn, *args)
        out_leaves = jax.tree_util.tree_leaves_with_path(out_avals)
        if len(out_sh_leaves) == len(out_leaves):
            for (path, leaf), sharding in zip(out_leaves, out_sh_leaves):
                shape = tuple(leaf.shape)
                dtype = np.dtype(leaf.dtype)
                gbytes = (int(np.prod(shape)) * dtype.itemsize
                          if shape else dtype.itemsize)
                actual_str, _, denom = _sharding_info(sharding, shape, mesh)
                pbytes = gbytes // max(denom, 1)
                out_bytes_per_device += pbytes
                p = _path_str(path)
                buffers.append(BufferInfo(
                    path=f"out/{p}" if p else "out", shape=shape,
                    dtype=str(dtype), actual=actual_str, intended=None,
                    global_bytes=gbytes, per_device_bytes=pbytes,
                    replicated=n_devices > 1 and denom == 1,
                    role="output", flags=[],
                ))
    except Exception:  # noqa: BLE001 - outputs are advisory
        pass

    # collective schedule from the compiled HLO
    try:
        hlo = compiled.as_text()
    except Exception:  # noqa: BLE001 - backends without HLO text export
        hlo = ""
    collectives = parse_collective_schedule(hlo, mesh_axes)

    sharding_report = ShardingReport(
        mesh_axes=mesh_axes, n_devices=n_devices,
        buffers=buffers, collectives=collectives,
    )

    # -- memory budget -----------------------------------------------------
    groups: Dict[str, int] = {}
    by_dtype: Dict[str, Dict[str, int]] = {}
    for b in buffers:
        if b.role == "output":
            continue
        label = b.path.split("/")[0]
        groups[label] = groups.get(label, 0) + b.per_device_bytes
        dd = by_dtype.setdefault(label, {})
        dd[b.dtype] = dd.get(b.dtype, 0) + b.per_device_bytes
    temp = peak = None
    source = "shape_walk"
    try:
        ma = compiled.memory_analysis()
        if ma is not None and getattr(ma, "temp_size_in_bytes", None) is not None:
            temp = int(ma.temp_size_in_bytes)
            # argument + output + temp - alias is XLA's own budget view;
            # aliased (donated) outputs don't double-count
            peak = int(ma.argument_size_in_bytes + ma.output_size_in_bytes
                       + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
            source = "memory_analysis"
    except Exception:  # noqa: BLE001
        pass
    if peak is None:
        donated_bytes = sum(b.per_device_bytes for b in buffers
                            if b.role == "donated input")
        peak = (sum(groups.values()) + out_bytes_per_device - donated_bytes)
        peak = max(peak, sum(groups.values()))
    hbm_limit = None
    try:
        from pipegoose_tpu.utils.profiler import device_memory_stats

        dev = (mesh.devices.reshape(-1)[0] if mesh is not None
               else jax.devices()[0])
        lim = device_memory_stats(dev).get("bytes_limit")
        hbm_limit = int(lim) if lim else None
    except Exception:  # noqa: BLE001
        pass
    top = [
        {"path": b.path, "per_device_bytes": b.per_device_bytes, "role": b.role}
        for b in sorted(buffers, key=lambda b: -b.per_device_bytes)[:10]
    ]
    memory_report = MemoryReport(
        groups=groups, output_bytes=out_bytes_per_device, temp_bytes=temp,
        peak_bytes=int(peak), source=source, hbm_limit=hbm_limit, top=top,
        by_dtype=by_dtype,
    )
    cost_flops = None
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        f = dict(ca or {}).get("flops")
        cost_flops = float(f) if f is not None else None
    except Exception:  # noqa: BLE001 - cost analysis is advisory
        pass
    n_instr = len(hlo_instruction_names(hlo)) if hlo else None
    return DoctorReport(sharding=sharding_report, memory=memory_report,
                        cost_flops=cost_flops,
                        hlo_instructions=n_instr or None)


# -- wire-byte estimation --------------------------------------------------


def estimated_wire_bytes(
    collective: CollectiveInfo, mesh_axes: Dict[str, int]
) -> int:
    """Per-device TRANSMITTED bytes of one collective, normalized across
    the ops' differing payload conventions (``CollectiveInfo.bytes`` is
    the instruction's OUTPUT bytes: a reduce-scatter reports its shard
    while an all-to-all reports the full array, so raw payloads cannot
    be compared across op kinds). Ring-algorithm estimates over the
    group size ``g`` spanned by the collective's mesh axes:

    - ``all-gather``: output is the full array; each device sends its
      shard ``g-1`` times interleaved -> ``(g-1)/g x bytes``.
    - ``reduce-scatter``: output is the shard; each device forwards a
      shard per hop for ``g-1`` hops -> ``(g-1) x bytes``.
    - ``all-reduce``: reduce-scatter + all-gather ->
      ``2(g-1)/g x bytes`` of the full-array output.
    - ``all-to-all``: each device keeps 1/g of the (full-array) output
      -> ``(g-1)/g x bytes``.
    - ``collective-permute``: one hop, ``bytes``.

    The comm-engine tests use this to compare the fp32 reduce-scatter
    gradient phase against its quantized all-to-all replacement on
    equal footing (docs/comm.md)."""
    g = 1
    for ax in collective.mesh_axes or ():
        g *= int(mesh_axes.get(ax, 1))
    if g <= 1:
        return 0
    b = collective.bytes
    op = collective.op
    if op == "all-gather":
        return b * (g - 1) // g
    if op == "reduce-scatter":
        return b * (g - 1)
    if op == "all-reduce":
        return 2 * b * (g - 1) // g
    if op == "all-to-all":
        return b * (g - 1) // g
    return b  # collective-permute and friends: one hop of the payload


def wire_bytes_by_axes(report: Any) -> Dict[Tuple[str, ...], int]:
    """{mesh-axes tuple -> estimated per-device wire bytes} over a
    report's collective schedule — the planner's comm-time numerator,
    grouped by the fabric each axis group rides (ICI vs DCI). A
    collective whose replica groups resolved to no axis subset lands
    under the empty tuple ``()`` at its one-hop payload bytes
    (``estimated_wire_bytes`` needs a group size and would report 0),
    so unattributed traffic stays visible, never silently dropped."""
    sr = _sharding_of(report)
    out: Dict[Tuple[str, ...], int] = {}
    for c in sr.collectives:
        if c.mesh_axes:
            key = tuple(c.mesh_axes)
            nbytes = estimated_wire_bytes(c, sr.mesh_axes)
        else:
            key, nbytes = (), c.bytes
        out[key] = out.get(key, 0) + nbytes
    return out


def wire_bytes_by_op(
    report: Any, axes: Optional[Tuple[str, ...]] = None
) -> Dict[str, int]:
    """{op -> estimated per-device wire bytes} over a report's
    collective schedule, optionally restricted to collectives spanning
    exactly ``axes`` — e.g. ``wire_bytes_by_op(rep, ("data",))`` is the
    gradient/optimizer traffic of a hybrid step."""
    sr = _sharding_of(report)
    out: Dict[str, int] = {}
    for c in sr.collectives:
        if axes is not None and c.mesh_axes != tuple(axes):
            continue
        out[c.op] = out.get(c.op, 0) + estimated_wire_bytes(c, sr.mesh_axes)
    return out


# -- telemetry gauges ------------------------------------------------------


def set_doctor_gauges(report: Any, registry: Any = None) -> None:
    """Land the report's headline numbers as gauges next to MFU:
    ``doctor.replicated_bytes``, ``doctor.resharding_bytes``,
    ``doctor.intentional_bytes``, ``doctor.hbm_peak_bytes``."""
    from pipegoose_tpu.telemetry.registry import get_registry

    reg = registry if registry is not None else get_registry()
    sr = getattr(report, "sharding", report)
    reg.gauge("doctor.replicated_bytes").set(float(sr.replicated_bytes))
    reg.gauge("doctor.resharding_bytes").set(float(sr.resharding_bytes))
    reg.gauge("doctor.intentional_bytes").set(float(sr.intentional_bytes))
    mem = getattr(report, "memory", None)
    if mem is not None:
        reg.gauge("doctor.hbm_peak_bytes").set(float(mem.peak_bytes))


# -- regression guards -----------------------------------------------------


class ShardingRegressionError(AssertionError):
    """A compiled program's partitioning plan violates a doctor guard."""


def _sharding_of(report: Any) -> ShardingReport:
    return getattr(report, "sharding", report)


def assert_no_resharding(report: Any, allow: Sequence[str] = ()) -> None:
    """Fail if GSPMD inserted any collective the user didn't write.

    ``allow`` is a list of fnmatch patterns matched against the
    collective's op name (``all-gather``), its source primitive
    (``dot_general``), and ``op:source`` — e.g.
    ``allow=["all-reduce:dot_general"]`` tolerates the partial-sum
    reductions of sharded matmuls while still pinning gathers."""
    sr = _sharding_of(report)
    bad = [
        c for c in sr.resharding_collectives
        if not any(
            fnmatch(c.op, pat) or fnmatch(c.source or "", pat)
            or fnmatch(f"{c.op}:{c.source}", pat)
            for pat in allow
        )
    ]
    if bad:
        rows = "\n".join(
            f"  {c.op}  {_fmt_bytes(c.bytes)}  "
            f"axes={','.join(c.mesh_axes) if c.mesh_axes else '?'}  "
            f"source={c.source or '-'}"
            for c in bad
        )
        raise ShardingRegressionError(
            f"{len(bad)} unintended (partitioner-inserted) collective(s) "
            f"in the compiled program — a PartitionSpec no longer lines up "
            f"with the dataflow:\n{rows}"
        )


def assert_fully_sharded(
    report: Any, min_bytes: int = 1 << 20, allow: Sequence[str] = ()
) -> None:
    """Fail if any input buffer of at least ``min_bytes`` is fully
    replicated across a multi-device mesh. ``allow`` holds fnmatch
    patterns over buffer paths (e.g. ``["params/*/ln*", "batch*"]``)."""
    sr = _sharding_of(report)
    bad = [
        b for b in sr.buffers
        if b.role != "output" and b.replicated and b.global_bytes >= min_bytes
        and not any(fnmatch(b.path, pat) for pat in allow)
    ]
    if bad:
        rows = "\n".join(
            f"  {b.path}  {'x'.join(map(str, b.shape))}  "
            f"{_fmt_bytes(b.global_bytes)} replicated "
            f"(intended {b.intended or '-'}, actual {b.actual})"
            for b in bad
        )
        raise ShardingRegressionError(
            f"{len(bad)} buffer(s) >= {_fmt_bytes(min_bytes)} are fully "
            f"replicated across {sr.n_devices} devices:\n{rows}"
        )


def assert_matches_intended(report: Any, allow: Sequence[str] = ()) -> None:
    """Fail if any buffer's actual sharding differs from its intended
    PartitionSpec. ``allow``: fnmatch patterns over buffer paths."""
    sr = _sharding_of(report)
    bad = [b for b in sr.mismatches()
           if not any(fnmatch(b.path, pat) for pat in allow)]
    if bad:
        rows = "\n".join(
            f"  {b.path}: intended {b.intended} != actual {b.actual}"
            for b in bad
        )
        raise ShardingRegressionError(
            f"{len(bad)} sharding mismatch(es) between intended "
            f"PartitionSpecs and the compiled program:\n{rows}"
        )


def _json_default(o: Any):
    if hasattr(o, "to_json"):
        return o.to_json()
    raise TypeError(f"not JSON-serializable: {type(o)}")


def report_json_dumps(report: Any, **kwargs: Any) -> str:
    """``json.dumps`` for reports and dicts containing them."""
    return json.dumps(report, default=_json_default, **kwargs)
