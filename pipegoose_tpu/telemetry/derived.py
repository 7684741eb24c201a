"""Derived gauges: MFU, tokens/s, HBM occupancy, per-step comm bytes.

The GSPMD / Mesh-TensorFlow lineage (arxiv 2105.04663, 1811.02084)
treats the COMPILER's cost model as the ground truth for utilization on
TPU: XLA already knows the per-step FLOPs and every collective it
emitted. This module turns those into operator-facing numbers:

- ``mfu``: achieved model-FLOPs utilization from ``compiled_cost``
  FLOPs (utils/profiler.py) against the per-device peak-FLOPs table;
- ``compiled_step_stats``: ONE lower+compile yielding flops, bytes
  accessed, AND per-collective communication bytes parsed from the
  compiled HLO (all-reduce / all-gather / reduce-scatter / all-to-all /
  collective-permute output shapes);
- ``hbm_utilization``: live HBM occupancy from ``device_memory_stats``
  (empty off-TPU — CPU devices report no memory stats).

``PEAK_FLOPS`` is the library's one table of per-chip peak bf16
FLOP/s (``TelemetryCallback``'s MFU and the planner's cost model read
it).
"""
from __future__ import annotations

import re
from typing import Any, Callable, Dict, Optional

import jax

from pipegoose_tpu.utils.profiler import compiled_cost, device_memory_stats

# per-chip peak bf16 FLOP/s (the MFU denominator; docs/observability.md
# documents the sources). "cpu" is a nominal placeholder so CPU smoke
# runs produce a finite, clearly-not-real number.
PEAK_FLOPS: Dict[str, float] = {
    "v5 lite": 197e12,  # v5e
    "v5e": 197e12,
    "v5p": 459e12,
    "v6 lite": 918e12,  # v6e (Trillium)
    "v6e": 918e12,
    "v4": 275e12,
    "cpu": 1e12,
}

# Peer tables to PEAK_FLOPS: per-chip interconnect bandwidth and HBM
# capacity — the denominators of the parallelism planner's static cost
# model (pipegoose_tpu/planner/). ICI is the intra-slice fabric every
# mesh axis rides by default; DCI is the data-center network a
# cross-slice axis (e.g. the DiLoCo outer loop) pays instead. Aggregate
# per-chip numbers from the public TPU system specs (ICI Gbps -> B/s);
# "cpu" rows are nominal placeholders so fake-device planning yields
# finite, clearly-not-real times with the same code path.
PEAK_ICI_BYTES: Dict[str, float] = {
    "v5 lite": 200e9,   # v5e: 1600 Gbps aggregate
    "v5e": 200e9,
    "v5p": 600e9,       # 4800 Gbps
    "v6 lite": 448e9,   # v6e: 3584 Gbps
    "v6e": 448e9,
    "v4": 300e9,        # 2400 Gbps
    "cpu": 10e9,
}

PEAK_DCI_BYTES: Dict[str, float] = {
    "v5 lite": 25e9,
    "v5e": 25e9,
    "v5p": 25e9,
    "v6 lite": 25e9,
    "v6e": 25e9,
    "v4": 25e9,
    "cpu": 1e9,
}

HBM_BYTES: Dict[str, float] = {
    "v5 lite": 16 * 1024**3,
    "v5e": 16 * 1024**3,
    "v5p": 95 * 1024**3,
    "v6 lite": 32 * 1024**3,
    "v6e": 32 * 1024**3,
    "v4": 32 * 1024**3,
    "cpu": 16 * 1024**3,
}

# Per-chip HBM BANDWIDTH (B/s, public system specs) — the denominator
# of the serving decode-layout cost model (planner/serving.py): a
# batch-1-per-slot decode step is memory-bound, so its floor is
# (resident weights + KV read) / this number. The quantized-inference
# win is exactly a smaller numerator here.
HBM_BW_BYTES: Dict[str, float] = {
    "v5 lite": 819e9,
    "v5e": 819e9,
    "v5p": 2765e9,
    "v6 lite": 1640e9,
    "v6e": 1640e9,
    "v4": 1228e9,
    "cpu": 50e9,
}

# mesh axes that ride the data-center network instead of ICI — the ONE
# definition both the planner cost model (CostModel.dci_axes default)
# and the measured fabric-utilization attribution (telemetry/xprof.py)
# key their bandwidth choice on. "diloco" is the cross-slice outer
# loop (optim/diloco.py).
DCI_AXES: tuple = ("diloco",)


def _kind_lookup(table: Dict[str, float], device_kind: Optional[str],
                 table_name: str) -> float:
    """Substring match of a device-kind string against a spec table. A
    kind no row matches is an error: a default would score a typo'd
    --device-kind, or a chip nobody has entered, against another
    machine's numbers."""
    if device_kind is None:
        dev = jax.devices()[0]
        device_kind = getattr(dev, "device_kind", dev.platform)
    kind = device_kind.lower()
    for k, v in table.items():
        if k in kind:
            return v
    raise ValueError(
        f"unknown device kind {device_kind!r}: no row of "
        f"telemetry.derived.{table_name} matches (rows: {sorted(table)}) "
        f"— add the chip's published number to the table"
    )


def peak_flops_for(device_kind: Optional[str] = None) -> float:
    """Peak FLOP/s for a device-kind string (substring match);
    defaults to the first visible device. An
    unknown kind raises, here and in the peer lookups below."""
    return _kind_lookup(PEAK_FLOPS, device_kind, "PEAK_FLOPS")


def ici_bytes_per_s_for(device_kind: Optional[str] = None) -> float:
    """Per-chip intra-slice interconnect bandwidth (B/s) for a
    device-kind string; defaults to the first visible device."""
    return _kind_lookup(PEAK_ICI_BYTES, device_kind, "PEAK_ICI_BYTES")


def dci_bytes_per_s_for(device_kind: Optional[str] = None) -> float:
    """Per-chip cross-slice (data-center network) bandwidth (B/s)."""
    return _kind_lookup(PEAK_DCI_BYTES, device_kind, "PEAK_DCI_BYTES")


def hbm_bytes_for(device_kind: Optional[str] = None) -> float:
    """Per-chip HBM capacity (bytes) from the spec table — the planner's
    feasibility budget where the backend reports no live ``bytes_limit``
    (fake CPU devices report none)."""
    return _kind_lookup(HBM_BYTES, device_kind, "HBM_BYTES")


def hbm_bw_bytes_per_s_for(device_kind: Optional[str] = None) -> float:
    """Per-chip HBM bandwidth (B/s) — the memory-bound decode cost
    model's denominator (planner/serving.py)."""
    return _kind_lookup(HBM_BW_BYTES, device_kind, "HBM_BW_BYTES")


def mfu(flops_per_step: float, step_seconds: float,
        device_kind: Optional[str] = None, peak: Optional[float] = None,
        n_devices: int = 1) -> float:
    """Achieved / peak FLOP/s. ``flops_per_step`` is the WHOLE step's
    model FLOPs (e.g. XLA's cost analysis of the jitted step);
    ``n_devices`` divides the peak pool it ran against."""
    if step_seconds <= 0:
        return 0.0
    peak = peak if peak is not None else peak_flops_for(device_kind)
    return flops_per_step / step_seconds / (peak * max(n_devices, 1))


def tokens_per_second(tokens: float, seconds: float) -> float:
    return tokens / seconds if seconds > 0 else 0.0


def hbm_utilization(device: Optional[Any] = None) -> dict:
    """{"bytes_in_use", "bytes_limit", "utilization"} from the device's
    live memory stats; {} where the backend reports none (CPU)."""
    stats = device_memory_stats(device)
    used = stats.get("bytes_in_use")
    limit = stats.get("bytes_limit") or stats.get("bytes_reservable_limit")
    if used is None:
        return {}
    out = {"bytes_in_use": int(used)}
    if limit:
        out["bytes_limit"] = int(limit)
        out["utilization"] = used / limit
    return out


# -- communication accounting from compiled HLO ---------------------------

_ITEMSIZE = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

# "f32[8,128]" with optional layout suffix "{1,0}"
_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")

_RESULT_RE = {
    # "%name = f32[8,16]{1,0} all-reduce(..." — shape(s) sit between '='
    # and the op name; a "-done" suffix never matches (its result
    # duplicates the "-start" tuple's output and must not count twice)
    op: re.compile(rf"=\s*(.*?)\s{op}(-start)?\(") for op in _COLLECTIVES
}


def _atom_bytes(dtype: str, dims: str) -> Optional[int]:
    size = _ITEMSIZE.get(dtype)
    if size is None:
        return None  # token/opaque types carry no payload
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * size


def _shape_bytes_list(shape_part: str) -> list:
    out = []
    for dtype, dims in _SHAPE_RE.findall(shape_part):
        b = _atom_bytes(dtype, dims)
        if b is not None:
            out.append(b)
    return out


def _shape_bytes(shape_part: str) -> int:
    return sum(_shape_bytes_list(shape_part))


def _split_top_level(shape_part: str) -> list:
    """Split a result-shape string into its TOP-LEVEL tuple elements,
    respecting nesting: ``"((f32[8], u8[2]), (f32[2]), u32[])"`` ->
    ``["(f32[8], u8[2])", "(f32[2])", "u32[]"]``. A non-tuple shape
    comes back as a single element. Layout braces (``{1,0}``) carry no
    parens, so only ``(``/``)`` depth matters."""
    s = shape_part.strip()
    if not s.startswith("("):
        return [s]
    body = s[1:s.rfind(")")] if ")" in s else s[1:]
    # dims ("[2,8]") and layouts ("{1,0}") hold commas too — only a
    # comma at depth 0 across ALL bracket kinds separates tuple elements
    elems, depth, start = [], 0, 0
    for i, ch in enumerate(body):
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        elif ch == "," and depth == 0:
            elems.append(body[start:i].strip())
            start = i + 1
    tail = body[start:].strip()
    if tail:
        elems.append(tail)
    return elems


def _async_start_bytes(shape_part: str) -> int:
    """Output payload of an async ``-start`` result tuple.

    Two printed forms exist:

    - nested (variadic): ``((operands...), (outputs...), [contexts])``
      — the LAST nested tuple is the output buffer set; sum exactly it.
    - flat: ``(operand..., output..., [context scalars])`` — strip the
      trailing scalar contexts (<= 8 bytes, e.g. the u32[] slots of
      collective-permute-start), then take the SECOND half — the output
      buffers. Correct for asymmetric collectives too (all-gather
      output > input, reduce-scatter output < input), where halving the
      summed tuple would miscount.
    """
    elems = _split_top_level(shape_part)
    nested = [e for e in elems if e.startswith("(")]
    if nested:
        return _shape_bytes(nested[-1])
    shapes = _shape_bytes_list(shape_part)
    while len(shapes) > 2 and shapes[-1] <= 8:
        shapes.pop()
    if len(shapes) < 2:
        return sum(shapes)  # unexpected non-tuple form: count as-is
    return sum(shapes[len(shapes) // 2:])


def _sync_bytes(shape_part: str) -> int:
    """Payload of a SYNC collective result: every top-level element is
    an output buffer (tuple-shaped variadic reduce-scatter /
    collective-permute included) EXCEPT trailing scalar context slots,
    which some permute forms keep even in the sync printing."""
    elems = _split_top_level(shape_part)
    sizes = [_shape_bytes(e) for e in elems]
    while len(sizes) > 1 and sizes[-1] <= 8 and elems[-1].startswith("u32"):
        sizes.pop()
        elems.pop()
    return sum(sizes)


def iter_collectives(hlo_text: str):
    """Yield one ``{"op", "bytes", "start", "line"}`` dict per
    collective instruction in an HLO module's text (async ``-done``
    halves skipped). The line-level form telemetry/doctor.py builds its
    schedule table on; ``collective_bytes`` is the aggregate view."""
    for line in hlo_text.splitlines():
        for op in _COLLECTIVES:
            m = _RESULT_RE[op].search(line)
            if m:
                start = bool(m.group(2))
                nbytes = (_async_start_bytes(m.group(1)) if start
                          else _sync_bytes(m.group(1)))
                yield {"op": op, "bytes": nbytes, "start": start,
                       "line": line}
                break


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-collective output bytes summed over an HLO module's text:
    {"all-reduce": N, ..., "total": M}. Output-shape bytes are the
    standard proxy for wire traffic (exact for all-reduce/all-gather
    payloads; a ring all-reduce moves ~2x on the wire — this counts the
    logical payload, the per-algorithm constant is the reader's)."""
    out = {k: 0 for k in _COLLECTIVES}
    for c in iter_collectives(hlo_text):
        out[c["op"]] += c["bytes"]
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    return out


def compiled_step_stats(fn: Callable, *args, **kwargs) -> dict:
    """ONE lower+compile of ``jit(fn)`` at these arg shapes, returning
    {"flops", "bytes_accessed", "comm_bytes", "comm_by_op"} — the
    compiler-ground-truth inputs to the MFU and comms gauges."""
    lowered = jax.jit(fn).lower(*args, **kwargs)
    compiled = lowered.compile()
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    ca = dict(ca or {})
    try:
        comm = collective_bytes(compiled.as_text())
    except Exception:  # noqa: BLE001 - backends without HLO text export
        comm = {"total": 0}
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
        "comm_bytes": int(comm.get("total", 0)),
        "comm_by_op": {k: v for k, v in comm.items()
                       if k != "total" and v},
    }


def step_flops(fn: Callable, *args, **kwargs) -> float:
    """XLA-reported FLOPs of one call of ``jit(fn)`` (compiled_cost
    sugar for the common MFU input)."""
    return float(compiled_cost(fn, *args, **kwargs).get("flops", 0.0))
