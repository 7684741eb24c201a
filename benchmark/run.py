"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in ``BENCHMARK.json``, its configuration and workload
files and its driver by name, refuses to run without the TPU chips the
cell asks for, and prints as its last line one JSON object: the cell's
end-to-end metrics (``--trace 0``) or its per-layer metrics
(``--trace 1``). See ``benchmark/README.md``.
"""
from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def require_devices(chips: int) -> list:
    """The TPU chips the cell asks for, or no run at all."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"benchmark: no TPU (jax found "
                         f"{devices[0].platform} x {len(devices)}); "
                         f"nothing was run")
    if len(devices) < chips:
        raise SystemExit(f"benchmark: the cell needs {chips} chips, jax "
                         f"found {len(devices)}; nothing was run")
    return devices[:chips]


def _json_number(x) -> bool:
    """``NaN`` and the infinities are not JSON."""
    return isinstance(x, (int, float)) and math.isfinite(x)


def layer_metrics(spec: dict, cell: str, result, here: str) -> dict:
    from benchmark import harness

    out = {}
    for m in harness.metrics_for(spec["per_layer"], cell):
        reader = harness.load_module(
            os.path.join(here, "layer_metrics", m["name"] + ".py"))
        value = reader.read(result)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def open_cell(root: str, name: str, seed: int, seconds: float, trace: bool):
    """Everything a run needs before its driver starts: the cell's files
    by name, the compilation cache, the chips (or no run), a context.
    Returns (spec, driver module, context, here)."""
    here = os.path.join(root, "benchmark")
    for p in (root, ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    # a fixed path inside the checkout: the path is part of the cache's
    # key. JAX reads the variable itself; where it is set, it wins.
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(root, ".jax_cache"))
    from benchmark import harness

    spec = harness.load_json(os.path.join(root, "BENCHMARK.json"))
    cell, config, workload = harness.find_cell(spec, name, here)

    import jax

    # every program goes to the cache, the small ones too: a warm run
    # compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = require_devices(cell["chips"])
    # the chip has answered: what follows is the program's own set-up.
    # Nothing of pipegoose_tpu is imported yet (the drivers do that).
    t_chip = time.perf_counter()

    from benchmark import rooflines
    from benchmark.compile_watch import CompileWatch

    ctx = harness.Context(
        cell=cell, config=config, workload=workload, seed=seed,
        seconds=seconds, trace=trace, devices=devices, t_chip=t_chip,
        peaks=rooflines.peaks_for(devices[0].device_kind),
        watch=CompileWatch().install(), checks=harness.Checks())
    driver = harness.load_module(
        os.path.join(here, "drivers", workload["driver"] + ".py"))
    return spec, driver, ctx, here


def main(argv=None, root: str = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec, driver, ctx, here = open_cell(root, args.workload, args.seed,
                                        args.seconds, bool(args.trace))
    from benchmark import harness

    cell, workload, devices = ctx.cell, ctx.workload, ctx.devices
    result = driver.run(ctx)

    for row in ctx.checks.rows:
        print("check " + json.dumps(row), flush=True)
    # Python, ``import jax`` and the backend coming up: the machine's, a
    # fact that nothing judges. ``setup_s`` starts where this ends.
    process_start_s = ctx.t_chip - T_PROCESS_START
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": result.memory_peak_bytes}
    line = {"correct": ctx.checks.correct, "attempted": result.attempted,
            "failed": result.failed, **result.extra,
            "process_start_s": process_start_s, "device": device}
    if ctx.trace:
        result.trace = ctx.trace_summary
        device["busy_s"] = result.trace["busy_s"]
        device["window_s"] = result.trace["window_s"]
        line["metrics"] = layer_metrics(spec, cell["name"], result, here)
        line["breakdown"] = result.trace["breakdown"]
    else:
        e2e = dict(result.end_to_end)
        e2e["setup_s"] = result.t_window_start - ctx.t_chip
        line["metrics"] = {}
        for m in harness.metrics_for(spec["end_to_end"], cell["name"]):
            if m["name"] not in e2e:
                raise SystemExit(f"benchmark: driver {workload['driver']!r} "
                                 f"did not report {m['name']!r}")
            line["metrics"][m["name"]] = {"value": float(e2e[m["name"]]),
                                          "unit": m["unit"]}
    # each number compared beside its limit: last on the line, and the
    # last lines on standard error
    line["checks"] = {
        r["name"]: {"value": r["value"] if _json_number(r["value"]) else None,
                    "limit": r["limit"]} for r in ctx.checks.rows}
    for r in ctx.checks.rows:
        print(f"check {r['name']} {r['value']} limit {r['limit']} "
              f"{'ok' if r['ok'] else 'NOT OK'}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
