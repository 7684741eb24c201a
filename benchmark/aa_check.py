"""Run one cell again and again on one tree and say whether each of its
end-to-end metrics could tell "unchanged" from a change.

    python3 benchmark/aa_check.py --workload <cell> --seeds 1,2,3,4,5,6 \\
        --seconds 30

Runs ``run.py`` once a seed, each in a child process of its own, in
turn; this process never imports JAX, so it never holds the chip. For
every end-to-end metric of the cell it prints the values, the median,
two spreads, the bound and whether the bound ``resolves`` (``spread``
<= bound / 2):

* ``spread``: the range over the median, leaving out the run farthest
  from the median: how the driver's check reckons whether a difference
  can be told;
* ``quartile_spread``: the distance between the first and the third
  quartile (``statistics.quantiles(values, n=4)``) over the median: what
  a bound is set from (about five times it, never under 0.01).

``process_start_s`` (a fact on the result line, judged by nothing) and
its sum with ``setup_s`` are reported the same way, with no bound. A
tool for the PR that sets a bound, and for whoever doubts a difference;
the benchmark's own runs never call it.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FACT_LINES = ("serve ", "train ", "setup ", "check ")


def spread(values) -> float | None:
    """Range over median with the run farthest from the median left out
    (where two are equally far, the one whose going narrows it more).
    Two values: their range over their median. Fewer: nothing to say."""
    xs = sorted(float(v) for v in values)
    if len(xs) < 2:
        return None
    med = statistics.median(xs)
    if len(xs) == 2:
        return (xs[1] - xs[0]) / abs(med)
    far = max(abs(x - med) for x in xs)
    rests = [xs[:i] + xs[i + 1:] for i, x in enumerate(xs)
             if abs(x - med) == far]
    return min(r[-1] - r[0] for r in rests) / abs(med)


def quartile_spread(values) -> float | None:
    """(Q3 - Q1) / median by ``statistics.quantiles(values, n=4)``."""
    xs = [float(v) for v in values]
    if len(xs) < 2:
        return None
    q1, _, q3 = statistics.quantiles(xs, n=4)
    return (q3 - q1) / abs(statistics.median(xs))


def summarise(values, bound=None) -> dict:
    """One metric's row: values, median, both spreads and, where it has
    a bound, whether the bound resolves."""
    row = {"values": list(values),
           "median": statistics.median(values) if values else None,
           "spread": spread(values),
           "quartile_spread": quartile_spread(values)}
    if bound is not None:
        row["bound"] = bound
        row["resolves"] = (row["spread"] is not None
                           and row["spread"] <= bound / 2)
    return row


def run_child(root: str, cell: str, seed: int, seconds: float) -> dict:
    """One untraced run of ``run.py`` in a process of its own. Returns
    its result line with the lines of facts it printed under ``facts``."""
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"),
         "--workload", cell, "--seed", str(seed), "--seconds", str(seconds),
         "--trace", "0"],
        cwd=root, stdout=subprocess.PIPE, text=True)
    out = proc.stdout.strip().splitlines()
    if proc.returncode != 0 or not out:
        raise SystemExit(f"aa_check: run.py exited {proc.returncode} on "
                         f"seed {seed}; its last lines: {out[-3:]}")
    line = json.loads(out[-1])
    line["facts"] = [x for x in out[:-1] if x.startswith(FACT_LINES)]
    return line


def check(spec: dict, cell: str, seeds, seconds: float, run_one) -> dict:
    """Run ``cell`` once a seed through ``run_one(cell, seed, seconds)``
    and reduce the result lines to one row a metric."""
    bounds = {m["name"]: m["bound"] for m in spec["end_to_end"]
              if "workloads" not in m or cell in m["workloads"]}
    lines = []
    for seed in seeds:
        line = run_one(cell, seed, seconds)
        print("run " + json.dumps({
            "seed": seed, "correct": line["correct"],
            "failed": line["failed"], "attempted": line["attempted"],
            "process_start_s": line.get("process_start_s"),
            **{k: v["value"] for k, v in line["metrics"].items()}}),
            flush=True)
        for fact in line.get("facts", ()):
            print(f"  {fact}", flush=True)
        lines.append(line)
    rows = {name: summarise([x["metrics"][name]["value"] for x in lines],
                            bound)
            for name, bound in bounds.items()}
    starts = [x["process_start_s"] for x in lines
              if x.get("process_start_s") is not None]
    if len(starts) == len(lines):
        rows["process_start_s"] = summarise(starts)
        rows["process_start_s+setup_s"] = summarise(
            [s + x["metrics"]["setup_s"]["value"]
             for s, x in zip(starts, lines)])
    return {"workload": cell, "seeds": list(seeds), "seconds": seconds,
            "all_correct": all(x["correct"] for x in lines),
            "metrics": rows}


def main(argv=None, root: str = ROOT, run_one=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    if args.workload not in {w["name"] for w in spec["workloads"]}:
        raise SystemExit(f"aa_check: unknown workload {args.workload!r}")
    if run_one is None:
        def run_one(cell, seed, seconds):
            return run_child(root, cell, seed, seconds)
    out = check(spec, args.workload, [int(s) for s in args.seeds.split(",")],
                args.seconds, run_one)
    for name, row in out["metrics"].items():
        print("metric " + json.dumps({"name": name, **row}), flush=True)
    print("aa " + json.dumps({k: v for k, v in out.items()
                              if k != "metrics"}), flush=True)
    return 0 if out["all_correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
