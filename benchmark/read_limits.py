"""Read the two numbers every limit is set from, in one process: what
sound runs of the program give over many seeds, and what the control —
the reference at one precision below the configuration's — gives.

    python3 benchmark/read_limits.py --workload <cell> --seeds 1,2,3 \\
        --control-seeds 1,2,3 --seconds 12

Prints one ``limits`` line per seed. A tool for the PR that sets or
changes a limit; the benchmark's own runs never call it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None, root: str = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", default="",
                    help="open-loop cells: run every seed at each of these "
                         "arrival rates instead of the file's (the sweep "
                         "that finds the sustained rate)")
    args = ap.parse_args(argv)
    if root not in sys.path:
        sys.path.insert(0, root)
    import dataclasses

    from benchmark import harness, run

    _, driver, first, _ = run.open_cell(root, args.workload, 0,
                                        args.seconds, False)
    control = {int(s) for s in args.control_seeds.split(",") if s}
    rates = [float(r) for r in args.rates.split(",") if r] or [None]
    for seed, rate in [(int(s), r) for r in rates
                       for s in args.seeds.split(",")]:
        workload = first.workload
        if rate is not None:
            workload = dict(workload, traffic=dict(workload["traffic"],
                                                   rate_per_s=rate))
        ctx = dataclasses.replace(first, seed=seed, workload=workload,
                                  checks=harness.Checks())
        result = driver.run(ctx)
        row = {"seed": seed, "rate_per_s": rate, "failed": result.failed,
               "attempted": result.attempted,
               "program": {r["name"]: r["value"] for r in ctx.checks.rows},
               "end_to_end": result.end_to_end}
        if seed in control:
            row["control"] = {r["name"]: r["value"]
                              for r in driver.control(ctx).rows}
        print("limits " + json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
