"""The open loop's cut-off bookkeeping, with the window flooded.

``tests/benchmark/test_benchmark_run.py::test_a_request_the_drain_does_
not_finish_is_cut_off_not_failed`` needs a request still decoding when
its window ends, and at its 20 requests/s that is decided by the CPU's
speed: it moved to the slow tier when the decode step got faster
(``tests/conftest.py``). This is the same check made independent of
speed: requests arrive far faster than any CPU serves them, so every
slot is decoding when the window ends.
"""
import json
import os
import sys

import jax

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "benchmark"))

from tiny_root import TINY_SERVE_NODRAIN, build  # noqa: E402

from benchmark import rooflines, run  # noqa: E402

PEAKS = {"flops_per_s": {"bfloat16": 1e12}, "hbm_bytes_per_s": 1e11,
         "hbm_bytes": 1e10}


def test_a_flooded_window_cuts_off_what_is_still_decoding(
        tmp_path, capsys, monkeypatch):
    root = build(str(tmp_path))
    flood = dict(TINY_SERVE_NODRAIN)
    flood["traffic"] = dict(flood["traffic"], rate_per_s=2000.0)
    with open(os.path.join(root, "benchmark", "workloads",
                           "tiny.serve-flood.json"), "w") as f:
        json.dump(flood, f)
    spec_path = os.path.join(root, "BENCHMARK.json")
    spec = json.load(open(spec_path))
    spec["workloads"].append({"name": "tiny.serve-flood", "config": "tiny",
                              "traffic": "serve-flood", "chips": 1,
                              "why": "test"})
    for m in spec["end_to_end"]:
        if m["name"] == "itl_p95_ms":
            m["workloads"].append("tiny.serve-flood")
    json.dump(spec, open(spec_path, "w"))
    # skip the harness's look for a chip — in the test, never through
    # an option of the benchmark
    monkeypatch.setattr(run, "require_devices",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(rooflines, "peaks_for", lambda kind: PEAKS)

    rc = run.main(["--workload", "tiny.serve-flood", "--seed", "11",
                   "--seconds", "0.5", "--trace", "0"], root=root)
    out = capsys.readouterr().out.strip().splitlines()
    line = json.loads(out[-1])
    serve = json.loads(next(x for x in out if x.startswith("serve "))[6:])
    assert rc == 0 and line["correct"] is True
    assert serve["planned"] == 1000
    assert 900 <= line["attempted"] == serve["submitted"]
    assert 1 <= line["cut_off"] <= 4          # at most the 4 slots
    assert line["cut_off"] == serve["cut_off"]
    # what never got a first token failed; what was decoding did not
    assert line["attempted"] == (serve["finished"] + serve["cut_off"]
                                 + line["failed"])
    assert line["failed"] >= 100
    assert serve["itl_gaps_in_window"] <= serve["itl_gaps"]
