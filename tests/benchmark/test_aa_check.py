"""aa_check.py: the spread rule on fixed lists, and the loop over seeds
with the child run stood in for by a function (run.py itself still
refuses to report without a TPU)."""
import json

import pytest
from tiny_root import build

from benchmark import aa_check


@pytest.mark.parametrize("values, want", [
    # six values, none far: the farthest from the median (10) goes
    ([10.0, 10.1, 9.9, 10.2, 10.0, 9.0], (10.2 - 9.9) / 10.0),
    # one outlier of six is left out
    ([8.30, 8.31, 8.29, 8.30, 8.32, 11.0], (8.32 - 8.29) / 8.305),
    # two outliers are not: the second stays in the range
    ([8.30, 8.31, 8.29, 8.30, 11.0, 11.5], (11.0 - 8.29) / 8.305),
    # ties: two runs equally far, the one whose going narrows it more
    ([1.0, 4.0, 5.0, 5.0, 9.0], 4.0 / 5.0),
    ([9.0, 10.0, 11.0], 1.0 / 10.0),
    # all alike
    ([7.0] * 6, 0.0),
    # two runs: nothing is left out
    ([10.0, 11.0], 1.0 / 10.5),
])
def test_spread_leaves_out_the_run_farthest_from_the_median(values, want):
    assert aa_check.spread(values) == pytest.approx(want)
    assert aa_check.spread(list(reversed(values))) == pytest.approx(want)


@pytest.mark.parametrize("values", [[], [3.0]])
def test_fewer_than_two_runs_have_no_spread(values):
    assert aa_check.spread(values) is None
    assert aa_check.quartile_spread(values) is None
    row = aa_check.summarise(values, bound=0.05)
    assert row["resolves"] is False


def test_quartile_spread_is_the_contracts():
    import statistics

    xs = [8.30, 8.31, 8.29, 8.30, 8.32, 11.0]
    q1, _, q3 = statistics.quantiles(xs, n=4)
    assert aa_check.quartile_spread(xs) == pytest.approx(
        (q3 - q1) / statistics.median(xs))
    # wider than the driver's spread here: the outlier pulls Q3
    assert aa_check.quartile_spread(xs) > aa_check.spread(xs)


@pytest.mark.parametrize("spread_share, resolves", [(0.004, True),
                                                    (0.02, False)])
def test_a_bound_resolves_where_the_spread_is_at_most_half_of_it(
        spread_share, resolves):
    values = [100.0, 100.0 * (1 + spread_share), 100.0, 100.0, 150.0]
    row = aa_check.summarise(values, bound=0.01)
    assert row["median"] == 100.0
    assert row["spread"] == pytest.approx(spread_share)
    assert row["resolves"] is resolves


def test_the_loop_runs_every_seed_in_turn_and_reduces_each_metric(
        tmp_path, capsys):
    root = build(str(tmp_path))
    asked = []

    def child(cell, seed, seconds):
        asked.append((cell, seed, seconds))
        k = len(asked)
        return {"correct": True, "attempted": 40, "failed": 0,
                "process_start_s": 10.0 + k,
                "metrics": {"itl_p95_ms": {"value": 8.0 + 0.01 * k,
                                           "unit": "ms"},
                            "setup_s": {"value": 6.0 + (3.0 if k == 2 else 0),
                                        "unit": "s"}},
                "facts": ['serve {"longest_ticks_start_s_ms": [[1.0, 9.0]]}']}

    rc = aa_check.main(["--workload", "tiny.serve", "--seeds", "5,6,7,8",
                        "--seconds", "2"], root=root, run_one=child)
    assert rc == 0
    assert asked == [("tiny.serve", s, 2.0) for s in (5, 6, 7, 8)]
    out = capsys.readouterr().out.splitlines()
    runs = [json.loads(x[4:]) for x in out if x.startswith("run ")]
    assert [r["seed"] for r in runs] == [5, 6, 7, 8]
    assert sum("longest_ticks_start_s_ms" in x for x in out) == 4
    rows = {r["name"]: r for r in
            (json.loads(x[7:]) for x in out if x.startswith("metric "))}
    # the cell's end-to-end metrics, each against its own bound, and the
    # two facts with none
    assert set(rows) == {"itl_p95_ms", "setup_s", "process_start_s",
                         "process_start_s+setup_s"}
    assert rows["itl_p95_ms"]["bound"] == 0.05
    assert rows["itl_p95_ms"]["values"] == [8.01, 8.02, 8.03, 8.04]
    assert rows["itl_p95_ms"]["resolves"] is True
    # one run of four read 9 s: left out, the rest agree
    assert rows["setup_s"]["spread"] == 0.0 and rows["setup_s"]["resolves"]
    assert "bound" not in rows["process_start_s"]
    assert rows["process_start_s+setup_s"]["values"] == [17.0, 21.0, 19.0,
                                                         20.0]
    assert json.loads(out[-1][3:])["all_correct"] is True


def test_a_run_that_is_not_correct_fails_the_call(tmp_path, capsys):
    root = build(str(tmp_path))

    def child(cell, seed, seconds):
        return {"correct": seed != 2, "attempted": 3, "failed": 0,
                "metrics": {"train_tokens_per_s": {"value": 1e4, "unit": "t"},
                            "setup_s": {"value": 5.0, "unit": "s"}}}

    assert aa_check.main(["--workload", "tiny.train", "--seeds", "1,2",
                          "--seconds", "1"], root=root, run_one=child) == 1
    assert "process_start_s" not in capsys.readouterr().out.split("metric")[1]
    with pytest.raises(SystemExit, match="unknown workload"):
        aa_check.main(["--workload", "no.such", "--seeds", "1",
                       "--seconds", "1"], root=root, run_one=child)
