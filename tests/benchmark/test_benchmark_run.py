"""run.py end to end at a tiny size on the CPU: it refuses to report
without a TPU; with the look for a chip stepped over inside the test it
drives the rest of a run; files dropped into a copy of the directories
are found with no code edit; a broken timed path and a lower precision
both come out as not correct."""
import json
import os

import jax
import numpy as np
import pytest
from tiny_root import build

from benchmark import harness, rooflines, run
from benchmark.reference import bloom_ref

LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device",
             "process_start_s", "checks"}
PEAKS = {"flops_per_s": {"bfloat16": 1e12}, "hbm_bytes_per_s": 1e11,
         "hbm_bytes": 1e10}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return build(str(tmp_path_factory.mktemp("bench_root")))


@pytest.fixture()
def no_chip_check(monkeypatch):
    """Skip the harness's look for a chip — in the test, never through
    an option of the benchmark."""
    monkeypatch.setattr(run, "require_devices",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(rooflines, "peaks_for", lambda kind: PEAKS)


def drive(root, capsys, cell, seed=11, trace=0, seconds=1):
    rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                   str(seconds), "--trace", str(trace)], root=root)
    out = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(out[-1]), out


def test_refuses_to_report_without_a_tpu(root, capsys):
    with pytest.raises(SystemExit) as err:
        run.main(["--workload", "tiny.train", "--seed", "1", "--seconds",
                  "1", "--trace", "0"], root=root)
    assert "no TPU" in str(err.value)
    assert '"correct"' not in capsys.readouterr().out


@pytest.mark.parametrize("cell", ["tiny.train", "tiny.train4"])
def test_train_cell_prints_the_contracts_last_line(root, capsys, no_chip_check,
                                                   cell):
    """One device, and tensor 2 x data 2 on four (virtual) devices."""
    rc, line, out = drive(root, capsys, cell, seed=2 ** 31 + 12)
    assert rc == 0 and set(line) == LINE_KEYS
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert all(set(m) == {"value", "unit"} for m in line["metrics"].values())
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    # every number compared is printed beside its limit
    checks = [json.loads(x[6:]) for x in out if x.startswith("check ")]
    assert {c["name"] for c in checks} == {
        "loss_rel_gap_max", "grad_norm_gap_worst_leaf",
        "param_change_gap_worst_leaf"}
    assert all({"value", "limit", "ok"} <= set(c) for c in checks)
    # ... and on the result line, as its last key
    assert list(line)[-1] == "checks"
    assert {k: (v["value"], v["limit"]) for k, v in line["checks"].items()} \
        == {c["name"]: (c["value"], c["limit"]) for c in checks}
    # an option the program no longer has is dropped with a printed note
    assert any("an_option_a_later_pr_deleted" in x for x in out)
    # where set-up went, by the host's clock
    setup = json.loads(next(x for x in out if x.startswith("setup "))[6:])
    assert {"import_s", "weights_s", "trainer_build_s", "first_step_s",
            "followed_steps_s", "steps_followed"} <= set(setup)
    assert setup["steps_followed"] == 3
    assert 0 < setup["first_step_s"] <= setup["followed_steps_s"]


def test_serve_cell_prints_the_contracts_last_line(root, capsys, no_chip_check):
    rc, line, out = drive(root, capsys, "tiny.serve", seconds=2)
    assert rc == 0 and set(line) == LINE_KEYS | {"cut_off"}
    assert set(line["metrics"]) == {"itl_p95_ms", "setup_s"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == 40            # rate 20/s for 2 s, every seed
    assert line["cut_off"] == 0               # the drain finished them all
    serve = json.loads(next(x for x in out if x.startswith("serve "))[6:])
    assert "generator_late_ms_p50" in serve
    # host from device: the engine's phase clock tick by tick, the share
    # of gaps whose tick admitted another request, the machine
    assert {"dispatch", "fetch", "prefill"} <= set(serve["tick_phase_ms"])
    assert all(p["p50"] <= p["p95"] <= p["max"]
               for p in serve["tick_phase_ms"].values())
    assert 0 < serve["itl_gaps_holding_prefill_pct"] < 100
    assert sum(c for _, c in serve["itl_hist_upper_ms_count"]) \
        == serve["itl_gaps"]
    assert serve["cpu_count"] >= 1 and len(serve["load_avg_at_start"]) == 3
    setup = json.loads(next(x for x in out if x.startswith("setup "))[6:])
    assert {"import_s", "weights_s", "engine_build_s", "warm_up_s"} \
        <= set(setup)


@pytest.mark.parametrize("cell", ["tiny.train", "tiny.serve"])
def test_setup_s_starts_when_the_chip_answers(root, capsys, monkeypatch, cell):
    """The look for a chip (here a stand-in that takes a while, as the
    backend coming up does) is not in ``setup_s``: it is
    ``process_start_s``, a fact on the line that nothing judges."""
    import time

    answered = []

    def slow_chip(chips):
        time.sleep(0.5)
        answered.append(time.perf_counter())
        return jax.devices()[:chips]

    monkeypatch.setattr(run, "require_devices", slow_chip)
    monkeypatch.setattr(rooflines, "peaks_for", lambda kind: PEAKS)
    t_call = time.perf_counter()
    rc, line, _ = drive(root, capsys, cell, seconds=1)
    t_done = time.perf_counter()
    assert rc == 0 and line["correct"] is True
    setup_s = line["metrics"]["setup_s"]["value"]
    assert line["process_start_s"] == pytest.approx(
        answered[0] - run.T_PROCESS_START, abs=0.2)
    assert line["process_start_s"] >= 0.5
    # set-up and the 1 s window both fit between the chip's answer and
    # the end, so the half second before the answer is not in it
    assert 0 < setup_s < (t_done - answered[0]) - 1.0
    assert setup_s < (t_done - t_call) - 1.5


def test_a_request_the_drain_does_not_finish_is_cut_off_not_failed(
        root, capsys, no_chip_check):
    """With no drain, what is still decoding at the window's end has its
    tokens so far served and timed: attempted, counted as ``cut_off``
    beside ``failed`` in the result line, and not failed."""
    rc, line, out = drive(root, capsys, "tiny.serve-nodrain", seconds=2)
    assert rc == 0 and line["correct"] is True
    serve = json.loads(next(x for x in out if x.startswith("serve "))[6:])
    assert 1 <= line["cut_off"] <= 4          # at most the 4 slots
    assert line["cut_off"] == serve["cut_off"]
    assert line["attempted"] == (serve["finished"] + serve["cut_off"]
                                 + line["failed"])
    assert serve["itl_gaps_in_window"] <= serve["itl_gaps"]


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        root, capsys, no_chip_check, monkeypatch):
    from pipegoose_tpu.optim.zero import DistributedOptimizer

    monkeypatch.setattr(DistributedOptimizer, "step",
                        lambda self, grads, state, params: (params, state))
    _, line, out = drive(root, capsys, "tiny.train")
    assert line["correct"] is False
    bad = {json.loads(x[6:])["name"] for x in out
           if x.startswith("check ") and not json.loads(x[6:])["ok"]}
    assert "param_change_gap_worst_leaf" in bad


def test_a_token_altered_where_it_is_produced_is_not_correct(
        root, capsys, no_chip_check, monkeypatch):
    from pipegoose_tpu.serving.scheduler import Scheduler

    real = Scheduler.record_token
    monkeypatch.setattr(
        Scheduler, "record_token",
        lambda self, req, token, now: real(self, req, (token + 1) % 512, now))
    _, line, _ = drive(root, capsys, "tiny.serve", seconds=2)
    assert line["correct"] is False


def test_dropped_in_files_are_found_by_name(root):
    spec = harness.load_json(os.path.join(root, "BENCHMARK.json"))
    here = os.path.join(root, "benchmark")
    cell, config, workload = harness.find_cell(spec, "tiny.train", here)
    assert config["sizes"]["hidden_size"] == 64 and workload["seq"] == 32
    result = harness.Result(
        end_to_end={}, attempted=3, failed=0, t_window_start=0.0,
        memory_peak_bytes=0,
        facts={"kind": "train", "steps": 3, "step_s": [0.5, 0.25, 0.75]})
    got = run.layer_metrics(spec, "tiny.train", result, here)
    assert got == {"steps_seen.tiny": {"value": 3.0, "unit": "steps"},
                   "step_ms.train": {"value": 500.0, "unit": "ms"}}
    # a reader that finds nothing to read returns nothing: left out
    result.facts = {"kind": "serve"}
    assert "step_ms.train" not in run.layer_metrics(
        spec, "tiny.train", result, here)
    with pytest.raises(SystemExit, match="unknown workload"):
        harness.find_cell(spec, "no.such-cell", here)


# -- the control: one precision below the configuration's ---------------------

SIZES = {"vocab_size": 2048, "hidden_size": 64, "n_layer": 2, "n_head": 4,
         "layer_norm_epsilon": 1e-5, "initializer_range": 0.08}


def test_lower_precision_training_fails_the_comparison():
    """The reference at fp8 in the program's place, held to the tiny
    cell's limits through the driver's own comparison — the limits the
    bfloat16 program passes in the tests above."""
    from benchmark import traffic, weights
    from tiny_root import REPO, TINY_TRAIN

    train = harness.load_module(
        os.path.join(REPO, "benchmark", "drivers", "train.py"))
    key = weights.seed_key(3)
    make_w0 = jax.jit(lambda: weights.make(key, SIZES, "bfloat16"))
    rows = [traffic.token_batch(2048, 3, s, 4, 32) for s in range(3)]
    kw = dict(sizes=SIZES, lr=3e-4, rows_per_call=2, store_dtype="bfloat16")
    ref = bloom_ref.adam_steps(make_w0, rows, precision="float32", **kw)
    low = bloom_ref.adam_steps(make_w0, rows, precision="fp8", **kw)
    checks = harness.Checks()
    train.compare(checks, low["losses"],
                  {k: v * (1 - train.ADAM_B1)
                   for k, v in low["grad_norm"].items()},
                  low["delta_norm"], ref, TINY_TRAIN["check"])
    assert not checks.correct
    same = harness.Checks()
    train.compare(same, ref["losses"],
                  {k: v * (1 - train.ADAM_B1)
                   for k, v in ref["grad_norm"].items()},
                  ref["delta_norm"], ref, TINY_TRAIN["check"])
    assert same.correct


def test_lower_precision_serving_fails_the_comparison():
    """At every position of seeded sequences, the token the fp8 forward
    puts first, scored by the float32 reference: the widest gap passes
    the tiny cell's limit, which the bfloat16 engine stays under."""
    from benchmark import weights
    from tiny_root import TINY_SERVE

    w = {k: v.astype("float32") for k, v in
         weights.make(weights.seed_key(5), SIZES, "bfloat16").items()}
    rng = np.random.default_rng(5)
    worst = 0.0
    fn = jax.jit(lambda t, p, prec: bloom_ref.next_token_scores(
        w, t, p, SIZES, prec), static_argnums=2)
    for _ in range(4):
        ids = jax.numpy.asarray(rng.integers(1, 2048, 128), "int32")
        _, picks = fn(ids, ids, "fp8")
        gap, _ = fn(ids, picks, "float32")
        worst = max(worst, float(gap.max()))
    assert worst > TINY_SERVE["check"]["served_logit_gap_max"]


def test_worst_leaf_gap_measures_against_the_median_leaf():
    want = {"a": 10.0, "b": 1.0, "c": 1e-9}
    got = {"a": 10.5, "b": 1.0, "c": 0.3}
    gap, leaf = harness.worst_leaf_gap(got, want)
    assert leaf == "c" and gap == pytest.approx(0.3)     # floor: median 1.0
    assert harness.worst_leaf_gap(want, want)[0] == 0.0
    assert harness.percentile(list(range(1, 101)), 90) == 90.0


def test_compile_watch_counts_a_new_shape():
    from benchmark.compile_watch import CompileWatch

    watch = CompileWatch().install()
    f = jax.jit(lambda x: x * 2 + 1)
    f(np.ones(3, np.float32))
    first = watch.count
    f(np.ones(3, np.float32))
    assert first >= 1 and watch.count == first
    f(np.ones(5, np.float32))
    assert watch.count > first
