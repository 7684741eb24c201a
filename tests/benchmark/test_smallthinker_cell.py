"""SmallThinker's serving cell at a tiny size on the CPU: the
configuration, its cell and its readers dropped into a copy of the
benchmark's directories as files (the way the real ones were added, with
no edit to a file that was there), driven through ``run.py`` under
driver ``serve_model``; a lower precision in the program's place fails
the comparison; each new reader against hand-built facts and a
hand-built trace, and on a program that lacks its names; the real files
against the catalog row and the issue's arithmetic."""
import json
import os

import jax
import pytest
from tiny_root import REPO, build

from benchmark import harness, rooflines, run

PEAKS = {"flops_per_s": {"bfloat16": 1e12}, "hbm_bytes_per_s": 1e11,
         "hbm_bytes": 1e10}
V5E = {"flops_per_s": {"bfloat16": 197e12}, "hbm_bytes_per_s": 819e9}
CELL = "tiny-smallthinker.serve-think"
REAL_CELL = "smallthinker-21b-a3b.serve-think-s16"
NEW_READERS = ("decode_hbm_roofline.think", "expert_read_roofline.think",
               "experts_touched_pct.think", "ring_rows_useful_pct.think",
               "ring_wrapped_pct.think", "prefill_flash_roofline.think")
SHARED_READERS = ("decode_step_ms.chat", "prefill_stall_ms.chat",
                  "tick_host_ms.chat", "engine_build_s.chat",
                  "program_first_call_s.chat", "step_launch_ms.serve",
                  "step_return_ms.serve", "step_upload_ms.serve",
                  "device_gap_ms.serve", "prefill_device_busy_pct.serve")

# the catalog row's ``config`` (model-configs/architectures.jsonl,
# SmallThinker-21BA3B-Instruct), every key
LAYOUT = [0, 1, 1, 1] * 13
CATALOG = {
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
    "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
    "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_attention_heads": 28, "num_hidden_layers": 52,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "rope_layout": LAYOUT,
    "rope_scaling": None, "rope_theta": 1500000,
    "sliding_window_layout": LAYOUT, "sliding_window_size": 4096,
    "tie_word_embeddings": False, "vocab_size": 151936,
}
REDUCED = {"num_hidden_layers": 12}


def _real(name):
    with open(os.path.join(REPO, "benchmark", name)) as f:
        return json.load(f)


def tiny_config():
    """The real configuration file at toy widths: a window of 24 (six pages:
    prompts of 4 to 40 tokens stand on both sides of it), 8
    experts of which a token picks 3, a group of 4 query heads a KV head;
    the per-layer lists stay whole, as in the real file."""
    config = _real("configs/smallthinker-21b-a3b.json")
    config.update(
        name="tiny-smallthinker",
        source="https://example.org/tiny-smallthinker",
        vocab_size=128, hidden_size=64, num_hidden_layers=5,
        num_attention_heads=8, num_key_value_heads=2, head_dim=16,
        moe_ffn_hidden_size=32, moe_num_primary_experts=8,
        moe_num_active_primary_experts=3, experts_held=[0, 8],
        sliding_window_size=24, initializer_range=0.3,
        model_options={"use_flash": False,
                       "an_option_a_later_pr_deleted": True})
    return config


# limit read on the CPU (``read_limits.py`` over this root): the program
# (bfloat16) 0.06 - 0.67 on seeds 1-3, 5, 7, 8, 2**31 + 52 to + 54, and
# 1.41, 1.71, 1.84 on seeds 4, 2**31 + 55 and 6 (a tail of single tokens
# where a router's third and fourth logit swap under bfloat16); the fp8
# control 5.41 - 9.96 over seeds 1-5 and 2**31 + 53 (std 0.3 weights at
# width 64 make logits tens apart)
TINY_CELL = {
    "driver": "serve_model",
    "engine": {"num_slots": 3, "num_pages": 64, "page_size": 4,
               "max_context": 64},
    "traffic": {
        "rate_per_s": 6.0, "order_seed": 11,
        "prompt": {"dist": "lognormal", "median": 14, "sigma": 0.7,
                   "min": 4, "max": 40},
        "output": {"dist": "lognormal", "median": 8, "sigma": 0.4,
                   "min": 4, "max": 12},
        "prompt_buckets": [8, 16, 40],
    },
    "drain_s": 60.0,
    "check": {"sample_requests": 4, "pad_to": [32, 64],
              "served_logit_gap_max": 3.5},
}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    dst = build(str(tmp_path_factory.mktemp("smallthinker_root")))
    here = os.path.join(dst, "benchmark")
    with open(os.path.join(here, "configs", "tiny-smallthinker.json"),
              "w") as f:
        json.dump(tiny_config(), f)
    with open(os.path.join(here, "workloads", CELL + ".json"), "w") as f:
        json.dump(TINY_CELL, f)
    real = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
    spec = harness.load_json(os.path.join(dst, "BENCHMARK.json"))
    spec["configs"].append({
        "name": "tiny-smallthinker",
        "source": "https://example.org/tiny-smallthinker",
        "file": "benchmark/configs/tiny-smallthinker.json",
        "reduced": tiny_config()["reduced"], "why": "test"})
    spec["workloads"].append({"name": CELL, "config": "tiny-smallthinker",
                              "traffic": "serve-think", "chips": 1,
                              "why": "test"})
    next(m for m in spec["end_to_end"]
         if m["name"] == "itl_p95_ms")["workloads"].append(CELL)
    for m in real["per_layer"]:
        if m["name"] in NEW_READERS + SHARED_READERS:
            spec["per_layer"] = [x for x in spec["per_layer"]
                                 if x["name"] != m["name"]] + [
                dict(m, workloads=[CELL] + (
                    ["tiny.serve"] if m["name"] == "decode_step_ms.chat"
                    else []))]
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f, indent=1)
    return dst


@pytest.fixture()
def no_chip_check(monkeypatch):
    monkeypatch.setattr(run, "require_devices",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(rooflines, "peaks_for", lambda kind: PEAKS)


def drive(root, capsys, seed, trace=0):
    rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds", "1",
                   "--trace", str(trace)], root=root)
    out = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(out[-1]), out


def test_the_cell_runs_from_dropped_in_files_and_is_correct(
        root, capsys, no_chip_check):
    rc, line, out = drive(root, capsys, seed=2 ** 31 + 53)
    assert rc == 0 and line["correct"] is True
    assert line["failed"] == 0 and line["cut_off"] == 0
    assert set(line["metrics"]) == {"itl_p95_ms", "setup_s"}
    checks = [json.loads(x[6:]) for x in out if x.startswith("check ")]
    assert [c["name"] for c in checks] == ["served_logit_gap_max"]
    serve = json.loads(next(x for x in out if x.startswith("serve "))[6:])
    # both cache kinds on the line, a ring never over slots x 7 pages,
    # window pages taken over, and the experts' counters out of the step
    kinds = serve["pages_by_kind"]
    assert set(kinds) == {"global", "window"}
    assert 0 < kinds["window"]["peak_in_use"] <= 3 * 7
    assert serve["window_pages_recycled"] > 0
    assert 0.0 < serve["experts_touched_share"] <= 1.0
    assert serve["expert_rows_max_over_mean"] >= 1.0
    assert any("an_option_a_later_pr_deleted" in x for x in out)
    setup = json.loads(next(x for x in out if x.startswith("setup "))[6:])
    assert setup["weights_gb"] > 0


def test_the_readers_read_a_run_of_the_cell(root, capsys, no_chip_check):
    """A run's result through every reader the cell lists (``run.py``'s
    own loop). With no device trace (the CPU has none) the readers of
    the trace find nothing and leave their metric out; the counters'
    readers read the engine's own ``finish_run()``: rings that are still
    filling beside rings that have wrapped."""
    spec, driver, ctx, here = run.open_cell(root, CELL, 5, 1.0, False)
    result = driver.run(ctx)
    capsys.readouterr()
    window = result.facts["run_metrics"]["window"]
    experts = result.facts["run_metrics"]["experts"]
    assert 0 < window["wrapped_row_share"] < 1
    assert 0 < window["rows_useful_share"] < 1
    # the sizes carry the window under the name the driver reads it by
    assert result.facts["sizes"]["sliding_window"] == 24
    assert max(result.facts["live_window"]) <= 3 * 24
    got = run.layer_metrics(spec, CELL, result, here)
    assert got["ring_wrapped_pct.think"]["value"] == pytest.approx(
        100 * window["wrapped_row_share"])
    assert got["ring_rows_useful_pct.think"]["value"] == pytest.approx(
        100 * window["rows_useful_share"])
    assert got["experts_touched_pct.think"]["value"] == pytest.approx(
        100 * experts["touched_share"])
    assert got["decode_step_ms.chat"]["value"] > 0
    for name in ("decode_hbm_roofline.think", "expert_read_roofline.think",
                 "prefill_flash_roofline.think"):
        assert name not in got


def _open(root, seed):
    import sys

    here = os.path.join(root, "benchmark")
    for p in (root, REPO):
        if p not in sys.path:
            sys.path.insert(0, p)
    spec = harness.load_json(os.path.join(root, "BENCHMARK.json"))
    cell, config, workload = harness.find_cell(spec, CELL, here)
    ctx = harness.Context(
        cell=cell, config=config, workload=workload, seed=seed, seconds=1.0,
        trace=False, devices=jax.devices()[:1], peaks=PEAKS, watch=None,
        checks=harness.Checks())
    driver = harness.load_module(
        os.path.join(here, "drivers", workload["driver"] + ".py"))
    return spec, driver, ctx, here


def test_a_lower_precision_in_the_programs_place_fails_the_comparison(root):
    """The reference at fp8 over a sample, held to the tiny cell's limit
    through the driver's own ``control``; the float32 reference's own
    picks read 0."""
    import numpy as np

    _, driver, ctx, _ = _open(root, seed=3)
    rng = np.random.default_rng(0)
    ctx.sample = [(rng.integers(1, 128, size=n).astype(np.int32), n - 16)
                  for n in (60, 33)]
    assert not driver.control(ctx).correct
    same, _ = driver.score(ctx, ctx.sample, picks="lower",
                           precision="float32")
    assert same == 0.0


def test_the_real_files_are_the_catalog_row_cut_as_they_say():
    config = _real("configs/smallthinker-21b-a3b.json")
    # every key of the catalog row under the same name, the reduced one
    # apart, and that is what ``reduced`` lists
    assert {k: config[k] for k in CATALOG} == {**CATALOG, **REDUCED}
    assert config["reduced"] == list(REDUCED)
    assert config["published"] == {k: CATALOG[k] for k in REDUCED}
    assert config["source"] == (
        "https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct/"
        "blob/main/config.json")
    assert config["dtype"] == "bfloat16"
    assert "layers 0-11 of 52" in config["deployment"]
    assert "pipeline stages" in config["deployment"]
    assert config["experts_held"] == [0, 64]
    assert {"router_input", "hidden_act", "dense_layers",
            "secondary_experts", "attention_bias", "rope_pairing",
            "initializer_range", "weights"} == set(config["assumed"])
    assert config["program"] == {
        "adapter": "program_smallthinker", "weights": "weights_smallthinker",
        "reference": "reference/smallthinker_ref"}
    assert config["model_options"] == {"use_flash": True,
                                       "moe_block_tokens": 2048}
    from benchmark import program_smallthinker as adapter
    from benchmark import rooflines_smallthinker as rl
    from benchmark import weights_smallthinker

    sizes = adapter.sizes(config)
    # the layouts stay whole in the file; three whole periods are held
    assert len(config["rope_layout"]) == 52
    assert sizes["rope_layout"] == sizes["sliding_window_layout"] \
        == [0, 1, 1, 1] * 3
    assert rl.layers_by_kind(sizes) == (3, 9)
    assert sizes["sliding_window"] == 4096
    # the issue's arithmetic, by the rooflines' own functions
    assert rl.attention_params(sizes) == 20_971_520
    assert rl.expert_params(sizes) == 5_898_240
    assert rl.layer_params(sizes) == 398_627_840
    n = weights_smallthinker.n_params(sizes)
    assert n == rl.model_params(sizes) == 5_561_448_960
    assert round(2 * n / 1e9, 2) == 11.12
    assert round(rl.model_params(sizes, layers=52) / 1e9, 1) == 21.5
    # active a token: attention, the router and six experts a layer,
    # beside the embedding and the head
    active = 52 * rl.layer_params(sizes, experts=6) + 2 * 151936 * 2560
    assert round(active / 1e9, 1) == 3.7
    # a decode step: every weight but the embedding and the experts it
    # did not touch; a key 2 KB a layer, a window layer's capped
    assert rl.decode_step_bytes(sizes, 0, 0, 0) == 2 * (
        n - 151936 * 2560 - 12 * 64 * 5_898_240)
    assert rl.decode_step_bytes(sizes, 35 * 12, 9000, 5000) \
        - rl.decode_step_bytes(sizes, 0, 0, 0) \
        == 420 * 11_796_480 + 2048 * (3 * 9000 + 9 * 5000)
    # the program's own configuration takes every published key
    cfg = adapter.make_config(config)
    assert cfg.held == (0, 64) == (0, cfg.moe_num_primary_experts)
    assert (cfg.use_flash, cfg.moe_block_tokens) == (True, 2048)
    desc = cfg.paged_model()
    assert desc.window == 4096 and desc.window_rule == "sliding"
    assert desc.layers_of("global") == 3 and desc.layers_of("window") == 9
    cell = _real("workloads/" + REAL_CELL + ".json")
    assert cell["driver"] == "serve_model"
    # every page 16 slots can reach
    assert cell["engine"] == {"num_slots": 16, "num_pages": 16 * 640,
                              "page_size": 16, "max_context": 10240}
    t = cell["traffic"]
    assert t["prompt"] == {"dist": "lognormal", "median": 1024, "sigma": 1.1,
                           "min": 64, "max": 8192}
    assert t["output"] == {"dist": "lognormal", "median": 768, "sigma": 0.5,
                           "min": 128, "max": 1536}
    assert t["prompt_buckets"] == [128, 256, 512, 1024, 1536, 2048, 3072,
                                   4096, 6144, 8192]
    assert "order_seed" in t and cell["drain_s"] == 35.0
    assert t["rate_per_s"] * 8 == int(t["rate_per_s"] * 8)   # a 0.125/s grid
    # every sequence the traffic can make has a padded length, and fits
    assert max(cell["check"]["pad_to"]) >= 8192 + 1536
    assert cell["engine"]["max_context"] >= 8192 + 1536
    assert cell["check"]["sample_requests"] == 8
    spec = _real("../BENCHMARK.json")
    entry = next(c for c in spec["configs"]
                 if c["name"] == "smallthinker-21b-a3b")
    assert entry["source"] == config["source"]
    assert entry["reduced"] == config["reduced"]
    assert entry["file"] == "benchmark/configs/smallthinker-21b-a3b.json"
    real_cell = next(w for w in spec["workloads"] if w["name"] == REAL_CELL)
    assert real_cell["chips"] == 1
    assert real_cell["config"] == "smallthinker-21b-a3b"
    # the new entries stand at the END of per_layer, in this order
    assert tuple(m["name"] for m in spec["per_layer"][-6:]) == NEW_READERS
    for m in spec["per_layer"]:
        if m["name"] in NEW_READERS:
            assert m["workloads"] == [REAL_CELL] and m["moves"] == "itl_p95_ms"
        if m["name"] in SHARED_READERS:
            assert REAL_CELL in m["workloads"]


# -- the readers ---------------------------------------------------------------

SIZES = {"hidden_size": 2560, "head_dim": 128, "num_key_value_heads": 4,
         "num_attention_heads": 28, "num_hidden_layers": 12,
         "moe_ffn_hidden_size": 768, "moe_num_primary_experts": 64,
         "experts_held": [0, 64], "sliding_window": 4096,
         "vocab_size": 151936, "rope_layout": [0, 1, 1, 1] * 3,
         "sliding_window_layout": [0, 1, 1, 1] * 3}


def _facts(**metrics):
    run_metrics = {"decode_steps": 2,
                   "experts": {"touched_by_step": [420, 380],
                               "touched_share": 0.52},
                   "window": {"wrapped_row_share": 0.125,
                              "rows_useful_share": 0.31}}
    run_metrics.update(metrics)
    return {"sizes": SIZES, "peaks": V5E, "dtype": "bfloat16",
            "ticks": [(0.1, 0), (0.2, 20000), (0.3, 30000)],
            "live_window": [0, 16000, 18000], "run_metrics": run_metrics}


def _read(name, facts, ops=None, modules=()):
    reader = harness.load_module(os.path.join(
        REPO, "benchmark", "layer_metrics", name + ".py"))
    trace = None if ops is None else {
        "devices": [{"busy_ns": 1, "ops": ops, "modules": list(modules)}]}
    result = harness.Result(end_to_end={}, attempted=1, failed=0,
                            t_window_start=0.0, memory_peak_bytes=0,
                            facts=facts, trace=trace)
    return reader.read(result)


def test_counter_readers_read_the_engines_counters():
    assert _read("experts_touched_pct.think", _facts()) == pytest.approx(52.0)
    assert _read("ring_wrapped_pct.think", _facts()) == pytest.approx(12.5)
    assert _read("ring_rows_useful_pct.think", _facts()) == pytest.approx(31.0)


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_reader_returns_none_on_a_program_that_lacks_its_names(name):
    """The parent's program: no ``window`` and no ``experts`` in
    ``finish_run()``, no ``flash_fwd`` and no ``ragged-dot`` in the
    trace, no decode program under its name. Nothing is read, nothing
    raises."""
    bare = dict(_facts(experts=None, window=None))
    bare["run_metrics"].pop("experts")
    bare["run_metrics"].pop("window")
    assert _read(name, bare) is None
    assert _read(name, bare, [("%fusion = f32[8] fusion()", 0, 5)],
                 [("jit_other(1)", 0, 9)]) is None


def test_decode_roofline_counts_touched_experts_and_keys_by_kind():
    from benchmark import rooflines_smallthinker as rl

    steps = [rl.decode_step_bytes(SIZES, 420, 20000, 16000),
             rl.decode_step_bytes(SIZES, 380, 30000, 18000)]
    # an expert 11.8 MB, a key 2 KB a layer
    assert steps[0] == pytest.approx(
        2 * rl.params_outside_experts(SIZES) + 420 * 11796480
        + 2048 * (3 * 20000 + 9 * 16000))
    ns = int(2 * sum(steps) / 819e9 * 1e9)     # at half the roofline
    modules = [("jit__step(123)", 0, ns // 2), ("jit__prefill(9)", ns, 2 * ns),
               ("jit__step(123)", 3 * ns, 3 * ns + ns // 2)]
    assert _read("decode_hbm_roofline.think", _facts(), [],
                 modules) == pytest.approx(50.0, rel=1e-3)
    assert _read("decode_hbm_roofline.think", _facts()) is None   # no trace


def test_expert_read_roofline_takes_the_calls_inside_the_decode_program():
    nbytes = (420 + 380) * 11796480
    ns = int(4 * nbytes / 819e9 * 1e9)         # a quarter of the roofline
    modules = [("jit__step(1)", 0, ns), ("jit__prefill(2)", 2 * ns, 9 * ns),
               ("jit__step(1)", 10 * ns, 11 * ns)]
    ops = [("%ragged-dot-metadata.3 = (s32[65]) custom-call(%g)", 0, 0),
           ("%ragged-dot-none.11 = bf16[96,768] custom-call(%x)", 0,
            ns // 2),
           ("%ragged-dot-none.9 = bf16[96,2560] custom-call(%x)",
            10 * ns, 10 * ns + ns // 2),
           # the prefill's grouped products are not the decode step's
           ("%ragged-dot-none.2 = bf16[12288,768] custom-call(%x)",
            3 * ns, 8 * ns),
           ("%fusion.3 = bf16[8] fusion(%ragged-dot-none.2)", 0, ns)]
    assert _read("expert_read_roofline.think", _facts(), ops,
                 modules) == pytest.approx(25.0, rel=1e-3)


def test_flash_roofline_charges_a_call_the_mean_over_the_layers_kinds():
    from benchmark import rooflines_smallthinker as rl

    assert rl.kept_pairs(8192, 4096) == 4096 * 4097 // 2 + 4096 * 4096
    (sg, fg, bg), (sw, fw, bw) = rl.prefill_flash_costs(8192, SIZES)
    assert (sg, sw) == (0.25, 0.75) and bg == bw
    assert fw / fg == pytest.approx(
        rl.kept_pairs(8192, 4096) / rl.kept_pairs(8192), rel=1e-12)
    # under the window the two kinds are one
    (_, f1, _), (_, f2, _) = rl.prefill_flash_costs(2048, SIZES)
    assert f1 == f2 == 4.0 * 28 * (2048 * 2049 // 2) * 128
    one = 0.25 * max(fg / 197e12, bg / 819e9) \
        + 0.75 * max(fw / 197e12, bw / 819e9)
    ns = int(5 * one * 1e9)
    # twelve calls of one prefill, each at a fifth of its roofline
    ops = [("%%flash_fwd.%d = (bf16[28,8192,128], f32[28,8192]) "
            "custom-call(%%q)" % i, 2 * i * ns, (2 * i + 1) * ns)
           for i in range(12)]
    ops += [("%flash_dq.1 = bf16[28,8192,128] custom-call(%q)", 0, 9 * ns),
            ("%flash_fwd.77 = (bf16[20,4096,256]) custom-call(%q)", 0,
             9 * ns)]
    assert _read("prefill_flash_roofline.think", _facts(),
                 ops) == pytest.approx(20.0, rel=1e-3)
