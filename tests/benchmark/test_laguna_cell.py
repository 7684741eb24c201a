"""Laguna-S-2.1's serving cell at a tiny size on the CPU: the
configuration, its cell and its readers dropped into a copy of the
benchmark's directories as files (the way the real ones were added, with
no edit to a file that was there), driven through ``run.py`` under
driver ``serve_model``; a lower precision in the program's place fails
the comparison; each new reader against hand-built facts and a
hand-built trace; the real files against the published sizes."""
import json
import os

import jax
import pytest
from tiny_root import REPO, build

from benchmark import harness, rooflines, run

PEAKS = {"flops_per_s": {"bfloat16": 1e12}, "hbm_bytes_per_s": 1e11,
         "hbm_bytes": 1e10}
V5E = {"flops_per_s": {"bfloat16": 197e12}, "hbm_bytes_per_s": 819e9}
CELL = "tiny-laguna.serve-mix"
REAL_CELL = "laguna-s-2.1.serve-mix-ep2share-s32"
NEW_READERS = ("decode_hbm_roofline.mix", "expert_read_roofline.mix",
               "experts_touched_pct.mix", "window_keys_read_pct.mix",
               "prefill_flash_roofline.mix")
SHARED_READERS = ("decode_step_ms.chat", "prefill_stall_ms.chat",
                  "tick_host_ms.chat", "engine_build_s.chat",
                  "program_first_call_s.chat")
FULL, SLIDING = "full_attention", "sliding_attention"


def _real(name):
    with open(os.path.join(REPO, "benchmark", name)) as f:
        return json.load(f)


def tiny_config():
    """The real configuration file at toy widths: window 8, 16 experts
    of which this share holds 0..7, both head counts and both rotary
    sets, the dense and the sparse feed-forward; the per-layer lists
    stay whole, as in the real file."""
    config = _real("configs/laguna-s-2.1.json")
    rope = config["rope_parameters"]
    rope[FULL].update(factor=8, original_max_position_embeddings=16,
                      attention_factor=1.2)
    config.update(
        name="tiny-laguna", source="https://example.org/tiny-laguna",
        vocab_size=128, hidden_size=64, intermediate_size=128,
        moe_intermediate_size=32, shared_expert_intermediate_size=32,
        num_key_value_heads=2, head_dim=16, num_experts=8,
        router_experts=16, experts_held=[0, 8], num_experts_per_tok=4,
        sliding_window=8, initializer_range=0.3,
        num_attention_heads_per_layer=[4, 6, 6, 6] * 12,
        model_options={"use_flash": False,
                       "an_option_a_later_pr_deleted": True})
    return config


# limit read on the CPU: the program (bfloat16) 0.15 - 0.93 over seeds
# 1-3, 5-8 and 2**31 + 35, and 4.62 on seed 4 (ONE token of 49, where
# a router's tenth and eleventh score swap under bfloat16; in float32
# the same run reads 0.0 everywhere); the fp8 control 5.0 - 7.8 over
# seeds 1-3, 7, 8 (std 0.3 weights at width 64 make logits tens apart)
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
              "served_logit_gap_max": 3.0},
}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    dst = build(str(tmp_path_factory.mktemp("laguna_root")))
    here = os.path.join(dst, "benchmark")
    with open(os.path.join(here, "configs", "tiny-laguna.json"), "w") as f:
        json.dump(tiny_config(), f)
    with open(os.path.join(here, "workloads", CELL + ".json"), "w") as f:
        json.dump(TINY_CELL, f)
    real = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
    spec = harness.load_json(os.path.join(dst, "BENCHMARK.json"))
    spec["configs"].append({
        "name": "tiny-laguna", "source": "https://example.org/tiny-laguna",
        "file": "benchmark/configs/tiny-laguna.json",
        "reduced": tiny_config()["reduced"], "why": "test"})
    spec["workloads"].append({"name": CELL, "config": "tiny-laguna",
                              "traffic": "serve-mix", "chips": 1,
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
    rc, line, out = drive(root, capsys, seed=2 ** 31 + 35)
    assert rc == 0 and line["correct"] is True
    assert line["failed"] == 0 and line["cut_off"] == 0
    assert set(line["metrics"]) == {"itl_p95_ms", "setup_s"}
    checks = [json.loads(x[6:]) for x in out if x.startswith("check ")]
    assert [c["name"] for c in checks] == ["served_logit_gap_max"]
    serve = json.loads(next(x for x in out if x.startswith("serve "))[6:])
    # both cache kinds on the line, a ring never over slots x 3 pages,
    # window pages taken over, and the experts' counters out of the step
    kinds = serve["pages_by_kind"]
    assert set(kinds) == {"global", "window"}
    assert 0 < kinds["window"]["peak_in_use"] <= 3 * 3
    assert kinds["window"]["capacity"] == 3 * 3
    assert serve["window_pages_recycled"] > 0
    assert 0.0 < serve["experts_touched_share"] <= 1.0
    assert serve["expert_rows_max_over_mean"] >= 1.0
    assert any("an_option_a_later_pr_deleted" in x for x in out)
    setup = json.loads(next(x for x in out if x.startswith("setup "))[6:])
    assert setup["weights_gb"] > 0


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


def test_the_real_files_are_the_published_sizes_cut_as_they_say():
    config = _real("configs/laguna-s-2.1.json")
    published = {
        "hidden_size": 3072, "head_dim": 128, "num_key_value_heads": 8,
        "num_attention_heads": 48, "intermediate_size": 12288,
        "moe_intermediate_size": 1024,
        "shared_expert_intermediate_size": 1024, "num_experts_per_tok": 10,
        "sliding_window": 512, "moe_routed_scaling_factor": 2.5,
        "norm_topk_prob": True, "rms_norm_eps": 1e-6,
        "mlp_only_layers": [0], "tie_word_embeddings": False,
        "gating": "per-head", "max_position_embeddings": 1048576}
    assert {k: config[k] for k in published} == published
    assert config["rope_parameters"][FULL] == {
        "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
        "original_max_position_embeddings": 8192, "beta_slow": 1,
        "beta_fast": 32, "attention_factor": 1.4852030263919618,
        "partial_rotary_factor": 0.5}
    assert config["rope_parameters"][SLIDING] == {
        "rope_type": "default", "rope_theta": 10000,
        "partial_rotary_factor": 1}
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 48,
                                   "num_experts": 256, "vocab_size": 100352}
    # the floors: a whole period and four layers behind the dense one,
    # 8 experts or more, an eighth of the rows or more
    assert config["num_hidden_layers"] == 5
    assert config["num_experts"] == 128 == config["experts_held"][1]
    assert config["router_experts"] == 256
    assert config["vocab_size"] * 2 == 100352
    from benchmark import program_laguna as adapter
    from benchmark import rooflines_laguna, weights_laguna

    sizes = adapter.sizes(config)
    assert sizes["layer_types"] == [FULL] + [SLIDING] * 3 + [FULL]
    assert sizes["num_attention_heads_per_layer"] == [48, 72, 72, 72, 48]
    # 11.0-11.3 GB of bfloat16 weights, 9.66 of them routed experts
    n = weights_laguna.n_params(sizes)
    assert 11.0e9 < 2 * n < 11.3e9
    held = 4 * 128 * rooflines_laguna.expert_bytes(sizes)
    assert held == pytest.approx(9.66e9, rel=0.01)
    outside = rooflines_laguna.params_outside_experts(sizes)
    assert 2 * n - held - 2 * 50176 * 3072 == 2 * outside
    cell = _real("workloads/" + REAL_CELL + ".json")
    assert cell["driver"] == "serve_model"
    assert cell["engine"] == {"num_slots": 32, "num_pages": 18432,
                              "page_size": 16, "max_context": 9216}
    t = cell["traffic"]
    assert t["prompt"] == {"dist": "lognormal", "median": 1024, "sigma": 1.2,
                           "min": 64, "max": 8192}
    assert t["output"] == {"dist": "lognormal", "median": 160, "sigma": 0.7,
                           "min": 32, "max": 640}
    assert t["prompt_buckets"] == [128, 256, 512, 1024, 1536, 2048, 3072,
                                   4096, 6144, 8192]
    assert "order_seed" in t and cell["drain_s"] == 12.0
    assert t["rate_per_s"] * 2 == int(t["rate_per_s"] * 2)   # steps of 0.5
    # every sequence the traffic can make has a padded length
    assert max(cell["check"]["pad_to"]) >= 8192 + 640
    spec = _real("../BENCHMARK.json")
    for m in spec["per_layer"]:
        if m["name"] in NEW_READERS:
            assert m["workloads"] == [REAL_CELL] and m["moves"] == "itl_p95_ms"
        if m["name"] in SHARED_READERS:
            assert REAL_CELL in m["workloads"]


# -- the readers ---------------------------------------------------------------

SIZES = {"hidden_size": 3072, "head_dim": 128, "num_key_value_heads": 8,
         "num_hidden_layers": 5, "intermediate_size": 12288,
         "moe_intermediate_size": 1024,
         "shared_expert_intermediate_size": 1024, "router_experts": 256,
         "experts_held": [0, 128], "mlp_only_layers": [0],
         "sliding_window": 512, "vocab_size": 50176,
         "layer_types": [FULL, SLIDING, SLIDING, SLIDING, FULL],
         "num_attention_heads_per_layer": [48, 72, 72, 72, 48]}


def _facts(**metrics):
    run_metrics = {"decode_steps": 2,
                   "experts": {"touched_by_step": [240, 200],
                               "touched_share": 0.43},
                   "window_key_share": 0.125}
    run_metrics.update(metrics)
    return {"sizes": SIZES, "peaks": V5E, "dtype": "bfloat16",
            "ticks": [(0.1, 0), (0.2, 20000), (0.3, 30000)],
            "live_window": [0, 6000, 8000], "run_metrics": run_metrics}


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
    assert _read("experts_touched_pct.mix", _facts()) == pytest.approx(43.0)
    assert _read("window_keys_read_pct.mix", _facts()) == pytest.approx(12.5)
    # a program without the counters (the parent): nothing to read
    assert _read("experts_touched_pct.mix", _facts(experts=None)) is None
    assert _read("window_keys_read_pct.mix",
                 _facts(window_key_share=None)) is None


def test_decode_roofline_counts_touched_experts_and_keys_by_kind():
    from benchmark import rooflines_laguna as rl

    steps = [rl.decode_step_bytes(SIZES, 240, 20000, 6000),
             rl.decode_step_bytes(SIZES, 200, 30000, 8000)]
    # outside the experts 1.17 GB, an expert 18.9 MB, a key 4 KB a layer
    assert steps[0] == pytest.approx(
        2 * rl.params_outside_experts(SIZES) + 240 * 18874368
        + 4096 * (2 * 20000 + 3 * 6000))
    ns = int(2 * sum(steps) / 819e9 * 1e9)     # at half the roofline
    modules = [("jit__step(123)", 0, ns // 2), ("jit__prefill(9)", ns, 2 * ns),
               ("jit__step(123)", 3 * ns, 3 * ns + ns // 2)]
    assert _read("decode_hbm_roofline.mix", _facts(), [],
                 modules) == pytest.approx(50.0, rel=1e-3)
    assert _read("decode_hbm_roofline.mix", _facts()) is None    # no trace
    assert _read("decode_hbm_roofline.mix", _facts(experts=None), [],
                 modules) is None


def test_expert_read_roofline_takes_the_calls_inside_the_decode_program():
    nbytes = (240 + 200) * 18874368
    ns = int(4 * nbytes / 819e9 * 1e9)         # a quarter of the roofline
    modules = [("jit__step(1)", 0, ns), ("jit__prefill(2)", 2 * ns, 9 * ns),
               ("jit__step(1)", 10 * ns, 11 * ns)]
    ops = [("%ragged-dot-metadata.3 = (s32[129]) custom-call(%g)", 0, 0),
           ("%ragged-dot-none.11 = bf16[320,1024] custom-call(%x)", 0,
            ns // 2),
           ("%ragged-dot-none.9 = bf16[320,3072] custom-call(%x)",
            10 * ns, 10 * ns + ns // 2),
           # the prefill's grouped products are not the decode step's
           ("%ragged-dot-none.2 = bf16[20480,1024] custom-call(%x)",
            3 * ns, 8 * ns),
           ("%fusion.3 = bf16[8] fusion(%ragged-dot-none.2)", 0, ns)]
    assert _read("expert_read_roofline.mix", _facts(), ops,
                 modules) == pytest.approx(25.0, rel=1e-3)
    assert _read("expert_read_roofline.mix", _facts(), [
        ("%fusion = f32[8] fusion()", 0, 5)], modules) is None


def test_flash_roofline_counts_the_pairs_the_masks_keep():
    from benchmark import rooflines_laguna as rl

    assert rl.kept_pairs(4, None) == 10
    assert rl.kept_pairs(8192, 512) == 512 * 513 // 2 + (8192 - 512) * 512
    assert rl.kept_pairs(256, 512) == rl.kept_pairs(256, None)
    full = rl.flash_fwd_cost(8192, 48, 8, 128, None)
    win = rl.flash_fwd_cost(8192, 72, 8, 128, 512)
    # a window layer at 72 heads computes an eighth of a full one's pairs
    assert win[0] / full[0] == pytest.approx(
        72 / 48 * rl.kept_pairs(8192, 512) / rl.kept_pairs(8192), rel=1e-9)
    least = sum(max(f / 197e12, b / 819e9) for f, b in (full, win))
    ns = int(5 * least * 1e9)
    ops = [("%flash_fwd.1 = (bf16[48,8192,128], f32[48,8192]) "
            "custom-call(%q)", 0, ns // 2),
           ("%flash_fwd.2 = (bf16[72,8192,128], f32[72,8192]) "
            "custom-call(%q)", ns, ns + ns // 2),
           ("%flash_dq.1 = bf16[72,8192,128] custom-call(%q)", 0, 9 * ns),
           ("%flash_fwd.7 = (bf16[20,4096,256]) custom-call(%q)", 0, 9 * ns)]
    assert _read("prefill_flash_roofline.mix", _facts(),
                 ops) == pytest.approx(20.0, rel=1e-3)
    for name in ("prefill_flash_roofline.mix", "expert_read_roofline.mix"):
        assert _read(name, _facts(), None) is None
