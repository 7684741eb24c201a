"""GLM-4.7-Flash's cell at a tiny size on the CPU: the configuration,
its cell and its readers dropped into a copy of the benchmark's
directories as files (the way the real ones were added, with no edit to
a file that was there), driven through ``run.py``; a lower precision in
the program's place fails the comparison; each new reader against
hand-built facts and a hand-built trace."""
import json
import os

import jax
import pytest
from tiny_root import REPO, build

from benchmark import harness, rooflines, run

PEAKS = {"flops_per_s": {"bfloat16": 1e12}, "hbm_bytes_per_s": 1e11,
         "hbm_bytes": 1e10}
CELL = "tiny-glm.train"
NEW_READERS = ("mfu_pct.train-moe", "mla_flash_roofline.train-moe",
               "expert_mm_roofline.train-moe", "fused_ce_roofline.train-moe",
               "expert_load_max_over_mean.train-moe")


def _real(name):
    with open(os.path.join(REPO, "benchmark", name)) as f:
        return json.load(f)


def tiny_config():
    """The real configuration file at toy widths: 16 experts of which
    this share holds 4..7, 500 rows of the vocabulary padded to 512."""
    config = _real("configs/glm-4.7-flash.json")
    config.update(
        name="tiny-glm", source="https://example.org/tiny-glm",
        hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=4,
        n_routed_experts=4, router_experts=16, experts_held=[4, 4],
        q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=12,
        qk_rope_head_dim=4, v_head_dim=16, vocab_size=500, vocab_pad_to=64,
        initializer_range=0.08,
        model_options={"remat": True, "use_flash": False, "fused_ce": False,
                       "an_option_a_later_pr_deleted": True})
    return config


# limits read on the CPU (seeds 1-6 of the program, 1-3 of the fp8
# control): loss 2.7e-5 - 2.1e-4 against 1.7e-3 - 2.5e-3, gradient
# 0.003 - 0.017 against 0.050 - 0.121; the parameters' change 0.005 -
# 0.007 against 0.011 - 0.016 (a step that leaves its state unchanged
# reads 1.0, which is what that limit is held against)
TINY_CELL = {
    "driver": "train_model",
    "mesh": {"tensor": 1, "data": 1},
    "global_batch": 4, "seq": 32, "learning_rate": 3e-4,
    "check": {"steps": 2, "reference_rows_per_call": 2,
              "loss_rel_gap_max": 6e-4, "grad_norm_gap_max": 0.03,
              "param_change_gap_max": 0.3},
}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    dst = build(str(tmp_path_factory.mktemp("glm_root")))
    here = os.path.join(dst, "benchmark")
    with open(os.path.join(here, "configs", "tiny-glm.json"), "w") as f:
        json.dump(tiny_config(), f)
    with open(os.path.join(here, "workloads", CELL + ".json"), "w") as f:
        json.dump(TINY_CELL, f)
    real = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
    spec = harness.load_json(os.path.join(dst, "BENCHMARK.json"))
    spec["configs"].append({
        "name": "tiny-glm", "source": "https://example.org/tiny-glm",
        "file": "benchmark/configs/tiny-glm.json",
        "reduced": tiny_config()["reduced"], "why": "test"})
    spec["workloads"].append({"name": CELL, "config": "tiny-glm",
                              "traffic": "train", "chips": 1, "why": "test"})
    spec["end_to_end"][0]["workloads"].append(CELL)
    for m in real["per_layer"]:
        if m["name"] in NEW_READERS + ("step_ms.train", "peak_hbm_gb.train"):
            spec["per_layer"] = [x for x in spec["per_layer"]
                                 if x["name"] != m["name"]] + [
                dict(m, workloads=[CELL] + (
                    ["tiny.train"] if m["name"] == "step_ms.train" else []))]
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
    rc, line, out = drive(root, capsys, seed=2 ** 31 + 29)
    assert rc == 0 and line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    checks = [json.loads(x[6:]) for x in out if x.startswith("check ")]
    assert {c["name"] for c in checks} == {
        "loss_rel_gap_max", "grad_norm_gap_worst_leaf",
        "param_change_gap_worst_leaf"}
    # the step's counters reach the driver's line
    train = json.loads(next(x for x in out if x.startswith("train "))[6:])
    first = train["first_counters"][0]
    assert len(first["rows_per_expert"]) == 3          # 2 layers + MTP
    assert 0.0 < first["local_pick_share"] < 1.0
    assert first["loss_main"] > 0 and first["loss_mtp"] > 0
    assert any("an_option_a_later_pr_deleted" in x for x in out)


def test_a_lower_precision_in_the_programs_place_fails_the_comparison(root):
    """The reference at fp8, held to the tiny cell's limits through the
    driver's own ``control``."""
    spec, driver, ctx, _ = _open(root, seed=3)
    ctx.reference = driver._reference_steps(ctx, "float32")
    assert not driver.control(ctx).correct
    same = harness.Checks()
    train = harness.load_module(
        os.path.join(REPO, "benchmark", "drivers", "train.py"))
    ref = ctx.reference
    train.compare(same, ref["losses"],
                  {k: v * (1 - train.ADAM_B1)
                   for k, v in ref["grad_norm"].items()},
                  ref["delta_norm"], ref, TINY_CELL["check"])
    assert same.correct


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


def test_the_real_configuration_is_the_published_one_cut_as_it_says():
    config = _real("configs/glm-4.7-flash.json")
    published = {
        "hidden_size": 2048, "intermediate_size": 10240,
        "moe_intermediate_size": 1536, "num_attention_heads": 20,
        "num_key_value_heads": 20, "n_shared_experts": 1,
        "routed_scaling_factor": 1.8, "num_experts_per_tok": 4,
        "first_k_dense_replace": 1, "num_nextn_predict_layers": 1,
        "q_lora_rank": 768, "kv_lora_rank": 512, "qk_nope_head_dim": 192,
        "qk_rope_head_dim": 64, "v_head_dim": 256, "rope_theta": 1000000,
        "rms_norm_eps": 1e-5, "n_group": 1, "topk_group": 1,
        "max_position_embeddings": 202752, "norm_topk_prob": True,
        "tie_word_embeddings": False}
    assert {k: config[k] for k in published} == published
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 47,
                                   "n_routed_experts": 64,
                                   "vocab_size": 154880}
    # the floors: four expert layers, 8 experts, an eighth of the rows
    assert config["num_hidden_layers"] - config["first_k_dense_replace"] == 4
    assert config["n_routed_experts"] == 8 == config["experts_held"][1]
    assert config["router_experts"] == 64
    assert config["vocab_size"] * 8 == 154880
    from benchmark import program_glm4_moe_lite as adapter
    from benchmark import weights_glm4_moe_lite as weights

    assert 700e6 < weights.n_params(adapter.sizes(config)) < 712e6
    cell = _real("workloads/glm-4.7-flash.train-ep8share-b4s4096.json")
    assert (cell["global_batch"], cell["seq"]) == (4, 4096)
    assert cell["mesh"] == {"tensor": 1, "data": 1}


# -- the readers ---------------------------------------------------------------

SIZES = {"hidden_size": 2048, "moe_intermediate_size": 1536,
         "intermediate_size": 10240, "num_hidden_layers": 5,
         "first_k_dense_replace": 1, "num_nextn_predict_layers": 1,
         "num_attention_heads": 20, "q_lora_rank": 768, "kv_lora_rank": 512,
         "qk_nope_head_dim": 192, "qk_rope_head_dim": 64, "v_head_dim": 256,
         "n_shared_experts": 1, "num_experts_per_tok": 4,
         "router_experts": 64, "experts_held": [0, 8], "vocab_size": 19360}
V5E = {"flops_per_s": {"bfloat16": 197e12}, "hbm_bytes_per_s": 819e9}


def _facts(**more):
    rows = [[1024.0] * 8] * 5
    facts = {"sizes": SIZES, "seq": 4096, "batch": 4, "rows_per_replica": 4,
             "tensor": 1, "chips": 1, "tokens_per_step": 16384,
             "peaks": V5E, "dtype": "bfloat16", "step_s": [1.0, 1.0],
             "counters": [{"rows_per_expert": rows}] * 2}
    facts.update(more)
    return facts


def _read(name, facts, ops=None):
    reader = harness.load_module(os.path.join(
        REPO, "benchmark", "layer_metrics", name + ".py"))
    trace = None if ops is None else {
        "devices": [{"busy_ns": 1, "ops": ops}]}
    result = harness.Result(end_to_end={}, attempted=1, failed=0,
                            t_window_start=0.0, memory_peak_bytes=0,
                            facts=facts, trace=trace)
    return reader.read(result)


def test_mfu_counts_the_rows_the_counters_say_were_routed():
    from benchmark import rooflines_glm4_moe_lite as moe

    forward = moe.forward_flops_per_token(SIZES, 4096)
    assert forward == pytest.approx(0.957e9, rel=0.01)      # ISSUE: ~0.96
    got = _read("mfu_pct.train-moe", _facts())
    assert got == pytest.approx(100 * 3 * forward * 16384 / 197e12, rel=1e-6)
    # twice the rows on the held experts: more work, a higher share
    busy = [{"rows_per_expert": [[2048.0] * 8] * 5}]
    assert _read("mfu_pct.train-moe", _facts(counters=busy)) > got
    # a step that left no counters, or no fenced steps: nothing to read
    assert _read("mfu_pct.train-moe", _facts(counters=[])) is None
    assert _read("mfu_pct.train-moe", _facts(step_s=[])) is None


def test_load_reader_is_one_for_an_even_load_and_the_skew_otherwise():
    assert _read("expert_load_max_over_mean.train-moe",
                 _facts()) == pytest.approx(1.0)
    skew = [{"rows_per_expert": [[8.0, 0, 0, 0, 0, 0, 0, 0],
                                 [1.0] * 8]}]
    assert _read("expert_load_max_over_mean.train-moe",
                 _facts(counters=skew)) == pytest.approx((8.0 + 1.0) / 2)
    assert _read("expert_load_max_over_mean.train-moe",
                 _facts(counters=[])) is None


def test_kernel_readers_find_their_calls_by_name():
    from benchmark import rooflines_glm4_moe_lite as moe

    flops, nbytes = moe.grouped_mm_call_cost(8192.0, SIZES)
    least = max(flops / 197e12, nbytes / 819e9)
    ms = int(2 * least * 1e9)           # every call at half its roofline
    ops = [("%ragged-dot-metadata.1 = (s32[9]) custom-call(%gs)", 0, 0),
           ("%ragged-dot-none.2 = f32[65536,1536] custom-call(%x)", 0, ms),
           ("%ragged-dot-none = f32[8,2048,1536] custom-call(%x)", ms, 2 * ms),
           ("%fusion.3 = bf16[8] fusion(%ragged-dot-none.2)", 0, 10 * ms)]
    assert _read("expert_mm_roofline.train-moe", _facts(),
                 ops) == pytest.approx(50.0, rel=1e-3)
    f, b = rooflines.flash_call_cost("fwd", 4, 4096, 20, 256)
    t = int(4 * max(f / 197e12, b / 819e9) * 1e9)
    flash = [("%flash_fwd.1 = (bf16[80,4096,256]) custom-call(%q)", 0, t),
             ("%flash_ring_fwd.1 = (f32[8]) custom-call(%q)", 0, 5 * t)]
    assert _read("mla_flash_roofline.train-moe", _facts(),
                 flash) == pytest.approx(25.0, rel=1e-3)
    ce = [("%transpose_jvp_fused_ce_dw__.2 = bf16[19456,2048] "
           "custom-call(%h)", 0, 10 ** 8)]
    got = _read("fused_ce_roofline.train-moe", _facts(), ce)
    want = 100 * (2 * 2.0 * 4 * 4095 * 19360 * 2048 / 197e12) / 0.1
    assert got == pytest.approx(want, rel=1e-6)      # 19,360 rows of work
    for name in NEW_READERS[1:4]:
        assert _read(name, _facts(), None) is None   # no trace
        assert _read(name, _facts(), [("%fusion = f32[8] fusion()", 0, 5)]
                     ) is None                       # no such kernel
