"""LongCat-Flash's serving cell at a tiny size on the CPU: the
configuration, its cell and its readers dropped into a copy of the
benchmark's directories as files (the way the real ones were added, with
no edit to a file that was there), driven through ``run.py`` under
driver ``serve_model``; a lower precision in the program's place fails
the comparison; each new reader against hand-built facts and a
hand-built trace; the real files against the catalog row and the issue's
counts."""
import json
import os

import jax
import pytest
from tiny_root import REPO, build

from benchmark import harness, rooflines, run

PEAKS = {"flops_per_s": {"bfloat16": 1e12}, "hbm_bytes_per_s": 1e11,
         "hbm_bytes": 1e10}
V5E = {"flops_per_s": {"bfloat16": 197e12}, "hbm_bytes_per_s": 819e9}
CELL = "tiny-longcat.serve-doc"
REAL_CELL = "longcat-flash-omni.serve-doc-ep32share-s16"
NEW_READERS = ("decode_hbm_roofline.mla", "expert_read_roofline.mla",
               "zero_expert_pick_pct.mla", "prefill_flash_roofline.mla")
SHARED_READERS = ("decode_step_ms.chat", "prefill_stall_ms.chat",
                  "tick_host_ms.chat", "engine_build_s.chat",
                  "program_first_call_s.chat", "decode_keys_read_pct.chat",
                  "step_launch_ms.serve",
                  "step_return_ms.serve", "step_upload_ms.serve",
                  "device_gap_ms.serve", "prefill_device_busy_pct.serve")

# the catalog row's ``config`` (model-configs/architectures.jsonl,
# LongCat-Flash-Omni), every key
CATALOG = {
    "attention_bias": False, "vocab_size": 131072, "hidden_size": 6144,
    "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048,
    "num_layers": 28, "num_attention_heads": 64, "kv_lora_rank": 512,
    "q_lora_rank": 1536, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "qk_nope_head_dim": 128, "mla_scale_q_lora": True,
    "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
    "n_routed_experts": 512, "max_position_embeddings": 131072,
    "rms_norm_eps": 1e-05, "rope_theta": 10000000,
    "attention_method": "MLA", "zero_expert_num": 256,
    "zero_expert_type": "identity", "moe_topk": 12,
}
REDUCED = {"num_layers": 4, "n_routed_experts": 16, "vocab_size": 16384}


def _real(name):
    with open(os.path.join(REPO, "benchmark", name)) as f:
        return json.load(f)


def tiny_config():
    """The real configuration file at toy widths, with the published
    shape of things: keys wider than values, more experts routed to than
    held, zero-compute experts, a bias that moves picks."""
    config = _real("configs/longcat-flash-omni.json")
    config.update(
        name="tiny-longcat", source="https://example.org/tiny-longcat",
        vocab_size=128, hidden_size=64, ffn_hidden_size=96,
        expert_ffn_hidden_size=32, num_layers=2, num_attention_heads=4,
        kv_lora_rank=32, q_lora_rank=48, qk_rope_head_dim=8, v_head_dim=12,
        qk_nope_head_dim=16, n_routed_experts=2, router_experts=8,
        experts_held=[0, 2], zero_expert_num=4, moe_topk=3,
        initializer_range=0.1, router_bias_std=0.03,
        model_options={"use_flash": False, "ffn_block_tokens": 16,
                       "an_option_a_later_pr_deleted": True})
    return config


# limit read on the CPU (``read_limits.py`` over this root): the program
# (bfloat16) 0.0 - 0.0072 on seeds 1-8 and 2**31 + 45; the fp8 control on
# the served samples of seeds 1-5 0.228 - 1.99, and over the 97 positions
# of the test below 0.38 - 0.97
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
              "served_logit_gap_max": 0.04},
}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    dst = build(str(tmp_path_factory.mktemp("longcat_root")))
    here = os.path.join(dst, "benchmark")
    with open(os.path.join(here, "configs", "tiny-longcat.json"), "w") as f:
        json.dump(tiny_config(), f)
    with open(os.path.join(here, "workloads", CELL + ".json"), "w") as f:
        json.dump(TINY_CELL, f)
    real = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
    spec = harness.load_json(os.path.join(dst, "BENCHMARK.json"))
    spec["configs"].append({
        "name": "tiny-longcat", "source": "https://example.org/tiny-longcat",
        "file": "benchmark/configs/tiny-longcat.json",
        "reduced": tiny_config()["reduced"], "why": "test"})
    spec["workloads"].append({"name": CELL, "config": "tiny-longcat",
                              "traffic": "serve-doc", "chips": 1,
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
    rc, line, out = drive(root, capsys, seed=2 ** 31 + 45)
    assert rc == 0 and line["correct"] is True
    assert line["failed"] == 0 and line["cut_off"] == 0
    assert set(line["metrics"]) == {"itl_p95_ms", "setup_s"}
    checks = [json.loads(x[6:]) for x in out if x.startswith("check ")]
    assert [c["name"] for c in checks] == ["served_logit_gap_max"]
    serve = json.loads(next(x for x in out if x.startswith("serve "))[6:])
    # one cache kind of pages; experts on the line, no window
    assert set(serve["pages_by_kind"]) == {"global"}
    assert serve["window_pages_recycled"] is None
    assert 0 < serve["experts_touched_share"] <= 1
    assert serve["prefills"] >= 6 and serve["decode_steps"] > 0
    assert any("an_option_a_later_pr_deleted" in x for x in out)
    setup = json.loads(next(x for x in out if x.startswith("setup "))[6:])
    assert setup["weights_gb"] > 0


def test_the_readers_read_a_run_of_the_cell(root, capsys, no_chip_check):
    """A run's result through every reader the cell lists (``run.py``'s
    own loop). With no device trace (the CPU has none) the readers of
    the trace find nothing and leave their metric out; the counters'
    readers read the engine's own ``finish_run()``."""
    spec, driver, ctx, here = run.open_cell(root, CELL, 5, 1.0, False)
    result = driver.run(ctx)
    capsys.readouterr()
    got = run.layer_metrics(spec, CELL, result, here)
    assert 0 < got["zero_expert_pick_pct.mla"]["value"] < 100
    assert 0 < got["decode_keys_read_pct.chat"]["value"] <= 100
    assert got["decode_step_ms.chat"]["value"] > 0
    for name in ("decode_hbm_roofline.mla", "expert_read_roofline.mla",
                 "prefill_flash_roofline.mla"):
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
    ctx.sample = [(rng.integers(1, 128, size=n).astype(np.int32), 4)
                  for n in (60, 37)]
    assert not driver.control(ctx).correct
    same, _ = driver.score(ctx, ctx.sample, picks="lower",
                           precision="float32")
    assert same == 0.0


def test_the_real_files_are_the_catalog_row_cut_as_they_say():
    config = _real("configs/longcat-flash-omni.json")
    # every key of the catalog row under the same name, the three
    # reduced ones apart, and those are what ``reduced`` lists
    assert {k: config[k] for k in CATALOG} == {**CATALOG, **REDUCED}
    assert config["reduced"] == list(REDUCED)
    assert config["published"] == {k: CATALOG[k] for k in REDUCED}
    assert config["source"] == ("https://huggingface.co/meituan-longcat/"
                                "LongCat-Flash-Omni/blob/main/config.json")
    assert config["router_experts"] == 512
    assert config["experts_held"] == [0, 16]
    assert config["norm_topk_prob"] is False and config["dtype"] == "bfloat16"
    assert "32 chips share each layer" in config["deployment"]
    assert "pipeline stages" in config["deployment"]
    assert {"norm_topk_prob", "mla_scales", "rope_pairing",
            "initializer_range", "router_bias_std"} <= set(config["assumed"])
    assert "encoders" in config["not_part_of_it"]
    from benchmark import program_longcat_flash as adapter
    from benchmark import rooflines_longcat_flash as rl
    from benchmark import weights_longcat_flash

    sizes = adapter.sizes(config)
    # the issue's arithmetic: one latent attention 90.57 M, a block
    # outside its routed experts 638.9 M, an expert 37.75 M
    assert rl.mla_params(sizes) == 90570752
    assert round(rl.block_params_outside_experts(sizes) / 1e6, 1) == 638.9
    assert rl.expert_params(sizes) == 3 * 6144 * 2048 == 37748736
    # this chip's share: 5.173 B parameters, 10.35 GB, counted two ways
    n = weights_longcat_flash.n_params(sizes)
    assert n == rl.held_params(sizes)
    assert round(n / 1e9, 3) == 5.173 and round(2 * n / 1e9, 2) == 10.35
    # the whole model by the same functions: the published 560 B
    whole = dict(sizes, num_layers=28, vocab_size=131072,
                 experts_held=[0, 512])
    assert round(rl.held_params(whole) / 1e9, 1) == 560.7
    # a cached token: 576 lanes x 2 B x 8 attentions
    assert rl.latent_bytes_per_token(sizes) == 9216
    # a decode step reads everything but the embedding and the experts it
    # did not touch
    h = 6144
    assert rl.params_outside_experts(sizes) == \
        n - 16384 * h - 4 * 16 * rl.expert_params(sizes)
    # the program's own configuration takes every published key
    cfg = adapter.make_config(config)
    assert (cfg.row_lanes, cfg.router_outputs, cfg.held) == (576, 768,
                                                            (0, 16))
    assert (cfg.scale_q, cfg.use_flash) == (2.0, True)
    assert cfg.paged_model().latent.stored == 640
    cell = _real("workloads/" + REAL_CELL + ".json")
    assert cell["driver"] == "serve_model"
    assert cell["engine"] == {"num_slots": 16, "num_pages": 9216,
                              "page_size": 16, "max_context": 9216}
    t = cell["traffic"]
    assert t["order_seed"] == 4501 and cell["drain_s"] == 20.0
    assert t["prompt"] == {"dist": "lognormal", "median": 4096, "sigma": 0.6,
                           "min": 512, "max": 8192}
    assert t["output"] == {"dist": "lognormal", "median": 256, "sigma": 0.7,
                           "min": 32, "max": 768}
    assert t["prompt_buckets"] == [512, 1024, 2048, 3072, 4096, 6144, 8192]
    assert t["rate_per_s"] % 0.25 == 0
    # every sequence the traffic can make has a padded length, and fits
    assert max(cell["check"]["pad_to"]) >= 8192 + 768
    assert cell["engine"]["max_context"] >= 8192 + 768
    assert cell["check"]["sample_requests"] == 12
    spec = _real("../BENCHMARK.json")
    entry = next(c for c in spec["configs"]
                 if c["name"] == "longcat-flash-omni")
    assert entry["source"] == config["source"]
    assert entry["reduced"] == config["reduced"]
    assert entry["file"] == "benchmark/configs/longcat-flash-omni.json"
    real_cell = next(w for w in spec["workloads"] if w["name"] == REAL_CELL)
    assert real_cell["chips"] == 1
    assert len(spec["workloads"]) <= 24
    assert sum(w["chips"] == 4 for w in spec["workloads"]) == 1
    itl = next(m for m in spec["end_to_end"] if m["name"] == "itl_p95_ms")
    assert REAL_CELL in itl["workloads"]
    # the new metrics stand at the END of the list, in this order
    assert [m["name"] for m in spec["per_layer"]][-4:] == list(NEW_READERS)
    for m in spec["per_layer"]:
        if m["name"] in NEW_READERS:
            assert m["workloads"] == [REAL_CELL] and m["moves"] == "itl_p95_ms"
        if m["name"] in SHARED_READERS:
            assert m["workloads"][-1] == REAL_CELL
    # the mix cell's own test holds ``experts_touched_pct.mix`` to that
    # cell alone: this cell's share of experts touched is a fact on its
    # ``serve`` line, and a reader's once a ``benchmark`` PR rewords it
    touched = next(m for m in spec["per_layer"]
                   if m["name"] == "experts_touched_pct.mix")
    assert REAL_CELL not in touched["workloads"]


# -- the readers ---------------------------------------------------------------

SIZES = {"vocab_size": 16384, "hidden_size": 6144, "ffn_hidden_size": 12288,
         "expert_ffn_hidden_size": 2048, "num_layers": 4,
         "num_attention_heads": 64, "kv_lora_rank": 512, "q_lora_rank": 1536,
         "qk_rope_head_dim": 64, "v_head_dim": 128, "qk_nope_head_dim": 128,
         "router_experts": 512, "zero_expert_num": 256,
         "experts_held": [0, 16]}
# another family's sizes: what the other cells' lines hand a reader
OTHER_SIZES = {"hidden_size": 3072, "head_dim": 128, "num_key_value_heads": 8}


def _facts(sizes=SIZES, **experts):
    base = {"touched_by_step": [10, 30], "touched_share": 0.3125,
            "zero_pick_share": 0.34}
    base.update(experts)
    return {"sizes": sizes, "peaks": V5E, "dtype": "bfloat16",
            "ticks": [(0.1, 0), (0.2, 20000), (0.3, 30000)],
            "live_window": [0, 0, 0],
            "run_metrics": {"decode_steps": 2, "experts": base}}


def _read(name, facts, modules=None, ops=()):
    reader = harness.load_module(os.path.join(
        REPO, "benchmark", "layer_metrics", name + ".py"))
    trace = None if modules is None else {
        "devices": [{"busy_ns": 1, "ops": list(ops),
                     "modules": list(modules)}]}
    result = harness.Result(end_to_end={}, attempted=1, failed=0,
                            t_window_start=0.0, memory_peak_bytes=0,
                            facts=facts, trace=trace)
    return reader.read(result)


def test_the_counter_reader_reads_the_engines_counter():
    assert _read("zero_expert_pick_pct.mla", _facts()) == pytest.approx(34.0)
    # a program whose experts bring no such counter, or none at all
    assert _read("zero_expert_pick_pct.mla",
                 _facts(zero_pick_share=None)) is None
    facts = _facts()
    facts["run_metrics"]["experts"] = None
    assert _read("zero_expert_pick_pct.mla", facts) is None


def test_decode_roofline_counts_weights_touched_experts_and_latent_rows():
    from benchmark import rooflines_longcat_flash as rl

    weights = 2 * rl.params_outside_experts(SIZES)
    assert rl.decode_step_bytes(SIZES, 10, 20000) == pytest.approx(
        weights + 10 * 3 * 6144 * 2048 * 2 + 20000 * 9216)
    total = 2 * weights + 40 * rl.expert_bytes(SIZES) + 50000 * 9216
    ns = int(2 * total / 819e9 * 1e9)          # at half the roofline
    modules = [("jit__step(123)", 0, ns // 2), ("jit__prefill(9)", ns, 2 * ns),
               ("jit__step(123)", 3 * ns, 3 * ns + ns // 2)]
    assert _read("decode_hbm_roofline.mla", _facts(),
                 modules) == pytest.approx(50.0, rel=1e-3)
    assert _read("decode_hbm_roofline.mla", _facts()) is None    # no trace
    assert _read("decode_hbm_roofline.mla", _facts(touched_by_step=None),
                 modules) is None
    # another family's line (its sizes hold no latent row)
    assert _read("decode_hbm_roofline.mla", _facts(OTHER_SIZES),
                 modules) is None


def test_expert_read_takes_the_grouped_products_inside_the_step():
    from benchmark import rooflines_longcat_flash as rl

    nbytes = 40 * rl.expert_bytes(SIZES)
    ns = int(nbytes / 819e9 * 1e9)
    modules = [("jit__step(1)", 0, 10 * ns),
               ("jit__prefill(2)", 20 * ns, 40 * ns),
               ("jit__step(1)", 50 * ns, 60 * ns)]
    ops = [("%ragged-dot-none.1 = bf16[192,2048] custom-call(..)", ns, 3 * ns),
           ("%ragged-dot-metadata = (s32[17]) custom-call(..)", 3 * ns,
            4 * ns),
           # a prefill's grouped product: outside the decode program
           ("%ragged-dot-none.2 = bf16[24576,2048] custom-call(..)", 21 * ns,
            30 * ns),
           ("%fusion.3 = bf16[16,6144] fusion(..)", 5 * ns, 6 * ns)]
    assert _read("expert_read_roofline.mla", _facts(), modules,
                 ops) == pytest.approx(100.0 / 3, rel=1e-3)
    assert _read("expert_read_roofline.mla", _facts(), modules,
                 ops[3:]) is None
    assert _read("expert_read_roofline.mla", _facts(OTHER_SIZES), modules,
                 ops) is None
    assert _read("expert_read_roofline.mla", _facts()) is None


def test_prefill_flash_counts_the_true_widths_so_padding_reads_as_lost():
    from benchmark import rooflines_longcat_flash as rl

    flops, nbytes = rl.flash_fwd_cost(4096, SIZES)
    pairs = 4096 * 4097 // 2
    assert flops == 2.0 * 64 * pairs * (192 + 128)
    assert nbytes == 64 * 4096 * (2 * 320 * 2 + 4)
    least = rooflines.least_time_s(flops, nbytes, V5E)[0]
    ns = int(2 * least * 1e9)
    ops = [("%flash_fwd.7 = (bf16[64,4096,192], f32[64,4096,1]) "
            "custom-call(..)", 0, ns),
           # another model's call (other heads) and another kernel
           ("%flash_fwd.9 = (bf16[48,4096,128]) custom-call(..)", ns, 3 * ns),
           ("%fusion.1 = bf16[64,4096,192] fusion(..)", 3 * ns, 4 * ns)]
    assert _read("prefill_flash_roofline.mla", _facts(), [],
                 ops) == pytest.approx(50.0, rel=1e-3)
    # at equal time a kernel padded to 256 lanes does the same counted
    # work: the share does not rise with the padding
    wide = [(ops[0][0].replace("192", "256"), 0, ns)]
    assert _read("prefill_flash_roofline.mla", _facts(), [],
                 wide) == pytest.approx(50.0, rel=1e-3)
    assert _read("prefill_flash_roofline.mla", _facts(), [],
                 ops[1:]) is None
    assert _read("prefill_flash_roofline.mla", _facts(OTHER_SIZES), [],
                 ops) is None
    assert _read("prefill_flash_roofline.mla", _facts()) is None
