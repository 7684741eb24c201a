"""Falcon-H1's serving cell at a tiny size on the CPU: the
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
CELL = "tiny-falcon.serve-chat"
REAL_CELL = "falcon-h1-34b.serve-chat-s64"
NEW_READERS = ("decode_hbm_roofline.ssm", "state_rows_useful_pct.ssm",
               "state_write_device_ms.ssm")
SHARED_READERS = ("decode_step_ms.chat", "prefill_stall_ms.chat",
                  "tick_host_ms.chat", "engine_build_s.chat",
                  "program_first_call_s.chat")


# every key of the published config.json but num_hidden_layers (72)
PUBLISHED = {
    "attention_bias": False,
    "attention_in_multiplier": 1,
    "attention_out_multiplier": 0.0375,
    "attn_layer_indices": None,
    "embedding_multiplier": 5.656854249492381,
    "head_dim": 128,
    "hidden_act": "silu",
    "hidden_size": 5120,
    "intermediate_size": 21504,
    "key_multiplier": 0.011048543456039804,
    "lm_head_multiplier": 0.0078125,
    "mamba_chunk_size": 128,
    "mamba_conv_bias": True,
    "mamba_d_conv": 4,
    "mamba_d_head": 128,
    "mamba_d_ssm": 4096,
    "mamba_d_state": 256,
    "mamba_expand": 2,
    "mamba_n_groups": 2,
    "mamba_n_heads": 32,
    "mamba_norm_before_gate": False,
    "mamba_proj_bias": False,
    "mamba_rms_norm": True,
    "mamba_use_mlp": True,
    "max_position_embeddings": 262144,
    "mlp_bias": False,
    "mlp_expansion_factor": 8,
    "mlp_multipliers": [
        0.1767766952966369,
        0.011160714285714284
    ],
    "model_type": "falcon_h1",
    "num_attention_heads": 20,
    "num_key_value_heads": 4,
    "num_logits_to_keep": 1,
    "projectors_bias": False,
    "rms_norm_eps": 1e-05,
    "rope_scaling": None,
    "rope_theta": 100000000000,
    "ssm_in_multiplier": 0.25,
    "ssm_multipliers": [
        0.3535533905932738,
        0.25,
        0.1767766952966369,
        0.5,
        0.3535533905932738
    ],
    "ssm_out_multiplier": 0.08838834764831845,
    "tie_word_embeddings": False,
    "vocab_size": 261120
}


def _real(name):
    with open(os.path.join(REPO, "benchmark", name)) as f:
        return json.load(f)


def tiny_config():
    """The real configuration file at toy widths; the multipliers as
    published but for the head's and the embedding's (logits of order 1,
    which the tiny cell's limit is read against)."""
    config = _real("configs/falcon-h1-34b.json")
    config.update(
        name="tiny-falcon", source="https://example.org/tiny-falcon",
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, mamba_d_ssm=64, mamba_n_heads=4, mamba_d_head=16,
        mamba_n_groups=2, mamba_d_state=8, mamba_chunk_size=8,
        lm_head_multiplier=1.0, initializer_range=0.3, in_proj_std=0.5,
        model_options={"use_flash": False,
                       "an_option_a_later_pr_deleted": True})
    return config


# limit read on the CPU: the program (bfloat16) 0.0 on seeds 1-3, 5, 6
# and 2**31 + 39 (every served token the reference's own pick) and 0.027
# - 0.044 on seeds 4, 7, 8; the fp8 control over the 85 positions of the
# test below 0.085 - 0.194 on seeds 1-5 (a gap is bounded by what a
# rounding moves a logit of order 1: only picks that swap count)
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
              "served_logit_gap_max": 0.06},
}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    dst = build(str(tmp_path_factory.mktemp("falcon_root")))
    here = os.path.join(dst, "benchmark")
    with open(os.path.join(here, "configs", "tiny-falcon.json"), "w") as f:
        json.dump(tiny_config(), f)
    with open(os.path.join(here, "workloads", CELL + ".json"), "w") as f:
        json.dump(TINY_CELL, f)
    real = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
    spec = harness.load_json(os.path.join(dst, "BENCHMARK.json"))
    spec["configs"].append({
        "name": "tiny-falcon", "source": "https://example.org/tiny-falcon",
        "file": "benchmark/configs/tiny-falcon.json",
        "reduced": tiny_config()["reduced"], "why": "test"})
    spec["workloads"].append({"name": CELL, "config": "tiny-falcon",
                              "traffic": "serve-chat", "chips": 1,
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
    rc, line, out = drive(root, capsys, seed=2 ** 31 + 39)
    assert rc == 0 and line["correct"] is True
    assert line["failed"] == 0 and line["cut_off"] == 0
    assert set(line["metrics"]) == {"itl_p95_ms", "setup_s"}
    checks = [json.loads(x[6:]) for x in out if x.startswith("check ")]
    assert [c["name"] for c in checks] == ["served_logit_gap_max"]
    serve = json.loads(next(x for x in out if x.startswith("serve "))[6:])
    # one cache kind of pages; no window, no experts on the line
    assert set(serve["pages_by_kind"]) == {"global"}
    assert serve["window_pages_recycled"] is None
    assert serve["experts_touched_share"] is None
    assert serve["prefills"] >= 6 and serve["decode_steps"] > 0
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
    ctx.sample = [(rng.integers(1, 128, size=n).astype(np.int32), 4)
                  for n in (60, 33)]
    assert not driver.control(ctx).correct
    same, _ = driver.score(ctx, ctx.sample, picks="lower",
                           precision="float32")
    assert same == 0.0


def test_the_real_files_are_the_published_sizes_cut_as_they_say():
    config = _real("configs/falcon-h1-34b.json")
    assert {k: config[k] for k in PUBLISHED} == PUBLISHED
    assert config["source"] == ("https://huggingface.co/tiiuae/"
                                "Falcon-H1-34B-Instruct/blob/main/config.json")
    assert config["num_hidden_layers"] == 6
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["published"] == {"num_hidden_layers": 72}
    assert (config["hidden_size"], config["intermediate_size"]) == (5120,
                                                                    21504)
    assert (config["num_attention_heads"], config["num_key_value_heads"],
            config["head_dim"]) == (20, 4, 128)
    assert (config["mamba_d_ssm"], config["mamba_n_heads"],
            config["mamba_d_head"], config["mamba_n_groups"],
            config["mamba_d_state"], config["mamba_d_conv"],
            config["mamba_chunk_size"]) == (4096, 32, 128, 2, 256, 4, 128)
    assert config["vocab_size"] == 261120
    assert (config["dtype"], config["state_dtype"]) == ("bfloat16", "float32")
    assert set(config["assumed"]) and "pipeline stages" in config["deployment"]
    from benchmark import program_falcon_h1 as adapter
    from benchmark import rooflines_falcon_h1, weights_falcon_h1

    sizes = adapter.sizes(config)
    # 430.1 M parameters a block, counted two ways; 10.51 GB in bfloat16
    assert weights_falcon_h1.block_params(sizes) == 430120032 \
        == rooflines_falcon_h1.block_params(sizes)
    n = weights_falcon_h1.n_params(sizes)
    assert n == 6 * 430120032 + 2 * 261120 * 5120 + 5120
    assert 10.50e9 < 2 * n < 10.52e9
    # a decode step multiplies everything but the embedding: 7.83 GB
    assert 2 * rooflines_falcon_h1.decode_weight_params(sizes) == \
        2 * (n - 261120 * 5120)
    # a cached token's keys and values 12,288 bytes, a slot's state 25.35
    # MB: as much as 2,063 cached tokens'
    assert rooflines_falcon_h1.kv_bytes_per_key(sizes) == 12288
    assert rooflines_falcon_h1.state_bytes_per_slot(sizes) == \
        6 * (32 * 128 * 256 * 4 + 3 * 5120 * 2) == 25350144
    # the program's own configuration takes every published key
    cfg = adapter.make_config(config)
    assert cfg.in_proj_dim == 9248 and cfg.use_flash
    assert dict((k, s) for k, s, _ in cfg.state_shapes()) == {
        "ssm": (32, 128, 256), "conv": (3, 5120)}
    cell = _real("workloads/" + REAL_CELL + ".json")
    assert cell["driver"] == "serve_model"
    assert cell["engine"] == {"num_slots": 64, "num_pages": 8192,
                              "page_size": 16, "max_context": 2048}
    chat = _real("workloads/bloom-560m.serve-chat-r8.json")["traffic"]
    t = cell["traffic"]
    # the chat cell's own mix: the two cells differ by the model alone
    for key in ("prompt", "output", "prompt_buckets"):
        assert t[key] == chat[key]
    assert "order_seed" in t and cell["drain_s"] == 14.0
    assert t["rate_per_s"] == int(t["rate_per_s"])        # whole requests/s
    # every sequence the traffic can make has a padded length
    assert max(cell["check"]["pad_to"]) >= 1024 + 384
    spec = _real("../BENCHMARK.json")
    entry = next(c for c in spec["configs"] if c["name"] == "falcon-h1-34b")
    assert entry["source"] == config["source"]
    assert entry["reduced"] == config["reduced"]
    assert len(spec["workloads"]) <= 24
    assert sum(w["chips"] == 4 for w in spec["workloads"]) == 1
    for m in spec["per_layer"]:
        if m["name"] in NEW_READERS:
            assert m["workloads"] == [REAL_CELL] and m["moves"] == "itl_p95_ms"
        if m["name"] in SHARED_READERS:
            assert REAL_CELL in m["workloads"]


# -- the readers ---------------------------------------------------------------

SIZES = {"hidden_size": 5120, "head_dim": 128, "num_key_value_heads": 4,
         "num_attention_heads": 20, "num_hidden_layers": 6,
         "intermediate_size": 21504, "vocab_size": 261120,
         "mamba_d_ssm": 4096, "mamba_n_heads": 32, "mamba_d_head": 128,
         "mamba_n_groups": 2, "mamba_d_state": 256, "mamba_d_conv": 4,
         "state_dtype": "float32"}


def _facts(**metrics):
    run_metrics = {"decode_steps": 2,
                   "state": {"slots": 64, "rows_live": 70, "rows_updated": 96,
                             "writes": 3}}
    run_metrics.update(metrics)
    return {"sizes": SIZES, "peaks": V5E, "dtype": "bfloat16",
            "ticks": [(0.1, 0), (0.2, 9000), (0.3, 12000)],
            "live_window": [0, 0, 0], "run_metrics": run_metrics}


def _read(name, facts, modules=None):
    reader = harness.load_module(os.path.join(
        REPO, "benchmark", "layer_metrics", name + ".py"))
    trace = None if modules is None else {
        "devices": [{"busy_ns": 1, "ops": [], "modules": list(modules)}]}
    result = harness.Result(end_to_end={}, attempted=1, failed=0,
                            t_window_start=0.0, memory_peak_bytes=0,
                            facts=facts, trace=trace)
    return reader.read(result)


def test_the_counter_reader_reads_the_engines_counters():
    assert _read("state_rows_useful_pct.ssm", _facts()) == pytest.approx(
        100 * 70 / 96)
    # a program without a state bank (the parent): nothing to read
    assert _read("state_rows_useful_pct.ssm", _facts(state=None)) is None
    assert _read("state_rows_useful_pct.ssm", _facts(
        state={"rows_live": 0, "rows_updated": 0})) is None


def test_decode_roofline_counts_weights_keys_and_the_live_rows_state_twice():
    from benchmark import rooflines_falcon_h1 as rl

    weights = 2 * rl.decode_weight_params(SIZES)
    assert rl.decode_step_bytes(SIZES, 9000, 30) == pytest.approx(
        weights + 12288 * 9000 + 2 * 25350144 * 30)
    total = 2 * weights + 12288 * 21000 + 2 * 25350144 * 70
    ns = int(2 * total / 819e9 * 1e9)          # at half the roofline
    modules = [("jit__step(123)", 0, ns // 2), ("jit__prefill(9)", ns, 2 * ns),
               ("jit__step(123)", 3 * ns, 3 * ns + ns // 2)]
    assert _read("decode_hbm_roofline.ssm", _facts(),
                 modules) == pytest.approx(50.0, rel=1e-3)
    assert _read("decode_hbm_roofline.ssm", _facts()) is None    # no trace
    assert _read("decode_hbm_roofline.ssm", _facts(state=None),
                 modules) is None


def test_state_write_takes_the_write_programs_executions():
    modules = [("jit__step(1)", 0, 5_000_000),
               ("jit__write(7)", 6_000_000, 7_000_000),
               ("jit__write(7)", 8_000_000, 11_000_000)]
    assert _read("state_write_device_ms.ssm", _facts(),
                 modules) == pytest.approx(2.0)
    assert _read("state_write_device_ms.ssm", _facts(state=None),
                 modules) is None
    assert _read("state_write_device_ms.ssm", _facts()) is None
    assert _read("state_write_device_ms.ssm", _facts(),
                 modules[:1]) is None
