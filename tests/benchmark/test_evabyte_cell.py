"""EvaByte's serving cell at a tiny size on the CPU: the configuration,
its cell and its readers dropped into a copy of the benchmark's
directories as files (the way the real ones were added, with no edit to a
file that was there), driven through ``run.py`` under driver
``serve_model``; a lower precision in the program's place fails the
comparison; each new reader against hand-built facts and a hand-built
trace; the real files against the catalog row and the issue's counts."""
import json
import os

import jax
import pytest
from tiny_root import REPO, build

from benchmark import harness, rooflines, run

PEAKS = {"flops_per_s": {"bfloat16": 1e12}, "hbm_bytes_per_s": 1e11,
         "hbm_bytes": 1e10}
V5E = {"flops_per_s": {"bfloat16": 197e12}, "hbm_bytes_per_s": 819e9}
CELL = "tiny-evabyte.serve-bytes"
REAL_CELL = "evabyte.serve-bytes-s8"
NEW_READERS = ("decode_hbm_roofline.eva", "cache_rows_useful_pct.eva",
               "prefill_attn_roofline.eva")
SHARED_READERS = ("decode_step_ms.chat", "prefill_stall_ms.chat",
                  "tick_host_ms.chat", "engine_build_s.chat",
                  "program_first_call_s.chat", "decode_keys_read_pct.chat",
                  "step_launch_ms.serve",
                  "step_return_ms.serve", "step_upload_ms.serve",
                  "device_gap_ms.serve", "prefill_device_busy_pct.serve")

# the catalog row's ``config`` (model-configs/architectures.jsonl,
# EvaByte), every key
CATALOG = {
    "attention_bias": False, "attention_class": "eva", "chunk_size": 16,
    "fp32_ln": False, "fp32_logits": True, "fp32_skip_add": True,
    "hidden_act": "silu", "hidden_size": 4096, "init_cutoff_factor": None,
    "init_fn": "v2", "init_std": 0.01275, "intermediate_size": 11008,
    "lazy_init": True, "max_position_embeddings": 32768,
    "max_seq_length": 32768, "mixedp_attn": True, "model_type": "evabyte",
    "norm_add_unit_offset": True, "num_attention_heads": 32,
    "num_chunks": None, "num_hidden_layers": 32, "num_key_value_heads": 32,
    "num_pred_heads": 8, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 100000, "tie_word_embeddings": False, "vocab_size": 320,
    "window_size": 2048,
}
REDUCED = {"num_hidden_layers": 12}


def _real(name):
    with open(os.path.join(REPO, "benchmark", name)) as f:
        return json.load(f)


def tiny_config():
    """The real configuration file at toy widths, with the published
    shape of things: a window of four chunks, a chunk a page, eight
    output heads."""
    config = _real("configs/evabyte.json")
    config.update(
        name="tiny-evabyte", source="https://example.org/tiny-evabyte",
        vocab_size=40, hidden_size=64, intermediate_size=96,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
        window_size=64, init_std=0.1,
        model_options={"use_flash": False, "ffn_block_tokens": 64,
                       "an_option_a_later_pr_deleted": True})
    return config


# limit read on the CPU (``read_limits.py`` over this root): the program
# (bfloat16) 0.0 - 0.0139 on seeds 1-8 and 2**31 + 45; the fp8 control on
# the served samples of seeds 1-5 0.114 - 0.283
TINY_CELL = {
    "driver": "serve_model",
    "engine": {"num_slots": 3, "num_pages": 8, "page_size": 16,
               "max_context": 384},
    "traffic": {
        "rate_per_s": 6.0, "order_seed": 11,
        "prompt": {"dist": "lognormal", "median": 120, "sigma": 0.7,
                   "min": 16, "max": 320},
        "output": {"dist": "lognormal", "median": 24, "sigma": 0.4,
                   "min": 8, "max": 48},
        "prompt_buckets": [32, 64, 128, 192, 320],
    },
    "drain_s": 120.0,
    "check": {"sample_requests": 4, "pad_to": [192, 384],
              "served_logit_gap_max": 0.04},
}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    dst = build(str(tmp_path_factory.mktemp("evabyte_root")))
    here = os.path.join(dst, "benchmark")
    with open(os.path.join(here, "configs", "tiny-evabyte.json"), "w") as f:
        json.dump(tiny_config(), f)
    with open(os.path.join(here, "workloads", CELL + ".json"), "w") as f:
        json.dump(TINY_CELL, f)
    real = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
    spec = harness.load_json(os.path.join(dst, "BENCHMARK.json"))
    spec["configs"].append({
        "name": "tiny-evabyte", "source": "https://example.org/tiny-evabyte",
        "file": "benchmark/configs/tiny-evabyte.json",
        "reduced": tiny_config()["reduced"], "why": "test"})
    spec["workloads"].append({"name": CELL, "config": "tiny-evabyte",
                              "traffic": "serve-bytes", "chips": 1,
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
    # two cache kinds of pages under ONE attention, no experts
    assert set(serve["pages_by_kind"]) == {"global", "window"}
    assert serve["window_pages_recycled"] is not None
    assert serve["experts_touched_share"] is None
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
    eva = result.facts["run_metrics"]["eva"]
    assert eva["summary_rows_needed"] > 0 and eva["summaries_written"] > 0
    got = run.layer_metrics(spec, CELL, result, here)
    assert got["cache_rows_useful_pct.eva"]["value"] == pytest.approx(
        100 * eva["rows_useful_share"])
    assert 0 < got["cache_rows_useful_pct.eva"]["value"] < 100
    assert 0 < got["decode_keys_read_pct.chat"]["value"] <= 100
    assert got["decode_step_ms.chat"]["value"] > 0
    for name in ("decode_hbm_roofline.eva", "prefill_attn_roofline.eva"):
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
    """The reference at fp8 over a sample that reaches past three
    windows, held to the tiny cell's limit through the driver's own
    ``control``; the float32 reference's own picks read 0."""
    import numpy as np

    _, driver, ctx, _ = _open(root, seed=3)
    rng = np.random.default_rng(0)
    ctx.sample = [(rng.integers(1, 40, size=n).astype(np.int32), 4)
                  for n in (230, 137)]
    assert not driver.control(ctx).correct
    same, _ = driver.score(ctx, ctx.sample, picks="lower",
                           precision="float32")
    assert same == 0.0


def test_the_real_files_are_the_catalog_row_cut_as_they_say():
    config = _real("configs/evabyte.json")
    # every key of the catalog row under the same name, the reduced one
    # apart, and that is what ``reduced`` lists
    assert {k: config[k] for k in CATALOG} == {**CATALOG, **REDUCED}
    assert config["reduced"] == list(REDUCED)
    assert config["published"] == {k: CATALOG[k] for k in REDUCED}
    assert config["source"] == ("https://huggingface.co/EvaByte/EvaByte/"
                                "blob/main/config.json")
    assert config["dtype"] == "bfloat16"
    assert "no layer is divided" in config["deployment"]
    assert "pipeline stages" in config["deployment"]
    assert {"head_dim", "rope_pairing", "phi_and_mu", "mixedp_attn",
            "head_layout", "weights", "summary_mass"} <= set(config["assumed"])
    assert config["program"] == {"adapter": "program_evabyte",
                                 "weights": "weights_evabyte",
                                 "reference": "reference/evabyte_ref"}
    assert config["model_options"] == {"use_flash": True,
                                       "ffn_block_tokens": 2048}
    from benchmark import program_evabyte as adapter
    from benchmark import rooflines_evabyte as rl
    from benchmark import weights_evabyte

    sizes = adapter.sizes(config)
    # the issue's arithmetic: a layer 202.39 M, embedding + head + norm
    # 11.80 M, this chip 4.881 GB, the whole model the published 6.5 B
    assert round(rl.layer_params(sizes) / 1e6, 2) == 202.39
    assert round(rl.top_params(sizes) / 1e6, 2) == 11.80
    n = weights_evabyte.n_params(sizes)
    assert n == rl.held_params(sizes)
    assert round(2 * n / 1e9, 3) == 4.881
    assert round(rl.held_params(dict(sizes, num_hidden_layers=32)) / 1e6) \
        == 6488
    cell = _real("workloads/" + REAL_CELL + ".json")
    engine = cell["engine"]
    # a slot: the ring's 128 pages (one fewer than the issue's 129: a
    # block window starts on a page and needs none to straddle) and 104
    # pages of summaries, 12 layers: 730 MB for the issue's 733
    assert rl.row_bytes(sizes) == 16384
    per_slot = rl.cache_bytes_per_slot(sizes, engine["page_size"],
                                       engine["max_context"])
    assert round(per_slot / 1e6) == 730
    # keys and values of every position would be 7.2 times that
    assert round(engine["max_context"] * 12 * 16384 / per_slot, 1) == 7.2
    # a decode step: the weights but the embedding and seven of the
    # eight output heads, and the rows the softmax needs at 16 KB a layer
    h = 4096
    assert rl.decode_step_bytes(sizes, 0, 0) == 2 * (n - 320 * h
                                                     - 7 * 320 * h)
    assert rl.decode_step_bytes(sizes, 1000, 500, 5) \
        - rl.decode_step_bytes(sizes, 0, 0) == 1500 * 12 * 16384 + 5 * h * 2
    # EVA's pairs: a window's triangle, and 128 summaries a closed window
    assert rl.eva_pairs(sizes, 2048) == {"window": 2048 * 2049 // 2,
                                         "summary": 0}
    assert rl.eva_pairs(sizes, 4096 + 10) == {
        "window": 2 * 2048 * 2049 // 2 + 55,
        "summary": 128 * 2048 + 256 * 10}
    flops, nbytes = rl.eva_prefill_cost(sizes, 4096)
    assert flops == 4.0 * 4096 * (2 * 2048 * 2049 // 2 + 128 * 2048)
    assert nbytes == (6 * 4096 + 2 * 256) * 4096 * 2
    # the program's own configuration takes every published key
    cfg = adapter.make_config(config)
    assert (cfg.head_dim, cfg.window_size, cfg.chunk_size) == (128, 2048, 16)
    assert (cfg.use_flash, cfg.ffn_block_tokens) == (True, 2048)
    desc = cfg.paged_model()
    assert desc.stride == 16 and desc.window_rule == "block"
    assert desc.layers_of("global") == desc.layers_of("window") == 12
    assert cell["driver"] == "serve_model"
    # every page 8 slots can reach: 8 x 104 summary pages (+ NULL)
    assert engine == {"num_slots": 8, "num_pages": 8 * 104 + 1,
                      "page_size": 16, "max_context": 26624}
    t = cell["traffic"]
    # the issue's drain
    assert t["order_seed"] == 4701 and cell["drain_s"] == 30.0
    assert t["prompt"] == {"dist": "lognormal", "median": 8192, "sigma": 0.6,
                           "min": 1024, "max": 24576}
    assert t["output"] == {"dist": "lognormal", "median": 768, "sigma": 0.6,
                           "min": 128, "max": 2048}
    assert t["prompt_buckets"] == [1024, 2048, 4096, 6144, 8192, 12288,
                                   16384, 24576]
    assert t["rate_per_s"] == 0.25
    assert 0.02 * 4 < cell["check"]["served_logit_gap_max"] < 0.588 / 3
    # every sequence the traffic can make has a padded length, and fits
    assert max(cell["check"]["pad_to"]) >= 24576 + 2048
    assert engine["max_context"] >= 24576 + 2048
    assert cell["check"]["sample_requests"] == 12
    spec = _real("../BENCHMARK.json")
    entry = next(c for c in spec["configs"] if c["name"] == "evabyte")
    assert entry["source"] == config["source"]
    assert entry["reduced"] == config["reduced"]
    assert entry["file"] == "benchmark/configs/evabyte.json"
    real_cell = next(w for w in spec["workloads"] if w["name"] == REAL_CELL)
    assert real_cell["chips"] == 1 and real_cell["config"] == "evabyte"
    assert f"{t['rate_per_s']:g}/s" in real_cell["why"]
    assert len(spec["workloads"]) <= 24
    assert sum(w["chips"] == 4 for w in spec["workloads"]) == 1
    itl = next(m for m in spec["end_to_end"] if m["name"] == "itl_p95_ms")
    assert REAL_CELL in itl["workloads"]
    # the new metrics stand in the list in THIS order (a later PR's may
    # follow them), and the cell is IN each shared reader's list
    names = [m["name"] for m in spec["per_layer"]]
    at = [names.index(n) for n in NEW_READERS]
    assert at == list(range(at[0], at[0] + 3))
    for m in spec["per_layer"]:
        if m["name"] in NEW_READERS:
            assert m["workloads"] == [REAL_CELL] and m["moves"] == "itl_p95_ms"
            assert m["unit"] == "%" and m["better"] == "higher"
        if m["name"] in SHARED_READERS:
            assert REAL_CELL in m["workloads"]
        # the other families' readers keep to their own cells
        if m["name"].endswith((".mix", ".ssm", ".mla")):
            assert REAL_CELL not in m["workloads"]


# -- the readers ---------------------------------------------------------------

SIZES = {"vocab_size": 320, "hidden_size": 4096, "intermediate_size": 11008,
         "num_hidden_layers": 12, "num_attention_heads": 32,
         "num_pred_heads": 8, "window_size": 2048, "chunk_size": 16}
# another family's sizes: what the other cells' lines hand a reader
OTHER_SIZES = {"hidden_size": 3072, "head_dim": 128, "num_key_value_heads": 8}


def _facts(sizes=SIZES, **eva):
    base = {"rows_live": 10, "window_rows_needed": 9000,
            "window_rows_gathered": 23040, "summary_rows_needed": 3000,
            "summary_rows_gathered": 7680, "summaries_written": 1,
            "rows_useful_share": 0.390625, "summary_key_share": 0.25}
    base.update(eva)
    return {"sizes": sizes, "peaks": V5E, "dtype": "bfloat16",
            "ticks": [(0.1, 0), (0.2, 20000)], "live_window": [0, 0],
            "run_metrics": {"decode_steps": 2, "eva": base}}


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
    assert _read("cache_rows_useful_pct.eva", _facts()) == \
        pytest.approx(39.0625)
    # a program without the counters: another family's, or the parent's
    facts = _facts()
    del facts["run_metrics"]["eva"]
    assert _read("cache_rows_useful_pct.eva", facts) is None
    assert _read("cache_rows_useful_pct.eva",
                 _facts(rows_useful_share=None)) is None


def test_decode_roofline_counts_weights_and_the_rows_the_softmax_needs():
    from benchmark import rooflines_evabyte as rl

    # two steps: 4,500 exact keys, 1,500 summaries and 5 rows alive each
    step = rl.decode_step_bytes(SIZES, 4500, 1500, 5)
    assert step == pytest.approx(
        2 * (12 * rl.layer_params(SIZES) + 4096 + 4096 * 320) + 5 * 8192
        + 6000 * 12 * 16384)
    ns = int(2 * step / 819e9 * 1e9)            # at half the roofline
    modules = [("jit__step(123)", 0, ns), ("jit__prefill(9)", ns, 3 * ns),
               ("jit__step(123)", 4 * ns, 5 * ns)]
    assert _read("decode_hbm_roofline.eva", _facts(),
                 modules) == pytest.approx(50.0, rel=1e-3)
    assert _read("decode_hbm_roofline.eva", _facts()) is None    # no trace
    assert _read("decode_hbm_roofline.eva", _facts(), modules[1:2]) is None
    facts = _facts()
    del facts["run_metrics"]["eva"]
    assert _read("decode_hbm_roofline.eva", facts, modules) is None
    # another family's line (its sizes hold no chunk)
    assert _read("decode_hbm_roofline.eva", _facts(OTHER_SIZES),
                 modules) is None


def test_prefill_attention_counts_the_pairs_the_mathematics_has():
    from benchmark import rooflines_evabyte as rl

    window = rooflines.least_time_s(
        *rl.eva_prefill_cost(SIZES, 8192, ("window",)), V5E)[0]
    summary = rooflines.least_time_s(
        *rl.eva_prefill_cost(SIZES, 8192, ("summary",)), V5E)[0]
    a, b = int(2 * window * 1e9), int(4 * summary * 1e9)
    ops = [
        # the window part: 32 heads x 4 windows as rows of 2,048
        ("%flash_fwd.7 = (bf16[128,2048,128], f32[128,1,2048]) "
         "custom-call(..)", 0, a),
        # the summary part: its two float32 rows, then the accumulator
        ("%flash_ring_fwd.3 = (f32[32,1,8192], f32[32,1,8192], "
         "f32[32,8192,128]) custom-call(..)", a, a + b),
        # another kernel, and a fusion that carries a head-wide result
        ("%fused_ce_fwd.1 = (f32[8192]) custom-call(..)", a + b, 2 * (a + b)),
        ("%fusion.1 = bf16[128,2048,128] fusion(..)", 0, a)]
    want = 100.0 * (window + summary) / (2 * window + 4 * summary)
    assert 25.0 < want < 50.0
    assert _read("prefill_attn_roofline.eva", _facts(), [],
                 ops) == pytest.approx(want, rel=1e-3)
    assert _read("prefill_attn_roofline.eva", _facts(), [],
                 ops[:1]) == pytest.approx(50.0, rel=1e-3)
    assert _read("prefill_attn_roofline.eva", _facts(), [],
                 ops[1:2]) == pytest.approx(25.0, rel=1e-3)
    # the heads 8 a call (what the program runs at 32 heads): four calls
    # a part do the layer's work, each a quarter of it
    grouped = []
    for g in range(4):
        grouped += [
            ("%flash_fwd.7 = (bf16[32,2048,128], f32[32,1,2048]) "
             "custom-call(..)", g * a // 4, (g + 1) * a // 4),
            ("%flash_ring_fwd.3 = (f32[8,1,8192], f32[8,1,8192], "
             "f32[8,8192,128]) custom-call(..)", a + g * b // 4,
             a + (g + 1) * b // 4)]
    assert _read("prefill_attn_roofline.eva", _facts(), [],
                 grouped) == pytest.approx(want, rel=1e-3)
    # a 6,144-byte bucket's 8 heads x 3 windows are no multiple of 32
    three = rooflines.least_time_s(
        *rl.eva_prefill_cost(SIZES, 6144, ("window",)), V5E)[0]
    part = [("%flash_fwd.7 = (bf16[24,2048,128], f32[24,1,2048]) "
             "custom-call(..)", 0, int(three / 4 * 2 * 1e9))]
    assert _read("prefill_attn_roofline.eva", _facts(), [],
                 part) == pytest.approx(50.0, rel=1e-3)
    # one partial window (a 1,024-byte bucket): its own triangle
    short = rooflines.least_time_s(
        *rl.eva_prefill_cost(SIZES, 1024, ("window",)), V5E)[0]
    one = [("%flash_fwd.2 = (bf16[32,1024,128], f32[32,1,1024]) "
            "custom-call(..)", 0, int(short * 1e9))]
    assert _read("prefill_attn_roofline.eva", _facts(), [],
                 one) == pytest.approx(100.0, rel=1e-3)
    assert _read("prefill_attn_roofline.eva", _facts(), [], ops[2:]) is None
    assert _read("prefill_attn_roofline.eva", _facts(OTHER_SIZES), [],
                 ops) is None
    assert _read("prefill_attn_roofline.eva", _facts()) is None
