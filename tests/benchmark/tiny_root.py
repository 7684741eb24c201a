"""A temporary copy of the benchmark's directories with a tiny
configuration and tiny cells dropped in as files — the way a later PR
adds them, with no code edit."""
from __future__ import annotations

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TINY_CONFIG = {
    "name": "tiny",
    "source": "https://example.org/tiny",
    "sizes": {"vocab_size": 512, "hidden_size": 64, "n_layer": 2,
              "n_head": 4, "layer_norm_epsilon": 1e-5,
              "initializer_range": 0.08},
    "dtype": "bfloat16",
    "reduced": [],
    "model_options": {"remat": True, "use_flash": False, "fused_ce": False,
                      "an_option_a_later_pr_deleted": True},
}

TINY_TRAIN = {
    "driver": "train",
    "mesh": {"tensor": 1, "data": 1},
    "global_batch": 4, "seq": 32, "learning_rate": 3e-4,
    # read on the CPU over seeds 1-6 (program) and 1-3 (fp8 control):
    # loss 0.5-1.2e-4 against 1.0-1.9e-3, gradient 0.002-0.005 against
    # 0.019-0.050, change 0.13-0.27 (bfloat16 rounding of the parameters;
    # a step that leaves its state unchanged reads 1.0)
    "check": {"steps": 3, "reference_rows_per_call": 2, "loss_rel_gap_max": 4e-4,
              "grad_norm_gap_max": 0.015, "param_change_gap_max": 0.8},
}

# tensor 2 x data 2 on four (virtual) devices; sizes that divide
TINY_TRAIN4 = dict(TINY_TRAIN, mesh={"tensor": 2, "data": 2}, global_batch=8)

TINY_SERVE = {
    "driver": "serve",
    "engine": {"num_slots": 4, "num_pages": 64, "page_size": 8,
               "max_context": 128},
    "traffic": {
        "rate_per_s": 20.0,
        "prompt": {"dist": "lognormal", "median": 20, "sigma": 0.6,
                   "min": 4, "max": 64},
        "output": {"dist": "lognormal", "median": 8, "sigma": 0.4,
                   "min": 4, "max": 12},
        "prompt_buckets": [16, 32, 64],
    },
    "drain_s": 20.0,
    # program 0-0.0097 over seeds 1-6; fp8 control 0.085-0.107 over 512
    # positions (test_lower_precision_serving_fails_the_comparison)
    "check": {"sample_requests": 4, "served_logit_gap_max": 0.03},
}


# no drain: what is still decoding at the window's end is cut off. The
# outputs are long enough (32 ticks and more) that some request is still
# decoding then on any CPU, however fast
TINY_SERVE_NODRAIN = dict(
    TINY_SERVE, drain_s=0.0,
    traffic=dict(TINY_SERVE["traffic"],
                 output={"dist": "lognormal", "median": 40, "sigma": 0.2,
                         "min": 32, "max": 48}))


def _metric(name, unit, layer=None, moves=None, cells=None, **kw):
    m = {"name": name, "unit": unit, "better": kw.get("better", "higher"),
         "source": kw.get("source", "host_clock")}
    if layer is None:
        m["bound"] = kw.get("bound", 0.05)
    else:
        m["layer"], m["moves"] = layer, moves
    if cells:
        m["workloads"] = cells
    return m


def build(dst: str) -> str:
    """Copy ``benchmark/`` to ``dst`` and drop the tiny files in.
    Returns the new root."""
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(dst, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    here = os.path.join(dst, "benchmark")
    with open(os.path.join(here, "configs", "tiny.json"), "w") as f:
        json.dump(TINY_CONFIG, f)
    for name, body in (("tiny.train", TINY_TRAIN), ("tiny.serve", TINY_SERVE),
                       ("tiny.train4", TINY_TRAIN4),
                       ("tiny.serve-nodrain", TINY_SERVE_NODRAIN)):
        with open(os.path.join(here, "workloads", name + ".json"), "w") as f:
            json.dump(body, f)
    with open(os.path.join(here, "layer_metrics", "steps_seen.tiny.py"),
              "w") as f:
        f.write("def read(run):\n    return run.facts.get('steps')\n")
    real = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    spec = {
        "command": real["command"], "paths": real["paths"],
        "run_seconds": 2,
        "configs": [{"name": "tiny", "source": TINY_CONFIG["source"],
                     "file": "benchmark/configs/tiny.json", "reduced": [],
                     "why": "test"}],
        "workloads": [
            {"name": "tiny.train", "config": "tiny", "traffic": "train",
             "chips": 1, "why": "test"},
            {"name": "tiny.serve", "config": "tiny", "traffic": "serve",
             "chips": 1, "why": "test"},
            {"name": "tiny.train4", "config": "tiny", "traffic": "train4",
             "chips": 4, "why": "test"},
            {"name": "tiny.serve-nodrain", "config": "tiny",
             "traffic": "serve-nodrain", "chips": 1, "why": "test"}],
        "end_to_end": [
            _metric("train_tokens_per_s", "tokens/s",
                    cells=["tiny.train", "tiny.train4"]),
            _metric("itl_p95_ms", "ms", better="lower",
                    cells=["tiny.serve", "tiny.serve-nodrain"]),
            _metric("setup_s", "s", better="lower", bound=0.1)],
        "per_layer": [
            _metric("steps_seen.tiny", "steps", "trainer",
                    "train_tokens_per_s", ["tiny.train"]),
            _metric("step_ms.train", "ms", "train step",
                    "train_tokens_per_s", ["tiny.train"], better="lower"),
            _metric("decode_step_ms.chat", "ms", "engine tick",
                    "itl_p95_ms", ["tiny.serve"], better="lower")],
    }
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f, indent=1)
    return dst
