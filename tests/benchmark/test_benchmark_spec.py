"""BENCHMARK.json against the contract's static rules, and every name
in it against the files it must be found by."""
import json
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys_and_limits(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert spec["paths"] == ["benchmark", "tests/benchmark"]
    assert 1 <= spec["run_seconds"] <= 51
    # a full check with the full 24 cells has to fit 43,200 s
    assert ((2 + 14 * 24) * (spec["run_seconds"] + 60) + 24 * 180 + 1200
            <= 43200)
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 * 1024


def test_every_name_and_unit_uses_only_permitted_characters(spec):
    names = [c["name"] for c in spec["configs"]]
    for w in spec["workloads"]:
        names += [w["name"], w["config"], w["traffic"]]
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    for m in spec["end_to_end"] + spec["per_layer"]:
        names.append(m["name"])
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for n in names:
        assert NAME.match(n), n
    for group in ("configs", "workloads"):
        got = [x["name"] for x in spec[group]]
        assert len(got) == len(set(got))
    metrics = [m["name"] for m in spec["end_to_end"] + spec["per_layer"]]
    assert len(metrics) == len(set(metrics))


def test_entries_have_just_the_contracts_keys(spec):
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["source"].startswith("https://")
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
    for m in spec["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in spec["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}


def test_cells_metrics_and_files_agree(spec):
    cells = {w["name"]: w for w in spec["workloads"]}
    configs = {c["name"]: c for c in spec["configs"]}
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    pairs = [(w["config"], w["traffic"]) for w in cells.values()]
    assert len(pairs) == len(set(pairs))
    assert {w["config"] for w in cells.values()} == set(configs)
    four = sum(1 for w in cells.values() if w["chips"] == 4)
    assert four <= max(1, len(cells) // 4)
    for name, w in cells.items():
        with open(os.path.join(REPO, "benchmark", "workloads",
                               name + ".json")) as f:
            body = json.load(f)
        assert os.path.exists(os.path.join(
            REPO, "benchmark", "drivers", body["driver"] + ".py"))
        mine = [m for m in e2e.values()
                if "workloads" not in m or name in m["workloads"]]
        assert len(mine) >= 2, f"{name} reports only setup_s"
    for c in configs.values():
        assert c["file"].startswith("benchmark/configs/")
        with open(os.path.join(REPO, c["file"])) as f:
            body = json.load(f)
        assert body["source"] == c["source"] and body["reduced"] == c["reduced"]
    for m in spec["per_layer"]:
        assert m["moves"] in e2e
        assert os.path.exists(os.path.join(
            REPO, "benchmark", "layer_metrics", m["name"] + ".py")), m["name"]
        for cell in m["workloads"]:
            mover = e2e[m["moves"]]
            assert cell in cells
            assert "workloads" not in mover or cell in mover["workloads"]
    for name in cells:
        assert any(name in m["workloads"] for m in spec["per_layer"])


def test_the_chat_cell_is_the_re_rated_one_and_set_up_has_its_layers(spec):
    """``bloom-560m.serve-chat`` (0.9 requests/s) went with the traffic
    its numbers were earned on; its metrics list the cell at 8/s, and
    ``setup_s``, the program's own since its clock starts when the chip
    answers, is moved by the engine's set-up metrics."""
    cells = {w["name"]: w for w in spec["workloads"]}
    assert "bloom-560m.serve-chat" not in cells
    assert not os.path.exists(os.path.join(
        REPO, "benchmark", "workloads", "bloom-560m.serve-chat.json"))
    new = cells["bloom-560m.serve-chat-r8"]
    assert (new["config"], new["traffic"], new["chips"]) == (
        "bloom-560m", "serve-chat-r8", 1)
    with open(os.path.join(REPO, "benchmark", "workloads",
                           new["name"] + ".json")) as f:
        assert json.load(f)["traffic"]["rate_per_s"] == 8.0
    listed = {m["name"]: m for m in spec["end_to_end"] + spec["per_layer"]
              if new["name"] in m.get("workloads", ())}
    assert "itl_p95_ms" in listed
    chat = {n for n in listed if n.endswith(".chat")}
    assert len(chat) == 8
    assert {n for n in chat if listed[n]["moves"] == "setup_s"} == {
        "engine_build_s.chat", "program_first_call_s.chat"}
    assert sum(1 for w in cells.values() if w["config"] == "bloom-560m") == 2


def test_layers_are_spelled_one_way(spec):
    layers = {m["layer"] for m in spec["per_layer"]}
    assert len({x.lower() for x in layers}) == len(layers)
    with open(os.path.join(REPO, "PERF.md")) as f:
        perf = f.read()
    for layer in layers:
        assert layer in perf, f"PERF.md's list of layers lacks {layer!r}"
