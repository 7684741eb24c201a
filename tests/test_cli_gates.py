"""The compile-time gates of ``scripts/mesh_doctor.py`` and
``scripts/plan_parallelism.py``, called through their ``main`` on the
suite's 8 fake CPU devices: what the exit code says of a sharding
regression, of a train step that lost its ring collectives, and of a
layout the planner ranks too low or prunes."""
import importlib.util
from pathlib import Path

import pytest

SCRIPTS = Path(__file__).resolve().parent.parent / "scripts"


def _main(name):
    spec = importlib.util.spec_from_file_location(name, SCRIPTS / f"{name}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.main


MESH = ["--tp", "2", "--dp", "4", "--check", "--quiet"]


@pytest.mark.parametrize("argv, rc", [
    # the hybrid train step, the decode step and the chunk program of a
    # tp=2 engine: no partitioner-inserted resharding, no mismatch
    (MESH + ["--serving"], 0),
    # the ring-overlap step with the int8 gradient wire keeps its
    # ppermute collectives
    (MESH + ["--overlap", "--grad-comm", "int8", "--expect-ppermute"], 0),
    # the monolithic step has none: the overlap gate fires
    (MESH + ["--expect-ppermute"], 2),
    # every replicated buffer over one byte counts as a violation
    (MESH + ["--min-shard-bytes", "1"], 2),
], ids=["serving", "overlap", "no-ppermute", "replicated"])
def test_mesh_doctor_gate_exit_code(devices, argv, rc):
    assert _main("mesh_doctor")(argv) == rc


# one overlap and remat setting: the three layouts of eight devices that
# four heads allow, a compile a layout and wire format
PLAN = ["--overlap-sweep", "on", "--remat-sweep", "on", "--check", "--quiet"]


@pytest.mark.parametrize("argv, rc", [
    (PLAN + ["--grad-comms", "fp32,int8", "--tp", "4", "--dp", "2",
             "--overlap", "--grad-comm", "int8", "--tolerance", "0.3"], 0),
    # eight-way tensor parallel over four heads is pruned, never ranked
    (PLAN + ["--grad-comms", "int8", "--tp", "8", "--dp", "1", "--overlap",
             "--grad-comm", "int8"], 2),
], ids=["expected-best", "infeasible"])
def test_planner_gate_exit_code(devices, argv, rc):
    assert _main("plan_parallelism")(argv) == rc
