"""Jit-safety lint (scripts/lint_jit_safety.py, ISSUE 7 satellite):
rule detection on inline sources, allowlist/waiver semantics, and the
gate itself — the shipped tree lints clean against the checked-in
allowlist."""
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
SCRIPT = REPO / "scripts" / "lint_jit_safety.py"

spec = importlib.util.spec_from_file_location("lint_jit_safety", SCRIPT)
lint = importlib.util.module_from_spec(spec)
spec.loader.exec_module(lint)


def _violations(src, relpath="pipegoose_tpu/fake.py", patterns=()):
    v, a = lint.lint_source(src, relpath, list(patterns))
    return v, a


def test_flags_host_sync_calls_in_jit_module():
    src = (
        "import time\n"
        "import numpy as np\n"
        "import jax\n"
        "def step(x):\n"
        "    t = time.perf_counter()\n"
        "    y = np.asarray(x)\n"
        "    z = x.item()\n"
        "    w = jax.device_get(x)\n"
        "    return y, z, w, t\n"
    )
    v, _ = _violations(src)
    rules = sorted(f.rule for f in v)
    assert rules == ["host-sync"] * 4
    msgs = " ".join(f.message for f in v)
    assert ".item()" in msgs and "np.asarray" in msgs
    assert "device_get" in msgs and "time.perf_counter" in msgs
    assert all(f.qualname == "step" for f in v)


def test_jnp_asarray_and_named_excepts_are_fine():
    src = (
        "import jax.numpy as jnp\n"
        "def step(x):\n"
        "    try:\n"
        "        return jnp.asarray(x)\n"
        "    except ValueError:\n"
        "        return x\n"
    )
    v, a = _violations(src)
    assert v == [] and a == []


def test_bare_except_flagged_even_in_allowlisted_module():
    src = (
        "def f():\n"
        "    try:\n"
        "        pass\n"
        "    except:\n"
        "        pass\n"
    )
    # whole-module allowlist entry clears host-sync but NOT bare-except
    v, _ = _violations(src, patterns=["pipegoose_tpu/fake.py"])
    assert [f.rule for f in v] == ["bare-except"]
    # a qualname-level entry (or inline waiver) is the only way out
    v, a = _violations(
        src, patterns=["pipegoose_tpu/fake.py",
                       "pipegoose_tpu/fake.py::f"])
    assert v == [] and [f.rule for f in a] == ["bare-except"]


def test_nondeterminism_rules():
    src = (
        "import random\n"
        "import datetime\n"
        "def seed_fn():\n"
        "    a = random.random()\n"
        "    b = datetime.datetime.now()\n"
        "    return a, b\n"
    )
    v, _ = _violations(src)
    assert sorted(f.rule for f in v) == ["nondeterminism"] * 2


def test_allowlist_module_and_qualname_granularity():
    src = (
        "import time\n"
        "def host_fn():\n"
        "    return time.time()\n"
        "def jit_fn():\n"
        "    return time.time()\n"
    )
    # module-level: everything allowed
    v, a = _violations(src, patterns=["pipegoose_tpu/*.py"])
    assert v == []
    # qualname-level: only host_fn allowed (nested scopes inherit)
    v, a = _violations(src,
                       patterns=["pipegoose_tpu/fake.py::host_fn"])
    assert [f.qualname for f in v] == ["jit_fn"]
    assert [f.qualname for f in a] == ["host_fn"]


def test_inline_waiver_comment():
    src = (
        "import time\n"
        "def f():\n"
        "    return time.time()  # jit-host-ok: fenced by caller\n"
    )
    v, a = _violations(src)
    assert v == [] and a == []


def test_star_qualname_entry_is_not_a_whole_module_waiver():
    """`path::*` may clear host-sync hits per-finding but must behave
    like a whole-module entry for bare-excepts: never clears them."""
    src = (
        "import time\n"
        "def f():\n"
        "    try:\n"
        "        return time.time()\n"
        "    except:\n"
        "        pass\n"
    )
    v, a = _violations(src, patterns=["pipegoose_tpu/fake.py::*"])
    assert [f.rule for f in v] == ["bare-except"]
    assert [f.rule for f in a] == ["host-sync"]


def test_nested_function_qualname_matches_parent_pattern():
    src = (
        "import numpy as np\n"
        "def outer():\n"
        "    def inner(x):\n"
        "        return np.asarray(x)\n"
        "    return inner\n"
    )
    v, _ = _violations(src, patterns=["pipegoose_tpu/fake.py::outer"])
    assert v == []


def test_repo_lints_clean_with_checked_in_allowlist():
    """The actual CI gate: the shipped library + allowlist pass."""
    proc = subprocess.run(
        [sys.executable, str(SCRIPT)],
        capture_output=True, text=True, timeout=120, cwd=str(REPO),
        env={**os.environ},
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "jit-safety lint: OK" in proc.stdout


def test_lint_tree_catches_a_planted_violation(tmp_path):
    pkg = tmp_path / "pipegoose_tpu"
    pkg.mkdir()
    (pkg / "bad.py").write_text(
        "def f(x):\n    return x.item()\n"
    )
    v, _ = lint.lint_tree("pipegoose_tpu", [], repo=str(tmp_path))
    assert len(v) == 1 and v[0].rule == "host-sync"
    assert v[0].path == "pipegoose_tpu/bad.py"
