"""``chip_smoke.py`` off the chip: it must fail before any phase runs
and never report ok. (What it does on the chip is the chip's to show;
nothing of it is rehearsed here at model width.)"""
import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent


def test_no_accelerator_exits_nonzero_without_a_result():
    proc = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no TPU" in proc.stderr
