"""The runnable examples stay runnable — each is executed as a real
subprocess on a fake-device CPU mesh (the reference's examples are its
de-facto user API too, README.md:82-85; ours must not bitrot)."""
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
# the examples import pipegoose_tpu from the repo; keep any existing
# PYTHONPATH behind it
ENV = {
    **os.environ,
    "PYTHONPATH": os.pathsep.join(
        [str(REPO)] + [p for p in [os.environ.get("PYTHONPATH", "")] if p]
    ),
}

# SERVING demos share the session's persistent XLA compilation cache
# (tests/conftest.py): they jit the same tiny-config engine programs
# the serving suite already compiled, so each subprocess starts warm.
# Training-step demos stay uncached, like the trainer tests (see
# conftest.py).
SERVING_DEMOS = {
    "serve_bloom.py", "request_trace_demo.py", "disagg_serving_demo.py",
    "quantized_serving_demo.py", "control_plane_demo.py",
    "kv_tier_demo.py", "goodput_demo.py",
}
CACHE_ENV = {
    # JAX's own variable where set; else the checkout's fixed path,
    # the same one chip_smoke.py and benchmark/run.py use
    "JAX_COMPILATION_CACHE_DIR": os.environ.get(
        "JAX_COMPILATION_CACHE_DIR", str(REPO / ".jax_cache")),
    "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
    "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "0",
}

CASES = [
    ("hybrid_parallelism.py", ["--fake-devices", "4", "--tp", "2", "--dp", "2"]),
    ("moe_training.py", ["--fake-devices", "8"]),
    ("long_context.py", ["--fake-devices", "8"]),
    ("encoder_mlm.py", ["--fake-devices", "8", "--tp", "2", "--dp", "4",
                        "--seq", "32"]),
    ("serve_bloom.py", ["--fake-devices", "8", "--tp", "2", "--requests",
                        "4", "--max-context", "32"]),
    ("telemetry_demo.py", ["--fake-devices", "8", "--tp", "2", "--dp", "4",
                           "--requests", "4", "--out-dir",
                           "/tmp/pipegoose_telemetry_demo_test"]),
    ("flight_recorder_demo.py", ["--fake-devices", "8", "--tp", "2",
                                 "--dp", "4", "--out-dir",
                                 "/tmp/pipegoose_flightrec_demo_test"]),
    ("mesh_doctor_demo.py", ["--fake-devices", "8", "--tp", "2",
                             "--dp", "4"]),
    ("request_trace_demo.py", ["--fake-devices", "8", "--out-dir",
                               "/tmp/pipegoose_reqtrace_demo_test"]),
    ("comm_overlap_demo.py", ["--fake-devices", "8", "--tp", "2",
                              "--dp", "4"]),
    ("disagg_serving_demo.py", ["--fake-devices", "8", "--tp-prefill", "2",
                                "--requests", "4"]),
    ("plan_parallelism_demo.py", ["--fake-devices", "8", "--top-k", "5"]),
    ("elastic_training_demo.py", ["--fake-devices", "8", "--tp", "2",
                                  "--dp", "4", "--out-dir",
                                  "/tmp/pipegoose_elastic_demo_test"]),
    ("quantized_serving_demo.py", ["--fake-devices", "8", "--tp", "2",
                                   "--requests", "4"]),
    ("control_plane_demo.py", ["--fake-devices", "8", "--requests", "10",
                               "--out-dir",
                               "/tmp/pipegoose_control_plane_demo_test"]),
    ("kv_tier_demo.py", ["--fake-devices", "8", "--requests", "4"]),
    ("goodput_demo.py", ["--fake-devices", "8", "--requests", "8",
                         "--out-dir", "/tmp/pipegoose_goodput_demo_test"]),
]


@pytest.mark.parametrize("script,args", CASES, ids=[c[0] for c in CASES])
def test_example_runs(script, args):
    env = {**ENV, **CACHE_ENV} if script in SERVING_DEMOS else ENV
    proc = subprocess.run(
        [sys.executable, str(REPO / "examples" / script), *args, "--steps", "2"],
        capture_output=True, text=True, timeout=900, cwd=str(REPO), env=env,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "done:" in proc.stdout, proc.stdout[-500:]
