"""Fake-cluster bootstrap: one process, N CPU devices, real SPMD paths.

The ONE way this repo simulates a TPU slice on a host: XLA's
``--xla_force_host_platform_device_count`` flag plus a ``jax_platforms``
pin, so jit/shard_map programs compile and run against a real N-device
mesh without hardware (the reference spawned N OS processes over
gloo/TCP instead — testing/utils.py:32-41; SURVEY.md §4). Previously
copy-pasted between tests/conftest.py, the mesh-doctor CLI and every
example; now the parallelism planner (pipegoose_tpu/planner/), the
CLIs, and the test suite all call here.

Two entry points, split by WHEN they may run:

- :func:`set_fake_device_flags` — pure ``XLA_FLAGS`` env mutation,
  never imports jax. The only piece that must run before the backend
  initializes; safe (and required) in a conftest/module prologue.
- :func:`fake_cluster` — flags + ``jax_platforms="cpu"`` config pin,
  and returns the device list. The explicit "CPU for tests" switch, in
  one call, for scripts, benches, and examples.
"""
from __future__ import annotations

import os
import re
from typing import List

_COUNT_FLAG = "xla_force_host_platform_device_count"


def set_fake_device_flags(n: int = 8, override: bool = True) -> None:
    """Put ``--xla_force_host_platform_device_count=n`` into XLA_FLAGS.

    Env mutation only — jax is not imported, so this is safe at any
    point before the first backend touch. ``override=False`` keeps an
    existing count (the test-suite convention: an operator-set
    XLA_FLAGS wins over the conftest default).
    """
    flag = f"--{_COUNT_FLAG}={n}"
    flags = os.environ.get("XLA_FLAGS", "")
    if _COUNT_FLAG in flags:
        if override:
            flags = re.sub(rf"--{_COUNT_FLAG}=\d+", flag, flags)
    else:
        flags = (flags + " " + flag).strip()
    os.environ["XLA_FLAGS"] = flags


def fake_cluster(n: int = 8, require: bool = False,
                 override: bool = True) -> List:
    """Pin the jax backend to ``n`` fake CPU devices and return them.

    Must run before the first backend touch. ``require=True`` raises if the backend came up with fewer than
    ``n`` devices — i.e. it was already initialized with other flags —
    instead of silently planning/benching on the wrong mesh.
    ``override=False`` keeps an operator-set device count in XLA_FLAGS
    (see :func:`set_fake_device_flags`); ``n`` is then only the
    default.
    """
    kept_existing = not override and _COUNT_FLAG in os.environ.get(
        "XLA_FLAGS", "")
    set_fake_device_flags(n, override=override)
    import jax

    jax.config.update("jax_platforms", "cpu")
    if not kept_existing:  # don't fight an operator-set count
        try:
            jax.config.update("jax_num_cpu_devices", n)
        except RuntimeError:
            # backend already up: ``require`` reports the count below
            pass
    devices = jax.devices()
    if require and len(devices) < n:
        raise RuntimeError(
            f"fake_cluster({n}) got {len(devices)} device(s) — the jax "
            f"backend was initialized before the fake-device flags were "
            f"set (call fake_cluster/set_fake_device_flags earlier)"
        )
    return devices
