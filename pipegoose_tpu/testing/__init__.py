"""Public testing utilities.

Analog of the reference's ``pipegoose/testing/utils.py`` (spawn /
init_parallel_context / calculate_parameter_similarity, testing/
utils.py:32-117). The reference simulates a cluster by spawning N OS
processes over gloo/TCP; on TPU the same coverage comes from XLA's
fake-device flag — one process, N CPU devices, exercising the real
jit/shard_map code paths (SURVEY.md §4). These helpers are what the
repo's own test suite builds on (tests/conftest.py).
"""
from __future__ import annotations

from typing import Any

from pipegoose_tpu.testing.chaos import (  # noqa: F401
    ChaosMonkey,
    ChaosSchedule,
    Injection,
    TransientIOFault,
    TransientTransferFault,
    schedule_fingerprint,
    tear_checkpoint,
)
from pipegoose_tpu.testing.fake_cluster import (  # noqa: F401
    fake_cluster,
    set_fake_device_flags,
)

__all__ = [
    "ChaosMonkey",
    "ChaosSchedule",
    "Injection",
    "TransientIOFault",
    "TransientTransferFault",
    "schedule_fingerprint",
    "tear_checkpoint",
    "fake_cluster",
    "set_fake_device_flags",
    "force_cpu_devices",
    "parameter_similarity",
    "assert_trees_allclose",
    "random_input_ids",
]


def force_cpu_devices(n: int = 8) -> None:
    """Pin the jax backend to ``n`` fake CPU devices.

    Back-compat alias of :func:`fake_cluster` (the reference's
    ``spawn``, testing/utils.py:32-41, plays this role with OS
    processes); new code should call ``fake_cluster`` directly for the
    returned device list and the ``require`` guard.
    """
    fake_cluster(n, require=False)


def parameter_similarity(tree_a: Any, tree_b: Any, rtol: float = 1e-3) -> float:
    """Fraction of leaves that are element-wise close — the reference's
    anti-false-positive guard (``calculate_parameter_similarity``,
    testing/utils.py:103-117): before asserting a parallelized run
    matches a reference run, assert the reference actually MOVED
    (similarity to its initial params < 1)."""
    import jax
    import numpy as np

    la = jax.tree_util.tree_leaves(tree_a)
    lb = jax.tree_util.tree_leaves(tree_b)
    if len(la) != len(lb):
        raise ValueError(f"tree sizes differ: {len(la)} vs {len(lb)}")
    close = sum(
        bool(np.allclose(np.asarray(a), np.asarray(b), rtol=rtol))
        for a, b in zip(la, lb)
    )
    return close / max(len(la), 1)


def assert_trees_allclose(
    got: Any, want: Any, rtol: float = 1e-5, atol: float = 1e-6, prefix: str = ""
) -> None:
    """np.testing.assert_allclose over two pytrees, leaf by leaf, with
    the tree path in the failure message. Tree structures must match —
    a silent zip over mismatched trees would truncate to the shorter."""
    import jax
    import numpy as np

    ts_got = jax.tree_util.tree_structure(got)
    ts_want = jax.tree_util.tree_structure(want)
    if ts_got != ts_want:
        raise AssertionError(
            f"{prefix}tree structures differ: {ts_got} vs {ts_want}"
        )
    for (path, w), g in zip(
        jax.tree_util.tree_leaves_with_path(want), jax.tree_util.tree_leaves(got)
    ):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=rtol, atol=atol,
            err_msg=f"{prefix}{jax.tree_util.keystr(path)}",
        )


def random_input_ids(vocab_size: int, shape: tuple, seed: int = 0):
    """Deterministic token batch (reference ``get_microbatch``,
    testing/utils.py:123-133, without the datasets dependency)."""
    import jax.numpy as jnp
    import numpy as np

    return jnp.asarray(np.random.RandomState(seed).randint(0, vocab_size, shape))
