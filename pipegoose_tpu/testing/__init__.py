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

from typing import Any, Callable

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
    "kernel_calls",
    "saved_residuals",
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


def kernel_calls(jaxpr: Any, name: str) -> int:
    """How often one run of ``jaxpr`` (``jax.make_jaxpr``'s result)
    calls the Pallas kernel named ``name``: a call inside a ``scan``
    counts once a trip, and every branch of a ``cond`` counts. What a
    program runs is then a fact of its jaxpr, with no chip: the flash
    forward once an attention layer, not twice
    (tests/ops/test_flash_attention.py)."""
    import jax

    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    calls = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            calls += eqn.params["name"] == name
            continue
        inner = sum(kernel_calls(sub, name)
                    for sub in jax.core.jaxprs_in_params(eqn.params))
        trips = eqn.params["length"] if eqn.primitive.name == "scan" else 1
        calls += trips * inner
    return calls


def saved_residuals(fn: Callable, *args: Any) -> list:
    """``[(shape, source)]`` of what ``jax.grad(fn)`` keeps of what
    ``fn`` COMPUTES (arguments and constants left out), sorted, as
    ``jax.ad_checkpoint.print_saved_residuals`` words them: a shape
    such as ``f32[2,64]`` and a source such as ``named 'flash_lse'``."""
    import contextlib
    import io

    from jax.ad_checkpoint import print_saved_residuals

    text = io.StringIO()
    with contextlib.redirect_stdout(text):
        print_saved_residuals(fn, *args)
    rows = (line.split(" ", 1) for line in text.getvalue().splitlines())
    return sorted((shape, rest.split(" from ")[0]) for shape, rest in rows
                  if not rest.startswith("from "))
