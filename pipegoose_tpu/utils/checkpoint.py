"""Sharded, mesh-aware, crash-atomic checkpointing.

TPU-native analog of the reference's checkpoint utils
(pipegoose/nn/utils.py:11-50), which write one torch state_dict file per
(tp, pp) coordinate named ``pytorch_model_tp_{tp}_pp_{pp}.bin``
(constants.py:4-5) — no optimizer state, no resharding on load, no async
save (SURVEY.md §5 flags this as a capability gap). Here checkpoints are
orbax/tensorstore: every array is written once in a sharded,
layout-independent format, and restore RESHARDS onto whatever mesh the
current run uses (different tp/pp/dp than the run that saved — the thing
the reference's per-coordinate files cannot do). Optimizer state and
step counters ride along in the same tree.

Crash-atomicity contract (the elasticity stack depends on it):

- every save writes to a ``<final>.tmp`` SIBLING and ``os.rename``s to
  the final name only after orbax finishes — a kill at any point leaves
  either the previous state or a ``.tmp`` directory, never a torn
  directory under a valid ``step_N`` name;
- transient I/O errors (``OSError``) are retried with exponential
  backoff up to ``retries`` times before surfacing — a blip on a
  network filesystem must not lose a checkpoint cadence slot;
- :func:`latest_step` / :func:`available_steps` list only COMPLETE
  checkpoints: ``.tmp`` siblings and empty directories (a crashed
  rename-less writer) are skipped, so a resume or an
  ``AutoRecovery`` restore never points at a torn newest checkpoint.

Fault injection for tests and the chaos harness
(``pipegoose_tpu/testing/chaos.py``): :func:`set_io_fault_hook`
installs a callable invoked at the start of every save ATTEMPT; raising
``OSError`` from it simulates a transient storage failure and exercises
the retry path without monkeypatching orbax.
"""
from __future__ import annotations

import dataclasses
import os
import shutil
import time
from typing import Any, Callable, List, Optional

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from pipegoose_tpu.distributed.parallel_context import ParallelContext

#: suffix of the in-progress sibling a save writes before the atomic
#: rename; anything carrying it is by definition incomplete
TMP_SUFFIX = ".tmp"

# test/chaos seam: called at the start of every save attempt; raising
# OSError simulates a transient storage failure (the retry loop below
# absorbs up to `retries` of them)
_IO_FAULT_HOOK: Optional[Callable[[], None]] = None


def set_io_fault_hook(
    hook: Optional[Callable[[], None]]
) -> Optional[Callable[[], None]]:
    """Install (or clear, with None) the save-attempt fault hook;
    returns the previous hook so tests can restore it."""
    global _IO_FAULT_HOOK
    prev, _IO_FAULT_HOOK = _IO_FAULT_HOOK, hook
    return prev


def _checkpointer():
    import orbax.checkpoint as ocp

    return ocp.StandardCheckpointer()


def _retry_io(fn: Callable[[], None], retries: int, backoff_s: float) -> None:
    """Run ``fn``; a transient ``OSError`` retries it with exponential
    backoff, ``retries`` attempts beyond the first, then surfaces."""
    for attempt in range(retries + 1):
        try:
            return fn()
        except OSError:
            if attempt >= retries:
                raise
            time.sleep(backoff_s * (2 ** attempt))


def save_pretrained(
    params: Any,
    path: str,
    step: Optional[int] = None,
    retries: int = 3,
    backoff_s: float = 0.05,
) -> str:
    """Write a sharded checkpoint (reference save_pretrained,
    nn/utils.py:11-28). Directory layout is orbax-standard; ``step``
    creates a numbered subdirectory for resumable training runs.

    Crash-atomic: the tree lands in ``<final>.tmp`` first and is
    renamed into place only after orbax reports the write finished, so
    a kill mid-save never leaves a torn directory under the final
    name. Transient ``OSError``s retry with exponential backoff
    (``retries`` attempts beyond the first); persistent ones surface.
    """
    path = os.path.abspath(path)
    if step is not None:
        path = os.path.join(path, f"step_{step}")
    if os.path.exists(path):
        # mirrors orbax's own exists check, but BEFORE the tmp write so
        # a doomed save doesn't burn I/O (and the rename can't clobber)
        raise ValueError(f"checkpoint already exists: {path}")
    tmp = path + TMP_SUFFIX

    def write():
        if _IO_FAULT_HOOK is not None:
            _IO_FAULT_HOOK()
        if os.path.isdir(tmp):
            # stale sibling from a crashed/failed earlier attempt
            shutil.rmtree(tmp)
        ckpt = _checkpointer()
        ckpt.save(tmp, params)
        ckpt.wait_until_finished()

    _retry_io(write, retries, backoff_s)
    # the commit point: atomic on one fs. One process renames, and a
    # failed rename retries the rename alone: the save above is
    # collective, and a process that repeated it by itself would leave
    # the others waiting at the barrier below
    if jax.process_index() == 0:
        _retry_io(lambda: os.rename(tmp, path), retries, backoff_s)
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices(
            f"pipegoose_checkpoint_commit:{path}"
        )
    return path


def from_pretrained(
    path: str,
    like: Any,
    specs: Any = None,
    parallel_context: Optional[ParallelContext] = None,
) -> Any:
    """Restore onto the CURRENT mesh, resharding as needed (reference
    from_pretrained, nn/utils.py:31-50, could only reload the exact
    (tp, pp) layout that saved). ``like`` is a pytree of arrays or
    ShapeDtypeStructs giving structure/shape/dtype; ``specs`` (optional)
    a matching PartitionSpec tree for the target sharding. Under a
    ``ParallelContext``, a stored array whose shape differs from
    ``like``'s raises."""
    return _restore(path, like, specs, parallel_context)


def _restore(path, like, specs, parallel_context, recut=()):
    """``from_pretrained``; the top-level subtrees named in ``recut``
    may change shape on the way in (orbax truncates or zero-pads them),
    every other leaf must match its stored shape."""
    import orbax.checkpoint as ocp

    path = os.path.abspath(path)
    ctx = parallel_context or ParallelContext.get_context()

    def to_struct(x, spec):
        shape = x.shape
        dtype = x.dtype
        if ctx is not None and spec is not None:
            sharding = NamedSharding(ctx.mesh, spec)
        elif ctx is not None:
            sharding = NamedSharding(ctx.mesh, P())
        else:
            sharding = None
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    if specs is None:
        specs = jax.tree_util.tree_map(lambda _: None, like)
    target = jax.tree_util.tree_map(
        to_struct, like, specs, is_leaf=lambda x: hasattr(x, "shape")
    )
    restore_args = ocp.checkpoint_utils.construct_restore_args(target)

    def relax(key_path, arg):
        if key_path[0].key in recut and hasattr(arg, "strict"):
            return dataclasses.replace(arg, strict=False)
        return arg

    if recut:
        restore_args = jax.tree_util.tree_map_with_path(relax, restore_args)
    return ocp.PyTreeCheckpointer().restore(
        path,
        args=ocp.args.PyTreeRestore(item=target, restore_args=restore_args),
    )


def _complete_step(path: str, name: str) -> Optional[int]:
    """``step_N`` -> N for a COMPLETE checkpoint directory, else None.

    Complete means: the canonical name (no ``.tmp`` sibling suffix — a
    writer that died before its atomic rename), parseable step number,
    a real directory, and non-empty (an empty dir is a writer that died
    between mkdir and content)."""
    if not name.startswith("step_") or name.endswith(TMP_SUFFIX):
        return None
    try:
        n = int(name.split("_", 1)[1])
    except ValueError:
        return None
    full = os.path.join(path, name)
    if not os.path.isdir(full):
        return None
    try:
        if not os.listdir(full):
            return None
    except OSError:
        return None
    return n


def available_steps(path: str) -> List[int]:
    """Steps of every COMPLETE ``step_N`` checkpoint under ``path``,
    newest first — the fallback order ``AutoRecovery`` walks when the
    newest checkpoint fails to restore."""
    path = os.path.abspath(path)
    if not os.path.isdir(path):
        return []
    steps = []
    for name in os.listdir(path):
        n = _complete_step(path, name)
        if n is not None:
            steps.append(n)
    return sorted(steps, reverse=True)


def latest_step(path: str) -> Optional[int]:
    """Largest COMPLETE ``step_N`` subdirectory, for resume. ``.tmp``
    siblings and empty directories (torn writes) are skipped — a kill
    mid-save must not leave a newest checkpoint that resume or
    recovery would then fail (or worse, half-succeed) to restore."""
    steps = available_steps(path)
    return steps[0] if steps else None


def save_train_state(
    path: str, step: int, params: Any, opt_state: Any = None, extra: Any = None
) -> str:
    """Checkpoint the full training state (params + optimizer shards +
    counters) — absent from the reference entirely (SURVEY.md §5).
    Inherits :func:`save_pretrained`'s crash-atomic tmp+rename and
    transient-retry behavior."""
    tree = {"params": params}
    if opt_state is not None:
        tree["opt_state"] = opt_state
    if extra is not None:
        tree["extra"] = extra
    return save_pretrained(tree, path, step=step)


def restore_train_state(
    path: str,
    step: Optional[int],
    like: Any,
    specs: Any = None,
    parallel_context: Optional[ParallelContext] = None,
) -> Any:
    if step is None:
        step = latest_step(path)
        if step is None:
            raise FileNotFoundError(f"no step_N checkpoints under {path}")
    # only the optimizer state may be re-cut: a ZeRO moment's dim 0 is
    # zero-padded to a multiple of dp, so a restore onto a mesh with
    # another dp (the elastic 8 -> 4 path) drops or adds padding rows.
    # A parameter of another shape is another model, and raises
    return _restore(
        os.path.join(path, f"step_{step}"), like, specs, parallel_context,
        recut=("opt_state",),
    )
