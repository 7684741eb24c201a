"""Checkpoint round-trip + cross-mesh resharding — the capability the
reference's per-(tp,pp)-file scheme lacks (nn/utils.py:11-50)."""
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from pipegoose_tpu.distributed import ParallelContext
from pipegoose_tpu.models import bloom
from pipegoose_tpu.utils import checkpoint as ckpt


@pytest.fixture()
def cfg_params():
    cfg = bloom.BloomConfig(vocab_size=64, hidden_size=32, n_layer=2, n_head=2)
    return cfg, bloom.init_params(cfg, jax.random.PRNGKey(0))


def _trees_equal(a, b):
    for (path, x), y in zip(
        jax.tree_util.tree_leaves_with_path(a), jax.tree_util.tree_leaves(b)
    ):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y), err_msg=str(path))


def test_roundtrip_replicated(tmp_path, cfg_params, devices):
    cfg, params = cfg_params
    ctx = ParallelContext(data_parallel_size=2)
    try:
        path = ckpt.save_pretrained(params, str(tmp_path / "m"))
        restored = ckpt.from_pretrained(path, params)
        _trees_equal(params, restored)
    finally:
        ctx.destroy()


def test_reshard_tp2_to_tp4(tmp_path, cfg_params, devices):
    """Save under TP=2, restore under TP=4 — per-coordinate files can't
    do this; sharded arrays reshard transparently."""
    cfg, params = cfg_params
    ctx2 = ParallelContext(tensor_parallel_size=2, data_parallel_size=4)
    specs = bloom.tp_specs(params)
    from pipegoose_tpu.nn.parallel import shard_tree

    sharded = shard_tree(params, specs, ctx2)
    path = ckpt.save_pretrained(sharded, str(tmp_path / "m2"))
    ctx2.destroy()

    ctx4 = ParallelContext(tensor_parallel_size=4, data_parallel_size=2)
    try:
        restored = ckpt.from_pretrained(path, params, specs, ctx4)
        _trees_equal(params, restored)
        qkv = restored["blocks"]["attn"]["qkv"]["kernel"]
        # now sharded 4-way on the out dim
        assert qkv.sharding.shard_shape(qkv.shape)[-1] == qkv.shape[-1] // 4
    finally:
        ctx4.destroy()


def test_train_state_resume(tmp_path, cfg_params, devices):
    cfg, params = cfg_params
    ctx = ParallelContext(data_parallel_size=2)
    try:
        opt = optax.adam(1e-3)
        opt_state = opt.init(params)
        ckpt.save_train_state(str(tmp_path / "run"), 3, params, opt_state)
        ckpt.save_train_state(str(tmp_path / "run"), 7, params, opt_state)
        assert ckpt.latest_step(str(tmp_path / "run")) == 7
        like = {"params": params, "opt_state": opt_state}
        restored = ckpt.restore_train_state(str(tmp_path / "run"), None, like)
        _trees_equal(params, restored["params"])
        _trees_equal(opt_state, restored["opt_state"])
    finally:
        ctx.destroy()


def test_missing_checkpoint_raises(tmp_path, cfg_params, devices):
    cfg, params = cfg_params
    with pytest.raises(FileNotFoundError):
        ckpt.restore_train_state(str(tmp_path / "nope"), None, {"params": params})


def _zero_like_state(tmp_path):
    """A train state whose moment was cut for dp=4: a (2, 3) parameter,
    its (4, 3) ZeRO moment with two rows of padding."""
    w = jnp.arange(6, dtype=jnp.float32).reshape(2, 3)
    mu = jnp.concatenate([w + 10, jnp.zeros_like(w)])
    ckpt.save_train_state(str(tmp_path / "run"), 1, {"w": w}, {"mu": mu})
    return w, mu


def _sds(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


@pytest.mark.parametrize("rows", [2, 6], ids=["dp4_to_dp2", "dp4_to_dp6"])
def test_restore_recuts_the_zero_padding_of_opt_state_only(
    tmp_path, devices, rows
):
    """A ZeRO moment's dim 0 is padded to a multiple of dp, so a
    restore onto another dp drops or adds padding rows (the elastic
    8 -> 4 path) — and touches nothing else."""
    w, mu = _zero_like_state(tmp_path)
    ctx = ParallelContext(data_parallel_size=2)
    try:
        like = {"params": {"w": w}, "opt_state": {"mu": _sds(rows, 3)}}
        restored = ckpt.restore_train_state(str(tmp_path / "run"), 1, like)
    finally:
        ctx.destroy()
    np.testing.assert_array_equal(np.asarray(restored["params"]["w"]), w)
    want = np.zeros((rows, 3), np.float32)
    want[:2] = np.asarray(w) + 10
    np.testing.assert_array_equal(np.asarray(restored["opt_state"]["mu"]), want)


@pytest.mark.parametrize("via", ["restore_train_state", "from_pretrained"])
def test_restore_of_a_parameter_of_another_shape_raises(
    tmp_path, devices, via
):
    """Another vocab, width or TP padding is another model: it must not
    load truncated or zero-padded, whatever the optimizer state may."""
    _zero_like_state(tmp_path)
    like = {"params": {"w": _sds(1, 3)}, "opt_state": {"mu": _sds(4, 3)}}
    ctx = ParallelContext(data_parallel_size=2)
    try:
        with pytest.raises(ValueError, match="not compatible with the stored"):
            if via == "restore_train_state":
                ckpt.restore_train_state(str(tmp_path / "run"), 1, like)
            else:
                ckpt.from_pretrained(str(tmp_path / "run" / "step_1"), like)
    finally:
        ctx.destroy()


# -- crash-atomicity contract (ISSUE 9) ------------------------------------


def _tiny():
    return {"w": jnp.arange(4, dtype=jnp.float32)}


def test_latest_step_skips_tmp_and_empty_directories(tmp_path):
    """A kill mid-save leaves a ``.tmp`` sibling (writer died before
    its atomic rename) or an empty directory — neither may ever be the
    checkpoint resume or recovery points at."""
    import os

    run = tmp_path / "run"
    ckpt.save_train_state(str(run), 2, _tiny())
    os.makedirs(run / "step_9.tmp")
    (run / "step_9.tmp" / "partial").write_text("torn")
    os.makedirs(run / "step_7")  # mkdir happened, content never landed
    (run / "step_junk").mkdir()  # unparseable step number
    assert ckpt.available_steps(str(run)) == [2]
    assert ckpt.latest_step(str(run)) == 2
    restored = ckpt.restore_train_state(
        str(run), None, {"params": _tiny()})
    np.testing.assert_array_equal(
        np.asarray(restored["params"]["w"]), np.arange(4))


def test_save_is_committed_by_rename(tmp_path):
    """The ``.tmp`` sibling must be gone after a successful save — the
    rename IS the commit point, and a stale sibling from a failed
    earlier attempt is cleaned up on retry."""
    import os

    path = ckpt.save_train_state(str(tmp_path / "run"), 3, _tiny())
    assert os.path.isdir(path) and not os.path.exists(path + ckpt.TMP_SUFFIX)


def test_save_retries_transient_io_errors(tmp_path):
    from pipegoose_tpu.testing import TransientIOFault

    fault = TransientIOFault(2)
    prev = ckpt.set_io_fault_hook(fault)
    try:
        ckpt.save_train_state(str(tmp_path / "run"), 1, _tiny())
    finally:
        ckpt.set_io_fault_hook(prev)
    assert fault.fired == 2  # two transient failures absorbed
    assert ckpt.latest_step(str(tmp_path / "run")) == 1
    restored = ckpt.restore_train_state(
        str(tmp_path / "run"), 1, {"params": _tiny()})
    np.testing.assert_array_equal(
        np.asarray(restored["params"]["w"]), np.arange(4))


def test_failed_rename_retries_the_rename_not_the_save(tmp_path, monkeypatch):
    """The save is collective on a multi-process run; a process that
    repeated it alone after a failed commit would hang the others. So
    a transient rename failure repeats only the rename."""
    import os

    writes, renames = [], []
    real_rename = os.rename

    def flaky_rename(src, dst):
        # orbax renames too, inside the save; only the commit is flaky
        if str(dst) == str(tmp_path / "m"):
            renames.append(dst)
            if len(renames) == 1:
                raise OSError("transient rename failure")
        real_rename(src, dst)

    monkeypatch.setattr(ckpt.os, "rename", flaky_rename)
    prev = ckpt.set_io_fault_hook(lambda: writes.append(1))
    try:
        path = ckpt.save_pretrained(_tiny(), str(tmp_path / "m"),
                                    backoff_s=0.0)
    finally:
        ckpt.set_io_fault_hook(prev)
    assert len(writes) == 1 and len(renames) == 2
    assert os.path.isdir(path) and not os.path.exists(path + ckpt.TMP_SUFFIX)


def test_save_surfaces_persistent_io_errors(tmp_path):
    from pipegoose_tpu.testing import TransientIOFault

    prev = ckpt.set_io_fault_hook(TransientIOFault(99))
    try:
        with pytest.raises(OSError, match="chaos"):
            ckpt.save_pretrained(_tiny(), str(tmp_path / "m"),
                                 retries=2, backoff_s=0.0)
    finally:
        ckpt.set_io_fault_hook(prev)


def test_save_refuses_existing_checkpoint(tmp_path):
    ckpt.save_train_state(str(tmp_path / "run"), 1, _tiny())
    with pytest.raises(ValueError, match="already exists"):
        ckpt.save_train_state(str(tmp_path / "run"), 1, _tiny())
