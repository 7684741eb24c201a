"""Trainer loop: fit, callbacks, checkpoint+resume (the reference left
all of trainer/ as stubs — SURVEY.md §2.1)."""
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from pipegoose_tpu.distributed import ParallelContext
from pipegoose_tpu.models import bloom
from pipegoose_tpu.optim.zero import DistributedOptimizer
from pipegoose_tpu.trainer import (
    Callback,
    CheckpointCallback,
    Trainer,
    TrainerStatus,
)


@pytest.fixture()
def parts(devices):
    cfg = bloom.BloomConfig(vocab_size=64, hidden_size=32, n_layer=2, n_head=2)
    params = bloom.init_params(cfg, jax.random.PRNGKey(0))
    ctx = ParallelContext(tensor_parallel_size=2, data_parallel_size=4)
    yield cfg, params, ctx
    ctx.destroy()


def _batches(cfg, n, batch=8, seq=8):
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)))
    return [ids] * n  # same batch -> loss must fall


def test_fit_runs_and_learns(parts):
    cfg, params, ctx = parts

    def loss_fn(p, ids):
        return bloom.loss_fn(p, ids, None, ids, cfg, tp_axis="tensor")

    events = []

    class Probe(Callback):
        def on_fit_start(self, t):
            events.append("start")

        def on_step_end(self, t, step, loss):
            events.append(step)

        def on_fit_end(self, t):
            events.append("end")

    trainer = Trainer(
        loss_fn,
        params,
        bloom.tp_specs(params),
        DistributedOptimizer(optax.adam(1e-3), axis_name="data"),
        ctx,
        callbacks=[Probe()],
    )
    state = trainer.fit(_batches(cfg, 5))
    assert state.status == TrainerStatus.FINISHED
    assert state.step == 5
    assert state.losses[-1] < state.losses[0]
    assert events[0] == "start" and events[-1] == "end" and events[1:-1] == [1, 2, 3, 4, 5]


def test_checkpoint_and_resume(parts, tmp_path):
    cfg, params, ctx = parts

    def loss_fn(p, ids):
        return bloom.loss_fn(p, ids, None, ids, cfg, tp_axis="tensor")

    opt = DistributedOptimizer(optax.adam(1e-3), axis_name="data")
    specs = bloom.tp_specs(params)
    run_dir = str(tmp_path / "run")

    t1 = Trainer(loss_fn, params, specs, opt, ctx,
                 callbacks=[CheckpointCallback(run_dir, every=2)])
    t1.fit(_batches(cfg, 4))

    # resume picks up the step-4 checkpoint
    t2 = Trainer(loss_fn, params, specs, opt, ctx, resume_dir=run_dir)
    assert t2.state.step == 4
    st = t2.fit(_batches(cfg, 2), max_steps=6)
    assert st.step == 6
    # resumed params differ from the fresh init (training had progressed)
    diff = float(
        jnp.abs(
            t2.params["blocks"]["attn"]["qkv"]["kernel"]
            - params["blocks"]["attn"]["qkv"]["kernel"]
        ).max()
    )
    assert diff > 0


def test_evaluate(parts):
    """evaluate() returns the sharded mean loss without touching params,
    and reflects training progress."""
    cfg, params, ctx = parts

    def loss_fn(p, ids):
        return bloom.loss_fn(p, ids, None, ids, cfg, tp_axis="tensor")

    trainer = Trainer(
        loss_fn, params, bloom.tp_specs(params),
        DistributedOptimizer(optax.adam(1e-2), axis_name="data"), ctx,
    )
    batches = _batches(cfg, 3)
    before = trainer.evaluate(batches)
    # matches the single-device loss on the same (replicated) batch
    ref = float(bloom.loss_fn(params, batches[0], None, batches[0], cfg))
    assert abs(before - ref) < 2e-4, (before, ref)

    p_before = jax.tree_util.tree_map(np.asarray, trainer.params)
    again = trainer.evaluate(batches)
    assert again == before  # eval is pure: params unchanged
    for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(p_before),
        jax.tree_util.tree_leaves(trainer.params),
    ):
        np.testing.assert_array_equal(a, np.asarray(b), err_msg=str(path))

    trainer.fit(_batches(cfg, 5), max_steps=5)
    assert trainer.evaluate(batches) < before  # training reduced eval loss


def test_evaluate_token_weighted(parts):
    """weight_fn turns the batch mean into the corpus token-weighted
    mean — the number eval reports should quote for ragged batches
    (VERDICT r2 weak #6: equal weights misreport uneven batches)."""
    cfg, params, ctx = parts

    def loss_fn(p, batch):
        ids, mask = batch["ids"], batch["mask"]
        return bloom.loss_fn(p, ids, mask, ids, cfg, tp_axis="tensor")

    from jax.sharding import PartitionSpec as P

    trainer = Trainer(
        loss_fn, params, bloom.tp_specs(params),
        DistributedOptimizer(optax.adam(1e-3), axis_name="data"), ctx,
        batch_spec={"ids": P("data"), "mask": P("data")},
    )

    rng = np.random.RandomState(4)
    batches = []
    for n_valid in (8, 3):  # ragged: second batch mostly padding
        ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (8, 8)))
        mask = np.ones((8, 8), np.int32)
        mask[:, n_valid:] = 0
        batches.append({"ids": ids, "mask": jnp.asarray(mask)})

    def wf(b):
        return float(np.asarray(b["mask"])[:, 1:].sum())

    got = trainer.evaluate(batches, weight_fn=wf)

    # manual corpus token mean from per-batch (loss, tokens)
    tot = w = 0.0
    for b in batches:
        loss = float(bloom.loss_fn(params, b["ids"], b["mask"], b["ids"], cfg))
        tok = wf(b)
        tot += loss * tok
        w += tok
    assert abs(got - tot / w) < 2e-4, (got, tot / w)

    equal = trainer.evaluate(batches)
    assert abs(equal - got) > 1e-6  # the two means genuinely differ here


def test_loss_history_ring_bounds_and_converts():
    """LossHistory (trainer/state.py): the per-step loss record stays
    bounded (ring) and opportunistically converts entries older than
    sync_lag to host floats, so long runs don't accumulate thousands of
    live device arrays — while keeping the list API AutoRecovery's
    rollback slicing relies on."""
    from pipegoose_tpu.trainer.state import LossHistory

    h = LossHistory(maxlen=8, sync_lag=2)
    for i in range(20):
        h.append(jnp.float32(i))
    assert len(h) == 8
    assert [float(x) for x in h] == [12.0, 13.0, 14.0, 15.0, 16.0, 17.0,
                                     18.0, 19.0]
    # everything older than sync_lag is already a plain host float
    assert all(isinstance(x, float) for x in h[:-2])
    # the newest sync_lag entries may still be device arrays
    assert not isinstance(h[-1], float)
    # list surgery (AutoRecovery's rollback) still works
    del h[6:]
    assert len(h) == 6 and float(h[-1]) == 17.0
    with pytest.raises(ValueError, match="maxlen"):
        LossHistory(maxlen=0)


def test_fit_populates_bounded_losses_and_health(parts):
    """fit() with with_health=True exposes the in-graph health pytree on
    state.last_health, and state.losses is the bounded LossHistory."""
    from pipegoose_tpu.telemetry.health import host_health
    from pipegoose_tpu.trainer.state import LossHistory

    cfg, params, ctx = parts

    def loss_fn(p, ids):
        return bloom.loss_fn(p, ids, None, ids, cfg, tp_axis="tensor")

    trainer = Trainer(
        loss_fn, params, bloom.tp_specs(params),
        DistributedOptimizer(optax.adam(1e-3), axis_name="data"), ctx,
        with_health=True,
    )
    state = trainer.fit(_batches(cfg, 3))
    assert isinstance(state.losses, LossHistory)
    assert len(state.losses) == 3
    h = host_health(state.last_health)
    assert h is not None and np.isfinite(h["grad_norm"])
    assert set(h["grad_norm_per_module"]) == set(params.keys())
    assert h["nonfinite_grad_leaves"] == 0.0


def test_trainer_doctor_and_profiler_trace_dir(parts, tmp_path):
    """One Trainer, two ISSUE-4 hooks: doctor() diffs the live compiled
    step against its own param/ZeRO/batch specs (zero mismatches, zero
    partitioner-inserted collectives, memory budget grouped by arg),
    and fit(profiler_trace_dir=...) wraps the loop in
    jax.profiler.trace so an XLA timeline is one flag away."""
    import os

    from pipegoose_tpu import telemetry

    cfg, params, ctx = parts

    def loss_fn(p, ids):
        return bloom.loss_fn(p, ids, None, ids, cfg, tp_axis="tensor")

    trainer = Trainer(
        loss_fn, params, bloom.tp_specs(params),
        DistributedOptimizer(optax.adam(1e-3), axis_name="data"), ctx,
    )
    report = trainer.doctor(jax.ShapeDtypeStruct((8, 8), jnp.int32))
    assert report.sharding.mismatches() == []
    assert report.sharding.resharding_bytes == 0
    telemetry.assert_no_resharding(report)
    telemetry.assert_matches_intended(report)
    assert set(report.memory.groups) == {"params", "opt_state", "batch"}

    trace_dir = str(tmp_path / "xla_trace")
    state = trainer.fit(_batches(cfg, 2), profiler_trace_dir=trace_dir)
    assert state.step == 2
    written = [
        os.path.join(root, f)
        for root, _, files in os.walk(trace_dir) for f in files
    ]
    assert written, f"no profiler artifacts under {trace_dir}"


def test_trainer_profile_measures_and_training_continues(parts):
    """Trainer.profile() (ISSUE 14): the measured twin of doctor() —
    runs the REAL compiled hybrid step under the profiler, attributes
    the fenced wall into compute / per-axis collectives / idle (summing
    within 5%), caches last_step_profile, and — because the step
    donates its buffers — the trainer adopts the final params/opt state
    so fit() continues cleanly afterwards."""
    cfg, params, ctx = parts

    def loss_fn(p, ids):
        return bloom.loss_fn(p, ids, None, ids, cfg, tp_axis="tensor")

    trainer = Trainer(
        loss_fn, params, bloom.tp_specs(params),
        DistributedOptimizer(optax.adam(1e-3), axis_name="data"), ctx,
    )
    batch = _batches(cfg, 1)[0]
    prof = trainer.profile(batch, steps=2)
    assert prof.source == "device_trace"
    assert prof.n_devices == 8 and prof.steps == 2
    # the hybrid step's collectives ride both mesh axes
    assert set(prof.comm_by_axes) >= {"data", "tensor"}
    total = prof.compute_s + prof.comm_s + prof.idle_s
    assert abs(total - prof.wall_step_s) <= 0.05 * prof.wall_step_s
    assert trainer.last_step_profile is prof
    # profiled steps were real optimizer steps on adopted buffers:
    # training continues (a stale donated params ref would crash here)
    state = trainer.fit(_batches(cfg, 2))
    assert state.step == 2
    assert np.isfinite(float(state.last_loss))


def test_fit_annotates_data_and_step_for_the_profiler(parts, annotations):
    """``train.data`` / ``train.step`` are profiler annotations with the
    registry off (telemetry/spans.py): a profiler session sees the loop
    without any telemetry switched on."""
    from pipegoose_tpu.telemetry import get_registry

    cfg, params, ctx = parts
    assert not get_registry().enabled
    trainer = Trainer(
        lambda p, ids: bloom.loss_fn(p, ids, None, ids, cfg,
                                     tp_axis="tensor"),
        params, bloom.tp_specs(params),
        DistributedOptimizer(optax.adam(1e-3), axis_name="data"), ctx)
    trainer.fit(_batches(cfg, 2))
    # the third pull finds the batches exhausted
    assert [n for kind, n in annotations
            if kind == "enter" and n.startswith("train.")] == [
        "train.data", "train.step", "train.data", "train.step", "train.data"]
    assert not any(k.startswith("span.train.")
                   for k in get_registry().snapshot()["histograms"])
