"""Chaos harness (pipegoose_tpu/testing/chaos.py): seeded schedules are
byte-reproducible, injections fire once and are logged to the flight
recorder, the checkpoint-I/O fault seam arms/disarms, and the same seed
yields the identical post-recovery loss trajectory end to end."""
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from pipegoose_tpu.distributed import ParallelContext
from pipegoose_tpu.models import bloom
from pipegoose_tpu.optim.zero import DistributedOptimizer
from pipegoose_tpu.testing import (
    ChaosMonkey,
    ChaosSchedule,
    Injection,
    TransientIOFault,
    schedule_fingerprint,
)
from pipegoose_tpu.trainer import (
    AutoRecovery,
    CheckpointCallback,
    Trainer,
    TrainingDiverged,
)
from pipegoose_tpu.utils import checkpoint as ckpt


# -- schedule determinism (the acceptance pin) -----------------------------


def test_seeded_schedule_is_byte_reproducible():
    kw = dict(nonfinite_grads=2, host_stall=1, ckpt_io_error=1)
    a = ChaosSchedule.seeded(7, 50, **kw)
    b = ChaosSchedule.seeded(7, 50, **kw)
    # IDENTICAL, not similar: fingerprint equality is the contract
    assert schedule_fingerprint(a) == schedule_fingerprint(b)
    assert a == b and len(a) == 4
    assert schedule_fingerprint(a) != schedule_fingerprint(
        ChaosSchedule.seeded(8, 50, **kw)
    )


def test_adding_a_kind_never_perturbs_earlier_kinds():
    """KINDS-order drawing: extending a schedule with a kind drawn later
    must keep every earlier kind's steps — so a replay study can add
    chaos dimensions without invalidating its baseline runs."""
    a = ChaosSchedule.seeded(7, 50, nonfinite_grads=2)
    b = ChaosSchedule.seeded(7, 50, nonfinite_grads=2, ckpt_io_error=1)
    steps = lambda s, kind: [i.step for i in s.injections if i.kind == kind]
    assert steps(a, "nonfinite_grads") == steps(b, "nonfinite_grads")


def test_schedule_validation():
    with pytest.raises(ValueError, match="unknown chaos kind"):
        Injection(1, "cosmic_ray")
    with pytest.raises(ValueError, match="step must be >= 1"):
        Injection(0, "host_stall")
    with pytest.raises(ValueError, match="do not fit"):
        ChaosSchedule.seeded(0, 3, host_stall=4)  # 4 injections, 3 steps
    # distinct steps across ALL kinds — never two on one step
    s = ChaosSchedule.seeded(3, 10, nonfinite_grads=5, host_stall=5)
    assert len({i.step for i in s.injections}) == 10


# -- fire-once + flight-recorder logging -----------------------------------


class _RingStub:
    def __init__(self):
        self.records = []

    def record(self, kind, **fields):
        self.records.append({"kind": kind, **fields})


def test_injections_fire_once_and_log_to_recorder():
    """Recovery REWINDS the step counter, so post-rollback steps replay
    through the schedule; an injection is an event, not a property of a
    step number — the second pass must be a no-op."""
    ring = _RingStub()
    monkey = ChaosMonkey(
        ChaosSchedule([Injection(2, "host_stall", (("stall_s", 0.0),))]),
        recorder=ring,
    )
    monkey.on_step_start(None, 1)   # "step 2 about to run"
    monkey.on_step_start(None, 1)   # replay after a rewind
    assert len(monkey.applied) == 1
    assert [r["kind"] for r in ring.records] == ["chaos.injection"]
    assert ring.records[0]["injection"] == "host_stall"
    assert ring.records[0]["step"] == 2


def test_tick_hook_applies_only_serving_kinds():
    sched = ChaosSchedule([
        Injection(3, "host_stall", (("stall_s", 0.0),)),
        Injection(4, "ckpt_io_error"),  # trainer-side: tick must skip it
    ])
    monkey = ChaosMonkey(sched)
    monkey.tick_hook(None, 3)
    monkey.tick_hook(None, 4)
    assert [i.kind for i in monkey.applied] == ["host_stall"]


def test_ckpt_io_error_arms_the_fault_seam_and_disarms(tmp_path):
    monkey = ChaosMonkey(ChaosSchedule([
        Injection(1, "ckpt_io_error", (("fail_times", 2),))
    ]))
    monkey.on_step_start(None, 0)
    try:
        # the armed fault makes the next save fail twice; the bounded
        # retry+backoff path must absorb both and land the checkpoint
        path = ckpt.save_pretrained(
            {"w": jnp.ones((4,))}, str(tmp_path / "m"), backoff_s=0.0)
        assert monkey.io_faults[0].fired == 2
        restored = ckpt.from_pretrained(path, {"w": jnp.ones((4,))})
        np.testing.assert_array_equal(np.asarray(restored["w"]), np.ones(4))
    finally:
        monkey.disarm()
    # disarmed: saves no longer hit the fault
    ckpt.save_pretrained({"w": jnp.ones((4,))}, str(tmp_path / "m2"))
    assert monkey.io_faults[0].fired == 2


def test_abort_disarms_and_disarm_restores_external_hook(tmp_path):
    """Leak containment for the process-global fault seam: when fit
    raises, the trainer's ``on_fit_abort`` teardown must disarm the
    monkey's fault (an armed injection outliving the run that armed it
    would fail the NEXT run's saves), and disarm must RESTORE a
    pre-existing external hook rather than clobber it to None."""
    external_calls = []

    def external_hook():
        external_calls.append(1)

    prev = ckpt.set_io_fault_hook(external_hook)
    try:
        monkey = ChaosMonkey(ChaosSchedule([
            Injection(1, "ckpt_io_error", (("fail_times", 99),))
        ]))
        monkey.on_step_start(None, 0)   # arms: hook is now the fault
        with pytest.raises(OSError, match="chaos"):
            ckpt.save_pretrained({"w": jnp.ones((4,))},
                                 str(tmp_path / "m"), retries=0)
        # fit raising routes through on_fit_abort -> disarm
        monkey.on_fit_abort(None, RuntimeError("boom"))
        # the EXTERNAL hook is back in place (called, benign)
        ckpt.save_pretrained({"w": jnp.ones((4,))}, str(tmp_path / "m2"))
        assert external_calls, "external hook was clobbered, not restored"
        monkey.disarm()   # idempotent: restoring twice must not unhook
        ckpt.save_pretrained({"w": jnp.ones((4,))}, str(tmp_path / "m3"))
        assert len(external_calls) == 2
    finally:
        ckpt.set_io_fault_hook(prev)


def test_fit_raising_does_not_leak_armed_fault(tmp_path):
    """End to end through a REAL failing fit: an armed ``ckpt_io_error``
    whose run aborts (no checkpoint to restore -> TrainingDiverged)
    must not leave the process-global fault hook installed — the next
    run in the same process would inherit the injection. Also pins that
    the trainer's failure path calls ``on_fit_abort`` at all, and that
    legacy duck-typed callbacks without the hook keep working."""
    cfg = bloom.BloomConfig(vocab_size=64, hidden_size=32, n_layer=2,
                            n_head=2)
    params = bloom.init_params(cfg, jax.random.PRNGKey(0))

    def loss_fn(p, ids):
        base = bloom.loss_fn(p, ids, None, ids, cfg, tp_axis="tensor")
        return jnp.where(ids[0, 0] == 0, jnp.float32(jnp.nan), base)

    def batch(s, poison=False):
        ids = np.random.RandomState(s).randint(1, cfg.vocab_size, (8, 8))
        if poison:
            ids[0, 0] = 0
        return jnp.asarray(ids)

    class Legacy:  # duck-typed callback predating on_fit_abort
        order = 5
        def on_fit_start(self, t): pass
        def on_step_start(self, t, s): pass
        def on_step_end(self, t, s, l): pass
        def on_fit_end(self, t): pass

    run_dir = str(tmp_path / "run")
    ctx = ParallelContext(tensor_parallel_size=2, data_parallel_size=4)
    try:
        monkey = ChaosMonkey(ChaosSchedule([
            Injection(1, "ckpt_io_error", (("fail_times", 99),)),
        ]), checkpoint_dir=run_dir)
        trainer = Trainer(
            loss_fn, params, bloom.tp_specs(params),
            DistributedOptimizer(optax.adam(1e-3), axis_name="data"), ctx,
            # no CheckpointCallback: the restore finds nothing and raises
            callbacks=[monkey, AutoRecovery(run_dir), Legacy()],
        )
        with pytest.raises(TrainingDiverged):
            trainer.fit([batch(1), batch(2, poison=True)])
        assert monkey.io_faults and monkey.io_faults[0].remaining > 0
        # the abort path disarmed the still-loaded fault
        ckpt.save_pretrained({"w": jnp.ones((4,))}, str(tmp_path / "m"))
    finally:
        ctx.destroy()
        ckpt.set_io_fault_hook(None)  # belt-and-braces for suite safety


def test_transient_io_fault_counts_down():
    fault = TransientIOFault(2)
    for _ in range(2):
        with pytest.raises(OSError, match="chaos"):
            fault()
    fault()  # third call passes
    assert fault.fired == 2


# -- trajectory determinism (same seed => same post-recovery losses) -------


def _run_with_chaos(seed, tmp_path, tag):
    cfg = bloom.BloomConfig(vocab_size=64, hidden_size=32, n_layer=2,
                            n_head=2)
    params = bloom.init_params(cfg, jax.random.PRNGKey(0))

    def loss_fn(p, ids):
        return bloom.loss_fn(p, ids, None, ids, cfg, tp_axis="tensor")

    def batch(s):
        ids = np.random.RandomState(s).randint(1, cfg.vocab_size, (8, 8))
        return jnp.asarray(ids)

    run_dir = str(tmp_path / f"run_{tag}")
    ctx = ParallelContext(tensor_parallel_size=2, data_parallel_size=4)
    try:
        schedule = ChaosSchedule.seeded(
            seed, max_step=6, nonfinite_grads=1, min_step=2)
        monkey = ChaosMonkey(schedule, checkpoint_dir=run_dir)
        rec = AutoRecovery(run_dir, max_restores=2)
        trainer = Trainer(
            loss_fn, params, bloom.tp_specs(params),
            DistributedOptimizer(optax.adam(1e-3), axis_name="data"), ctx,
            callbacks=[monkey, CheckpointCallback(run_dir, every=1), rec],
        )
        state = trainer.fit([batch(s) for s in range(1, 8)])
        return (schedule, monkey.applied_json(), rec.restores,
                [float(l) for l in state.losses])
    finally:
        ctx.destroy()


def test_seeded_nonfinite_bomb_is_recovered_with_finite_losses(tmp_path):
    """One seeded run end to end: the bomb fires once, is rolled back to
    the last checkpoint once, and the run ends on finite losses."""
    _, applied, restores, losses = _run_with_chaos(11, tmp_path, "a")
    assert len(applied) == 1 and restores == 1
    assert losses and all(np.isfinite(losses))


def test_same_seed_same_injections_same_loss_trajectory(tmp_path):
    """The replayability contract end to end: two runs from one seed
    inject identically AND recover onto the identical loss trajectory —
    a chaos failure that cannot be replayed cannot be debugged."""
    sched_a, applied_a, restores_a, losses_a = _run_with_chaos(
        11, tmp_path, "a")
    sched_b, applied_b, restores_b, losses_b = _run_with_chaos(
        11, tmp_path, "b")
    assert schedule_fingerprint(sched_a) == schedule_fingerprint(sched_b)
    assert applied_a == applied_b and len(applied_a) == 1
    assert restores_a == restores_b == 1
    assert all(np.isfinite(losses_a))
    # bitwise, not approximately: same mesh, same data, same injections
    assert losses_a == losses_b
