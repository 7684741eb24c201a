"""Elastic-training demo: lose half the mesh mid-run, watch the run
replan, reshard, and keep going — no manual restart.

The run wires the full elasticity stack (docs/robustness.md):

- ``ChaosMonkey`` (testing/chaos.py) — a deterministic schedule
  injects a ``device_loss`` at step 3: half the 8-device fake cluster
  "is preempted", and the structured ``device_loss`` trigger fired
  through the ``FlightRecorder`` names the lost and surviving ids;
- ``ElasticRecovery`` (trainer/elastic.py) — consumes the trigger,
  picks a feasible layout at the surviving count (keep tp, shrink dp),
  rebuilds ``ParallelContext`` + the compiled hybrid step over exactly
  the survivors, cross-mesh-restores the step-2 orbax checkpoint, and
  lets ``fit`` resume — the same Python loop, now driving a 4-device
  program;
- the ``elastic_resume`` black box — ONE JSON artifact naming the lost
  devices, the chosen layout, the rewind step, and the doctor's
  zero-resharding verdict on the rebuilt program.

    python examples/elastic_training_demo.py --fake-devices 8 --tp 2 --dp 4
    JAX_PLATFORMS=cpu python examples/elastic_training_demo.py --steps 2

``--steps`` counts the POST-RESUME steps: the prologue (two clean
steps, a checkpoint at step 2, the loss at step 3) is fixed so the
demo always has a checkpoint to rewind to.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tp", type=int, default=2)
    ap.add_argument("--dp", type=int, default=4)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=16)
    ap.add_argument("--steps", type=int, default=3,
                    help="steps to run AFTER the reshard-and-resume")
    ap.add_argument("--lose", type=int, default=4,
                    help="devices lost at step 3")
    ap.add_argument("--out-dir", default="elastic_out")
    ap.add_argument("--fake-devices", type=int, default=None,
                    help="run on N fake CPU devices")
    args = ap.parse_args()
    if args.fake_devices:
        from pipegoose_tpu.testing import force_cpu_devices
        force_cpu_devices(args.fake_devices)

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from pipegoose_tpu.distributed import ParallelContext
    from pipegoose_tpu.models import bloom
    from pipegoose_tpu.optim.zero import DistributedOptimizer
    from pipegoose_tpu.telemetry import FlightRecorder
    from pipegoose_tpu.testing import ChaosMonkey, ChaosSchedule, Injection
    from pipegoose_tpu.trainer import (
        CheckpointCallback,
        ElasticRecovery,
        Trainer,
    )

    os.makedirs(args.out_dir, exist_ok=True)
    ckpt_dir = os.path.join(args.out_dir, "ckpt")
    bb_dir = os.path.join(args.out_dir, "blackbox")
    # the demo owns its out-dir: a stale step_N checkpoint from a prior
    # run would make orbax refuse the save
    shutil.rmtree(ckpt_dir, ignore_errors=True)
    shutil.rmtree(bb_dir, ignore_errors=True)

    cfg = bloom.BloomConfig(vocab_size=256, hidden_size=64, n_layer=2,
                            n_head=4)
    params = bloom.init_params(cfg, jax.random.PRNGKey(0))
    ctx = ParallelContext(tensor_parallel_size=args.tp,
                          data_parallel_size=args.dp)
    n0 = len(list(ctx.mesh.devices.flat))

    def loss_fn(p, ids):
        return bloom.loss_fn(p, ids, None, ids, cfg, tp_axis="tensor")

    def batches():
        rng = np.random.RandomState(0)
        # prologue (2 clean steps + the doomed step 3) + the resumed
        # tail; one extra batch replaces the rolled-back step's
        for _ in range(3 + args.steps + 1):
            yield jnp.asarray(
                rng.randint(1, cfg.vocab_size, (args.batch, args.seq))
            )

    recorder = FlightRecorder(bb_dir, capacity=32)
    monkey = ChaosMonkey(
        ChaosSchedule([Injection(3, "device_loss",
                                 (("n_lose", args.lose),))]),
        recorder=recorder, checkpoint_dir=ckpt_dir,
    )
    recovery = ElasticRecovery(ckpt_dir, max_restores=2, recorder=recorder)
    trainer = Trainer(
        loss_fn, params, bloom.tp_specs(params),
        DistributedOptimizer(optax.adam(1e-3), axis_name="data"), ctx,
        callbacks=[monkey, CheckpointCallback(ckpt_dir, every=2),
                   recorder, recovery],
    )
    state = trainer.fit(batches(), max_steps=3 + args.steps)

    assert recovery.restores == 1, recovery.restores
    assert all(np.isfinite(float(l)) for l in state.losses)
    (resume,) = recovery.resumes
    n1 = len(list(trainer.parallel_context.mesh.devices.flat))
    assert n1 == n0 - args.lose, (n0, n1)
    box = json.load(open(resume["dump_path"]))
    assert box["trigger"]["name"] == "elastic_resume"

    summary = {
        "devices_before": n0,
        "devices_after": n1,
        "lost_device_ids": resume["lost_device_ids"],
        "layout_after": resume["layout"],
        "restored_step": resume["restored_step"],
        "doctor_zero_resharding": resume["doctor_zero_resharding"],
        "steps": state.step,
        "final_loss": round(float(state.losses[-1]), 4),
        "black_box": resume["dump_path"],
    }
    print(json.dumps(summary, indent=2))
    print(
        f"done: lost {args.lose} of {n0} devices at step 3, replanned to "
        f"dp={resume['layout']['dp']} tp={resume['layout']['tp']} on the "
        f"{n1} survivors, cross-mesh-restored step "
        f"{resume['restored_step']}, and ran to step {state.step} — see "
        f"{os.path.basename(resume['dump_path'])}"
    )


if __name__ == "__main__":
    main()
