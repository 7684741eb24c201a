"""Flight-recorder demo: an injected mid-run gradient overflow, caught
by the in-graph health stats, black-boxed by the flight recorder,
healed by AutoRecovery, and exported as a Perfetto trace.

The run wires the full health/forensics stack
(docs/observability.md):

- ``Trainer(with_health=True)`` — the compiled step also returns
  global + per-module grad norms, update stats, and nonfinite-leaf
  counts (telemetry/health.py);
- ``FlightRecorder`` — rings the last N step records and, on the
  poisoned step (an ``inf`` gradient bomb localized to the embedding
  group), dumps ``blackbox_stepNNNNNNNN_nonfinite.json`` naming the
  offending module group;
- ``AutoRecovery(recorder=...)`` — consumes the structured trigger,
  restores the last checkpoint, and the run continues to its target
  step count;
- ``ChromeTraceExporter`` — the span stream plus a theoretical
  ``GPipeScheduler`` clock timeline land in ``trace.json``; open it at
  https://ui.perfetto.dev, and the ``pipeline.bubble_fraction`` gauge
  sits next to the MFU gauge in the snapshot.

    python examples/flight_recorder_demo.py --fake-devices 8 --tp 2 --dp 4
    JAX_PLATFORMS=cpu python examples/flight_recorder_demo.py --steps 4
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import shutil


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=16)
    ap.add_argument("--steps", type=int, default=4)
    ap.add_argument("--out-dir", default="flightrec_out")
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

    from pipegoose_tpu import telemetry
    from pipegoose_tpu.distributed import ParallelContext
    from pipegoose_tpu.models import bloom
    from pipegoose_tpu.nn.pipeline_parallel.scheduler import GPipeScheduler
    from pipegoose_tpu.optim.zero import DistributedOptimizer
    from pipegoose_tpu.telemetry import (
        ChromeTraceExporter,
        FlightRecorder,
        TelemetryCallback,
        register_pipeline_gauges,
    )
    from pipegoose_tpu.trainer import AutoRecovery, CheckpointCallback, Trainer

    os.makedirs(args.out_dir, exist_ok=True)
    ckpt_dir = os.path.join(args.out_dir, "ckpt")
    bb_dir = os.path.join(args.out_dir, "blackbox")
    trace_path = os.path.join(args.out_dir, "trace.json")
    # the demo owns its out-dir: a stale step_N checkpoint from a prior
    # run would make orbax refuse the save (and stale black boxes would
    # confuse the assertions below)
    shutil.rmtree(ckpt_dir, ignore_errors=True)
    shutil.rmtree(bb_dir, ignore_errors=True)

    cfg = bloom.BloomConfig(vocab_size=256, hidden_size=64, n_layer=2,
                            n_head=4)
    params = bloom.init_params(cfg, jax.random.PRNGKey(0))
    ctx = ParallelContext(tensor_parallel_size=args.tp,
                          data_parallel_size=args.dp)

    POISON = 0  # batches whose first token is 0 detonate the bomb

    def loss_fn(p, ids):
        base = bloom.loss_fn(p, ids, None, ids, cfg, tp_axis="tensor")
        # gradient-overflow injector: inf * ||embed||^2 poisons the
        # embedding group's gradients (and only that group) — the
        # stand-in for a real bad-batch / optimizer blow-up
        bomb = jnp.where(ids[0, 0] == POISON, jnp.float32(jnp.inf), 0.0)
        return base + bomb * jnp.sum(
            jnp.square(p["embed"]["weight"].astype(jnp.float32))
        )

    def batches():
        rng = np.random.RandomState(0)
        # one extra batch: the poisoned step is rolled back and its
        # replacement comes from the stream's tail
        for i in range(args.steps + 1):
            ids = rng.randint(1, cfg.vocab_size, (args.batch, args.seq))
            if i == 1:  # mid-run: after the first checkpoint exists
                ids[0, 0] = POISON
            yield jnp.asarray(ids)

    reg = telemetry.get_registry()
    trace = ChromeTraceExporter(trace_path, registry=reg)
    recorder = FlightRecorder(bb_dir, capacity=32)
    recovery = AutoRecovery(ckpt_dir, max_restores=2, recorder=recorder)
    trainer = Trainer(
        loss_fn, params, bloom.tp_specs(params),
        DistributedOptimizer(optax.adam(1e-3), axis_name="data"), ctx,
        with_health=True,
        callbacks=[
            CheckpointCallback(ckpt_dir, every=1),
            recorder,
            recovery,
            TelemetryCallback(fence=True),  # enables the registry too
        ],
    )
    state = trainer.fit(batches(), max_steps=args.steps)

    assert recovery.restores == 1, recovery.restores
    assert state.step == args.steps, state.step
    dumps = sorted(glob.glob(os.path.join(bb_dir, "blackbox_*.json")))
    assert dumps, "gradient overflow produced no black box"
    box = json.load(open(dumps[0]))
    assert box["trigger"]["name"] == "nonfinite"
    assert "'embed'" in box["trigger"]["reason"]

    # Perfetto trace: measured spans + the theoretical pipeline clock
    # timeline of an (M=8, P=4) GPipe schedule next to them
    sched = GPipeScheduler(8, 4)
    step_p50 = reg.histogram("span.train.step.seconds").quantile(0.5)
    bubble = register_pipeline_gauges(sched, registry=reg,
                                      step_seconds=step_p50)
    trace.add_pipeline_timeline(sched, clock_s=max(step_p50, 1e-3) / 8)
    trace.write()
    trace.close()

    final_health = telemetry.host_health(state.last_health)
    summary = {
        "steps": state.step,
        "restores": recovery.restores,
        "trigger": box["trigger"]["name"],
        "trigger_reason": box["trigger"]["reason"],
        "black_box": dumps[0],
        "final_grad_norm": round(final_health["grad_norm"], 4),
        "final_update_ratio": round(final_health["update_ratio"], 6),
        "pipeline_bubble_fraction": round(bubble, 4),
        "trace": trace_path,
    }
    print(json.dumps(summary, indent=2))
    print(
        f"done: {state.step} steps with 1 gradient overflow black-boxed "
        f"({os.path.basename(dumps[0])}) and auto-recovered; open "
        f"{trace_path} in ui.perfetto.dev"
    )


if __name__ == "__main__":
    main()
