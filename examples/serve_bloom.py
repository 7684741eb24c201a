"""Continuous-batching BLOOM serving over the paged KV pool — mixed-
length requests multiplexed through a fixed slot set by
``ServingEngine.run`` (pipegoose_tpu/serving/, docs/serving.md).

    python examples/serve_bloom.py --fake-devices 8 --tp 2
    python examples/serve_bloom.py --requests 12 --slots 4
"""
from __future__ import annotations

import argparse
import json

import jax
import numpy as np

from pipegoose_tpu.models import bloom


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--slots", type=int, default=3)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--max-context", type=int, default=64)
    ap.add_argument("--steps", type=int, default=None,
                    help="cap max_new_tokens per request (smoke runs)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--fake-devices", type=int, default=None,
                    help="run on N fake CPU devices")
    args = ap.parse_args()
    if args.fake_devices:
        from pipegoose_tpu.testing import force_cpu_devices
        force_cpu_devices(args.fake_devices)

    from pipegoose_tpu.distributed import ParallelContext
    from pipegoose_tpu.serving import Request, ServingEngine

    cfg = bloom.BloomConfig(vocab_size=256, hidden_size=128, n_layer=2,
                            n_head=4)
    params = bloom.init_params(cfg, jax.random.PRNGKey(0))

    # a mixed-length workload: short chats next to long completions —
    # exactly where padded batching wastes decode steps
    rng = np.random.RandomState(args.seed)
    requests = []
    for _ in range(args.requests):
        prompt_len = int(rng.randint(2, args.max_context // 2))
        max_new = int(rng.randint(2, args.max_context - prompt_len))
        if args.steps:
            max_new = min(max_new, args.steps)
        requests.append(Request(
            prompt=rng.randint(1, cfg.vocab_size, (prompt_len,)),
            max_new_tokens=max_new))

    ctx = mesh = param_specs = None
    if args.tp > 1:
        dp = max(len(jax.devices()) // args.tp, 1)
        ctx = ParallelContext(tensor_parallel_size=args.tp,
                              data_parallel_size=dp)
        mesh, param_specs = ctx.mesh, bloom.tp_specs(params)
    try:
        pool_pages = 1 + args.slots * (args.max_context // args.page_size)
        engine = ServingEngine(
            params, cfg, num_slots=args.slots, num_pages=pool_pages,
            page_size=args.page_size, max_context=args.max_context,
            mesh=mesh, param_specs=param_specs,
        )
        outputs, metrics = engine.run(requests)
    finally:
        if ctx is not None:
            ctx.destroy()

    del metrics["tick_timeline"]    # a row a tick: too long to print
    print(json.dumps(metrics, indent=2))
    print(
        f"done: {len(outputs)} requests through {args.slots} slots "
        f"(tp={args.tp}), {metrics['generated_tokens']} tokens in "
        f"{metrics['decode_steps']} decode steps, slot occupancy "
        f"{metrics['slot_occupancy']}"
    )


if __name__ == "__main__":
    main()
