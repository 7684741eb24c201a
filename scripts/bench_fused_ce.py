"""The fused cross-entropy's two kernels ALONE, at a head's shape given on
the command line: median of fenced calls, the plan each kernel took.

    python scripts/bench_fused_ce.py --tokens 16384 --rows 250880 --hidden 1024
    python scripts/bench_fused_ce.py --tokens 16384 --rows 19456 --hidden 2048 \
        --valid 19360 --block-v 256

``--rows`` are the rows of the head ONE device holds (the vocabulary over
``tensor``), ``--block-v`` the backward's tile as a model passes it (the
forward plans its own). The three train cells' shapes are in
``tests/ops/test_chip_compile.py:CE_CELLS``. On the TPU each kernel is
compiled, called once unmeasured and then ``--calls`` times, every call
fenced by ``block_until_ready``; the line holds the median, every call,
the achieved TFLOP/s and the share of the chip's peak
(``benchmark/peaks.json``; an unknown device kind is an error). Off the
TPU nothing is timed: the plans are printed and, at whatever small shape
was asked for, the interpreter's results are held against a dense head.

A step's head is these two calls and nothing else, so their times are the
device-trace items ``jvp_fused_ce_fwd_`` and ``transpose_jvp_fused_ce_bwd__``
of a train cell (PERF.md §5). Nothing a cell runs imports this file.
"""
import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

MATMULS = {"fwd": 1, "bwd": 3}  # of 2 * tokens * rows * hidden each


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tokens", type=int, required=True)
    ap.add_argument("--rows", type=int, required=True)
    ap.add_argument("--hidden", type=int, required=True)
    ap.add_argument("--valid", type=int, default=None)
    ap.add_argument("--layout", choices=["vh", "hv"], default="vh")
    ap.add_argument("--block-v", type=int, default=512)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


def operands(args):
    """``(h, w, targets, lse, g, offset)`` on the default device, from
    the seed; ``lse`` and ``g`` are the backward's, made plausible (a
    uniform softmax's lse, unit weights)."""
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(args.dtype)
    kh, kw, kt = jax.random.split(jax.random.PRNGKey(args.seed), 3)
    h = (jax.random.normal(kh, (args.tokens, args.hidden)) * 0.3).astype(dtype)
    w_shape = ((args.rows, args.hidden) if args.layout == "vh"
               else (args.hidden, args.rows))
    w = (jax.random.normal(kw, w_shape) * 0.3).astype(dtype)
    targets = jax.random.randint(kt, (args.tokens,), 0,
                                 args.valid or args.rows, jnp.int32)
    lse = jnp.full((args.tokens,), jnp.log(float(args.rows)), jnp.float32)
    g = jnp.ones((args.tokens,), jnp.float32)
    return h, w, targets, lse, g, jnp.zeros((1,), jnp.int32)


def plans(args, limit):
    """What each kernel's picker takes at this shape under ``limit``."""
    import jax.numpy as jnp

    from pipegoose_tpu.ops import fused_ce

    itemsize = jnp.dtype(args.dtype).itemsize
    bt, ni, n_super, bv = fused_ce._pick_fwd_plan(
        args.tokens, args.rows, args.hidden, itemsize, limit)
    block_v, exact_v = fused_ce._pick_block(args.rows, args.block_v)
    bwd_t = fused_ce._token_block(args.tokens, 256)
    bni, bn_super = fused_ce._pick_super_block(
        args.tokens, bwd_t, block_v, args.hidden, itemsize, limit)
    return {
        "vmem_limit_bytes": limit,
        "fwd": {"block_t": bt, "resident_tokens": bt * ni,
                "head_walks": n_super, "block_v": bv,
                "exact": bool(fused_ce._fwd_vocab_tiles(args.rows)),
                "grid_steps": n_super * (args.rows // bv),
                "vmem_bytes": fused_ce._fwd_working_set_bytes(
                    bt * ni, bt, bv, args.hidden, itemsize)},
        "bwd": {"block_t": bwd_t, "resident_tokens": bwd_t * bni,
                "head_walks": bn_super, "block_v": block_v,
                "exact": exact_v,
                "grid_steps": bn_super * (args.rows // block_v) * bni,
                "vmem_bytes": fused_ce._bwd_working_set_bytes(
                    bwd_t * bni, bwd_t, block_v, args.hidden, itemsize)},
    }


def kernels(args, interpret):
    """The two jitted calls: the forward's ``(lse, target logit)`` and
    the backward's ``(dh, dw)``."""
    import jax

    from pipegoose_tpu.ops import fused_ce

    vh = args.layout == "vh"
    block_v = fused_ce._pick_block(args.rows, args.block_v)[0]

    def fwd(h, w, targets, lse, g, offset):
        return fused_ce._fwd_pallas(h, w, targets, offset, args.valid,
                                    interpret, vh)

    def bwd(h, w, targets, lse, g, offset):
        return fused_ce._bwd_pallas(h, w, targets, lse, g, offset,
                                    args.valid,
                                    fused_ce._token_block(args.tokens, 256),
                                    block_v, interpret, vh)

    return {"fwd": jax.jit(fwd), "bwd": jax.jit(bwd)}


def timed(fn, ops, calls):
    """Seconds of each of ``calls`` fenced calls, the first call (which
    compiles) apart."""
    import jax

    t0 = time.perf_counter()
    jax.block_until_ready(fn(*ops))
    first = time.perf_counter() - t0
    each = []
    for _ in range(calls):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*ops))
        each.append(time.perf_counter() - t0)
    return first, each


def against_dense(args, ops, fns):
    """Largest gaps of the interpreter's forward against a dense head
    (float32 logits): the rehearsal's check, small shapes only."""
    import jax
    import jax.numpy as jnp

    h, w, targets, _, _, _ = ops
    w_vh = w if args.layout == "vh" else w.T
    logits = jnp.einsum("th,vh->tv", h.astype(jnp.float32),
                        w_vh.astype(jnp.float32))
    if args.valid is not None:
        logits = jnp.where(jnp.arange(args.rows) < args.valid, logits, -1e9)
    lse, tl = fns["fwd"](*ops)
    want_lse = jax.nn.logsumexp(logits, axis=1)
    want_tl = jnp.take_along_axis(logits, targets[:, None], 1)[:, 0]
    return {"lse_gap_max": float(jnp.abs(lse - want_lse).max()),
            "target_logit_gap_max": float(jnp.abs(tl - want_tl).max())}


def main(argv=None):
    args = parse(argv)
    import jax

    from pipegoose_tpu.ops import flash_attention

    device = jax.devices()[0]
    on_chip = device.platform == "tpu"
    line = {
        "device": {"platform": device.platform,
                   "device_kind": device.device_kind,
                   "count": jax.device_count()},
        "shape": {k: getattr(args, k) for k in
                  ("tokens", "rows", "hidden", "valid", "layout", "block_v",
                   "dtype")},
        "plan": plans(args, flash_attention._vmem_limit_bytes()),
    }
    ops = operands(args)
    fns = kernels(args, interpret=not on_chip)
    if not on_chip:
        line["timed"] = "not measured: no TPU here"
        line["interpreter_against_dense"] = against_dense(args, ops, fns)
        print(json.dumps(line))
        return 0
    from benchmark import rooflines

    peak = rooflines.peaks_for(device.device_kind)["flops_per_s"]["bfloat16"]
    work = 2.0 * args.tokens * (args.valid or args.rows) * args.hidden
    for kind, fn in fns.items():
        first, each = timed(fn, ops, args.calls)
        median = statistics.median(each)
        flops = MATMULS[kind] * work
        line[kind] = {
            "calls": args.calls, "first_call_s": round(first, 3),
            "ms": round(median * 1e3, 4),
            "ms_each": [round(s * 1e3, 4) for s in each],
            "tflops_per_s": round(flops / median / 1e12, 2),
            "share_of_peak_pct": round(100.0 * flops / median / peak, 2),
        }
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
