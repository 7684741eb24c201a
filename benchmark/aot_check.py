"""Compile a cell's programs at real size for a described v5e:2x2 —
no chip needed, nothing runs. Prints ``memory_analysis()`` of each
program: how ``num_pages`` is chosen, and how a four-chip call is
rehearsed before it is paid for.

    JAX_PLATFORMS=cpu python3 benchmark/aot_check.py --workload <cell>

A compile that passes here is not a chip run and is never reported as
one. This is a tool: the benchmark's runs never import it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HBM_BYTES = 16e9


def describe_topology():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


def _report(name, compiled, resident=0):
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    row = {"program": name,
           "argument_gb": ma.argument_size_in_bytes / 1e9,
           "output_gb": ma.output_size_in_bytes / 1e9,
           "alias_gb": ma.alias_size_in_bytes / 1e9,
           "temp_gb": ma.temp_size_in_bytes / 1e9,
           "program_total_gb": total / 1e9,
           "with_other_resident_gb": (total + resident) / 1e9,
           "fits_16gb": total + resident <= HBM_BYTES,
           "custom_calls": compiled.as_text().count("tpu_custom_call")}
    print(json.dumps(row), flush=True)
    return row


def check_train(config, workload, topo):
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from benchmark import program_bloom, weights
    from pipegoose_tpu import ParallelContext
    from pipegoose_tpu.models import bloom
    from pipegoose_tpu.optim.zero import DistributedOptimizer
    from pipegoose_tpu.parallel.hybrid import (
        make_hybrid_train_step,
        zero_state_spec,
    )

    sizes = config["sizes"]
    tp, dp = workload["mesh"]["tensor"], workload["mesh"]["data"]
    pctx = ParallelContext(tensor_parallel_size=tp, data_parallel_size=dp,
                           devices=topo.devices[:tp * dp])
    cfg = program_bloom.make_config(config, config.get("model_options"))
    dtype = jnp.dtype(config["dtype"])
    shapes = jax.eval_shape(
        lambda k: program_bloom.to_tree(weights.make(k, sizes, dtype)),
        jax.random.PRNGKey(0))
    specs = bloom.tp_specs(shapes)
    sds = jax.tree_util.tree_map(
        lambda x, s: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=NamedSharding(pctx.mesh, s)),
        shapes, specs)
    optimizer = DistributedOptimizer(optax.adam(workload["learning_rate"]),
                                     axis_name="data")
    init_fn, make_step = make_hybrid_train_step(
        lambda p, ids: bloom.loss_fn(p, ids, None, ids, cfg,
                                     tp_axis="tensor"),
        specs, optimizer, pctx)
    opt = jax.eval_shape(init_fn, sds)
    ospec = zero_state_spec(optimizer, shapes, specs, pctx.mesh)
    opt = jax.tree_util.tree_map(
        lambda x, s: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=NamedSharding(pctx.mesh, s)),
        opt, ospec)
    ids = jax.ShapeDtypeStruct(
        (workload["global_batch"], workload["seq"]), jnp.int32,
        sharding=NamedSharding(pctx.mesh, P("data")))
    compiled = make_step(sds).lower(sds, opt, ids).compile()
    row = _report("train_step", compiled)
    text = compiled.as_text()
    print(json.dumps({"collectives": {
        op: text.count(op + "(") + text.count(op + "-start(") for op in
        ("all-reduce", "reduce-scatter", "all-gather",
         "collective-permute")}}), flush=True)
    return [row]


def check_serve(config, workload, topo):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from benchmark import program_bloom, rooflines, weights
    from pipegoose_tpu.serving import ServingEngine

    sizes = config["sizes"]
    dtype = jnp.dtype(config["dtype"])
    one = SingleDeviceSharding(topo.devices[0])

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one)

    cfg = program_bloom.make_config(config)
    params = jax.tree_util.tree_map(sds, jax.eval_shape(
        lambda k: program_bloom.to_tree(weights.make(k, sizes, dtype)),
        jax.random.PRNGKey(0)))
    eng = ServingEngine(params, cfg, **workload["engine"])
    i32 = jnp.int32
    kp = jax.tree_util.tree_map(sds, eng.k_pages)
    vp = jax.tree_util.tree_map(sds, eng.v_pages)
    slots, width = eng.num_slots, eng.table_width
    weights_b = rooflines.all_params(sizes) * dtype.itemsize
    pool_b = sum(x.size * x.dtype.itemsize for x in
                 jax.tree_util.tree_leaves((eng.k_pages, eng.v_pages)))
    print(json.dumps({"weights_gb": weights_b / 1e9, "pool_gb": pool_b / 1e9,
                      **workload["engine"]}), flush=True)

    def vec(*shape):
        return jax.ShapeDtypeStruct(shape, i32, sharding=one)

    rows = [_report("decode_step", eng._step.lower(
        params, vec(slots), kp, vp, vec(slots, width), vec(slots)).compile())]
    bucket = max(workload["traffic"]["prompt_buckets"])
    pre = eng._prefill.lower(params, vec(1, bucket), vec(1, bucket)).compile()
    rows.append(_report(f"prefill_{bucket}", pre, resident=pool_b))
    cache = jax.tree_util.tree_map(sds, jax.eval_shape(
        eng._prefill, params, vec(1, bucket), vec(1, bucket))[1])
    rows.append(_report(f"write_{bucket}", eng._write.lower(
        kp, vp, cache, vec(width), jax.ShapeDtypeStruct((), i32, sharding=one)
    ).compile(), resident=weights_b))
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    import jax

    from benchmark import harness

    # the kernels choose their lane by the default backend, which here
    # is the CPU: steer them to the Mosaic lane for this compile only
    jax.default_backend = lambda: "tpu"
    spec = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    _, config, workload = harness.find_cell(spec, args.workload)
    topo = describe_topology()
    check = {"train": check_train, "serve": check_serve}[workload["driver"]]
    rows = check(config, workload, topo)
    return 0 if all(r["fits_16gb"] for r in rows) else 1


if __name__ == "__main__":
    sys.exit(main())
