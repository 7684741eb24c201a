"""Did a change move another cell's program? Hashes of the LOWERED
programs of the benchmark's cells, one git revision beside another.

    python scripts/lowered_program_hashes.py HEAD index
    python scripts/lowered_program_hashes.py HEAD~1 HEAD --only bloom-1b7

For each revision (``index`` is what ``git write-tree`` holds) the tree
is unpacked into ONE and the same directory in turn (``_scratch/lowered/
tree``: a Mosaic kernel's bytecode carries file:line of every frame
under the checkout, so two paths never hash alike), and a child process
lowers, for a described v5e (no chip; nothing is compiled or run, the
weights are shapes):

- the train step of the three training cells, as their drivers build it;
- the largest prefill bucket of each served family that runs a flash
  kernel (``bloom-560m.serve-chat-r8`` runs none).

Two hashes a cell: of the text as it is, and of the text with every
Mosaic kernel re-printed WITHOUT source locations. An edit that moves
lines of a kernel's file (a docstring) changes the first and not the
second; the second differing means the program differs. The texts stay
in ``_scratch/lowered/<revision>/`` for ``diff``. Exit code 1 where a
cell's second hash differs between the two revisions, and the cells are
named; PERF.md, PR 49, holds the readings this was written for.
"""
import argparse
import base64
import hashlib
import importlib
import json
import os
import re
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORK = os.path.join(ROOT, "_scratch", "lowered")


def sha(text: str) -> str:
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def without_kernel_locations(text: str) -> str:
    """The lowered text with each Mosaic kernel's bytecode replaced by
    the hash of its module printed without debug info."""
    from jax._src.interpreters import mlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    ctx = mlir.make_ir_context()
    tpu.register_dialect(ctx)
    ctx.allow_unregistered_dialects = True

    def one(m):
        with ctx:
            mod = ir.Module.parse(base64.b64decode(m.group(1)))
            return 'body\\22: \\22<' + sha(
                mod.operation.get_asm(enable_debug_info=False)) + '>'

    return re.sub(r'body\\22: \\22([A-Za-z0-9+/=]+)', one, text)


def lower_tree(tree: str, out: str, only: str) -> dict:
    """Runs in the child, inside ``tree``: {cell: hashes}."""
    sys.path.insert(0, tree)
    os.chdir(tree)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    import jax.numpy as jnp

    # the kernels ask the default backend whether to interpret: lower
    # what the chip would run
    jax.default_backend = lambda: "tpu"
    from benchmark import aot_check, harness

    topo = aot_check.describe_topology()
    chip = topo.devices[0]
    spec = harness.load_json(os.path.join(tree, "BENCHMARK.json"))
    key = jax.random.PRNGKey(0)

    def parts(config):
        names = config["program"]
        return tuple(harness.load_module(os.path.join(
            harness.HERE, names[k] + ".py")) for k in ("adapter", "weights"))

    def bloom_train(cell):
        import optax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from benchmark import program_bloom, weights
        from pipegoose_tpu import ParallelContext
        from pipegoose_tpu.models import bloom
        from pipegoose_tpu.optim.zero import DistributedOptimizer
        from pipegoose_tpu.parallel.hybrid import (make_hybrid_train_step,
                                                   zero_state_spec)

        _, config, workload = harness.find_cell(spec, cell)
        tp, dp = workload["mesh"]["tensor"], workload["mesh"]["data"]
        pctx = ParallelContext(tensor_parallel_size=tp, data_parallel_size=dp,
                               devices=topo.devices[:tp * dp])
        cfg = program_bloom.make_config(config, config.get("model_options"))
        dtype = jnp.dtype(config["dtype"])
        shapes = jax.eval_shape(lambda k: program_bloom.to_tree(
            weights.make(k, config["sizes"], dtype)), key)
        specs = bloom.tp_specs(shapes)

        def placed(tree_, specs_):
            return jax.tree_util.tree_map(
                lambda x, s: jax.ShapeDtypeStruct(
                    x.shape, x.dtype, sharding=NamedSharding(pctx.mesh, s)),
                tree_, specs_)

        params = placed(shapes, specs)
        optimizer = DistributedOptimizer(
            optax.adam(workload["learning_rate"]), axis_name="data")
        init_fn, make_step = make_hybrid_train_step(
            lambda p, ids: bloom.loss_fn(p, ids, None, ids, cfg,
                                         tp_axis="tensor"),
            specs, optimizer, pctx)
        opt = placed(jax.eval_shape(init_fn, params),
                     zero_state_spec(optimizer, shapes, specs, pctx.mesh))
        ids = jax.ShapeDtypeStruct(
            (workload["global_batch"], workload["seq"]), jnp.int32,
            sharding=NamedSharding(pctx.mesh, P("data")))
        try:
            with jax.default_device(chip):
                return make_step(params).lower(params, opt, ids).as_text()
        finally:
            pctx.destroy()

    def expert_train(cell):
        _, config, workload = harness.find_cell(spec, cell)
        adapter, weights = parts(config)
        cfg = adapter.make_config(config, config.get("model_options"))
        dtype = jnp.dtype(config["dtype"])
        shapes = jax.eval_shape(lambda k: adapter.to_tree(
            weights.make(k, adapter.sizes(config), dtype), config), key)
        model = adapter._model()
        ids = jax.ShapeDtypeStruct(
            (workload["global_batch"], workload["seq"]), jnp.int32)
        fn = jax.value_and_grad(lambda p, i: model.loss_and_counters(
            p, i, None, i, cfg, tp_axis=None), has_aux=True)
        with jax.default_device(chip):
            return jax.jit(fn).trace(shapes, ids).lower(
                lowering_platforms=("tpu",)).as_text()

    def prefill(cell):
        _, config, workload = harness.find_cell(spec, cell)
        adapter, weights = parts(config)
        cfg = adapter.make_config(config)
        dtype = jnp.dtype(config["dtype"])
        shapes = jax.eval_shape(lambda k: adapter.to_tree(
            weights.make(k, adapter.sizes(config), dtype), config), key)
        model = importlib.import_module(type(cfg).__module__)
        ids = jax.ShapeDtypeStruct(
            (1, max(workload["traffic"]["prompt_buckets"])), jnp.int32)
        with jax.default_device(chip):
            return jax.jit(lambda p, i, m: model.prefill(p, i, m, cfg)).trace(
                shapes, ids, ids).lower(lowering_platforms=("tpu",)).as_text()

    jobs = [("bloom-560m.train-b8s2048", bloom_train),
            ("bloom-1b7.train-tp2dp2", bloom_train),
            ("glm-4.7-flash.train-ep8share-b4s4096", expert_train),
            ("laguna-s-2.1.serve-mix-ep2share-s32", prefill),
            ("falcon-h1-34b.serve-chat-s64", prefill),
            ("longcat-flash-omni.serve-doc-ep32share-s16", prefill),
            ("evabyte.serve-bytes-s8", prefill)]
    cells = {w["name"] for w in spec["workloads"]}
    found = {}
    for cell, fn in jobs:
        if cell not in cells or (only and only not in cell):
            continue
        text = fn(cell)
        with open(os.path.join(out, cell + ".txt"), "w") as f:
            f.write(text)
        found[cell] = {
            "as_it_is": sha(text),
            "no_kernel_locations": sha(without_kernel_locations(text)),
            "bytes": len(text),
            "flash_fwd": text.count('kernel_name = "flash_fwd"'),
            "flash_bwd": text.count('kernel_name = "flash_bwd"')}
        print(cell, json.dumps(found[cell]), flush=True)
    return found


def hashes_of(rev: str, only: str) -> dict:
    tree, out = os.path.join(WORK, "tree"), os.path.join(WORK, rev.replace("/", "_"))
    for d in (tree, out):
        shutil.rmtree(d, ignore_errors=True)
        os.makedirs(d)
    treeish = subprocess.check_output(
        ["git", "write-tree"], cwd=ROOT, text=True).strip() \
        if rev == "index" else rev
    archive = subprocess.run(["git", "archive", treeish], cwd=ROOT,
                             check=True, capture_output=True).stdout
    subprocess.run(["tar", "-x", "-C", tree], input=archive, check=True)
    print(f"== {rev}", flush=True)
    subprocess.run([sys.executable, os.path.abspath(__file__), "--tree", tree,
                    "--out", out, "--only", only], check=True)
    with open(os.path.join(out, "hashes.json")) as f:
        return json.load(f)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("revisions", nargs="*", help="git revisions, or 'index'")
    ap.add_argument("--only", default="", help="cells whose name holds this")
    ap.add_argument("--tree", help=argparse.SUPPRESS)
    ap.add_argument("--out", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.tree:
        found = lower_tree(args.tree, args.out, args.only)
        with open(os.path.join(args.out, "hashes.json"), "w") as f:
            json.dump(found, f, indent=1)
        return 0
    if not args.revisions:
        ap.error("name one revision, or two to compare")
    sides = [hashes_of(rev, args.only) for rev in args.revisions]
    if len(sides) < 2:
        return 0
    differ = [cell for cell in sides[0]
              if sides[0][cell]["no_kernel_locations"]
              != sides[-1].get(cell, {}).get("no_kernel_locations")]
    print("programs that differ:", ", ".join(differ) or "none")
    return 1 if differ else 0


if __name__ == "__main__":
    sys.exit(main())
