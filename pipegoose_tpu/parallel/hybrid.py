"""Hybrid-parallel train-step composition.

The TPU-native replacement for the reference's wrapper-chaining pattern
(examples/hybrid_parallelism.py: TensorParallel(...).parallelize() ->
DataParallel(...).parallelize() -> DistributedOptimizer(...)): here the
same composition is ONE compiled SPMD program — a ``shard_map`` over the
mesh in which the loss/grad runs tensor-parallel, the batch is sharded
over the data axis, and the ZeRO-1 optimizer reduce-scatters grads and
all-gathers params. No hooks, no module mutation, no per-param
collectives.
"""
from __future__ import annotations

from typing import Any, Callable, Optional

import jax
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from pipegoose_tpu.distributed.compat import shard_map
from pipegoose_tpu.distributed.parallel_context import ParallelContext
from pipegoose_tpu.optim.zero import (
    DistributedOptimizer,
    ZeroState,
    ef_state_specs,
    shard_shapes,
    state_specs,
)

def spec_mentions(spec: P, axis: str) -> bool:
    """Whether a PartitionSpec shards any dim over ``axis`` — the one
    axis-membership helper (telemetry/health.py imports it from here;
    the reverse direction would cycle through the telemetry package
    __init__ back into trainer/hybrid)."""
    for entry in spec:
        if entry == axis:
            return True
        if isinstance(entry, (tuple, list)) and axis in entry:
            return True
    return False


_spec_mentions = spec_mentions  # module-internal alias


def sync_replicated_grads(grads: Any, param_specs: Any, axes: tuple) -> Any:
    """Reduce grads of params NOT sharded over an axis, for each entry in
    ``axes`` — either a plain axis name (psum) or ``(axis, op)`` with op
    in {"sum", "mean"}.

    - "sum" (pipe): a replicated param *used* on only some ranks of the
      axis (embedding on the first stage, ln_f/LM head on the last) —
      each rank holds a partial contribution, the true grad is the sum.
    - "mean" (expert): the axis carries *different tokens* (expert-data
      parallelism) — replicated params average like DP (the reference's
      EXPERT_DATA routing, data_parallel.py:35-43).
    """

    for entry in axes:
        _, op = entry if isinstance(entry, tuple) else (entry, "sum")
        if op not in ("sum", "mean"):
            raise ValueError(f"grad sync op must be 'sum' or 'mean', got {op!r}")

    def f(g, spec):
        for entry in axes:
            ax, op = entry if isinstance(entry, tuple) else (entry, "sum")
            if not _spec_mentions(spec, ax):
                g = lax.psum(g, ax) if op == "sum" else lax.pmean(g, ax)
        return g

    return jax.tree_util.tree_map(
        f, grads, param_specs, is_leaf=lambda x: isinstance(x, P)
    )


def trainable(tree: Any, frozen: Any) -> Any:
    """``tree`` less the leaves ``frozen`` marks True (a pytree of bools
    shaped like the params; None = nothing frozen). A frozen leaf becomes
    ``None``, which jax takes for an empty subtree: gradients, the
    optimizer and its state never see it. ``tree`` may hold
    PartitionSpecs at the leaves."""
    if frozen is None:
        return tree
    return jax.tree_util.tree_map(lambda f, x: None if f else x, frozen, tree)


def with_frozen(frozen: Any, params: Any, new_trainable: Any) -> Any:
    """The params tree again: updated leaves from ``new_trainable``, the
    frozen ones as ``params`` has them."""
    if frozen is None:
        return new_trainable
    return jax.tree_util.tree_map(
        lambda f, p, n: p if f else n, frozen, params, new_trainable
    )


def zero_state_spec(
    optimizer: DistributedOptimizer, params: Any, param_specs: Any, mesh
) -> ZeroState:
    """PartitionSpec tree for the ZeRO-1 optimizer state on ``mesh`` —
    used by the train step's in/out specs and by checkpoint restore
    (restoring without these would replicate the sharded state)."""
    dp = optimizer.axis_name and mesh.shape.get(optimizer.axis_name, 1) or 1
    shapes = jax.eval_shape(optimizer.inner.init, shard_shapes(params, dp))
    inner_spec = state_specs(shapes, params, param_specs, optimizer.axis_name or "data")
    ef_spec = None
    if getattr(optimizer, "error_feedback", False) and optimizer.axis_name:
        ef_spec = ef_state_specs(params, param_specs, optimizer.axis_name)
    return ZeroState(inner_spec, ef_spec)


def train_step_intended_specs(
    optimizer: DistributedOptimizer,
    params: Any,
    param_specs: Any,
    mesh,
    batch_spec: P = P("data"),
    with_rng: bool = False,
    frozen: Any = None,
) -> tuple:
    """The INTENDED PartitionSpec tuple for a hybrid train step's
    ``(params, opt_state, batch[, rng])`` arguments — what the mesh
    doctor (telemetry/doctor.py) diffs the compiled program against.
    One source of truth: the same ``param_specs`` the step was built
    with plus the derived ZeRO state specs, so a drifted spec shows up
    as a compile-time diff instead of a slow step."""
    specs = (
        param_specs,
        # the optimizer's state covers the trainable leaves alone
        zero_state_spec(optimizer, trainable(params, frozen),
                        trainable(param_specs, frozen), mesh),
        batch_spec,
    )
    return specs + ((P(),) if with_rng else ())


def parallel_context_sizes(candidate: Any) -> dict:
    """``ParallelContext`` kwargs implied by one planner candidate
    (duck-typed: anything with ``dp``/``tp``/``pp``/``ep`` attributes,
    normally a ``pipegoose_tpu.planner.Candidate``). The enumeration
    hook lives HERE so the layout-to-mesh mapping has one source of
    truth — the planner, the CLIs, and tests all build their contexts
    through it instead of hand-assembling axis sizes."""
    return dict(
        tensor_parallel_size=int(getattr(candidate, "tp", 1)),
        pipeline_parallel_size=int(getattr(candidate, "pp", 1)),
        data_parallel_size=int(getattr(candidate, "dp", 1)),
        expert_parallel_size=int(getattr(candidate, "ep", 1)),
    )


def hybrid_step_kwargs(candidate: Any) -> dict:
    """:func:`make_hybrid_train_step` kwargs implied by one planner
    candidate: the gradient wire precision, the overlap declaration,
    and — for a pipelined candidate — the ``("pipe",)`` grad sync the
    stage-partial gradients need (test_3d_parallel's composition)."""
    kw: dict = dict(
        grad_comm=getattr(candidate, "grad_comm", None),
        overlap_tp=bool(getattr(candidate, "overlap_tp", False)),
    )
    if int(getattr(candidate, "pp", 1)) > 1:
        kw["grad_sync_axes"] = ("pipe",)
    return kw


def hybrid_build_config(
    loss_fn: Callable[..., jax.Array],
    param_specs: Any,
    optimizer: DistributedOptimizer,
    batch_spec: P = P("data"),
    loss_axis: Any = "data",
    grad_sync_axes: tuple = (),
    with_rng: bool = False,
    n_accum: int = 1,
    with_health: bool = False,
    grad_comm: Optional[str] = None,
    overlap_tp: bool = False,
    has_aux: bool = False,
    frozen: Any = None,
) -> dict:
    """Capture everything :func:`make_hybrid_train_step` needs EXCEPT
    the ``ParallelContext`` — the step-rebuild hook. The trainer stores
    this dict at construction; after an elastic mesh change
    (``trainer/elastic.py``: device loss shrank the cluster), the SAME
    config re-lowered through :func:`build_hybrid_train_step` on the
    new context yields the recompiled step — one source of truth, no
    drift between the original build and the rebuild."""
    return dict(
        loss_fn=loss_fn,
        param_specs=param_specs,
        optimizer=optimizer,
        batch_spec=batch_spec,
        loss_axis=loss_axis,
        grad_sync_axes=grad_sync_axes,
        with_rng=with_rng,
        n_accum=n_accum,
        with_health=with_health,
        grad_comm=grad_comm,
        overlap_tp=overlap_tp,
        has_aux=has_aux,
        frozen=frozen,
    )


def build_hybrid_train_step(config: dict, parallel_context: ParallelContext):
    """(init_fn, make_step) for a stored :func:`hybrid_build_config` on
    ``parallel_context`` — the other half of the rebuild hook."""
    cfg = dict(config)
    return make_hybrid_train_step(
        cfg.pop("loss_fn"), cfg.pop("param_specs"), cfg.pop("optimizer"),
        parallel_context, **cfg,
    )


def _set_comm_gauges(params, mesh, optimizer, comm_mode: str,
                     overlap_tp: bool, dp_axis: str) -> None:
    """Export the communication-engine config/savings next to the MFU
    gauges: ``comm.overlap_enabled`` (0/1) and, for a compressed
    gradient reduction, the analytic per-step ``comm.bytes_saved``
    (distributed/compressed.py). One registry branch when telemetry is
    disabled — the library-instrumentation contract."""
    from pipegoose_tpu.telemetry.registry import get_registry

    reg = get_registry()
    if not reg.enabled:
        return
    reg.gauge(
        "comm.overlap_enabled",
        help="1 when the TP ring collective-matmul overlap path is on",
    ).set(1.0 if overlap_tp else 0.0)
    ax = getattr(optimizer, "axis_name", None) or dp_axis
    n = mesh.shape.get(ax, 1)
    # always write all three (last-build-wins): an fp32 build after a
    # quantized one must not leave stale savings on the exporters
    saved = 0.0
    if comm_mode != "fp32" and n > 1:
        from pipegoose_tpu.distributed.compressed import grad_comm_bytes_saved

        saved = float(grad_comm_bytes_saved(params, n, comm_mode))
    reg.gauge(
        "comm.bytes_saved",
        help="analytic per-step gradient-reduction wire bytes saved "
             "vs fp32 by grad_comm compression",
    ).set(saved)
    reg.gauge("comm.grad_wire_bits").set(
        {"fp32": 32.0, "bf16": 16.0, "int8": 8.0}[comm_mode]
    )


def make_hybrid_train_step(
    loss_fn: Callable[[Any, Any], jax.Array],
    param_specs: Any,
    optimizer: DistributedOptimizer,
    parallel_context: Optional[ParallelContext] = None,
    batch_spec: P = P("data"),
    loss_axis: str = "data",
    grad_sync_axes: tuple = (),
    with_rng: bool = False,
    n_accum: int = 1,
    with_health: bool = False,
    grad_comm: Optional[str] = None,
    overlap_tp: bool = False,
    has_aux: bool = False,
    frozen: Any = None,
):
    """Build (init_fn, step_fn), both jitted over the context's mesh.

    - ``loss_fn(params, batch) -> scalar`` runs on per-device shards
      inside shard_map (use tp_axis='tensor' collectives inside it).
    - ``param_specs``: PartitionSpec pytree for params (e.g.
      ``bloom.tp_specs``).
    - ``optimizer``: ZeRO-1 ``DistributedOptimizer``; its state lives
      sharded over the data axis for the whole run.

    step_fn(params, opt_state, batch) -> (params, opt_state, loss);
    params and opt_state buffers are donated.

    ``with_rng=True``: ``loss_fn(params, batch, rng)`` and
    ``step_fn(params, opt_state, batch, rng)`` — pass a FRESH key every
    step (e.g. ``jax.random.fold_in(base, step)``); fold the data/expert
    axis indices inside ``loss_fn`` for per-rank diversity (the
    reference seeded every rank identically, parallel_context.py:253-261,
    which SURVEY.md §7 flags as wrong for router noise).

    ``n_accum > 1``: gradient accumulation — the per-device batch shard
    is split into ``n_accum`` microbatches scanned with rematerialization
    (core/accumulation.py), so peak activation memory is one
    microbatch's while the optimizer sees the full-batch gradient.

    ``with_health=True``: step_fn additionally returns a small
    replicated pytree of in-graph health scalars (global and
    per-top-level-module grad norms, applied-update max-abs/norm,
    nonfinite-leaf counts, update/param norm ratio —
    telemetry/health.py), fused into the SAME compiled program. The
    flag is resolved at build time, so the off path lowers to a
    byte-identical program (zero recompiles, zero per-step cost —
    pinned by tests/telemetry/test_health.py); on, it costs one grad
    all-reduce tree plus two scalar-vector collectives.

    ``grad_comm``: wire precision of the DP/ZeRO gradient reduction —
    "fp32" | "bf16" | "int8" (distributed/compressed.py). None (the
    default) inherits the optimizer's own setting. With a ZeRO
    ``axis_name`` the compressed reduce-scatter replaces the fp32
    ``psum_scatter`` inside the optimizer; with ``axis_name=None``
    (plain unsharded optimizer, i.e. plain DP) a compressed mean
    all-reduce runs on the grads before the optimizer step, over every
    loss axis, for params not sharded over that axis (the compressed
    analog of ``grad_sync_axes=((ax, "mean"), ...)`` — combining both
    for the same axis raises). Docs: docs/comm.md.

    ``has_aux=True``: ``loss_fn`` returns ``(loss, aux)`` and step_fn
    returns ``aux`` last: a small pytree of counters (rows per expert,
    loss terms apart, ...), averaged over the loss axes like the loss
    and replicated. Resolved at build time like ``with_health``: off,
    the step lowers to the byte-identical program
    (tests/trainer/test_step_aux.py).

    ``frozen``: a pytree of bools shaped like the params; a True leaf
    takes no gradient and no optimizer state and comes back from the
    step as it went in (a router's selection bias, whose update rule is
    no gradient's). None: every leaf trains.

    ``overlap_tp``: declare that ``loss_fn`` runs the ring
    collective-matmul path (``config.overlap_tp`` on the model) — the
    flag only drives telemetry (``comm.overlap_enabled``) and the
    doctor's expectations; the overlap path's gradients are exact by
    construction, so no grad-sync change is needed.
    """
    ctx = parallel_context or ParallelContext.get_context()
    if ctx is None:
        raise ValueError("no ParallelContext; construct one first")
    mesh = ctx.mesh

    from pipegoose_tpu.distributed.compressed import check_grad_comm

    if grad_comm is not None and grad_comm != getattr(
        optimizer, "grad_comm", "fp32"
    ):
        optimizer = optimizer.replace(grad_comm=check_grad_comm(grad_comm))
    comm_mode = check_grad_comm(getattr(optimizer, "grad_comm", "fp32"))
    # plain-DP path: no ZeRO axis to fold the compression into — the
    # compressed mean all-reduce runs on the whole grad tree instead
    plain_dp_comm = comm_mode != "fp32" and optimizer.axis_name is None

    if n_accum > 1:
        if has_aux:
            raise ValueError("has_aux with n_accum > 1: the accumulating "
                             "loss carries one scalar")
        from pipegoose_tpu.core.accumulation import make_accumulating_loss

        loss_fn = make_accumulating_loss(loss_fn, n_accum)

    # what the gradient and the optimizer see (all of it unless frozen)
    train_specs = trainable(param_specs, frozen)

    def _state_spec_for(params):
        return zero_state_spec(
            optimizer, trainable(params, frozen), train_specs, mesh
        )

    def init_fn(params):
        spec = _state_spec_for(params)
        f = shard_map(
            optimizer.init,
            mesh=mesh,
            in_specs=(train_specs,),
            out_specs=spec,
            check_vma=False,
        )
        return jax.jit(f)(trainable(params, frozen))

    loss_axes = loss_axis if isinstance(loss_axis, tuple) else (loss_axis,)
    if plain_dp_comm:
        # the compressed path below already mean-syncs over every loss
        # axis (for params not sharded over it) — a ("data", "mean")
        # grad_sync entry on top would average twice
        for entry in grad_sync_axes:
            ax, op = entry if isinstance(entry, tuple) else (entry, "sum")
            if ax in loss_axes and op == "mean":
                raise ValueError(
                    f"grad_comm={comm_mode!r} with an unsharded optimizer "
                    f"already mean-syncs grads over {loss_axes}; drop "
                    f"({ax!r}, 'mean') from grad_sync_axes"
                )
    if with_health:
        from pipegoose_tpu.telemetry.health import health_stats

        # grads of params replicated over an already-synced axis
        # (grad_sync_axes ran first) are exact; the remaining loss axes
        # still hold per-rank partials and need the health pmean
        synced = {e[0] if isinstance(e, tuple) else e for e in grad_sync_axes}
        health_mean_axes = tuple(a for a in loss_axes if a not in synced)

    def _loss_and_grads(params, batch, *rng):
        """(loss, aux or None, grads over the trainable leaves)."""
        if frozen is None:
            out, grads = jax.value_and_grad(loss_fn, has_aux=has_aux)(
                params, batch, *rng
            )
        else:
            out, grads = jax.value_and_grad(
                lambda t: loss_fn(with_frozen(frozen, params, t), batch, *rng),
                has_aux=has_aux,
            )(trainable(params, frozen))
        return (*out, grads) if has_aux else (out, None, grads)

    def _step(all_params, opt_state, batch, *rng):
        loss, aux, grads = _loss_and_grads(all_params, batch, *rng)
        params = trainable(all_params, frozen)
        if grad_sync_axes:
            grads = sync_replicated_grads(grads, train_specs, grad_sync_axes)
        if plain_dp_comm:
            from pipegoose_tpu.distributed.compressed import (
                compressed_all_reduce_mean,
            )

            # the compressed analog of sync_replicated_grads with
            # (axis, "mean") for every loss axis: params SHARDED over
            # an axis hold genuinely different grads there (e.g.
            # expert weights on an expert axis) and must not be mixed
            def comp_sync(g, spec):
                for ax in loss_axes:
                    if not _spec_mentions(spec, ax):
                        g = compressed_all_reduce_mean(g, ax, comm_mode)[0]
                return g

            grads = jax.tree_util.tree_map(
                comp_sync, grads, train_specs,
                is_leaf=lambda x: isinstance(x, P),
            )
        new_params, new_state = optimizer.step(grads, opt_state, params)
        for ax in loss_axes:
            loss = lax.pmean(loss, ax)
        out = (with_frozen(frozen, all_params, new_params), new_state, loss)
        if with_health:
            out += (health_stats(
                grads, params, new_params, train_specs,
                axes=tuple(mesh.axis_names), mean_axes=health_mean_axes,
            ),)
        if has_aux:
            for ax in loss_axes:
                aux = lax.pmean(aux, ax)
            out += (aux,)
        return out

    def make_step(params):
        _set_comm_gauges(params, mesh, optimizer, comm_mode, overlap_tp,
                         loss_axes[0])
        spec = _state_spec_for(params)
        in_specs = (param_specs, spec, batch_spec) + ((P(),) if with_rng else ())
        # the health tree is all replicated scalars, the counters are
        # replicated too: one P() prefix spec each
        out_specs = (param_specs, spec, P()) + (P(),) * (with_health + has_aux)
        f = shard_map(
            _step,
            mesh=mesh,
            in_specs=in_specs,
            out_specs=out_specs,
            check_vma=False,
        )
        return jax.jit(f, donate_argnums=(0, 1))

    return init_fn, make_step
