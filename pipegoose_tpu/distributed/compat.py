"""The codebase's ``shard_map`` call shape over ``jax.shard_map``."""
from __future__ import annotations

import jax


def shard_map(f, mesh, in_specs, out_specs, check_vma=False):
    """``jax.shard_map`` with positional mesh/specs and the replication
    check off by default: every sharded entry point here passes
    pytree-of-arrays params, which defeat the inference."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)
