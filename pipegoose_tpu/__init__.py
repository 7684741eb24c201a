"""pipegoose_tpu: a TPU-native 3D/4D-parallel training framework.

Built from scratch for JAX/XLA/Pallas with the capabilities of
xrsrke/pipegoose (reference surveyed in SURVEY.md): tensor, data,
pipeline, expert, and sequence parallelism plus a ZeRO-1 distributed
optimizer — expressed as one compiled SPMD program over a
``jax.sharding.Mesh`` instead of process groups, RPC, and threads.
"""
from pipegoose_tpu.distributed import ParallelContext, ParallelMode

__version__ = "0.1.0"
__all__ = ["ParallelContext", "ParallelMode"]
