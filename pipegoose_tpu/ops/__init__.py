"""Hand-written Pallas TPU kernels (flash attention, fused
cross-entropy) with ``interpret=`` CPU fallbacks. The modules are
import-on-demand (``from pipegoose_tpu.ops import flash_attention as
fa``)."""
