"""Continuous-batching inference serving over a paged KV-cache pool.

The layer above the model stack that the per-call ``generate()`` /
``generate_tp()`` paths cannot provide: request multiplexing, plus the
opt-in serving-perf modes — content-addressed copy-on-write prefix
caching, chunked prefill, self-speculative decoding, and quantized
inference (``weight_dtype``/``kv_dtype``: int8/int4 weights through the
dequant-fused matmul, int8 KV pages with per-page scale planes). See
docs/serving.md for the request lifecycle, page-table layout, the
prefix-cache / COW / eviction semantics, and the quantization accuracy
contract.
"""
from pipegoose_tpu.serving.disagg import DisaggEngine
from pipegoose_tpu.serving.engine import (
    ReplicaFault,
    RequestOutput,
    ServingEngine,
    make_skewed_replay,
)
from pipegoose_tpu.serving.kv_pool import (
    NULL_PAGE,
    PagePool,
    copy_page,
    dequantize_kv,
    gather_pages,
    init_pages,
    paged_decode_step,
    paged_prefill_chunk,
    quantize_kv,
    write_prompt_pages,
)
from pipegoose_tpu.serving.prefix_cache import PrefixCache, PrefixHit
from pipegoose_tpu.serving.scheduler import Request, Scheduler, Status

__all__ = [
    "DisaggEngine",
    "NULL_PAGE",
    "PagePool",
    "PrefixCache",
    "PrefixHit",
    "ReplicaFault",
    "Request",
    "RequestOutput",
    "Scheduler",
    "ServingEngine",
    "Status",
    "copy_page",
    "dequantize_kv",
    "gather_pages",
    "init_pages",
    "make_skewed_replay",
    "quantize_kv",
    "paged_decode_step",
    "paged_prefill_chunk",
    "write_prompt_pages",
]
