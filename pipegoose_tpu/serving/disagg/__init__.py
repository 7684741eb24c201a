"""Disaggregated prefill/decode serving (ROADMAP item 2).

Prefill is compute-bound, decode is HBM-bandwidth-bound; serving one
model on one mesh sizes both pools wrong. This package splits them:

- **transfer.py** — the page-granular KV transfer primitive between
  two differently sharded pools, at WIRE precision (int8 pages ship
  q + scale planes, never fp; fp pools get an opt-in bf16 wire), with
  a bounded in-flight queue and a fault seam.
- **workers.py** — ``PrefillWorker`` (streams completed pages chunk by
  chunk off a ``prefill_only`` engine, hands off the first token) and
  ``DecodeWorker`` (stages against the transfer ledger, imports,
  admits via ``admit_with_pages``, owns the re-prefill fallback).
- **engine.py** — ``DisaggEngine``, the one-host-thread orchestrator
  over both pools' steppable-run APIs.

Greedy output is token-identical to a single-engine run (pinned across
fp/int8 KV and the tp 2 -> 1 reshard), and the request tracer's new
``transfer`` phase keeps queue + prefill + transfer + decode + stall
== e2e exact. See docs/serving.md "Disaggregated prefill/decode".
"""
from pipegoose_tpu.serving.disagg.engine import DisaggEngine
from pipegoose_tpu.serving.disagg.transfer import (
    PageHandoff,
    PoolTransfer,
    TransferError,
    TransferQueue,
    set_transfer_fault,
)
from pipegoose_tpu.serving.disagg.workers import DecodeWorker, PrefillWorker

__all__ = [
    "DecodeWorker",
    "DisaggEngine",
    "PageHandoff",
    "PoolTransfer",
    "PrefillWorker",
    "TransferError",
    "TransferQueue",
    "set_transfer_fault",
]
