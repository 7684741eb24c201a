"""Synchronous continuous-batching serving engine over the paged pool.

``ServingEngine.run(requests)`` drives the host-side loop the ROADMAP's
"heavy traffic" north star needs above the per-call ``generate()``:

    while work remains:
        admit queued requests into free slots        (scheduler.admit;
                                                      prefix-cache hits
                                                      share KV pages)
        advance prefills                             (one CHUNK per
                                                      prefilling request
                                                      per tick, or the
                                                      legacy monolithic
                                                      prefill)
        one jitted decode step over ALL active slots (paged_decode_step,
                                                      or a draft+verify
                                                      speculative cycle)
        record tokens; evict finished, reclaim pages (scheduler)

A decode tick hands the device only what it does not hold. The step
takes tokens, lengths and page table(s) as ONE packed int32 buffer and
returns the next step's beside its picks (a live row's pick and its
length plus one, the tables as they were), which stays on the device.
The host fetches the picks as ever, prepares the same buffer from its
requests and compares it with its copy of the device's: equal, and the
tick transfers nothing; different (a row admitted, ended, retracted or
grown by a page, or rows moved outside the step by a speculative
cycle, a chunk or a transfer), and the buffer goes over whole, in one
transfer (``finish_run()["step_uploads"]`` counts those steps).

Three opt-in performance modes layer onto the PR 1 engine without
changing its defaults:

- ``prefix_cache=True`` — content-addressed COW page sharing
  (serving/prefix_cache.py): a new request whose prompt prefix is
  already cached SKIPS prefill for the shared pages entirely; only its
  unique tail is forwarded, with copy-on-write duplication when the
  tail begins mid-page of a shared page.
- ``prefill_chunk=N`` — chunked prefill: long prompts advance N tokens
  per engine tick THROUGH the page tables (``paged_prefill_chunk``),
  interleaved with decode steps, instead of one monolithic prefill that
  stalls every decoding neighbor. The per-tick mixed step keeps the
  PR 3 ``decode_stall`` watchdog quiet and bounds the inter-decode-step
  gap (``serving.decode_gap_seconds``) by one chunk's compute.
- ``speculative=(k, n)`` — SELF-speculative decoding: a shallow-exit
  draft (the first ``k`` transformer layers + final LN + lm head, same
  weights) proposes up to ``n`` tokens per slot, and ONE batched
  verification pass through the full model (the same
  ``paged_prefill_chunk`` program, all-logits mode) scores the whole
  bundle. Accepted tokens are exactly the full model's greedy tokens —
  greedy parity is structural, not approximate.

The engine serves a model by ONE description of its blocks and of its
cache (serving/blocks.py: ``describe(config)``): BLOOM's, or the one a
config object gives of itself (``paged_model``). A model whose layers
keep two cache kinds (global layers every page, window layers a ring:
models/laguna.py) gets a bank, a page table and an allocator a kind,
under the same scheduler and tick. A model whose layers keep a STATE
beside their keys and values (models/falcon_h1.py: a recurrence's and a
short convolution's, overwritten every token, not appended to) gets a
state bank, a row a slot a layer (kv_pool.init_state): at admission the
prefill's result is put in the slot's row by the program that writes the
prompt's pages, so a slot's leftover state is never read; every decode
step reads and overwrites the rows of the live slots in place; a
preempted request re-prefills prompt plus generated tokens, so no state
is ever saved. A model whose window layers' ONE attention also reads a
summary a chunk of what left the window (``blocks.Summaries``:
models/evabyte.py) is a two-kind model whose ``global`` rows stand for a
chunk of positions each: its global pages are counted a row a chunk
(``PagePool.stride``), both tables reach every such layer, and the
decode step writes a summary in the step that completes its chunk. The
opt-in modes below are built for a cache that is global pages of a row a
position and nothing more, and refuse each such model by name.

Everything device-side is compiled with STATIC shapes: the decode step
is one program for the (num_slots, page-table-width) layout regardless
of which slots are live, prefills bucket prompt lengths to page
multiples (chunked prefill compiles exactly ONE chunk shape), and the
draft/verify pair adds two more. Page buffers are DONATED through every
step — the pool lives in place, never copied.

Greedy decoding only (the continuous-batching contract here is
token-identity with per-request ``generate()`` — the prefix cache,
chunking, and speculation are all invisible in the tokens); under a
mesh the whole step runs in shard_map with head-sharded pages and
``global_greedy_pick`` over the vocab shards, exactly like
models/_decode.py's sharded driver.

Metrics follow utils/profiler.py's convention of returning plain dicts
the caller can JSON-dump. Three clocks are always on, each a handful of
``now()`` calls: ``tick_phase_s`` splits the host wall inside
``tick_once`` into admit / prefill / prepare / dispatch / fetch /
record (``TICK_PHASES``), ``tick_timeline`` keeps the same boundaries
tick by tick with the realtime clock at each tick's entry
(``TIMELINE_COLUMNS``; ``dispatch`` split into ``upload``, 0.0 where
the tick hands nothing over, and ``call``; ``last_tick()`` reads the
newest row of a live run), so that the ticks
can be laid beside a profiler session's device line, and ``setup`` keeps
the wall of ``__init__`` and of the first call of every jitted program,
which is the call that compiled it or loaded it from the cache. The same
regions are spans (telemetry/spans.py): ``serving.admit``,
``serving.prefill``, ``serving.prepare``, ``serving.decode_step`` with
its children ``.upload``, ``.dispatch`` (the call alone) and ``.fetch``,
``serving.record``. A span is always a
``jax.profiler.TraceAnnotation``, so a profiler session shows what the
host was doing in every gap of the device's line, and is recorded as
``span.<path>.seconds`` only when the registry is enabled. There is no
span around the whole tick: it would prefix the paths of the others.

The engine is also instrumented against the telemetry registry: on top
of the PR 2 gauges/histograms it counts prefix-cache ``hit_tokens``/
``miss_tokens``/``shared_pages``/``cow_copies``, prefill chunks and
forwarded prefill tokens (the prefill-FLOP meter the cache shrinks),
pool fragmentation, decode-step gaps, and speculative draft/accept
tallies. The legacy aggregate dict keeps its exact keys —
``benchmark/drivers/serve.py``, ``chip_smoke.py``, ``DisaggEngine`` and
the examples parse it; new information lands under NEW keys only.
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from pipegoose_tpu.distributed.compat import shard_map
from pipegoose_tpu.models._decode import (
    global_greedy_pick,
    greedy_token,
    vocab_mask_for,
)
from pipegoose_tpu.serving.blocks import (
    GLOBAL,
    SLIDING,
    WINDOW,
    describe,
    ring_pages,
    summaries_seen,
)
from pipegoose_tpu.serving.kv_pool import (
    SUMMARY_COUNTERS,
    PagePool,
    check_kv_dtype,
    copy_page,
    init_pages,
    init_state,
    paged_decode_step,
    paged_prefill_chunk,
    ring_reach,
    state_walk_plan,
    walk_plan,
    walked_chunks,
    walked_rows,
    walked_state_rows,
    write_prompt_pages,
    write_state,
)
from pipegoose_tpu.serving.kv_tier.restore import (
    RestoreManager,
    RestorePlanner,
)
from pipegoose_tpu.serving.prefix_cache import PrefixCache
from pipegoose_tpu.serving.scheduler import Request, Scheduler, Status
from pipegoose_tpu.telemetry.registry import get_registry
from pipegoose_tpu.telemetry.spans import span


class ReplicaFault(RuntimeError):
    """An unplanned replica failure (the deterministic fault seam's
    crash kind, or a real exception escaping ``tick_once``). The
    control plane's contract on catching one: quarantine the replica
    (FAILED), best-effort ``abort_run``, and SALVAGE its admitted
    requests onto the survivors (serving/control_plane/plane.py)."""


@dataclass
class RequestOutput:
    uid: int
    prompt: np.ndarray
    generated: np.ndarray
    finish_reason: str
    queue_latency_s: float
    # None when the request was never served (finish_reason="shed"):
    # a 0.0 would read as an instant first token and drag aggregate
    # TTFT DOWN exactly when the system is degraded — filter shed rows
    # (or skip Nones) before aggregating
    ttft_s: Optional[float]
    decode_tokens_per_s: Optional[float]
    e2e_latency_s: float = 0.0  # submit -> done wall time
    tenant: Optional[str] = None  # multi-tenant identity (None = untagged)

    @property
    def tokens(self) -> np.ndarray:
        return np.concatenate([np.asarray(self.prompt, np.int64),
                               np.asarray(self.generated, np.int64)])


# host-wall phases of one ``tick_once``, in the order they run; their
# accumulators (``finish_run()["tick_phase_s"]``) sum to the wall inside
# ``tick_once``. ``admit`` holds the tick hook, ``prefill`` every prefill
# or chunk up to its token fetch (0.0 exactly when none ran), ``record``
# the tail of a tick that decoded nothing; ``dispatch`` + ``fetch`` is
# ``decode_step_time_s``.
TICK_PHASES = ("admit", "prefill", "prepare", "dispatch", "fetch", "record")

# the same clock's boundaries, one row a tick, the newest
# ``TIMELINE_CAPACITY`` ticks of a run (``finish_run()["tick_timeline"]``,
# ``last_tick()``). ``t_wall_ns`` is ``time.time_ns()`` and ``t_start`` the
# run's ``now()``, both read at the tick's entry; the seven durations
# follow in the order they ran, each from the boundary before it, so a
# phase's ends on either clock are the entry plus the durations before
# it. ``upload`` + ``call`` is ``dispatch``: the step's packed inputs made
# ready for the device (the copy alone; 0.0 exactly where the device holds
# them already), then the call of the jitted step, which transfers them.
# ``rows``: requests in the decode step (0 where none ran); ``prefills``:
# requests whose prefill, or a chunk of it, ran in this tick (0 exactly where
# ``prefill`` is 0.0). Each column sums to its ``tick_phase_s`` entry.
TIMELINE_COLUMNS = ("t_wall_ns", "t_start", "admit", "prefill", "prepare",
                    "upload", "call", "fetch", "record", "rows", "prefills")
TIMELINE_CAPACITY = 32_768


def _unpack(carry, num_slots: int, widths):
    """(tokens, lengths, page table(s)) of a decode step out of its one
    packed buffer, ``[tokens | lengths | table (| window table)]``, a
    slot an entry or a row: views of a numpy buffer, static slices of a
    traced one. ``widths``: the table's width, or ``{kind: width}`` for
    a two-kind model (``ServingEngine._by_kind``)."""
    n = num_slots
    at = 2 * n

    def take(width):
        nonlocal at
        at += n * width
        return carry[at - n * width:at].reshape(n, width)

    tables = ({kind: take(width) for kind, width in widths.items()}
              if isinstance(widths, dict) else take(widths))
    return carry[:n], carry[n:2 * n], tables


class _RunState:
    """Accumulators for one serving run — the state ``run()`` kept in
    locals before the steppable extraction (``start_run`` /
    ``tick_once`` / ``finish_run``), so a control plane can interleave
    N replica engines tick-by-tick in one host thread. Host-side but for
    ``carry``, the decode step's small inputs as the device holds them."""

    __slots__ = (
        "now", "tick_hook", "t0", "tok0", "done", "outputs",
        "per_request", "generated_total", "shed_count", "steps",
        "prefills", "chunks", "spec_drafted", "spec_accepted",
        "occ_slots", "occ_pages", "stalled", "tick", "t_last_decode",
        "max_gap", "step_time", "phase_s", "timeline", "table", "seq_lens",
        "tokens", "packed", "held", "held_tokens", "held_lens", "carry",
        "step_uploads", "uploaded",
        "keys_walked", "keys_reached", "window_table", "window_keys_walked",
        "window_keys_reached", "ring_rows", "ring_rows_wrapped",
        "ring_keys_needed", "ring_keys_gathered", "occ_window", "peak_pages",
        "recycled0",
        "experts_touched", "expert_skew", "rows_routed", "zero_pick_share",
        "state_rows_updated", "state_rows_live", "state_writes",
        "state_peak_slots", "summary_rows",
    )

    def __init__(self, engine: "ServingEngine", now, tick_hook):
        self.now = now
        self.tick_hook = tick_hook
        self.t0 = 0.0                   # set at the end of start_run
        self.tok0 = engine._m_tokens.value
        self.done: List[Request] = []   # finished, outputs not built yet
        self.outputs: List[RequestOutput] = []
        self.per_request: List[dict] = []
        self.generated_total = 0
        self.shed_count = 0
        self.steps = self.prefills = self.chunks = 0
        self.spec_drafted = self.spec_accepted = 0
        self.occ_slots = self.occ_pages = 0.0
        self.stalled = 0
        self.tick = 0
        self.t_last_decode: Optional[float] = None
        self.max_gap = 0.0
        self.step_time = 0.0            # summed decode-step wall time
        # key columns the plain decode steps walked / could have reached
        self.keys_walked = self.keys_reached = 0
        # the same for a window layer's ring, where the pool has one
        self.window_keys_walked = self.window_keys_reached = 0
        # a SLIDING ring's rows, step by step: live rows and those at or
        # past the window (their ring has wrapped); key columns the live
        # rows' windows hold, and those the walk gathered for them (every
        # row walks as far as the furthest)
        self.ring_rows = self.ring_rows_wrapped = 0
        self.ring_keys_needed = self.ring_keys_gathered = 0
        self.occ_window = 0.0
        self.peak_pages = dict.fromkeys(engine.pool.kinds, 0)
        self.recycled0 = engine.pool.recycled
        # the decode steps' counters (a model whose blocks bring any):
        # experts a step touched over its sparse layers, step by step;
        # busiest expert's rows over the mean, summed; rows routed here
        self.experts_touched: List[int] = []
        self.expert_skew = 0.0
        self.rows_routed = 0
        # picks on zero-compute experts over all picks, summed step by
        # step (None: the model routes to none)
        self.zero_pick_share: Optional[float] = None
        # the state bank (a model that has one): rows the decode steps'
        # walks read and wrote, rows alive in them, prefill results put
        # in a slot, the most slots holding a request in a step
        self.state_rows_updated = self.state_rows_live = 0
        self.state_writes = self.state_peak_slots = 0
        # a ring and its summaries under one softmax (a model that has
        # them): the decode steps' counters, summed, a layer a step
        # (``kv_pool._summary_counters``'s names); None: none came
        self.summary_rows: Optional[dict] = None
        self.phase_s = dict.fromkeys(TICK_PHASES, 0.0)
        self.timeline: deque = deque(maxlen=TIMELINE_CAPACITY)
        # what a decode step takes from the host, in ONE buffer: tokens,
        # lengths and page table(s) are views of ``packed``, filled by
        # ``prepare`` every tick
        self.packed = np.zeros((engine._carry_size,), np.int32)
        self.tokens, self.seq_lens, table = engine._unpack_carry(self.packed)
        self.table, self.window_table = (
            (table, None) if engine.pool.window is None
            else (table[GLOBAL], table[WINDOW]))
        # the same as the DEVICE holds it (``carry``: the last step's own
        # next inputs, or the buffer last sent; None before the run's
        # first step) and the host's copy of that, advanced by the step's
        # rule: a tick whose ``packed`` equals ``held`` sends nothing
        self.carry = None
        self.held = np.zeros_like(self.packed)
        self.held_tokens, self.held_lens, _ = engine._unpack_carry(self.held)
        # decode steps that took a transfer; this tick's own (0 or 1)
        self.step_uploads = self.uploaded = 0

    def close_tick(self, t_wall_ns, t_start, admit, prefill, prepare, upload,
                   call, fetch, record, rows, prefills) -> None:
        """Book one finished tick: its row (``TIMELINE_COLUMNS``) into
        the ring and its durations into the phase sums, from the same
        values, so every column sums to its phase."""
        phase = self.phase_s
        phase["admit"] += admit
        phase["prefill"] += prefill
        phase["prepare"] += prepare
        phase["dispatch"] += upload + call
        phase["fetch"] += fetch
        phase["record"] += record
        self.timeline.append((t_wall_ns, t_start, admit, prefill, prepare,
                              upload, call, fetch, record, rows, prefills))

    def advance_held(self, nxt) -> None:
        """The step's own rule for its next inputs, on the host's copy:
        a live row's token is the one it just picked and its length one
        more; a dead row keeps its zeros; the tables stay."""
        live = self.held_lens > 0
        self.held_tokens[:] = np.where(live, nxt, 0)
        self.held_lens += live


class ServingEngine:
    """Greedy continuous-batching inference over a paged KV pool.

    ``num_slots`` bounds the decode batch, ``num_pages * page_size`` the
    pooled KV capacity, ``max_context`` the per-request prompt+new
    budget (it fixes the page-table width, i.e. the attention span the
    step compiles for). Pass ``mesh``/``param_specs`` for tensor
    parallelism (vocab/head-sharded params, same contract as
    ``generate_tp``). ``prefix_cache``/``prefill_chunk``/``speculative``
    are the opt-in serving-perf modes (module docstring), all default
    OFF. Every paged program (decode step, draft, verify, chunk) reads
    the pool one way, the walk (``kv_pool._attend_rows``): the page
    table in chunks of whole pages, only as far as the longest live
    sequence of the call, each chunk's rows as stored contracted on the
    matrix unit against a block-diagonal query.
    ``finish_run()["decode_key_share"]`` (gauge
    ``serving.decode_key_share``) is the share of the table's key
    columns the plain decode steps walked; over a sliding ring
    ``finish_run()["window"]`` gives the live rows whose ring has
    wrapped (``wrapped_row_share``) and the key columns the live rows'
    windows hold over those their steps' walks gathered
    (``rows_useful_share``), counted by the same arithmetic."""

    def __init__(self, params, config, *, num_slots: int = 4,
                 num_pages: int = 64, page_size: int = 16,
                 max_context: int = 256, mesh=None, param_specs=None,
                 tp_axis: str = "tensor",
                 registry=None, recorder=None, stall_patience: int = 100,
                 prefix_cache: bool = False,
                 prefill_chunk: Optional[int] = None,
                 speculative: Optional[Tuple[int, int]] = None,
                 tracer=None,
                 weight_dtype: Optional[str] = None,
                 kv_dtype: Optional[str] = None,
                 weight_group_size: int = 32,
                 prefill_only: bool = False,
                 sentinel=None,
                 host_tier=None,
                 host_tier_wire: Optional[str] = None,
                 cost_model=None,
                 memledger=None):
        """``recorder``: optional ``telemetry.FlightRecorder`` — every
        decode step lands in its ring, and the no-decode-progress
        watchdog dumps a black box through it before raising.
        ``stall_patience``: scheduler iterations that admit nothing,
        prefill nothing, and decode nothing before the watchdog declares
        a stall. ``speculative=(k, n)``: draft with the first ``k``
        layers, propose up to ``n`` tokens per verification.
        ``tracer``: optional ``telemetry.reqtrace.RequestTracer`` —
        records every request's lifecycle timeline (admit, prefill
        chunks + cache hits, first token, decode ticks, spec cycles,
        preemptions) and attributes its TTFT/e2e latency; default None
        keeps the tick path at one attribute read + branch per hook
        site (guard-tested < 5 µs).

        ``weight_dtype`` ("int8" | "int4", default None; "fp" is an
        accepted alias for None, matching kv_dtype): quantize
        the block kernels at construction (quant/quantize_params) — the
        TP layers dispatch to the dequant-fused matmul, halving (or
        quartering) resident weight HBM. ``kv_dtype`` ("int8", default
        None=fp): int8 KV pages with a per-page scale plane —
        quantize-on-write, dequantize-in-gather (serving/kv_pool.py).
        ``weight_group_size``: int4 contraction-group width. Both
        default OFF: a default-constructed engine builds the exact
        PR 1/6 programs, byte for byte.

        ``prefill_only=True`` turns the engine into a disaggregated
        PREFILL POOL (serving/disagg/): the admission ledger reserves
        only ``pages_for(prompt)`` (nothing here ever decodes), and a
        completed prefill HANDS OFF — first token + exported KV pages —
        through the handoff hook (:meth:`set_handoff_hook`) instead of
        entering decode. Requires ``prefill_chunk`` (the chunk is the
        streaming boundary) and a hook before the first run.

        ``host_tier``: optional ``serving.kv_tier.HostTier`` — evicted
        refcount-1 prefix chains spill into host DRAM at wire precision
        and later lookup misses restore the pages instead of
        recomputing them (requires ``prefix_cache=True``).
        ``host_tier_wire`` ("bf16"): narrow FP pools on the wire;
        forbidden for int8 pools (their q+scale planes ARE the wire
        format). ``cost_model``: optional calibrated
        ``planner.cost.CostModel`` — its fitted launch/bandwidth/
        overhead constants decide restore-vs-recompute per prefix
        length; default None always restores.

        ``memledger``: optional ``telemetry.memledger.MemoryLedger``
        (or ``True`` to construct one) — live byte-exact per-owner-
        class page accounting with leak audits and an exhaustion
        forecast. Default None keeps every pool event and tick at one
        attribute read + branch (guard-tested < 5 µs)."""
        t_build = time.perf_counter()
        if max_context % page_size:
            raise ValueError("max_context must be a multiple of page_size")
        if prefill_only and prefill_chunk is None:
            raise ValueError(
                "prefill_only requires prefill_chunk: the chunk is the "
                "disagg streaming boundary (and the monolithic prefill "
                "path cannot hand off)"
            )
        if stall_patience < 1:
            raise ValueError(f"stall_patience must be >= 1, got {stall_patience}")
        model = describe(config)
        # a cache that is more than global pages
        more = ([f"{len(model.kinds)} cache kinds {model.kinds}"]
                if len(model.kinds) > 1 else []) + (
            ["a state a slot beside its pages"] if model.state else []) + (
            ["a latent row a token in one bank"]
            if model.latent is not None else [])
        if more:
            # built for global pages of keys and values alone, none
            # half-carried over: a prefix page, a draft, a chunk or a
            # transfer would each need the window layers' ring, or the
            # state as it stood there (a shared prefix's, a refused
            # draft's, a chunk's), beside the global layers' pages; over
            # a latent row each of their programs would need its form
            # without the values' bank
            asked = {
                "prefix_cache": prefix_cache, "speculative": speculative,
                "prefill_chunk": prefill_chunk, "kv_dtype": kv_dtype,
                "weight_dtype": weight_dtype, "host_tier": host_tier,
                "prefill_only": prefill_only, "mesh": mesh,
                "memledger": memledger,
            }
            for mode, value in asked.items():
                if value is not None and value is not False \
                        and value != "fp":
                    raise ValueError(
                        f"{mode} is not built for a model with "
                        f"{' and '.join(more)}: serve it with the "
                        f"engine's defaults")
        elif mesh is not None:
            model = describe(config, tp_axis)
        self.model = model
        if speculative is not None:
            k, n = speculative
            if not 1 <= k < model.n_layer:
                raise ValueError(
                    f"speculative draft depth {k} must be in "
                    f"[1, n_layer={model.n_layer})"
                )
            if n < 1:
                raise ValueError(f"speculative draft length {n} must be >= 1")
        self.recorder = recorder
        self.stall_patience = stall_patience
        self.tracer = tracer
        # ``sentinel``: optional ``telemetry.sentinel.PerfSentinel`` —
        # every finished run's tokens/s + decode-step/idle split is
        # compared against its rolling baseline, and a regression fires
        # a perf_regression black box naming the component. Default
        # None keeps finish_run at one attribute read + branch
        # (guard-tested < 5 µs, the tracer/recorder contract).
        self.sentinel = sentinel
        self.last_doctor_report = None   # refreshed by doctor()/doctor_chunk()
        self.last_step_profile = None    # refreshed by profile()
        self._run: Optional[_RunState] = None   # live steppable run
        # deterministic failure seam (testing/chaos.py replica_crash /
        # replica_wedge): None | "crash" (tick_once raises ReplicaFault
        # every call until cleared) | "wedge" (tick_once returns without
        # doing any work — the engine looks alive but makes no progress,
        # which is exactly what the control plane's heartbeat must catch)
        self._fault: Optional[str] = None
        if recorder is not None and tracer is not None:
            # a decode_stall (or any) black box then embeds the live
            # request timelines: the dump NAMES the stuck request
            recorder.set_request_tracer(tracer)
        self.registry = registry if registry is not None else get_registry()
        # resolve metric handles ONCE: inc/set/observe check the enabled
        # flag themselves, so the hot loop's disabled cost stays one
        # branch per site (no per-step registry lock + name lookup)
        reg = self.registry
        self._m_tokens = reg.counter("serving.tokens_total")
        self._m_requests = reg.counter("serving.requests_total")
        # deadline shedding (graceful degradation): shed / requests is
        # the degraded-mode ratio the default SLO set watches
        # (telemetry/slo.py shed_fraction target)
        self._m_shed = reg.counter("serving.shed_total")
        self._m_prefills = reg.counter("serving.prefills_total")
        self._m_steps = reg.counter("serving.decode_steps_total")
        self._m_step_uploads = reg.counter("serving.step_uploads")
        self._m_ttft = reg.histogram("serving.ttft_seconds")
        self._m_tok_lat = reg.histogram("serving.decode_token_seconds")
        self._m_e2e = reg.histogram("serving.e2e_latency_seconds")
        self._m_queue = reg.gauge("serving.queue_depth")
        self._m_active = reg.gauge("serving.slots_active")
        self._m_slot_occ = reg.gauge("serving.slot_occupancy")
        self._m_page_occ = reg.gauge("serving.page_occupancy")
        self._m_tps = reg.gauge("serving.tokens_per_s")
        # prefix cache / chunked prefill / speculative instrumentation
        self._m_hit_tok = reg.counter("serving.prefix_cache.hit_tokens")
        self._m_miss_tok = reg.counter("serving.prefix_cache.miss_tokens")
        self._m_shared = reg.counter("serving.prefix_cache.shared_pages")
        self._m_cow = reg.counter("serving.prefix_cache.cow_copies")
        self._m_cached = reg.gauge("serving.prefix_cache.cached_pages")
        # pages leaf-first eviction could recover right now — the head-
        # room half of the admission ledger, and the router's tie-break
        self._m_evictable = reg.gauge("serving.prefix_cache.evictable_pages")
        self._m_frag = reg.gauge("serving.pool.fragmentation")
        self._m_key_share = reg.gauge("serving.decode_key_share")
        # by cache kind (a one-kind pool sets the global gauge alone)
        self._m_pages_kind = {
            GLOBAL: reg.gauge("serving.pages_in_use.global"),
            WINDOW: reg.gauge("serving.pages_in_use.window"),
        }
        self._m_recycled = reg.counter("serving.window_pages_recycled_total")
        self._m_ring_wrapped = reg.gauge("serving.ring_wrapped_share")
        self._m_ring_useful = reg.gauge("serving.ring_rows_useful_share")
        self._m_experts = reg.gauge("serving.experts_touched_share")
        self._m_zero_picks = reg.gauge("serving.zero_pick_share")
        self._m_rows_useful = reg.gauge("serving.eva_rows_useful_share")
        self._m_summary_keys = reg.gauge("serving.eva_summary_key_share")
        self._m_state_slots = reg.gauge("serving.state_slots_in_use")
        self._m_state_writes = reg.counter("serving.state_writes_total")
        self._m_prefill_tok = reg.counter("serving.prefill_tokens_total")
        self._m_chunks = reg.counter("serving.prefill_chunks_total")
        self._m_gap = reg.histogram("serving.decode_gap_seconds")
        self._m_spec_cycles = reg.counter("serving.spec.cycles")
        self._m_spec_draft = reg.counter("serving.spec.draft_tokens")
        self._m_spec_acc = reg.counter("serving.spec.accepted_tokens")
        self.params = params
        self.config = config
        self.num_slots = num_slots
        self.page_size = page_size
        # entries of a slot's global page table: a page holds
        # ``page_size`` rows, a row ``stride`` positions (1, or a
        # summary's chunk)
        self.table_width = -(-max_context // (page_size * model.stride))
        self.mesh = mesh
        self.param_specs = param_specs
        self.tp_axis = tp_axis
        self.prefill_chunk = prefill_chunk
        self.speculative = speculative
        tp = mesh.shape[tp_axis] if mesh is not None else 1
        if model.n_kv_head % tp:
            raise ValueError(
                f"n_head={model.n_kv_head} not divisible by tp={tp}")
        # quantized inference knobs (ROADMAP item 4) — both default OFF.
        # "fp" is the explicit no-quantization alias both knobs accept
        # (check_kv_dtype does the same for kv_dtype), so a planner row's
        # candidate dict feeds straight back into the constructor
        if weight_dtype == "fp":
            weight_dtype = None
        self.weight_dtype = weight_dtype
        self.kv_dtype = check_kv_dtype(kv_dtype)
        # key columns a trip of the decode read's walk visits, and all
        # a table reaches: the host counts what the decode program walks
        self._walk_keys = walk_plan(page_size, self.table_width)[0] * page_size
        self._reach_keys = self.table_width * page_size
        # a window layer keeps a ring of pages a slot, and the pool every
        # ring its slots can hold (+ the kind's NULL page)
        ring = (ring_pages(model.window, page_size, model.window_rule)
                if WINDOW in model.kinds else 0)
        self._ring_keys = (walk_plan(page_size, ring)[1] * self._walk_keys
                           if ring else 0)
        # positions a summary stands for, where the window layers keep
        # summaries beside their ring (None: none does)
        self._chunk_size = (model.summaries.chunk
                            if model.summaries is not None else None)
        # sparse layers and held experts over them, as the decode
        # step's counters show them (0: the model brings none)
        self._sparse_layers = self._experts_held = 0
        self.quant_spec = None
        if weight_dtype is not None:
            from pipegoose_tpu.quant import (
                QuantSpec,
                quantize_param_specs,
                quantize_params,
            )
            from pipegoose_tpu.quant.weights import validate_tp_compat

            self.quant_spec = QuantSpec(weight_dtype, weight_group_size)
            validate_tp_compat(config, tp, self.quant_spec)
            if mesh is not None and param_specs is not None:
                # derive the q/scale PartitionSpecs from the fp tree
                # BEFORE the params change shape underneath them
                param_specs = quantize_param_specs(
                    param_specs, params, self.quant_spec
                )
            params = quantize_params(params, self.quant_spec)
            self.params = params
            self.param_specs = param_specs
        self.pool = PagePool(num_pages, page_size,
                             window_pages=num_slots * ring + 1 if ring else 0,
                             ring=ring, stride=model.stride)
        self._run_prefill_tokens = self._run_hit_tokens = 0  # set per run()
        self.prefix_cache = PrefixCache(self.pool) if prefix_cache else None
        # KV memory hierarchy (serving/kv_tier/): optional host-DRAM
        # spill target behind the prefix cache. ``host_tier_wire``
        # narrows FP pools on the wire (int8 pools already spill
        # wire-exact q+scale planes and forbid a wire dtype).
        if host_tier is not None and self.prefix_cache is None:
            raise ValueError("host_tier requires prefix_cache=True "
                             "(the tier backs the cache's evictions)")
        if host_tier_wire is not None:
            if host_tier is None:
                raise ValueError("host_tier_wire requires a host_tier")
            if self.kv_dtype == "int8":
                raise ValueError(
                    "host_tier_wire is for fp pools; int8 pages already "
                    "spill wire-exact (q+scale planes verbatim)")
        self.host_tier = host_tier
        self.host_tier_wire = host_tier_wire
        self.prefill_only = prefill_only
        # disagg handoff seam: hook(engine, req, first_token, t) runs at
        # prefill completion BEFORE the scheduler releases the pages, so
        # it can export them (serving/disagg/workers.py)
        self._handoff_hook = None
        self.sched = Scheduler(num_slots, self.pool, max_context,
                               prefix_cache=self.prefix_cache,
                               chunk_tokens=prefill_chunk,
                               tracer=tracer,
                               prefill_only=prefill_only)
        # paged prefill path: required by the cache (the tail attends to
        # shared pages) and by chunking; the legacy monolithic
        # forward_cached + write_prompt_pages path stays the default
        self._paged_prefill = prefix_cache or prefill_chunk is not None
        # fleet-directory publication seam: the control plane installs
        # hook(tokens, location) per replica; None costs one branch
        self.on_prefix_publish = None
        # goodput compile/warmup detection (telemetry/goodput.py): one
        # entry per jitted program family x width actually executed —
        # the control plane reads the counter delta around a tick to
        # book first-run (compile + warmup) wall separately from
        # steady-state productive wall. The entry keeps the host wall of
        # that first call, the one that compiled the program or loaded
        # it from the cache: (family, width) -> seconds, None while the
        # call is in flight
        self._first_call_s: dict = {}
        self.programs_run = 0
        # every cached engine gets a RestoreManager (cheap — nothing
        # compiles until the first spill/pull), so it can serve as a
        # pull PEER even without a host tier of its own
        self.kv_tier = (RestoreManager(self)
                        if self.prefix_cache is not None else None)
        if self.kv_tier is not None and cost_model is not None:
            n_params = sum(int(x.size)
                           for x in jax.tree_util.tree_leaves(params))
            self.kv_tier.planner = RestorePlanner(
                cost_model, n_params=n_params)
        if self.host_tier is not None:
            if self.host_tier._m_bytes is None:
                self.host_tier.bind_registry(self.registry)
            self.prefix_cache.spill_hook = self.kv_tier.spill
        self.k_pages, self.v_pages = init_pages(
            model, num_pages, page_size, kv_dtype=self.kv_dtype,
            window_pages=self.pool.window.num_pages if ring else 0,
        )
        # the state bank, a row a slot a layer ({}: the model keeps none)
        self.state = init_state(model, num_slots)
        self._state_rows = state_walk_plan(num_slots)[0]
        # a decode step's packed inputs: a token and a length a slot, then
        # its row of every cache kind's page table
        carry_widths = self._carry_widths = self._by_kind(lambda width: width)
        self._carry_size = num_slots * (2 + self.table_width + ring)
        valid = getattr(config, "valid_vocab_size", None)
        mask_fn = vocab_mask_for(config)
        spec_k = speculative[0] if speculative else None

        if mesh is None:
            def _prefill(params, ids, mask):
                # the model's own forward over the bucketed prompt
                logits, cache = model.prefill(params, ids, mask)
                return greedy_token(logits, mask_fn), cache

            if model.left_pad:
                def _write(k_pages, v_pages, cache, phys, pad):
                    return write_prompt_pages(
                        k_pages, v_pages, cache, phys, pad, page_size
                    )
            elif model.latent is not None:
                def _write(pages, cache, phys, pad, length):
                    # the one bank of a latent row: no values' bank in
                    # the program
                    return write_prompt_pages(
                        pages, None, cache, phys, pad, page_size,
                        length)[:1]
            elif not model.state:
                def _write(k_pages, v_pages, cache, phys, pad, length):
                    return write_prompt_pages(
                        k_pages, v_pages, cache, phys, pad, page_size,
                        length, stride=model.stride)
            else:
                def _write(k_pages, v_pages, cache, phys, pad, length,
                           state, slot):
                    # the prompt's pages and the slot's state in one
                    # program: the next step finds both or neither
                    return write_prompt_pages(
                        k_pages, v_pages, cache, phys, pad, page_size,
                        length) + (write_state(state, cache["state"], slot),)

            def step_body(params, tokens, k_pages, v_pages, table, seq_lens,
                          state):
                # a fourth result: the blocks' counters, and a fifth: the
                # state bank ({} where a model has none: no leaf, the
                # same program)
                logits, k_pages, v_pages, counters, state = \
                    paged_decode_step(
                        params, tokens, k_pages, v_pages, table, seq_lens,
                        model, with_counters=True, state=state,
                    )
                return (greedy_token(logits, mask_fn), k_pages, v_pages,
                        counters, state)

            def _chunk(params, ids, k_pages, v_pages, table, start, n_valid):
                logits, k_pages, v_pages = paged_prefill_chunk(
                    params, ids, k_pages, v_pages, table, start, n_valid,
                    config)
                return greedy_token(logits, mask_fn), k_pages, v_pages

            def _copy(k_pages, v_pages, src, dst):
                return copy_page(k_pages, v_pages, src, dst)

            def _draft(params, tokens, k_pages, v_pages, table, seq_lens, ok):
                logits, k_pages, v_pages = paged_decode_step(
                    params, tokens, k_pages, v_pages, table, seq_lens,
                    config, write_ok=ok, draft_layers=spec_k,
                )
                return greedy_token(logits, mask_fn), k_pages, v_pages

            def _verify(params, ids, k_pages, v_pages, table, start, n_valid):
                logits, k_pages, v_pages = paged_prefill_chunk(
                    params, ids, k_pages, v_pages, table, start, n_valid,
                    config, all_logits=True)
                return greedy_token(logits, mask_fn), k_pages, v_pages

            self._prefill = jax.jit(_prefill)
            self._write = jax.jit(
                _write, donate_argnums=(0, 1, 6) if model.state
                else (0,) if model.latent is not None else (0, 1))
            self._chunk = jax.jit(_chunk, donate_argnums=(2, 3))
            self._copy = jax.jit(_copy, donate_argnums=(0, 1))
            self._draft = jax.jit(_draft, donate_argnums=(2, 3))
            self._verify = jax.jit(_verify, donate_argnums=(2, 3))
        else:
            # pages: a row holds its heads major, so sharding the rows
            # gives each shard its nh/tp heads. int8 pools are {"q",
            # "scale"} pytrees: the per-head scales shard WITH their heads
            vspec = P(None, None, None, tp_axis)
            pspec = (
                {"q": vspec, "scale": vspec}
                if self.kv_dtype == "int8" else vspec
            )
            hspec = P(None, None, None, tp_axis, None)   # fp prefill cache
            cspec = {"k": hspec, "v": hspec}

            def _prefill_body(params, ids, mask):
                logits, cache = model.prefill(params, ids, mask)
                return global_greedy_pick(logits, tp_axis, valid), cache

            def _write_body(k_pages, v_pages, cache, phys, pad):
                return write_prompt_pages(
                    k_pages, v_pages, cache, phys, pad, page_size
                )

            def _step_body(params, tokens, k_pages, v_pages, table, seq_lens):
                logits, k_pages, v_pages = paged_decode_step(
                    params, tokens, k_pages, v_pages, table, seq_lens,
                    model, tp_axis)
                tok = global_greedy_pick(logits, tp_axis, valid)
                return tok, k_pages, v_pages, {}, {}

            def _chunk_body(params, ids, k_pages, v_pages, table, start,
                            n_valid):
                logits, k_pages, v_pages = paged_prefill_chunk(
                    params, ids, k_pages, v_pages, table, start, n_valid,
                    config, tp_axis)
                tok = global_greedy_pick(logits, tp_axis, valid)
                return tok, k_pages, v_pages

            def _copy_body(k_pages, v_pages, src, dst):
                return copy_page(k_pages, v_pages, src, dst)

            def _draft_body(params, tokens, k_pages, v_pages, table,
                            seq_lens, ok):
                logits, k_pages, v_pages = paged_decode_step(
                    params, tokens, k_pages, v_pages, table, seq_lens,
                    config, tp_axis, write_ok=ok, draft_layers=spec_k,
                )
                tok = global_greedy_pick(logits, tp_axis, valid)
                return tok, k_pages, v_pages

            def _verify_body(params, ids, k_pages, v_pages, table, start,
                             n_valid):
                logits, k_pages, v_pages = paged_prefill_chunk(
                    params, ids, k_pages, v_pages, table, start, n_valid,
                    config, tp_axis, all_logits=True)
                b, c, _ = logits.shape
                tok = global_greedy_pick(
                    logits.reshape(b * c, -1), tp_axis, valid
                ).reshape(b, c)
                return tok, k_pages, v_pages

            self._prefill = jax.jit(shard_map(
                _prefill_body, mesh=mesh,
                in_specs=(param_specs, P(), P()), out_specs=(P(), cspec),
                check_vma=False,
            ))
            self._write = jax.jit(shard_map(
                _write_body, mesh=mesh,
                in_specs=(pspec, pspec, cspec, P(), P()),
                out_specs=(pspec, pspec), check_vma=False,
            ), donate_argnums=(0, 1))
            sharded_step = shard_map(
                _step_body, mesh=mesh,
                in_specs=(param_specs, P(), pspec, pspec, P(), P()),
                out_specs=(P(), pspec, pspec, {}, {}), check_vma=False,
            )

            def step_body(params, tokens, k_pages, v_pages, table, seq_lens,
                          state):
                return sharded_step(params, tokens, k_pages, v_pages, table,
                                    seq_lens)

            self._chunk = jax.jit(shard_map(
                _chunk_body, mesh=mesh,
                in_specs=(param_specs, P(), pspec, pspec, P(), P(), P()),
                out_specs=(P(), pspec, pspec), check_vma=False,
            ), donate_argnums=(2, 3))
            self._copy = jax.jit(shard_map(
                _copy_body, mesh=mesh,
                in_specs=(pspec, pspec, P(), P()),
                out_specs=(pspec, pspec), check_vma=False,
            ), donate_argnums=(0, 1))
            self._draft = jax.jit(shard_map(
                _draft_body, mesh=mesh,
                in_specs=(param_specs, P(), pspec, pspec, P(), P(), P()),
                out_specs=(P(), pspec, pspec), check_vma=False,
            ), donate_argnums=(2, 3))
            self._verify = jax.jit(shard_map(
                _verify_body, mesh=mesh,
                in_specs=(param_specs, P(), pspec, pspec, P(), P(), P()),
                out_specs=(P(), pspec, pspec), check_vma=False,
            ), donate_argnums=(2, 3))
            sharding = jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s), pspec,
                is_leaf=lambda x: isinstance(x, P),
            )
            self.k_pages = jax.device_put(self.k_pages, sharding)
            self.v_pages = jax.device_put(self.v_pages, sharding)
            self._pspec = pspec

        # The decode step takes its small inputs PACKED, one int32 buffer
        # (``_RunState.packed``), and returns the NEXT step's beside its
        # picks, computed where it is needed and left there: a tick in
        # which nothing else changed hands the device nothing.
        def _step(params, carry, k_pages, v_pages, state={}):
            tokens, seq_lens, table = _unpack(carry, num_slots, carry_widths)
            nxt, k_pages, v_pages, counters, state = step_body(
                params, tokens, k_pages, v_pages, table, seq_lens, state)
            # a sixth result, the next step's inputs: a live row's token
            # is this pick and its length one more, a dead row keeps its
            # zeros, the tables stay (``_RunState.advance_held`` is the
            # host's copy of this rule)
            live = seq_lens > 0
            head = jnp.concatenate(
                [jnp.where(live, nxt.astype(carry.dtype), 0), seq_lens + live])
            carry = jax.lax.dynamic_update_slice(carry, head, (0,))
            return nxt, k_pages, v_pages, counters, state, carry

        donate = (1, 2, 3, 4)
        if model.latent is not None:
            both = _step

            def _step(params, carry, pages):
                # over a latent row the pool is ONE bank: the program
                # takes, donates and returns no bank of values
                nxt, pages, _, counters, state, carry = both(
                    params, carry, pages, None)
                return nxt, pages, counters, state, carry

            donate = (1, 2)
        self._step = jax.jit(_step, donate_argnums=donate)
        # A buffer from the host goes to the CALL as the host array it
        # is: the jitted call's own argument path transfers it for a
        # fraction of what ``jax.device_put`` or ``jnp.asarray`` cost the
        # host (PERF.md §6, PR 43). Under a mesh it is first placed on
        # every device, where the step leaves its next inputs: a host
        # array there meets a second compiled program.
        if mesh is None:
            self._place = lambda buf: buf
        else:
            everywhere = NamedSharding(mesh, P())
            self._place = lambda buf: jax.device_put(buf, everywhere)
        # live memory ledger (telemetry/memledger.py) — attached LAST:
        # bytes-per-page is measured from the live pool arrays above
        self.memledger = None
        if memledger:
            from pipegoose_tpu.telemetry.memledger import MemoryLedger

            self.attach_memledger(
                memledger if isinstance(memledger, MemoryLedger)
                else MemoryLedger())
        self._build_s = time.perf_counter() - t_build

    def doctor(self, large_bytes: int = 1 << 20, registry=None):
        """Mesh-doctor report (telemetry/doctor.py) for the compiled
        paged DECODE step — the serving hot path: actual shardings of
        params and KV pages diffed against the engine's intended specs
        (head-sharded pages under TP), the collective schedule
        (``global_greedy_pick``'s all_gathers are the only intended
        traffic), and the per-device HBM budget dominated by the page
        pool. Shape-only: nothing executes, no pages are touched."""
        from pipegoose_tpu.telemetry.doctor import diagnose, set_doctor_gauges

        carry = jax.ShapeDtypeStruct((self._carry_size,), jnp.int32)
        intended = None
        if self.mesh is not None:
            intended = (self.param_specs, P(), self._pspec, self._pspec)
        pool = self._pool()
        report = diagnose(
            self._step, self.params, carry, *pool, *self._state_arg(),
            intended=intended,
            labels=("params", "carry", "k_pages", "v_pages")[:2 + len(pool)]
            + ("state",) * len(self._state_arg()),
            mesh=self.mesh, large_bytes=large_bytes,
        )
        set_doctor_gauges(report, registry=registry or self.registry)
        self.last_doctor_report = report   # /debug/doctor serves this
        return report

    def doctor_chunk(self, large_bytes: int = 1 << 20, registry=None):
        """Same report for the compiled CHUNKED-PREFILL program — the
        other half of the mixed step. CI pins it at zero
        partitioner-inserted resharding (scripts/mesh_doctor.py
        --serving), so a PartitionSpec regression in the chunk path dies
        at compile time like one in the decode path would."""
        from pipegoose_tpu.telemetry.doctor import diagnose, set_doctor_gauges

        i32 = jnp.int32
        c = self.prefill_chunk or self.page_size
        ids = jax.ShapeDtypeStruct((1, c), i32)
        table = jax.ShapeDtypeStruct((1, self.table_width), i32)
        start = jax.ShapeDtypeStruct((1,), i32)
        n_valid = jax.ShapeDtypeStruct((1,), i32)
        intended = None
        if self.mesh is not None:
            intended = (self.param_specs, P(), self._pspec, self._pspec,
                        P(), P(), P())
        report = diagnose(
            self._chunk, self.params, ids, self.k_pages, self.v_pages,
            table, start, n_valid,
            intended=intended,
            labels=("params", "ids", "k_pages", "v_pages", "table",
                    "start", "n_valid"),
            mesh=self.mesh, large_bytes=large_bytes,
        )
        set_doctor_gauges(report, registry=registry or self.registry)
        self.last_doctor_report = report
        return report

    def profile(self, steps: int = 3, warmup: int = 2,
                trace_dir: Optional[str] = None, registry=None):
        """Measured device-time attribution (telemetry/xprof.py) of the
        compiled paged DECODE step — the runtime twin of
        :meth:`doctor`: runs the real step on a synthetic full-slot
        batch whose page tables point at the NULL page (so the writes
        land in the page whose content is garbage by design and no live
        request's KV is touched), under the XLA profiler, and returns
        the ``StepProfile`` splitting the fenced step into compute /
        per-axis collectives / idle. Cached on ``last_step_profile``
        (the ops server's ``/debug/profile`` provider). Not callable
        mid-run — the step donates the KV pages and the engine adopts
        the final buffers afterwards."""
        from pipegoose_tpu.telemetry.xprof import profile_step

        if self._run is not None:
            raise RuntimeError("profile() cannot run during a serving run")
        # every row dead: zero lengths, tables of the NULL page
        carry = self._place(np.zeros((self._carry_size,), np.int32))
        final: dict = {}

        n = len(self._pool())

        def update(out, cur):
            # out = (next_tokens, the pool's banks, counters, state, the
            # next step's inputs); the inputs, the pages and the state
            # bank were donated — thread (and finally adopt) the new
            # buffers
            final["pool"], final["state"] = out[1:1 + n], out[n + 2]
            return (cur[0], out[n + 3]) + tuple(out[1:1 + n]) + (
                (out[n + 2],) if self.state else ())

        try:
            profile = profile_step(
                self._step, self.params, carry, *self._pool(),
                *self._state_arg(),
                steps=steps, warmup=warmup, update_args=update,
                mesh=self.mesh, trace_dir=trace_dir,
                registry=registry or self.registry,
            )
        finally:
            # the FIRST executed call already donated the stored page
            # buffers: adopt the newest generation even when trace
            # parsing/export raises, or the engine's next decode step
            # would touch deleted arrays
            if final:
                self._adopt(final["pool"])
                self.state = final["state"]
        self.last_step_profile = profile
        return profile

    def memory_report(self, registry=None) -> dict:
        """Host-side HBM census of the engine's RESIDENT state — the
        serving view of the doctor's memory budget, grouped by dtype so
        a quantized engine's ~2x drop is a number, not a vibe. Weights
        come from the live param tree (quantized leaves count their
        int8/int4+scale bytes), KV from the live pool arrays (values +
        scale planes). ``page_capacity_ratio`` is the measured
        bytes-per-page multiplier vs an fp pool of the same geometry:
        how many times more pages the same KV HBM holds at this
        ``kv_dtype`` (the >= 1.8x acceptance meter). Sets the
        ``serving.hbm.weights_bytes`` / ``serving.hbm.kv_bytes`` gauge
        pair next to ``doctor.hbm_peak_bytes``."""
        from pipegoose_tpu.quant.weights import quantized_weight_bytes

        weights = quantized_weight_bytes(self.params)
        kv_by: dict = {}
        for leaf in jax.tree_util.tree_leaves((self.k_pages, self.v_pages)):
            nbytes = int(leaf.size) * int(np.dtype(leaf.dtype).itemsize)
            key = str(leaf.dtype)
            kv_by[key] = kv_by.get(key, 0) + nbytes
        kv_total = int(sum(kv_by.values()))
        model = self.model
        num_pages = self.pool.num_pages
        # a page of one bank layer, and the banks, by the description
        row = (self.page_size * model.row_lanes
               * int(np.dtype(model.dtype).itemsize))
        by_kind = {
            kind: {"layers": model.layers_of(kind),
                   "num_pages": self.pool.of(kind).num_pages,
                   "fp_bytes": model.banks * model.layers_of(kind)
                   * self.pool.of(kind).num_pages * row}
            for kind in model.kinds}
        fp_total = sum(k["fp_bytes"] for k in by_kind.values())
        report = {
            "weight_dtype": self.weight_dtype or "fp",
            "kv_dtype": self.kv_dtype or "fp",
            "weights": weights,
            "kv": {
                "bytes_by_dtype": kv_by,
                "total_bytes": kv_total,
                "num_pages": num_pages,
                "bytes_per_page": kv_total // num_pages,
                "fp_bytes_per_page": fp_total // num_pages,
                "page_capacity_ratio": round(fp_total / max(kv_total, 1), 4),
                # a bank a cache kind (one, "global", for most models)
                "by_kind": by_kind,
            },
        }
        if self.state:
            # the state bank beside the pool: a row a slot a layer
            report["state"] = {"slots": self.num_slots,
                               "total_bytes": self._state_bytes()}
        if self.host_tier is not None:
            # exact slab census: pages x wire bytes (q+scale for int8
            # pools — never fp-sized), the ISSUE's pinned invariant
            report["host_tier"] = {
                "resident_pages": self.host_tier.resident_pages,
                "resident_bytes": self.host_tier.resident_bytes,
                "budget_bytes": self.host_tier.byte_budget,
            }
        reg = registry if registry is not None else self.registry
        reg.gauge(
            "serving.hbm.weights_bytes",
            help="resident model weight bytes (quantized leaves counted "
                 "at their wire size)",
        ).set(float(weights["total_bytes"]))
        reg.gauge(
            "serving.hbm.kv_bytes",
            help="resident KV page-pool bytes (values + scale planes)",
        ).set(float(kv_total))
        reg.gauge(
            "serving.hbm.kv_page_capacity_ratio",
            help="pages the same HBM holds vs an fp pool (1.0 = fp)",
        ).set(float(report["kv"]["page_capacity_ratio"]))
        return report

    # -- internals ---------------------------------------------------------

    def attach_tracer(self, tracer) -> None:
        """Attach (or detach, with None) a ``RequestTracer`` after
        construction — the engine and its scheduler share the handle,
        and an attached flight recorder starts embedding the tracer's
        timelines in black-box dumps. Post-hoc attachment exists so a
        warm engine (compiled programs, seeded cache) can run one traced
        replay without rebuilding."""
        self.tracer = tracer
        self.sched.tracer = tracer
        if self.recorder is not None:
            self.recorder.set_request_tracer(tracer)

    def attach_memledger(self, ledger) -> None:
        """Attach (or detach, with None) a ``telemetry.memledger.
        MemoryLedger``: binds it to the pool (as the synchronous event
        observer), the scheduler, the prefix cache, the host tier, the
        flight recorder, and the registry, with the bytes-per-page
        MEASURED from the live pool arrays (q+scale planes for int8
        pools — the same census ``memory_report`` does). Post-hoc
        attachment adopts a warm pool via the ledger's ``resync``."""
        if ledger is None:
            if self.memledger is not None:
                self.memledger.unbind()
            self.memledger = None
            return
        total = 0
        for leaf in jax.tree_util.tree_leaves((self.k_pages, self.v_pages)):
            total += int(leaf.size) * int(np.dtype(leaf.dtype).itemsize)
        ledger.bind(
            self.pool, sched=self.sched, cache=self.prefix_cache,
            host_tier=self.host_tier, recorder=self.recorder,
            registry=self.registry,
            bytes_per_page=total // self.pool.num_pages,
        )
        self.memledger = ledger

    def _note_program(self, family: str, width: int) -> bool:
        """Record one jitted-program execution for the goodput
        ledger's compile/warmup detection: the first (family, width)
        pair is the tick that paid the XLA compile. Returns True for
        that first execution; the call site then keeps its host wall in
        ``_first_call_s`` (``finish_run()["setup"]``): dispatch to
        completed fetch where it fetches, the call alone (in which the
        compile or the cache load runs) where it does not."""
        key = (family, width)
        if key in self._first_call_s:
            return False
        self._first_call_s[key] = None
        self.programs_run += 1
        return True

    def _state_arg(self) -> tuple:
        """The state bank as the decode program's last argument: nothing
        for a model without (its program takes no such argument)."""
        return (self.state,) if self.state else ()

    def _pool(self) -> tuple:
        """The pool's banks as a program takes them: keys and values, or
        the one bank of a latent row."""
        if self.v_pages is None:
            return (self.k_pages,)
        return self.k_pages, self.v_pages

    def _adopt(self, banks) -> None:
        """The banks a program returned, in :meth:`_pool`'s order."""
        self.k_pages, self.v_pages = (
            banks if len(banks) == 2 else (banks[0], None))

    def _state_bytes(self) -> int:
        return sum(int(x.size) * int(np.dtype(x.dtype).itemsize)
                   for x in self.state.values())

    def _by_kind(self, make):
        """``make(table width)`` for every cache kind of the model: the
        bare value for a one-kind model, ``{kind: ..}`` for a two-kind
        one (what the paged programs take as a page table)."""
        if self.pool.window is None:
            return make(self.table_width)
        return {GLOBAL: make(self.table_width), WINDOW: make(self.pool.ring)}

    def _unpack_carry(self, carry):
        """(tokens, lengths, page table(s)) out of a decode step's packed
        buffer (``_unpack``) at this engine's sizes."""
        return _unpack(carry, self.num_slots, self._carry_widths)

    def _phys_rows(self, req: Request):
        """A request's page-table rows for the page write, by kind."""
        def row(width, pages):
            out = np.zeros((width,), np.int32)
            out[:len(pages)] = pages
            return jnp.asarray(out)

        if self.pool.window is None:
            return row(self.table_width, req.pages)
        return {GLOBAL: row(self.table_width, req.pages),
                WINDOW: row(self.pool.ring, req.window_pages)}

    def _note_counters(self, rs, counters: dict) -> None:
        """One plain decode step's counter channel: ``rows_per_expert``
        (sparse layers, held experts), the rows each held expert was
        sent; where the model routes to zero-compute experts too,
        ``zero_picks`` and ``picks`` (a sparse layer each): the live
        rows' picks that cost nothing, and all of them."""
        if "summary_rows" in counters:
            # a layer's two walks: what the softmax needed, what the
            # walks gathered for it, the summaries written
            got = rs.summary_rows = rs.summary_rows or {}
            for name, n in zip(SUMMARY_COUNTERS, counters["summary_rows"]):
                got[name] = got.get(name, 0) + int(n)
        rows = counters.get("rows_per_expert")
        if rows is None:
            return
        layers, held = rows.shape
        self._sparse_layers, self._experts_held = layers, layers * held
        touched = int((rows > 0).sum())
        rs.experts_touched.append(touched)
        total = rows.sum(axis=1)
        rs.rows_routed += int(total.sum())
        rs.expert_skew += float(
            (rows.max(axis=1) * held / np.maximum(total, 1)).sum())
        self._m_experts.set(touched / (layers * held))
        zero = counters.get("zero_picks")
        if zero is not None:
            share = float(zero.sum()) / max(int(counters["picks"].sum()), 1)
            rs.zero_pick_share = (rs.zero_pick_share or 0.0) + share
            self._m_zero_picks.set(share)

    def _ledger_tick(self, rs) -> None:
        """Per-tick ledger hook (conservation check + forecast +
        occupancy sample). With no ledger attached (the default) the
        cost is this one attribute read + branch — the disabled-path
        guard test times exactly this call."""
        ml = self.memledger
        if ml is None:
            return
        ml.on_tick(rs.tick, t=rs.now())

    def set_handoff_hook(self, hook) -> None:
        """Install (or clear, with None) the disagg handoff seam:
        ``hook(engine, req, first_token, t)`` runs at each prefill's
        completion, BEFORE the scheduler releases the request's pages —
        the one moment the finished prompt KV is both complete and
        still addressable for export (serving/disagg/workers.py's
        PrefillWorker is the production hook)."""
        self._handoff_hook = hook

    def set_peer_source(self, peer) -> None:
        """Default cross-replica pull source: every queued request
        probes ``peer``'s prefix inventory before admission (demo /
        two-engine tests; the control plane hints per request through
        the fleet directory instead). Requires a prefix cache."""
        if self.kv_tier is None:
            raise RuntimeError("set_peer_source requires prefix_cache=True")
        self.kv_tier.set_peer_source(peer)

    def admit_transferred(self, req: Request, first_token: int) -> bool:
        """Disagg decode-pool admission: bind a fully materialized
        transfer (every page imported at wire precision) to a free
        slot, skipping prefill — ``Scheduler.admit_with_pages`` does
        the lifecycle; this wrapper adds the engine bookkeeping a
        normal admission would have accrued (request counter, prefix-
        cache publication of the transferred-in prompt pages so later
        LOCAL re-prefills hit them, run-state done collection when the
        request finishes at admission). Returns False when no slot is
        free (the staged transfer keeps its pages + reservation)."""
        rs = self._run
        if rs is None:
            raise RuntimeError("admit_transferred needs start_run first")
        if self.prefix_cache is not None:
            # transferred-in pages are real prompt KV with FINAL
            # content: publish the full pages exactly like a local
            # prefill would, so a fallback (or migrated) request
            # sharing the prefix hits them. BEFORE admission, from the
            # stage record — a request finishing AT admission
            # (max_new=1/eos) releases its pages inside
            # admit_with_pages, and publishing freed pages would be a
            # no-op at best
            stage = self.sched.transfers.get(req.uid)
            if stage is not None:
                n_full = req.prompt_len // self.page_size
                self.prefix_cache.insert(
                    np.asarray(req.prompt)[:n_full * self.page_size],
                    stage["pages"][:n_full],
                )
                self._m_cached.set(self.prefix_cache.cached_pages)
                if self.on_prefix_publish is not None and n_full:
                    self.on_prefix_publish(
                        np.asarray(req.prompt)[:n_full * self.page_size],
                        "hbm",
                    )
        if not self.sched.admit_with_pages(req, first_token, rs.now()):
            return False
        self._m_requests.inc()
        self._observe_ttft(req)
        if req.status is Status.DONE:
            rs.done.append(req)
        return True

    def _observe_ttft(self, req: Request) -> None:
        """Record TTFT into the histogram EXACTLY ONCE per request. Two
        engine paths can complete a prefill (the monolithic
        ``_prefill_request`` and the paged ``_prefill_chunk_tick``), and
        a preempted-then-re-admitted request re-enters prefill with its
        preserved ``t_first_token`` — the ``ttft_observed`` flag makes a
        double observation structurally impossible regardless of which
        path(s) a request crosses."""
        if (req.ttft_observed or req.t_first_token is None
                or req.t_submit is None):
            return
        req.ttft_observed = True
        self._m_ttft.observe(req.t_first_token - req.t_submit)

    def _prefill_request(self, req: Request, now) -> None:
        """Legacy monolithic prefill: run the bucketed contiguous
        forward, scatter the prompt KV into the request's pages (and,
        for a model with a state a slot, the state after the last token
        into the slot's row), and record the first generated token. A
        request re-admitted after a preemption forwards its prompt plus
        every generated token but the pending one (``target_len``) and
        resumes decoding on that one: the forward's own pick repeats it
        and is dropped."""
        tr = self.tracer
        t0 = now() if tr is not None else 0.0
        with span("serving.prefill", registry=self.registry):
            s = req.target_len
            # whole pages of POSITIONS (a global page may hold more)
            bucket = self.pool.logical_pages(s) * self.page_size
            first = self._note_program("prefill", bucket)
            first_write = self._note_program("write", bucket)
            # the padding before the prompt (BLOOM) or behind it (a
            # model whose positions count from the prompt's start)
            left = self.model.left_pad
            pad = bucket - s if left else 0
            ids = np.zeros((1, bucket), np.int32)
            ids[0, pad:pad + s] = req.tokens[:s]
            mask = np.zeros((1, bucket), np.int32)
            mask[0, pad:pad + s] = 1
            t_call = now()
            tok, cache = self._prefill(
                self.params, jnp.asarray(ids), jnp.asarray(mask)
            )
            t_write = now()
            # dispatched and never fetched: on the device the write runs
            # after this span has closed, in the next fetch's wait
            pool = self._pool()
            written = self._write(
                *pool, cache, self._phys_rows(req),
                jnp.asarray(pad, jnp.int32),
                *(() if left else (jnp.asarray(s, jnp.int32),)),
                *((self.state, jnp.asarray(req.slot, jnp.int32))
                  if self.state else ()),
            )
            self._adopt(written[:len(pool)])
            if self.state:
                # the slot starts from this prefill's state, whatever
                # its last request left in the row
                self.state = written[len(pool)]
                self._run.state_writes += 1
                self._m_state_writes.inc()
            t_fetch = now()
            # the token fetch syncs the device, so the span's wall time
            # covers the prefill's actual device work
            tok = int(np.asarray(tok)[0])  # host fetch syncs the device:
            t1 = now()                     # span + chunk dur = device work
            if first:
                # its own call and the wait for its token, not the
                # write's call that runs between the two
                self._first_call_s["prefill", bucket] = (
                    (t_write - t_call) + (t1 - t_fetch))
            if first_write:
                self._first_call_s["write", bucket] = t_fetch - t_write
            if tr is not None:
                tr.on_prefill_chunk(req, t1, dur_s=t1 - t0, tokens=s)
            if req.generated:
                # resumed after preemption: nothing new to record
                req.status = Status.DECODE
                if tr is not None:
                    tr.on_resume(req, t1)
            else:
                self.sched.record_token(req, tok, t1)
                self._m_tokens.inc()  # the prefill's token
        self._m_prefill_tok.inc(s)
        self._run_prefill_tokens += s
        self._m_prefills.inc()
        self._observe_ttft(req)

    def _start_prefill(self, req: Request, now) -> None:
        """Paged-path admission follow-up: account the cache hit and run
        the pending copy-on-write duplication (the shared page whose
        mid-page tail this request will write gets a private copy; the
        admission pin on the source is dropped right after)."""
        if self.prefix_cache is not None:
            # chunk-only engines have no cache: 100%-miss counters here
            # would read as a misconfigured cache on a dashboard
            self._m_hit_tok.inc(req.hit_tokens)
            self._m_miss_tok.inc(req.target_len - req.hit_tokens)
            self._m_shared.inc(req.prefilled_len // self.page_size)
            self._run_hit_tokens += req.hit_tokens
        if req.cow is not None:
            src, m = req.cow
            dst = req.pages[req.prefilled_len // self.page_size]
            self.k_pages, self.v_pages = self._copy(
                self.k_pages, self.v_pages,
                jnp.asarray(src, jnp.int32), jnp.asarray(dst, jnp.int32),
            )
            if self.pool.ledger is not None:
                self.pool.tag = ("cow", req.uid)
            self.pool.release([src])   # the PrefixCache.acquire pin
            req.cow = None
            req.prefilled_len += m
            self._m_cow.inc()
            if self.tracer is not None:
                self.tracer.on_cow(req, now())

    def _prefill_chunk_tick(self, req: Request, now) -> None:
        """Advance one prefill chunk through the page tables; on
        reaching the target, record the first token (fresh request) or
        resume decoding (preempted re-admission: the pending token is
        already in ``generated``)."""
        target = req.target_len
        begin = req.prefilled_len
        end = min(begin + (self.prefill_chunk or target - begin), target)
        n = end - begin
        # program width: ONE shape when chunking (the last chunk pads),
        # page-multiple buckets otherwise — same compile bound as the
        # monolithic path's prompt buckets
        prog = (self.prefill_chunk if self.prefill_chunk is not None
                else self.pool.pages_for(n) * self.page_size)
        first = self._note_program("chunk", prog)
        self.sched.ensure_pages(req, end)
        ids = np.zeros((1, prog), np.int32)
        ids[0, :n] = req.tokens[begin:end]
        table = np.zeros((1, self.table_width), np.int32)
        table[0, :len(req.pages)] = req.pages
        tr = self.tracer
        t0 = now()
        with span("serving.prefill", registry=self.registry):
            tok, self.k_pages, self.v_pages = self._chunk(
                self.params, jnp.asarray(ids), self.k_pages, self.v_pages,
                jnp.asarray(table), jnp.asarray([begin], jnp.int32),
                jnp.asarray([n], jnp.int32),
            )
            tok = int(np.asarray(tok)[0])  # sync: span = device work
        t1 = now()
        if first:
            self._first_call_s["chunk", prog] = t1 - t0
        if tr is not None:
            tr.on_prefill_chunk(req, t1, dur_s=t1 - t0, tokens=n)
        req.prefilled_len = end
        self._m_chunks.inc()
        self._m_prefill_tok.inc(n)
        self._run_prefill_tokens += n
        if end < target:
            return
        if self.prefix_cache is not None:
            # content now stable for every FULL prompt page: publish
            n_full = req.prompt_len // self.page_size
            self.prefix_cache.insert(
                np.asarray(req.prompt)[:n_full * self.page_size],
                req.pages[:n_full],
            )
            self._m_cached.set(self.prefix_cache.cached_pages)
            if self.on_prefix_publish is not None and n_full:
                self.on_prefix_publish(
                    np.asarray(req.prompt)[:n_full * self.page_size], "hbm",
                )
        self._m_prefills.inc()
        if self._handoff_hook is not None:
            # disagg prefill pool: the first token exists NOW — hand it
            # off with the remaining un-streamed pages instead of
            # decoding here. The hook exports from the still-allocated
            # pages; finish_handoff then frees slot + pages +
            # reservation and opens the transfer attribution phase.
            t1 = now()
            self._m_tokens.inc()       # the prefill's token, as always
            self._handoff_hook(self, req, int(tok), t1)
            self.sched.finish_handoff(req, t1)
            self._observe_ttft(req)
            return
        if req.generated:
            # resumed after preemption: the forwarded tail's last logits
            # re-derive the pending token (greedy is deterministic);
            # nothing new to record — decode picks up where it left off
            req.status = Status.DECODE
            if tr is not None:
                tr.on_resume(req, now())
            return
        had_first = req.t_first_token is not None
        self.sched.record_token(req, tok, now())
        self._m_tokens.inc()
        self._observe_ttft(req)
        if tr is not None and had_first and req.status is not Status.DONE:
            # disagg transfer-failure fallback: the request already
            # carries its handoff-time first token, so record_token
            # fired no first-token hook — without this resume the
            # timeline would book the whole decode as prefill (a DONE
            # request's timeline just closed; re-opening it would leak
            # a ghost)
            tr.on_resume(req, now())

    def _spec_cycle(self, rows: List[Request], now, done: List[Request]):
        """One speculative decode cycle over the active batch: draft up
        to n tokens per slot with the k-layer shallow exit, verify the
        whole bundle in one full-model pass, emit the longest verified
        prefix plus the correction token. Finished requests land in
        ``done``. Returns (emitted, drafted, accepted, surviving rows
        — lazy growth may retract a neighbor mid-batch)."""
        spec_k, n_spec = self.speculative
        first = self._note_program("spec", n_spec)
        table = np.zeros((self.num_slots, self.table_width), np.int32)
        seq = np.zeros((self.num_slots,), np.int32)
        tok0 = np.zeros((self.num_slots,), np.int32)
        g = np.zeros((self.num_slots,), np.int32)
        for r in rows:
            if r.status is not Status.DECODE:
                continue  # retracted by an earlier row's lazy growth
            # bound per-slot draft depth so verified writes stay inside
            # the admission worst case: positions <= cached + remaining-1
            g_i = min(n_spec, r.max_new_tokens - len(r.generated) - 1)
            self.sched.ensure_pages(r, r.cached_len + g_i + 1)
        rows = [r for r in rows if r.status is Status.DECODE]
        for r in rows:
            g_i = min(n_spec, r.max_new_tokens - len(r.generated) - 1)
            table[r.slot, :len(r.pages)] = r.pages
            seq[r.slot] = r.cached_len
            tok0[r.slot] = r.generated[-1]
            g[r.slot] = g_i
        drafts: List[np.ndarray] = []
        cur = jnp.asarray(tok0)
        jtable = jnp.asarray(table)
        tr = self.tracer
        t_c0 = now()
        # same span as the plain path: speculative mode must not make
        # the decode-step stream vanish from dashboards/Perfetto
        with span("serving.decode_step", registry=self.registry):
            for j in range(n_spec):
                cur, self.k_pages, self.v_pages = self._draft(
                    self.params, cur, self.k_pages, self.v_pages, jtable,
                    jnp.asarray(seq + j), jnp.asarray(g > j),
                )
                drafts.append(cur)   # device array: no sync between steps
            # one host fetch AFTER the loop so every draft dispatch
            # enqueues back-to-back (no per-token dispatch-RTT gaps)
            drafts = [np.asarray(d) for d in drafts]
            ids = np.zeros((self.num_slots, n_spec + 1), np.int32)
            ids[:, 0] = tok0
            for j, d in enumerate(drafts):
                ids[:, j + 1] = d
            toks, self.k_pages, self.v_pages = self._verify(
                self.params, jnp.asarray(ids), self.k_pages, self.v_pages,
                jtable, jnp.asarray(seq), jnp.asarray(g + 1),
            )
            toks = np.asarray(toks)  # host fetch syncs: span = device work
        t = now()
        if first:
            # the draft and the verify program together
            self._first_call_s["spec", n_spec] = t - t_c0
        emitted = accepted = 0
        for r in rows:
            i = r.slot
            m = 0
            while m < g[i] and int(drafts[m][i]) == int(toks[i, m]):
                m += 1
            accepted += m
            if tr is not None:
                tr.on_spec(r, t, dur_s=t - t_c0, drafted=int(g[i]),
                           accepted=m)
            # the verified tokens ARE the full model's greedy stream:
            # m matched drafts + the correction/bonus token
            for j in range(m + 1):
                self.sched.record_token(r, int(toks[i, j]), t)
                emitted += 1
                if r.status is Status.DONE:
                    done.append(r)
                    break
        drafted = int(g.sum())
        self._m_spec_cycles.inc()
        self._m_spec_draft.inc(drafted)
        self._m_spec_acc.inc(accepted)
        return emitted, drafted, accepted, rows

    def _trace_tick(self, active, t_step: float, t: float) -> None:
        """Per-request decode-tick fan-out into the tracer (one bounded
        event per active request). With tracing off (the default) the
        cost is this one attribute read + branch — the disabled-path
        guard test times exactly this call."""
        tr = self.tracer
        if tr is None:
            return
        dur = t - t_step
        for req in active:
            tr.on_decode_tick(req, t, dur_s=dur)

    def _stall(self, steps: int, wall_s: float) -> None:
        """No-decode-progress watchdog tripped: dump a black box (when a
        recorder is attached) and raise instead of livelocking."""
        queued = len(self.sched.queue)
        head = self.sched.queue[0] if queued else None
        reason = (
            f"no decode progress for {self.stall_patience} scheduler "
            f"iterations: {queued} queued, 0 active, "
            f"{self.pool.free_count}/{self.pool.capacity} pages free"
        )
        if head is not None:
            worst = self.pool.pages_for(self.sched._worst_tokens(head))
            reason += (
                f"; queue head uid={head.uid} needs {worst} pages worst-case"
            )
        where = ""
        if self.recorder is not None:
            trig = self.recorder.trigger_decode_stall(
                steps, reason,
                context={
                    "num_slots": self.num_slots,
                    "page_size": self.page_size,
                    "pages_free": self.pool.free_count,
                    "pages_total": self.pool.capacity,
                    "queued": queued,
                    "decode_steps": steps,
                    "wall_s": wall_s,
                },
            )
            if trig.dump_path:
                where = f" (black box: {trig.dump_path})"
        self._run = None   # the stall is terminal for this run
        raise RuntimeError(f"serving decode stall: {reason}{where}")

    # -- API ---------------------------------------------------------------

    def run(self, requests: Sequence[Request], now=time.perf_counter,
            tick_hook=None):
        """Serve ``requests`` to completion; returns
        (list[RequestOutput] in submit order, aggregate-metrics dict).
        ``tick_hook(engine, tick)``: optional per-iteration callback —
        the test/orchestration seam for mid-run interventions such as
        ``engine.sched.preempt`` (the evict/re-admit contract).

        A thin driver over the steppable run API (``start_run`` /
        ``tick_once`` / ``finish_run``): same order of operations as
        the pre-extraction monolith, token-identity test-pinned. The
        control plane (serving/control_plane/) uses the steppable form
        directly to interleave N replica engines in one host thread."""
        self.start_run(requests, now=now, tick_hook=tick_hook)
        try:
            while not self.sched.all_done():
                self.tick_once()
            return self.finish_run()
        except BaseException:
            # a raising tick_hook (or the stall watchdog) must leave
            # the engine reusable, exactly like the pre-extraction
            # monolith whose state lived in locals
            self.abort_run()
            raise

    def abort_run(self) -> None:
        """Discard a live steppable run (exception recovery): per-run
        accumulators drop, the engine becomes reusable. Requests still
        in the scheduler are NOT touched — callers owning them (the
        control plane's drain path) withdraw first. No-op when no run
        is in progress. The injected fault (if any) stays armed: a
        crashed replica stays crashed until :meth:`inject_fault`
        explicitly clears it (the rejoin path)."""
        self._run = None

    def inject_fault(self, kind: Optional[str]) -> None:
        """Arm (or clear, ``kind=None``) the deterministic failure
        seam: ``"crash"`` makes every subsequent :meth:`tick_once`
        raise :class:`ReplicaFault`; ``"wedge"`` makes it return
        without doing any work — alive on the wire, dead in fact. The
        chaos harness's ``replica_crash`` / ``replica_wedge`` kinds arm
        this; the control plane's health state machine is what must
        notice."""
        if kind not in (None, "crash", "wedge"):
            raise ValueError(
                f"unknown fault kind {kind!r} (expected None, 'crash' "
                f"or 'wedge')"
            )
        self._fault = kind

    def start_run(self, requests: Sequence[Request] = (),
                  now=time.perf_counter, tick_hook=None) -> None:
        """Begin a steppable run: reset the per-run accumulators, point
        the tracer at ``now``'s time domain, submit ``requests``. Drive
        with :meth:`tick_once` until ``sched.all_done()`` (or until an
        orchestrator decides to stop) and close with
        :meth:`finish_run`."""
        if self._run is not None:
            raise RuntimeError("a serving run is already in progress")
        if self.prefill_only and self._handoff_hook is None:
            raise RuntimeError(
                "a prefill_only engine needs a handoff hook before it "
                "runs (set_handoff_hook) — finished prefills have "
                "nowhere to go otherwise"
            )
        self._run_prefill_tokens = 0   # prompt tokens forwarded this run
        self._run_hit_tokens = 0       # prompt tokens served by the cache
        if self.kv_tier is not None:
            self.kv_tier.on_run_start()
        if self.tracer is not None:
            # one time domain: tracer-internal timestamps (e.g. preempt
            # hooks) must come from the same clock as t_submit/t_done
            self.tracer.set_clock(now)
        rs = _RunState(self, now, tick_hook)
        self._run = rs
        for r in requests:
            self.submit_request(r)
        rs.t0 = now()

    def submit_request(self, req: Request, reuse_uid: bool = False) -> None:
        """Mid-run ingress — the control-plane router's dispatch entry
        point (and the drain path's re-admission target: a migrated
        request keeps its first-submission timestamps, see
        ``Scheduler.submit``). ``reuse_uid=True`` keeps an existing
        cross-scheduler uid (the disagg transfer-failure fallback)."""
        rs = self._run
        if rs is None:
            raise RuntimeError("submit_request needs start_run first")
        self.sched.submit(req, rs.now(), reuse_uid=reuse_uid)
        self._m_requests.inc()
        self._m_queue.set(len(self.sched.queue))

    @property
    def run_in_progress(self) -> bool:
        return self._run is not None

    def last_tick(self) -> Optional[dict]:
        """The live run's newest tick by column (``TIMELINE_COLUMNS``),
        and ``step_uploads``, 1 where its decode step took its inputs
        from the host: what a driver reads after a ``tick_once`` for
        that tick's phases. None with no run in progress or before its
        first tick."""
        rs = self._run
        if rs is None or not rs.timeline:
            return None
        return dict(zip(TIMELINE_COLUMNS, rs.timeline[-1]),
                    step_uploads=rs.uploaded)

    def tick_once(self) -> bool:
        """One scheduler iteration: admit, shed, advance prefills, one
        decode step over the active slots, record tokens. Returns True
        when the tick made progress (admitted / prefilled / decoded /
        shed) — the idle-replica signal a control plane polls."""
        rs = self._run
        if rs is None:
            raise RuntimeError("tick_once needs start_run first")
        if self._fault == "crash":
            raise ReplicaFault(
                "injected replica crash (testing/chaos.py fault seam)"
            )
        if self._fault == "wedge":
            # no work, no state change — but the engine's OWN stall
            # watchdog still counts, so a standalone run() eventually
            # raises instead of livelocking; a control plane's health
            # heartbeat catches the wedge much earlier
            rs.stalled += 1
            if rs.stalled >= self.stall_patience:
                self._stall(rs.steps, rs.now() - rs.t0)
            return False
        reg = self.registry
        now = rs.now
        # host wall by phase (TICK_PHASES): every boundary closes the
        # phase before it, so the phases sum to the wall inside this
        # call; ``close_tick`` books them, sums and timeline row alike
        t_wall_ns = time.time_ns()
        t_start = t_mark = now()
        rs.tick += 1
        rs.uploaded = 0
        with span("serving.admit", registry=reg):
            if rs.tick_hook is not None:
                rs.tick_hook(self, rs.tick)
            if self.kv_tier is not None:
                # KV-tier pre-admission intercept: give the queue head one
                # shot at a cross-replica pull and/or a host-tier restore,
                # so the admission below sees the pages as ordinary cache
                # hits (restore) or resumes chunked prefill (pull)
                self.kv_tier.tick_intercept(now)
            admitted = self.sched.admit(now())
            shed_now = self.sched.drain_shed()
            if shed_now:
                # shedding IS the degraded-but-healthy mode: a counter
                # and terminal outputs, never a watchdog trigger — the
                # SLO shed-fraction target decides when it's too much
                self._m_shed.inc(len(shed_now))
                rs.done.extend(shed_now)
        t = now()
        d_admit = t - t_mark
        t_mark = t
        d_prefill = 0.0
        chunked_this_tick = 0
        if self._paged_prefill:
            for req in admitted:
                self._start_prefill(req, now)
            # one chunk per prefilling request per tick: the "mixed
            # step" — prefill advances below, decode advances after,
            # every tick
            for req in [r for r in self.sched.active()
                        if r.status is Status.PREFILL]:
                if req.status is not Status.PREFILL:
                    continue  # retracted by an earlier neighbor's
                    # lazy growth this very loop: back in the queue
                self._prefill_chunk_tick(req, now)
                t = now()
                d_prefill += t - t_mark
                t_mark = t
                rs.chunks += 1
                chunked_this_tick += 1
                if req.status is Status.DONE:
                    rs.done.append(req)
                if req.status is not Status.PREFILL:
                    rs.prefills += 1
        else:
            for req in admitted:
                self._prefill_request(req, now)
                t = now()
                d_prefill += t - t_mark
                t_mark = t
                rs.prefills += 1
                if req.status is Status.DONE:
                    rs.done.append(req)
        prefilled = chunked_this_tick if self._paged_prefill \
            else len(admitted)
        active = [r for r in self.sched.active()
                  if r.status is Status.DECODE]
        self._m_queue.set(len(self.sched.queue))
        if not active:
            # no admission, no prefill chunk AND no decode work:
            # nothing in this loop is time-dependent, so repeated
            # no-progress iterations mean the queue is stuck (e.g. a
            # reservation the pool can never cover). The watchdog
            # turns that silent livelock into a black-box dump + a
            # loud error.
            if admitted or chunked_this_tick or shed_now:
                # shedding is progress: the queue shrank
                rs.stalled = 0
            else:
                rs.stalled += 1
                if rs.stalled >= self.stall_patience:
                    self._stall(rs.steps, now() - rs.t0)
            rs.t_last_decode = None
            self._ledger_tick(rs)
            rs.close_tick(t_wall_ns, t_start, d_admit, d_prefill, 0.0, 0.0,
                          0.0, 0.0, now() - t_mark, 0, prefilled)
            # everything admitted finished at prefill
            return bool(admitted or chunked_this_tick or shed_now)
        rs.stalled = 0
        use_spec = (
            self.speculative is not None
            and any(r.max_new_tokens - len(r.generated) > 1
                    for r in active)
        )
        if use_spec:
            # a speculative cycle builds its own tables and interleaves
            # its draft and verify dispatches with their fetches: its
            # whole wall is booked under ``fetch``
            t_step = t_call = t_disp = now()
            emitted, drafted, accepted, active = self._spec_cycle(
                active, now, rs.done)
            rs.spec_drafted += drafted
            rs.spec_accepted += accepted
            t = now()
        else:
            with span("serving.prepare", registry=reg):
                for req in active:
                    if req.status is Status.DECODE:
                        self.sched.ensure_page(req)
                # lazy growth may have RETRACTED a neighbor (temporal
                # cache-ledger interference — see Scheduler.ensure_pages);
                # only still-decoding survivors join the step
                active = [r for r in active if r.status is Status.DECODE]
                rs.packed.fill(0)       # tokens, lengths, the table(s)
                for req in active:
                    rs.table[req.slot, :len(req.pages)] = req.pages
                    rs.seq_lens[req.slot] = req.cached_len
                    rs.tokens[req.slot] = req.generated[-1]
                if rs.window_table is not None:
                    for req in active:
                        rs.window_table[req.slot, :len(req.window_pages)] = \
                            req.window_pages
                # the device holds the last step's own next inputs; they
                # are this step's unless a row was admitted, ended, was
                # retracted or took a page, or rows moved outside the
                # step (a speculative cycle, a chunk, a transfer)
                rs.uploaded = int(rs.carry is None
                                  or not np.array_equal(rs.packed, rs.held))
            first = self._note_program("step", 0)
            t_step = t_call = now()
            with span("serving.decode_step", registry=reg):
                # what changed made ready for the device, then the call,
                # which transfers it: the two halves of ``dispatch``
                with span("upload", registry=reg):
                    if rs.uploaded:
                        # ONE buffer, and a copy of it: on the CPU the
                        # device may share the host's buffer, which the
                        # next ``prepare`` refills
                        rs.carry = self._place(rs.packed.copy())
                        np.copyto(rs.held, rs.packed)
                        rs.step_uploads += 1
                        self._m_step_uploads.inc()
                        t_call = now()
                with span("dispatch", registry=reg):
                    nxt, *pool, counters, self.state, rs.carry = self._step(
                        self.params, rs.carry, *self._pool(),
                        *self._state_arg(),
                    )
                    self._adopt(pool)
                t_disp = now()
                # the host waiting on the device: what it waits for is
                # this step and whatever was queued before it (a page
                # write dispatched by this tick's prefill)
                with span("fetch", registry=reg):
                    nxt = np.asarray(nxt)  # host fetch syncs: span = work
                    # the step's counters, out of the jitted step beside
                    # its tokens ({} for a model whose blocks bring none)
                    counters = {k: np.asarray(v)
                                for k, v in counters.items()}
            t = now()
            rs.advance_held(nxt)
            if first:
                self._first_call_s["step", 0] = t - t_step
            emitted = len(active)
        with span("serving.record", registry=reg):
            if not use_spec:
                self._trace_tick(active, t_step, t)
            if rs.t_last_decode is not None:
                gap = t_step - rs.t_last_decode
                self._m_gap.observe(gap)
                rs.max_gap = max(rs.max_gap, gap)
            rs.t_last_decode = t
            rs.steps += 1
            rs.step_time += t - t_step
            if not use_spec:
                # the program's own arithmetic on the lengths it was sent
                furthest = int(rs.seq_lens.max())
                by_pos = walked_chunks(
                    furthest, self._walk_keys) * self._walk_keys
                # over summaries the global table is walked as far as
                # the furthest row sees any
                walked = by_pos if self._chunk_size is None else walked_rows(
                    summaries_seen(furthest, self.model.window,
                                   self._chunk_size, self.model.window_rule),
                    self._walk_keys) * self._walk_keys
                rs.keys_walked += min(walked, self._reach_keys)
                rs.keys_reached += self._reach_keys
                if self._ring_keys:
                    # a window layer's read walks its ring, no further
                    # (a block window's only as far as a row stands in)
                    reach = int(ring_reach(rs.seq_lens, self.model.window,
                                           self.model.window_rule))
                    ring_walked = min(
                        walked_chunks(reach, self._walk_keys)
                        * self._walk_keys, self._ring_keys)
                    rs.window_keys_walked += ring_walked
                    rs.window_keys_reached += self._ring_keys
                    if self.model.window_rule == SLIDING:
                        pos = rs.seq_lens[rs.seq_lens > 0]
                        rs.ring_rows += pos.size
                        rs.ring_rows_wrapped += int(
                            (pos >= self.model.window).sum())
                        rs.ring_keys_needed += int(np.minimum(
                            pos + 1, self.model.window).sum())
                        rs.ring_keys_gathered += pos.size * ring_walked
                if counters:
                    self._note_counters(rs, counters)
                if self.state:
                    # the walk over the state bank, as the program made
                    # it: every trip's rows up to the highest live slot
                    trips = walked_state_rows(
                        max(r.slot for r in active), self._state_rows)
                    rs.state_rows_updated += trips * self._state_rows
                    rs.state_rows_live += len(active)
                    rs.state_peak_slots = max(rs.state_peak_slots,
                                              len(active))
                    self._m_state_slots.set(len(active))
            slot_occ = len(active) / self.num_slots
            used = self.pool.used_by_kind()
            page_occ = used[GLOBAL] / self.pool.capacity
            rs.occ_slots += slot_occ
            rs.occ_pages += page_occ
            for kind, n in used.items():
                rs.peak_pages[kind] = max(rs.peak_pages[kind], n)
                self._m_pages_kind[kind].set(n)
            if self.pool.window is not None:
                rs.occ_window += used[WINDOW] / self.pool.window.capacity
            # per-token decode latency: a plain step emits one token per
            # active slot; a speculative cycle may emit several — both
            # normalize to seconds per token per slot
            self._m_tok_lat.observe(
                (t - t_step) * len(active) / max(emitted, 1))
            self._m_steps.inc()
            self._m_tokens.inc(emitted)
            self._m_active.set(len(active))
            self._m_slot_occ.set(slot_occ)
            self._m_page_occ.set(page_occ)
            if reg.enabled:
                # fragmentation() sorts the free list — too heavy for
                # the disabled path's one-branch cost contract
                self._m_frag.set(self.pool.fragmentation())
                if self.prefix_cache is not None:
                    # refresh per step, not just on insert: pressure
                    # eviction happens exactly when dashboards look
                    self._m_cached.set(self.prefix_cache.cached_pages)
                    self._m_evictable.set(
                        self.prefix_cache.evictable_count()
                    )
            # the occupancy TIME SERIES the end-of-run averages flatten
            reg.event("serving.step", step=rs.steps, active=len(active),
                      queue_depth=len(self.sched.queue), dur_s=t - t_step,
                      slot_occupancy=slot_occ, page_occupancy=page_occ,
                      tokens=emitted)
            if self.recorder is not None:
                self.recorder.observe_serving_step(
                    rs.steps, active=len(active),
                    queue_depth=len(self.sched.queue), dur_s=t - t_step,
                    tokens=emitted,
                )
            if not use_spec:
                for req in active:
                    self.sched.record_token(req, int(nxt[req.slot]), t)
                    if req.status is Status.DONE:
                        rs.done.append(req)
            self._ledger_tick(rs)
        rs.close_tick(t_wall_ns, t_start, d_admit, d_prefill,
                      t_step - t_mark, t_call - t_step, t_disp - t_call,
                      t - t_disp, now() - t, len(active), prefilled)
        return True

    def _build_output(self, r: Request) -> RequestOutput:
        """One finished request -> (RequestOutput, per-request dict),
        appended to the run's accumulated rows."""
        rs = self._run
        if r.finish_reason == "shed":
            # terminal but never served: the whole life was queue
            # (or requeue) wait; TTFT/decode are None (matching the
            # per_request dict) and the latency histograms are NOT
            # observed — a shed row must not flatter (or poison)
            # the served tail
            rs.shed_count += 1
            e2e = r.t_done - r.t_submit
            out = RequestOutput(
                uid=r.uid, prompt=np.asarray(r.prompt),
                generated=np.asarray(r.generated, np.int64),
                finish_reason="shed",
                queue_latency_s=e2e,
                ttft_s=None,
                decode_tokens_per_s=None,
                e2e_latency_s=e2e,
                tenant=r.tenant,
            )
            row = {
                "uid": r.uid,
                "tenant": r.tenant,
                "prompt_len": r.prompt_len,
                "new_tokens": len(r.generated),
                "finish_reason": "shed",
                "queue_latency_s": round(e2e, 6),
                "ttft_s": None,
                "e2e_latency_s": round(e2e, 6),
                "decode_tokens_per_s": None,
            }
        else:
            decode_s = max(r.t_done - r.t_admit, 1e-9)
            e2e = r.t_done - r.t_submit
            self._m_e2e.observe(e2e)
            out = RequestOutput(
                uid=r.uid, prompt=np.asarray(r.prompt),
                generated=np.asarray(r.generated, np.int64),
                finish_reason=r.finish_reason,
                queue_latency_s=r.t_admit - r.t_submit,
                ttft_s=r.t_first_token - r.t_submit,
                decode_tokens_per_s=len(r.generated) / decode_s,
                e2e_latency_s=e2e,
                tenant=r.tenant,
            )
            row = {
                "uid": r.uid,
                "tenant": r.tenant,
                "prompt_len": r.prompt_len,
                "new_tokens": len(r.generated),
                "finish_reason": r.finish_reason,
                "queue_latency_s": round(r.t_admit - r.t_submit, 6),
                "ttft_s": round(r.t_first_token - r.t_submit, 6),
                "e2e_latency_s": round(e2e, 6),
                "decode_tokens_per_s": round(len(r.generated) / decode_s, 2),
            }
        rs.outputs.append(out)
        rs.per_request.append(row)
        rs.generated_total += len(out.generated)
        return out

    def take_finished(self) -> List[Tuple[Request, RequestOutput]]:
        """Pop requests finished since the last call as
        (request, output) pairs — the control plane's incremental
        collection point, so completions can be attributed to tenants
        and replicas while the run is still going. :meth:`finish_run`
        still reports EVERY request in its outputs/metrics regardless
        (rows accumulate run-wide)."""
        rs = self._run
        if rs is None:
            raise RuntimeError("take_finished needs start_run first")
        taken = [(r, self._build_output(r))
                 for r in sorted(rs.done, key=lambda r: r.uid)]
        rs.done = []
        return taken

    def finish_run(self):
        """Close the run: build outputs for everything not already
        taken, set the wall-rate gauge, return (outputs in uid order,
        aggregate-metrics dict). The metrics cover the WHOLE run
        including requests handed out through :meth:`take_finished`."""
        rs = self._run
        if rs is None:
            raise RuntimeError("finish_run needs start_run first")
        now = rs.now
        wall = max(now() - rs.t0, 1e-9)
        # telemetry tokens/s from the COUNTER delta: cross-checks the
        # per-step instrumentation against the legacy aggregate below
        # (tests pin agreement within 1%)
        self._m_tps.set((self._m_tokens.value - rs.tok0) / wall)
        for r in sorted(rs.done, key=lambda r: r.uid):
            self._build_output(r)
        rs.done = []
        order = sorted(range(len(rs.outputs)),
                       key=lambda i: rs.outputs[i].uid)
        outputs = [rs.outputs[i] for i in order]
        per_request = [rs.per_request[i] for i in order]
        metrics = {
            "wall_time_s": round(wall, 6),
            "decode_steps": rs.steps,
            # of them, the steps that took their inputs from the host (the
            # others ran on what the step before left on the device)
            "step_uploads": rs.step_uploads,
            # summed decode-step wall time: generated / this = the
            # decode-POOL rate (prefill stalls excluded) — DisaggEngine's
            # "prefill off the critical path" meter
            "decode_step_time_s": round(rs.step_time, 6),
            "prefills": rs.prefills,
            "generated_tokens": rs.generated_total,
            "decode_tokens_per_s": round(rs.generated_total / wall, 2),
            "slot_occupancy": round(rs.occ_slots / rs.steps, 4)
            if rs.steps else 0.0,
            "page_occupancy": round(rs.occ_pages / rs.steps, 4)
            if rs.steps else 0.0,
            "requests": per_request,
            # tokens actually forwarded through prefill this run — the
            # FLOP meter every engine flavor reports on the same basis
            # (prompt tokens only, never decode; cache hits subtract)
            "prefill_tokens": self._run_prefill_tokens,
            # deadline-shed terminal count (graceful degradation)
            "shed_requests": rs.shed_count,
            # host wall inside tick_once by phase (TICK_PHASES)
            "ticks": rs.tick,
            "tick_phase_s": {k: round(v, 6) for k, v in rs.phase_s.items()},
            # the same clock tick by tick (TIMELINE_COLUMNS): the newest
            # TIMELINE_CAPACITY rows, and how many older ones went
            "tick_timeline": {
                "columns": list(TIMELINE_COLUMNS),
                "rows": [list(row) for row in rs.timeline],
                "dropped": rs.tick - len(rs.timeline),
            },
            # engine-lifetime facts, the same in every run's metrics:
            # the wall of __init__ and of each program's first call
            "setup": {
                "build_s": round(self._build_s, 6),
                "first_call_s": {
                    f"{family}/{width}": round(sec, 6)
                    for (family, width), sec in self._first_call_s.items()
                    if sec is not None
                },
            },
        }
        # key columns the plain decode steps walked over those their
        # tables reach: how far the read's walk engaged
        share = rs.keys_walked / rs.keys_reached if rs.keys_reached else 0.0
        metrics["decode_key_share"] = round(share, 6)
        self._m_key_share.set(share)
        metrics["pages_by_kind"] = {
            kind: {"capacity": self.pool.of(kind).capacity,
                   "peak_in_use": rs.peak_pages[kind]}
            for kind in self.pool.kinds}
        if self.pool.window is not None:
            steps = max(rs.steps, 1)
            metrics["pages_by_kind"][GLOBAL]["occupancy"] = round(
                rs.occ_pages / steps, 4)
            metrics["pages_by_kind"][WINDOW]["occupancy"] = round(
                rs.occ_window / steps, 4)
            recycled = self.pool.recycled - rs.recycled0
            metrics["window_pages_recycled"] = recycled
            self._m_recycled.inc(recycled)
            # a window layer's walk over its ring, and over what a global
            # layer's walk of the same steps visited
            metrics["decode_key_share_by_kind"] = {
                GLOBAL: metrics["decode_key_share"],
                WINDOW: round(rs.window_keys_walked
                              / max(rs.window_keys_reached, 1), 6)}
            metrics["window_key_share"] = round(
                rs.window_keys_walked / max(rs.keys_walked, 1), 6)
            if self.model.window_rule == SLIDING:
                # both ring states in one step: the live rows whose ring
                # has wrapped, and the key columns the live rows' windows
                # hold over those the walk gathered for them (a row walks
                # as far as the furthest row of its step)
                metrics["window"] = {
                    "wrapped_row_share": round(
                        rs.ring_rows_wrapped / max(rs.ring_rows, 1), 6),
                    "rows_useful_share": round(
                        rs.ring_keys_needed
                        / max(rs.ring_keys_gathered, 1), 6)}
                self._m_ring_wrapped.set(
                    metrics["window"]["wrapped_row_share"])
                self._m_ring_useful.set(
                    metrics["window"]["rows_useful_share"])
        if rs.experts_touched:
            n = len(rs.experts_touched)
            metrics["experts"] = {
                # experts a decode step touched, summed over its sparse
                # layers, step by step; the share of those held; rows on
                # the busiest expert over the mean, a layer a step
                "touched_by_step": rs.experts_touched,
                "touched_share": round(
                    sum(rs.experts_touched) / (n * self._experts_held), 6),
                "rows_max_over_mean": round(
                    rs.expert_skew / (n * self._sparse_layers), 4),
                "rows_routed": rs.rows_routed,
                "held_a_step": self._experts_held,
            }
            if rs.zero_pick_share is not None:
                # picks that fell on zero-compute experts, mean over the
                # steps as ``touched_share`` is
                metrics["experts"]["zero_pick_share"] = round(
                    rs.zero_pick_share / n, 6)
        if rs.summary_rows is not None:
            got = rs.summary_rows
            needed = got["window_rows_needed"] + got["summary_rows_needed"]
            gathered = (got["window_rows_gathered"]
                        + got["summary_rows_gathered"])
            # one attention over a ring and its summaries: rows the
            # softmax needed over rows its two walks gathered, and the
            # summaries' share of the keys it needed
            metrics["eva"] = dict(
                got,
                rows_useful_share=round(needed / max(gathered, 1), 6),
                summary_key_share=round(
                    got["summary_rows_needed"] / max(needed, 1), 6))
            self._m_rows_useful.set(metrics["eva"]["rows_useful_share"])
            self._m_summary_keys.set(metrics["eva"]["summary_key_share"])
        if self.state:
            metrics["state"] = {
                "slots": self.num_slots,
                "bytes_per_slot": self._state_bytes() // self.num_slots,
                # the most slots holding a request in a decode step, and
                # the mean share of them that did
                "peak_slots_in_use": rs.state_peak_slots,
                "occupancy": round(rs.occ_slots / rs.steps, 4)
                if rs.steps else 0.0,
                # rows whose state the decode steps read and wrote (their
                # walks reach the highest live slot), rows alive in them
                "rows_updated": rs.state_rows_updated,
                "rows_live": rs.state_rows_live,
                # prefill results put in a slot (admissions, re-admissions)
                "writes": rs.state_writes,
            }
        if self._paged_prefill:
            metrics["prefill_chunks"] = rs.chunks
            metrics["max_decode_gap_s"] = round(rs.max_gap, 6)
        if self.prefix_cache is not None:
            hit = self._run_hit_tokens
            fwd = self._run_prefill_tokens
            metrics["prefix_cache"] = {
                "hit_tokens": hit,
                "prefill_tokens": fwd,
                "hit_rate": round(hit / (hit + fwd), 4) if hit + fwd else 0.0,
                "cached_pages": self.prefix_cache.cached_pages,
                "shared_pages_now": self.pool.shared_count,
            }
        if self.kv_tier is not None and (
                self.host_tier is not None
                or self.kv_tier.pulls or self.kv_tier.fallbacks):
            metrics["kv_tier"] = dict(self.kv_tier.run_stats())
            if self.host_tier is not None:
                metrics["kv_tier"]["host"] = self.host_tier.stats()
        if self.memledger is not None:
            # peak per-class occupancy + fragmentation + leak/audit
            # verdicts: the run's memory trajectory in one block
            metrics["memory"] = self.memledger.run_summary()
        if self.speculative is not None:
            metrics["speculative"] = {
                "draft_tokens": rs.spec_drafted,
                "accepted_tokens": rs.spec_accepted,
                "acceptance_rate": round(
                    rs.spec_accepted / rs.spec_drafted, 4)
                if rs.spec_drafted else 0.0,
            }
        self._sentinel_observe(rs, wall)
        self._run = None
        return outputs, metrics

    def _sentinel_observe(self, rs, wall: float) -> None:
        """Per-run perf-sentinel hook: with no sentinel attached (the
        default) the cost is this one attribute read + branch — the
        disabled-path guard test times exactly this call. With one, the
        run's throughput and its decode-step vs idle split feed the
        rolling baseline; a regression dumps a perf_regression black
        box naming the component ("idle time 3.2x baseline")."""
        s = self.sentinel
        if s is None:
            return
        if rs.steps == 0:
            # a run with no decode steps — everything deadline-shed, or
            # a prefill-only/handoff run — is the DEGRADED-BUT-HEALTHY
            # mode (docs/robustness.md), not a perf sample: tokens/s=0
            # and idle=wall would fire a spurious perf_regression
            # against a per-step baseline it isn't comparable to
            return
        steps = rs.steps
        s.observe(
            {
                "decode_step_s": rs.step_time / steps,
                # host-side time between decode steps (queue handling,
                # prefill waits, stalls) — the component a host stall
                # or scheduler regression inflates
                "idle_s": max(wall - rs.step_time, 0.0) / steps,
            },
            step=rs.steps,
            tokens_per_s=rs.generated_total / wall if wall > 0 else 0.0,
            context={"num_slots": self.num_slots,
                     "decode_steps": rs.steps,
                     "wall_s": wall},
        )


def make_skewed_replay(*, n_requests: int, n_prefixes: int, prefix_len: int,
                       suffix_lens: Sequence[int], max_new: int,
                       vocab: int, seed: int = 0, zipf_a: float = 1.2,
                       n_tenants: Optional[int] = None,
                       tenant_zipf_a: float = 1.2,
                       working_set_factor: Optional[float] = None,
                       num_pages: Optional[int] = None,
                       page_size: Optional[int] = None):
    """Synthetic heavy-traffic replay with SKEWED prompt reuse: each
    request's prompt is one of ``n_prefixes`` shared prefixes (drawn
    Zipf-style — rank r with weight 1/r^a, the few-hot-system-prompts
    shape production traffic has) followed by a private random suffix.
    Returns a list of (prompt ndarray, max_new) pairs; every call with
    the same seed replays the identical trace, so cache-on and
    cache-off arms measure the same workload.

    ``n_tenants``: multi-tenant flavor — each request additionally
    draws a tenant name ("t0".."tN") from a SECOND independent Zipf
    (``tenant_zipf_a``), the one-hot-customer shape the control plane's
    fairness ledger exists for, and the rows become (prompt, max_new,
    tenant) TRIPLES. Default None keeps the legacy pair shape, so
    every existing caller unpacks unchanged.

    ``working_set_factor``: size the distinct-prefix corpus RELATIVE to
    a pool's HBM capacity instead of passing ``n_prefixes`` absolutely
    — factor 2.0 against (``num_pages``, ``page_size``) makes the
    prefix working set twice what the pool can hold, the guaranteed-
    overflow replay the KV-tier tests need (every factor > 1 forces
    LRU eviction; the tier turns those evictions into restores instead
    of recomputes). Requires ``num_pages`` and ``page_size``;
    overrides ``n_prefixes``."""
    if working_set_factor is not None:
        if num_pages is None or page_size is None:
            raise ValueError(
                "working_set_factor needs num_pages and page_size — it "
                "sizes the prefix corpus against the pool's capacity")
        if working_set_factor <= 0:
            raise ValueError(
                f"working_set_factor must be > 0, got {working_set_factor}")
        # pool capacity is num_pages - 1 (the scheduler's slack page)
        cap_tokens = (num_pages - 1) * page_size
        n_prefixes = max(1, -(-int(working_set_factor * cap_tokens)
                              // max(prefix_len, 1)))
    rng = np.random.RandomState(seed)
    prefixes = [rng.randint(1, vocab, (prefix_len,)) for _ in range(n_prefixes)]
    weights = np.array([1.0 / (r + 1) ** zipf_a for r in range(n_prefixes)])
    weights /= weights.sum()
    t_weights = None
    if n_tenants is not None:
        if n_tenants < 1:
            raise ValueError(f"n_tenants must be >= 1, got {n_tenants}")
        t_weights = np.array(
            [1.0 / (r + 1) ** tenant_zipf_a for r in range(n_tenants)]
        )
        t_weights /= t_weights.sum()
    specs = []
    for _ in range(n_requests):
        pfx = prefixes[rng.choice(n_prefixes, p=weights)]
        sfx = rng.randint(1, vocab, (int(rng.choice(suffix_lens)),))
        prompt = np.concatenate([pfx, sfx])
        if t_weights is None:
            specs.append((prompt, max_new))
        else:
            tenant = f"t{int(rng.choice(n_tenants, p=t_weights))}"
            specs.append((prompt, max_new, tenant))
    return specs
