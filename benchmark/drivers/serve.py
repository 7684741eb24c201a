"""Driver ``serve``: a default-constructed ``ServingEngine`` at the
workload's sizes, under an open loop: arrivals on a schedule, latency
from when a request was DUE; arrivals stop at the window's end and a
bounded drain (``drain_s``) gives the last ones their first tokens.

A request still decoding when the drain ends is ``cut_off``: attempted,
not failed, its tokens so far served and timed. The longest outputs
outlast window and drain together, so ``failed`` is kept for what the
system refused: a request shed, or one with no first token by then.
``itl_p95_ms`` takes every gap of the window's requests, the drain's
too. A traced run traces the same window and drain.

The engine gets sizes and no path switch. After the window the engine
is freed and the plain reference scores a seeded sample of the served
requests; see ``check`` in the workload's file.
"""
from __future__ import annotations

import gc
import json
import os
import time

import numpy as np

from benchmark import harness, program_bloom, traffic, weights
from benchmark.reference import bloom_ref

PAD_TO = 128        # the reference compiles one program per padded length
# upper edges (ms) of the gap histogram on the ``serve`` line
GAP_EDGES_MS = (4, 5, 6, 7, 8, 9, 10, 11, 12, 13, 14, 16, 18, 20, 25, 30, 40,
                60, 100, 1000)


def _warm_up(engine, Request, buckets, vocab):
    """One request per prompt bucket: the engine compiles one prefill
    and one page-write program per page count, and the decode step."""
    reqs = [Request(prompt=np.full((b,), 1 + i % (vocab - 1), np.int32),
                    max_new_tokens=2) for i, b in enumerate(buckets)]
    engine.run(reqs)
    if engine.pool.used_count:
        raise SystemExit("benchmark: the pool did not drain after warm-up")


class Book:
    """Per-request token timestamps, kept by the driver: the engine has
    no per-token clock without its tracer."""

    def __init__(self):
        self.watch = []          # (request, planned) not yet finished
        self.times = {}          # id(request) -> [t of each token]
        self.stalled = {}        # id(request) -> [the gap BEFORE each token
        #                          held another request's prefill]
        self.rows = []           # (request, planned, times) finished

    def add(self, req, planned):
        self.watch.append((req, planned))
        self.times[id(req)] = []
        self.stalled[id(req)] = []

    def after_tick(self, t, done_status):
        """Stamp tokens that appeared in this tick. A tick in which some
        request got its first token ran that request's prefill before
        its decode step: every other request's gap ending here held it."""
        admitted = any(req.generated and not self.times[id(req)]
                       for req, _ in self.watch)
        still = []
        for req, planned in self.watch:
            ts = self.times[id(req)]
            n = len(req.generated)
            if n > len(ts):
                held = admitted and bool(ts)
                if not ts:
                    ts.append(req.t_first_token)
                    self.stalled[id(req)].append(False)
                self.stalled[id(req)].extend([held] * (n - len(ts)))
                ts.extend([t] * (n - len(ts)))
            if req.status is done_status:
                self.rows.append((req, planned, ts))
            else:
                still.append((req, planned))
        self.watch = still


def _phase_clock(engine):
    """The engine's running ``tick_phase_s`` accumulators, the dict that
    ``finish_run()`` reports at the end, or None on a program that keeps
    none: read after every tick, their differences are one tick's phases."""
    return getattr(getattr(engine, "_run", None), "phase_s", None)


def _phase_facts(names, per_tick):
    """p50, p95 and the longest, in ms, of every phase over the window's
    ticks; of ``prefill`` over the ticks that held one."""
    out = {}
    for i, name in enumerate(names):
        xs = [1e3 * row[i] for row in per_tick
              if name != "prefill" or row[i] > 0.0]
        if xs:
            out[name] = {"p50": harness.percentile(xs, 50),
                         "p95": harness.percentile(xs, 95), "max": max(xs)}
    return out


def run(ctx):
    t_run = time.perf_counter()
    import jax
    import jax.numpy as jnp

    from pipegoose_tpu.serving import Request, ServingEngine
    from pipegoose_tpu.serving.scheduler import Status

    t_imported = time.perf_counter()
    w = ctx.workload
    sizes = ctx.config["sizes"]
    vocab = sizes["vocab_size"]
    dtype = jnp.dtype(ctx.config["dtype"])
    spec = dict(w["traffic"], page_size=w["engine"]["page_size"])
    key = weights.seed_key(ctx.seed)

    params = jax.block_until_ready(jax.jit(lambda k: program_bloom.to_tree(
        weights.make(k, sizes, dtype)))(key))
    t_weights = time.perf_counter()
    engine = ServingEngine(params, program_bloom.make_config(ctx.config),
                           **w["engine"])
    del params
    t_engine = time.perf_counter()
    _warm_up(engine, Request, spec["prompt_buckets"], vocab)
    t_warm = time.perf_counter()
    seconds = ctx.seconds
    plan = traffic.plan(spec, vocab, ctx.seed,
                        traffic.n_requests(spec, seconds))
    print("setup " + json.dumps({
        "chip_to_driver_s": t_run - ctx.t_chip,
        "import_s": t_imported - t_run, "weights_s": t_weights - t_imported,
        "engine_build_s": t_engine - t_weights,
        "warm_up_s": t_warm - t_engine,
        "plan_s": time.perf_counter() - t_warm,
        "lowerings_and_compiles": ctx.watch.count}), flush=True)

    book, ticks, late, tick_phases = Book(), [], [], []
    nxt = 0

    def submit(t_now):
        nonlocal nxt
        p = plan[nxt]
        nxt += 1
        req = Request(prompt=p.prompt, max_new_tokens=p.new_tokens)
        with harness.annotate("serve.submit"):
            engine.submit_request(req)
        book.add(req, p)
        late.append(t_now - p.due_s)

    def tick(t0):
        before = tuple(clock.values()) if clock is not None else None
        with harness.annotate("serve.tick"):
            engine.tick_once()
        t = time.perf_counter()
        if before is not None:
            tick_phases.append(tuple(
                b - a for a, b in zip(before, clock.values())))
        live = sum(r.cached_len for r in engine.sched.active()
                   if r.status is Status.DECODE)
        ticks.append((t - t0, live))
        book.after_tick(t, Status.DONE)

    compiles = ctx.watch.count
    load_avg = os.getloadavg()
    with harness.traced_window(ctx):
        engine.start_run([], now=time.perf_counter)
        clock = _phase_clock(engine)
        t0 = time.perf_counter()
        while True:
            now = time.perf_counter() - t0
            if now >= seconds:
                break
            while nxt < len(plan) and plan[nxt].due_s <= now:
                submit(now)
            if engine.sched.all_done():
                # idle: wait for the next arrival, never past it
                nap = (plan[nxt].due_s if nxt < len(plan) else seconds) - now
                with harness.annotate("serve.idle"):
                    time.sleep(max(0.0, min(nap, 0.0005)))
                continue
            tick(t0)
        t_end = time.perf_counter() - t0
        # arrivals have stopped; what is in flight gets its latencies
        while not engine.sched.all_done() \
                and time.perf_counter() - t0 < seconds + w["drain_s"]:
            tick(t0)
        drained_at = time.perf_counter() - t0
    harness.refuse_compiles(ctx, compiles)
    phase_names = tuple(clock or ())
    _, run_metrics = engine.finish_run()
    peak = harness.memory_peak_bytes(ctx.devices)

    # finished: every token served. cut: still decoding when the bounded
    # drain ended — served so far, not failed. failed: shed, or no first
    # token by then.
    done = [(r, p, ts) for r, p, ts in book.rows
            if r.finish_reason == "length"]
    cut = [(r, p, book.times[id(r)]) for r, p in book.watch
           if book.times[id(r)]]
    waiting = len(book.watch) - len(cut)
    failed = (len(book.rows) - len(done)) + waiting
    attempted = len(book.rows) + len(cut) + waiting
    ttft = [1e3 * (r.t_first_token - t0 - p.due_s) for r, p, _ in done + cut]
    pairs = [(a, b) for _, _, ts in done + cut for a, b in zip(ts, ts[1:])]
    gaps = [1e3 * (b - a) for a, b in pairs]
    in_window = [1e3 * (b - a) for a, b in pairs if b - t0 <= seconds]
    held = [h for r, _, _ in done + cut for h in book.stalled[id(r)][1:]]
    stalled = [g for g, h in zip(gaps, held) if h]
    plain = [g for g, h in zip(gaps, held) if not h]
    e2e = {"itl_p95_ms": harness.percentile(gaps, 95)}
    print("serve " + json.dumps({
        "planned": len(plan), "submitted": nxt, "finished": len(done),
        "cut_off": len(cut), "failed": failed,
        "window_end_s": t_end, "drained_at_s": drained_at,
        "generator_late_ms_p50": 1e3 * harness.percentile(late, 50),
        "generator_late_ms_max": 1e3 * max(late),
        "ttft_p50_ms": harness.percentile(ttft, 50) if ttft else None,
        "ttft_p90_ms": harness.percentile(ttft, 90) if ttft else None,
        "itl_gaps": len(gaps), "itl_gaps_in_window": len(in_window),
        "itl_p50_ms": harness.percentile(gaps, 50),
        "itl_p95_in_window_ms": harness.percentile(in_window, 95),
        # a gap "holds a prefill" when its tick admitted another request
        "itl_gaps_holding_prefill_pct": 100.0 * len(stalled) / len(gaps),
        "itl_p50_holding_prefill_ms":
            harness.percentile(stalled, 50) if stalled else None,
        "itl_p50_plain_ms": harness.percentile(plain, 50) if plain else None,
        "itl_p95_plain_ms": harness.percentile(plain, 95) if plain else None,
        "itl_hist_upper_ms_count": _histogram(gaps),
        # host from device in a run that reads high: the engine's own
        # phase clock, tick by tick (prefill over the ticks that held one)
        "tick_phase_ms": _phase_facts(phase_names, tick_phases),
        "cpu_count": os.cpu_count(), "load_avg_at_start": list(load_avg),
        "slot_occupancy_pct": 100.0 * run_metrics["slot_occupancy"],
        "page_occupancy_pct": 100.0 * run_metrics["page_occupancy"],
        "longest_ticks_start_s_ms": _longest(ticks),
        "decode_steps": run_metrics["decode_steps"],
        "prefills": run_metrics["prefills"]}), flush=True)

    # free the engine, then let the reference score a sample
    sample = _sample(done or cut, ctx.seed, w["check"]["sample_requests"])
    del engine, book
    gc.collect()
    ctx.sample = sample
    worst, n_tokens = score(ctx, sample, picks="served")
    ctx.checks.add("served_logit_gap_max", worst,
                   w["check"]["served_logit_gap_max"],
                   note=f"{n_tokens} served tokens of {len(sample)} requests")

    return harness.Result(
        end_to_end=e2e, attempted=attempted, failed=failed,
        t_window_start=t0, memory_peak_bytes=peak,
        extra={"cut_off": len(cut)},
        facts={"ticks": ticks, "run_metrics": run_metrics, "sizes": sizes,
               "peaks": ctx.peaks, "dtype": ctx.config["dtype"]})


def _histogram(gaps):
    """[upper edge in ms, gaps under it and not under the edge before]
    for every edge that holds any: where p95 sits among the gaps."""
    counts = np.histogram(gaps, bins=(0,) + GAP_EDGES_MS + (np.inf,))[0]
    edges = GAP_EDGES_MS + (None,)
    return [[e, int(c)] for e, c in zip(edges, counts) if c]


def _longest(ticks, n=3):
    """The ``n`` longest stretches between the ends of successive ticks,
    as (start offset in s, length in ms): where a stall sits, if one."""
    ends = [t for t, _ in ticks]
    spans = sorted(((b - a, a) for a, b in zip(ends, ends[1:])),
                   reverse=True)[:n]
    return [[round(a, 3), round(1e3 * d, 1)] for d, a in spans]


def control(ctx):
    """The reference in the program's place, one precision below the
    configuration's: at every generated position of the same sample,
    the token that the fp8 forward puts first, scored by the float32
    reference. Needs ``run`` first."""
    worst, n_tokens = score(ctx, ctx.sample, picks="lower", precision="fp8")
    checks = harness.Checks()
    checks.add("served_logit_gap_max", worst,
               ctx.workload["check"]["served_logit_gap_max"],
               note=f"{n_tokens} tokens of {len(ctx.sample)} requests")
    return checks


def _sample(done, seed, n):
    """``n`` finished requests drawn from the seed, the longest among
    them: (prompt + generated, prompt length). (Where a window finished
    none, the requests it cut off stand in with what they were served.)"""
    if not done:
        return []
    seqs = [(np.concatenate([np.asarray(r.prompt, np.int32),
                             np.asarray(r.generated, np.int32)]),
             len(r.prompt)) for r, _, _ in done]
    longest = max(range(len(seqs)), key=lambda i: len(seqs[i][0]))
    rng = np.random.default_rng([int(seed), 7])
    rest = [i for i in rng.permutation(len(seqs)) if i != longest]
    return [seqs[i] for i in [longest] + rest[:n - 1]]


def score(ctx, sample, picks="served", precision="float32"):
    """The widest gap, over every generated position of the sample, by
    which a token's float32 reference logit lies below the reference's
    best. ``picks`` "served": the tokens the program served. "lower":
    the tokens the reference itself puts first at ``precision`` — the
    control. Returns (gap, tokens compared)."""
    import jax
    import jax.numpy as jnp

    if not sample:
        return float("nan"), 0
    sizes = ctx.config["sizes"]
    dtype = jnp.dtype(ctx.config["dtype"])
    w0 = jax.jit(lambda k: {n: v.astype(jnp.float32) for n, v in
                            weights.make(k, sizes, dtype).items()})(
        weights.seed_key(ctx.seed))
    fn = jax.jit(lambda w, t, p, prec: bloom_ref.next_token_scores(
        w, t, p, sizes, prec), static_argnums=3)
    worst, n_tokens = 0.0, 0
    for tokens, n_prompt in sample:
        n = len(tokens)
        padded = np.zeros((-(-n // PAD_TO) * PAD_TO,), np.int32)
        padded[:n] = tokens
        ids = jnp.asarray(padded)
        follow = jnp.roll(ids, -1)
        if picks == "lower":
            _, follow = fn(w0, ids, follow, precision)
        gap, _ = fn(w0, ids, follow, "float32")
        gen = np.asarray(gap)[n_prompt - 1:n - 1]
        worst = max(worst, float(gen.max()))
        n_tokens += len(gen)
    return worst, n_tokens
