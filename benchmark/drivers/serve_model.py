"""Driver ``serve_model``: driver ``serve``'s procedure for any model
whose configuration file names its own adapter, weights and reference
(``program.adapter`` / ``.weights`` / ``.reference``, files beside
``program_bloom.py``), so that another architecture adds files and no
driver.

As ``serve``: a default-constructed ``ServingEngine`` at the workload's
sizes under an open loop (arrivals on a schedule, latency from when a
request was DUE, a bounded drain, ``cut_off`` for what still decodes
then); a warm-up request a prompt bucket; the engine freed; the plain
reference scores a seeded sample of the served requests, the longest
among them. ``Book``, ``_warm_up``, ``_sample``, the phase clock and the
histogram are ``drivers/serve.py``'s own, loaded from that file.

What differs: the adapter says what ``ServingEngine`` is given; the
weights are made a leaf at a time (11 GB do not pass through one call);
the reference takes them in the configuration's dtype and widens what it
uses; a sequence is padded to one of the few lengths ``check.pad_to``
lists (a program a length); the ``serve`` line carries page occupancy by
cache kind and the rows an expert, and ``facts`` what the cell's readers
read: the keys live in every tick by cache kind, the engine's counters.
"""
from __future__ import annotations

import gc
import json
import os
import time

import numpy as np

from benchmark import harness, traffic

_serve = harness.load_module(os.path.join(harness.HERE, "drivers", "serve.py"))


def _parts(config: dict) -> tuple:
    """(adapter, weights, reference) modules, by the names the
    configuration file gives."""
    names = config["program"]
    return tuple(
        harness.load_module(os.path.join(harness.HERE, names[k] + ".py"))
        for k in ("adapter", "weights", "reference"))


def run(ctx):
    t_run = time.perf_counter()
    import jax
    import jax.numpy as jnp

    adapter, weights, _ = _parts(ctx.config)
    # first of all: a program without this model fails here, at once
    cfg = adapter.make_config(ctx.config)

    from pipegoose_tpu.serving import Request, ServingEngine
    from pipegoose_tpu.serving.scheduler import Status

    t_imported = time.perf_counter()
    w = ctx.workload
    sizes = adapter.sizes(ctx.config)
    vocab = sizes["vocab_size"]
    dtype = jnp.dtype(ctx.config["dtype"])
    window = sizes.get("sliding_window") or 0
    spec = dict(w["traffic"], page_size=w["engine"]["page_size"])

    params = jax.block_until_ready(adapter.to_tree(
        weights.make(weights.seed_key(ctx.seed), sizes, dtype), ctx.config))
    weight_bytes = sum(x.size * x.dtype.itemsize
                       for x in jax.tree_util.tree_leaves(params))
    t_weights = time.perf_counter()
    engine = ServingEngine(params, cfg, **w["engine"])
    del params
    t_engine = time.perf_counter()
    _serve._warm_up(engine, Request, spec["prompt_buckets"], vocab)
    t_warm = time.perf_counter()
    seconds = ctx.seconds
    plan = traffic.plan(spec, vocab, ctx.seed,
                        traffic.n_requests(spec, seconds))
    print("setup " + json.dumps({
        "chip_to_driver_s": t_run - ctx.t_chip,
        "import_s": t_imported - t_run, "weights_s": t_weights - t_imported,
        "weights_gb": weight_bytes / 1e9,
        "engine_build_s": t_engine - t_weights,
        "warm_up_s": t_warm - t_engine,
        "plan_s": time.perf_counter() - t_warm,
        "lowerings_and_compiles": ctx.watch.count}), flush=True)

    book, ticks, late, tick_phases = _serve.Book(), [], [], []
    live_window = []
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
        rows = [r.cached_len for r in engine.sched.active()
                if r.status is Status.DECODE]
        # keys a global layer holds of the rows alive, and a window layer
        ticks.append((t - t0, sum(rows)))
        live_window.append(sum(min(n, window) for n in rows))
        book.after_tick(t, Status.DONE)

    compiles = ctx.watch.count
    load_avg = os.getloadavg()
    with harness.traced_window(ctx):
        engine.start_run([], now=time.perf_counter)
        clock = _serve._phase_clock(engine)
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
    held = [h for r, _, _ in done + cut for h in book.stalled[id(r)][1:]]
    stalled = [g for g, h in zip(gaps, held) if h]
    plain = [g for g, h in zip(gaps, held) if not h]
    e2e = {"itl_p95_ms": harness.percentile(gaps, 95)}
    experts = run_metrics.get("experts") or {}
    print("serve " + json.dumps({
        "planned": len(plan), "submitted": nxt, "finished": len(done),
        "cut_off": len(cut), "failed": failed,
        "window_end_s": t_end, "drained_at_s": drained_at,
        "generator_late_ms_p50": 1e3 * harness.percentile(late, 50),
        "generator_late_ms_max": 1e3 * max(late),
        "ttft_p50_ms": harness.percentile(ttft, 50) if ttft else None,
        "ttft_p90_ms": harness.percentile(ttft, 90) if ttft else None,
        "itl_gaps": len(gaps),
        "itl_p50_ms": harness.percentile(gaps, 50),
        "itl_gaps_holding_prefill_pct": 100.0 * len(stalled) / len(gaps),
        "itl_p50_holding_prefill_ms":
            harness.percentile(stalled, 50) if stalled else None,
        "itl_p95_holding_prefill_ms":
            harness.percentile(stalled, 95) if stalled else None,
        "itl_p50_plain_ms": harness.percentile(plain, 50) if plain else None,
        "itl_p95_plain_ms": harness.percentile(plain, 95) if plain else None,
        "itl_hist_upper_ms_count": _serve._histogram(gaps),
        "tick_phase_ms": _serve._phase_facts(phase_names, tick_phases),
        "cpu_count": os.cpu_count(), "load_avg_at_start": list(load_avg),
        "slot_occupancy_pct": 100.0 * run_metrics["slot_occupancy"],
        "page_occupancy_pct": 100.0 * run_metrics["page_occupancy"],
        # by cache kind: capacity, the most in use, mean occupancy
        "pages_by_kind": run_metrics.get("pages_by_kind"),
        "window_pages_recycled": run_metrics.get("window_pages_recycled"),
        "window_key_share": run_metrics.get("window_key_share"),
        "experts_touched_share": experts.get("touched_share"),
        "expert_rows_max_over_mean": experts.get("rows_max_over_mean"),
        "longest_ticks_start_s_ms": _serve._longest(ticks),
        "decode_steps": run_metrics["decode_steps"],
        "prefills": run_metrics["prefills"]}), flush=True)

    # free the engine, then let the reference score a sample
    sample = _serve._sample(done or cut, ctx.seed,
                            w["check"]["sample_requests"])
    del engine, book
    gc.collect()
    ctx.sample = sample
    t_ref = time.perf_counter()
    worst, n_tokens = score(ctx, sample, picks="served")
    print(f"reference: {time.perf_counter() - t_ref:.1f} s, {n_tokens} "
          f"tokens of {len(sample)} requests, longest "
          f"{max((len(t) for t, _ in sample), default=0)}", flush=True)
    ctx.checks.add("served_logit_gap_max", worst,
                   w["check"]["served_logit_gap_max"],
                   note=f"{n_tokens} served tokens of {len(sample)} requests")

    return harness.Result(
        end_to_end=e2e, attempted=attempted, failed=failed,
        t_window_start=t0, memory_peak_bytes=peak,
        extra={"cut_off": len(cut)},
        facts={"ticks": ticks, "live_window": live_window,
               "run_metrics": run_metrics, "sizes": sizes,
               "peaks": ctx.peaks, "dtype": ctx.config["dtype"]})


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


def score(ctx, sample, picks="served", precision="float32"):
    """The widest gap, over every generated position of the sample, by
    which a token's float32 reference logit lies below the reference's
    best. ``picks`` "served": the tokens the program served. "lower":
    the tokens the reference itself puts first at ``precision``: the
    control. Returns (gap, tokens compared)."""
    import jax
    import jax.numpy as jnp

    if not sample:
        return float("nan"), 0
    adapter, weights, reference = _parts(ctx.config)
    sizes = adapter.sizes(ctx.config)
    dtype = jnp.dtype(ctx.config["dtype"])
    # the seed's weights again, in the configuration's dtype: the
    # reference widens what it uses, a layer and an expert at a time
    w0 = weights.make(weights.seed_key(ctx.seed), sizes, dtype)
    fn = jax.jit(lambda w, t, p, prec: reference.next_token_scores(
        w, t, p, sizes, prec), static_argnums=3)
    lengths = sorted(ctx.workload["check"]["pad_to"])
    worst, n_tokens = 0.0, 0
    for tokens, n_prompt in sample:
        n = len(tokens)
        padded = np.zeros((next(x for x in lengths if x >= n),), np.int32)
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
