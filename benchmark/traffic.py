"""One general traffic generator, driven by a workload's data file.

Every seed gets the SAME multiset of prompt lengths, output lengths and
inter-arrival gaps — the quantiles of the declared distributions — with
other token ids, and in another order unless the mix fixes one. So runs
differ by content and at most by order, never by the amount of work.

    "traffic": {
      "rate_per_s": 4.0,                 # Poisson arrivals, open loop
      "order_seed": 5105,                # optional: ONE order for every seed
      "prompt": {"dist": "lognormal", "median": 192, "sigma": 0.9,
                 "min": 32, "max": 1024},
      "output": {"dist": "lognormal", ...},
      "prompt_buckets": [64, 128, ...]   # snapped up, less 0..page_size-1
    }

(``page_size`` comes from the workload's ``engine``.) Without
``order_seed`` each seed permutes lengths and gaps afresh. With it every
seed replays the ONE schedule that ``order_seed`` draws (which request
arrives when, how long its prompt and its output are) and fills it with
its own token ids. Under load a tail latency follows which requests
overlap which (how many live rows each admission stalls, which long
outputs fall into the drain): a fresh permutation a seed, and the one
order entered at another request a seed, both moved ``itl_p95_ms`` by
3-5% on one commit, more than a 10% bound can resolve (PERF.md, PR 34).
A closed loop, a
bursty arrival process or another length distribution is a branch here
and a key there, added by the benchmark PR that brings the first cell
to use it.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np


@dataclass
class Planned:
    due_s: float            # offset from the window's start
    prompt: np.ndarray      # int32 token ids
    new_tokens: int


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def draw_lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` values at the distribution's evenly spaced quantiles."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    z = np.asarray([NormalDist().inv_cdf(float(x)) for x in _quantiles(n)])
    vals = np.clip(spec["median"] * np.exp(spec["sigma"] * z),
                   spec["min"], spec["max"])
    return np.rint(vals).astype(int)


def snap_to_bucket(length: int, buckets) -> int:
    for b in sorted(buckets):
        if length <= b:
            return b
    raise ValueError(f"length {length} is above the largest bucket")


def exponential_gaps(rate_per_s: float, n: int) -> np.ndarray:
    """``n`` Poisson inter-arrival gaps at their quantiles, rescaled so
    they sum to exactly n / rate."""
    gaps = -np.log1p(-_quantiles(n)) / rate_per_s
    return gaps * (n / rate_per_s) / gaps.sum()


def plan(spec: dict, vocab_size: int, seed: int, n: int) -> list:
    """``n`` requests for this seed: the first is due at 0 and each next
    one a permuted gap later, so all ``n`` fall inside n / rate seconds."""
    rng = np.random.default_rng(int(seed))
    order = (np.random.default_rng(int(spec["order_seed"]))
             if "order_seed" in spec else rng)
    ps = spec["page_size"]
    buckets = spec["prompt_buckets"]
    prompts = order.permutation(draw_lengths(spec["prompt"], n))
    outputs = order.permutation(draw_lengths(spec["output"], n))
    trims = order.permutation(np.arange(n) % ps)
    gaps = order.permutation(exponential_gaps(spec["rate_per_s"], n))
    due = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
    out = []
    for i in range(n):
        length = snap_to_bucket(int(prompts[i]), buckets) - int(trims[i])
        ids = rng.integers(1, vocab_size, size=length, dtype=np.int64)
        out.append(Planned(float(due[i]), ids.astype(np.int32),
                           int(outputs[i])))
    return out


def n_requests(spec: dict, seconds: float) -> int:
    """How many requests a window of ``seconds`` plans: the arrivals due
    in it."""
    return max(1, math.floor(spec["rate_per_s"] * seconds))


def token_batch(vocab_size: int, seed: int, step: int, batch: int,
                seq: int) -> np.ndarray:
    """Training rows for one step: every row differs, every step differs."""
    rng = np.random.default_rng([int(seed), int(step)])
    return rng.integers(0, vocab_size, size=(batch, seq),
                        dtype=np.int64).astype(np.int32)
