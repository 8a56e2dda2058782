"""The one generator every serving traffic file is read by.

A serving traffic file gives an open-loop arrival rate and the distributions
of prompt and output lengths (lognormal, with prompts rounded up to a closed
set of buckets, outputs clipped).  The generator follows
``benchmarks/bench_serving.make_trace`` (Poisson arrivals, lognormal lengths,
bucketed prompts) with one change for steadiness: the arrival gaps and the
lengths are the evenly spaced quantiles of their distributions, the same set
for every seed, and the seed only shuffles their order and draws the prompt
tokens.  Each segment of the stream (the lead before the measured window,
the window, the arrivals after it) holds its own whole set, so every seed
offers the window the same work in another order.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np

__all__ = ["Arrival", "serve_arrivals"]


@dataclass
class Arrival:
    rid: int
    at: float          # seconds after the stream starts
    prompt: list[int]
    max_new: int


def _lognormal_quantiles(median: float, sigma: float, n: int) -> np.ndarray:
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    return np.exp(math.log(median) + sigma * z)


def _segment(spec: dict, rng, start: float, length: float):
    """(arrival times, prompt lengths, output lengths) of one segment: the
    quantiles of exponential gaps, scaled to fill it exactly, and of the
    lengths, each shuffled by ``rng``."""
    rate = float(spec["rate_rps"])
    n = max(int(math.ceil(length * rate)), 1)
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u)
    gaps *= length / gaps.sum()
    p = spec["prompt"]
    buckets = sorted(int(b) for b in p["buckets"])
    raw = _lognormal_quantiles(p["median"], p["sigma"], n)
    prompts = np.array([next((b for b in buckets if b >= x), buckets[-1]) for x in raw])
    o = spec["output"]
    outs = np.clip(np.round(_lognormal_quantiles(o["median"], o["sigma"], n)), o["min"], o["max"])
    gaps, prompts, outs = rng.permutation(gaps), rng.permutation(prompts), rng.permutation(outs)
    at = start + np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return at, prompts, outs


def serve_arrivals(spec: dict, seed: int, segments_s: list[float], vocab: int) -> list[Arrival]:
    """Arrivals at ``spec['rate_rps']`` over consecutive segments of
    ``segments_s`` seconds (lead, window, tail), each a whole quantile set."""
    rng = np.random.default_rng(seed)
    at, prompts, outs, start = [], [], [], 0.0
    for length in segments_s:
        a, p, o = _segment(spec, rng, start, float(length))
        at.append(a)
        prompts.append(p)
        outs.append(o)
        start += float(length)
    at, prompts, outs = np.concatenate(at), np.concatenate(prompts), np.concatenate(outs)
    return [
        Arrival(i, float(at[i]), [int(x) for x in rng.integers(0, vocab, int(prompts[i]))],
                int(outs[i]))
        for i in range(len(at))
    ]
