"""The one generator of serving traffic, driven by a mix's data file.

A mix file (``traffic/<mix>.json``) holds parameters only:

    {"arrivals": {"rate_per_s": 2.0,
                  "burst": {"every_s": 10, "for_s": 2, "factor": 4}},
     "prompt_tokens": {"median": 1020, "sigma": 0.6,
                       "buckets": [256, 512, 1024, 1792]},
     "output_tokens": {"median": 129, "sigma": 0.7, "min": 16, "max": 256},
     "sizes_seed": 1}

Arrivals are an open loop: a Poisson process at ``rate_per_s`` on average,
optionally modulated by bursts (the rate times ``factor`` for ``for_s``
seconds out of every ``every_s``, with the mean rate kept). Lengths are
lognormal with the given median and sigma; prompts are rounded to the
nearest bucket, outputs clipped to [min, max]. Other keys of the file
(its source, what was assumed) are for the reader.

Every seed offers the same work in another order: ``sizes_seed`` draws
one set of gaps between arrivals and one set of (prompt, output) lengths
for the window, and the run's seed shuffles each set and draws what the
prompts say. So the number of requests and of tokens is the same in every
run, and which requests meet in a queue is the seed's.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass
class Arrival:
    due_s: float                  # seconds after the window opens
    tokens: np.ndarray            # (P,) int32 prompt
    max_new_tokens: int


def _lognormal(rng, spec: dict, n: int) -> np.ndarray:
    return np.exp(np.log(spec["median"]) + spec["sigma"]
                  * rng.standard_normal(n))


def prompt_lengths(rng, spec: dict, n: int) -> np.ndarray:
    buckets = np.asarray(sorted(spec["buckets"]))
    raw = _lognormal(rng, spec, n)
    return buckets[np.argmin(np.abs(raw[:, None] - buckets[None]), axis=1)]


def output_lengths(rng, spec: dict, n: int) -> np.ndarray:
    raw = np.ceil(_lognormal(rng, spec, n))
    return np.clip(raw, spec["min"], spec["max"]).astype(np.int64)


def _rate_integral(t: np.ndarray, rate: float, burst: dict | None):
    """Expected arrivals by time ``t`` (the integrated rate)."""
    if not burst:
        return rate * t
    every, span, factor = burst["every_s"], burst["for_s"], burst["factor"]
    # the base rate b keeps the mean: b * (every + (factor - 1) * span)
    # arrivals per period of ``every`` seconds equals rate * every
    base = rate * every / (every + (factor - 1) * span)
    periods, phase = np.divmod(t, every)
    in_burst = np.minimum(phase, span)
    per_period = base * (every + (factor - 1) * span)
    return (periods * per_period + base * phase
            + base * (factor - 1) * in_burst)


def _invert(targets: np.ndarray, rate, burst, seconds: float) -> np.ndarray:
    """Times at which the integrated rate reaches ``targets`` (bisection:
    the integral is increasing and piecewise linear)."""
    lo = np.zeros_like(targets)
    hi = np.full_like(targets, seconds)
    for _ in range(60):
        mid = (lo + hi) / 2
        below = _rate_integral(mid, rate, burst) < targets
        lo = np.where(below, mid, lo)
        hi = np.where(below, hi, mid)
    return hi


def arrivals(mix: dict, seconds: float, seed: int, vocab: int) -> list[Arrival]:
    """The window's requests, in order of due time."""
    arr = mix["arrivals"]
    rate, burst = float(arr["rate_per_s"]), arr.get("burst")
    fixed = np.random.default_rng(mix["sizes_seed"])
    total = float(_rate_integral(np.asarray(seconds, float), rate, burst))
    # unit-rate gaps in integrated-rate space, up to the window's total
    gaps = []
    acc = 0.0
    while True:
        g = float(fixed.exponential(1.0))
        if acc + g >= total:
            break
        acc += g
        gaps.append(g)
    n = len(gaps)
    plens = prompt_lengths(fixed, mix["prompt_tokens"], n)
    olens = output_lengths(fixed, mix["output_tokens"], n)
    rng = np.random.default_rng(seed)
    due = _invert(np.cumsum(rng.permutation(gaps)), rate, burst, seconds)
    order = rng.permutation(n)
    plens, olens = plens[order], olens[order]
    return [Arrival(float(due[i]),
                    rng.integers(1, vocab, int(plens[i]), dtype=np.int32),
                    int(olens[i]))
            for i in range(n)]
