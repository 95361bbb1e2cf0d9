"""Open-loop arrival schedules, drawn from the seed.

The two processes of ``benchmarks/arrivals.py``, kept here so that the
yardstick does not move with the program's own benchmark suite: Poisson
arrivals, and bursts of ``burst_size`` with Poisson burst starts whose
members land within ``spread`` of the mean burst period after the start.
Here each is conditioned on its count: a run offers exactly
``round(rate * seconds)`` requests inside its window (for a Poisson process
the arrival times are then sorted uniforms), so every seed offers the same
amount of work in another order and at other instants.

All schedules are offsets in seconds from the start of the window, sorted.
"""
from __future__ import annotations

import numpy as np


def _conditioned_starts(rng, n: int, span: float) -> np.ndarray:
    """``n`` Poisson arrival times conditioned to fall in ``[0, span)``:
    cumulative exponential gaps normalised by their total (n + 1 gaps), which
    are distributed as ``n`` sorted uniforms."""
    gaps = rng.exponential(1.0, size=n + 1)
    return np.cumsum(gaps)[:n] / gaps.sum() * span


def count(rate_qps: float, seconds: float) -> int:
    """How many requests a window of ``seconds`` at ``rate_qps`` offers."""
    return max(int(round(rate_qps * seconds)), 1)


def window_arrivals(traffic: dict, rate_qps: float, seconds: float,
                    rng: np.random.Generator) -> np.ndarray:
    """The schedule of one run: ``round(rate_qps * seconds)`` requests in
    ``[0, seconds)`` following ``traffic["arrivals"]`` (``poisson`` or
    ``burst`` with ``burst_size`` and ``spread``)."""
    n = count(rate_qps, seconds)
    kind = traffic["arrivals"]
    if kind == "poisson":
        return _conditioned_starts(rng, n, seconds)
    if kind == "burst":
        size = int(traffic["burst_size"])
        period = size / rate_qps
        jitter = float(traffic["spread"]) * period
        n_bursts = -(-n // size)
        starts = _conditioned_starts(rng, n_bursts, max(seconds - jitter, 0.0))
        t = np.repeat(starts, size)[:n] + rng.uniform(0.0, jitter, size=n)
        return np.sort(t)
    raise ValueError(f"unknown arrival process {kind!r} "
                     "(expected 'poisson' or 'burst')")
