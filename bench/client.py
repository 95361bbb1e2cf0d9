"""The open-loop client: submits each request when it is due, steps the
engine in between, and logs every request on one clock.

Latency runs from the instant a request was due to the engine's ``t_done``
for it, so a stall in ``step()`` that delays later submissions is counted
against those requests (no coordinated omission); how late the client
itself submitted is logged as generator lag. The loop is single-threaded,
as the engine is.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, List, Optional, Sequence

import numpy as np


@dataclasses.dataclass
class ClientLog:
    """Per-request record of one window; times in seconds from its start."""
    seconds: float
    due: np.ndarray                  # (R,) when each request was due
    submitted: np.ndarray            # (R,) when it was submitted (nan: never)
    done: np.ndarray                 # (R,) engine t_done (nan: unanswered)
    ids: np.ndarray                  # (R, k) int64, -1 where unanswered
    dists: np.ndarray                # (R, k) float64
    t0: float = 0.0                  # perf_counter() at the window's start

    @property
    def answered(self) -> np.ndarray:
        return ~np.isnan(self.done)

    @property
    def latency_ms(self) -> np.ndarray:
        """Due-to-done latency of every answered request."""
        a = self.answered
        return (self.done[a] - self.due[a]) * 1e3

    @property
    def gen_lag_ms(self) -> np.ndarray:
        s = ~np.isnan(self.submitted)
        return (self.submitted[s] - self.due[s]) * 1e3

    def completed_in_window(self) -> int:
        return int((self.answered & (self.done <= self.seconds)).sum())


def percentile(values, q: float) -> float:
    """The q-th percentile (numpy's linear interpolation); nan if empty."""
    v = np.asarray(values, np.float64)
    return float(np.percentile(v, q)) if v.size else float("nan")


def qps(log: ClientLog) -> float:
    """Requests completed inside the window over the window's length."""
    return log.completed_in_window() / log.seconds


def _idle(engine) -> bool:
    return not engine.queue and not (engine.continuous and engine.inflight())


# a step shorter than this only polled the engine; it is not logged
MIN_SPAN_S = 50e-6


def drive(engine, queries: np.ndarray, due: np.ndarray, seconds: float,
          k: int, grace: float = 60.0,
          spans: Optional[List[tuple]] = None,
          on_step: Optional[Callable[[list], None]] = None,
          filters: Optional[Sequence] = None) -> ClientLog:
    """Run one window: request ``i`` (query ``queries[i]``, and filter
    ``filters[i]`` where filters are given) is due at ``due[i]`` seconds
    after the start. After the window closes the loop keeps stepping,
    unforced, until every request is answered or ``grace`` seconds have
    passed.

    ``spans``, if given, receives ``(name, start, end)`` in window seconds
    for every submit, sleep and step that did work (``bench.submit``,
    ``bench.wait``, ``bench.step``): what the host was doing, for the trace
    reduction. ``on_step`` is called with each step's completed requests."""
    log_span = spans.append if spans is not None else None
    n = len(due)
    submitted = np.full(n, np.nan)
    done = np.full(n, np.nan)
    ids = np.full((n, k), -1, np.int64)
    dists = np.full((n, k), np.nan)
    row_of = {}
    i = 0
    clock = time.perf_counter
    t0 = clock()
    stop = t0 + seconds + grace
    while True:
        now = clock()
        if i < n and due[i] <= now - t0:
            while i < n and due[i] <= clock() - t0:
                if filters is None:
                    rid = engine.submit(queries[i])
                else:
                    rid = engine.submit(queries[i], filter=filters[i])
                row_of[rid] = i
                submitted[i] = clock() - t0
                i += 1
            if log_span is not None:
                log_span(("bench.submit", now - t0, clock() - t0))
        a = clock()
        completed = engine.step()
        b = clock()
        if log_span is not None and b - a > MIN_SPAN_S:
            log_span(("bench.step", a - t0, b - t0))
        if on_step is not None:
            on_step(completed)
        for r in completed:
            row = row_of.pop(r.rid)
            done[row] = r.t_done - t0
            ids[row] = np.asarray(r.ids)
            dists[row] = np.asarray(r.dists)
        if i == n and not row_of:
            break
        if clock() > stop:
            break
        if i < n and _idle(engine):
            wait = due[i] - (clock() - t0)
            if wait > 0:
                a = clock()
                time.sleep(min(wait, 0.002))
                if log_span is not None:
                    log_span(("bench.wait", a - t0, clock() - t0))
    return ClientLog(seconds=seconds, due=np.asarray(due, np.float64),
                     submitted=submitted, done=done, ids=ids, dists=dists,
                     t0=t0)
