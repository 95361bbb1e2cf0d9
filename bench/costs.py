"""What one traversal needs to read, counted from its own counters.

Per query, Algorithm 1 reads one adjacency row of ``R`` int32 ids per
expansion (``n_hops``), one ``M``-byte PQ code per PQ distance (``n_pq``)
and one ``D``-float32 raw vector per accurate distance (``n_acc``). The
count is the same whatever implements the search, so a share of the
roofline built on it compares implementations.
"""
from __future__ import annotations

import numpy as np


def query_bytes(n_hops, n_pq, n_acc, config: dict) -> np.ndarray:
    """Bytes each query's traversal needs (arrays of per-query counters)."""
    r = int(config["index"]["max_degree"])
    m = int(config["index"]["pq_subvectors"])
    d = int(config["dim"])
    return (np.asarray(n_hops, np.float64) * r * 4
            + np.asarray(n_pq, np.float64) * m
            + np.asarray(n_acc, np.float64) * d * 4)


def real_lanes(entries):
    """(entry, number of real lanes) for each dispatch that served any."""
    for e in entries:
        n = e.get("n")
        if n:
            yield e, int(n)


def window_bytes(entries, config: dict) -> float:
    """Bytes the window's real queries needed, over every dispatch."""
    return float(sum(query_bytes(e["n_hops"][:n], e["n_pq"][:n],
                                 e["n_acc"][:n], config).sum()
                     for e, n in real_lanes(entries)))


def window_queries(entries) -> int:
    return sum(n for _, n in real_lanes(entries))


def module_seconds(trace: dict | None, prefix: str) -> float:
    """Device seconds of the compiled modules whose name starts with
    ``prefix`` (0 without a trace)."""
    if not trace:
        return 0.0
    return sum(s for name, s in trace["module_s"].items()
               if name.startswith(prefix))
