"""The plain reference and the comparison that decides ``correct``.

The reference is exact brute-force k-nearest-neighbour search in float64
over the benchmark's own corpus: it imports nothing of the program and takes
nothing the program made. The served answers (the ``k`` ids and distances
that each request got back) are held to four numbers:

* ``unanswered``: requests due in the window that got no answer a minute
  after it closed (limit 0);
* ``bad_ids``: answers with an id outside the corpus or repeated within one
  request's list (limit 0);
* ``dist_gap_max``: the widest gap between a served distance and the
  float64 distance from that request's own query to the id served, over
  every answer, in units of ``|q| |x|`` (the scale of a distance's rounding
  error; 1 for unit vectors). It catches answers handed to the wrong
  request, altered ids, and distances computed in a lower precision;
* ``recall_at_10``: recall@k of every answered request against the exact
  top-k, held to a floor (``recall_at_10_min``). It catches a traversal
  that finds worse neighbours (fewer rounds, a shorter list, a broken PQ
  lookup), whose answers the exact rerank still gives true distances.

The corpus is fixed per configuration and every seed offers the same set of
queries (``bench/run.py``), so the recall of a sound run is the same number
on every seed; the floor sits between it and the traversal fault's reading
(``bench/control.py``).
"""
from __future__ import annotations

import numpy as np

CHECKS = ("unanswered", "bad_ids", "dist_gap_max", "recall_at_10")
# a check passes when its value is at most its limit, except these: at least
FLOORS = {"recall_at_10": "recall_at_10_min"}


def _normalize(x: np.ndarray) -> np.ndarray:
    return x / np.maximum(np.linalg.norm(x, axis=-1, keepdims=True), 1e-300)


def exact_knn(queries: np.ndarray, base: np.ndarray, k: int, metric: str,
              chunk: int = 256) -> np.ndarray:
    """(Q, k) ids of the exact nearest neighbours, nearest first, in
    float64. Distances are squared L2 (``l2``) or minus the cosine
    (``angular``)."""
    b = np.asarray(base, np.float64)
    if metric == "angular":
        b = _normalize(b)
    b2 = (b * b).sum(-1)
    out = np.empty((len(queries), k), np.int64)
    for s in range(0, len(queries), chunk):
        q = np.asarray(queries[s:s + chunk], np.float64)
        if metric == "angular":
            d = -(_normalize(q) @ b.T)
        elif metric == "l2":
            d = b2[None, :] - 2.0 * (q @ b.T)    # + |q|^2, same for a row
        else:
            raise ValueError(f"unknown metric {metric!r}")
        idx = np.argpartition(d, k, axis=1)[:, :k]
        row = np.take_along_axis(d, idx, axis=1)
        out[s:s + chunk] = np.take_along_axis(
            idx, np.argsort(row, axis=1, kind="stable"), axis=1)
    return out


def distance_gaps(queries: np.ndarray, base: np.ndarray, ids: np.ndarray,
                  dists: np.ndarray, metric: str) -> np.ndarray:
    """(Q, k) gap between each served distance and the float64 distance of
    the served id from its request's query, over ``|q| |x|``; ``inf`` where
    the id is not in the corpus."""
    ids = np.asarray(ids, np.int64)
    valid = (ids >= 0) & (ids < len(base))
    q = np.asarray(queries, np.float64)[:, None, :]
    x = np.asarray(base, np.float64)[np.where(valid, ids, 0)]
    if metric == "angular":
        q, x = _normalize(q), _normalize(x)
        ref = -(q * x).sum(-1)
    else:
        ref = ((x - q) ** 2).sum(-1)
    scale = np.linalg.norm(q, axis=-1) * np.linalg.norm(x, axis=-1)
    gap = np.abs(np.asarray(dists, np.float64) - ref) / np.maximum(scale,
                                                                  1e-300)
    return np.where(valid, gap, np.inf)


def bad_id_rows(ids: np.ndarray, n: int) -> np.ndarray:
    """(Q,) bool: a row holds an id outside ``[0, n)`` or an id twice."""
    ids = np.asarray(ids, np.int64)
    outside = ((ids < 0) | (ids >= n)).any(axis=1)
    s = np.sort(ids, axis=1)
    repeated = (s[:, 1:] == s[:, :-1]).any(axis=1)
    return outside | repeated


def compare(queries: np.ndarray, base: np.ndarray, ids: np.ndarray,
            dists: np.ndarray, answered: np.ndarray, metric: str,
            truth: np.ndarray | None = None) -> dict:
    """The readings of one run. ``queries`` (R, dim) are the requests due in
    the window, ``ids``/``dists`` (R, k) their answers in the corpus's own
    id space, ``answered`` (R,) bool, ``truth`` (R, k) their exact top-k
    (computed here when not given). Returns the four numbers and the
    per-request failure masks (before limits)."""
    answered = np.asarray(answered, bool)
    got = np.flatnonzero(answered)
    k = np.asarray(ids).shape[1]
    if truth is None:
        truth = exact_knn(queries, base, k, metric)
    bad = bad_id_rows(ids[got], len(base))
    gaps = distance_gaps(queries[got], base, ids[got], dists[got], metric)
    row_gap = gaps.max(axis=1) if len(got) else np.zeros(0)
    row_recall = row_recalls(ids[got], truth[got], k)
    return {
        "unanswered": int((~answered).sum()),
        "bad_ids": int(bad.sum()),
        "dist_gap_max": float(row_gap.max()) if len(got) else 0.0,
        "recall_at_10": float(row_recall.mean()) if len(got) else 0.0,
        "_row_gap": row_gap,
        "_row_bad": bad,
        "_row_recall": row_recall,
        "_rows": got,
    }


def verdict(readings: dict, limits: dict) -> tuple[bool, int, dict]:
    """(correct, failed requests, {name: {"value", "limit"[, "at_least"]}}).
    A request fails with a bad id or a gap over the limit; when the recall
    is under its floor, so does every request whose own recall is."""
    limits = dict(limits, unanswered=0, bad_ids=0)
    shown, ok = {}, {}
    for name in CHECKS:
        floor = FLOORS.get(name)
        limit = limits[floor] if floor else limits[name]
        shown[name] = {"value": readings[name], "limit": limit}
        if floor:
            shown[name]["at_least"] = True
            ok[name] = readings[name] >= limit
        else:
            ok[name] = readings[name] <= limit
    wrong = readings["_row_bad"] | (readings["_row_gap"]
                                    > limits["dist_gap_max"])
    if not ok["recall_at_10"]:
        wrong |= readings["_row_recall"] < limits["recall_at_10_min"]
    failed = readings["unanswered"] + int(wrong.sum())
    return all(ok.values()), failed, shown


def row_recalls(ids: np.ndarray, truth: np.ndarray, k: int) -> np.ndarray:
    """(R,) |served top-k ∩ exact top-k| / k of each row."""
    hits = [len(set(a[:k].tolist()) & set(b[:k].tolist()))
            for a, b in zip(np.asarray(ids), np.asarray(truth))]
    return np.asarray(hits, np.float64) / k


def recall_at_k(ids: np.ndarray, truth: np.ndarray, k: int) -> float:
    """Mean over rows of |served top-k ∩ exact top-k| / k."""
    r = row_recalls(ids, truth, k)
    return float(r.mean()) if r.size else float("nan")
