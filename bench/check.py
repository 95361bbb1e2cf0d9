"""The plain reference and the comparison that decides ``correct``.

The reference is exact brute-force k-nearest-neighbour search in float64
over the benchmark's own corpus: it imports nothing of the program and takes
nothing the program made. The served answers (the ``k`` ids and distances
that each request got back) are held to four numbers:

* ``unanswered``: requests due in the window that got no answer a minute
  after it closed (limit 0);
* ``bad_ids``: answers with an id outside the corpus, repeated within one
  request's list, or, where the request carries a predicate, an id whose
  tag set lacks one of its tags (limit 0);
* ``dist_gap_max``: the widest gap between a served distance and the
  float64 distance from that request's own query to the id served, over
  every answer, in units of ``|q| |x|`` (the scale of a distance's rounding
  error; 1 for unit vectors). It catches answers handed to the wrong
  request, altered ids, and distances computed in a lower precision;
* ``recall_at_10``: recall@k of every answered request against the exact
  top-k, held to a floor (``recall_at_10_min``). It catches a traversal
  that finds worse neighbours (fewer rounds, a shorter list, a broken PQ
  lookup), whose answers the exact rerank still gives true distances.

Where the configuration has tags (``bench/corpus.py`` ``make_labels``), the
reference is the exact top-k among the base rows whose tag set contains
every tag of the request's predicate.

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


class TagSets:
    """The base rows' tag sets, row i holding ``tags[offsets[i]:offsets[i +
    1]]``. A predicate is a row of tags, -1 where it has no more; a base row
    passes it when its set contains every one of them."""

    def __init__(self, offsets: np.ndarray, tags: np.ndarray):
        self.offsets = np.asarray(offsets, np.int64)
        tags = np.asarray(tags, np.int64)
        self.n = len(self.offsets) - 1
        rows = np.repeat(np.arange(self.n), np.diff(self.offsets))
        order = np.argsort(tags, kind="stable")
        self._by_tag, self._rows = tags[order], rows[order]
        self._width = int(tags.max(initial=-1)) + 1
        self._keys = rows * self._width + tags        # (row, tag) pairs

    def rows_with(self, tag: int) -> np.ndarray:
        """The base rows that have ``tag``, ascending."""
        a, b = np.searchsorted(self._by_tag, [tag, tag + 1])
        return self._rows[a:b]

    def count(self, predicate) -> int:
        """How many base rows pass ``predicate``."""
        rows = None
        for t in predicate:
            if t >= 0:
                r = self.rows_with(t)
                rows = r if rows is None else np.intersect1d(
                    rows, r, assume_unique=True)
        return self.n if rows is None else len(rows)

    def admitted(self, predicates: np.ndarray) -> np.ndarray:
        """(R, N) bool: base row j passes predicate r."""
        out = np.ones((len(predicates), self.n), bool)
        for r, pred in enumerate(np.asarray(predicates)):
            for t in pred[pred >= 0]:
                has = np.zeros(self.n, bool)
                has[self.rows_with(t)] = True
                out[r] &= has
        return out

    def contains(self, ids: np.ndarray, predicates: np.ndarray) -> np.ndarray:
        """(R, k) bool: base row ``ids[r, j]`` passes ``predicates[r]``;
        false for an id outside the corpus."""
        ids = np.asarray(ids, np.int64)
        ok = (ids >= 0) & (ids < self.n)
        for tag in np.asarray(predicates, np.int64).T:
            t = tag[:, None]
            key = np.where(ok, ids, 0) * self._width + np.clip(
                t, 0, max(self._width - 1, 0))
            ok &= (t < 0) | ((t < self._width) & np.isin(key, self._keys))
        return ok


def exact_knn(queries: np.ndarray, base: np.ndarray, k: int, metric: str,
              chunk: int = 256, tag_sets: TagSets | None = None,
              predicates: np.ndarray | None = None) -> np.ndarray:
    """(Q, k) ids of the exact nearest neighbours, nearest first, in
    float64. Distances are squared L2 (``l2``) or minus the cosine
    (``angular``). With ``predicates`` (one row per query) only the base
    rows of ``tag_sets`` that pass a query's predicate are its neighbours;
    -1 fills a row whose predicate admits fewer than ``k``."""
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
        if predicates is not None:
            d = np.where(tag_sets.admitted(predicates[s:s + chunk]), d,
                         np.inf)
        idx = np.argpartition(d, k, axis=1)[:, :k]
        row = np.take_along_axis(d, idx, axis=1)
        order = np.argsort(row, axis=1, kind="stable")
        ids = np.take_along_axis(idx, order, axis=1)
        if predicates is not None:
            ids = np.where(np.isinf(np.take_along_axis(row, order, axis=1)),
                           -1, ids)
        out[s:s + chunk] = ids
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


def bad_id_rows(ids: np.ndarray, n: int,
                passes: np.ndarray | None = None) -> np.ndarray:
    """(Q,) bool: a row holds an id outside ``[0, n)``, an id twice, or,
    given ``passes`` (Q, k), an id its request's predicate refuses."""
    ids = np.asarray(ids, np.int64)
    outside = ((ids < 0) | (ids >= n)).any(axis=1)
    s = np.sort(ids, axis=1)
    repeated = (s[:, 1:] == s[:, :-1]).any(axis=1)
    bad = outside | repeated
    if passes is not None:
        bad |= ~np.asarray(passes, bool).all(axis=1)
    return bad


def compare(queries: np.ndarray, base: np.ndarray, ids: np.ndarray,
            dists: np.ndarray, answered: np.ndarray, metric: str,
            truth: np.ndarray | None = None,
            tag_sets: TagSets | None = None,
            predicates: np.ndarray | None = None) -> dict:
    """The readings of one run. ``queries`` (R, dim) are the requests due in
    the window, ``ids``/``dists`` (R, k) their answers in the corpus's own
    id space, ``answered`` (R,) bool, ``truth`` (R, k) their exact top-k
    (computed here when not given), ``predicates`` (R, T) their predicates
    over ``tag_sets``, if they carry any. Returns the four numbers and the
    per-request failure masks (before limits)."""
    answered = np.asarray(answered, bool)
    got = np.flatnonzero(answered)
    k = np.asarray(ids).shape[1]
    if truth is None:
        truth = exact_knn(queries, base, k, metric, tag_sets=tag_sets,
                          predicates=predicates)
    passes = None
    if predicates is not None:
        passes = tag_sets.contains(ids[got], np.asarray(predicates)[got])
    bad = bad_id_rows(ids[got], len(base), passes)
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
