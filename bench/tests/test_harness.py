"""The harness's arithmetic and plumbing, on the CPU: due-time latency and
generator lag, percentiles and qps, discovery by file name, the bytes
behind the roofline, and ``run.py`` refusing to run off a TPU."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time
from collections import deque
from types import SimpleNamespace

import numpy as np
import pytest

from bench import catalog, check, client, costs
from bench.tests.helpers import REPO, tiny_root


class StallingEngine:
    """Answers each submitted request on the next step, instantly, except
    that the step at ``stall_at`` seconds blocks for ``stall`` seconds."""
    continuous = False

    def __init__(self, stall_at: float, stall: float, k: int = 2):
        self.queue = deque()
        self.k = k
        self.stall_at, self.stall = stall_at, stall
        self.t0 = None
        self.stalled = False

    def submit(self, q):
        rid = len(self.queue) + getattr(self, "_n", 0)
        self._n = rid + 1
        self.queue.append(rid)
        return rid

    def step(self):
        now = time.perf_counter()
        if self.t0 is None:
            self.t0 = now
        if not self.stalled and now - self.t0 >= self.stall_at:
            self.stalled = True
            time.sleep(self.stall)
        done = []
        t = time.perf_counter()
        while self.queue:
            rid = self.queue.popleft()
            done.append(SimpleNamespace(rid=rid, t_done=t,
                                        ids=np.arange(self.k),
                                        dists=np.zeros(self.k)))
        return done


def test_latency_counts_from_due_time_through_a_stall():
    due = np.linspace(0.0, 0.6, 31)             # one request every 20 ms
    eng = StallingEngine(stall_at=0.2, stall=0.25)
    log = client.drive(eng, np.zeros((31, 2)), due, seconds=0.6, k=2)
    assert log.answered.all()
    lat = log.latency_ms
    # requests due during the stall wait for it: latency from due time
    stalled = (due > 0.21) & (due < 0.44)
    assert lat[stalled].max() > 150.0
    assert np.all(lat[due > 0.5] < 50.0)
    # they were also submitted late, and that lag is what the client shows
    lag = log.gen_lag_ms
    assert lag[stalled].max() > 150.0
    assert np.all(lag >= 0.0)
    # latency is never shorter than the client's own lag
    assert np.all(lat + 1e-6 >= lag)


def _log(due, done, seconds):
    n = len(due)
    return client.ClientLog(seconds=seconds, due=np.asarray(due, float),
                            submitted=np.asarray(due, float),
                            done=np.asarray(done, float),
                            ids=np.zeros((n, 1), np.int64),
                            dists=np.zeros((n, 1)))


def test_percentiles_and_qps_cover_the_whole_window():
    due = np.arange(100) * 0.1                   # 10 s window
    done = due + np.r_[np.full(98, 0.010), 1.0, np.nan]
    log = _log(due, done, 10.0)
    lat = log.latency_ms
    assert len(lat) == 99                        # the unanswered one is out
    assert client.percentile(lat, 50) == pytest.approx(10.0)
    assert client.percentile(lat, 99) == pytest.approx(
        np.percentile(np.r_[np.full(98, 10.0), 1000.0], 99))
    # done after the close (9.9 + 1.0 s) does not count towards qps
    assert log.completed_in_window() == 98
    assert client.qps(log) == pytest.approx(9.8)
    assert np.isnan(client.percentile([], 99))


def test_discovery_finds_added_files_by_name(tmp_path):
    root = tiny_root(tmp_path)
    (root / "bench" / "traffic" / "extra.json").write_text(
        json.dumps({"scheduler": "batch", "batch_size": 4,
                    "arrivals": "poisson", "load": 0.5, "knee_qps": 10}))
    cfg = json.loads((root / "bench/configs/sift128-l2.json").read_text())
    cfg.update(name="extra-cfg", adapter="bench/adapters/extra.py")
    (root / "bench/configs/extra-cfg.json").write_text(json.dumps(cfg))
    (root / "bench/adapters").mkdir()
    (root / "bench/adapters/extra.py").write_text(
        "def attach(index, offsets, tags, to_corpus):\n    pass\n\n"
        "def request_filter(tags):\n    return ('all', tags)\n")
    (root / "bench/metrics/extra_metric.py").write_text(
        "def read(record):\n    return record['x'] * 2\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "extra-cfg", "source": "s",
                             "file": "bench/configs/extra-cfg.json",
                             "reduced": [], "why": "w"})
    bench["workloads"].append({"name": "extra", "config": "extra-cfg",
                               "traffic": "extra", "chips": 1, "why": "w"})
    bench["per_layer"].append({"name": "extra_metric", "unit": "x",
                               "better": "lower", "source": "host_clock",
                               "layer": "client (bench)", "moves": "qps",
                               "workloads": ["extra"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    b = catalog.load_benchmark(root)
    w = catalog.workload(b, "extra")
    assert catalog.config(b, w["config"], root)["name"] == "extra-cfg"
    assert catalog.traffic(w["traffic"], root)["batch_size"] == 4
    assert "extra_metric" in catalog.per_layer(b, "extra")
    assert "extra_metric" not in catalog.per_layer(b, "tiny")
    assert catalog.reader("extra_metric", root)({"x": 21}) == 42
    assert catalog.units(b)["extra_metric"] == "x"
    with pytest.raises(KeyError):
        catalog.workload(b, "missing")
    extra = catalog.config(b, "extra-cfg", root)
    assert catalog.adapter(extra, root).request_filter((3,)) == ("all", (3,))
    assert catalog.adapter(catalog.config(b, "sift128-l2", root),
                           root) is None
    with pytest.raises(ValueError):
        catalog.adapter(dict(extra, adapter="src/repro/__init__.py"), root)


def test_every_benchmark_metric_has_a_reader_and_every_cell_its_files():
    b = catalog.load_benchmark(REPO)
    for m in b["per_layer"]:
        assert callable(catalog.reader(m["name"], REPO))
    for c in b["configs"]:
        config = catalog.config(b, c["name"], REPO)
        adapter = catalog.adapter(config, REPO)
        assert (adapter is None) == ("labels" not in config)
        if adapter is not None:
            assert callable(adapter.attach)
            assert callable(adapter.request_filter)
    for w in b["workloads"]:
        assert catalog.config(b, w["config"], REPO)["name"] == w["config"]
        t = catalog.traffic(w["traffic"], REPO)
        assert t["load"] * t["knee_qps"] > 0
        assert catalog.end_to_end(b, w["name"])
        assert catalog.per_layer(b, w["name"])
    assert catalog.peaks("TPU v5 lite", REPO)["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        catalog.peaks("cpu", REPO)


def test_bytes_behind_the_roofline():
    config = {"dim": 128, "index": {"max_degree": 32, "pq_subvectors": 32}}
    # one query: 10 hops x 32 ids x 4 B + 100 codes x 32 B + 5 x 128 x 4 B
    one = costs.query_bytes([10], [100], [5], config)
    assert one[0] == 10 * 32 * 4 + 100 * 32 + 5 * 128 * 4
    entries = [
        # a batch of bucket 4 with 3 real lanes: the padding lane is left out
        {"bucket": 4, "n": 3, "rounds": np.array([5, 7, 9, 11]),
         "n_hops": np.array([10, 10, 10, 99]),
         "n_pq": np.array([100, 100, 100, 999]),
         "n_acc": np.array([5, 5, 5, 99])},
        # a retired lane group (continuous): every lane is real
        {"bucket": None, "n": 1, "rounds": np.array([3]),
         "n_hops": np.array([10]), "n_pq": np.array([100]),
         "n_acc": np.array([5])},
        {"bucket": 2, "n": 0, "rounds": np.array([1, 1]),
         "n_hops": np.array([1, 1]), "n_pq": np.array([1, 1]),
         "n_acc": np.array([1, 1])},
    ]
    assert costs.window_bytes(entries, config) == 4 * one[0]
    assert costs.window_queries(entries) == 4
    roof = catalog.reader("graph_search_roofline", REPO)
    trace = {"module_s": {"jit_graph_search": 2e-6, "jit_other": 1.0}}
    rec = {"counters": entries, "config": config, "trace": trace,
           "peaks": {"hbm_bytes_per_s": 819e9}}
    expect = 100.0 * 4 * one[0] / 819e9 / 2e-6
    assert roof(rec) == pytest.approx(expect)
    assert roof(dict(rec, trace=None)) is None
    util = catalog.reader("lane_round_util", REPO)
    assert util(rec) == pytest.approx(100.0 * (5 + 7 + 9) / (4 * 11))
    rounds = catalog.reader("rounds_per_query", REPO)
    assert rounds(rec) == pytest.approx((5 + 7 + 9 + 3) / 4)


def test_recall_floor_fails_a_run_and_its_worst_requests():
    base = np.eye(4, dtype=np.float32)
    queries = base[[0, 1, 2]]
    ids = np.array([[0, 1], [1, 0], [3, 2]])    # row 2 misses its neighbour
    dists = ((base[ids] - queries[:, None, :]) ** 2).sum(-1)
    r = check.compare(queries, base, ids, dists, np.ones(3, bool), "l2")
    assert r["recall_at_10"] == pytest.approx(2.5 / 3)
    assert r["dist_gap_max"] < 1e-6
    ok, failed, shown = check.verdict(r, {"dist_gap_max": 1e-4,
                                          "recall_at_10_min": 0.8})
    assert ok and failed == 0 and shown["recall_at_10"]["at_least"]
    ok, failed, _ = check.verdict(r, {"dist_gap_max": 1e-4,
                                      "recall_at_10_min": 0.9})
    assert not ok and failed == 1


def _tag_sets():
    # rows: 0 {1, 2}, 1 {2}, 2 {}, 3 {1, 2, 5}, 4 {5}, 5 {1}
    offsets = np.array([0, 2, 3, 3, 6, 7, 8])
    tags = np.array([1, 2, 2, 1, 2, 5, 5, 1])
    return check.TagSets(offsets, tags)


def test_filtered_reference_matches_a_naive_loop():
    rng = np.random.default_rng(7)
    n, vocab = 400, 12
    counts = rng.integers(0, 5, size=n)
    offsets = np.concatenate([[0], np.cumsum(counts)])
    rows = [np.sort(rng.choice(vocab, size=c, replace=False))
            for c in counts]
    sets = check.TagSets(offsets, np.concatenate(rows))
    base = rng.standard_normal((n, 8)).astype(np.float32)
    queries = rng.standard_normal((40, 8)).astype(np.float32)
    predicates = np.full((40, 2), -1)
    predicates[:, 0] = rng.integers(0, vocab, size=40)
    predicates[20:, 1] = (predicates[20:, 0] + 1 + rng.integers(
        0, vocab - 1, size=20)) % vocab
    predicates[5] = -1                              # no predicate: all rows
    for metric in ("l2", "angular"):
        got = check.exact_knn(queries, base, 5, metric, chunk=16,
                              tag_sets=sets, predicates=predicates)
        for q, pred, ids in zip(queries, predicates, got):
            want = set(pred[pred >= 0].tolist())
            ok = [i for i in range(n) if want <= set(rows[i].tolist())]
            x = base[ok].astype(np.float64)
            if metric == "angular":
                x = x / np.linalg.norm(x, axis=1, keepdims=True)
                d = -(x @ q.astype(np.float64))
            else:
                d = ((x - q.astype(np.float64)) ** 2).sum(1)
            near = [ok[i] for i in np.argsort(d, kind="stable")[:5]]
            assert ids[:len(near)].tolist() == near
            assert np.all(ids[len(near):] == -1)     # fewer than k pass
    ts = _tag_sets()
    assert ts.count([1, 2]) == 2 and ts.count([5, -1]) == 2
    assert ts.count([-1, -1]) == 6 and ts.count([7]) == 0
    assert ts.admitted(np.array([[1, 2], [-1, -1]])).tolist() == [
        [True, False, False, True, False, False], [True] * 6]


def test_an_id_outside_its_predicate_is_a_bad_id():
    ts = _tag_sets()
    base = np.eye(6, dtype=np.float32)
    queries = base[[0, 4]]
    predicates = np.array([[1, 2], [5, -1]])
    ids = np.array([[0, 3], [4, 3]])                # all pass
    dists = ((base[ids] - queries[:, None, :]) ** 2).sum(-1)
    ok = check.compare(queries, base, ids, dists, np.ones(2, bool), "l2",
                       tag_sets=ts, predicates=predicates)
    assert ok["bad_ids"] == 0 and ok["recall_at_10"] == 1.0
    assert ts.contains(ids, predicates).all()
    assert ts.contains(np.array([[0, -1]]), predicates[:1]).tolist() == [
        [True, False]]
    ids = np.array([[0, 5], [4, 3]])                # row 5 lacks tag 2
    dists = ((base[ids] - queries[:, None, :]) ** 2).sum(-1)
    r = check.compare(queries, base, ids, dists, np.ones(2, bool), "l2",
                      tag_sets=ts, predicates=predicates)
    assert r["bad_ids"] == 1 and r["_row_bad"].tolist() == [True, False]
    assert r["dist_gap_max"] < 1e-6
    correct, failed, shown = check.verdict(r, {"dist_gap_max": 1e-4,
                                               "recall_at_10_min": 0.0})
    assert not correct and failed == 1
    assert list(shown) == list(check.CHECKS)
    # the same answers, unfiltered, are sound
    r = check.compare(queries, base, ids, dists, np.ones(2, bool), "l2")
    assert r["bad_ids"] == 0


def test_gc_pauses_are_timed_inside_the_window():
    import gc

    from bench import run

    pauses = run.GcPauses()
    a = time.perf_counter()
    gc.collect()
    b = time.perf_counter()
    pauses.close()
    assert len(pauses.spans) >= 1
    assert 0.0 < pauses.seconds_between(a, b) <= b - a
    assert pauses.seconds_between(b + 1.0, b + 2.0) == 0.0
    pauses.spans = [(1.0, 3.0)]
    assert pauses.seconds_between(2.0, 10.0) == pytest.approx(1.0)
    read = catalog.reader("gc_pause_pct", REPO)
    log = SimpleNamespace(seconds=20.0)
    assert read({"gc_pause_s": 0.5, "log": log}) == pytest.approx(2.5)


def _run_py(cwd, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(extra_env or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "sift-batch-poisson",
         "--seed", "4000000000", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_exits_nonzero_without_a_tpu():
    p = _run_py(REPO)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_run_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    p = _run_py(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
