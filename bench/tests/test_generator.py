"""The benchmark's generator, on the CPU at a small size: the corpus is a
function of the configuration and the traffic a function of ``--seed``, it
keeps each configuration's shape and metric, and the served path's recall
on it rises with the search list."""
from __future__ import annotations

import hashlib
import json

import numpy as np
import pytest

from bench import catalog, check, corpus, run
from bench.tests.helpers import LABELS, REPO

SEED = 3_000_000_017                    # seeds may exceed 32 bits

# The data of the configurations without tags, as it was before a
# configuration could carry them: sha256 (first 16 hex digits) of dtype,
# shape and bytes of the corpus, the query pool, the pool rows of 8,288
# requests in SEED's order, and the float64 top 10 of the first 256 pool
# rows; and the index build's seed. Tags draw on streams of their own, so
# none of this may move.
PINNED = {
    "sift128-l2": {"base": "66f39ad014d6c128", "pool": "4683964a97814831",
                   "order": "ecd9a63103d1ffde", "truth": "b7f4da14e1d72e8d",
                   "build_seed": 559249116},
    "glove100-angular": {"base": "7d09cf2ac57ce1db",
                         "pool": "23e07abe412b74ab",
                         "order": "ecd9a63103d1ffde",
                         "truth": "adf9948ed2a3169b",
                         "build_seed": 559249116},
}
# each mix at its cell's rate over 51 s, and over the traced 2 s, from a
# pool of 4,096: (requests, due times, pool rows, traced due, traced rows)
PINNED_SCHEDULES = {
    "poisson-batch-under": (8288, "11a6ff4c027cdbf5", "ecd9a63103d1ffde",
                            "4d82382d4db04fc9", "e677eec48affde88"),
    "burst-cont": (4590, "841caabbf9b00d6f", "7768c87e2d91f8af",
                   "00a15522bbfb106c", "f25c39bcbfc71bc4"),
}


def _digest(a) -> str:
    a = np.ascontiguousarray(a)
    return hashlib.sha256(str(a.dtype).encode() + str(a.shape).encode()
                          + a.tobytes()).hexdigest()[:16]


def _config(name, **changes):
    with open(REPO / "bench" / "configs" / f"{name}.json") as f:
        cfg = json.load(f)
    cfg.update(changes)
    return cfg


@pytest.mark.parametrize("name", ["sift128-l2", "glove100-angular"])
def test_corpus_is_a_function_of_the_configuration(name):
    cfg = _config(name, num_base=500, num_queries=64)
    b1, q1 = corpus.make_corpus(cfg)
    b2, q2 = corpus.make_corpus(cfg)
    assert np.array_equal(b1, b2) and np.array_equal(q1, q2)
    other = json.loads(json.dumps(cfg))
    other["assumed"]["data_seed"] += 1
    b3, q3 = corpus.make_corpus(other)
    assert not np.array_equal(b1, b3) and not np.array_equal(q1, q3)
    assert corpus.build_seed(cfg) == corpus.build_seed(json.loads(
        json.dumps(cfg)))
    assert 0 <= corpus.build_seed(cfg) < 2 ** 31


@pytest.mark.parametrize("mix", ["poisson-batch-under", "burst-cont"])
def test_traffic_is_a_function_of_the_seed(mix):
    traffic = catalog.traffic(mix, REPO)
    pool = np.arange(4096, dtype=np.float32)[:, None]
    due1, q1 = run.schedule(traffic, 150.0, 20.0, pool, SEED)
    due2, q2 = run.schedule(traffic, 150.0, 20.0, pool, SEED)
    due3, q3 = run.schedule(traffic, 150.0, 20.0, pool, SEED + 1)
    assert np.array_equal(due1, due2) and np.array_equal(q1, q2)
    assert not np.array_equal(due1, due3) and not np.array_equal(q1, q3)
    # every seed offers the same work: as many requests, inside the
    # window, each pool query at most once per pass over the pool
    assert len(due1) == len(due3) == 3000
    assert 0.0 <= due1.min() and due1.max() < 20.0
    assert np.all(np.diff(due1) >= 0)
    assert len(np.unique(q1)) == len(q1)
    # the same queries on every seed, in another order
    assert np.array_equal(np.sort(q1, axis=0), np.sort(q3, axis=0))
    t1, _ = run.schedule(traffic, 150.0, 20.0, pool, SEED, traced=True)
    assert not np.array_equal(t1, due1)


@pytest.mark.parametrize("name", ["sift128-l2", "glove100-angular"])
def test_generator_keeps_dimension_and_metric(name):
    cfg = _config(name, num_base=500, num_queries=64)
    base, queries = corpus.make_corpus(cfg)
    assert base.shape == (500, cfg["dim"]) and base.dtype == np.float32
    assert queries.shape == (64, cfg["dim"]) and queries.dtype == np.float32
    if cfg["metric"] == "angular":
        assert np.allclose(np.linalg.norm(base, axis=1), 1.0, atol=1e-5)
    else:
        assert np.linalg.norm(base, axis=1).std() > 0.1


def _served_recall(index, to_corpus, base, queries, list_size):
    from repro.configs.base import SearchConfig
    from repro.serve.engine import ServingEngine

    s = index.config.search
    cfg = SearchConfig(k=10, list_size=list_size, t_init=min(s.t_init,
                                                               list_size),
                       t_step=s.t_step, repetition_rate=s.repetition_rate,
                       beta=s.beta)
    eng = ServingEngine(index, batch_size=64, cfg=cfg)
    rids = [eng.submit(q) for q in queries]
    eng.drain()
    ids = to_corpus[np.stack([eng.done[r].ids for r in rids])]
    truth = check.exact_knn(queries, base, 10, index.dataset.metric)
    return check.recall_at_k(ids, truth, 10)


@pytest.mark.parametrize("name", ["sift128-l2", "glove100-angular"])
def test_served_recall_rises_with_the_search_list(name):
    from bench import system

    # ~20 vectors a cluster, as at the configurations' full size
    cfg = _config(name, num_base=3000, num_queries=64)
    cfg["assumed"]["num_clusters"] = 150
    base, queries = corpus.make_corpus(cfg)
    index, to_corpus = system.build(cfg, base, corpus.build_seed(cfg))
    recalls = [_served_recall(index, to_corpus, base, queries, size)
               for size in (10, 64)]
    assert recalls[0] < recalls[1]
    assert recalls[1] >= 0.8


@pytest.mark.parametrize("name", ["sift128-l2", "glove100-angular"])
def test_existing_configurations_data_is_pinned(name):
    cfg = _config(name)
    base, pool = corpus.make_corpus(cfg)
    order = run.query_order(corpus.rng_for(SEED, corpus.STREAM_ORDER), 8288,
                            len(pool))
    truth = check.exact_knn(pool[:256], base, 10, cfg["metric"])
    assert {"base": _digest(base), "pool": _digest(pool),
            "order": _digest(order.astype(np.int64)),
            "truth": _digest(truth.astype(np.int64)),
            "build_seed": corpus.build_seed(cfg)} == PINNED[name]


@pytest.mark.parametrize("mix", ["poisson-batch-under", "burst-cont"])
def test_existing_schedules_are_pinned(mix):
    traffic = catalog.traffic(mix, REPO)
    rate = traffic["load"] * traffic["knee_qps"]
    pool = np.arange(4096)[:, None]
    due, q = run.schedule(traffic, rate, 51.0, pool, SEED)
    tdue, tq = run.schedule(traffic, rate, 2.0, pool, SEED, traced=True)
    assert (len(due), _digest(due), _digest(q[:, 0].astype(np.int64)),
            _digest(tdue), _digest(tq[:, 0].astype(np.int64))) \
        == PINNED_SCHEDULES[mix]
    rows = run.window_rows(traffic, rate, 51.0, 4096, SEED)[1]
    assert np.array_equal(q[:, 0], rows)


def _labelled(name="sift128-l2", **changes):
    cfg = _config(name, num_base=1200, num_queries=200, **changes)
    cfg["assumed"]["num_clusters"] = 40
    cfg["labels"] = dict(LABELS)
    return cfg


def test_labels_leave_the_corpus_and_are_a_function_of_the_configuration():
    cfg = _labelled()
    plain = json.loads(json.dumps(cfg))
    del plain["labels"]
    for a, b in zip(corpus.make_corpus(cfg), corpus.make_corpus(plain)):
        assert np.array_equal(a, b)
    one, two = corpus.make_labels(cfg), corpus.make_labels(cfg)
    for a, b in zip(one, two):
        assert np.array_equal(a, b)
    other = json.loads(json.dumps(cfg))
    other["assumed"]["data_seed"] += 1
    assert not np.array_equal(corpus.make_labels(other).tags, one.tags)
    # CSR over every base row; ascending tags, none twice, in the vocabulary
    assert one.offsets[0] == 0 and len(one.offsets) == cfg["num_base"] + 1
    assert one.offsets[-1] == len(one.tags)
    for i in range(cfg["num_base"]):
        row = one.tags[one.offsets[i]:one.offsets[i + 1]]
        assert 1 <= len(row) <= 8 and np.all(np.diff(row) > 0)
    assert one.tags.min() >= 0 and one.tags.max() < 256
    # tags follow the cluster: two vectors of one cluster share more tags
    # than two of different clusters
    _, assign, _, _, _ = corpus._clusters(cfg, cfg["num_base"], 1)
    sets = [set(one.tags[one.offsets[i]:one.offsets[i + 1]].tolist())
            for i in range(cfg["num_base"])]
    same = [len(sets[i] & sets[j]) for i in range(300)
            for j in range(i + 1, 300) if assign[i] == assign[j]]
    apart = [len(sets[i] & sets[j]) for i in range(300)
             for j in range(i + 1, 300) if assign[i] != assign[j]]
    assert np.mean(same) > 1.5 * np.mean(apart)


def test_every_predicate_admits_min_matches():
    cfg = _labelled()
    labels = corpus.make_labels(cfg)
    n_tags = (labels.predicates >= 0).sum(axis=1)
    assert set(n_tags.tolist()) == {1, 2}
    assert 0.45 < (n_tags == 1).mean() < 0.75
    sets = [set(labels.tags[labels.offsets[i]:labels.offsets[i + 1]]
                .tolist()) for i in range(cfg["num_base"])]
    for pred, admits in zip(labels.predicates, labels.admits):
        want = set(pred[pred >= 0].tolist())
        assert len(want) == len(pred[pred >= 0])
        naive = sum(want <= s for s in sets)
        assert naive == admits >= cfg["labels"]["min_matches"]
    # selectivities span orders of magnitude
    assert labels.admits.max() > 20 * labels.admits.min()


def test_min_matches_under_k_is_refused():
    cfg = _labelled()
    cfg["labels"]["min_matches"] = cfg["k"] - 1
    with pytest.raises(ValueError):
        corpus.make_labels(cfg)


def test_uint8_quantize_rounds_and_clips():
    cfg = _labelled(dim=192)
    cfg["assumed"].update(quantize="uint8", center_mean=128.0,
                          center_std=40.0, noise_std=20.0)
    base, queries = corpus.make_corpus(cfg)
    for x in (base, queries):
        assert x.dtype == np.float32 and x.shape[1] == 192
        assert x.min() >= 0 and x.max() <= 255
        assert np.array_equal(x, np.rint(x))
    assert 100 < base.mean() < 156
    cfg["assumed"]["quantize"] = "int4"
    with pytest.raises(ValueError):
        corpus.make_corpus(cfg)
