"""The benchmark's generator, on the CPU at a small size: the corpus is a
function of the configuration and the traffic a function of ``--seed``, it
keeps each configuration's shape and metric, and the served path's recall
on it rises with the search list."""
from __future__ import annotations

import json

import numpy as np
import pytest

from bench import catalog, check, corpus, run
from bench.tests.helpers import REPO

SEED = 3_000_000_017                    # seeds may exceed 32 bits


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
