"""The comparison that decides ``correct`` has to fail what it guards
against. On the CPU at a small size:

* the control, the reference one precision lower (vectors stored in
  bfloat16), reads over every configuration's limit;
* a whole run of the harness, with the chip check skipped, comes out
  correct on the sound program and not correct when the timed path is
  broken underneath: an answer altered where the search produces it,
  answers handed to the wrong requests, or a traversal cut short (its
  answers keep exact distances; only the recall floor catches it);
* with tags and per-request predicates, a whole run is correct under both
  schedulers, and not correct when the engine drops the requests' filters;
  the control, filtered too, still fails.
"""
from __future__ import annotations

import dataclasses
import io
import json

import numpy as np
import pytest

from bench import check, control, corpus, run
from bench.tests.helpers import LABELS, REPO, tiny_root

SEED = 2_900_000_011


@pytest.mark.parametrize("name", ["sift128-l2", "glove100-angular"])
def test_control_fails_and_the_reference_passes(name):
    with open(REPO / "bench" / "configs" / f"{name}.json") as f:
        cfg = json.load(f)
    cfg.update(num_base=2000, num_queries=256)
    base, queries = corpus.make_corpus(cfg)
    ids, dists = control.control_answers(base, queries, cfg["k"],
                                         cfg["metric"])
    low = control.readings(cfg, base, queries, ids, dists)
    assert not low["correct"]
    gap = low["checks"]["dist_gap_max"]
    assert gap["value"] > 3 * gap["limit"]
    # the same answers with float32 distances pass
    truth = check.exact_knn(queries, base, cfg["k"], cfg["metric"])
    x = base[truth].astype(np.float32)
    q = queries[:, None, :].astype(np.float32)
    if cfg["metric"] == "angular":
        x = x / np.linalg.norm(x, axis=-1, keepdims=True)
        q = q / np.linalg.norm(q, axis=-1, keepdims=True)
        d32 = -(x * q).sum(-1)
    else:
        d32 = ((x - q) ** 2).sum(-1)
    assert control.readings(cfg, base, queries, truth, d32)["correct"]


@pytest.mark.parametrize("quantize", [None, "uint8"])
def test_filtered_control_fails_and_the_reference_passes(quantize):
    """With tags; and with vectors of whole numbers 0..255, which bfloat16
    holds exactly, so that the control keeps 4 bits of each."""
    with open(REPO / "bench" / "configs" / "sift128-l2.json") as f:
        cfg = json.load(f)
    cfg.update(num_base=2000, num_queries=256, labels=LABELS)
    if quantize:
        cfg["assumed"].update(quantize=quantize, center_mean=128.0,
                              center_std=24.0, noise_std=24.0)
    base, queries = corpus.make_corpus(cfg)
    labels = corpus.make_labels(cfg)
    sets = check.TagSets(labels.offsets, labels.tags)
    preds = labels.predicates
    if quantize:
        ids, dists = control.control_answers(
            base, queries, cfg["k"], "l2", tag_sets=sets, predicates=preds)
        assert control.readings(cfg, base, queries, ids, dists,
                                tag_sets=sets, predicates=preds)["correct"]
    ids, dists = control.control_answers(
        base, queries, cfg["k"], "l2", tag_sets=sets, predicates=preds,
        precision=control.lower_precision(cfg))
    assert sets.contains(ids, preds).all()
    low = control.readings(cfg, base, queries, ids, dists, tag_sets=sets,
                           predicates=preds)
    assert not low["correct"]
    gap = low["checks"]["dist_gap_max"]
    assert gap["value"] > 3 * gap["limit"]
    assert low["checks"]["bad_ids"]["value"] == 0
    truth = check.exact_knn(queries, base, cfg["k"], "l2", tag_sets=sets,
                            predicates=preds)
    d32 = ((base[truth] - queries[:, None, :]) ** 2).sum(-1)
    sound = control.readings(cfg, base, queries, truth, d32, tag_sets=sets,
                             predicates=preds)
    assert sound["correct"]
    assert sound["checks"]["recall_at_10"]["value"] == 1.0
    # the unfiltered answers break the predicates
    plain = check.exact_knn(queries, base, cfg["k"], "l2")
    d32 = ((base[plain] - queries[:, None, :]) ** 2).sum(-1)
    dropped = control.readings(cfg, base, queries, plain, d32, tag_sets=sets,
                               predicates=preds)
    assert not dropped["correct"] and dropped["checks"]["bad_ids"]["value"]


def _tiny_run(root, trace=0, err=None):
    args = run.parse(["--workload", "tiny", "--seed", str(SEED),
                      "--seconds", "1.5", "--trace", str(trace)])
    return run.run(args, root=root, on_chip=False, err=err or io.StringIO())


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("bench"), rate=60.0)


def test_sound_run_is_correct(root):
    res = _tiny_run(root)
    assert res["correct"] and res["failed"] == 0
    assert res["attempted"] == 90
    assert list(res)[-1] == "checks"
    recall = res["checks"]["recall_at_10"]
    assert recall["at_least"] and recall["value"] >= recall["limit"]
    assert set(res["metrics"]) >= {"p50_ms", "recall_at_10", "setup_s"}


def test_sound_continuous_traced_run_is_correct(tmp_path):
    """The continuous scheduler's warm-up leaves nothing to compile in the
    window, and a traced run reads its counters."""
    res = _tiny_run(tiny_root(tmp_path, scheduler="continuous", rate=60.0),
                    trace=1)
    assert res["correct"] and res["failed"] == 0
    assert res["metrics"]["compiles_in_window"]["value"] == 0
    assert res["metrics"]["ticks_per_query"]["value"] >= 1
    assert res["metrics"]["rounds_per_query"]["value"] > 0


def _patch_execute(monkeypatch, alter):
    from repro.plan.planner import QueryPlanner

    orig = QueryPlanner.execute

    def broken(self, plan, queries):
        ex = orig(self, plan, queries)
        return ex._replace(ids=alter(np.array(ex.ids)))
    monkeypatch.setattr(QueryPlanner, "execute", broken)


def test_altered_answer_is_not_correct(root, monkeypatch):
    def alter(ids):
        ids[0, 0] = (ids[0, 0] + 1) % 1500       # one id, where produced
        return ids
    _patch_execute(monkeypatch, alter)
    res = _tiny_run(root)
    assert not res["correct"] and res["failed"] > 0


def test_answers_to_the_wrong_requests_are_not_correct(root, monkeypatch):
    _patch_execute(monkeypatch, lambda ids: np.roll(ids, 1, axis=0))
    res = _tiny_run(root)
    assert not res["correct"]
    assert res["checks"]["dist_gap_max"]["value"] > 1.0


def test_traversal_cut_short_is_not_correct(root, monkeypatch):
    """At most 4 rounds of traversal: the rerank still gives each served id
    its exact distance, so only the recall floor fails."""
    from repro.configs.base import PlanConfig
    from repro.plan.searcher import Searcher

    orig = Searcher.open.__func__

    def cut(cls, index, plan=None, **kw):
        pc = plan or PlanConfig()
        search = dataclasses.replace(pc.search or index.config.search,
                                     max_rounds=4)
        return orig(cls, index, dataclasses.replace(pc, search=search), **kw)
    monkeypatch.setattr(Searcher, "open", classmethod(cut))
    res = _tiny_run(root)
    checks = res["checks"]
    assert not res["correct"] and res["failed"] > 0
    assert checks["dist_gap_max"]["value"] <= checks["dist_gap_max"]["limit"]
    assert checks["recall_at_10"]["value"] < checks["recall_at_10"]["limit"]


@pytest.fixture(scope="module")
def filtered_root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("filtered"), rate=30.0,
                     labels=True)


@pytest.mark.parametrize("scheduler", ["batch", "continuous"])
def test_sound_filtered_run_is_correct(scheduler, filtered_root, tmp_path):
    """Every request carries its pool row's predicate; the warm-up leaves
    no filtered shape to compile in the window."""
    root = filtered_root if scheduler == "batch" else tiny_root(
        tmp_path, scheduler=scheduler, rate=30.0, labels=True)
    err = io.StringIO()
    res = _tiny_run(root, err=err)
    assert res["correct"] and res["failed"] == 0
    assert res["attempted"] == 45
    assert " 0 compiles in the window" in err.getvalue()
    assert "# filters: 45 filtered queries" in err.getvalue()
    assert res["checks"]["recall_at_10"]["value"] >= 0.85


def test_dropped_filters_are_not_correct(filtered_root, monkeypatch):
    """The engine serves the requests without their filters: the answers
    are the unfiltered neighbours, with exact distances, and break their
    predicates."""
    from repro.serve.engine import ServingEngine

    orig = ServingEngine.submit

    def dropped(self, query, filter=None, tenant=None):
        return orig(self, query, tenant=tenant)
    monkeypatch.setattr(ServingEngine, "submit", dropped)
    res = _tiny_run(filtered_root)
    checks = res["checks"]
    assert not res["correct"] and res["failed"] > 0
    assert checks["bad_ids"]["value"] > 0
    assert checks["dist_gap_max"]["value"] <= checks["dist_gap_max"]["limit"]
