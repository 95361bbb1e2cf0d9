"""The tag-set adapter (``bench/adapters/tag_sets.py``) and the filtered
cell's metric readers, on the CPU: the program's containment masks equal
the reference's ``TagSets.admitted`` for every pool predicate of the
``yfcc192-filtered`` labels at their full vocabulary, a whole run through
the adapter compiles nothing in its window and comes out correct, and
the two readers return numbers on a record and None without their
counters."""
from __future__ import annotations

import io
import json
import shutil
from types import SimpleNamespace

import numpy as np
import pytest

from bench import catalog, check, corpus, costs, run
from bench.tests.helpers import REPO, tiny_root

ADAPTER = "bench/adapters/tag_sets.py"
SEED = 2_900_000_017


def _yfcc(num_base: int, num_queries: int) -> dict:
    with open(REPO / "bench" / "configs" / "yfcc192-filtered.json") as f:
        cfg = json.load(f)
    cfg.update(num_base=num_base, num_queries=num_queries)
    return cfg


def test_adapter_masks_equal_the_reference_for_every_pool_predicate():
    cfg = _yfcc(2000, 64)
    assert cfg["labels"]["vocabulary"] == 200_386
    labels = corpus.make_labels(cfg)
    admitted = check.TagSets(labels.offsets, labels.tags).admitted(
        labels.predicates)
    adapter = catalog.adapter(cfg, REPO)
    to_corpus = np.random.default_rng(7).permutation(cfg["num_base"])
    index = SimpleNamespace(dataset=SimpleNamespace(num_base=cfg["num_base"]))
    adapter.attach(index, labels.offsets, labels.tags, to_corpus)
    for pred, want in zip(labels.predicates, admitted):
        spec = adapter.request_filter(tuple(int(t) for t in pred[pred >= 0]))
        # row i of the index is corpus row to_corpus[i]
        np.testing.assert_array_equal(index.attributes.mask(spec),
                                      want[to_corpus])
    assert index.attributes.nbytes < 64 * (cfg["num_base"] + len(labels.tags))


def test_run_through_the_adapter_is_correct_with_no_compile_in_window(
        tmp_path):
    """A tiny labelled checkout, at the full vocabulary, served through the
    tag-set adapter: the warm-up leaves nothing to compile in the traced
    run's window, and the run reads the filter-compile metric."""
    root = tiny_root(tmp_path, rate=30.0, labels=True)
    path = root / "bench" / "configs" / "sift128-l2.json"
    cfg = json.loads(path.read_text())
    cfg["adapter"] = ADAPTER
    cfg["labels"]["vocabulary"] = 200_386
    path.write_text(json.dumps(cfg))
    shutil.copy(REPO / ADAPTER, root / ADAPTER)
    err = io.StringIO()
    args = run.parse(["--workload", "tiny", "--seed", str(SEED),
                      "--seconds", "1.5", "--trace", "1"])
    res = run.run(args, root=root, on_chip=False, err=err)
    assert res["correct"] and res["failed"] == 0
    assert " 0 compiles in the window" in err.getvalue()
    assert "# filters: 45 filtered queries" in err.getvalue()
    metrics = res["metrics"]
    assert metrics["compiles_in_window"]["value"] == 0
    assert metrics["filter_compile_ms_per_query"]["value"] > 0
    assert "filter_search_roofline" not in metrics     # no peaks off a chip


def test_filter_compile_reader():
    read = catalog.reader("filter_compile_ms_per_query", REPO)
    assert read({"engine": {"filter_compile_s": 0.25,
                            "filtered_queries": 100}}) == pytest.approx(2.5)
    assert read({"engine": {"filtered_queries": 100}}) is None
    assert read({"engine": {"filter_compile_s": 0.0,
                            "filtered_queries": 0}}) is None


def test_filter_search_roofline_reader():
    config = {"dim": 192, "index": {"max_degree": 32, "pq_subvectors": 48}}
    entries = [
        # a masked traversal of one real lane in a bucket of 2
        {"bucket": 2, "n": 1, "rounds": np.array([120, 1]),
         "n_hops": np.array([200, 9]), "n_pq": np.array([3000, 9]),
         "n_acc": np.array([40, 9])},
        # a scan: 300 passing rows' codes, 64 reranked rows
        {"bucket": 1, "n": 1, "rounds": np.array([1]),
         "n_hops": np.array([0]), "n_pq": np.array([300]),
         "n_acc": np.array([64])},
    ]
    need = (200 * 32 * 4 + 3000 * 48 + 40 * 192 * 4) \
        + (300 * 48 + 64 * 192 * 4)
    assert costs.window_bytes(entries, config) == need
    read = catalog.reader("filter_search_roofline", REPO)
    trace = {"module_s": {"jit_graph_search": 3e-6, "jit__scan_kernel": 1e-6,
                          "jit_other": 1.0}}
    rec = {"counters": entries, "config": config, "trace": trace,
           "peaks": {"hbm_bytes_per_s": 819e9}}
    assert read(rec) == pytest.approx(100.0 * need / 819e9 / 4e-6)
    assert read(dict(rec, trace=None)) is None
    assert read(dict(rec, peaks=None)) is None
    assert read(dict(rec, trace={"module_s": {"jit_other": 1.0}})) is None
