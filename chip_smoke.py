"""Bring-up smoke of the served search path on a TPU.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # a (data=4, model=1) mesh only

One chip: builds a SIFT-shaped index from a fixed seed with the
``benchmarks/common.py`` settings (128-d f32, L2, R=32, build list 64, PQ
M=32 C=256, hot fraction 0.03) at 50,000 vectors, a cut of SIFT1M's 1M
forced by the host-side graph build. It serves 256 queries through
``ServingEngine`` (which opens ``Searcher.open(index)``), once with the
batch-flush scheduler and once with ``continuous=True``, checks that the
corpus lives on the chip, checks recall@10 against ``exact_knn`` on the
same data and the ids of 32 queries against ``search_reference`` (the
NumPy transliteration of Algorithm 1), and serves the same requests again
with the Pallas kernels compiled (``SearchConfig(use_pallas=True)``),
which must return the same ids as the jnp path for at least 99% of
results.

Four chips: the distributed plan (``Searcher.open(ShardedCorpus, mesh=...)``)
in ``nsp`` and ``fetch`` mode over one 20,000-vector index of the same
shape, each checked for shard placement, recall and agreement with the
flat search on one chip.

Every phase that fails makes the script exit nonzero. Only when all pass is
the last line of standard output ``{"ok": true, "device": {...}}``. The
times printed are bring-up readings, not benchmark numbers. The script runs
in one process and starts no children: the chip belongs to one process.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(_ROOT, "src"))
sys.path.insert(0, _ROOT)

# SIFT1M cut to what the host-side graph build finishes well inside the
# run's 1200 s (CPU rehearsal: 237 s at 50k, over 600 s at 75k)
NUM_BASE = 50_000
# the mesh phase checks placement and collectives, which do not depend on
# the corpus size, and four chips are charged for every second of build
FOUR_CHIP_BASE = 20_000
NUM_QUERIES = 256
BATCH = 64
K = 10
# CPU rehearsals at NUM_BASE (batch flush) and FOUR_CHIP_BASE (flat): the
# floors sit 0.02 below the recall@10 they measured
RECALL_FLOOR = 0.1323             # rehearsal: 0.1523
FOUR_CHIP_RECALL_FLOOR = 0.2191    # rehearsal: 0.2391
MIN_AGREEMENT = 0.99
# the engine's Bloom filter may drop a node the oracle's exact visited set
# keeps; tests/test_search.py holds the engine to the same share
NUM_REFERENCE = 32
MIN_REFERENCE_AGREEMENT = 0.9
NOT_A_BENCHMARK = "(bring-up timing, not a benchmark number)"


class SmokeError(RuntimeError):
    """A phase of the smoke failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeError(what)


def smoke_config(num_base: int, num_queries: int):
    """The benchmark suite's SIFT-like configuration at ``num_base``."""
    from benchmarks.common import proxima_config

    cfg = proxima_config("sift-like", hot=0.03)
    return dataclasses.replace(cfg, dataset=dataclasses.replace(
        cfg.dataset, num_base=num_base, num_queries=num_queries))


def build(num_base: int, num_queries: int, log=print):
    """Index + exact ground truth on the same (reordered) data."""
    from repro.core import build_index
    from repro.core.dataset import exact_knn

    cfg = smoke_config(num_base, num_queries)
    log(f"# corpus: {num_base} x {cfg.dataset.dim} f32 {cfg.dataset.metric} "
        f"(SIFT1M's 1,000,000 cut to {num_base}), {num_queries} queries")
    t0 = time.perf_counter()
    index = build_index(cfg, reorder_samples=64)
    ds = index.dataset
    gt = exact_knn(ds.queries, ds.base, K, ds.metric)
    log(f"# set-up: index build + exact kNN {time.perf_counter() - t0:.1f} s")
    return index, gt


def reference_ids(index, n: int):
    """Top-k ids of the first ``n`` queries from ``search_reference``, the
    NumPy transliteration of Algorithm 1 (exact visited set)."""
    import numpy as np

    from repro.core.search import search_reference

    g = index.graph
    return np.stack([
        search_reference(g.adjacency, g.degrees, index.codes,
                         index._search_base(), index.codebook.centroids,
                         g.entry_point, q, index.config.search,
                         index.dataset.metric, hot_count=index.hot_count)[0]
        for q in index.dataset.queries[:n]])


def _platforms(arrays) -> set:
    import jax

    return {d.platform for a in jax.tree_util.tree_leaves(arrays)
            for d in a.devices()}


def serve(index, cfg, continuous: bool, batch: int, log=print):
    """All queries through one ``ServingEngine``; ids in submit order."""
    import numpy as np

    from repro.serve.engine import ServingEngine

    name = "continuous" if continuous else "batch-flush"
    if cfg.use_pallas:
        name += "+pallas"
    t0 = time.perf_counter()
    eng = ServingEngine(index, batch_size=batch, cfg=cfg,
                        continuous=continuous)
    warm = time.perf_counter() - t0
    t0 = time.perf_counter()
    rids = [eng.submit(q) for q in index.dataset.queries]
    eng.drain()
    wall = time.perf_counter() - t0
    log(f"# {name}: compile+warm-up {warm:.2f} s, serve "
        f"{len(rids)} queries {wall:.3f} s {NOT_A_BENCHMARK}")
    return eng, np.stack([eng.done[r].ids for r in rids])


def run_one_chip(num_base: int = NUM_BASE, num_queries: int = NUM_QUERIES,
                 recall_floor: float = RECALL_FLOOR, platform: str = "tpu",
                 batch: int = BATCH, log=print) -> dict:
    """The single-chip phases; raises :class:`SmokeError` on a failure."""
    from repro.core.dataset import recall_at_k
    from repro.kernels import ops

    index, gt = build(num_base, num_queries, log)
    cfg = index.config.search
    ref = reference_ids(index, min(NUM_REFERENCE, num_queries))
    out = {}
    ids = {}
    for continuous in (False, True):
        eng, got = serve(index, cfg, continuous, batch, log)
        name = "continuous" if continuous else "batch_flush"
        where = _platforms(eng.searcher.corpus)
        check(where == {platform},
              f"{name}: corpus arrays on {where}, not on {platform}")
        rec = recall_at_k(got, gt, K)
        agree = recall_at_k(got[:len(ref)], ref, K)
        log(f"# {name}: recall@{K} {rec:.4f} (floor {recall_floor:.4f}); "
            f"{agree:.4f} of ids agree with search_reference on "
            f"{len(ref)} queries (need {MIN_REFERENCE_AGREEMENT})")
        check(rec >= recall_floor,
              f"{name}: recall@{K} {rec:.4f} below floor {recall_floor:.4f}")
        check(agree >= MIN_REFERENCE_AGREEMENT,
              f"{name}: ids agree with search_reference on {agree:.4f}")
        out[f"recall_{name}"] = rec
        ids[name] = got

    check(ops.interpret_mode() == (platform != "tpu"),
          "Pallas interpret mode does not follow the backend")
    _, got = serve(index, dataclasses.replace(cfg, use_pallas=True),
                   False, batch, log)
    agree = recall_at_k(got, ids["batch_flush"], K)
    log(f"# pallas vs jnp: {agree:.4f} of result ids agree "
        f"(need {MIN_AGREEMENT})")
    check(agree >= MIN_AGREEMENT,
          f"pallas ids agree with jnp on {agree:.4f} < "
          f"{MIN_AGREEMENT}")
    out["pallas_agreement"] = agree
    return out


def _check_placement(corpus, mesh) -> None:
    """Shard i of every (P, N/P, ·) array on mesh device i of the data
    axis; every other array replicated on all mesh devices."""
    from repro.core.distributed import REPLICATED_FIELDS, SHARDED_FIELDS

    devices = list(mesh.devices.reshape(-1))
    for f in SHARDED_FIELDS + REPLICATED_FIELDS:
        arr = getattr(corpus, f)
        check(set(arr.devices()) == set(devices),
              f"{f}: on {arr.devices()}, not on the mesh")
    for f in SHARDED_FIELDS:
        for sh in getattr(corpus, f).addressable_shards:
            row = sh.index[0].start
            check(sh.data.shape[0] == 1 and sh.device == devices[row],
                  f"{f}: shard {row} on {sh.device}")
    for f in REPLICATED_FIELDS:
        check(getattr(corpus, f).sharding.is_fully_replicated,
              f"{f}: not replicated")


def run_four_chips(num_base: int = FOUR_CHIP_BASE,
                   num_queries: int = NUM_QUERIES,
                   recall_floor: float = FOUR_CHIP_RECALL_FLOOR,
                   log=print) -> dict:
    """The mesh phase: ``nsp`` and ``fetch`` against the flat search on
    one chip; raises :class:`SmokeError` on a failure."""
    import jax

    from repro.core.dataset import recall_at_k
    from repro.core.distributed import shard_corpus
    from repro.launch.mesh import make_mesh
    from repro.plan import Searcher, SearchRequest

    p = 4
    check(len(jax.devices()) >= p, f"{len(jax.devices())} devices, need {p}")
    mesh = make_mesh((p, 1), ("data", "model"))
    index, gt = build(num_base, num_queries, log)
    cfg = index.config.search
    queries = index.dataset.queries

    t0 = time.perf_counter()
    flat = Searcher.open(index).search(SearchRequest(queries=queries))
    first = time.perf_counter() - t0
    flat_rec = recall_at_k(flat.ids, gt, K)
    ref = reference_ids(index, min(NUM_REFERENCE, num_queries))
    ref_agree = recall_at_k(flat.ids[:len(ref)], ref, K)
    log(f"# flat on one chip: recall@{K} {flat_rec:.4f}, {ref_agree:.4f} of "
        f"ids agree with search_reference; first call {first:.2f} s "
        f"{NOT_A_BENCHMARK}")
    check(flat_rec >= recall_floor,
          f"flat: recall@{K} {flat_rec:.4f} below floor {recall_floor:.4f}")
    check(ref_agree >= MIN_REFERENCE_AGREEMENT,
          f"flat: ids agree with search_reference on {ref_agree:.4f}")
    out = {"recall_flat": flat_rec}

    sc = shard_corpus(index.graph.adjacency, index.codes,
                      index._search_base(), index.codebook.centroids,
                      int(index.graph.entry_point), index.hot_count, p)
    for mode in ("nsp", "fetch"):
        s = Searcher.open(sc, cfg=cfg, metric=index.dataset.metric,
                          mesh=mesh, mode=mode)
        _check_placement(s.planner.dcorpus, mesh)
        t0 = time.perf_counter()
        res = s.search(SearchRequest(queries=queries))
        first = time.perf_counter() - t0
        t0 = time.perf_counter()
        res = s.search(SearchRequest(queries=queries))
        again = time.perf_counter() - t0
        rec = recall_at_k(res.ids, gt, K)
        agree = recall_at_k(res.ids, flat.ids, K)
        log(f"# {mode} on {p} chips: recall@{K} {rec:.4f}, {agree:.4f} of "
            f"ids agree with flat; first call {first:.2f} s, again "
            f"{again:.3f} s {NOT_A_BENCHMARK}")
        check(res.plan.kind == "distributed", f"{mode}: plan {res.plan.kind}")
        check(rec >= recall_floor,
              f"{mode}: recall@{K} {rec:.4f} below floor {recall_floor:.4f}")
        check(agree >= MIN_AGREEMENT,
              f"{mode}: ids agree with flat on {agree:.4f}")
        out[f"recall_{mode}"] = rec
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the (data=4, model=1) mesh phase")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    print(f"# device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}")
    if dev.platform != "tpu":
        print("chip_smoke: no TPU found; this smoke runs only on the chip",
              file=sys.stderr)
        return 1

    from repro.launch.cache import enable_compile_cache

    print(f"# compile cache: {enable_compile_cache()}")
    try:
        if args.four_chips:
            run_four_chips()
        else:
            run_one_chip()
    except SmokeError as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
