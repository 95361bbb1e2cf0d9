"""Flat vs segmented build: peak builder RSS and served recall at equal
corpus size — the memory claim behind the out-of-core builder.

The monolithic pipeline's working set is dominated by the exact-kNN
temporaries of the graph build (an O(n^2) distance block plus argpartition
scratch); the segmented builder bounds those by the SEGMENT, so its peak
RSS must sit well below the flat build's while the stitched graph serves
recall@10 within 1% of the flat-built index.

Peak RSS is a PROCESS-lifetime high-water mark (``resource.getrusage``
never goes down), so each build mode runs in its own child subprocess; the
parent collects one JSON line per child.

``--smoke`` asserts (loudly) that segmented peak RSS < flat peak RSS and
segmented recall@10 >= flat recall@10 - 0.01.

    PYTHONPATH=src python -m benchmarks.build_bench [--smoke]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

NUM_BASE = 4000
NUM_SEGMENTS = 4
DIM = 64


def _bench_cfg():
    from repro.configs.base import (
        DatasetConfig, GraphConfig, PQConfig, ProximaConfig, SearchConfig,
    )

    return ProximaConfig(
        dataset=DatasetConfig(name="sift-like", num_base=NUM_BASE,
                              num_queries=64, dim=DIM, num_clusters=16,
                              cluster_std=0.3, seed=0),
        pq=PQConfig(num_subvectors=8, num_centroids=64, kmeans_iters=8),
        graph=GraphConfig(max_degree=24, build_list_size=48, alpha=1.2),
        search=SearchConfig(k=10, list_size=64, t_init=16, t_step=8,
                            repetition_rate=3, beta=1.06),
        hot_node_fraction=0.03,
    )


def _child(mode: str) -> None:
    """Build in ``mode`` (flat | segmented), serve the held-out queries
    through the flat engine, print ONE json line: peak RSS + recall +
    build seconds (+ stitch/NAND accounting for the segmented mode)."""
    import resource

    import jax.numpy as jnp
    import numpy as np

    from repro.core.dataset import make_dataset, recall_at_k
    from repro.core.search import graph_search

    cfg = _bench_cfg()
    ds = make_dataset(cfg.dataset)
    t0 = time.perf_counter()
    extra = {}
    if mode == "flat":
        from repro.core.index import build_index_monolithic

        index = build_index_monolithic(cfg, dataset=ds, reorder_samples=16)
    else:
        from repro.core.segmented import build_segmented
        from repro.nand.simulator import simulate_build

        seg = build_segmented(cfg, dataset=ds, reorder_samples=16,
                              segment_size=NUM_BASE // NUM_SEGMENTS)
        sim = simulate_build(seg.build_trace())
        extra = {
            "num_segments": seg.num_segments,
            "cross_edges": seg.stitch.cross_edges,
            "build_write_amplification": sim.write_amplification,
        }
        index = seg.to_flat()
    build_s = time.perf_counter() - t0

    res = graph_search(index.corpus(), jnp.asarray(ds.queries),
                       cfg.search, ds.metric)
    recall = recall_at_k(np.asarray(res.ids), index.dataset.gt, 10)
    # ru_maxrss: KB on Linux — the process high-water mark, which the build
    # temporaries dominate at this scale
    peak_kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    print(json.dumps({
        "mode": mode, "peak_rss_mb": peak_kb / 1024.0,
        "recall_at_10": recall, "build_s": build_s, **extra,
    }))


def _run_child(mode: str) -> dict:
    # the child measures host RSS and needs no chip; the parent may hold it
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = os.path.abspath(src) + os.pathsep \
        + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, "-m", "benchmarks.build_bench", "--child", mode],
        capture_output=True, text=True, timeout=1800, env=env,
        cwd=os.path.join(os.path.dirname(__file__), ".."),
    )
    if r.returncode != 0:
        raise RuntimeError(
            f"build_bench child {mode!r} failed:\n{r.stderr[-2000:]}"
        )
    return json.loads(r.stdout.strip().splitlines()[-1])


def main(out=print, smoke: bool = False) -> None:
    flat = _run_child("flat")
    seg = _run_child("segmented")
    for row in (flat, seg):
        out(
            f"build_{row['mode']},{row['build_s'] * 1e6:.0f},"
            f"peak_mb={row['peak_rss_mb']:.1f};recall={row['recall_at_10']:.4f}"
        )
    out(
        f"build_segmented_vs_flat,0.0,"
        f"rss_ratio={seg['peak_rss_mb'] / max(flat['peak_rss_mb'], 1e-9):.3f};"
        f"recall_delta={seg['recall_at_10'] - flat['recall_at_10']:+.4f};"
        f"segments={seg['num_segments']};"
        f"build_wa={seg['build_write_amplification']:.3f}"
    )
    if smoke:
        assert seg["peak_rss_mb"] < flat["peak_rss_mb"], (
            f"segmented peak RSS {seg['peak_rss_mb']:.1f} MB must be BELOW "
            f"flat {flat['peak_rss_mb']:.1f} MB — the out-of-core working "
            "set is not bounded by the segment"
        )
        assert seg["recall_at_10"] >= flat["recall_at_10"] - 0.01, (
            f"segmented recall {seg['recall_at_10']:.4f} fell more than 1% "
            f"below flat {flat['recall_at_10']:.4f} — stitching lost "
            "navigability"
        )
        out("build_bench_smoke,0.0,ok")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--child", default="",
                    help="internal: run one build mode in-process")
    args = ap.parse_args()
    if args.child:
        _child(args.child)
    else:
        main(smoke=args.smoke)
