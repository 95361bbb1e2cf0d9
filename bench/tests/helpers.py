"""A small benchmark checkout for tests: the real metrics and generator,
one tiny configuration and traffic mix, and one cell using them; the
configuration may carry tags, served through ``tag_columns.py``."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
if str(REPO / "src") not in sys.path:
    sys.path.insert(0, str(REPO / "src"))       # the program under test


CONFIG, NUM_BASE = "sift128-l2", 1500
ADAPTER = "bench/adapters/tag_columns.py"
# tags over a vocabulary of 256, for the generator's and controls' tests
LABELS = {"vocabulary": 256, "tags_per_vector": [2, 8], "zipf_exponent": 1.0,
          "cluster_tags": 8, "cluster_share": 0.5,
          "query_tag_shares": [0.6, 0.4], "min_matches": 10}
# tags over a vocabulary of 64: predicates of one or two tags that admit
# from 10 to most of the 1,500 rows, so both the scan and the masked
# traversal serve
TINY_LABELS = {"vocabulary": 64, "tags_per_vector": [2, 6],
               "zipf_exponent": 1.0, "cluster_tags": 8, "cluster_share": 0.5,
               "query_tag_shares": [0.5, 0.5], "min_matches": 10}


def tiny_root(root: Path, scheduler: str = "batch",
              rate: float = 40.0, labels: bool = False) -> Path:
    """A checkout under ``root`` whose one cell, ``tiny``, serves a
    1,500-vector cut of ``sift128-l2`` at ``rate`` requests per second with
    the ``scheduler`` (``batch`` or ``continuous``), batches or slots of 8;
    with ``labels``, each vector has tags and each request a predicate."""
    (root / "bench" / "configs").mkdir(parents=True, exist_ok=True)
    (root / "bench" / "traffic").mkdir(parents=True, exist_ok=True)
    if not (root / "bench" / "metrics").exists():
        shutil.copytree(REPO / "bench" / "metrics", root / "bench" / "metrics")
    with open(REPO / "bench" / "configs" / f"{CONFIG}.json") as f:
        cfg = json.load(f)
    cfg.update(num_base=NUM_BASE, num_queries=128)
    cfg["assumed"]["num_clusters"] = 64
    if labels:
        cfg.update(labels=TINY_LABELS, adapter=ADAPTER)
        (root / ADAPTER).parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(REPO / "bench" / "tests" / "tag_columns.py",
                    root / ADAPTER)
    with open(root / "bench" / "configs" / f"{CONFIG}.json", "w") as f:
        json.dump(cfg, f)
    traffic = {"scheduler": scheduler, "batch_size": 8, "slots": 8,
               "flush_us": 2000, "burst_size": 4, "spread": 0.1,
               "arrivals": "burst" if scheduler == "continuous"
               else "poisson", "load": 1.0, "knee_qps": rate}
    with open(root / "bench" / "traffic" / "tiny.json", "w") as f:
        json.dump(traffic, f)
    with open(REPO / "BENCHMARK.json") as f:
        bench = json.load(f)
    bench["workloads"] = [{"name": "tiny", "config": CONFIG,
                           "traffic": "tiny", "chips": 1, "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.pop("workloads", None)
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    return root
