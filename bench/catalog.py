"""Finds the benchmark's parts by the names in ``BENCHMARK.json``.

* a configuration: the ``file`` its entry names (``bench/configs/*.json``);
  its sizes, generator (``bench/corpus.py``), index, search settings and
  limits. It may add a ``labels`` block (``vocabulary``,
  ``tags_per_vector``, ``zipf_exponent``, ``cluster_tags``,
  ``cluster_share``, ``query_tag_shares``, ``min_matches``; see
  ``corpus.make_labels``): each base vector then carries a tag set and each
  request a predicate. Such a configuration names an ``adapter`` in its
  file, a module under ``bench/`` with ``attach(index, offsets, tags,
  to_corpus)``, which hands the built index the tag sets (``to_corpus`` maps
  the index's row numbers to the corpus's), and ``request_filter(tags)``,
  which turns a predicate's tags into the hashable value the program's
  ``submit`` takes as ``filter``. The program's filter interface is used
  there and nowhere else in the benchmark;
* a traffic mix: ``bench/traffic/<traffic>.json``;
* a per-layer metric: ``bench/metrics/<metric>.py``, a module with
  ``read(record) -> float | None``;
* the device's peaks: ``bench/peaks.json``, keyed by ``device_kind``.

Adding a configuration, a mix or a metric is adding its file and its entry;
nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
BENCH_DIR = "bench"


def load_benchmark(root: Path = ROOT) -> dict:
    with open(Path(root) / "BENCHMARK.json") as f:
        return json.load(f)


def workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    known = ", ".join(w["name"] for w in bench["workloads"])
    raise KeyError(f"no workload {name!r} in BENCHMARK.json ({known})")


def config(bench: dict, name: str, root: Path = ROOT) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            with open(Path(root) / c["file"]) as f:
                return json.load(f)
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def traffic(name: str, root: Path = ROOT) -> dict:
    with open(Path(root) / BENCH_DIR / "traffic" / f"{name}.json") as f:
        return json.load(f)


def _applies(metric: dict, workload_name: str) -> bool:
    cells = metric.get("workloads")
    return cells is None or workload_name in cells


def end_to_end(bench: dict, workload_name: str) -> list:
    """Names of the end-to-end metrics this cell reports."""
    return [m["name"] for m in bench["end_to_end"]
            if _applies(m, workload_name)]


def per_layer(bench: dict, workload_name: str) -> list:
    """Names of the per-layer metrics this cell reports."""
    return [m["name"] for m in bench["per_layer"]
            if _applies(m, workload_name)]


def units(bench: dict) -> dict:
    return {m["name"]: m["unit"]
            for m in bench["end_to_end"] + bench["per_layer"]}


def _module(path: Path, prefix: str):
    spec = importlib.util.spec_from_file_location(
        prefix + "".join(c if c.isalnum() else "_" for c in path.stem), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(name: str, root: Path = ROOT):
    """The ``read`` function of ``bench/metrics/<name>.py``."""
    path = Path(root) / BENCH_DIR / "metrics" / f"{name}.py"
    return _module(path, "bench_metric_").read


def adapter(config: dict, root: Path = ROOT):
    """The module that a configuration's ``adapter`` names, or None where
    it names none."""
    name = config.get("adapter")
    if name is None:
        return None
    path = (Path(root) / name).resolve()
    if (Path(root) / BENCH_DIR).resolve() not in path.parents:
        raise ValueError(f"adapter {name!r} of {config['name']!r} is not "
                         f"a file under {BENCH_DIR}/")
    return _module(path, "bench_adapter_")


def peaks(device_kind: str, root: Path = ROOT) -> dict:
    with open(Path(root) / BENCH_DIR / "peaks.json") as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (known: {', '.join(table)})")
    return table[device_kind]
