"""The controls of the comparison that decides ``correct``, and the
program's own readings beside them, in one process.

Two controls have to come out as not correct:

* the precision control: the reference in the program's place one precision
  lower. The configurations state float32 vectors and distances; the
  control stores the corpus and the queries in bfloat16 (the step that would
  tempt a later change: half the bytes per raw vector) and runs exact search
  in float32 arithmetic over the rounded values on the device. It fails
  ``dist_gap_max``. Vectors of whole numbers 0..255 (``assumed.quantize``
  ``uint8``) are exact in bfloat16, so for them the control keeps 4 bits of
  each value (``int4``).
* the traversal fault: the program itself with its traversal capped at
  ``FAULT_ROUNDS`` rounds (a sound query takes about twice as many), served
  through the same engine, scheduler and traffic. Its answers carry exact
  distances (the rerank is intact), so only the recall floor can fail it.
  (Halving the search list instead moves recall by under 0.002: early
  termination, not the list, ends these searches.)
* the filter fault, for a configuration with ``labels``: the program's
  engine serving the same requests without their filters. Its answers
  break their predicates, which ``bad_ids`` counts.

With labels every control keeps the requests' predicates: the precision
control searches only the rows each predicate admits.

    python bench/control.py --workload <cell> --seconds <s>[,<s>...] \\
        [--program-seeds a,b,...] [--control-seeds x,y,z] [--rate <q/s>] \
        [--control-seconds <s>]

builds the cell's index once, then drives one window of the cell's traffic
per program seed and length through the program's engine, and one per
control seed through each fault's engine, and runs the precision control on
the requests of each control seed (at ``--control-seconds``, by default
the first length). Each prints one JSON line: the readings
against the limits, and the window's latency and throughput. ``--rate``
replaces the cell's rate (a trial of another load). The benchmark's own
runs never run this; ``bench/tests/test_control.py`` runs the controls at a
small size.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import numpy as np  # noqa: E402

from bench import arrivals, catalog, check, client, corpus  # noqa: E402

# the traversal fault's cap on rounds
FAULT_ROUNDS = 16


def lower_precision(config: dict) -> str:
    """The precision the control stores a configuration's vectors in."""
    return "int4" if config["assumed"].get("quantize") == "uint8" \
        else "bfloat16"


def control_answers(base: np.ndarray, queries: np.ndarray, k: int,
                    metric: str, chunk: int = 512,
                    tag_sets: check.TagSets | None = None,
                    predicates: np.ndarray | None = None,
                    precision: str = "bfloat16"):
    """(ids, dists) of exact top-k search over vectors stored in
    ``precision`` (``bfloat16``, or ``int4``: a value 0..255 kept as the
    middle of its 16-wide step), computed in float32 on the default JAX
    device; with ``predicates``, over the base rows of ``tag_sets`` that
    each query's predicate admits."""
    import jax
    import jax.numpy as jnp

    def stored(x):
        x = jnp.asarray(x, jnp.float32)
        if precision == "int4":
            x = jnp.floor(x / 16.0) * 16.0 + 8.0
        if metric == "angular":
            x = x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True),
                                1e-12)
        if precision == "bfloat16":
            x = x.astype(jnp.bfloat16).astype(jnp.float32)
        return x

    @jax.jit
    def top(q, b, admitted):
        if metric == "angular":
            d = -jnp.matmul(q, b.T, precision="highest")
        else:
            d = (jnp.sum(b * b, -1)[None, :]
                 - 2.0 * jnp.matmul(q, b.T, precision="highest"))
        if admitted is not None:
            d = jnp.where(admitted, d, jnp.inf)
        _, ids = jax.lax.top_k(-d, k)
        x = b[ids]                                       # (Q, k, D)
        if metric == "angular":
            dist = -jnp.sum(x * q[:, None, :], -1)
        else:
            dist = jnp.sum((x - q[:, None, :]) ** 2, -1)
        return ids, dist

    b = stored(base)
    ids, dists = [], []
    for s in range(0, len(queries), chunk):
        admitted = None if predicates is None else tag_sets.admitted(
            predicates[s:s + chunk])
        i, d = top(stored(queries[s:s + chunk]), b, admitted)
        ids.append(np.asarray(i))
        dists.append(np.asarray(d))
    return np.concatenate(ids).astype(np.int64), np.concatenate(dists)


def readings(config: dict, base, queries, ids, dists, answered=None,
             tag_sets=None, predicates=None) -> dict:
    """The comparison of a run, over these answers."""
    if answered is None:
        answered = np.ones(len(queries), bool)
    r = check.compare(queries, base, ids, dists, answered, config["metric"],
                      tag_sets=tag_sets, predicates=predicates)
    correct, failed, shown = check.verdict(r, config["limits"])
    return {"correct": correct, "failed": failed, "checks": shown}


def served_ids(log: client.ClientLog, to_corpus: np.ndarray) -> np.ndarray:
    """A window's answers in the corpus's id space, -1 where unanswered."""
    ids = np.where(log.ids >= 0, to_corpus[np.maximum(log.ids, 0)], -1)
    return np.where(log.answered[:, None], ids, -1)


def window(engine, config, traffic, rate, seconds, data, to_corpus,
           seed, drop_filters: bool = False) -> dict:
    """One window of the cell's traffic through ``engine``, each request
    with its filter unless ``drop_filters``: its readings and its latency
    and throughput."""
    from bench.run import window_rows

    k = int(config["k"])
    due, rows = window_rows(traffic, rate, seconds, len(data.pool), seed)
    queries, filters, predicates = data.requests(rows)
    log = client.drive(engine, queries, due, seconds, k,
                       filters=None if drop_filters else filters)
    engine.done.clear()
    out = readings(config, data.base, queries, served_ids(log, to_corpus),
                   log.dists, log.answered, data.tag_sets, predicates)
    lat = log.latency_ms
    out.update(requests=len(due), qps=client.qps(log),
               **{f"p{q}_ms": client.percentile(lat, q)
                  for q in (50, 90, 99)})
    return out


def _seeds(text: str) -> list:
    return [int(s) for s in text.split(",") if s]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", required=True,
                    help="window length(s), comma-separated")
    ap.add_argument("--program-seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--rate", type=float, default=None)
    ap.add_argument("--control-seconds", type=float, default=None)
    args = ap.parse_args(argv)

    from bench import run

    run.enable_compile_cache(ROOT)
    dev = run.require_chips(1)[0]
    sys.path.insert(0, str(ROOT / "src"))
    from bench import system

    bench = catalog.load_benchmark()
    cell = catalog.workload(bench, args.workload)
    config = catalog.config(bench, cell["config"])
    traffic = catalog.traffic(cell["traffic"])
    rate = args.rate or float(traffic["load"]) * float(traffic["knee_qps"])
    lengths = [float(s) for s in args.seconds.split(",")]
    control_lengths = [args.control_seconds or lengths[0]]
    data = run.load_data(config)
    index, to_corpus = system.build(config, data.base,
                                    corpus.build_seed(config))
    data.attach(index, to_corpus)
    requests = max(arrivals.count(rate, s)
                   for s in lengths + control_lengths)
    device = {"platform": dev.platform, "kind": dev.device_kind}

    def emit(kind, seed, seconds, out):
        out.update(kind=kind, workload=cell["name"], seed=seed,
                   seconds=seconds, rate=rate, device=device)
        print(json.dumps(out), flush=True)

    runs = [("program", None, _seeds(args.program_seeds), lengths),
            ("rounds_cut", {"max_rounds": FAULT_ROUNDS},
             _seeds(args.control_seeds), control_lengths)]
    if data.labels is not None:
        runs.append(("filter_dropped", None, _seeds(args.control_seeds),
                     control_lengths))
    for kind, changes, seeds, seconds_list in runs:
        if not seeds:
            continue
        engine = system.open_engine(index, traffic, metrics=False,
                                    search_changes=changes)
        run.warm_up(engine, data, requests)
        for seed in seeds:
            for seconds in seconds_list:
                emit(kind, seed, seconds,
                     window(engine, config, traffic, rate, seconds, data,
                            to_corpus, seed,
                            drop_filters=kind == "filter_dropped"))
        del engine
    for seed in _seeds(args.control_seeds):
        for seconds in control_lengths:
            rows = run.window_rows(traffic, rate, seconds, len(data.pool),
                                   seed)[1]
            queries, _, predicates = data.requests(rows)
            precision = lower_precision(config)
            ids, dists = control_answers(
                data.base, queries, int(config["k"]), config["metric"],
                tag_sets=data.tag_sets, predicates=predicates,
                precision=precision)
            out = readings(config, data.base, queries, ids, dists,
                           tag_sets=data.tag_sets, predicates=predicates)
            out["requests"] = len(queries)
            emit(precision, seed, seconds, out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
