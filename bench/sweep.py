"""Find the knee of one configuration under one scheduler, once, on the chip.

    python bench/sweep.py --config <config> --traffic <mix> --seed <n> \
        --seconds <s> [--start <q/s>] [--bisect <steps>]

Builds the index once and serves open-loop windows of the mix's arrival
process at rates that double from ``--start`` until the engine no longer
keeps up, then bisects between the last rate it sustained and the first it
did not. A rate is sustained when, at the window's close, no more requests
are still waiting than two batches' worth or 2% of those offered, whichever
is more. The mix's own ``load`` and ``knee_qps`` are ignored. Prints one
JSON line per window and, last, ``{"knee_qps": ...}``. The cells then fix
their rate as a multiple of that knee in their traffic files; the benchmark's
runs never sweep.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import numpy as np  # noqa: E402

from bench import catalog, check, client, corpus, run  # noqa: E402


def window(engine, data, traffic, rate, seconds, seed, k):
    """One window, each request with its pool row's filter, if any:
    (pool rows, log, requests late at the close)."""
    due, rows = run.window_rows(traffic, rate, seconds, len(data.pool), seed)
    queries, filters, _ = data.requests(rows)
    log = client.drive(engine, queries, due, seconds, k, grace=30.0,
                       filters=filters)
    engine.done.clear()
    late = int((~log.answered | (log.done > seconds)).sum())
    return rows, log, late


def sustained(late: int, offered: int, batch: int) -> bool:
    return late <= max(2 * batch, 0.02 * offered)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--start", type=float, default=25.0)
    ap.add_argument("--bisect", type=int, default=4)
    args = ap.parse_args(argv)

    run.enable_compile_cache(ROOT)
    dev = run.require_chips(1)[0]
    sys.path.insert(0, str(ROOT / "src"))
    from bench import system

    bench = catalog.load_benchmark()
    config = catalog.config(bench, args.config)
    traffic = catalog.traffic(args.traffic)
    k = int(config["k"])
    data = run.load_data(config)
    index, to_corpus = system.build(config, data.base,
                                    corpus.build_seed(config))
    data.attach(index, to_corpus)
    engine = system.open_engine(index, traffic, metrics=False)
    batch = int(traffic["batch_size"])
    # rates are not known before the sweep: warm every bucket
    run.warm_up(engine, data, batch * len(data.pool))

    def trial(rate, i):
        rows, log, late = window(engine, data, traffic, rate,
                                 args.seconds, args.seed + i, k)
        ok = sustained(late, len(log.due), batch)
        lat = log.latency_ms
        got = np.flatnonzero(log.answered)[:512]
        served = to_corpus[np.maximum(log.ids[got], 0)]
        queries, _, predicates = data.requests(rows[got])
        truth = check.exact_knn(queries, data.base, k, config["metric"],
                                tag_sets=data.tag_sets,
                                predicates=predicates)
        print(json.dumps({
            "rate": rate, "offered": len(log.due), "qps": client.qps(log),
            "late_at_close": late, "sustained": ok,
            "p50_ms": client.percentile(lat, 50),
            "p99_ms": client.percentile(lat, 99),
            "recall_at_10": check.recall_at_k(served, truth, k),
            "device": dev.device_kind}), flush=True)
        return ok

    good, bad, rate, i = 0.0, None, args.start, 0
    while bad is None:
        if trial(rate, i):
            good, rate = rate, rate * 2
        else:
            bad = rate
        i += 1
    for _ in range(args.bisect):
        mid = (good + bad) / 2
        if trial(mid, i):
            good = mid
        else:
            bad = mid
        i += 1
    print(json.dumps({"config": args.config, "traffic": args.traffic,
                      "knee_qps": good, "first_unsustained_qps": bad,
                      "device": dev.device_kind}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
