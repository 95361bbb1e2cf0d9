"""One benchmark run of one cell on the chip.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration and a
traffic mix. One run, in one process:

1. generates the configuration's corpus and query pool (with its tags and
   predicates, where it has ``labels``), and draws the window's traffic
   from ``--seed`` (``bench/corpus.py``);
2. builds the index with the program's ``build_index``, on the host, and
   hands it the tag sets through the configuration's adapter;
3. opens ``ServingEngine`` with the mix's scheduler and warms up every shape
   the mix can use (``bench/system.py``);
4. offers ``round(rate * seconds)`` requests open-loop over ``--seconds``
   (``bench/arrivals.py``, ``bench/client.py``), each with the filter of
   its pool row where the configuration has labels, then waits for the
   stragglers;
5. compares every answer with the float64 brute-force reference
   (``bench/check.py``) and prints the result as one JSON line.

``--trace 0`` reports the cell's end-to-end metrics. ``--trace 1`` runs the
same window with the engine's own metrics on, then ``TRACE_SECONDS`` more
of the same traffic under the JAX profiler, and reports the per-layer
metrics (``bench/metrics/*.py``): host-side ones from the window, device
ones and the traversal counters from the traced seconds, with the device's
busy time and a breakdown.

It runs only on a TPU: without one, or with fewer chips than the cell asks
for, it exits 3 and prints no result. JAX's compilation cache is kept in
``.jax_cache/`` at the checkout's root.
"""
from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, NamedTuple, Optional  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import numpy as np  # noqa: E402

from bench import arrivals, catalog, check, client, corpus  # noqa: E402

EXIT_NO_CHIP = 3
# a traced run adds this many seconds of the cell's traffic under the
# profiler after its window: device metrics come from it alone
TRACE_SECONDS = 2.0
LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def enable_compile_cache(root: Path) -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout;
    every program is cached, however fast it compiled."""
    import jax

    path = str(Path(root) / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def require_chips(chips: int) -> list:
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX found {devices[0].platform}, not a TPU")
    if len(devices) < chips:
        raise NoChip(f"{len(devices)} chips, the cell needs {chips}")
    return devices


class Data(NamedTuple):
    """A configuration's data: its corpus and query pool and, where it has
    ``labels``, their tags, the tag sets as the reference reads them, the
    adapter, and each pool row's filter as the program takes it."""
    base: np.ndarray
    pool: np.ndarray
    labels: Optional[corpus.Labels] = None
    tag_sets: Optional[check.TagSets] = None
    adapter: Any = None
    filters: Optional[list] = None

    def requests(self, rows: np.ndarray):
        """(queries, filters or None, predicates or None) of requests that
        carry pool rows ``rows``."""
        if self.labels is None:
            return self.pool[rows], None, None
        return (self.pool[rows], [self.filters[r] for r in rows],
                self.labels.predicates[rows])

    def attach(self, index, to_corpus: np.ndarray) -> None:
        """Hand the built index the base rows' tag sets."""
        if self.adapter is not None:
            self.adapter.attach(index, self.labels.offsets, self.labels.tags,
                                to_corpus)


def load_data(config: dict, root: Path = ROOT) -> Data:
    """The configuration's data, generated from its ``data_seed``."""
    base, pool = corpus.make_corpus(config)
    if "labels" not in config:
        return Data(base, pool)
    adapter = catalog.adapter(config, root)
    if adapter is None:
        raise ValueError(f"configuration {config['name']!r} has labels and "
                         "names no adapter")
    labels = corpus.make_labels(config)
    filters = [adapter.request_filter(tuple(int(t) for t in p[p >= 0]))
               for p in labels.predicates]
    return Data(base, pool, labels, check.TagSets(labels.offsets,
                                                  labels.tags),
                adapter, filters)


def row_uses(requests: int, pool: int) -> np.ndarray:
    """(pool,) how many of a window's ``requests`` carry each pool row
    (``query_order``)."""
    return np.bincount(np.arange(requests) % pool, minlength=pool)


def query_order(rng, n: int, pool: int) -> np.ndarray:
    """Pool row of each of ``n`` requests: rows ``0 .. n-1`` (modulo the
    pool), the same set on every seed, in an order drawn from ``rng``. The
    seed moves when each query comes, not which queries come, so a sound
    run's recall is the same number on every seed."""
    return rng.permutation(np.arange(n) % pool)


class CompileCounter:
    """Records each jit lowering (a shape or program not compiled before in
    this process) with the time it happened."""

    def __init__(self):
        import jax

        self.times: list = []
        self.names: list = []
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **kwargs):
        if event == LOWERING_EVENT:
            self.times.append(time.perf_counter())
            self.names.append(kwargs.get("fun_name", "?"))

    def between(self, a: float, b: float) -> list:
        """Names of the programs lowered between ``a`` and ``b``."""
        return [n for t, n in zip(self.times, self.names) if a <= t <= b]


class GcPauses:
    """Records each pause of Python's garbage collector (``gc.callbacks``):
    the host runtime under the engine, which the window does not tune."""

    def __init__(self):
        self.spans: list = []
        self._start = None
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase, info):
        if phase == "start":
            self._start = time.perf_counter()
        elif self._start is not None:
            self.spans.append((self._start, time.perf_counter()))
            self._start = None

    def close(self) -> None:
        gc.callbacks.remove(self._on_gc)

    def seconds_between(self, a: float, b: float) -> float:
        """Seconds of pause that fall between ``a`` and ``b``."""
        return sum(max(0.0, min(e, b) - max(s, a)) for s, e in self.spans)


def end_to_end_value(name: str, log: client.ClientLog, recall: float,
                     setup_s: float) -> float:
    if name == "setup_s":
        return setup_s
    if name == "recall_at_10":
        return recall
    if name == "qps":
        return client.qps(log)
    if name == "p50_ms":
        return client.percentile(log.latency_ms, 50)
    raise KeyError(f"no end-to-end metric {name!r}")


def _device_info(devices, chips: int) -> dict:
    used = devices[:chips]
    peak = 0
    for d in used:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": used[0].platform, "kind": used[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def _host_plane(spans, window_ns: tuple) -> dict:
    """The client's own spans, on the trace's clock from the window's
    start ``window_ns[0]``."""
    lo = window_ns[0]
    events = [["bench.window", lo, window_ns[1] - lo]]
    events += [[name, lo + a * 1e9, (b - a) * 1e9] for name, a, b in spans]
    return {"name": "/host:bench", "spans": events}


def run(args, root: Path = ROOT, on_chip: bool = True,
        err=sys.stderr) -> dict:
    """One run; returns the result object (``correct`` and the rest)."""
    bench = catalog.load_benchmark(root)
    cell = catalog.workload(bench, args.workload)
    if on_chip:
        enable_compile_cache(root)
        devices = require_chips(int(cell["chips"]))
    else:
        import jax

        devices = jax.devices()
    sys.path.insert(0, str(ROOT / "src"))
    from bench import system

    config = catalog.config(bench, cell["config"], root)
    traffic = catalog.traffic(cell["traffic"], root)
    seed, seconds, k = args.seed, float(args.seconds), int(config["k"])
    rate = float(traffic["load"]) * float(traffic["knee_qps"])

    data = load_data(config, root)
    t_build = time.perf_counter()
    index, to_corpus = system.build(config, data.base,
                                    corpus.build_seed(config))
    data.attach(index, to_corpus)
    t_engine = time.perf_counter()
    engine = system.open_engine(index, traffic, metrics=bool(args.trace))
    windows = (seconds, TRACE_SECONDS) if args.trace else (seconds,)
    warm_up(engine, data, max(arrivals.count(rate, s) for s in windows))
    t_warm = time.perf_counter()

    due, rows = window_rows(traffic, rate, seconds, len(data.pool), seed)
    queries, filters, _ = data.requests(rows)

    stats0 = dict(engine.stats)
    compiles = CompileCounter()
    pauses = GcPauses() if args.trace else None
    t_open = time.perf_counter()
    setup_s = t_open - _T0
    log = client.drive(engine, queries, due, seconds, k, filters=filters)
    in_window = compiles.between(log.t0, log.t0 + seconds)
    if pauses is not None:
        pauses.close()
    stats = {name: v - stats0[name] for name, v in engine.stats.items()}
    queue_wait = None
    if engine.obs.metrics.enabled:
        queue_wait = engine.obs.metrics.merged_histogram("queue_wait_ms")
    logs = [(rows, log)]
    if args.trace:
        traced = traced_window(engine, traffic, rate, data, seed, k)
        logs.append(traced[:2])
        counters, reduced = traced[2:]
    if in_window:
        print(f"# lowered in the window: {', '.join(in_window)}", file=err)
    device = _device_info(devices, int(cell["chips"]))
    del engine, index
    gc.collect()

    # ---- reference, once the windows have closed and the engine is gone
    queries, _, predicates = data.requests(
        np.concatenate([r for r, _ in logs]))
    answered = np.concatenate([lg.answered for _, lg in logs])
    ids = np.concatenate([lg.ids for _, lg in logs])
    dists = np.concatenate([lg.dists for _, lg in logs])
    served = np.where(ids >= 0, to_corpus[np.maximum(ids, 0)], -1)
    served = np.where(answered[:, None], served, -1)
    truth = check.exact_knn(queries, data.base, k, config["metric"],
                            tag_sets=data.tag_sets, predicates=predicates)
    readings = check.compare(queries, data.base, served, dists, answered,
                             config["metric"], truth, data.tag_sets,
                             predicates)
    correct, failed, shown = check.verdict(readings, config["limits"])
    got = np.flatnonzero(answered[:len(log.due)])
    recall = check.recall_at_k(served[got], truth[got], k)

    units = catalog.units(bench)
    metrics = {}
    if args.trace:
        record = {
            "log": log, "engine": stats, "counters": counters.entries,
            "compiles_in_window": len(in_window), "queue_wait_ms": queue_wait,
            "gc_pause_s": pauses.seconds_between(log.t0, log.t0 + seconds),
            "trace": reduced, "config": config, "traffic": traffic,
            "peaks": catalog.peaks(device["kind"], root) if on_chip else None,
        }
        for name in catalog.per_layer(bench, cell["name"]):
            value = catalog.reader(name, root)(record)
            if value is not None:
                metrics[name] = {"value": float(value), "unit": units[name]}
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
    else:
        for name in catalog.end_to_end(bench, cell["name"]):
            metrics[name] = {"value": float(end_to_end_value(
                name, log, recall, setup_s)), "unit": units[name]}
    result = {"correct": bool(correct), "attempted": int(len(answered)),
              "failed": int(failed), "metrics": metrics, "device": device}
    if args.trace:
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = shown
    print(f"# {cell['name']} seed {seed}: set-up {setup_s:.3f} s (build "
          f"{t_engine - t_build:.1f} s, engine open + warm-up "
          f"{t_warm - t_engine:.1f} s); {len(due)} requests at "
          f"{rate:.4f}/s over {seconds} s, "
          f"{stats['batches']} batches, {stats['ticks']} ticks, "
          f"{len(in_window)} compiles in the window", file=err)
    if data.labels is not None:
        print(f"# filters: {stats['filtered_queries']} filtered queries, "
              f"{stats['filter_scan_batches']} scan batches, "
              f"{stats['batches'] / max(stats['queries'], 1):.4f} batches "
              f"a query", file=err)
    lat = log.latency_ms
    print("# window: " + ", ".join(
        f"p{q} {client.percentile(lat, q):.3f} ms" for q in (50, 90, 99))
        + f", {client.qps(log):.3f} queries/s", file=err)
    for name, c in shown.items():
        bound = "at least" if c.get("at_least") else "limit"
        print(f"check {name}: {c['value']!r} ({bound} {c['limit']!r})",
              file=err)
    return result


def window_rows(traffic: dict, rate: float, seconds: float, pool: int,
                seed: int, traced: bool = False):
    """(due times, pool rows) of the measured window, or of the traced
    one, over a pool of ``pool`` queries."""
    a, o = (corpus.STREAM_TRACE_ARRIVALS, corpus.STREAM_TRACE_ORDER) \
        if traced else (corpus.STREAM_ARRIVALS, corpus.STREAM_ORDER)
    due = arrivals.window_arrivals(traffic, rate, seconds,
                                   corpus.rng_for(seed, a))
    return due, query_order(corpus.rng_for(seed, o), len(due), pool)


def schedule(traffic: dict, rate: float, seconds: float, pool, seed: int,
             traced: bool = False):
    """(due times, queries) of the measured window, or of the traced one."""
    due, rows = window_rows(traffic, rate, seconds, len(pool), seed, traced)
    return due, pool[rows]


def warm_up(engine, data: Data, requests: int) -> None:
    """``system.warm_up`` for windows of at most ``requests`` requests."""
    from bench import system

    if data.labels is None:
        system.warm_up(engine, data.pool)
        return
    system.warm_up(engine, data.pool, data.filters, data.labels.admits,
                   row_uses(requests, len(data.pool)))


def traced_window(engine, traffic, rate, data: Data, seed, k):
    """A further ``TRACE_SECONDS`` of the same traffic under the profiler,
    after the measured window: (pool rows, log, counters, reduced trace)."""
    import jax

    from bench import system, trace_reduce

    due, rows = window_rows(traffic, rate, TRACE_SECONDS, len(data.pool),
                            seed, traced=True)
    queries, filters, _ = data.requests(rows)
    counters = system.Counters(engine)
    spans: list = []
    t_close = [float("inf")]

    def on_step(completed):
        if counters.on:
            counters.after_step(completed)
            # the traced window has closed: stop counting
            counters.on = time.perf_counter() <= t_close[0]

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        jax.profiler.start_trace(trace_dir)
        with jax.profiler.TraceAnnotation("bench.anchor"):
            anchor = time.perf_counter()
        t_close[0] = time.perf_counter() + TRACE_SECONDS
        log = client.drive(engine, queries, due, TRACE_SECONDS, k,
                           spans=spans, on_step=on_step, filters=filters)
        jax.profiler.stop_trace()
        t_read = time.perf_counter()
        trace = trace_reduce.from_xplane(trace_dir)
        print(f"# trace: stopped and read in "
              f"{time.perf_counter() - log.t0 - TRACE_SECONDS:.1f} s "
              f"(reading {time.perf_counter() - t_read:.1f} s)",
              file=sys.stderr)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    lo = _anchor_ns(trace) + (log.t0 - anchor) * 1e9
    trace["planes"].append(_host_plane(spans,
                                       (lo, lo + TRACE_SECONDS * 1e9)))
    return rows, log, counters, trace_reduce.reduce_trace(trace)


def _anchor_ns(trace: dict) -> float:
    for plane in trace["planes"]:
        for name, s, _ in plane.get("spans", ()):
            if name == "bench.anchor":
                return s
    raise ValueError("no bench.anchor annotation in the trace")


def main(argv=None) -> int:
    args = parse(argv)
    try:
        result = run(args)
    except NoChip as e:
        print(f"bench/run.py: {e}; this benchmark runs only on the chip",
              file=sys.stderr)
        return EXIT_NO_CHIP
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
