"""The system under test, as the benchmark opens it: the program's index
build over the benchmark's corpus, its ``ServingEngine``, the warm-up of
every shape a cell's traffic uses, and the counters a traced run reads.

This is the only module of the benchmark that imports the program, besides
the configurations' adapters (``catalog.adapter``): they alone use the
program's filter interface, so that a configuration can bring its own.
"""
from __future__ import annotations

import numpy as np


def proxima_config(config: dict, seed: int):
    """The program's ``ProximaConfig`` for a configuration file."""
    from repro.configs.base import (
        DatasetConfig, GraphConfig, PQConfig, ProximaConfig, SearchConfig,
    )

    ix, s = config["index"], config["search"]
    return ProximaConfig(
        dataset=DatasetConfig(name=config["name"],
                              num_base=int(config["num_base"]),
                              num_queries=0, dim=int(config["dim"]),
                              metric=config["metric"], seed=seed),
        pq=PQConfig(num_subvectors=ix["pq_subvectors"],
                    num_centroids=ix["pq_centroids"],
                    kmeans_iters=ix["pq_kmeans_iters"], seed=seed),
        graph=GraphConfig(max_degree=ix["max_degree"],
                          build_list_size=ix["build_list_size"],
                          alpha=ix["alpha"], seed=seed),
        search=SearchConfig(k=int(config["k"]), list_size=s["list_size"],
                            t_init=s["t_init"], t_step=s["t_step"],
                            repetition_rate=s["repetition_rate"],
                            beta=s["beta"]),
        hot_node_fraction=ix["hot_node_fraction"],
    )


def build(config: dict, base: np.ndarray, seed: int):
    """(index, internal -> corpus id map). The index renumbers the corpus
    (hot nodes first); answers come back in its numbering.

    The build is the offline phase, and it runs on the host: the program's
    JAX steps of it (PQ k-means and encoding) go to the CPU device, so the
    index is the same on every platform. Built on a TPU, the angular
    configuration's index serves a recall@10 of 0.28 where the same
    program's host build serves 0.82 (``PERF.md``, Open questions)."""
    import jax

    from repro.core import build_index
    from repro.core.dataset import Dataset

    cfg = proxima_config(config, seed)
    k = int(config["k"])
    ds = Dataset(base=base, queries=np.zeros((0, base.shape[1]), np.float32),
                 gt=np.zeros((0, k), np.int32), metric=config["metric"],
                 config=cfg.dataset)
    with jax.default_device(jax.devices("cpu")[0]):
        index = build_index(
            cfg, dataset=ds,
            reorder_samples=int(config["index"]["reorder_samples"]))
    if index.reordering is None:
        to_corpus = np.arange(base.shape[0])
    else:
        to_corpus = np.asarray(index.reordering.inv, np.int64)
    return index, to_corpus


def open_engine(index, traffic: dict, metrics: bool,
                search_changes: dict | None = None):
    """``ServingEngine`` with the mix's scheduler; ``metrics`` turns the
    engine's own registry on (traced runs only). ``search_changes`` alters
    the index's search settings (the control's traversal fault only)."""
    import dataclasses

    from repro.configs.base import ObsConfig
    from repro.serve.engine import ServingEngine

    import jax

    cfg = None
    if search_changes:
        cfg = dataclasses.replace(index.config.search, **search_changes)
    continuous = traffic["scheduler"] == "continuous"
    engine = ServingEngine(
        index, batch_size=int(traffic["batch_size"]), cfg=cfg,
        flush_us=float(traffic.get("flush_us", 2000.0)),
        continuous=continuous,
        slots=int(traffic["slots"]) if continuous else None,
        obs=ObsConfig(metrics=True) if metrics else None,
    )
    want = jax.devices()[0].platform
    where = {d.platform for a in jax.tree_util.tree_leaves(
        engine.searcher.corpus) for d in a.devices()}
    if where != {want}:
        raise RuntimeError(f"the served corpus is on {where}, not {want}")
    return engine


def _pow2_upto(n: int):
    b = 1
    while b <= n:
        yield b
        b *= 2


def _filter_representatives(filters, admits, uses) -> list:
    """[(pool row, most requests)]: the pool rows whose filters warm every
    filtered shape, and the most requests that share one filter in a
    window. The program's filtered shapes depend on a filter only through
    how many base rows it admits (the regime, the widened search list, the
    scan's power-of-two length), and each of those is monotone in it; so
    the filters that admit the fewest and the most rows within each band
    of that count (2**(j - 1), 2**j] cover every shape of the band."""
    groups: dict = {}
    for row, f in enumerate(filters):
        groups.setdefault(f, []).append(row)
    bands: dict = {}
    for rows in groups.values():
        most = int(sum(uses[r] for r in rows))
        if most:
            n = int(admits[rows[0]])
            bands.setdefault((n - 1).bit_length(), []).append(
                (n, rows[0], most))
    out = []
    for band in bands.values():
        most = max(m for _, _, m in band)
        for row in sorted({min(band)[1], max(band)[1]}):
            out.append((row, most))
    return out


def warm_up(engine, queries: np.ndarray, filters=None, admits=None,
            uses=None) -> None:
    """Compile every shape the window can use, through the engine's own
    path: each power-of-two batch bucket up to ``batch_size``; in
    continuous mode also the slot pool's init/step/refill and the retire
    gather + finalize at every power-of-two row count up to ``slots``.
    Leaves the engine idle with its completed map empty.

    With ``filters`` (each pool row's), ``admits`` (the base rows each
    admits) and ``uses`` (how many requests of a window carry each row)
    the window's requests are all filtered: each bucket, or the slot pool,
    is warmed with the filters of ``_filter_representatives``, and a
    bucket only up to the most requests that share a filter."""
    from repro.serve import engine as engine_mod

    if filters is None:
        work = [(None, None, 2 * engine.slots if engine.continuous
                 else engine.batch_size)]
    else:
        work = [(row, filters[row], most) for row, most in
                _filter_representatives(filters, admits, uses)]
    for row, f, most in work:
        def submit(i):
            if f is None:
                engine.submit(queries[i % len(queries)])
            else:
                engine.submit(queries[row], filter=f)

        if engine.continuous and f is None:
            # twice the pool: the second half refills slots of a live state
            for i in range(2 * engine.slots):
                submit(i)
            engine.drain()
        elif engine.continuous:
            # one pool a filter; later sizes refill slots of a live state
            for b in _pow2_upto(min(2 * engine.slots, most)):
                for i in range(b):
                    submit(i)
                engine.drain()
        else:
            for b in _pow2_upto(min(engine.batch_size,
                                    1 << (most - 1).bit_length())):
                for i in range(b):
                    submit(i)
                engine.step(force=True)
    if engine.continuous:
        for pool in engine._pools.values():
            for b in _pow2_upto(len(pool.requests)):
                rows = np.zeros((b,), np.int64)
                core = pool.session.finalize(
                    engine_mod._gather_rows(pool.state, rows))
                np.asarray(core.ids)
    engine.drain()
    engine.done.clear()
    if engine.obs.metrics.enabled:
        engine.obs.metrics.clear()


class Counters:
    """Per-dispatch traversal counters of a traced window: one entry per
    flushed batch (``bucket`` lanes, the first ``n`` real) or per retired
    lane group (continuous; ``bucket`` is None). Reads the core
    ``SearchResult`` that the plan layer returns with each execution."""

    FIELDS = ("rounds", "n_hops", "n_pq", "n_acc")

    def __init__(self, engine):
        self.entries: list = []
        self.on = True
        self._seen = 0
        if engine.continuous:
            for sess in engine._sessions.values():
                if sess is not None:
                    sess.complete = self._wrap_complete(sess.complete)
            # a filter that first comes in the window opens a session then
            planner = engine.searcher.planner
            planner.round_session = self._wrap_session(planner.round_session)
        else:
            engine.searcher.execute = self._wrap_execute(
                engine.searcher.execute)

    def _record(self, core, bucket):
        if not self.on:
            return
        e = {f: np.asarray(getattr(core, f)) for f in self.FIELDS}
        e["bucket"] = bucket
        e["n"] = None if bucket is not None else len(e["rounds"])
        self.entries.append(e)

    def _wrap_execute(self, execute):
        def wrapped(plan, queries):
            ex = execute(plan, queries)
            self._record(ex.counters, len(queries))
            return ex
        return wrapped

    def _wrap_session(self, round_session):
        def wrapped(plan):
            sess = round_session(plan)
            if sess is not None:
                sess.complete = self._wrap_complete(sess.complete)
            return sess
        return wrapped

    def _wrap_complete(self, complete):
        def wrapped(queries, core_rows):
            self._record(core_rows, None)
            return complete(queries, core_rows)
        return wrapped

    def after_step(self, completed: list) -> None:
        """Give the batches flushed in this step their real row count."""
        for e in self.entries[self._seen:]:
            if e["n"] is None:
                e["n"] = len(completed)
        self._seen = len(self.entries)
