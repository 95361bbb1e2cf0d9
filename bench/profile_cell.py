"""Where a cell's host time goes, by the program's own spans, on the chip.

    python bench/profile_cell.py --workload <cell> --seeds <n> [<n> ...] \
        [--seconds 51] [--fixture <path.json.gz>]

One process, one chip. It builds the cell's index once, as ``run.py`` does,
and opens two engines on it with the cell's scheduler: one with the
engine's metrics on (as a traced benchmark run opens it), one with its
tracer on as well. For each seed, the traced engine serves a measured
window of the cell's traffic; then each engine serves ``TRACE_SECONDS`` of
it under the JAX profiler. It prints to stderr the three longest steps of
each measured window with their spans, and one JSON line to stdout:

* from the measured window (tracer events and registry):
  ``sched_host_ms_per_step``, ``plan_host_ms_per_dispatch``,
  ``host_syncs_per_query``, ``p50_ms``, ``dropped_events``;
* from each profiled window, tracing off and on: ``device_idle_share``,
  the client's mean ``bench.step`` duration (``step_ms``), ``idle_gaps``;
  and, tracing on, ``idle_by_span`` (also by span path),
  ``device_scopes`` and ``gather_device_ms_per_query``.

Device ops are named by scope through the HLO text of the traversal
programs, compiled again at every batch shape the engine runs (a v5e's op
events carry no ``op_name``). ``--fixture`` writes 300 ms of the first
seed's traced profile in the plain form of ``bench/spans.py``, for the
tests. Off a TPU it exits 3, as ``run.py`` does.
"""
from __future__ import annotations

import argparse
import gzip
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import numpy as np  # noqa: E402

from bench import catalog, client, corpus, run, spans, system  # noqa: E402
from bench import trace_reduce  # noqa: E402

TRACE_SECONDS = 2.0
FIXTURE_NS = 300e6
TOP_STEPS = 3


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--fixture", default=None)
    return ap.parse_args(argv)


def open_engine(index, traffic: dict, tracing: bool):
    """The cell's ``ServingEngine`` (as ``system.open_engine`` opens it for
    a traced run) with the tracer on or off."""
    from repro.configs.base import ObsConfig
    from repro.serve.engine import ServingEngine

    continuous = traffic["scheduler"] == "continuous"
    return ServingEngine(
        index, batch_size=int(traffic["batch_size"]),
        flush_us=float(traffic.get("flush_us", 2000.0)),
        continuous=continuous,
        slots=int(traffic["slots"]) if continuous else None,
        obs=ObsConfig(metrics=True, tracing=tracing),
    )


def _reset(engine) -> None:
    engine.done.clear()
    engine.obs.metrics.clear()
    engine.obs.tracer.clear()


def measured(engine, traffic, rate, pool, seed, seconds, k, err) -> dict:
    """A measured window with the tracer on: the host-side numbers."""
    due, queries = run.schedule(traffic, rate, seconds, pool, seed)
    _reset(engine)
    stats0 = dict(engine.stats)
    log = client.drive(engine, queries, due, seconds, k)
    stats = {n: v - stats0[n] for n, v in engine.stats.items()}
    events = engine.obs.tracer.events()
    for name, ts, dur, below in spans.longest_steps(events, TOP_STEPS):
        at = (engine.obs.tracer._epoch + ts / 1e6) - log.t0
        parts = ", ".join(f"{p} {d / 1e3:.3f}" for p, d in below
                          if d >= 0.01 * dur or d >= 1e3)
        print(f"# seed {seed}: {name} at {at:.3f} s of the window, "
              f"{dur / 1e3:.3f} ms: {parts}", file=err)
    return {
        "p50_ms": client.percentile(log.latency_ms, 50),
        "sched_host_ms_per_step": spans.sched_host_ms_per_step(events),
        "plan_host_ms_per_dispatch": spans.plan_host_ms_per_dispatch(events),
        "host_syncs_per_query": stats["device_syncs"] / stats["queries"]
        if stats["queries"] else None,
        "steps": len(spans.steps(events)),
        "dropped_events": engine.obs.tracer.dropped,
    }


def traversal_hlo(engine, query: np.ndarray) -> list:
    """The optimized HLO text of every traversal program the engine runs,
    at every batch shape it runs it (the op events of a v5e's trace name
    their instruction, not its scope). The programs are compiled already,
    so lowering them again finds them in the compilation cache."""
    import importlib

    import jax.numpy as jnp

    from repro.plan.request import SearchRequest
    from repro.serve import engine as engine_mod

    # the module, not the package's ``search`` function of the same name
    search = importlib.import_module("repro.core.search")
    texts = []
    if engine.continuous:
        for pool in engine._pools.values():
            sess, state = pool.session, pool.state
            lanes = len(pool.requests)
            args = (sess.cfg, sess.metric)
            q = jnp.zeros((lanes, query.shape[-1]), jnp.float32)
            lowered = [
                search.init_search_state.lower(
                    sess.corpus, q, *args, sess.bloom_bits, sess.num_hashes,
                    sess._mask),
                search.graph_search_step.lower(
                    sess.corpus, state, *args, sess.bloom_bits,
                    sess.num_hashes, sess._mask)]
            lowered += [search.finalize_search.lower(
                sess.corpus, engine_mod._gather_rows(
                    state, np.zeros((b,), np.int64)), *args, sess._mask)
                for b in system._pow2_upto(lanes)]
            texts += [lw.compile().as_text() for lw in lowered]
        return texts
    planner = engine.searcher.planner
    plan = engine.searcher.plan(SearchRequest(queries=query))
    pc = planner.plan_cfg
    for b in system._pow2_upto(engine.batch_size):
        q = np.zeros((b, query.shape[-1]), np.float32)
        texts.append(search.graph_search.lower(
            planner.corpus, q, plan.cfg, planner.metric, pc.bloom_bits,
            pc.num_hashes).compile().as_text())
    return texts


def profiled(engine, traffic, rate, pool, seed, k, tracing: bool,
             hlo_index=None, fixture=None, err=sys.stderr) -> dict:
    """``TRACE_SECONDS`` of the cell's traffic under the profiler."""
    import jax

    due, queries = run.schedule(traffic, rate, TRACE_SECONDS, pool, seed,
                                traced=True)
    _reset(engine)
    client_spans: list = []
    trace_dir = tempfile.mkdtemp(prefix="bench-profile-")
    try:
        jax.profiler.start_trace(trace_dir)
        with jax.profiler.TraceAnnotation("bench.anchor"):
            anchor = time.perf_counter()
        log = client.drive(engine, queries, due, TRACE_SECONDS, k,
                           spans=client_spans)
        jax.profiler.stop_trace()
        trace = spans.from_xplane(trace_dir, hlo_index)
        lo = run._anchor_ns(trace) + (log.t0 - anchor) * 1e9
        trace["planes"].append(run._host_plane(
            client_spans, (lo, lo + TRACE_SECONDS * 1e9)))
        if tracing and fixture is not None:
            write_fixture(trace_dir, trace, client_spans, lo, fixture,
                          hlo_index)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    reduced = trace_reduce.reduce_trace(trace)
    steps_s = [b - a for name, a, b in client_spans if name == "bench.step"]
    in_window = int((log.answered & (log.done <= TRACE_SECONDS)).sum())
    out = {
        "device_idle_share": 100.0 * (1.0 - reduced["busy_s"]
                                      / reduced["window_s"]),
        "busy_s": reduced["busy_s"],
        "step_ms": 1e3 * float(np.mean(steps_s)) if steps_s else None,
        "steps": len(steps_s),
        "idle_gaps": reduced["idle_gaps"],
        "device_ops": reduced["device_ops"],
    }
    if tracing:
        scopes = spans.device_scopes(trace)
        out.update(
            idle_by_span=spans.idle_by_span(trace),
            idle_by_path=spans.idle_by_span(trace, by_path=True),
            device_scopes=scopes,
            gather_device_ms_per_query=spans.gather_device_ms_per_query(
                scopes, in_window),
            top_op_scopes=_top_op_scopes(trace),
        )
    return out


def _top_op_scopes(trace: dict, n: int = 10) -> list:
    """The ``n`` ops with the most self time, each with its module and
    scope (``[op, module, scope, seconds]``)."""
    ops = [(op, module, scope, sec) for p in trace["planes"]
           for module, op, scope, sec in p.get("ops", ())]
    return [list(x) for x in sorted(ops, key=lambda x: -x[3])[:n]]


def write_fixture(trace_dir, trace, client_spans, lo, path,
                  hlo_index=None) -> None:
    """``FIXTURE_NS`` of the window from its middle, in the plain form."""
    start = lo + 0.5 * (TRACE_SECONDS * 1e9 - FIXTURE_NS)
    small = spans.from_xplane(trace_dir, hlo_index, lo_ns=start,
                              hi_ns=start + FIXTURE_NS)
    host = run._host_plane(client_spans, (lo, lo + TRACE_SECONDS * 1e9))
    keep = [[n, max(s, start), min(s + d, start + FIXTURE_NS)
             - max(s, start)] for n, s, d in host["spans"]
            if s < start + FIXTURE_NS and s + d > start
            and n != trace_reduce.WINDOW]
    small["planes"].append({"name": host["name"], "spans": [
        [trace_reduce.WINDOW, start, FIXTURE_NS]] + keep})
    Path(path).parent.mkdir(parents=True, exist_ok=True)
    with gzip.open(path, "wt") as f:
        json.dump(small, f)


def profile(args, root: Path = ROOT, on_chip: bool = True,
            err=sys.stderr) -> dict:
    bench = catalog.load_benchmark(root)
    cell = catalog.workload(bench, args.workload)
    if on_chip:
        run.enable_compile_cache(root)
        devices = run.require_chips(int(cell["chips"]))
    else:
        import jax

        devices = jax.devices()
    sys.path.insert(0, str(ROOT / "src"))
    config = catalog.config(bench, cell["config"], root)
    traffic = catalog.traffic(cell["traffic"], root)
    k = int(config["k"])
    base, pool = corpus.make_corpus(config)
    index, _ = system.build(config, base, corpus.build_seed(config))
    engines = {}
    for tracing in (False, True):
        engines[tracing] = open_engine(index, traffic, tracing)
        system.warm_up(engines[tracing], pool)
    t0 = time.perf_counter()
    hlo_index = spans.hlo_scope_index(traversal_hlo(engines[True], pool[0]))
    print(f"# {len(hlo_index)} scoped instructions from the compiled "
          f"traversal programs in {time.perf_counter() - t0:.1f} s",
          file=err)
    rate = float(traffic["load"]) * float(traffic["knee_qps"])
    seeds = []
    for i, seed in enumerate(args.seeds):
        one = {"seed": seed, "measured": measured(
            engines[True], traffic, rate, pool, seed, args.seconds, k, err)}
        for tracing in (False, True):
            one["traced_on" if tracing else "traced_off"] = profiled(
                engines[tracing], traffic, rate, pool, seed, k, tracing,
                hlo_index, fixture=args.fixture if i == 0 else None,
                err=err)
        seeds.append(one)
    return {"workload": cell["name"], "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind},
        "seeds": seeds}


def main(argv=None) -> int:
    args = parse(argv)
    try:
        result = profile(args)
    except run.NoChip as e:
        print(f"bench/profile_cell.py: {e}; it runs only on the chip",
              file=sys.stderr)
        return run.EXIT_NO_CHIP
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
