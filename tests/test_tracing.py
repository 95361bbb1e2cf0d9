"""The tracer's second sink (profiler annotations), its bounded buffer, the
span trees of both schedulers, the device-sync counter, and the
traversal's named scopes."""
import glob
import importlib

import numpy as np
import pytest

from repro.obs import NULL_SPAN, SYNC_SITES, Observability, Tracer
from repro.obs import tracing

search_mod = importlib.import_module("repro.core.search")

ROUND_SCOPES = ("loop-cond", "beam-select", "gather-adjacency",
                "gather-codes", "pq-lookup", "merge-sort", "gather-base",
                "rerank-exact", "topk-termination", "state-select")
BATCH_SCOPES = ROUND_SCOPES + ("adt-build", "init-lanes", "final-rerank",
                               "final-topk")


def _profiled(tmp_path, fn):
    """Host events named ``repro.*`` that ``fn`` leaves in a profiler
    trace, as ``(name, start_ns, end_ns)``."""
    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for plane in ProfileData.from_file(path).planes
            for line in plane.lines for e in line.events
            if e.name.startswith("repro.")]


def _spans(tr, name):
    return [e for e in tr.events() if e["ph"] == "X" and e["name"] == name]


def _inside(inner, outer):
    return (outer["ts"] - 1e-3 <= inner["ts"] and inner["ts"] + inner["dur"]
            <= outer["ts"] + outer["dur"] + 1e-3)


def _children(tr, parent, names):
    return {e["name"] for e in tr.events() if e["ph"] == "X"
            and e["name"] in names and e is not parent and _inside(e, parent)}


# ---------------------------------------------------------------------------
# Two sinks
# ---------------------------------------------------------------------------

def test_enabled_tracer_writes_nested_profiler_annotations(tmp_path):
    tr = Tracer()

    def spans():
        with tr.span("batch"):
            with tr.span("dispatch"):
                pass
            with tr.span("fetch"):
                pass
        tr.async_begin("queue-wait", 1)      # async spans: Chrome only
        tr.async_end("queue-wait", 1)

    got = _profiled(tmp_path, spans)
    by = {name: (s, e) for name, s, e in got}
    assert set(by) == {"repro.batch", "repro.dispatch", "repro.fetch"}
    for child in ("repro.dispatch", "repro.fetch"):
        assert by["repro.batch"][0] <= by[child][0]
        assert by[child][1] <= by["repro.batch"][1]
    assert by["repro.dispatch"][1] <= by["repro.fetch"][0]
    # the Chrome export keeps its own events
    assert {e["name"] for e in tr.events() if e["ph"] == "X"} == \
        {"batch", "dispatch", "fetch"}


def test_disabled_tracer_writes_no_annotation(tmp_path):
    tr = Tracer(enabled=False)

    def spans():
        with tr.span("batch") as sp:
            assert sp is NULL_SPAN
            with tr.span("dispatch") as inner:
                assert inner is NULL_SPAN

    assert _profiled(tmp_path, spans) == []
    assert tr.events() == []


def test_tracer_buffer_drops_oldest_and_counts(monkeypatch):
    monkeypatch.setattr(tracing, "MAX_EVENTS", 4)
    tr = Tracer()
    for i in range(10):
        with tr.span("s", i=i):
            pass
    kept = [e for e in tr.events() if e["ph"] == "X"]
    assert [e["args"]["i"] for e in kept] == [6, 7, 8, 9]
    assert tr.dropped == 6
    assert len([e for e in tr.events() if e["ph"] == "M"]) == 2
    tr.clear()
    assert tr.dropped == 0 and len(tr.events()) == 2


# ---------------------------------------------------------------------------
# Span trees of the two schedulers
# ---------------------------------------------------------------------------

def test_batch_engine_span_tree(tiny_index):
    from repro.serve.engine import ServingEngine

    obs = Observability.on(tracing=True, nand_billing=False)
    eng = ServingEngine(tiny_index, batch_size=8, flush_us=0.0, obs=obs)
    obs.tracer.clear()
    for q in tiny_index.dataset.queries[:12]:
        eng.submit(q)
    eng.drain()
    tr = obs.tracer
    batches = _spans(tr, "batch")
    assert len(batches) == 2
    leaves = ("batch-assembly", "kernel-execute", "dispatch", "device-wait",
              "fetch", "post-process", "recompile-watch")
    for b in batches:
        assert _children(tr, b, leaves) == set(leaves)
    # every new leaf sits inside a batch, nested under kernel-execute
    for name in ("dispatch", "device-wait", "fetch"):
        for e in _spans(tr, name):
            assert any(_inside(e, k) for k in _spans(tr, "kernel-execute"))
            assert any(_inside(e, b) for b in batches)
    assert {e["cat"] for e in _spans(tr, "dispatch")} == {"plan"}
    assert {e["cat"] for e in batches} == {"serve"}
    assert not _spans(tr, "plan-lookup")


def test_continuous_engine_span_tree(tiny_index):
    from repro.serve.engine import ServingEngine

    obs = Observability.on(tracing=True, nand_billing=False)
    eng = ServingEngine(tiny_index, batch_size=8, continuous=True, slots=4,
                        obs=obs)
    obs.tracer.clear()
    for q in tiny_index.dataset.queries[:6]:
        eng.submit(q)
    eng.drain()
    tr = obs.tracer
    ticks = _spans(tr, "tick")
    assert ticks
    tick_names = ("refill", "admit", "init", "select-lanes", "quiet-lanes",
                  "round", "active-sync", "device-wait",
                  "fetch", "retire", "finalize", "complete",
                  "post-process", "recompile-watch")
    seen = set()
    for t in ticks:
        seen |= _children(tr, t, tick_names)
    assert seen == set(tick_names)
    for name in tick_names:
        for e in _spans(tr, name):
            assert any(_inside(e, t) for t in ticks), name
    # the retire is one program (``finalize``) and one read: no separate
    # row gather
    for r in _spans(tr, "retire"):
        assert _children(tr, r, tick_names) >= {
            "finalize", "device-wait", "fetch", "complete", "post-process"}
    assert not _spans(tr, "gather-rows")
    for a in _spans(tr, "admit"):
        assert _children(tr, a, tick_names) >= {"init", "quiet-lanes"}
    # the activity mask comes back with the advance dispatch (``round``):
    # its read launches nothing of its own
    for a in _spans(tr, "active-sync"):
        assert _children(tr, a, tick_names) == {"device-wait", "fetch"}


# ---------------------------------------------------------------------------
# Device syncs
# ---------------------------------------------------------------------------

def _syncs(obs) -> dict:
    return {k: v for k, v in obs.metrics.snapshot()["counters"]
            .get("device_syncs", {}).items()}


def test_device_syncs_batch_scheduler(tiny_index):
    from repro.serve.engine import ServingEngine

    obs = Observability.on(tracing=False, nand_billing=False)
    eng = ServingEngine(tiny_index, batch_size=8, flush_us=0.0, obs=obs)
    base = eng.stats["device_syncs"]
    assert base == 1                           # the warm-up search
    obs.metrics.clear()
    for q in tiny_index.dataset.queries[:12]:
        eng.submit(q)
    eng.drain()                                # batches of 8 and 4
    assert _syncs(obs) == {"site=execute": 2.0}
    assert eng.stats["device_syncs"] - base == 2


def test_device_syncs_continuous_scheduler(tiny_index):
    from repro.serve.engine import ServingEngine

    obs = Observability.on(tracing=True, nand_billing=False)
    eng = ServingEngine(tiny_index, batch_size=8, continuous=True, slots=4,
                        obs=obs)
    base = eng.stats["device_syncs"]
    copies0 = eng.stats["device_copies"]
    obs.metrics.clear()
    obs.tracer.clear()
    d0, r0 = eng.stats["pool_dispatches"], eng.stats["pool_rounds"]
    for q in tiny_index.dataset.queries[:6]:
        eng.submit(q)
    eng.drain()
    dispatches = eng.stats["pool_dispatches"] - d0
    retires = len(_spans(obs.tracer, "retire"))
    # one active pull per advance dispatch and one packed fetch (ids,
    # dists, counters and rounds) per dispatch that retires lanes; nothing
    # else blocks, and each read copies one array
    assert _syncs(obs) == {"site=active": float(dispatches),
                           "site=retire": float(retires)}
    assert retires >= 2                        # 6 queries through 4 slots
    assert eng.stats["pool_rounds"] - r0 > dispatches   # rounds batched
    assert eng.stats["device_syncs"] - base == dispatches + retires
    assert eng.stats["device_copies"] - copies0 == dispatches + retires
    assert {k.split("=")[1] for k in _syncs(obs)} <= SYNC_SITES


def test_device_syncs_count_with_metrics_off(tiny_index):
    """``total`` counts on the disabled path too; the registry stays
    empty."""
    from repro.obs import NULL_OBS
    from repro.serve.engine import ServingEngine

    eng = ServingEngine(tiny_index, batch_size=4, flush_us=0.0)
    base = eng.stats["device_syncs"]
    for q in tiny_index.dataset.queries[:4]:
        eng.submit(q)
    eng.drain()
    assert eng.stats["device_syncs"] - base == 1
    assert NULL_OBS.metrics.snapshot()["counters"] == {}


# ---------------------------------------------------------------------------
# Named scopes in the traversal
# ---------------------------------------------------------------------------

def _op_names(compiled_text: str) -> str:
    import re

    return "\n".join(re.findall(r'op_name="([^"]*)"', compiled_text))


@pytest.mark.parametrize("program", ["graph_search", "graph_search_step",
                                     "graph_search_advance"])
def test_traversal_programs_carry_named_scopes(tiny_index, program):
    import jax.numpy as jnp

    corpus = tiny_index.corpus()
    cfg = tiny_index.config.search
    metric = tiny_index.dataset.metric
    q = jnp.asarray(tiny_index.dataset.queries[:2])
    if program == "graph_search":
        low = search_mod.graph_search.lower(corpus, q, cfg, metric)
        want = BATCH_SCOPES
    else:
        st = search_mod.init_search_state(corpus, q, cfg, metric)
        args = (np.int32(1),) if program == "graph_search_advance" else ()
        low = getattr(search_mod, program).lower(corpus, st, *args, cfg,
                                                 metric)
        want = ROUND_SCOPES
    names = _op_names(low.compile().as_text())
    for scope in want:
        assert f"/{scope}/" in names or f"({scope})" in names, scope


def test_finalize_and_init_carry_named_scopes(tiny_index):
    import jax.numpy as jnp

    corpus = tiny_index.corpus()
    cfg = tiny_index.config.search
    metric = tiny_index.dataset.metric
    q = jnp.asarray(tiny_index.dataset.queries[:2])
    init = _op_names(search_mod.init_search_state.lower(
        corpus, q, cfg, metric).compile().as_text())
    assert "adt-build" in init and "init-lanes" in init
    st = search_mod.init_search_state(corpus, q, cfg, metric)
    fin = _op_names(search_mod.finalize_search.lower(
        corpus, st, cfg, metric).compile().as_text())
    assert "final-rerank" in fin and "final-topk" in fin

