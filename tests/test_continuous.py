"""Continuous (iteration-level) serving engine behaviour.

The bit-identity of the round-step kernels themselves lives in
``test_plan.py`` (the equivalence matrix); this file covers the SCHEDULER:
slot pools, immediate retirement, refill, drain bounds, streaming
consolidation safety, the deferred-plan recache, and the observability
surface the continuous path adds."""
import numpy as np
import pytest

from repro.serve.engine import ServingEngine


def test_continuous_matches_batch_results(tiny_index):
    """Same queries, same results (bit-identical ids/dists), regardless of
    which scheduler served them."""
    q = tiny_index.dataset.queries[:13]
    cont = ServingEngine(tiny_index, batch_size=8, continuous=True, slots=4)
    rc = [cont.submit(qq) for qq in q]
    cont.drain()
    batch = ServingEngine(tiny_index, batch_size=8, flush_us=0.0)
    rb = [batch.submit(qq) for qq in q]
    batch.drain()
    for a, b in zip(rc, rb):
        np.testing.assert_array_equal(cont.done[a].ids, batch.done[b].ids)
        np.testing.assert_array_equal(cont.done[a].dists,
                                      batch.done[b].dists)
    assert cont.stats["retired"] == len(q)
    assert cont.stats["queries"] == len(q)
    assert cont.stats["batches"] == 0          # never fell back


def test_lanes_retire_across_ticks_not_at_barrier(tiny_index):
    """Iteration-level scheduling: lanes finish on THEIR round, so a pool's
    completions spread over multiple ticks instead of arriving as one
    whole-batch barrier."""
    q = tiny_index.dataset.queries[:12]
    eng = ServingEngine(tiny_index, batch_size=8, continuous=True, slots=12)
    for qq in q:
        eng.submit(qq)
    retire_ticks = []
    guard = 0
    while eng.queue or eng.inflight():
        done = eng.step(force=True)
        if done:
            retire_ticks.append(len(done))
        guard += 1
        assert guard < 500
    assert sum(retire_ticks) == len(q)
    assert len(retire_ticks) > 1, (
        "all lanes retired in one tick — scheduler degenerated to a barrier"
    )


def test_slot_refill_serves_backlog(tiny_index):
    """A pool smaller than the workload turns over: freed slots re-admit
    queued requests until the backlog drains, and in-flight lanes never
    exceed the pool size."""
    q = tiny_index.dataset.queries
    eng = ServingEngine(tiny_index, batch_size=8, continuous=True, slots=3)
    rids = [eng.submit(qq) for qq in np.tile(q, (2, 1))[:20]]
    guard = 0
    while eng.queue or eng.inflight():
        eng.step(force=True)
        assert eng.inflight() <= 3
        guard += 1
        assert guard < 2000
    assert all(r in eng.done for r in rids)
    assert eng.stats["retired"] == 20


def test_drain_guard_raises_instead_of_spinning(tiny_index):
    eng = ServingEngine(tiny_index, batch_size=8, continuous=True, slots=4)
    eng.submit(tiny_index.dataset.queries[0])
    with pytest.raises(RuntimeError, match="drain"):
        eng.drain(max_steps=0)
    eng.drain()                                # recovers with a real budget
    assert eng.stats["retired"] == 1


def test_deferred_plan_recached_on_flush(tiny_index):
    """Satellite: when flush-time planning succeeds for a request whose plan
    was deferred, the plan is cached back onto it AND every queued
    same-filter request — later flushes never re-plan them."""
    q = tiny_index.dataset.queries[:6]
    eng = ServingEngine(tiny_index, batch_size=4, flush_us=0.0)
    for qq in q:
        eng.submit(qq)
    for r in eng.queue:
        r.plan = None                          # simulate deferred planning
    done = eng.step(force=True)                # flush replans the head once
    assert len(done) == 4
    assert all(r.plan is not None for r in done)
    # the two still-queued requests were recached from the head's plan
    assert all(r.plan is not None for r in eng.queue)
    plans = {id(r.plan) for r in list(eng.queue) + done}
    assert len(plans) == 1                     # one shared plan object
    eng.drain()
    assert eng.stats["queries"] == 6


def test_continuous_streaming_consolidation_safety(tiny_index):
    """Consolidation mid-flight: in-flight merged lanes complete against the
    old base BEFORE the rebuild, sessions reset, and post-consolidation
    submits serve correctly against the new id space."""
    from repro.stream import MutableIndex

    mut = MutableIndex(tiny_index)
    eng = ServingEngine(mut, batch_size=8, continuous=True, slots=4,
                        auto_consolidate=False)
    q = tiny_index.dataset.queries
    ext = eng.insert(np.asarray(q[0]) + 1e-4)
    eng.delete(3)
    rids = [eng.submit(qq) for qq in q[:6]]
    eng.step(force=True)                       # lanes now mid-traversal
    assert eng.inflight() > 0
    inflight = eng.inflight()
    eng.consolidate()                          # must complete lanes first
    assert eng.inflight() == 0
    # every in-flight lane retired against the OLD base; queued requests
    # stay queued and admit to fresh post-rebuild sessions
    assert sum(r in eng.done for r in rids) >= inflight
    assert eng.stats["consolidations"] == 1
    eng.drain()
    assert all(r in eng.done for r in rids)
    # deleted id never surfaces; the insert is findable after the rebuild
    for r in rids:
        assert 3 not in set(int(i) for i in eng.done[r].ids)
    r2 = eng.submit(q[0])
    eng.drain()
    assert ext in set(int(i) for i in eng.done[r2].ids)


def test_continuous_obs_surface(tiny_index):
    """The tick scheduler reports slot occupancy, per-lane rounds and NAND
    billing into the shared registry — and stays inside the recompile
    budget."""
    from repro.obs import Observability

    obs = Observability.on(nand_billing=True)
    eng = ServingEngine(tiny_index, batch_size=8, continuous=True, slots=4,
                        obs=obs)
    for qq in tiny_index.dataset.queries[:10]:
        eng.submit(qq)
    eng.drain()
    m = obs.metrics
    assert eng.stats["ticks"] > 0
    assert m.gauge_value("slot_occupancy", kind="flat",
                         strategy="none") is not None
    rounds = m.merged_histogram("rounds_in_flight")
    assert rounds is not None and rounds.count == 10
    assert rounds.mean > 1.0                   # real traversals, not no-ops
    lat = m.merged_histogram("request_latency_ms")
    assert lat is not None and lat.count == 10
    assert m.merged_histogram("nand_latency_us") is not None
    assert m.counter_total("unexpected_recompiles") == 0


def test_continuous_double_buffer_billing(tiny_index):
    """ServingEngine(nand=NandConfig(double_buffer=True)) bills a shorter
    modeled round than the sequential default for the same served work."""
    from repro.nand.device import NandConfig
    from repro.obs import Observability

    q = tiny_index.dataset.queries[:8]
    rounds = {}
    for db in (False, True):
        obs = Observability.on(nand_billing=True)
        eng = ServingEngine(tiny_index, batch_size=8, continuous=True,
                            slots=4, obs=obs,
                            nand=NandConfig(double_buffer=db))
        for qq in q:
            eng.submit(qq)
        eng.drain()
        m = obs.metrics
        rounds[db] = m.merged_histogram("nand_round_latency_us").mean
        saved = m.merged_histogram("nand_overlap_saved_us").mean
        assert (saved > 0.0) == db
    assert rounds[True] < rounds[False]


def test_continuous_non_steppable_plan_falls_back(tiny_index):
    """Plans without a round-steppable spine (bitmap scans) serve through
    the batch-flush path transparently."""
    from repro.filter import FilterSpec, random_attributes

    store = random_attributes(tiny_index.dataset.num_base,
                              {"category": 8, "price": 1000}, seed=7)
    eng = ServingEngine(tiny_index, batch_size=8, continuous=True, slots=4,
                        attributes=store, flush_us=0.0)
    sharp = FilterSpec.range("price", 0, 4)
    rids = [eng.submit(qq, filter=sharp)
            for qq in tiny_index.dataset.queries[:5]]
    eng.drain()
    assert all(r in eng.done for r in rids)
    assert eng.stats["fallback_batches"] >= 1
    assert eng.stats["retired"] == 0           # nothing took the tick path
    mask = np.asarray(store.mask(sharp))
    passing = set(np.flatnonzero(mask).tolist())
    for r in rids:
        got = [int(i) for i in eng.done[r].ids if i >= 0]
        assert set(got) <= passing


def test_pool_counters_count_rounds_run(tiny_index):
    """One pool admitted at once runs until its slowest lane quiesces, one
    dispatch per distinct quiesce round: ``pool_rounds`` is the slowest
    lane's rounds and ``pool_dispatches`` the number of retire points."""
    from repro.plan import SearchRequest, Searcher

    q = tiny_index.dataset.queries[:6]
    eng = ServingEngine(tiny_index, batch_size=8, continuous=True, slots=8)
    for qq in q:
        eng.submit(qq)
    eng.drain()
    rounds = np.asarray(Searcher.open(tiny_index).search(
        SearchRequest(queries=q)).raw.rounds)
    assert eng.stats["pool_rounds"] == int(rounds.max())
    assert eng.stats["pool_dispatches"] == len(np.unique(rounds))
    assert eng.stats["ticks"] == eng.stats["pool_dispatches"]


def test_convergence_records_match_one_round_path(tiny_index):
    """With per-round telemetry on, the engine advances one round per
    dispatch and records exactly what ``trace_session`` (one-round
    stepping) records for the same lanes."""
    from repro.obs import ConvergenceLog, Observability, trace_session
    from repro.plan import SearchRequest, Searcher

    q = tiny_index.dataset.queries[:6]
    obs = Observability.on(tracing=False, nand_billing=False,
                           convergence=True)
    eng = ServingEngine(tiny_index, batch_size=8, continuous=True, slots=8,
                        obs=obs)
    for qq in q:
        eng.submit(qq)
    eng.drain()
    assert eng.stats["pool_rounds"] == eng.stats["pool_dispatches"] > 1

    s = Searcher.open(tiny_index)
    log = ConvergenceLog()
    trace_session(s.round_session(s.plan(SearchRequest(queries=q[:1]))), q,
                  log)
    got, want = obs.convergence.to_arrays(), log.to_arrays()
    assert got.keys() == want.keys()
    for f in want:
        np.testing.assert_array_equal(got[f], want[f], err_msg=f)
    assert obs.convergence.labels == log.labels


def test_served_retires_compile_nothing(tiny_index):
    """A freshly opened continuous engine has warmed the packed retire at
    every bucket it can pad to: serving twice its slots compiles no new
    ``finalize_rows`` program."""
    from repro.core.search import jit_cache_sizes

    eng = ServingEngine(tiny_index, batch_size=8, continuous=True, slots=4)
    before = jit_cache_sizes()["finalize_rows"]
    for q in tiny_index.dataset.queries[:8]:
        eng.submit(q)
    done = eng.drain()
    assert len(done) == 8 and eng.stats["retired"] == 8
    assert jit_cache_sizes()["finalize_rows"] == before
