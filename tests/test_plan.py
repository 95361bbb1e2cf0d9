"""Query-plan layer: planner strategy choice, the Searcher facade, and the
legacy-equivalence regression suite — the contract that makes the API
redesign safe: every legacy entry point must produce bit-identical
(ids, dists) to the equivalent ``Searcher.search(SearchRequest)`` call
across the beam/filter/shard/stream matrix."""
import dataclasses
import warnings

import numpy as np
import pytest

from repro.configs.base import FilterConfig, PlanConfig, SearchConfig
from repro.filter import FilterSpec, random_attributes
from repro.plan import (
    PlanConfig as PlanConfigReexport,
    QueryPlan,
    SearchRequest,
    SearchStats,
    Searcher,
    validate_attribute_store,
)


@pytest.fixture(scope="module")
def tiny_store(tiny_index):
    return random_attributes(tiny_index.dataset.num_base,
                             {"category": 8, "price": 1000}, seed=7)


# the spec selectivities hit both filtered regimes: ~0.5 -> masked
# traversal, ~0.005 -> bitmap PQ scan (brute_force_selectivity = 0.02)
SPEC_MODERATE = FilterSpec.range("price", 0, 499)
SPEC_SHARP = FilterSpec.range("price", 0, 4)


def _legacy(callable_, *args, **kwargs):
    """Run a deprecated entry point, asserting it warns as documented.
    Warnings are deduplicated per entry point per process, so re-arm them
    first — each equivalence cell must see its own warning."""
    from repro.plan.searcher import reset_legacy_warnings

    reset_legacy_warnings()
    with pytest.warns(DeprecationWarning):
        return callable_(*args, **kwargs)


def test_legacy_warning_dedup(tiny_index):
    """A hammered legacy entry point warns once per process, not per call."""
    from repro.core import search as legacy_search
    from repro.plan.searcher import reset_legacy_warnings

    corpus = tiny_index.corpus()
    cfg = tiny_index.config.search
    q = tiny_index.dataset.queries[:2]
    reset_legacy_warnings()
    with pytest.warns(DeprecationWarning):
        legacy_search(corpus, q, cfg)
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        legacy_search(corpus, q, cfg)      # second call: silent
    reset_legacy_warnings()
    with pytest.warns(DeprecationWarning):
        legacy_search(corpus, q, cfg)      # re-armed


# ---------------------------------------------------------------------------
# Equivalence matrix: {beam E in {1,4}} x {filtered, unfiltered}
#                     x {tiled, flat} x {static, mutable}
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("beam", [1, 4])
@pytest.mark.parametrize("filtered", [False, True])
@pytest.mark.parametrize("tiled", [False, True])
@pytest.mark.parametrize("mutable", [False, True])
def test_planner_matches_legacy_paths(tiny_index, tiny_store, beam,
                                      filtered, tiled, mutable):
    """Each cell: the facade's (ids, dists) are bit-identical to the legacy
    entry point serving that cell (core.search / filter.filtered_search /
    shard.sharded_search / stream.search_merged)."""
    from repro.core import graph_search
    from repro.filter import adapt_search_cfg, tile_node_masks
    from repro.shard.search import sharded_search_kernel
    from repro.stream import MutableIndex
    from repro.stream.searcher import merged_search_kernel

    idx = tiny_index
    q = idx.dataset.queries[:8]
    cfg = dataclasses.replace(idx.config.search, beam_width=beam)
    spec = SPEC_MODERATE if filtered else None
    mask = tiny_store.mask(SPEC_MODERATE)
    fcfg = FilterConfig()
    n_tiles = 2 if tiled else 1

    if mutable:
        # fresh store per cell: streaming inserts append rows, and the
        # module-scoped tiny_store must keep matching the frozen corpus
        mut_store = random_attributes(idx.dataset.num_base,
                                      {"category": 8, "price": 1000}, seed=7)
        mask = mut_store.mask(SPEC_MODERATE)
        mut = MutableIndex(idx, attributes=mut_store)
        if tiled:
            mut.set_num_tiles(2, "hash")
        v = np.asarray(q[0]) + 1e-4
        mut.insert(v, attrs={"category": 1, "price": 250})
        mut.delete(3)
        legacy = merged_search_kernel(mut, q, cfg, filter_spec=spec)
        legacy_ids, legacy_dists = legacy.ids, legacy.dists
        s = Searcher.open(mut, cfg=cfg)
    elif tiled:
        s = Searcher.open(idx, cfg=cfg, num_tiles=2, shard_policy="hash",
                          attributes=tiny_store if filtered else None)
        if filtered:
            # the legacy tiled-filtered path: caller-adapted config +
            # per-tile mask slices into sharded_search
            eff = adapt_search_cfg(cfg, float(mask.mean()), fcfg)
            node_masks = tile_node_masks(s.tiled.tile_ids, mask)
            legacy = sharded_search_kernel(s.tiled, q, eff,
                                           idx.dataset.metric,
                                           node_masks=node_masks)
        else:
            legacy = sharded_search_kernel(s.tiled, q, cfg,
                                           idx.dataset.metric)
        legacy_ids = np.asarray(legacy.ids)
        legacy_dists = np.asarray(legacy.dists)
    else:
        s = Searcher.open(idx, cfg=cfg,
                          attributes=tiny_store if filtered else None)
        if filtered:
            # legacy flat-filtered semantics == filtered_search: adapted
            # config + masked traversal (selectivity ~0.5 -> traversal)
            eff = adapt_search_cfg(cfg, float(mask.mean()), fcfg)
            import jax.numpy as jnp

            legacy = graph_search(idx.corpus(), q, eff, idx.dataset.metric,
                                  node_mask=jnp.asarray(mask))
        else:
            legacy = graph_search(idx.corpus(), q, cfg, idx.dataset.metric)
        legacy_ids = np.asarray(legacy.ids)
        legacy_dists = np.asarray(legacy.dists)

    res = s.search(SearchRequest(queries=q, filter=spec))
    np.testing.assert_array_equal(res.ids, legacy_ids)
    np.testing.assert_array_equal(res.dists, legacy_dists)
    # the plan records what actually ran
    assert res.plan.cfg.beam_width == beam
    expect_kind = "merged" if mutable else ("tiled" if tiled else "flat")
    assert res.plan.kind == expect_kind
    assert res.stats.num_tiles == n_tiles
    assert res.stats.kind == expect_kind
    if filtered:
        assert res.plan.spec == spec


# ---------------------------------------------------------------------------
# The five deprecated wrappers delegate (and warn)
# ---------------------------------------------------------------------------

def test_wrapper_core_search_delegates(tiny_index):
    from repro.core import graph_search, search

    idx = tiny_index
    q = idx.dataset.queries[:4]
    legacy = _legacy(search, idx.corpus(), q, idx.config.search,
                     idx.dataset.metric)
    direct = graph_search(idx.corpus(), q, idx.config.search,
                          idx.dataset.metric)
    np.testing.assert_array_equal(np.asarray(legacy.ids),
                                  np.asarray(direct.ids))
    # counters survive the wrapper (it returns the raw kernel result)
    assert (np.asarray(legacy.n_hops) == np.asarray(direct.n_hops)).all()


def test_wrapper_core_search_node_mask(tiny_index, tiny_store):
    """core.search(node_mask=...) applies the mask VERBATIM (no selectivity
    adaptation) — the wrapper must preserve that semantics."""
    import jax.numpy as jnp

    from repro.core import graph_search, search

    idx = tiny_index
    q = idx.dataset.queries[:4]
    mask = tiny_store.mask(SPEC_MODERATE)
    legacy = _legacy(search, idx.corpus(), q, idx.config.search,
                     idx.dataset.metric, node_mask=jnp.asarray(mask))
    direct = graph_search(idx.corpus(), q, idx.config.search,
                          idx.dataset.metric, node_mask=jnp.asarray(mask))
    np.testing.assert_array_equal(np.asarray(legacy.ids),
                                  np.asarray(direct.ids))


def test_wrapper_filtered_search_delegates(tiny_index, tiny_store):
    from repro.filter import filtered_search

    idx = tiny_index
    q = idx.dataset.queries[:4]
    s = Searcher.open(idx, attributes=tiny_store)
    for spec, mode in ((SPEC_MODERATE, "traversal"), (SPEC_SHARP, "scan")):
        fres = _legacy(filtered_search, idx.corpus(), q,
                       tiny_store.mask(spec), idx.config.search,
                       idx.dataset.metric)
        assert fres.mode == mode
        res = s.search(SearchRequest(queries=q, filter=spec))
        np.testing.assert_array_equal(fres.ids, res.ids)
        np.testing.assert_array_equal(fres.dists, res.dists)


def test_wrapper_sharded_search_delegates(tiny_index):
    from repro.shard import partition_index, sharded_search
    from repro.shard.search import sharded_search_kernel

    idx = tiny_index
    q = idx.dataset.queries[:4]
    tiled, _ = partition_index(idx, 2, "hash")
    legacy = _legacy(sharded_search, tiled, q, idx.config.search,
                     idx.dataset.metric)
    direct = sharded_search_kernel(tiled, q, idx.config.search,
                                   idx.dataset.metric)
    np.testing.assert_array_equal(np.asarray(legacy.ids),
                                  np.asarray(direct.ids))
    assert legacy.per_tile.ids.shape[0] == 2


def test_wrapper_search_merged_delegates(tiny_index):
    from repro.stream import MutableIndex, search_merged
    from repro.stream.searcher import merged_search_kernel

    mut = MutableIndex(tiny_index)
    q = tiny_index.dataset.queries[:4]
    mut.insert(np.asarray(q[0]) + 1e-4)
    legacy = _legacy(search_merged, mut, q)
    direct = merged_search_kernel(mut, q)
    np.testing.assert_array_equal(legacy.ids, direct.ids)
    np.testing.assert_array_equal(legacy.dists, direct.dists)


# ---------------------------------------------------------------------------
# Planner strategy choice + plan caching
# ---------------------------------------------------------------------------

def test_planner_strategy_selection(tiny_index, tiny_store):
    s = Searcher.open(tiny_index, attributes=tiny_store)
    q = tiny_index.dataset.queries[:2]
    plan_m = s.plan(SearchRequest(queries=q, filter=SPEC_MODERATE))
    assert (plan_m.kind, plan_m.strategy) == ("flat", "masked")
    # masked traversal inflates the candidate list (selectivity-adapted cfg)
    assert plan_m.cfg.list_size > tiny_index.config.search.list_size
    plan_s = s.plan(SearchRequest(queries=q, filter=SPEC_SHARP))
    assert plan_s.strategy == "scan"
    assert plan_s.cfg.list_size == tiny_index.config.search.list_size
    plan_e = s.plan(SearchRequest(
        queries=q, filter=FilterSpec.eq("price", 10_000)))
    assert plan_e.strategy == "empty"
    assert s.search(SearchRequest(queries=q,
                                  filter=FilterSpec.eq("price", 10_000))
                    ).ids.max() == -1
    # all-pass spec normalizes to the unfiltered plan (same cache key)
    plan_all = s.plan(SearchRequest(queries=q, filter=FilterSpec()))
    plan_none = s.plan(SearchRequest(queries=q))
    assert plan_all.cache_key == plan_none.cache_key


def test_plan_cache_hits(tiny_index, tiny_store):
    s = Searcher.open(tiny_index, attributes=tiny_store)
    q = tiny_index.dataset.queries[0]
    for _ in range(5):
        s.plan(SearchRequest(queries=q, filter=SPEC_MODERATE))
        s.plan(SearchRequest(queries=q))
    st = s.plan_cache_stats()
    assert st["plan_cache_misses"] == 2
    assert st["plan_cache_hits"] == 8
    # distinct per-request overrides are distinct plans
    s.plan(SearchRequest(queries=q, overrides={"beam_width": 4}))
    assert s.plan_cache_stats()["plan_cache_misses"] == 3


def test_request_overrides_and_k(tiny_index):
    s = Searcher.open(tiny_index)
    q = tiny_index.dataset.queries[:4]
    res = s.search(SearchRequest(queries=q, k=3,
                                 overrides={"beam_width": 4}))
    assert res.ids.shape == (4, 3)
    assert res.plan.cfg.k == 3 and res.plan.cfg.beam_width == 4
    assert res.stats.k == 3 and res.stats.beam_width == 4


def test_search_stats_as_dict(tiny_index):
    s = Searcher.open(tiny_index)
    res = s.search(SearchRequest(queries=tiny_index.dataset.queries[:4]))
    d = res.stats.as_dict()
    assert isinstance(d, dict)
    assert d["kind"] == "flat" and d["strategy"] == "none"
    assert d["hops"] > 0 and d["rounds"] > 0
    assert set(d) >= {"queries", "k", "selectivity", "pq", "acc",
                      "hot_hops", "free_pq", "delta_candidates",
                      "beam_width", "num_tiles"}


def test_engine_stats_derived_from_dataclass(tiny_index):
    from repro.serve.engine import EngineStats, ServingEngine

    eng = ServingEngine(tiny_index, batch_size=4, flush_us=0.0)
    assert isinstance(eng._stats, EngineStats)
    for qq in tiny_index.dataset.queries[:4]:
        eng.submit(qq)
    eng.drain()
    d = eng.stats
    assert d["batches"] == 1 and d["queries"] == 4
    # plan-cache counters and the device-sync count surface through the
    # dict view (merged from the planner at read time — they are not
    # EngineStats fields)
    assert d["plan_cache_misses"] >= 1
    assert d["plan_cache_hits"] >= 3
    assert d["device_syncs"] >= 1
    assert set(d) == set(EngineStats().as_dict()) | {
        "plan_cache_hits", "plan_cache_misses",
        "device_syncs"}, "dict view drifted"


def test_validate_attribute_store_shared_helper(tiny_index, tiny_store):
    from repro.serve.engine import ServingEngine

    short = random_attributes(10, {"price": 10}, seed=0)
    with pytest.raises(ValueError, match="attribute store has 10 rows"):
        Searcher.open(tiny_index, attributes=short)
    with pytest.raises(ValueError, match="attribute store has 10 rows"):
        ServingEngine(tiny_index, batch_size=4, attributes=short)
    assert validate_attribute_store(None, 123, "x") is None
    assert validate_attribute_store(tiny_store,
                                    tiny_index.dataset.num_base,
                                    "index") is tiny_store


def test_plan_config_collapses_engine_kwargs(tiny_index):
    """PlanConfig is the one knob object: an engine built from it matches
    one built from the legacy per-feature kwargs."""
    from repro.serve.engine import ServingEngine

    assert PlanConfigReexport is PlanConfig
    pc = PlanConfig(num_tiles=2, shard_policy="hash", beam_width=4)
    e1 = ServingEngine(tiny_index, batch_size=4, flush_us=0.0, plan=pc)
    e2 = ServingEngine(tiny_index, batch_size=4, flush_us=0.0, num_tiles=2,
                       shard_policy="hash", beam_width=4)
    assert e1.num_tiles == e2.num_tiles == 2
    assert e1.cfg == e2.cfg and e1.cfg.beam_width == 4
    q = tiny_index.dataset.queries[:4]
    r1 = [e1.submit(qq) for qq in q]
    r2 = [e2.submit(qq) for qq in q]
    e1.drain(), e2.drain()
    np.testing.assert_array_equal(
        np.stack([e1.done[r].ids for r in r1]),
        np.stack([e2.done[r].ids for r in r2]),
    )


def test_trace_from_plan_execution_matches_legacy(tiny_index, tiny_store):
    from repro.nand.simulator import (
        trace_from_plan_execution, trace_from_search_result,
    )

    idx = tiny_index
    geo = dict(dim=idx.dataset.dim, r_degree=idx.graph.adjacency.shape[1],
               index_bits=idx.gap.bit_width if idx.gap else 32,
               pq_bits=8 * idx.codes.shape[1], metric=idx.dataset.metric)
    s = Searcher.open(idx, attributes=tiny_store)
    q = idx.dataset.queries[:4]
    res = s.search(SearchRequest(queries=q))
    assert trace_from_plan_execution(res, index=idx) == \
        trace_from_search_result(res.raw, **geo)
    # filtered: mode/selectivity/attr_bits come off the plan
    fres = s.search(SearchRequest(queries=q, filter=SPEC_MODERATE))
    t = trace_from_plan_execution(fres, index=idx)
    assert t.filter_mode == "pushdown"
    assert t.attr_bits == tiny_store.attr_bits
    assert 0.0 < t.filter_selectivity < 1.0
    assert t.filter_selectivity == pytest.approx(fres.plan.selectivity)


def test_queryplan_hashable_cache_key(tiny_index, tiny_store):
    s = Searcher.open(tiny_index, attributes=tiny_store)
    q = tiny_index.dataset.queries[0]
    p1 = s.plan(SearchRequest(queries=q, filter=SPEC_MODERATE))
    p2 = s.plan(SearchRequest(queries=q, filter=SPEC_MODERATE))
    assert isinstance(p1, QueryPlan)
    assert hash(p1.cache_key) == hash(p2.cache_key)
    assert p1.cache_key != s.plan(SearchRequest(queries=q)).cache_key


def test_distributed_plan_single_device(tiny_index):
    """The distributed spine through the facade on a 1x1 mesh is
    bit-identical to the legacy distributed_search wrapper and consistent
    with the flat path's result sets."""
    import jax
    from jax.sharding import Mesh

    from repro.core import graph_search
    from repro.core.distributed import distributed_search, shard_corpus

    idx = tiny_index
    cfg = idx.config.search
    q = idx.dataset.queries[:4]
    sc = shard_corpus(idx.graph.adjacency, idx.codes, idx.dataset.base,
                      idx.codebook.centroids, int(idx.graph.entry_point),
                      idx.hot_count, num_shards=1)
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))
    legacy_ids, legacy_d = _legacy(distributed_search, sc, q, cfg,
                                   idx.dataset.metric, mesh=mesh)
    s = Searcher.open(sc, cfg=cfg, metric=idx.dataset.metric, mesh=mesh)
    res = s.search(SearchRequest(queries=q))
    assert res.plan.kind == "distributed"
    np.testing.assert_array_equal(res.ids, np.asarray(legacy_ids))
    np.testing.assert_array_equal(res.dists, np.asarray(legacy_d))
    flat = graph_search(idx.corpus(), q, cfg, idx.dataset.metric)
    assert (np.sort(res.ids, 1) == np.sort(np.asarray(flat.ids), 1)).mean() \
        >= 0.9


def test_tenant_isolated_in_plan_key(tiny_index):
    """The tenant slot is part of the batching identity: two tenants never
    share a plan cache key (the multi-tenancy roadmap contract)."""
    s = Searcher.open(tiny_index)
    q = tiny_index.dataset.queries[0]
    pa = s.plan(SearchRequest(queries=q, tenant="a"))
    pb = s.plan(SearchRequest(queries=q, tenant="b"))
    assert pa.tenant == "a" and pb.tenant == "b"
    assert pa.cache_key != pb.cache_key


def test_merged_scan_billing_not_discounted(tiny_index):
    """Regression: a sharp filter on a mutable index routes the base
    through the bitmap scan, whose candidate stream is the passing subset
    itself — the plan-derived pushdown billing must not discount it by the
    selectivity (the flat path already special-cases this)."""
    from repro.nand.simulator import trace_from_plan_execution
    from repro.stream import MutableIndex

    store = random_attributes(tiny_index.dataset.num_base,
                              {"category": 8, "price": 1000}, seed=7)
    mut = MutableIndex(tiny_index, attributes=store)
    s = Searcher.open(mut)
    q = tiny_index.dataset.queries[:4]
    res = s.search(SearchRequest(queries=q, filter=SPEC_SHARP))
    assert res.raw.base_mode == "scan"
    assert trace_from_plan_execution(res, index=mut).filter_selectivity \
        == 1.0
    # the traversal regime keeps the measured passing fraction
    res2 = s.search(SearchRequest(queries=q, filter=SPEC_MODERATE))
    assert res2.raw.base_mode == "traversal"
    t2 = trace_from_plan_execution(res2, index=mut)
    assert 0.0 < t2.filter_selectivity < 1.0


def test_typed_request_filter_field(tiny_index):
    """serve.Request.filter is typed Optional[FilterSpec] (satellite)."""
    import typing

    from repro.serve.engine import Request

    hints = typing.get_type_hints(Request)
    assert hints["filter"] == typing.Optional[FilterSpec]


# ---------------------------------------------------------------------------
# Round-step equivalence: the continuous-batching spine.  Iterating the
# exported step kernels to quiescence must be BIT-identical to the
# lax.while_loop executor across {beam 1,4} x {unfiltered, masked} x
# {flat, merged} — the contract that lets the iteration-level scheduler
# serve the same results as a batch flush.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("beam", [1, 4])
@pytest.mark.parametrize("filtered", [False, True])
@pytest.mark.parametrize("mutable", [False, True])
def test_round_session_matches_batch_execute(tiny_index, tiny_store, beam,
                                             filtered, mutable):
    """RoundSession init/step*/finalize/complete == Searcher.search for the
    same plan, field for field."""
    idx = tiny_index
    q = idx.dataset.queries[:8]
    cfg = dataclasses.replace(idx.config.search, beam_width=beam)
    spec = SPEC_MODERATE if filtered else None

    if mutable:
        from repro.stream import MutableIndex

        mut_store = random_attributes(idx.dataset.num_base,
                                      {"category": 8, "price": 1000}, seed=7)
        mut = MutableIndex(idx, attributes=mut_store)
        v = np.asarray(q[0]) + 1e-4
        mut.insert(v, attrs={"category": 1, "price": 250})
        mut.delete(3)
        s = Searcher.open(mut, cfg=cfg)
    else:
        s = Searcher.open(idx, cfg=cfg,
                          attributes=tiny_store if filtered else None)

    batch = s.search(SearchRequest(queries=q, filter=spec))
    plan = s.plan(SearchRequest(queries=q[:1], filter=spec))
    sess = s.planner.round_session(plan)
    assert sess is not None, f"plan {plan.kind}/{plan.strategy} not steppable"

    state = sess.init(q)
    guard = cfg.max_rounds + 2
    while sess.active(state).any():
        state = sess.step(state)
        guard -= 1
        assert guard > 0, "round stepping failed to quiesce"
    res = sess.complete(q, sess.finalize(state))

    np.testing.assert_array_equal(np.asarray(res.ids),
                                  np.asarray(batch.ids))
    np.testing.assert_array_equal(np.asarray(res.dists),
                                  np.asarray(batch.dists))


@pytest.mark.parametrize("beam", [1, 4])
@pytest.mark.parametrize("masked", [False, True])
def test_core_stepped_matches_while_loop(tiny_index, tiny_store, beam,
                                         masked):
    """core.search.graph_search_stepped (init/step/finalize kernels driven
    from the host) is bit-identical to graph_search's lax.while_loop on
    every SearchResult field."""
    from repro.core.search import graph_search, graph_search_stepped

    idx = tiny_index
    corpus = idx.corpus()
    q = idx.dataset.queries[:6]
    cfg = dataclasses.replace(idx.config.search, beam_width=beam)
    mask = np.asarray(tiny_store.mask(SPEC_MODERATE)) if masked else None

    a = graph_search(corpus, q, cfg, idx.dataset.metric, node_mask=mask)
    b = graph_search_stepped(corpus, q, cfg, idx.dataset.metric,
                             node_mask=mask)
    for f in a._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(a, f)), np.asarray(getattr(b, f)),
            err_msg=f"field {f} diverged between while_loop and stepped",
        )


def test_round_session_none_for_scan_plans(tiny_index, tiny_store):
    """Bitmap-scan plans have no per-round structure: the planner declines a
    session and callers fall back to whole-batch execution."""
    s = Searcher.open(tiny_index, attributes=tiny_store)
    plan = s.plan(SearchRequest(queries=tiny_index.dataset.queries[:1],
                                filter=SPEC_SHARP))
    assert plan.strategy == "scan"
    assert s.planner.round_session(plan) is None


def test_step_is_noop_on_quiesced_lanes(tiny_index):
    """Stepping a fully-done state changes NO state leaf — free slots in a
    continuous pool never burn rounds or drift."""
    import jax

    s = Searcher.open(tiny_index)
    plan = s.plan(SearchRequest(queries=tiny_index.dataset.queries[:1]))
    sess = s.planner.round_session(plan)
    state = sess.init(tiny_index.dataset.queries[:4])
    guard = tiny_index.config.search.max_rounds + 2
    while sess.active(state).any():
        state = sess.step(state)
        guard -= 1
        assert guard > 0
    again = sess.step(state)
    for a, b in zip(jax.tree_util.tree_leaves(state),
                    jax.tree_util.tree_leaves(again)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
