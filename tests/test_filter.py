"""Filtered ANN subsystem: FilterSpec/AttributeStore compilation, the
all-pass bit-identity guarantee, selectivity-adaptive regime choice,
filtered edge cases (empty result / all-pass / tombstone interaction),
per-tile bitmap slices with zero-pass tile skipping, per-request engine
filters batched by hash, NAND predicate-pushdown billing, and the
``upgrade_config`` forward-compat regression guard."""
import dataclasses
import pickle

import numpy as np
import pytest

from repro.configs.base import (
    FilterConfig, ProximaConfig, SearchConfig, StreamConfig, upgrade_config,
)
from repro.core import search, search_reference
from repro.core.dataset import exact_knn, recall_at_k
from repro.filter import (
    AttributeStore, FilterSpec, adapt_search_cfg, attach_attributes,
    bitmap_popcount, encode_categorical, filtered_search, pack_bitmap,
    random_attributes, tile_node_masks, unpack_bitmap,
)


@pytest.fixture(scope="module")
def tiny_store(tiny_index):
    # NOT attached to the shared index — tests pass masks/stores explicitly
    # so the session fixture stays pristine for attribute-free suites
    return random_attributes(tiny_index.dataset.num_base,
                             {"category": 8, "price": 1000}, seed=5)


# ---------------------------------------------------------------------------
# Spec + store units
# ---------------------------------------------------------------------------

def test_spec_compilation_and_composition():
    store = AttributeStore.from_columns({
        "cat": np.asarray([0, 1, 2, 1, 0]),
        "price": np.asarray([10, 20, 30, 40, 50]),
    })
    np.testing.assert_array_equal(
        store.mask(FilterSpec.eq("cat", 1)), [0, 1, 0, 1, 0])
    np.testing.assert_array_equal(
        store.mask(FilterSpec.range("price", 20, 40)), [0, 1, 1, 1, 0])
    np.testing.assert_array_equal(
        store.mask(FilterSpec.range("price", None, 30)), [1, 1, 1, 0, 0])
    np.testing.assert_array_equal(
        store.mask(FilterSpec.isin("cat", [0, 2])), [1, 0, 1, 0, 1])
    both = FilterSpec.eq("cat", 1) & FilterSpec.range("price", 30, None)
    np.testing.assert_array_equal(store.mask(both), [0, 0, 0, 1, 0])
    assert store.mask(FilterSpec()).all()           # empty spec passes all
    assert not store.mask(FilterSpec.isin("cat", [])).any()
    assert store.selectivity(FilterSpec.eq("cat", 0)) == pytest.approx(0.4)
    with pytest.raises(KeyError):
        store.mask(FilterSpec.eq("nope", 1))
    # specs are hashable and equal by value (the engine batches by this)
    assert hash(both) == hash(
        FilterSpec.eq("cat", 1) & FilterSpec.range("price", 30, None))


def test_bitmap_roundtrip_and_store_append():
    rng = np.random.default_rng(0)
    mask = rng.random(77) < 0.3
    bm = pack_bitmap(mask)
    assert bm.dtype == np.uint32
    np.testing.assert_array_equal(unpack_bitmap(bm, 77), mask)
    assert bitmap_popcount(bm) == int(mask.sum())

    store = AttributeStore.from_columns({"f": np.arange(3)})
    assert store.attr_bits == 32
    rid = store.append({"f": 7})
    assert rid == 3 and len(store) == 4
    assert store.append([9]) == 4
    np.testing.assert_array_equal(store.column("f"), [0, 1, 2, 7, 9])
    codes, vocab = encode_categorical(["shoes", "hats", "shoes"])
    np.testing.assert_array_equal(codes, [0, 1, 0])
    assert vocab == {"shoes": 0, "hats": 1}


def test_attach_attributes_validates(tiny_index):
    from repro.serve.engine import ServingEngine

    with pytest.raises(ValueError):
        attach_attributes(tiny_index, random_attributes(3))
    with pytest.raises(ValueError):   # frozen engine validates length too
        ServingEngine(tiny_index, batch_size=4, flush_us=0.0,
                      attributes=random_attributes(3))
    try:
        store = attach_attributes(
            tiny_index, random_attributes(tiny_index.dataset.num_base))
        assert tiny_index.attributes is store
    finally:
        tiny_index.attributes = None   # keep the shared fixture pristine


# ---------------------------------------------------------------------------
# All-pass bit-identity + edge cases
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("beam", [1, 4])
def test_allpass_filter_bit_identical(tiny_index, tiny_store, beam):
    """An all-pass FilterSpec goes through the masked traversal kernel yet
    returns bit-identical ids AND distances to the unfiltered search, at
    E=1 and E>1 (the acceptance guarantee)."""
    cfg = dataclasses.replace(tiny_index.config.search, beam_width=beam)
    q = tiny_index.dataset.queries[:8]
    metric = tiny_index.dataset.metric
    base = search(tiny_index.corpus(), q, cfg, metric)
    fres = filtered_search(tiny_index.corpus(), q,
                           tiny_store.mask(FilterSpec()), cfg, metric)
    assert fres.mode == "traversal" and fres.selectivity == 1.0
    assert fres.effective == cfg                     # no inflation at s=1
    np.testing.assert_array_equal(np.asarray(base.ids), fres.ids)
    np.testing.assert_array_equal(np.asarray(base.dists), fres.dists)


def test_empty_filter_returns_padding(tiny_index, tiny_store):
    fres = filtered_search(
        tiny_index.corpus(), tiny_index.dataset.queries[:4],
        np.zeros(tiny_index.dataset.num_base, bool),
        tiny_index.config.search, tiny_index.dataset.metric)
    assert fres.mode == "empty"
    assert (fres.ids == -1).all()
    assert np.isinf(fres.dists).all()
    assert int(np.asarray(fres.result.n_hops).sum()) == 0


def test_adaptive_regimes_and_admission(tiny_index, tiny_store):
    """Moderate selectivity -> masked traversal with an inflated frontier;
    high selectivity -> bitmap PQ scan. Both admit only passing nodes and
    clear the 0.9 recall bar against the filtered brute-force oracle."""
    cfg = tiny_index.config.search
    metric = tiny_index.dataset.metric
    q = tiny_index.dataset.queries
    fcfg = FilterConfig()
    for spec, want_mode in [
        (FilterSpec.range("price", 0, 99), "traversal"),   # ~10%
        (FilterSpec.range("price", 0, 14), "scan"),        # ~1.5%
    ]:
        mask = tiny_store.mask(spec)
        fres = filtered_search(tiny_index.corpus(), q, mask, cfg, metric,
                               filter_cfg=fcfg)
        assert fres.mode == want_mode
        got = fres.ids.ravel()
        assert all(mask[i] for i in got if i >= 0)
        pids = np.nonzero(mask)[0]
        k_eff = min(cfg.k, len(pids))
        gt = pids[exact_knn(q, tiny_index.dataset.base[pids], k_eff, metric)]
        assert recall_at_k(fres.ids, gt, k_eff) >= 0.9
    # inflation is pow2-quantized and capped
    eff = adapt_search_cfg(cfg, 0.1, fcfg)
    assert eff.list_size == cfg.list_size * 8
    assert eff.repetition_rate == cfg.repetition_rate + fcfg.relax_repetition
    assert adapt_search_cfg(cfg, 0.5, fcfg).list_size == cfg.list_size * 2


def test_reference_oracle_filtered(tiny_index, tiny_store):
    """search_reference(node_mask=...) returns only passing ids and agrees
    with the masked JAX engine on the large majority of results."""
    idx = tiny_index
    cfg = idx.config.search
    metric = idx.dataset.metric
    mask = tiny_store.mask(FilterSpec.range("price", 0, 199))   # ~20%
    eff = adapt_search_cfg(cfg, float(mask.mean()), FilterConfig())
    fres = filtered_search(idx.corpus(), idx.dataset.queries, mask, cfg,
                           metric)
    overlap = 0.0
    nq = 8
    for i in range(nq):
        ids, dists, _ = search_reference(
            idx.graph.adjacency, idx.graph.degrees, idx.codes,
            idx._search_base(), idx.codebook.centroids,
            idx.graph.entry_point, idx.dataset.queries[i], eff, metric,
            hot_count=idx.hot_count, node_mask=mask,
        )
        got = set(int(v) for v in ids if v >= 0)
        assert all(mask[v] for v in got)
        assert (np.diff(dists[np.isfinite(dists)]) >= -1e-6).all()
        overlap += len(got & set(int(v) for v in fres.ids[i] if v >= 0))
    assert overlap / (nq * cfg.k) >= 0.8


# ---------------------------------------------------------------------------
# Shard layer: per-tile bitmap slices + zero-pass skipping
# ---------------------------------------------------------------------------

def test_sharded_filtered_zero_pass_tiles(tiny_index):
    from repro.shard import partition_index, sharded_search

    idx = tiny_index
    tiled, _ = partition_index(idx, 4, "contiguous")
    hot = idx.hot_count
    mask = np.zeros(idx.dataset.num_base, bool)
    mask[hot + 20: hot + 140] = True     # cold band -> lands on few tiles
    nm = tile_node_masks(tiled.tile_ids, mask)
    counts = nm.sum(1)
    assert (counts == 0).any(), "test premise: at least one zero-pass tile"
    res = sharded_search(tiled, idx.dataset.queries[:6], idx.config.search,
                         idx.dataset.metric, node_masks=nm)
    probed = np.asarray(res.probed)
    hops = np.asarray(res.per_tile.n_hops)
    for p in range(4):
        if counts[p] == 0:               # skipped channel: no work, unprobed
            assert not probed[p].any()
            assert hops[p].sum() == 0
        else:
            assert probed[p].all()
    ids = np.asarray(res.ids)
    assert all(mask[i] for i in ids.ravel() if i >= 0)
    assert (ids[:, 0] >= 0).all()        # passing band still served


# ---------------------------------------------------------------------------
# Stream layer: attributes on insert, filter ∧ tombstone in merged search
# ---------------------------------------------------------------------------

def test_stream_filter_tombstone_interaction(tiny_index):
    from repro.stream import MutableIndex

    idx = tiny_index
    store = random_attributes(idx.dataset.num_base,
                              {"category": 8, "price": 1000}, seed=5)
    mut = MutableIndex(
        idx,
        StreamConfig(delta_capacity=256, consolidate_fraction=0.9,
                     brute_force_below=64, base_overfetch=16),
        attributes=store,
    )
    with pytest.raises(ValueError):
        mut.insert(idx.dataset.queries[0])           # attrs now required
    spec = FilterSpec.range("price", 0, 199)
    rng = np.random.default_rng(2)

    def _vec():
        return (
            idx.dataset.base[rng.integers(0, idx.dataset.num_base)]
            + 0.05 * rng.standard_normal(idx.dataset.dim)
        ).astype(np.float32)

    # group A passes the range spec; group B carries a sentinel price no
    # random base row can have (card 1000 -> values < 1000)
    group_a = [mut.insert(_vec(), attrs={"category": 1, "price": 100})
               for _ in range(8)]
    group_b = [mut.insert(_vec(), attrs={"category": 1, "price": 1500})
               for _ in range(8)]
    # tombstone a few PASSING base nodes, one of A and one of B
    dead = [int(i) for i in np.nonzero(store.mask(spec))[0][:4]]
    dead += [group_a[0], group_b[0]]
    for d in dead:
        assert mut.delete(d)
    res = mut.search(idx.dataset.queries[:8], idx.config.search,
                     filter_spec=spec)
    emask = mut.attributes.mask(spec)
    for i in np.asarray(res.ids).ravel():
        if i >= 0:
            assert emask[i], "non-passing id admitted"
            assert i not in mut.tombstones, "tombstoned id admitted"
    # a filter matching only the delta inserts returns only LIVE inserts —
    # the combined filter ∧ tombstone mask on the delta stream
    res2 = mut.search(idx.dataset.queries[:4], idx.config.search,
                      filter_spec=FilterSpec.eq("price", 1500))
    got = set(int(i) for i in np.asarray(res2.ids).ravel() if i >= 0)
    assert got and got <= set(group_b) - {group_b[0]}
    # empty-result filter through the merged path
    res3 = mut.search(idx.dataset.queries[:4], idx.config.search,
                      filter_spec=FilterSpec.eq("price", 2500))
    assert (np.asarray(res3.ids) == -1).all()


# ---------------------------------------------------------------------------
# Serving engine: per-request filters, batching by filter hash
# ---------------------------------------------------------------------------

def test_engine_filtered_requests(tiny_index, tiny_store):
    from repro.serve.engine import ServingEngine

    idx = tiny_index
    eng = ServingEngine(idx, batch_size=8, flush_us=0.0,
                        attributes=tiny_store)
    q = idx.dataset.queries[:12]
    spec = FilterSpec.range("price", 0, 99)
    mask = tiny_store.mask(spec)
    rids_f = [eng.submit(qq, filter=spec) for qq in q[:6]]
    rids_u = [eng.submit(qq) for qq in q[6:]]
    eng.drain()
    assert eng.stats["filtered_queries"] == 6
    # homogeneous batches: filtered results match the direct filtered path
    direct = filtered_search(idx.corpus(), q[:6], mask, eng.cfg,
                             idx.dataset.metric, filter_cfg=eng.filter_cfg)
    got = np.stack([eng.done[r].ids for r in rids_f])
    np.testing.assert_array_equal(got, direct.ids)
    # unfiltered requests are untouched by the batch split
    base = search(idx.corpus(), q[6:], eng.cfg, idx.dataset.metric)
    got_u = np.stack([eng.done[r].ids for r in rids_u])
    assert (np.sort(got_u, 1) == np.sort(np.asarray(base.ids), 1)).all()
    # an all-pass spec is normalized to the unfiltered batch
    rid = eng.submit(q[0], filter=FilterSpec())
    eng.drain()
    assert eng.done[rid].filter is None
    # filtered submit without a store raises
    bare = ServingEngine(idx, batch_size=4, flush_us=0.0)
    bare.submit(q[0], filter=spec)
    with pytest.raises(RuntimeError):
        bare.drain()


# ---------------------------------------------------------------------------
# NAND predicate pushdown billing
# ---------------------------------------------------------------------------

def test_pushdown_strictly_cheaper_transfer(tiny_index, tiny_store):
    from repro.nand.simulator import filter_comparison, trace_from_search_result

    idx = tiny_index
    spec = FilterSpec.range("price", 0, 99)
    mask = tiny_store.mask(spec)
    fres = filtered_search(idx.corpus(), idx.dataset.queries, mask,
                           idx.config.search, idx.dataset.metric)
    trace = trace_from_search_result(
        fres, dim=idx.dataset.dim, r_degree=idx.graph.max_degree,
        index_bits=idx.gap.bit_width if idx.gap else 32,
        pq_bits=idx.codebook.num_subvectors * 8,
        metric=idx.dataset.metric, attr_bits=tiny_store.attr_bits,
    )
    assert trace.filter_selectivity == pytest.approx(fres.selectivity)
    cmp = filter_comparison(trace)
    push, host = cmp["pushdown"], cmp["host"]
    # the acceptance bar: pushdown bills attribute words as spare-area
    # reads and ships only passing candidates -> strictly less channel
    # transfer energy than host-side filtering of the same trace
    assert push.transfer_pj_per_query < host.transfer_pj_per_query
    assert cmp["transfer_bytes_saved"] > 0
    assert host.traffic_bytes_per_query["attrs"] > 0
    assert push.traffic_bytes_per_query["attrs"] == 0.0
    assert push.traffic_bytes_per_query["pq_codes"] < \
        host.traffic_bytes_per_query["pq_codes"]
    # an unfiltered trace is billed exactly as before (attrs category empty)
    off = trace_from_search_result(
        fres.result, dim=idx.dataset.dim, r_degree=idx.graph.max_degree,
        index_bits=32, pq_bits=256, metric=idx.dataset.metric)
    assert off.filter_mode == "off" and off.attr_bits == 0
    # scan-mode regression: its candidate stream IS the passing subset, so
    # pushdown must not discount it again by the mask selectivity
    scan = filtered_search(idx.corpus(), idx.dataset.queries,
                           tiny_store.mask(FilterSpec.range("price", 0, 14)),
                           idx.config.search, idx.dataset.metric)
    assert scan.mode == "scan"
    scan_trace = trace_from_search_result(
        scan, dim=idx.dataset.dim, r_degree=idx.graph.max_degree,
        index_bits=32, pq_bits=256, metric=idx.dataset.metric,
        attr_bits=tiny_store.attr_bits)
    assert scan_trace.filter_selectivity == 1.0


def test_masked_search_beta_one_no_nan(tiny_index, tiny_store):
    """Regression: with beta=1.0 (used by the fig11/fig13 sweeps) and a
    filter leaving fewer than T passing candidates, the masked margin
    anchor is +inf — the threshold must stay +inf (rerank all passing),
    not go NaN and drop every result."""
    idx = tiny_index
    cfg = dataclasses.replace(idx.config.search, beta=1.0)
    mask = tiny_store.mask(FilterSpec.range("price", 0, 39))   # ~4% passing
    res = search(idx.corpus(), idx.dataset.queries[:6], cfg,
                 idx.dataset.metric, node_mask=np.asarray(mask))
    ids = np.asarray(res.ids)
    assert (ids[:, 0] >= 0).any(), "all results dropped (NaN threshold)"
    assert all(mask[i] for i in ids.ravel() if i >= 0)
    rid, rdists, _ = search_reference(
        idx.graph.adjacency, idx.graph.degrees, idx.codes,
        idx._search_base(), idx.codebook.centroids, idx.graph.entry_point,
        idx.dataset.queries[0], cfg, idx.dataset.metric,
        hot_count=idx.hot_count, node_mask=mask,
    )
    assert (rid >= 0).any() and not np.isnan(rdists).any()


def test_insert_attr_validation_precedes_mutation(tiny_index):
    """Regression: a malformed attrs row must fail BEFORE the vector is
    inserted, or the attribute table desyncs from the external ids."""
    from repro.stream import MutableIndex

    store = random_attributes(tiny_index.dataset.num_base, seed=3)
    mut = MutableIndex(tiny_index, attributes=store)
    before = (len(mut.delta), mut.next_ext, len(store))
    with pytest.raises(KeyError):
        mut.insert(tiny_index.dataset.queries[0], attrs={"typo": 1})
    assert (len(mut.delta), mut.next_ext, len(store)) == before
    ext = mut.insert(tiny_index.dataset.queries[0],
                     attrs={"category": 2, "price": 7})
    assert ext == tiny_index.dataset.num_base and len(store) == ext + 1


# ---------------------------------------------------------------------------
# upgrade_config forward-compat (regression guard for every future field)
# ---------------------------------------------------------------------------

def _strip_fields(cfg: ProximaConfig, names) -> ProximaConfig:
    """Simulate an instance pickled before ``names`` existed: rebuild the
    object with only the remaining attributes set."""
    old = object.__new__(ProximaConfig)
    for f in dataclasses.fields(ProximaConfig):
        if f.name not in names:
            object.__setattr__(old, f.name, getattr(cfg, f.name))
    return old


def test_upgrade_config_fills_missing_fields():
    cfg = ProximaConfig(search=SearchConfig(k=7, list_size=96))
    # a config pickled before FilterConfig existed upgrades with defaults
    old = _strip_fields(cfg, {"filter"})
    assert not hasattr(old, "filter")
    up = upgrade_config(old)
    assert up.filter == FilterConfig()
    assert up.search.k == 7 and up.search.list_size == 96
    # ... and the same holds for EVERY field, one at a time (the guard any
    # future ProximaConfig field inherits for free)
    for f in dataclasses.fields(ProximaConfig):
        up = upgrade_config(_strip_fields(cfg, {f.name}))
        expected = (
            f.default_factory()
            if f.default_factory is not dataclasses.MISSING else f.default
        )
        assert getattr(up, f.name) == expected
        assert upgrade_config(up) is up          # complete -> unchanged
    # pickle round-trip of a stripped instance stays upgradeable
    old = _strip_fields(cfg, {"filter", "shard", "stream"})
    thawed = pickle.loads(pickle.dumps(old))
    up = upgrade_config(thawed)
    assert up.filter == FilterConfig()
    assert up.search.k == 7


def test_sharded_corpus_upgrades_pre_shard_config(tiny_index):
    # ProximaIndex.sharded_corpus goes through upgrade_config rather than a
    # getattr default shim: an index whose config predates ShardConfig (and
    # BuildConfig) still shards with default policy
    import dataclasses as dc

    old_cfg = _strip_fields(tiny_index.config, {"shard", "build"})
    old_index = dc.replace(tiny_index, config=old_cfg)
    tiled, part = old_index.sharded_corpus(num_tiles=2)
    assert part.num_tiles == 2
    ref, _ = tiny_index.sharded_corpus(num_tiles=2)
    assert np.asarray(tiled.adjacency).shape == np.asarray(ref.adjacency).shape


# ---------------------------------------------------------------------------
# Tag-set fields: CSR + posting lists, containment predicates, served
# through ServingEngine against the plain filtered reference
# ---------------------------------------------------------------------------

TAG_VOCAB = 200_386          # the NeurIPS'23 filtered track's yfcc-10M vocabulary
TAG_ROWS, TAG_DIM, TAG_QUERIES = 1500, 192, 12


@pytest.fixture(scope="module")
def tag_data():
    """Seeded uint8-valued 192-d vectors (a Gaussian mixture rounded and
    clipped to 0..255; clusters of about 16, under the build list, so the
    graph's kNN lists reach across clusters) and 6-14 tags a row drawn by Zipf popularity over
    the full vocabulary, as CSR plus the Python sets the reference uses."""
    rng = np.random.default_rng(20261019)
    centers = rng.normal(128.0, 24.0, (96, TAG_DIM))

    def members(n):
        x = centers[rng.integers(0, len(centers), n)] \
            + rng.normal(0.0, 24.0, (n, TAG_DIM))
        return np.clip(np.rint(x), 0, 255).astype(np.float32)

    base, queries = members(TAG_ROWS), members(TAG_QUERIES)
    pop = 1.0 / np.arange(1, TAG_VOCAB + 1)
    drawn = rng.choice(TAG_VOCAB, size=(TAG_ROWS, 14), p=pop / pop.sum())
    counts = rng.integers(6, 15, TAG_ROWS)
    rows = [np.unique(drawn[i, :counts[i]]) for i in range(TAG_ROWS)]
    offsets = np.concatenate([[0], np.cumsum([len(r) for r in rows])])
    tags = np.concatenate(rows).astype(np.int32)
    sets = [set(r.tolist()) for r in rows]
    freq = np.bincount(tags, minlength=TAG_VOCAB)
    return dict(base=base, queries=queries, offsets=offsets, tags=tags,
                sets=sets, freq=freq)


def _admitted(sets, tags) -> np.ndarray:
    """The reference's pass mask: Python ``set`` containment, row by row."""
    want = set(tags)
    return np.asarray([want <= s for s in sets], bool)


def _predicates(freq) -> dict:
    """Predicates by regime over the 1,500 rows (scan: at most 2% pass,
    ``FilterConfig.brute_force_selectivity``; masked: more), each admitting
    at least k = 10 rows, and ones that admit none."""
    order = np.argsort(-freq, kind="stable")
    rare = [int(t) for t in order if 12 <= freq[t] <= 25]
    common = [int(t) for t in order[:4]]
    absent = int(np.flatnonzero(freq == 0)[-1])
    return {"masked": [(common[0],), (common[1],), (common[0], common[1])],
            "scan": [(rare[0],), (rare[1],), (common[0], rare[2])],
            "none": [(absent,), (common[0], absent), (TAG_VOCAB + 5,),
                     (-1,), (1 << 40,)]}


@pytest.fixture(scope="module")
def tag_index(tag_data):
    from repro.configs.base import (
        DatasetConfig, GraphConfig, PQConfig,
    )
    from repro.core import build_index
    from repro.core.dataset import Dataset

    cfg = ProximaConfig(
        dataset=DatasetConfig(name="yfcc-like", num_base=TAG_ROWS,
                              num_queries=0, dim=TAG_DIM, metric="l2"),
        pq=PQConfig(num_subvectors=48, num_centroids=128, kmeans_iters=8),
        graph=GraphConfig(max_degree=24, build_list_size=48, alpha=1.2),
        search=SearchConfig(k=10, list_size=64, t_init=16, t_step=8,
                            repetition_rate=3, beta=1.06),
        hot_node_fraction=0.03,
    )
    base = tag_data["base"]
    ds = Dataset(base=base, queries=np.zeros((0, TAG_DIM), np.float32),
                 gt=np.zeros((0, 10), np.int32), metric="l2",
                 config=cfg.dataset)
    index = build_index(cfg, dataset=ds, reorder_samples=24)
    to_corpus = np.arange(TAG_ROWS) if index.reordering is None \
        else np.asarray(index.reordering.inv, np.int64)
    store = AttributeStore.from_columns(
        {}, {"tags": (tag_data["offsets"], tag_data["tags"])})
    attach_attributes(index, store.permuted(to_corpus))
    return index, to_corpus


@pytest.mark.parametrize("permuted", [False, True])
@pytest.mark.parametrize("regime", ["masked", "scan", "none"])
def test_tag_mask_matches_python_sets(tag_data, regime, permuted):
    store = AttributeStore.from_columns(
        {}, {"tags": (tag_data["offsets"], tag_data["tags"])})
    sets = tag_data["sets"]
    if permuted:
        perm = np.random.default_rng(3).permutation(TAG_ROWS)
        store, sets = store.permuted(perm), [sets[i] for i in perm]
    tf = store.tag_fields["tags"]
    for tags in _predicates(tag_data["freq"])[regime]:
        spec = FilterSpec.contains("tags", tags)
        want = _admitted(sets, tags)
        mask, read = store.mask_and_reads(spec)
        np.testing.assert_array_equal(mask, want)
        assert int(mask.sum()) == int(want.sum())
        assert store.selectivity(spec) == pytest.approx(want.mean())
        # posting lists are read shortest first, and not past an empty one
        lens = sorted(len(tf.postings(t)) for t in tags)
        assert read == (lens[0] if lens[0] == 0 else sum(lens))
        for t in tags:
            np.testing.assert_array_equal(
                tf.postings(t), np.flatnonzero(_admitted(sets, (t,))))


def test_contains_spec_normalised_and_mixed_with_columns():
    a = FilterSpec.contains("tags", {7, 3})
    assert a == FilterSpec.contains("tags", (3, 7, 3, 7))
    assert hash(a) == hash(FilterSpec.contains("tags", [np.int64(3), 7]))
    assert a.predicates[0].tags == (3, 7)
    store = AttributeStore.from_columns(
        {"cat": np.asarray([0, 1, 1, 0])},
        {"tags": (np.asarray([0, 2, 3, 5, 5]), np.asarray([3, 7, 3, 3, 7]))})
    np.testing.assert_array_equal(store.mask(a), [1, 0, 1, 0])
    both = a & FilterSpec.eq("cat", 1)
    np.testing.assert_array_equal(store.mask(both), [0, 0, 1, 0])
    np.testing.assert_array_equal(
        store.mask(FilterSpec.eq("cat", 0) & FilterSpec.contains("tags", ())),
        [1, 0, 0, 1])                      # no tag to hold: every row holds it
    np.testing.assert_array_equal(
        store.mask(both), store.permuted([0, 1, 2, 3]).mask(both))
    assert store.attr_bits == 32 + 32 * 5 // 4     # column + 32 a tag a row
    with pytest.raises(KeyError, match="unknown tag field"):
        store.mask(FilterSpec.contains("labels", (3,)))
    with pytest.raises(TypeError, match="tag index"):
        a.evaluate(store.values, store.fields)
    with pytest.raises(ValueError, match="one tag twice"):
        AttributeStore.from_columns({}, {"tags": ([0, 2], [4, 4])})


def test_tag_store_holds_nothing_of_rows_by_vocabulary(tag_data):
    store = AttributeStore.from_columns(
        {}, {"tags": (tag_data["offsets"], tag_data["tags"])})
    stored = len(tag_data["tags"])
    assert int(tag_data["tags"].max()) > TAG_VOCAB // 2    # the tail is used
    tf = store.tag_fields["tags"]
    arrays = [store.values, tf.offsets, tf.tags, tf._rows, tf._keys,
              tf._starts]
    assert all(a.size <= TAG_ROWS + stored + 1 for a in arrays)
    assert store.nbytes == sum(a.nbytes for a in arrays)
    assert store.nbytes <= 8 * (2 * TAG_ROWS + 4 * stored)
    assert store.nbytes * 1000 < TAG_ROWS * TAG_VOCAB


@pytest.mark.parametrize("regime", ["masked", "scan"])
def test_served_tag_filters_match_reference(tag_data, tag_index, regime):
    """Both regimes through ``ServingEngine`` against the plain reference:
    float64 exact top-k among the rows Python ``set`` containment admits."""
    from repro.serve.engine import ServingEngine

    index, to_corpus = tag_index
    base64 = tag_data["base"].astype(np.float64)
    eng = ServingEngine(index, batch_size=8, flush_us=0.0)
    preds = _predicates(tag_data["freq"])[regime]
    reqs = [(qi, tags, eng.submit(q, filter=FilterSpec.contains("tags", tags)))
            for tags in preds for qi, q in enumerate(tag_data["queries"])]
    eng.drain()
    assert {eng.done[r].plan.strategy for _, _, r in reqs} == {regime}
    assert (eng.stats["filter_scan_batches"] > 0) == (regime == "scan")
    assert eng.stats["filtered_queries"] == len(reqs)
    recalls, gaps = [], []
    for qi, tags, rid in reqs:
        r = eng.done[rid]
        ids = to_corpus[np.asarray(r.ids)]           # corpus row ids
        admitted = np.flatnonzero(_admitted(tag_data["sets"], tags))
        assert len(admitted) >= 10
        assert set(ids.tolist()) <= set(admitted.tolist())
        q = tag_data["queries"][qi].astype(np.float64)
        truth = admitted[exact_knn(q[None], base64[admitted], 10, "l2")[0]]
        recalls.append(len(set(ids.tolist()) & set(truth.tolist())) / 10)
        exact = ((base64[ids] - q) ** 2).sum(-1)
        gaps.append(np.abs(np.asarray(r.dists, np.float64) - exact).max())
    # the track's own operating point; this data reads 0.997 (masked), 1.0
    # (scan)
    assert np.mean(recalls) >= 0.9
    # whole-number vectors: every squared difference, and every partial sum
    # (at most 192 * 255^2 < 2^24), is exact in float32
    assert max(gaps) == 0.0


def test_empty_tag_predicates_answer_nothing(tag_data, tag_index):
    from repro.serve.engine import ServingEngine

    index, _ = tag_index
    eng = ServingEngine(index, batch_size=8, flush_us=0.0)
    q = tag_data["queries"][0]
    rids = [eng.submit(q, filter=FilterSpec.contains("tags", tags))
            for tags in _predicates(tag_data["freq"])["none"]]
    eng.drain()
    for rid in rids:
        assert eng.done[rid].plan.strategy == "empty"
        assert (np.asarray(eng.done[rid].ids) == -1).all()


def test_filter_compile_span_and_counters(tag_data, tag_index):
    from repro.configs.base import ObsConfig
    from repro.serve.engine import ServingEngine

    index, _ = tag_index
    store = index.attributes
    eng = ServingEngine(index, batch_size=8, flush_us=0.0,
                        obs=ObsConfig(metrics=True, tracing=True))
    preds = _predicates(tag_data["freq"])
    specs = [FilterSpec.contains("tags", t)
             for t in (preds["masked"][2], preds["scan"][0])]
    for spec in specs + specs[:1]:           # the third is a plan-cache hit
        eng.submit(tag_data["queries"][0], filter=spec)
    eng.drain()
    st = eng.stats
    assert st["filter_masks_compiled"] == 2
    assert st["filter_posting_entries"] == sum(
        store.mask_and_reads(s)[1] for s in specs)
    assert st["filter_compile_s"] > 0
    m = eng.obs.metrics
    assert m.counter_total("filter_masks_compiled") == 2
    assert m.counter_total("filter_posting_entries") == \
        st["filter_posting_entries"]
    assert m.counter_total("filter_compile_s") == pytest.approx(
        st["filter_compile_s"])
    spans = [e for e in eng.obs.tracer.events()
             if e["ph"] == "X" and e["name"] == "filter-compile"]
    assert len(spans) == 2 and {e["cat"] for e in spans} == {"plan"}
    assert {e["args"]["strategy"] for e in spans} == {"masked", "scan"}


def test_mutable_index_refuses_tag_field_insert(tag_data, tag_index):
    from repro.stream import MutableIndex

    index, to_corpus = tag_index
    store = index.attributes
    mut = MutableIndex(index, attributes=store)
    before = (len(mut.delta), mut.next_ext, len(store), store.nbytes)
    with pytest.raises(NotImplementedError, match="tag-set fields"):
        mut.insert(tag_data["queries"][0], attrs={"tags": (1, 2)})
    assert (len(mut.delta), mut.next_ext, len(store), store.nbytes) == before
    # the tag filter still serves reads through the merged path
    tags = _predicates(tag_data["freq"])["masked"][2]
    res = mut.search(tag_data["queries"][:2],
                     filter_spec=FilterSpec.contains("tags", tags))
    ids = to_corpus[np.asarray(res.ids)[np.asarray(res.ids) >= 0]]
    assert ids.size and _admitted(tag_data["sets"], tags)[ids].all()
