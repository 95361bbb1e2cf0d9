"""``Searcher`` — the one supported host-side query API.

NDSEARCH and the computational-storage ANN platform of Kim et al. both hide
their accelerators behind a single query facade with an internal scheduler
picking the execution strategy; ``Searcher`` is that facade for this stack::

    s = Searcher.open(index, num_tiles=4, probe_tiles=2)
    res = s.search(SearchRequest(queries=q, k=10,
                                 filter=FilterSpec.eq("category", 3)))
    res.ids, res.dists            # (Q, k) numpy
    res.stats.as_dict()           # structured SearchStats
    res.plan                      # the executed QueryPlan (billing handle)

``open`` accepts every target the five legacy entry points used to take —
a built ``ProximaIndex``, a streaming ``stream.MutableIndex``, a raw device
``core.search.Corpus``, a partitioned ``shard.TiledCorpus``, or a
round-robin ``core.distributed.ShardedCorpus`` plus device mesh — resolves
a :class:`repro.configs.base.PlanConfig` against the index's own config,
and hands planning/execution to :class:`QueryPlanner`.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Optional

import numpy as np

from repro.configs.base import (
    FilterConfig, PlanConfig, SearchConfig,
)
from repro.obs import Observability
from repro.plan.planner import (
    Execution, IndexCapabilities, QueryPlan, QueryPlanner,
)
from repro.plan.request import SearchRequest, SearchResult

# legacy entry points that already warned this process — benchmark/serving
# loops hammer the deprecated wrappers thousands of times, and one warning
# per entry point is signal where one per call is stderr spam
_warned_legacy: set = set()


def warn_legacy(old: str, new: str = "repro.plan.Searcher.search") -> None:
    """One DeprecationWarning per legacy ENTRY POINT per process — the five
    pre-plan entry points are kept as thin wrappers that build a request and
    delegate.  ``reset_legacy_warnings`` re-arms them (tests)."""
    if old in _warned_legacy:
        return
    _warned_legacy.add(old)
    warnings.warn(
        f"{old} is a deprecated entry point kept for compatibility; build a "
        f"SearchRequest and call {new} instead (see README 'query plan "
        f"layer')",
        DeprecationWarning, stacklevel=3,
    )


def reset_legacy_warnings() -> None:
    """Re-arm every deduplicated deprecation warning (test helper)."""
    _warned_legacy.clear()


def validate_attribute_store(store, expected_rows: int, owner: str):
    """THE attribute-store/corpus length check, shared by ``Searcher.open``
    and ``ServingEngine`` (it used to be copy-pasted per engine branch).
    Returns the store for chaining; ``None`` passes through."""
    if store is not None and len(store) != expected_rows:
        raise ValueError(
            f"attribute store has {len(store)} rows, {owner} has "
            f"{expected_rows}"
        )
    return store


class Searcher:
    """Facade over one opened search target.  Use :meth:`open`."""

    def __init__(self, *, planner: QueryPlanner, plan_cfg: PlanConfig,
                 index=None, num_tiles: int = 1,
                 shard_policy: Optional[str] = None):
        self.planner = planner
        self.plan_cfg = plan_cfg
        self._index = index
        self.num_tiles = num_tiles
        self.shard_policy = shard_policy

    # --------------------------------------------------------------- opening
    @classmethod
    def open(cls, index, plan: Optional[PlanConfig] = None, *,
             cfg: Optional[SearchConfig] = None,
             metric: Optional[str] = None,
             attributes=None,
             num_tiles: Optional[int] = None,
             shard_policy: Optional[str] = None,
             probe_tiles: Optional[int] = None,
             beam_width: Optional[int] = None,
             filter_cfg: Optional[FilterConfig] = None,
             bloom_bits: Optional[int] = None,
             num_hashes: Optional[int] = None,
             use_vmap: Optional[bool] = None,
             mesh=None,
             mode: Optional[str] = None,
             data_axis: Optional[str] = None,
             queue_axis: Optional[str] = None,
             obs=None) -> "Searcher":
        """Open a search target.  Keyword arguments override the matching
        ``PlanConfig`` fields; unset fields defer to the index's own
        ``ProximaConfig`` sections, so ``Searcher.open(index)`` reproduces
        the index's configured serving mode exactly.

        ``obs`` takes an :class:`repro.obs.Observability` bundle (or an
        ``ObsConfig``); the planner then bills plan-cache traffic and wraps
        kernel execution in spans/histograms.  ``None`` (default) keeps the
        shared no-op bundle — zero overhead."""
        pc = plan or PlanConfig()
        obs = Observability.resolve(obs)
        kw = dict(search=cfg, num_tiles=num_tiles, shard_policy=shard_policy,
                  probe_tiles=probe_tiles, beam_width=beam_width,
                  filter=filter_cfg, bloom_bits=bloom_bits,
                  num_hashes=num_hashes, use_vmap=use_vmap, mode=mode,
                  data_axis=data_axis, queue_axis=queue_axis)
        pc = dataclasses.replace(
            pc, **{k: v for k, v in kw.items() if v is not None})

        from repro.core.search import Corpus

        if mesh is not None or _is_sharded_corpus(index):
            return cls._open_distributed(index, pc, metric, mesh, obs)
        if _is_mutable(index):
            return cls._open_mutable(index, pc, metric, attributes, obs)
        if isinstance(index, Corpus):
            return cls._open_corpus(index, pc, metric, attributes, obs)
        if _is_tiled(index):
            return cls._open_tiled(index, pc, metric, attributes, obs)
        if _is_segmented(index):
            return cls._open_segmented(index, pc, metric, attributes, obs)
        return cls._open_index(index, pc, metric, attributes, obs)

    # -- target-specific constructors (mirror the legacy engine branches) ----
    @classmethod
    def _resolve_cfg(cls, pc: PlanConfig, default: SearchConfig):
        scfg = pc.search or default
        if pc.beam_width is not None:
            scfg = dataclasses.replace(scfg, beam_width=pc.beam_width)
        return scfg

    @staticmethod
    def _probe_warning(probe_tiles: int, num_tiles: int, policy) -> None:
        if probe_tiles and num_tiles > 1 and policy != "cluster":
            warnings.warn(
                "probe_tiles routing assumes geometry-aware tiles "
                "(shard_policy='cluster'); with hash/contiguous allocation "
                "tile centroids are near-identical and routed recall "
                "collapses", stacklevel=3,
            )

    @classmethod
    def _open_index(cls, index, pc, metric, attributes, obs):
        from repro.configs.base import upgrade_config

        # pre-shard/filter-era pickled configs lack whole sections; upgrade
        # once at the boundary, then read fields directly
        cfg_full = upgrade_config(index.config)
        scfg = cls._resolve_cfg(pc, cfg_full.search)
        metric = metric or index.dataset.metric
        fcfg = pc.filter or cfg_full.filter
        shard_cfg = cfg_full.shard
        n_tiles = shard_cfg.num_tiles if pc.num_tiles is None else pc.num_tiles
        policy = shard_cfg.policy if pc.shard_policy is None \
            else pc.shard_policy
        probe = shard_cfg.probe_tiles if pc.probe_tiles is None \
            else pc.probe_tiles
        attributes = validate_attribute_store(
            attributes, index.dataset.num_base, "index"
        ) if attributes is not None else getattr(index, "attributes", None)
        tiled = corpus = None
        if n_tiles > 1:
            tiled, _ = index.sharded_corpus(n_tiles, policy)
        else:
            corpus = index.corpus()
        cls._probe_warning(probe, n_tiles, policy)
        caps = IndexCapabilities(
            kind="tiled" if tiled is not None else "flat",
            tiled=tiled is not None, num_tiles=n_tiles,
            has_attributes=attributes is not None,
        )
        planner = QueryPlanner(
            capabilities=caps, cfg=scfg, metric=metric, filter_cfg=fcfg,
            plan_cfg=pc, corpus=corpus, tiled=tiled, attributes=attributes,
            probe_tiles=probe, obs=obs,
        )
        return cls(planner=planner, plan_cfg=pc, index=index,
                   num_tiles=n_tiles, shard_policy=policy)

    @classmethod
    def _open_mutable(cls, mutable, pc, metric, attributes, obs):
        from repro.configs.base import upgrade_config

        base = mutable.base
        cfg_full = upgrade_config(base.config)
        scfg = cls._resolve_cfg(pc, cfg_full.search)
        metric = metric or base.dataset.metric
        fcfg = pc.filter or cfg_full.filter
        shard_cfg = cfg_full.shard
        probe = shard_cfg.probe_tiles if pc.probe_tiles is None \
            else pc.probe_tiles
        if attributes is not None:
            validate_attribute_store(
                attributes, mutable.next_ext,
                "mutable index (allocated external ids)",
            )
            mutable.attributes = attributes
        # tiling defaults come from the MutableIndex itself (it may have
        # been tiled manually); sync back only on an explicit request so an
        # opener with defaults never clobbers the index's serving mode
        n_tiles = mutable.num_tiles if pc.num_tiles is None else pc.num_tiles
        policy = mutable.shard_policy if pc.shard_policy is None \
            else pc.shard_policy
        if (n_tiles, policy) != (mutable.num_tiles, mutable.shard_policy):
            mutable.set_num_tiles(n_tiles, policy)
        cls._probe_warning(probe, n_tiles, policy)
        caps = IndexCapabilities(
            kind="merged", mutable=True, tiled=n_tiles > 1,
            num_tiles=n_tiles,
            has_attributes=mutable.attributes is not None,
        )
        planner = QueryPlanner(
            capabilities=caps, cfg=scfg, metric=metric, filter_cfg=fcfg,
            plan_cfg=pc, mutable=mutable, attributes=mutable.attributes,
            probe_tiles=probe, obs=obs,
        )
        if obs.enabled:
            mutable.obs = obs      # stream path: insert/consolidate spans
        return cls(planner=planner, plan_cfg=pc, index=mutable,
                   num_tiles=n_tiles, shard_policy=policy)

    @classmethod
    def _open_corpus(cls, corpus, pc, metric, attributes, obs):
        scfg = cls._resolve_cfg(pc, pc.search or SearchConfig())
        caps = IndexCapabilities(kind="flat",
                                 has_attributes=attributes is not None)
        planner = QueryPlanner(
            capabilities=caps, cfg=scfg, metric=metric or "l2",
            filter_cfg=pc.filter or FilterConfig(), plan_cfg=pc,
            corpus=corpus, attributes=attributes, obs=obs,
        )
        return cls(planner=planner, plan_cfg=pc)

    @classmethod
    def _open_tiled(cls, tiled, pc, metric, attributes, obs):
        scfg = cls._resolve_cfg(pc, pc.search or SearchConfig())
        probe = pc.probe_tiles or 0
        caps = IndexCapabilities(kind="tiled", tiled=True,
                                 num_tiles=tiled.num_tiles,
                                 has_attributes=attributes is not None)
        planner = QueryPlanner(
            capabilities=caps, cfg=scfg, metric=metric or "l2",
            filter_cfg=pc.filter or FilterConfig(), plan_cfg=pc,
            tiled=tiled, attributes=attributes, probe_tiles=probe, obs=obs,
        )
        return cls(planner=planner, plan_cfg=pc,
                   num_tiles=tiled.num_tiles)

    @classmethod
    def _open_segmented(cls, seg_index, pc, metric, attributes, obs):
        """A segment-built index (``core.segmented.SegmentedIndex``) is
        tiled-capable BY CONSTRUCTION: its segments are emitted as tiles
        directly (``shard.tiles_from_segments`` — no repartition, no per-
        tile graph rebuild) and its segment centroids are the router's
        coarse index, so ``probe_tiles`` routing works out of the box."""
        from repro.configs.base import upgrade_config

        cfg_full = upgrade_config(seg_index.config)
        scfg = cls._resolve_cfg(pc, cfg_full.search)
        metric = metric or seg_index.metric
        fcfg = pc.filter or cfg_full.filter
        probe = cfg_full.shard.probe_tiles if pc.probe_tiles is None \
            else pc.probe_tiles
        attributes = validate_attribute_store(
            attributes, seg_index.num_base, "segmented index")
        tiled, _ = seg_index.tiled_corpus()
        n_segments = seg_index.num_segments
        caps = IndexCapabilities(
            kind="tiled", tiled=True, num_tiles=n_segments,
            has_attributes=attributes is not None, segments=n_segments,
        )
        planner = QueryPlanner(
            capabilities=caps, cfg=scfg, metric=metric, filter_cfg=fcfg,
            plan_cfg=pc, tiled=tiled, attributes=attributes,
            probe_tiles=probe, obs=obs,
        )
        return cls(planner=planner, plan_cfg=pc, index=seg_index,
                   num_tiles=n_segments, shard_policy="segments")

    @classmethod
    def _open_distributed(cls, dcorpus, pc, metric, mesh, obs):
        from repro.core.distributed import place_corpus

        if mesh is None:
            raise ValueError("distributed targets need mesh=")
        dcorpus = place_corpus(dcorpus, mesh, pc.data_axis)
        scfg = cls._resolve_cfg(pc, pc.search or SearchConfig())
        caps = IndexCapabilities(
            kind="distributed", mesh_devices=int(mesh.size),
            num_tiles=getattr(dcorpus, "num_shards", 1),
        )
        planner = QueryPlanner(
            capabilities=caps, cfg=scfg, metric=metric or "l2",
            filter_cfg=pc.filter or FilterConfig(), plan_cfg=pc,
            dcorpus=dcorpus, mesh=mesh, obs=obs,
        )
        return cls(planner=planner, plan_cfg=pc,
                   num_tiles=getattr(dcorpus, "num_shards", 1))

    # -------------------------------------------------------------- querying
    def plan(self, request: SearchRequest) -> QueryPlan:
        return self.planner.plan(request)

    def execute(self, plan: QueryPlan, queries) -> Execution:
        """Run a precompiled plan over a (possibly padded) query batch —
        the serving engine's batch-flush path."""
        return self.planner.execute(plan, queries)

    def search(self, request: SearchRequest) -> SearchResult:
        """Plan + execute one request.  The only supported entry point."""
        plan = self.planner.plan(request)
        ex = self.planner.execute(plan, request.queries)
        res = SearchResult(ids=ex.ids, dists=ex.dists,
                           stats=self.planner.stats_for(plan, ex),
                           plan=plan, raw=ex.raw)
        qm = self.obs.quality
        if qm is not None:
            # shadow-recall sampling (off-path exact-oracle replay); the
            # engine's flush/retire paths feed the monitor themselves since
            # they execute plans directly
            qm.observe(self, plan, request.queries, res.ids)
        return res

    def round_session(self, plan: QueryPlan):
        """Steppable session for a plan (``None`` when the plan has no
        round-steppable spine) — planner pass-through, the continuous
        engine's and the convergence-telemetry driver's entry point."""
        return self.planner.round_session(plan)

    # ------------------------------------------------------- quality oracle
    def shadow_ground_truth(self, plan: QueryPlan, queries):
        """Exact-oracle neighbor ids for a query batch under ``plan``, in the
        plan's own result-id space — the shadow-recall estimator's ground
        truth (``obs.quality.QualityMonitor``).

        The oracle population is exactly what the plan searched: for merged
        plans the LIVE external corpus (``MutableIndex.live_vectors`` —
        tombstoned vectors excluded, delta inserts included; filtered via the
        live ``ext_mask``), for masked/scan plans the attribute-passing
        subset of the base, otherwise the full base.  Returns ``(Q, k')``
        int64 with ``k' = min(plan.cfg.k, population)`` (``k' = 0`` when
        nothing passes), or ``None`` where no oracle is resolvable —
        distributed fan-outs, legacy caller-mask plans (the one-shot mask is
        not durable), and raw tiled corpora with no backing dataset."""
        from repro.core.dataset import exact_knn

        if plan.kind == "distributed" or plan.mask_token:
            return None
        q = np.atleast_2d(np.asarray(queries, np.float32))
        k = int(plan.cfg.k)
        if plan.kind == "merged":
            mut = self.planner.mutable
            ext_ids, vecs = mut.live_vectors()
            if plan.spec is not None:
                _, ext_mask = mut.filter_masks(plan.spec)
                keep = np.asarray(ext_mask, bool)[ext_ids]
                ext_ids, vecs = ext_ids[keep], vecs[keep]
            if ext_ids.size == 0:
                return np.empty((q.shape[0], 0), np.int64)
            nn = exact_knn(q, vecs, k, mut.metric)   # caps k at |population|
            return ext_ids[nn].astype(np.int64)
        base = self._oracle_base()
        if base is None:
            return None
        if plan.spec is not None:
            mask = np.asarray(self.planner._mask_for(plan.spec), bool)
            pids = np.nonzero(mask)[0]
            if pids.size == 0:
                return np.empty((q.shape[0], 0), np.int64)
            nn = exact_knn(q, base[pids], k, self.metric)
            return pids[nn].astype(np.int64)
        return exact_knn(q, base, k, self.metric).astype(np.int64)

    def _oracle_base(self):
        """Base vectors in the target's internal (reordered) id space, or
        ``None`` when the opened target carries no raw vectors."""
        idx = self._index
        ds = getattr(idx, "dataset", None) if idx is not None else None
        if ds is not None:
            return np.asarray(ds.base, np.float32)
        if self.planner.corpus is not None:
            return np.asarray(self.planner.corpus.base, np.float32)
        return None

    # ------------------------------------------------------------ inspection
    @property
    def cfg(self) -> SearchConfig:
        return self.planner.cfg

    @property
    def metric(self) -> str:
        return self.planner.metric

    @property
    def filter_cfg(self) -> FilterConfig:
        return self.planner.filter_cfg

    @property
    def capabilities(self) -> IndexCapabilities:
        return self.planner.capabilities

    @property
    def mutable(self):
        return self.planner.mutable

    @property
    def corpus(self):
        return self.planner.corpus

    @property
    def tiled(self):
        return self.planner.tiled

    @property
    def attributes(self):
        return self.planner.attributes

    @property
    def probe_tiles(self) -> int:
        return self.planner.probe_tiles

    @property
    def obs(self) -> Observability:
        return self.planner.obs

    @property
    def index(self):
        """Current base index — the mutable's latest after consolidation."""
        if self.planner.mutable is not None:
            return self.planner.mutable.base
        return self._index

    def plan_cache_stats(self) -> dict:
        return {"plan_cache_hits": self.planner.plan_cache_hits,
                "plan_cache_misses": self.planner.plan_cache_misses}


def _is_mutable(obj) -> bool:
    return hasattr(obj, "delta") and hasattr(obj, "tombstones") \
        and hasattr(obj, "base")


def _is_tiled(obj) -> bool:
    return hasattr(obj, "tile_ids") and hasattr(obj, "entry_points")


def _is_sharded_corpus(obj) -> bool:
    return hasattr(obj, "num_shards") and hasattr(obj, "hot_adjacency")


def _is_segmented(obj) -> bool:
    """Segment-built index: per-segment mini-indexes + shared codebook,
    no single flat graph (``core.segmented.SegmentedIndex``)."""
    return hasattr(obj, "segments") and hasattr(obj, "codebook") \
        and not hasattr(obj, "graph")
