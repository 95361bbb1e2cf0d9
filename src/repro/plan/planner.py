"""QueryPlanner — request + index capabilities -> executable ``QueryPlan``.

The planner inspects what the index can do (static vs mutable, tiled vs
flat, attribute store present, device mesh size) and what the request asks
for (filter selectivity, beam width, per-request overrides) and compiles a
``QueryPlan``: a hashable strategy record naming the composition of existing
kernels that serves the request —

  * ``kind``      — which execution spine: ``flat`` (one compiled Algorithm-1
    engine), ``tiled`` (per-channel fan-out + cross-tile merge), ``merged``
    (base + DRAM delta segment with tombstone fusion), ``distributed``
    (shard_map collectives over a device mesh);
  * ``strategy``  — where the filter runs: ``none``, ``masked`` traversal
    (inflated frontier), bitmap PQ ``scan``, ``empty`` short-circuit, or
    ``adaptive`` (mutable targets — the admission mask depends on the live
    tombstone set, so the regime is re-decided at execute time exactly as
    the legacy merged path did);
  * the *effective* ``SearchConfig`` actually executed (selectivity-adapted
    for masked traversal), the routing fan-in (``probe_tiles``), and the
    billing facts the NAND model reads off the plan (``selectivity``,
    ``attr_bits``, ``pushdown``).

``plan.cache_key`` is the batching identity: two requests with the same key
execute the same compiled composition, which is what lets ``ServingEngine``
batch by plan instead of by ad-hoc filter hash.  Compiled artifacts (pass
masks, per-tile bitmap slices) are planner-cached per key — the replacement
for the engine's old ``_filter_cache``.

Every plan is bit-identical to the legacy entry point it replaces: the
executor calls the SAME kernels with the SAME arguments the five old paths
did (see tests/test_plan.py for the enforced equivalence matrix).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, NamedTuple, Optional, Tuple

import numpy as np

from repro.configs.base import (
    FilterConfig, PlanConfig, SearchConfig, upgrade_config,
)
from repro.filter.spec import FilterSpec
from repro.obs import NULL_OBS, DeviceSyncs, Observability
from repro.plan.request import SearchRequest, SearchStats


@dataclasses.dataclass(frozen=True)
class IndexCapabilities:
    """What the opened index supports — the planner's input alongside the
    request (derived once by ``Searcher.open``)."""
    kind: str                        # flat | tiled | merged | distributed
    mutable: bool = False
    tiled: bool = False
    num_tiles: int = 1
    has_attributes: bool = False
    mesh_devices: int = 0            # device count (distributed targets)
    segments: int = 0                # >0: segment-built index served through
                                     # direct-emitted tiles (one per build
                                     # segment), with segment centroids as
                                     # the router's coarse index


@dataclasses.dataclass(frozen=True)
class QueryPlan:
    """One executable strategy: the composition of kernels serving a
    request.  Frozen and hashable — ``cache_key`` is the serving layer's
    batching identity and the artifact-cache key."""
    kind: str                        # flat | tiled | merged | distributed
    strategy: str                    # none | masked | scan | empty | adaptive
    cfg: SearchConfig                # EFFECTIVE config executed (adapted)
    metric: str
    spec: Optional[FilterSpec] = None
    selectivity: float = 1.0         # exact passing fraction (static targets)
    probe_tiles: int = 0             # 0 -> full fan-out
    num_tiles: int = 1
    attr_bits: int = 0               # spare-area word the NAND model bills
    pushdown: bool = True            # predicate evaluated inside the tile
    tenant: Optional[str] = None     # namespace slot — part of the cache
                                     # key, so tenants never co-batch (the
                                     # multi-tenancy roadmap item's hook)
    mask_token: int = 0              # >0: plan built from a caller mask, not
                                     # a spec (legacy wrappers) — keeps the
                                     # artifact cache collision-free

    @property
    def cache_key(self) -> tuple:
        """Batching/artifact identity — everything that selects a distinct
        compiled execution (selectivity is derived from ``spec``, so it is
        deliberately absent)."""
        return (self.kind, self.strategy, self.metric, self.cfg, self.spec,
                self.probe_tiles, self.tenant, self.mask_token)


class Execution(NamedTuple):
    """Internal executor reply: host arrays + the raw kernel result + the
    counter source the stats/billing layers read."""
    ids: np.ndarray
    dists: np.ndarray
    raw: Any
    counters: Any                    # core SearchResult-like (or None)
    selectivity: float
    delta_candidates: float


def _mean_counters(res) -> dict:
    """Per-query mean counters from a core ``SearchResult`` (a sharded
    result's (P, Q) counters are summed across the tile axis first — the
    total cross-channel work per query, same convention as the NAND
    traces)."""
    if res is None:
        return {}
    per = res.per_tile if hasattr(res, "per_tile") else res
    agg = (lambda x: float(np.asarray(x).sum(0).mean())) \
        if np.asarray(per.n_hops).ndim > 1 else \
        (lambda x: float(np.asarray(x).mean()))
    return dict(
        hops=agg(per.n_hops), pq=agg(per.n_pq), acc=agg(per.n_acc),
        hot_hops=agg(per.n_hot_hops), free_pq=agg(per.n_free_pq),
        rounds=agg(per.rounds),
    )


def flat_filtered_search(corpus, queries, mask, cfg: SearchConfig,
                         metric: str, filter_cfg: Optional[FilterConfig] = None):
    """Selectivity-adaptive filtered search over a flat corpus through a
    one-off plan — the SINGLE regime-decision point, shared by the
    ``filter.filtered_search`` wrapper (via ``Searcher``) and the merged
    base-segment path (``stream.searcher``).  Returns the
    ``FilteredSearchResult`` the legacy path produced, bit-identically."""
    fcfg = filter_cfg or FilterConfig()
    pc = PlanConfig(search=cfg, filter=fcfg)
    planner = QueryPlanner(
        capabilities=IndexCapabilities(kind="flat"), cfg=cfg, metric=metric,
        filter_cfg=fcfg, plan_cfg=pc, corpus=corpus,
    )
    request = SearchRequest(queries=queries, node_mask=mask, adaptive=True)
    return planner.execute(planner.plan(request), queries).raw


class QueryPlanner:
    """Compiles ``SearchRequest`` -> ``QueryPlan`` and executes plans over
    one opened target.  Owns the plan cache (hit/miss counters feed the
    serving stats and ``benchmarks/planner_bench``) and the per-plan
    artifact cache (compiled masks / per-tile bitmap slices)."""

    def __init__(
        self,
        *,
        capabilities: IndexCapabilities,
        cfg: SearchConfig,
        metric: str,
        filter_cfg: FilterConfig,
        plan_cfg: PlanConfig,
        corpus=None,
        tiled=None,
        mutable=None,
        dcorpus=None,
        mesh=None,
        attributes=None,
        probe_tiles: int = 0,
        obs: Optional[Observability] = None,
    ):
        self.capabilities = capabilities
        self.cfg = cfg
        self.metric = metric
        self.filter_cfg = filter_cfg
        self.plan_cfg = plan_cfg
        self.corpus = corpus
        self.tiled = tiled
        self.mutable = mutable
        self.dcorpus = dcorpus
        self.mesh = mesh
        self.attributes = attributes
        self.probe_tiles = int(probe_tiles or 0)
        self._plan_cache: Dict[tuple, QueryPlan] = {}
        self._mask_cache: Dict[FilterSpec, np.ndarray] = {}
        self._artifacts: Dict[tuple, dict] = {}
        self._mask_tokens = 0
        self.plan_cache_hits = 0
        self.plan_cache_misses = 0
        self.obs = obs or NULL_OBS
        # every blocking device->host read of this target's served path
        self.syncs = DeviceSyncs(self.obs)

    # ------------------------------------------------------------- planning
    def plan(self, request: SearchRequest) -> QueryPlan:
        """Compile (or fetch from the plan cache) the strategy serving
        ``request``.  Mask-escape-hatch requests are compiled fresh — the
        mask has no hashable identity."""
        if request.node_mask is not None:
            return self._plan_for_mask(request)
        spec = request.filter
        if spec is not None and getattr(spec, "is_all", False):
            spec = None              # all-pass spec == unfiltered plan
        key = (spec, request.k, request.override_items(),
               request.probe_tiles, request.tenant)
        cached = self._plan_cache.get(key)
        if cached is not None:
            self.plan_cache_hits += 1
            if self.obs.enabled:
                self.obs.metrics.counter("plan_cache_hits",
                                         tenant=request.tenant)
            return cached
        self.plan_cache_misses += 1
        plan = self._compile(spec, request)
        self._plan_cache[key] = plan
        if self.obs.enabled:
            self.obs.metrics.counter("plan_cache_misses",
                                     tenant=request.tenant)
            self.obs.metrics.counter("plans_compiled", kind=plan.kind,
                                     strategy=plan.strategy,
                                     tenant=request.tenant)
        return plan

    def _effective_cfg(self, request: SearchRequest) -> SearchConfig:
        cfg = self.cfg
        if request.k is not None and request.k != cfg.k:
            cfg = dataclasses.replace(cfg, k=int(request.k))
        items = request.override_items()
        if items:
            cfg = dataclasses.replace(cfg, **dict(items))
        return cfg

    def _resolved_probe(self, request: SearchRequest) -> int:
        p = self.probe_tiles if request.probe_tiles is None \
            else int(request.probe_tiles)
        return int(p or 0)

    def _mask_for(self, spec: FilterSpec) -> np.ndarray:
        mask = self._mask_cache.get(spec)
        if mask is None:
            if self.attributes is None:
                raise RuntimeError(
                    "filtered search needs an attribute store — pass "
                    "attributes= to Searcher.open / ServingEngine or attach "
                    "one to the index"
                )
            mask = np.asarray(self.attributes.mask(spec), bool)
            self._mask_cache[spec] = mask
        return mask

    def _filter_strategy(self, mask: np.ndarray, k: int) -> Tuple[str, float]:
        """The selectivity regime switch — the exact ``filtered_search``
        decision, now owned by the planner."""
        n = mask.size
        n_pass = int(mask.sum())
        sel = n_pass / max(n, 1)
        if n_pass == 0:
            return "empty", 0.0
        if sel <= self.filter_cfg.brute_force_selectivity or n_pass <= k:
            return "scan", sel
        return "masked", sel

    def _attr_bits(self) -> int:
        if self.attributes is not None:
            return int(self.attributes.attr_bits)
        return int(self.filter_cfg.attr_bits)

    def _compile(self, spec: Optional[FilterSpec],
                 request: SearchRequest) -> QueryPlan:
        from repro.filter.traversal import adapt_search_cfg, tile_node_masks

        cfg = self._effective_cfg(request)
        probe = self._resolved_probe(request)
        caps = self.capabilities
        common = dict(metric=self.metric, probe_tiles=probe,
                      num_tiles=caps.num_tiles, tenant=request.tenant,
                      pushdown=bool(self.filter_cfg.pushdown))
        if caps.kind == "distributed":
            if spec is not None:
                raise NotImplementedError(
                    "the distributed (device-mesh) path has no filtered "
                    "traversal — drop the filter or open a flat/tiled target"
                )
            return QueryPlan(kind="distributed", strategy="none", cfg=cfg,
                             **common)
        if caps.mutable:
            # the admission mask depends on the live tombstone set, so the
            # regime is re-decided inside the merged kernel at execute time
            strategy = "none" if spec is None else "adaptive"
            return QueryPlan(kind="merged", strategy=strategy, cfg=cfg,
                             spec=spec,
                             attr_bits=self._attr_bits() if spec else 0,
                             **common)
        if caps.tiled:
            if spec is None:
                return QueryPlan(kind="tiled", strategy="none", cfg=cfg,
                                 **common)
            mask = self._mask_for(spec)
            sel = float(mask.mean())
            eff = adapt_search_cfg(cfg, sel, self.filter_cfg)
            plan = QueryPlan(kind="tiled", strategy="masked", cfg=eff,
                             spec=spec, selectivity=sel,
                             attr_bits=self._attr_bits(), **common)
            self._artifacts[plan.cache_key] = {
                "mask": mask,
                "node_masks": tile_node_masks(self.tiled.tile_ids, mask),
            }
            return plan
        # ---- flat ----------------------------------------------------------
        if spec is None:
            return QueryPlan(kind="flat", strategy="none", cfg=cfg, **common)
        mask = self._mask_for(spec)
        strategy, sel = self._filter_strategy(mask, cfg.k)
        eff = adapt_search_cfg(cfg, sel, self.filter_cfg) \
            if strategy == "masked" else cfg
        plan = QueryPlan(kind="flat", strategy=strategy, cfg=eff, spec=spec,
                         selectivity=sel, attr_bits=self._attr_bits(),
                         **common)
        self._artifacts[plan.cache_key] = {"mask": mask}
        return plan

    def _plan_for_mask(self, request: SearchRequest) -> QueryPlan:
        """Plans for caller-precompiled masks — what the deprecated wrappers
        delegate through.  ``adaptive`` selects ``filtered_search``
        semantics (regime switch + config adaptation) vs the verbatim
        ``core.search(node_mask=...)`` traversal."""
        from repro.filter.traversal import adapt_search_cfg

        cfg = self._effective_cfg(request)
        probe = self._resolved_probe(request)
        caps = self.capabilities
        self._mask_tokens += 1
        token = self._mask_tokens
        common = dict(metric=self.metric, probe_tiles=probe,
                      num_tiles=caps.num_tiles, tenant=request.tenant,
                      mask_token=token, attr_bits=self._attr_bits(),
                      pushdown=bool(self.filter_cfg.pushdown))
        if caps.kind == "tiled":
            # per-tile slices, applied verbatim (legacy sharded_search
            # leaves config adaptation to its caller)
            node_masks = np.asarray(request.node_mask, bool)
            plan = QueryPlan(kind="tiled", strategy="masked", cfg=cfg,
                             selectivity=float(node_masks.mean()), **common)
            self._artifacts[plan.cache_key] = {"node_masks": node_masks}
            return plan
        if caps.kind != "flat":
            raise NotImplementedError(
                "precompiled node masks apply to flat or tiled targets only "
                f"(target is {caps.kind}); use FilterSpec requests instead"
            )
        mask = np.asarray(request.node_mask, bool)
        if not request.adaptive:
            plan = QueryPlan(kind="flat", strategy="masked", cfg=cfg,
                             selectivity=float(mask.mean()), **common)
            self._artifacts[plan.cache_key] = {"mask": mask}
            return plan
        strategy, sel = self._filter_strategy(mask, cfg.k)
        eff = adapt_search_cfg(cfg, sel, self.filter_cfg) \
            if strategy == "masked" else cfg
        plan = QueryPlan(kind="flat", strategy=strategy, cfg=eff,
                         selectivity=sel, **common)
        self._artifacts[plan.cache_key] = {"mask": mask}
        return plan

    # -------------------------------------------------------- round stepping
    def round_session(self, plan: QueryPlan):
        """The round-steppable form of ``plan`` (a ``repro.plan.rounds.
        RoundSession``), or ``None`` when the plan has no per-round spine —
        tiled / distributed fan-outs, bitmap scans, empty short-circuits,
        one-shot mask-token plans — in which case callers fall back to
        whole-batch ``execute``.  Merged plans re-decide the live filter
        regime here (exactly like the merged kernel does at execute time)
        and are steppable only when it resolves to masked traversal on a
        single-tile base."""
        from repro.plan.rounds import RoundSession

        pc = self.plan_cfg
        if plan.kind == "flat" and not plan.mask_token:
            if plan.strategy == "none":
                return RoundSession(
                    planner=self, plan=plan, corpus=self.corpus, cfg=plan.cfg,
                    metric=self.metric, bloom_bits=pc.bloom_bits,
                    num_hashes=pc.num_hashes,
                )
            if plan.strategy == "masked":
                art = self._artifacts.get(plan.cache_key) or {}
                mask = art.get("mask")
                if mask is None:
                    return None
                return RoundSession(
                    planner=self, plan=plan, corpus=self.corpus, cfg=plan.cfg,
                    metric=self.metric, bloom_bits=pc.bloom_bits,
                    num_hashes=pc.num_hashes, node_mask=mask,
                    selectivity=plan.selectivity,
                )
            return None
        if plan.kind == "merged":
            mut = self.mutable
            if mut is None or getattr(mut, "num_tiles", 1) > 1:
                return None
            k = plan.cfg.k
            k_base = min(plan.cfg.list_size,
                         k + mut.stream_cfg.base_overfetch)
            base_cfg = dataclasses.replace(plan.cfg, k=k_base) \
                if k_base != k else plan.cfg
            # the merged kernel calls graph_search with ITS defaults (the
            # flat_filtered_search planner likewise uses a default
            # PlanConfig), so merged sessions must too — bit-identity
            common = dict(planner=self, plan=plan, metric=mut.metric,
                          bloom_bits=1 << 17, num_hashes=8, mutable=mut)
            if plan.strategy == "none":
                return RoundSession(corpus=mut.corpus(), cfg=base_cfg,
                                    **common)
            # adaptive: combined filter ∧ ¬tombstone admission masks against
            # the LIVE tombstone set, regime re-decided like the kernel does
            fcfg = upgrade_config(mut.base.config).filter
            base_mask, ext_mask = mut.filter_masks(plan.spec)
            base_mask = np.asarray(base_mask, bool)
            n_pass = int(base_mask.sum())
            sel = n_pass / max(base_mask.size, 1)
            if n_pass == 0 or sel <= fcfg.brute_force_selectivity \
                    or n_pass <= base_cfg.k:
                return None          # scan / empty regimes: not steppable
            from repro.filter.traversal import adapt_search_cfg

            eff = adapt_search_cfg(base_cfg, sel, fcfg)
            return RoundSession(corpus=mut.corpus(), cfg=eff,
                                node_mask=base_mask, ext_mask=ext_mask,
                                selectivity=sel, base_mode="traversal",
                                **common)
        return None

    def _artifacts_for(self, plan: QueryPlan) -> dict:
        """Compiled artifacts for a plan.  Spec-keyed plans keep theirs
        cached (the engine re-executes them every flush); mask-token plans
        are ONE-SHOT — the caller-supplied mask has no durable identity, so
        its artifacts are popped here to keep a long-lived planner from
        accumulating one (N,) mask per legacy-wrapper call."""
        if plan.mask_token:
            return self._artifacts.pop(plan.cache_key, {})
        return self._artifacts.get(plan.cache_key, {})

    # ------------------------------------------------------------ execution
    def execute(self, plan: QueryPlan, queries) -> Execution:
        """Run one compiled plan over a query batch — dispatching to the
        SAME kernels, with the SAME arguments, as the legacy entry point the
        plan replaces (the bit-identity contract).

        With observability enabled the execution is wrapped in a
        ``kernel-execute`` span and billed into ``kernel_execute_ms``
        (labeled by plan kind / filter strategy / tenant).  Inside it,
        ``dispatch`` covers the kernel call up to its return, and the
        result fetch (``DeviceSyncs.get``) the wait for the device and the
        copy to the host; the traversal rounds inside the compiled
        while_loop show only in the device trace, by named scope."""
        obs = self.obs
        if not obs.enabled:
            return self._execute_plan(plan, queries)
        import time as _time

        t0 = _time.perf_counter()
        with obs.tracer.span("kernel-execute", cat="plan", kind=plan.kind,
                             strategy=plan.strategy) as sp:
            ex = self._execute_plan(plan, queries)
            sp.set(queries=int(np.atleast_2d(np.asarray(ex.ids)).shape[0]))
        obs.metrics.observe(
            "kernel_execute_ms", (_time.perf_counter() - t0) * 1e3,
            kind=plan.kind, strategy=plan.strategy, tenant=plan.tenant,
        )
        return ex

    def _execute_plan(self, plan: QueryPlan, queries) -> Execution:
        import jax.numpy as jnp

        tracer, syncs = self.obs.tracer, self.syncs
        if plan.kind == "distributed":
            from repro.core.distributed import distributed_search_kernel

            pc = self.plan_cfg
            with tracer.span("dispatch", cat="plan"):
                raw = distributed_search_kernel(
                    self.dcorpus, queries, plan.cfg, self.metric, pc.mode,
                    mesh=self.mesh, data_axis=pc.data_axis,
                    queue_axis=pc.queue_axis, bloom_bits=pc.bloom_bits,
                    num_hashes=pc.num_hashes,
                )
            ids, dists = syncs.get("execute", raw)
            return Execution(ids=ids, dists=dists, raw=raw, counters=None,
                             selectivity=1.0, delta_candidates=0.0)

        q_np = np.atleast_2d(np.asarray(queries, np.float32))
        if plan.kind == "merged":
            from repro.stream.searcher import merged_search_kernel

            # the merged kernel fetches and fuses on the host itself, so
            # its dispatch span holds its waits and copies too
            with tracer.span("dispatch", cat="plan"):
                res = merged_search_kernel(
                    self.mutable, q_np, plan.cfg,
                    probe_tiles=plan.probe_tiles or None,
                    filter_spec=plan.spec,
                )
            return Execution(ids=res.ids, dists=res.dists, raw=res,
                             counters=res.base, selectivity=res.selectivity,
                             delta_candidates=float(
                                 np.asarray(res.delta_candidates).mean()),
                             )
        if plan.kind == "tiled":
            from repro.shard.search import sharded_search_kernel

            node_masks = None
            if plan.strategy == "masked":
                node_masks = self._artifacts_for(plan)["node_masks"]
            with tracer.span("dispatch", cat="plan"):
                res = sharded_search_kernel(
                    self.tiled, q_np, plan.cfg, self.metric,
                    use_vmap=self.plan_cfg.use_vmap,
                    probe_tiles=plan.probe_tiles or None,
                    node_masks=node_masks,
                )
            ids, dists = syncs.get("execute", (res.ids, res.dists))
            return Execution(ids=ids, dists=dists, raw=res,
                             counters=res, selectivity=plan.selectivity,
                             delta_candidates=0.0)

        # ---- flat ----------------------------------------------------------
        from repro.core.search import empty_search_result, graph_search
        from repro.filter.traversal import FilteredSearchResult, scan_search

        pc = self.plan_cfg
        if plan.strategy == "none":
            with tracer.span("dispatch", cat="plan"):
                res = graph_search(self.corpus, q_np, plan.cfg, self.metric,
                                   pc.bloom_bits, pc.num_hashes)
            ids, dists = syncs.get("execute", (res.ids, res.dists))
            return Execution(ids=ids, dists=dists, raw=res,
                             counters=res, selectivity=1.0,
                             delta_candidates=0.0)
        nq = q_np.shape[0]
        if plan.strategy == "scan":
            mask = self._artifacts_for(plan)["mask"]
            # the bitmap scan fetches its candidates on the host itself
            with tracer.span("dispatch", cat="plan"):
                fres = scan_search(self.corpus, jnp.asarray(q_np), mask,
                                   plan.cfg, self.metric, self.filter_cfg,
                                   plan.selectivity)
        else:
            if plan.strategy == "empty":
                with tracer.span("dispatch", cat="plan"):
                    core = empty_search_result(nq, plan.cfg.k)
                mode, sel = "empty", 0.0
            else:                    # masked traversal, plan.cfg pre-adapted
                mask = self._artifacts_for(plan)["mask"]
                with tracer.span("dispatch", cat="plan"):
                    core = graph_search(self.corpus, jnp.asarray(q_np),
                                        plan.cfg, self.metric, pc.bloom_bits,
                                        pc.num_hashes,
                                        node_mask=jnp.asarray(mask))
                mode, sel = "traversal", plan.selectivity
            ids, dists = syncs.get("execute", (core.ids, core.dists))
            fres = FilteredSearchResult(
                ids=ids, dists=dists, result=core, mode=mode,
                selectivity=sel, effective=plan.cfg,
            )
        return Execution(ids=fres.ids, dists=fres.dists, raw=fres,
                         counters=fres.result, selectivity=fres.selectivity,
                         delta_candidates=0.0)

    # ----------------------------------------------------------------- stats
    def stats_for(self, plan: QueryPlan, execution: Execution) -> SearchStats:
        counters = _mean_counters(execution.counters)
        return SearchStats(
            queries=int(np.atleast_2d(execution.ids).shape[0]),
            k=plan.cfg.k, kind=plan.kind, strategy=plan.strategy,
            selectivity=float(execution.selectivity),
            delta_candidates=float(execution.delta_candidates),
            beam_width=int(upgrade_config(plan.cfg).beam_width),
            num_tiles=plan.num_tiles, **counters,
        )
