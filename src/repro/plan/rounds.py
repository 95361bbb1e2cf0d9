"""Round-stepped plan execution — the continuous-batching bridge between the
plan layer and the ``core.search`` step kernels.

A :class:`RoundSession` is the steppable form of one compiled ``QueryPlan``:
where ``QueryPlanner.execute`` runs the plan's whole traversal inside one
``lax.while_loop``, a session exposes the SAME traversal one round at a time
(``init`` / ``step`` / ``active`` / ``finalize``) so an iteration-level
scheduler (``ServingEngine(continuous=True)``) can retire finished lanes and
refill their slots between rounds.  ``complete`` then applies the plan's
post-processing (filtered-result wrapping, or the merged path's delta /
tombstone fusion) to a retired lane batch, producing the same plan-layer
``SearchResult`` the batch executor returns — bit-identically, which is what
lets the round-step equivalence suite compare the two paths end to end.

Not every plan has a round-steppable spine.  Sessions exist for:

  * ``flat``/``none``      — the plain Algorithm-1 traversal;
  * ``flat``/``masked``    — masked traversal with the planner-cached mask;
  * ``merged``/``none``    — the single-tile base traversal stepped, with
    ``stream.searcher._merge_base_delta`` fusing delta candidates and
    tombstones at retire time (delta/tombstone state is read LIVE at retire;
    the base admission mask is pinned at session creation);
  * ``merged``/``adaptive`` — ditto, when the live regime decision resolves
    to masked traversal.

``tiled``/``distributed`` fan-outs, bitmap ``scan``s and ``empty``
short-circuits have no per-round structure; ``QueryPlanner.round_session``
returns ``None`` for them and callers fall back to whole-batch ``execute``.
"""
from __future__ import annotations

from typing import Any, Optional

import numpy as np

from repro.configs.base import SearchConfig


class RoundSession:
    """Steppable execution of one ``QueryPlan``.  Create via
    ``QueryPlanner.round_session(plan)``; all lane batches passed to
    ``init``/``step`` must share one shape ``(Q, D)`` — the fixed slot-pool
    shape — so the step kernel compiles once per (plan, Q)."""

    def __init__(
        self,
        *,
        planner,
        plan,
        corpus,
        cfg: SearchConfig,
        metric: str,
        bloom_bits: int,
        num_hashes: int,
        node_mask: Optional[np.ndarray] = None,
        mutable=None,
        ext_mask: Optional[np.ndarray] = None,
        selectivity: float = 1.0,
        base_mode: str = "none",
    ):
        import jax.numpy as jnp

        self.planner = planner
        self.plan = plan
        self.corpus = corpus
        self.cfg = cfg                  # EFFECTIVE traversal config (merged
                                        # sessions: base over-fetch k applied)
        self.metric = metric
        self.bloom_bits = int(bloom_bits)
        self.num_hashes = int(num_hashes)
        self._mask = None if node_mask is None else jnp.asarray(node_mask, bool)
        self.mutable = mutable
        self.ext_mask = ext_mask
        self.selectivity = float(selectivity)
        self.base_mode = base_mode

    # ------------------------------------------------------------- stepping
    # Spans (category ``plan``) mark each device program's dispatch —
    # ``init``, ``round``, ``finalize`` — and each blocking read goes through
    # the planner's ``DeviceSyncs``.
    def init(self, queries):
        """Round 0 for a (Q, D) batch -> ``core.search.SearchState``."""
        import jax.numpy as jnp

        from repro.core.search import init_search_state

        with self.planner.obs.tracer.span("init", cat="plan"):
            q = jnp.asarray(np.atleast_2d(np.asarray(queries, np.float32)))
            return init_search_state(self.corpus, q, self.cfg, self.metric,
                                     self.bloom_bits, self.num_hashes,
                                     self._mask)

    def step(self, state):
        """ONE traversal round over every lane; quiet lanes pass through."""
        from repro.core.search import graph_search_step

        with self.planner.obs.tracer.span("round", cat="plan"):
            return graph_search_step(self.corpus, state, self.cfg,
                                     self.metric, self.bloom_bits,
                                     self.num_hashes, self._mask)

    def active(self, state) -> np.ndarray:
        """(Q,) bool host array — lanes with rounds still to run."""
        from repro.core.search import search_state_active

        tracer = self.planner.obs.tracer
        with tracer.span("active-sync", cat="plan"):
            with tracer.span("dispatch", cat="plan"):
                active = search_state_active(state, self.cfg)
            return self.planner.syncs.get("active", active)

    def rounds(self, state) -> np.ndarray:
        """(Q,) int host array — rounds each lane has executed so far."""
        return self.planner.syncs.get("rounds", state.lanes.rounds)

    def finalize(self, state):
        """Beta rerank + top-k over the batch -> core ``SearchResult``."""
        from repro.core.search import finalize_search

        with self.planner.obs.tracer.span("finalize", cat="plan"):
            return finalize_search(self.corpus, state, self.cfg, self.metric,
                                   self._mask)

    def record_round(self, log, qids, state, select=None) -> None:
        """Append one per-round telemetry record per (selected) lane to an
        ``obs.convergence.ConvergenceLog`` — the engine's tick path and the
        off-line dataset driver (``obs.convergence.trace_session``) share
        this so the feature extraction has one owner (the session knows the
        effective k)."""
        log.record_lanes(qids, state, int(self.cfg.k), select=select)

    # -------------------------------------------------------------- retire
    def complete(self, queries, core_res):
        """Post-process a finalized lane batch into the plan-layer
        ``SearchResult`` the batch executor would have returned for the same
        queries: wrap filtered results, or (merged plans) fuse the base
        candidates with the LIVE delta segment and tombstone set.  The reply
        feeds ``obs.record_plan_execution`` unchanged — retired batches bill
        exactly like flushed ones."""
        from repro.plan.planner import Execution
        from repro.plan.request import SearchResult as PlanSearchResult

        plan = self.plan
        if plan.kind == "merged":
            from repro.stream.searcher import MergedResult, _merge_base_delta

            q_np = np.atleast_2d(np.asarray(queries, np.float32))
            ext_mask = self.ext_mask
            if plan.spec is not None:
                # the external-id mask is re-derived LIVE: vectors inserted
                # after session creation extend the id space (the pinned
                # mask would be short) and their attribute rows must filter
                # the delta stream; only the base traversal's admission
                # mask stays pinned for the lane's flight
                _, ext_mask = self.mutable.filter_masks(plan.spec)
            ids, dists, n_delta = _merge_base_delta(
                self.mutable, q_np, np.asarray(core_res.ids),
                np.asarray(core_res.dists), ext_mask, plan.cfg.k,
            )
            raw: Any = MergedResult(
                ids=ids, dists=dists, base=core_res,
                delta_candidates=n_delta, selectivity=self.selectivity,
                base_mode=self.base_mode,
            )
            ex = Execution(ids=ids, dists=dists, raw=raw, counters=core_res,
                           selectivity=self.selectivity,
                           delta_candidates=float(np.asarray(n_delta).mean()))
        elif plan.strategy == "masked":
            from repro.filter.traversal import FilteredSearchResult

            raw = FilteredSearchResult(
                ids=np.asarray(core_res.ids), dists=np.asarray(core_res.dists),
                result=core_res, mode="traversal",
                selectivity=plan.selectivity, effective=plan.cfg,
            )
            ex = Execution(ids=raw.ids, dists=raw.dists, raw=raw,
                           counters=core_res, selectivity=plan.selectivity,
                           delta_candidates=0.0)
        else:
            ex = Execution(ids=np.asarray(core_res.ids),
                           dists=np.asarray(core_res.dists), raw=core_res,
                           counters=core_res, selectivity=1.0,
                           delta_candidates=0.0)
        stats = self.planner.stats_for(plan, ex)
        return PlanSearchResult(ids=ex.ids, dists=ex.dists, stats=stats,
                                plan=plan, raw=ex.raw)
