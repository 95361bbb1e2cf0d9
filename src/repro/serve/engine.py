"""Batched ANN-search serving engine — the software twin of the paper's
search-engine frontend (scheduler + N_q queues, §IV-D), rebuilt on the
query-plan layer.

Requests arrive individually; each ``submit`` compiles (or plan-cache-hits)
a ``repro.plan.QueryPlan`` and the scheduler packs requests into fixed-size
batches BY PLAN CACHE KEY (requests sharing a compiled execution strategy —
same kind, filter strategy, effective config — flush together; with uniform
filters this degenerates to plain FIFO batching, exactly the old
filter-hash behaviour).  The flush runs the plan once over the padded
bucket through the shared ``Searcher`` facade and completes futures.
Single-threaded event-loop style, deterministic.

The engine serves every target the plan layer can open — a frozen
``ProximaIndex`` (flat or tiled) or a streaming ``stream.MutableIndex``.
In streaming mode ``insert``/``delete`` interleave with ``submit``: updates
apply immediately (the delta segment is DRAM-resident), queued queries
observe every update applied before their batch flushes, and consolidation
runs *between* batches once the delta exceeds its configured fraction —
never inside one, so the compiled base search shape is stable within a
batch.

All per-feature constructor kwargs (num_tiles / shard_policy / probe_tiles
/ beam_width) are legacy sugar folded into one ``PlanConfig``; the ad-hoc
per-spec ``_filter_cache`` is gone — compiled masks live in the planner's
artifact cache, keyed by plan.

Observability (``repro.obs``): pass ``obs=Observability.on()`` (or an
``ObsConfig``) and the engine records queue-wait / end-to-end latency
histograms and a batch-occupancy gauge labeled by plan kind / filter
strategy / tenant, emits per-request ``queue-wait`` async trace spans
nested over each flush's ``batch`` > ``batch-assembly`` / ``kernel-execute``
/ ``post-process`` spans, watches the jit caches for unexpected recompiles
(budget: pow2 buckets x distinct executed plans), and — with
``nand_billing`` — bills every flushed batch through the NAND cost model
into the same registry.  The default is the shared no-op bundle: one
predictable branch per call site, no allocation, no timing.

All engine timing uses ``time.perf_counter()`` — the monotonic clock;
``time.time()`` is wall-clock and jumps under NTP step corrections, which
produced negative latencies and spurious/missed flush timeouts.

Continuous batching (``ServingEngine(continuous=True)``): instead of
flushing whole batches through one ``lax.while_loop``, the engine keeps a
fixed pool of ``slots`` in-flight lanes per plan cache key and, on each
``step()`` call (a "tick"), advances ALL of them in one device dispatch
until the first lane quiesces (the plan layer's ``RoundSession.advance``
over the ``core.search`` round-step kernels).  Lanes whose traversal
quiesces are retired on that round — beta rerank, delta/tombstone fusion
for merged plans, NAND billing, future completion — and their slots refill
from the queue on the next tick, so no query ever waits on another's last
round.  Requests are admitted the moment a slot is free (no flush window);
plans without a round-steppable spine (tiled / distributed fan-outs, bitmap
scans) fall back to the batch-flush path transparently.  Slot pools hold
ONE fixed lane shape per plan, so the round-step kernels compile once per
(plan, slots) — the same pow2-bucket recompile budget applies.

Streaming caveats in continuous mode: a lane traverses the base corpus (and,
when filtered, the admission mask) pinned at its session's creation, while
tombstones and the delta segment are read LIVE at retire time — deleted
vectors never surface, inserts are visible to every lane retired after them.
Consolidation rebuilds the base id space, so the engine completes all
in-flight merged lanes BEFORE consolidating (including the capacity-forced
consolidation inside ``insert``) and then re-creates their sessions against
the fresh base.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import time
from collections import deque
from typing import Deque, Dict, List, Optional, Union

import numpy as np

from repro.configs.base import PlanConfig, SearchConfig
from repro.core.index import ProximaIndex
from repro.core.search import next_pow2
from repro.filter.spec import FilterSpec
from repro.obs import (
    KernelWatch, Observability, SLOTracker, record_plan_execution,
)
from repro.plan import QueryPlan, Searcher, SearchRequest
from repro.stream.mutable import MutableIndex


@dataclasses.dataclass
class Request:
    rid: int
    query: np.ndarray
    t_submit: float = 0.0
    t_done: float = 0.0
    ids: Optional[np.ndarray] = None
    dists: Optional[np.ndarray] = None
    # per-request attribute filter — requests sharing a compiled plan (the
    # spec is part of its cache key) are batched together so one compiled
    # execution serves the whole batch; None = unfiltered
    filter: Optional[FilterSpec] = None
    # namespace slot: part of the plan cache key (tenants never co-batch)
    # and the SLO tracker's accounting key
    tenant: Optional[str] = None
    # the compiled strategy serving this request (assigned at submit)
    plan: Optional[QueryPlan] = None

    @property
    def latency_ms(self) -> float:
        return (self.t_done - self.t_submit) * 1e3


@dataclasses.dataclass
class EngineStats:
    """Structured serving counters — the typed record ``ServingEngine.stats``
    derives its back-compat dict from (no more hand-maintained counter dict
    to drift)."""
    batches: int = 0
    queries: int = 0
    pad_fraction: float = 0.0        # running MEAN pad share over batches
    inserts: int = 0
    deletes: int = 0
    consolidations: int = 0
    filtered_queries: int = 0
    filter_scan_batches: int = 0
    ticks: int = 0                   # continuous mode: round-step ticks run
    pool_dispatches: int = 0         # continuous mode: advance dispatches
                                     # (one host read each)
    pool_rounds: int = 0             # continuous mode: traversal rounds
                                     # those dispatches ran
    retired: int = 0                 # continuous mode: lanes retired
    fallback_batches: int = 0        # continuous mode: non-steppable plans
                                     # served through the batch-flush path
    slo_violations: int = 0          # rolling-window SLO breaches observed
                                     # (per-tenant detail in the registry's
                                     # slo_violations{tenant,slo} counters)
    # plan_cache_hits / plan_cache_misses intentionally live on the PLANNER
    # (the component that owns the cache); ``ServingEngine.stats`` merges
    # them into the dict view at read time instead of hand-syncing fields

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class _SlotPool:
    """One plan's fixed pool of in-flight lanes (continuous mode).  ``state``
    is a ``core.search.SearchState`` over exactly ``len(requests)`` lanes —
    the ONE compiled shape this pool's round-step kernels ever see; free
    slots hold quiesced dummy lanes (``done=True``) so stepping them is a
    no-op."""
    session: object                          # plan.RoundSession
    requests: List[Optional[Request]]        # slot -> in-flight request
    state: object = None                     # lazily built on first admit

    @property
    def occupied(self) -> int:
        return sum(r is not None for r in self.requests)


_select_jit = None


def _select_lanes(mask: np.ndarray, new, old):
    """Per-lane select over two same-shape ``SearchState``s: lane i comes
    from ``new`` where ``mask[i]`` — the fixed-shape slot-refill primitive
    (no concatenation, no shape change, no recompile).  Jitted as one call:
    per-leaf eager ``where``s cost a dispatch per state field."""
    global _select_jit
    import jax
    import jax.numpy as jnp

    if _select_jit is None:
        def _f(m, a, b):
            return jax.tree_util.tree_map(
                lambda x, y: jnp.where(
                    m.reshape(m.shape + (1,) * (x.ndim - 1)), x, y),
                a, b,
            )
        _select_jit = jax.jit(_f)
    return _select_jit(np.asarray(mask), new, old)


_gather_jit = None


def _gather_rows(state, rows: np.ndarray):
    """Row-gather a ``SearchState`` down to the given lanes (device-side),
    every leaf, in one jitted call.  The served retire does not use it
    (``RoundSession.retire`` gathers, finalizes and packs in one program);
    ``finalize(_gather_rows(state, rows))`` is the stepped reference that
    retire is tested against."""
    global _gather_jit
    import jax

    if _gather_jit is None:
        _gather_jit = jax.jit(
            lambda s, i: jax.tree_util.tree_map(lambda a: a[i], s))
    return _gather_jit(state, rows)


def _quiet_free_lanes(state, occupied: np.ndarray):
    """Force ``done=True`` on unoccupied lanes so they never burn rounds —
    a free slot's dummy query must not traverse."""
    import jax.numpy as jnp

    m = jnp.asarray(occupied)
    lanes = state.lanes._replace(
        done=jnp.where(m, state.lanes.done, True))
    return state._replace(lanes=lanes)


class ServingEngine:
    def __init__(
        self,
        index: Union[ProximaIndex, MutableIndex],
        batch_size: int = 32,
        cfg: Optional[SearchConfig] = None,
        flush_us: float = 2000.0,
        auto_consolidate: bool = True,
        num_tiles: Optional[int] = None,
        shard_policy: Optional[str] = None,
        probe_tiles: Optional[int] = None,
        beam_width: Optional[int] = None,
        attributes=None,
        plan: Optional[PlanConfig] = None,
        obs=None,
        continuous: bool = False,
        slots: Optional[int] = None,
        nand=None,
        nand_queues: Optional[int] = None,
        slo=None,
    ):
        """``slo`` takes a ``{tenant: obs.SLOTarget}`` mapping (key ``None``
        covers untenanted traffic); completed requests then feed per-tenant
        rolling latency windows — and, with ``obs`` quality monitoring on,
        shadow-recall windows — whose breaches count into
        ``EngineStats.slo_violations`` and the registry's
        ``slo_violations{tenant,slo}`` counters."""
        pcfg = plan or PlanConfig()
        legacy = dict(search=cfg, num_tiles=num_tiles,
                      shard_policy=shard_policy, probe_tiles=probe_tiles,
                      beam_width=beam_width)
        pcfg = dataclasses.replace(
            pcfg, **{k: v for k, v in legacy.items() if v is not None})
        self.obs = Observability.resolve(obs)
        self.searcher = Searcher.open(index, pcfg, attributes=attributes,
                                      obs=self.obs)
        self.batch_size = batch_size
        self.flush_us = flush_us
        self.auto_consolidate = auto_consolidate
        self.continuous = bool(continuous)
        self.slots = int(slots) if slots else batch_size
        self.nand = nand                     # NandConfig override for billing
                                             # (e.g. double_buffer=True)
        self.nand_queues = nand_queues       # modeled scheduler queue count
                                             # (Fig. 16 N_q sweep knob)
        self.queue: Deque[Request] = deque()
        self.done: Dict[int, Request] = {}
        self._next = 0
        self._stats = EngineStats()
        self._plan_keys_seen: set = set()    # recompile-budget denominator
        self._pools: Dict[tuple, _SlotPool] = {}
        self._sessions: Dict[tuple, object] = {}   # key -> RoundSession|None
        self._plan_memo: Dict[int, tuple] = {}     # id(plan) -> (plan,
                                                   #   session, cache_key)
        self._slo = SLOTracker(self.obs.metrics, slo) if slo else None
        if self.obs.quality is not None and self._slo is not None:
            # shadow-recall samples are the only recall observations the SLO
            # windows can get — wire the monitor to feed them
            self.obs.quality.slo = self._slo
        if self.obs.enabled:
            self.obs.install_kernel_hooks()
        # warm the compile for the full-batch bucket (smaller power-of-two
        # buckets compile lazily on first use); warm-up queries are synthetic
        # — keep them out of the shadow-recall sampling stream
        dummy = np.zeros((batch_size, self.index.dataset.dim), np.float32)
        qm = self.obs.quality
        with (qm.paused() if qm is not None else contextlib.nullcontext()):
            self.searcher.search(SearchRequest(queries=dummy))
        if self.continuous:
            # warm the round-step kernels at the slot-pool shape for the
            # default (unfiltered) plan, so serving-time ticks start hot
            plan0 = self.searcher.plan(SearchRequest(queries=dummy[:1]))
            sess0 = self._session_for(plan0)
            if sess0 is not None:
                z = np.zeros((self.slots, dummy.shape[1]), np.float32)
                st, _, _ = sess0.advance(sess0.init(z), 1)
                # and the retire at every power-of-two row count a retire
                # can pad to
                for b in range(int(math.log2(next_pow2(self.slots))) + 1):
                    sess0.retire(st, np.zeros((1 << b,), np.int64))
        # recompile watchdog baselined AFTER warm-up, so only serving-time
        # jit-cache growth is judged against the pow2-bucket x plan budget
        self._watch = KernelWatch(self.obs.metrics) \
            if self.obs.metrics.enabled else None

    def _bucket(self, n: int) -> int:
        """Smallest power-of-two >= n, capped at batch_size — the fixed set
        of compiled batch shapes (at most log2(batch_size)+1 executables, so
        varying queue depths never trigger a fresh jit compile)."""
        return min(next_pow2(max(n, 1)), self.batch_size)

    # -------------------------------------------- plan-layer pass-throughs
    @property
    def mutable(self) -> Optional[MutableIndex]:
        return self.searcher.mutable

    @property
    def index(self) -> ProximaIndex:
        """Current base index — always the mutable's latest after any
        consolidation (including capacity-forced ones inside insert)."""
        return self.searcher.index

    @property
    def cfg(self) -> SearchConfig:
        return self.searcher.cfg

    @property
    def metric(self) -> str:
        return self.searcher.metric

    @property
    def filter_cfg(self):
        return self.searcher.filter_cfg

    @property
    def attributes(self):
        return self.searcher.attributes

    @property
    def tiled(self):
        return self.searcher.tiled

    @property
    def corpus(self):
        return self.searcher.corpus

    @property
    def num_tiles(self) -> int:
        return self.searcher.num_tiles

    @property
    def shard_policy(self):
        return self.searcher.shard_policy

    @property
    def probe_tiles(self) -> int:
        return self.searcher.probe_tiles

    @property
    def stats(self) -> dict:
        """Back-compat dict view, derived from the structured
        ``EngineStats`` with the planner's plan-cache counters, its counts
        of blocking device->host reads (``device_syncs``) and of the arrays
        they copied (``device_copies``) and its filter compilation counters
        (``filter_masks_compiled``, ``filter_posting_entries``,
        ``filter_compile_s``) merged in at read time (the planner owns
        them; nothing is hand-synced)."""
        d = self._stats.as_dict()
        d.update(self.searcher.plan_cache_stats())
        syncs = self.searcher.planner.syncs
        d["device_syncs"] = syncs.total
        d["device_copies"] = syncs.copies
        d.update(self.searcher.planner.filter_compile_stats())
        return d

    def slo_status(self) -> dict:
        """Per-tenant rolling-window SLO state (empty without ``slo=``)."""
        return self._slo.status() if self._slo is not None else {}

    # --------------------------------------------------------------- requests
    def submit(self, query: np.ndarray, filter: Optional[FilterSpec] = None,
               tenant: Optional[str] = None) -> int:
        """Queue one query; ``filter`` (a hashable ``FilterSpec``) restricts
        results to attribute-passing nodes. The request's ``QueryPlan`` is
        compiled here (plan-cache hit for every repeated spec) and requests
        batch by its cache key — ``tenant`` is part of that key, so tenants
        never co-batch and their latency/recall account separately (SLO
        tracking, quality labels)."""
        rid = self._next
        self._next += 1
        if filter is not None and getattr(filter, "is_all", False):
            filter = None                 # all-pass spec == unfiltered batch
        q = np.asarray(query, np.float32)
        obs = self.obs
        try:
            plan = self.searcher.plan(SearchRequest(queries=q, filter=filter,
                                                    tenant=tenant))
        except RuntimeError:
            # missing attribute store: accept the request and surface the
            # error at flush time, like the legacy engine did
            plan = None
        self.queue.append(Request(rid=rid, query=q,
                                  t_submit=time.perf_counter(),
                                  filter=filter, tenant=tenant, plan=plan))
        if obs.enabled:
            # queue residency is an async span: many requests overlap, so a
            # synchronous nested span on one track cannot represent it
            obs.tracer.async_begin("queue-wait", rid)
            obs.metrics.gauge("queue_depth", float(len(self.queue)))
        return rid

    def insert(self, vector: np.ndarray, attrs=None) -> int:
        """Streaming insert; returns the stable external id. Visible to every
        query flushed after this call. ``attrs`` is the new vector's
        attribute row when the index carries an attribute store."""
        if self.mutable is None:
            raise RuntimeError("engine serves a frozen index — wrap it in "
                               "stream.MutableIndex for online updates")
        if self.continuous and self.mutable.delta_full:
            # this insert WILL consolidate (delta at capacity): complete
            # in-flight merged lanes first — they traverse the base corpus
            # whose id space the consolidation is about to rebuild
            self._complete_merged_pools()
        before = self.mutable.stats["consolidations"]
        ext = self.mutable.insert(vector, attrs=attrs)  # may consolidate
        consolidated = self.mutable.stats["consolidations"] - before
        if consolidated and self.continuous:
            self._reset_merged_sessions()
        self._stats.consolidations += consolidated
        self._stats.inserts += 1
        return ext

    def delete(self, ext_id: int) -> bool:
        """Streaming delete (tombstone). Filtered from every later flush."""
        if self.mutable is None:
            raise RuntimeError("engine serves a frozen index — wrap it in "
                               "stream.MutableIndex for online updates")
        ok = self.mutable.delete(ext_id)
        if ok:
            self._stats.deletes += 1
        return ok

    # ------------------------------------------------------------- scheduling
    def _flush_due(self) -> bool:
        """Full batch, or the OLDEST QUEUED request has waited ``flush_us``.

        The timeout is anchored to the head request's submit time, not the
        last flush: after an idle gap the first request of a new burst must
        still wait its full window for batch-mates (measuring from the last
        flush made it flush immediately in a batch of 1, defeating
        batching). An empty->nonempty enqueue restarts the clock naturally —
        the new head carries a fresh ``t_submit``."""
        if len(self.queue) >= self.batch_size:
            return True
        return (
            bool(self.queue)
            and (time.perf_counter() - self.queue[0].t_submit) * 1e6
            >= self.flush_us
        )

    def step(self, force: bool = False) -> List[Request]:
        """Advance the engine; returns completed requests.

        Batch mode: run one plan-homogeneous batch if due (full bucket or
        flush timeout).  Continuous mode: one scheduler tick — admit queued
        requests into free slots, advance every in-flight lane until the
        first one quiesces, retire lanes that quiesced; plans without a
        steppable spine flush through the batch path when due.  In
        streaming mode, consolidation triggers between batches/ticks."""
        if self.continuous:
            return self._tick(force)
        return self._step_batch(force)

    def _step_batch(self, force: bool = False) -> List[Request]:
        """Run one batch if due; returns completed requests.

        Batches are homogeneous in PLAN: the flush takes the head request's
        plan cache key and gathers (in FIFO order) only requests sharing it
        — one compiled execution serves the whole batch. Other-plan
        requests keep their place at the front of the queue for the next
        flush. With uniform filters (the common case, and every unfiltered
        workload) this is plain FIFO batching."""
        if not (force and self.queue) and not self._flush_due():
            return []
        head = self.queue[0]
        plan = head.plan
        if plan is None:             # deferred planning error (e.g. filter
            plan = self.searcher.plan(  # without a store) raises HERE
                SearchRequest(queries=head.query, filter=head.filter,
                              tenant=head.tenant))
            # planning succeeded after all — cache the plan back onto the
            # head and every queued same-filter request, so they batch under
            # the real cache key and are never re-planned on later flushes
            head.plan = plan
            for r in self.queue:
                if r.plan is None and r.filter == head.filter \
                        and r.tenant == head.tenant:
                    r.plan = plan

        def _key(r: Request):
            return r.plan.cache_key if r.plan is not None \
                else ("unplanned", r.filter)

        key = plan.cache_key
        obs = self.obs
        with obs.tracer.span("batch", kind=plan.kind,
                             strategy=plan.strategy) as bsp:
            with obs.tracer.span("batch-assembly"):
                batch: List[Request] = []
                skipped: List[Request] = []
                while self.queue and len(batch) < self.batch_size:
                    r = self.queue.popleft()
                    (batch if _key(r) == key else skipped).append(r)
                self.queue.extendleft(reversed(skipped))
                n = len(batch)
                t_assembled = time.perf_counter()
                if obs.enabled:
                    for r in batch:
                        # the request leaves the queue here — close its
                        # async residency span and bill queue-wait
                        obs.tracer.async_end("queue-wait", r.rid)
                        obs.metrics.observe(
                            "queue_wait_ms",
                            (t_assembled - r.t_submit) * 1e3,
                            kind=plan.kind, strategy=plan.strategy,
                            tenant=plan.tenant,
                        )
                q = np.stack([r.query for r in batch])
                bucket = self._bucket(n)
                if n < bucket:  # pad to the bucket's compiled shape
                    q = np.concatenate(
                        [q, np.zeros((bucket - n, q.shape[1]), np.float32)]
                    )
            ex = self.searcher.execute(plan, q)   # kernel-execute span inside
            now = time.perf_counter()
            with obs.tracer.span("post-process"):
                ids, dists = ex.ids, ex.dists
                if plan.spec is not None:
                    self._stats.filtered_queries += n
                if plan.kind == "flat" and plan.strategy == "scan":
                    self._stats.filter_scan_batches += 1
                for i, r in enumerate(batch):
                    r.ids, r.dists, r.t_done = ids[i], dists[i], now
                    self.done[r.rid] = r
                    if obs.enabled:
                        obs.metrics.observe(
                            "request_latency_ms", r.latency_ms,
                            kind=plan.kind, strategy=plan.strategy,
                            tenant=plan.tenant,
                        )
                    if self._slo is not None:
                        self._slo.record_latency(plan.tenant, r.latency_ms)
                if obs.quality is not None:
                    # off-path shadow-recall sampling over the batch's
                    # UNPADDED rows (also feeds the SLO recall windows)
                    obs.quality.observe(self.searcher, plan, q[:n], ids[:n])
                if self._slo is not None:
                    self._stats.slo_violations = self._slo.total_violations
            if obs.enabled:
                bsp.set(queries=n, bucket=bucket)
                obs.metrics.gauge("batch_occupancy", n / bucket)
                obs.metrics.gauge("queue_depth", float(len(self.queue)))
            if obs.nand_billing:
                with obs.tracer.span("nand-billing"):
                    from repro.plan.request import SearchResult
                    pres = SearchResult(
                        ids=ex.ids, dists=ex.dists,
                        stats=self.searcher.planner.stats_for(plan, ex),
                        plan=plan, raw=ex.raw,
                    )
                    record_plan_execution(
                        obs.metrics, pres,
                        index=self.mutable if self.mutable is not None
                        else self._index_or_none(),
                        nand=self.nand, batch_queries=n,
                        n_queues=self.nand_queues,
                    )
            if self._watch is not None:
                with obs.tracer.span("recompile-watch"):
                    self._plan_keys_seen.add(key)
                    self._watch.sample()
                    # the pow2-bucket contract as a LIVE assertion: at most
                    # log2(batch)+1 compiled shapes per distinct executed
                    # plan
                    buckets = int(math.log2(next_pow2(self.batch_size))) + 1
                    self._watch.check(buckets * len(self._plan_keys_seen))
        # running MEAN pad fraction over all batches (a sum would grow
        # without bound and read as >100% padding after a few batches)
        b = self._stats.batches
        self._stats.pad_fraction = (
            self._stats.pad_fraction * b + (bucket - n) / bucket
        ) / (b + 1)
        self._stats.batches = b + 1
        self._stats.queries += n
        if (
            self.auto_consolidate
            and self.mutable is not None
            and self.mutable.needs_consolidation()
        ):
            self.consolidate()
        return batch

    # ----------------------------------------------- continuous (tick) mode
    def _plan_entry(self, plan: Optional[QueryPlan]):
        """(session, cache_key) for a plan — None session when the plan has
        no round-steppable spine.  Memoized by plan object IDENTITY: the
        planner's plan cache hands out one ``QueryPlan`` per cache key, so
        the admission scan resolves a queued request with one dict lookup
        instead of re-hashing its config/spec tuple every tick.  The memo
        entry holds the plan itself, keeping the id stable."""
        if plan is None:
            return None, None
        entry = self._plan_memo.get(id(plan))
        if entry is None:
            key = plan.cache_key
            if key not in self._sessions:
                self._sessions[key] = \
                    self.searcher.planner.round_session(plan)
            entry = (plan, self._sessions[key], key)
            self._plan_memo[id(plan)] = entry
        return entry[1], entry[2]

    def _session_for(self, plan: Optional[QueryPlan]):
        """Cached ``RoundSession`` for a plan (None when the plan has no
        round-steppable spine — also cached, so the planner is asked once
        per cache key)."""
        return self._plan_entry(plan)[0]

    def inflight(self) -> int:
        """Lanes currently mid-traversal across every slot pool."""
        return sum(p.occupied for p in self._pools.values())

    def _admit(self, pool: _SlotPool, admissions: List[tuple]) -> None:
        """Fill freed slots: init a full-pool state for the refill queries
        and per-lane-select it into the live state (fixed shapes — one
        compiled init/step per pool, regardless of how many slots refill)."""
        dim = self.index.dataset.dim if self._index_or_none() is not None \
            else len(admissions[0][1].query)
        S = len(pool.requests)
        qmat = np.zeros((S, dim), np.float32)
        refill = np.zeros((S,), bool)
        for slot, r in admissions:
            qmat[slot] = r.query
            refill[slot] = True
            pool.requests[slot] = r
        tracer = self.obs.tracer
        fresh = pool.session.init(qmat)
        state = fresh
        if pool.state is not None:
            with tracer.span("select-lanes"):
                state = _select_lanes(refill, fresh, pool.state)
        occupied = np.array([r is not None for r in pool.requests])
        with tracer.span("quiet-lanes"):
            pool.state = _quiet_free_lanes(state, occupied)

    def _refill(self) -> None:
        """Admit queued requests into free slots, FIFO, creating slot pools
        per plan cache key on first use.  Requests whose plan is unplanned
        (deferred planning error) or not round-steppable stay queued for the
        batch-flush fallback."""
        if not self.queue:
            return
        with self.obs.tracer.span("refill"):
            self._refill_queued()

    def _refill_queued(self) -> None:
        obs = self.obs
        admitted: Dict[tuple, List[tuple]] = {}
        remaining: Deque[Request] = deque()
        now = time.perf_counter()
        # per-pool free-slot budget: a full pool rejects its requests with
        # one dict lookup (no O(slots) slot scan per queued request), so a
        # deep backlog costs the tick a cheap identity-memo pass, not
        # repeated plan-key hashing
        free = {k: len(p.requests) - p.occupied
                for k, p in self._pools.items()}
        while self.queue:
            r = self.queue.popleft()
            sess, key = self._plan_entry(r.plan)
            if sess is None:
                remaining.append(r)
                continue
            pool = self._pools.get(key)
            if pool is None:
                pool = _SlotPool(session=sess,
                                 requests=[None] * self.slots)
                self._pools[key] = pool
                free[key] = self.slots
            if free[key] <= 0:
                remaining.append(r)          # pool full — wait for retires
                continue
            taken = {s for s, _ in admitted.get(key, ())}
            slot = next((i for i, req in enumerate(pool.requests)
                         if req is None and i not in taken), None)
            if slot is None:
                remaining.append(r)
                continue
            free[key] -= 1
            admitted.setdefault(key, []).append((slot, r))
            if obs.enabled:
                obs.tracer.async_end("queue-wait", r.rid)
                obs.metrics.observe(
                    "queue_wait_ms", (now - r.t_submit) * 1e3,
                    kind=r.plan.kind, strategy=r.plan.strategy,
                    tenant=r.plan.tenant,
                )
        self.queue = remaining
        for key, admissions in admitted.items():
            with obs.tracer.span("admit", lanes=len(admissions)):
                self._admit(self._pools[key], admissions)
            self._plan_keys_seen.add(key)

    def _step_pool(self, pool: _SlotPool) -> List[Request]:
        """Advance a pool's lanes until the first one quiesces (one device
        dispatch, one host read); finalize + hand back every lane that
        quiesced.  Retired batches bill through the NAND model exactly like
        flushed ones (``RoundSession.complete`` returns the same plan-layer
        result shape)."""
        obs = self.obs
        # per-round telemetry needs the host after every round
        limit = 1 if obs.convergence is not None \
            else pool.session.cfg.max_rounds
        pool.state, active, ran = pool.session.advance(pool.state, limit)
        self._stats.pool_dispatches += 1
        self._stats.pool_rounds += ran
        if obs.convergence is not None:
            # per-round telemetry for every occupied lane — live requests
            # grow the same learned-ET dataset the off-line driver collects
            occ = [i for i, r in enumerate(pool.requests) if r is not None]
            if occ:
                with obs.tracer.span("record-round"):
                    pool.session.record_round(
                        obs.convergence,
                        [pool.requests[i].rid for i in occ],
                        pool.state, select=occ)
        rows = [i for i, r in enumerate(pool.requests)
                if r is not None and not active[i]]
        if not rows:
            return []
        plan = pool.session.plan
        with obs.tracer.span("retire", kind=plan.kind,
                             strategy=plan.strategy, lanes=len(rows)):
            return self._retire(pool, rows)

    def _retire(self, pool: _SlotPool, rows: List[int]) -> List[Request]:
        """Finalize the quiesced ``rows`` of a pool, complete their requests
        and free their slots."""
        obs = self.obs
        session = pool.session
        plan = session.plan
        n = len(rows)
        pad = np.full((next_pow2(n),), rows[0], np.int64)  # pow2 gather shape
        pad[:n] = rows
        core = session.retire(pool.state, pad)    # one dispatch, one copy
        core_rows = type(core)(*(f[:n] for f in core))
        qrows = np.stack([pool.requests[i].query for i in rows])
        rounds = core_rows.rounds
        with obs.tracer.span("complete", cat="plan"):
            pres = session.complete(qrows, core_rows)
        now = time.perf_counter()
        completed: List[Request] = []
        with obs.tracer.span("post-process"):
            for j, i in enumerate(rows):
                r = pool.requests[i]
                r.ids, r.dists, r.t_done = pres.ids[j], pres.dists[j], now
                self.done[r.rid] = r
                pool.requests[i] = None
                completed.append(r)
                if obs.enabled:
                    obs.metrics.observe(
                        "request_latency_ms", r.latency_ms, kind=plan.kind,
                        strategy=plan.strategy, tenant=plan.tenant,
                    )
                    obs.metrics.observe("rounds_in_flight", float(rounds[j]),
                                        kind=plan.kind,
                                        strategy=plan.strategy)
                if self._slo is not None:
                    self._slo.record_latency(plan.tenant, r.latency_ms)
                if obs.convergence is not None:
                    obs.convergence.finalize_lane(r.rid, int(rounds[j]))
            if obs.quality is not None:
                obs.quality.observe(self.searcher, plan, qrows, pres.ids)
            if self._slo is not None:
                self._stats.slo_violations = self._slo.total_violations
        if plan.spec is not None:
            self._stats.filtered_queries += n
        self._stats.retired += n
        self._stats.queries += n
        if obs.nand_billing:
            with obs.tracer.span("nand-billing"):
                record_plan_execution(
                    obs.metrics, pres,
                    index=self.mutable if self.mutable is not None
                    else self._index_or_none(),
                    nand=self.nand, batch_queries=n,
                    n_queues=self.nand_queues,
                )
        return completed

    def _tick(self, force: bool = False) -> List[Request]:
        """One scheduler tick: refill free slots from the queue, advance
        every occupied pool until one of its lanes quiesces, retire
        quiesced lanes.  Requests the round-step path cannot serve flush
        through the batch path when due (or on ``force``)."""
        obs = self.obs
        completed: List[Request] = []
        with obs.tracer.span("tick"):
            self._refill()
            for key, pool in self._pools.items():
                if pool.occupied == 0:
                    continue
                completed.extend(self._step_pool(pool))
                if obs.enabled:
                    obs.metrics.gauge("slot_occupancy",
                                      pool.occupied / len(pool.requests),
                                      kind=pool.session.plan.kind,
                                      strategy=pool.session.plan.strategy)
            # non-steppable head (tiled/distributed/scan plans, deferred
            # planning errors): served through the batch-flush path below
            fallback = bool(self.queue) \
                and self._session_for(self.queue[0].plan) is None \
                and (force or self._flush_due())
            if not fallback and self._watch is not None:
                with obs.tracer.span("recompile-watch"):
                    self._watch.sample()
                    # continuous pools retire at pow2 buckets up to the
                    # slot count, so the budget widens to max(batch, slots)
                    width = max(self.batch_size, self.slots)
                    buckets = int(math.log2(next_pow2(width))) + 1
                    self._watch.check(
                        buckets * max(len(self._plan_keys_seen), 1))
        self._stats.ticks += 1
        if obs.enabled:
            obs.metrics.gauge("queue_depth", float(len(self.queue)))
        if fallback:
            n0 = self._stats.batches
            completed.extend(self._step_batch(force=force))
            self._stats.fallback_batches += self._stats.batches - n0
        if (
            self.auto_consolidate
            and self.mutable is not None
            and self.mutable.needs_consolidation()
        ):
            self.consolidate()
        return completed

    def _complete_merged_pools(self) -> List[Request]:
        """Run every in-flight MERGED lane to completion (they traverse the
        pre-consolidation base corpus, whose id space is about to be
        rebuilt).  Retired requests land in ``done`` as usual."""
        out: List[Request] = []
        for key, pool in self._pools.items():
            if pool.session.plan.kind != "merged":
                continue
            guard = self.cfg.max_rounds + 2
            while pool.occupied and guard:
                out.extend(self._step_pool(pool))
                guard -= 1
        return out

    def _reset_merged_sessions(self) -> None:
        """Drop merged sessions + pools — they pin the pre-consolidation
        corpus/masks.  Fresh ones are created on the next admit."""
        for key in [k for k, p in self._pools.items()
                    if p.session.plan.kind == "merged"]:
            del self._pools[key]
        for key in [k for k, s in self._sessions.items()
                    if s is not None and s.plan.kind == "merged"]:
            del self._sessions[key]
        self._plan_memo = {i: e for i, e in self._plan_memo.items()
                           if e[2] in self._sessions}

    def _index_or_none(self):
        """Served base index, or None for raw-corpus targets (those carry no
        NAND geometry; billing then counts the batch as unbilled)."""
        try:
            idx = self.index
        except AttributeError:
            return None
        return idx

    def consolidate(self) -> None:
        """Fold the delta segment into a rebuilt base index.  In continuous
        mode, in-flight merged lanes complete first — their states reference
        the old base id space."""
        if self.mutable is None:
            return
        self._complete_merged_pools()
        self.mutable.consolidate()
        self._reset_merged_sessions()
        self._stats.consolidations += 1

    def drain(self, max_steps: Optional[int] = None) -> List[Request]:
        """Force-run until the queue (and, in continuous mode, every
        in-flight lane) is empty.  Bounded: a plan that cannot make progress
        raises instead of spinning forever.  The default budget is generous
        — batch mode completes >= 1 request per forced step; a continuous
        lane finishes within ``max_rounds`` ticks."""
        out: List[Request] = []
        if max_steps is None:
            pending = len(self.queue) + self.inflight()
            per = (self.cfg.max_rounds + 2) if self.continuous else 2
            max_steps = per * (pending + 1) + 16
        steps = 0
        while self.queue or (self.continuous and self.inflight()):
            if steps >= max_steps:
                raise RuntimeError(
                    f"drain() exceeded {max_steps} steps with "
                    f"{len(self.queue)} queued and {self.inflight()} "
                    "in-flight — a plan that cannot execute (or a stuck "
                    "lane) is spinning the loop"
                )
            out.extend(self.step(force=True))
            steps += 1
        return out
