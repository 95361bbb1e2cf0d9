"""Proxima graph search — Algorithm 1 of the paper, as a fixed-shape JAX
program (vmapped over the query batch = the ASIC's N_q search queues).

Per traversal round (one iteration of the ``lax.while_loop``):
  1. pop the E best unevaluated candidates from the sorted list (Alg.1 l.4;
     E = ``SearchConfig.beam_width``, the beam-parallel generalization —
     the E adjacency fetches of one round are independent NAND page reads
     issued to parallel planes/channels, §IV-D dataflow)
  2. fetch their E*R neighbours in one indexed gather, dedup the combined
     set, Bloom-filter already-visited ones                    (l.6, §IV-B)
  3. PQ-distance all fresh ones via the ADT in one batch       (l.7)
  4. one (L + E*R) merge + sort, keep top L                    (l.10)
  5. if the top-T entries are all evaluated: rerank top T with accurate
     distances (cached), check early termination (r stable rounds), then
     grow T by T_step                                          (l.11-16)
Post-loop: beta-margin rerank of every candidate whose PQ distance is within
beta of the T-th candidate's, then return top-k by accurate distance (l.19-22).

Filtered traversal (``node_mask``, the ``repro.filter`` subsystem): a (N,)
boolean pass mask restricts *result admission*, never routing — non-passing
nodes still enter the candidate list and route the traversal exactly as
before, but only mask-passing nodes count for the early-termination top-k,
the beta-margin rerank threshold (taken at the T-th *passing* candidate) and
the final top-k. With an all-true mask every selection reduces to the
unfiltered arithmetic, so an all-pass filter is bit-identical to
``node_mask=None`` at every beam width.

Counters (per query) feed the NAND performance model and the memory-traffic
benchmarks: hops (index fetches = expansions, up to E per round), pq (code
fetches + LUT distance computations), acc (raw-vector fetches), hot_hops /
free_pq (hot-node repetition hits), rounds (serial traversal rounds — the
critical-path length; hops/rounds is the realized beam parallelism).
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import SearchConfig, upgrade_config
from repro.core import bloom
from repro.core.pq import compute_adt, pq_distance

INF = jnp.float32(jnp.inf)


class Corpus(NamedTuple):
    """Device-resident search structures (one NAND tile's worth)."""
    adjacency: jnp.ndarray      # (N, R) int32 padded
    codes: jnp.ndarray          # (N, M) uint8 PQ codes
    base: jnp.ndarray           # (N, D) f32 raw vectors (rerank path)
    centroids: jnp.ndarray      # (M, C, dsub) f32 PQ codebook
    entry_point: jnp.ndarray    # () int32
    hot_count: jnp.ndarray      # () int32 — ids < hot_count are "hot nodes"


class SearchResult(NamedTuple):
    ids: jnp.ndarray            # (Q, k) int32
    dists: jnp.ndarray          # (Q, k) f32 accurate distances
    n_hops: jnp.ndarray         # (Q,) expansions (index fetches)
    n_pq: jnp.ndarray           # (Q,) PQ distance computations
    n_acc: jnp.ndarray          # (Q,) accurate distance computations
    n_hot_hops: jnp.ndarray     # (Q,) expansions that hit a hot node
    n_free_pq: jnp.ndarray      # (Q,) PQ fetches covered by hot-node pages
    rounds: jnp.ndarray         # (Q,) traversal rounds


class _State(NamedTuple):
    ids: jnp.ndarray            # (L,) int32, -1 padding, sorted by dist
    dists: jnp.ndarray          # (L,) f32 traversal (PQ) distances
    acc: jnp.ndarray            # (L,) f32 accurate distances, +inf if unknown
    evaluated: jnp.ndarray      # (L,) bool
    bits: jnp.ndarray           # (W,) uint32 Bloom filter
    t: jnp.ndarray              # () int32 dynamic list size
    prev_topk: jnp.ndarray      # (k,) int32 last reranked top-k (sorted ids)
    stable: jnp.ndarray         # () int32 consecutive stable rounds
    done: jnp.ndarray           # () bool
    n_hops: jnp.ndarray
    n_pq: jnp.ndarray
    n_acc: jnp.ndarray
    n_hot: jnp.ndarray
    n_free: jnp.ndarray
    rounds: jnp.ndarray


def next_pow2(n: int) -> int:
    """Smallest power of two >= n (n >= 1) — bitonic networks and compiled
    batch buckets all pad to this."""
    return 1 << max(n - 1, 0).bit_length()


def empty_search_result(nq: int, k: int) -> SearchResult:
    """A no-work result batch: -1 ids, +inf distances, zeroed counters —
    what a skipped channel (zero-pass tile) or an empty-filter query batch
    contributes."""
    z = jnp.zeros((nq,), jnp.int32)
    return SearchResult(
        ids=jnp.full((nq, k), -1, jnp.int32),
        dists=jnp.full((nq, k), jnp.inf, jnp.float32),
        n_hops=z, n_pq=z, n_acc=z, n_hot_hops=z, n_free_pq=z, rounds=z,
    )


def l2_normalize(x, xp=jnp):
    """Unit-normalize rows — THE angular-metric normalization, shared by the
    JAX search, the reference oracle and the index's device-corpus export
    (``xp`` selects numpy for host-side callers)."""
    return x / xp.maximum(xp.linalg.norm(x, axis=-1, keepdims=True), 1e-12)


def _exact_dist(q, x, metric: str):
    """q (D,), x (K, D) -> (K,). Angular assumes pre-normalized inputs.
    Operator-only arithmetic: works identically on jnp (traced search) and
    np (reference oracle) inputs — the single exact-distance path. The
    inner product is an elementwise sum, not a matmul, so it stays f32 on
    the TPU, whose default matmul precision rounds f32 operands to bf16."""
    if metric == "l2":
        diff = x - q[None, :]
        return (diff * diff).sum(-1)
    return -(x * q[None, :]).sum(-1)


def _dedup_round(neighbors: jnp.ndarray) -> jnp.ndarray:
    """Mask duplicates within one fetched neighbour row (padding repeats)."""
    r = neighbors.shape[0]
    eq = neighbors[None, :] == neighbors[:, None]
    lower = jnp.tril(jnp.ones((r, r), bool), k=-1)
    return ~(eq & lower).any(axis=1)


def _merge_sort_topl(ids, dists, acc, evaluated, n_ids, n_dists):
    """Merge L existing + R new candidates, sort by dist, keep top L."""
    l = ids.shape[0]
    all_ids = jnp.concatenate([ids, n_ids])
    all_d = jnp.concatenate([dists, n_dists])
    all_acc = jnp.concatenate([acc, jnp.full(n_ids.shape, INF)])
    all_ev = jnp.concatenate([evaluated, jnp.zeros(n_ids.shape, bool)])
    order = jnp.argsort(all_d, stable=True)
    return (
        all_ids[order][:l],
        all_d[order][:l],
        all_acc[order][:l],
        all_ev[order][:l],
    )


def _topk_ids_by(ids, key, k):
    """ids of the k smallest keys, returned sorted by id for set comparison."""
    _, idx = jax.lax.top_k(-key, k)
    got = ids[idx]
    return jnp.sort(got)


def _merge_sort_topl_bitonic(ids, dists, acc, evaluated, n_ids, n_dists):
    """Kernel-path variant of ``_merge_sort_topl``: the merged (L+R) list is
    sorted by the Pallas bitonic network (the ASIC's shared Bitonic Sorter),
    carrying the position index as payload; other payloads follow by gather."""
    from repro.kernels import ops

    l = ids.shape[0]
    pad = next_pow2(l + n_ids.shape[0]) - l - n_ids.shape[0]
    # the power-of-two padding is itself empty entries (id -1, +inf), so a
    # padding slot sorted into the top L reads as empty
    all_ids = jnp.concatenate([ids, n_ids, jnp.full((pad,), -1, jnp.int32)])
    all_d = jnp.concatenate([dists, n_dists, jnp.full((pad,), INF)])
    all_acc = jnp.concatenate([acc, jnp.full((n_ids.shape[0] + pad,), INF)])
    all_ev = jnp.concatenate([evaluated,
                              jnp.zeros((n_ids.shape[0] + pad,), bool)])
    pos = jnp.arange(all_d.shape[0], dtype=jnp.int32)
    # NOTE: bitonic is not stable; +inf-keyed entries are interchangeable
    # (all carry id=-1), so only exact finite-key ties can reorder.
    _, perm = ops.bitonic_sort_pairs(all_d[None], pos[None])
    perm = perm[0, :l]
    return all_ids[perm], all_d[perm], all_acc[perm], all_ev[perm]


def _passes_of(ids, node_mask):
    """Valid AND mask-passing, elementwise (-1 slots never pass). With
    ``node_mask=None`` this is plain validity — the unfiltered path."""
    valid = ids >= 0
    if node_mask is None:
        return valid
    return valid & node_mask[jnp.maximum(ids, 0)]


def _build_adts(corpus: Corpus, queries: jnp.ndarray, cfg: SearchConfig,
                metric: str) -> jnp.ndarray:
    """Batched ADT construction (Pallas pq_adt kernel path) — shared by the
    while_loop kernel and ``init_search_state``."""
    if not cfg.use_pq:
        return jnp.zeros((queries.shape[0], 1, 1), jnp.float32)
    with jax.named_scope("adt-build"):
        if cfg.use_pallas:
            from repro.kernels import ops

            return ops.pq_adt(queries, corpus.centroids, metric)
        return jax.vmap(lambda q: compute_adt(q, corpus.centroids, metric))(
            queries
        )


def _round_fns(corpus: Corpus, cfg: SearchConfig, metric: str,
               bloom_bits: int, num_hashes: int, node_mask):
    """THE traversal round, factored out of the ``lax.while_loop``: returns
    ``(init_one, cond, body)`` per-query functions.  ``graph_search`` wraps
    them back into a while_loop and ``graph_search_step`` applies exactly one
    guarded round — both paths trace the SAME functions, which is what makes
    the round-step path bit-identical to the while_loop kernel (enforced by
    the round-step equivalence suite in tests/test_plan.py).

    ``cond`` is also the vmap batching rule for while_loop: jax lowers a
    vmapped while_loop to "loop while any(cond), select(cond, body(s), s)
    per lane" — so one ``graph_search_step`` application IS one iteration of
    the vmapped loop, and iterating it until no lane is active reproduces
    the loop's fixpoint exactly (extra steps on a finished batch are
    no-ops).

    The round's parts carry ``jax.named_scope`` names (``beam-select``,
    ``gather-adjacency``, ``gather-codes``, ``pq-lookup``, ``merge-sort``,
    ``gather-base``, ``rerank-exact``, ``topk-termination``,
    ``state-select``; ``init-lanes`` for round 0, ``loop-cond`` for the
    activity test) so that the device trace can attribute each op's time
    to one of them; scopes change only the HLO metadata, not the compiled
    program."""
    cfg = upgrade_config(cfg)    # pre-beam pickled configs: fill defaults
    L, k = cfg.list_size, cfg.k
    R = corpus.adjacency.shape[1]
    # beam wider than the candidate list can never pop more than L entries
    E = min(max(int(cfg.beam_width), 1), L)
    use_pq, do_et = cfg.use_pq, cfg.early_termination
    t_init = cfg.t_init if do_et else L
    t_step = cfg.t_step if do_et else L
    merge = _merge_sort_topl_bitonic if cfg.use_pallas else _merge_sort_topl

    def tdist(q, adt, ids):
        if use_pq:
            with jax.named_scope("gather-codes"):
                codes = corpus.codes[ids]
            with jax.named_scope("pq-lookup"):
                if cfg.use_pallas:
                    from repro.kernels import ops

                    return ops.pq_lookup(codes, adt)
                return pq_distance(codes, adt)
        with jax.named_scope("gather-base"):
            rows = corpus.base[ids]
        with jax.named_scope("rerank-exact"):
            return _exact_dist(q, rows, metric)

    def init_one(q, adt):
        with jax.named_scope("init-lanes"):
            ep = corpus.entry_point
            d0 = tdist(q, adt, ep[None])[0]
            ids0 = jnp.full((L,), -1, jnp.int32).at[0].set(ep)
            dists0 = jnp.full((L,), INF).at[0].set(d0)
            acc0 = jnp.full((L,), INF)
            if not use_pq:
                acc0 = acc0.at[0].set(d0)
            bits0 = bloom.bloom_init(bloom_bits)
            bits0 = bloom.insert(bits0, ep[None], jnp.ones((1,), bool),
                                 num_hashes)
            return _State(
                ids=ids0, dists=dists0, acc=acc0,
                evaluated=jnp.zeros((L,), bool), bits=bits0,
                t=jnp.int32(min(t_init, L)),
                prev_topk=jnp.full((k,), -2, jnp.int32),
                stable=jnp.int32(0), done=jnp.bool_(False),
                n_hops=jnp.int32(0), n_pq=jnp.int32(1 if use_pq else 0),
                n_acc=jnp.int32(0 if use_pq else 1),
                n_hot=jnp.int32(0), n_free=jnp.int32(0), rounds=jnp.int32(0),
            )

    def cond(s: _State):
        with jax.named_scope("loop-cond"):
            return (~s.done) & (s.rounds < cfg.max_rounds)

    def body(q, adt, s: _State):
        # scopes follow the round's own order, so the traced program is the
        # one it was before they were added
        with jax.named_scope("beam-select"):
            valid = s.ids >= 0
            unev = valid & ~s.evaluated
            n_unev = unev.sum()
            has_unev = unev.any()
            # positions of unevaluated entries in list (distance) order: a
            # stable sort of ~unev floats them to the front, so sel[:E] are
            # the E best unevaluated candidates — the round's beam. E == 1
            # keeps the original O(L) argmax instead of the O(L log L) sort.
            if E == 1:
                sel = jnp.argmax(unev)[None]               # (1,)
            else:
                sel = jnp.argsort(~unev, stable=True)[:E]  # (E,) distinct
            sel_valid = jnp.arange(E) < n_unev             # (E,)
            vs = jnp.where(sel_valid, s.ids[sel], 0)       # (E,) beam ids

        # ---- expand the beam: one E-row adjacency gather ---------------
        with jax.named_scope("gather-adjacency"):
            neigh = corpus.adjacency[vs].reshape(E * R)    # (E*R,)
            fresh = _dedup_round(neigh) \
                & ~bloom.contains(s.bits, neigh, num_hashes)
            fresh = fresh & jnp.repeat(sel_valid, R)
        nd = tdist(q, adt, neigh)                      # one batched call
        with jax.named_scope("pq-lookup"):
            nd = jnp.where(fresh, nd, INF)
        with jax.named_scope("gather-adjacency"):
            bits = bloom.insert(s.bits, neigh, fresh, num_hashes)
        with jax.named_scope("beam-select"):
            evaluated = s.evaluated.at[sel].set(s.evaluated[sel] | sel_valid)
            n_new = fresh.sum()
            is_hot = (vs < corpus.hot_count) & sel_valid   # (E,)
        with jax.named_scope("merge-sort"):
            ids, dists, acc, evaluated = merge(
                s.ids, s.dists, s.acc, evaluated,
                jnp.where(fresh, neigh, -1).astype(jnp.int32), nd,
            )

        # ---- top-T evaluated? -> rerank + early-termination ------------
        with jax.named_scope("topk-termination"):
            valid = ids >= 0
            pl = _passes_of(ids, node_mask)
            in_t = (jnp.arange(L) < s.t) & valid
            all_eval = jnp.where(in_t.any(), (~in_t | evaluated).all(),
                                 False)
            # only passing candidates are admitted to the reranked top-k
            # (non-passing ones still route; in_t implies valid, so with no
            # mask in_t & pl == in_t and this is the unfiltered arithmetic)
            need = in_t & pl & jnp.isinf(acc)
        with jax.named_scope("gather-base"):
            rows = corpus.base[jnp.maximum(ids, 0)]
        with jax.named_scope("rerank-exact"):
            acc_new = _exact_dist(q, rows, metric)
            acc2 = jnp.where(need & all_eval, acc_new, acc)
            n_acc_new = jnp.where(all_eval, need.sum(), 0)
            if use_pq:
                rerank_key = jnp.where(in_t & pl, acc2, INF)
            else:
                acc2 = jnp.where(valid, dists, INF)
                rerank_key = jnp.where(in_t & pl, acc2, INF)
        with jax.named_scope("topk-termination"):
            new_topk = _topk_ids_by(ids, rerank_key, k)
            same = (new_topk == s.prev_topk).all()
            stable = jnp.where(all_eval, jnp.where(same, s.stable + 1, 1),
                               s.stable)
            prev_topk = jnp.where(all_eval, new_topk, s.prev_topk)
            t = jnp.where(all_eval, s.t + t_step, s.t)

            terminated = do_et & all_eval & (stable >= cfg.repetition_rate)
            exhausted = ~has_unev
            overflow = t > L
            done = terminated | exhausted | overflow

            hot_new = (fresh.reshape(E, R) & is_hot[:, None]).sum()
            new = _State(
                ids=ids, dists=dists, acc=acc2, evaluated=evaluated,
                bits=bits, t=jnp.minimum(t, L), prev_topk=prev_topk,
                stable=stable, done=done,
                n_hops=s.n_hops + jnp.minimum(n_unev, E).astype(jnp.int32),
                n_pq=s.n_pq + (n_new if use_pq else 0),
                n_acc=s.n_acc + n_acc_new + (0 if use_pq else n_new),
                n_hot=s.n_hot + is_hot.sum().astype(jnp.int32),
                n_free=s.n_free + hot_new,
                rounds=s.rounds + 1,
            )
        # lanes that were already done keep their state (vmap-safety)
        with jax.named_scope("state-select"):
            return jax.tree_util.tree_map(
                lambda a, b: jnp.where(s.done, a, b), s, new
            )

    return init_one, cond, body


@partial(
    jax.jit,
    static_argnames=("cfg", "metric", "bloom_bits", "num_hashes"),
)
def graph_search(
    corpus: Corpus,
    queries: jnp.ndarray,
    cfg: SearchConfig,
    metric: str = "l2",
    bloom_bits: int = 1 << 17,
    num_hashes: int = 8,
    node_mask: jnp.ndarray | None = None,
) -> SearchResult:
    """Batched Proxima traversal KERNEL. queries: (Q, D). ``node_mask`` (N,)
    bool, if given, admits only passing nodes to the result set (filtered
    search — see the module docstring).

    This is the innermost compiled engine every ``repro.plan.QueryPlan``
    composes (flat, masked, per-tile fan-out, merged base segment); call it
    through ``repro.plan.Searcher`` unless you are writing a kernel.  The
    round-stepped decomposition of the same traversal —
    ``init_search_state`` / ``graph_search_step`` / ``finalize_search`` —
    serves the continuous-batching engine and is bit-identical to this
    while_loop at every round count."""
    if metric == "angular":
        queries = l2_normalize(queries)
    adts = _build_adts(corpus, queries, cfg, metric)
    init_one, cond, body = _round_fns(corpus, cfg, metric, bloom_bits,
                                      num_hashes, node_mask)

    def one_query(q, adt):
        return jax.lax.while_loop(
            cond, lambda s: body(q, adt, s), init_one(q, adt)
        )

    s = jax.vmap(one_query)(queries, adts)
    return _finalize_batch(corpus, cfg, metric, node_mask, queries, s)


def _finalize_batch(corpus: Corpus, cfg: SearchConfig, metric: str,
                    node_mask, queries: jnp.ndarray, s: _State) -> SearchResult:
    """Post-loop beta-margin rerank + top-k extraction over a BATCHED lane
    state (Alg.1 l.19-22) — shared verbatim by the while_loop kernel and the
    round-step path's ``finalize_search``."""
    L, k = cfg.list_size, cfg.k
    with jax.named_scope("final-rerank"):
        # ---- final beta rerank, batched (Alg.1 l.19-21; Pallas l2_rerank)
        valid = s.ids >= 0                                       # (Q, L)
        pass_l = _passes_of(s.ids, node_mask)                    # (Q, L)
        if node_mask is None:
            t_idx = jnp.clip(s.t, 1, L) - 1
            d_t = jnp.take_along_axis(s.dists, t_idx[:, None], 1)[:, 0]
            thr = d_t + (cfg.beta - 1.0) * jnp.abs(d_t)      # sign-safe margin
        else:
            # margin anchor = the T-th PASSING candidate's distance. The
            # list is distance-sorted with all valid entries a prefix, so
            # with an all-true mask "T-th passing" is exactly position T-1
            # (or the +inf padding when fewer than T are valid) —
            # bit-identical to the unfiltered read above.
            rank = jnp.cumsum(pass_l, axis=1)                    # (Q, L)
            tt = jnp.clip(s.t, 1, L)
            is_t = pass_l & (rank == tt[:, None])
            d_t = jnp.where(is_t, s.dists, -INF).max(axis=1)
            d_t = jnp.where(rank[:, -1] >= tt, d_t, INF)
            # inf anchor (fewer than T passing): rerank every passing
            # candidate — guarded, since beta == 1.0 would turn inf + 0*inf
            # into NaN and silently drop all results
            thr = jnp.where(jnp.isinf(d_t), INF,
                            d_t + (cfg.beta - 1.0) * jnp.abs(d_t))
        if cfg.use_pq and cfg.rerank:
            need = pass_l & (s.dists <= thr[:, None]) & jnp.isinf(s.acc)
            cand = corpus.base[jnp.maximum(s.ids, 0)]        # (Q, L, D)
            if cfg.use_pallas:
                from repro.kernels import ops

                acc_new = ops.l2_rerank(queries, cand, metric)
            else:
                acc_new = jax.vmap(lambda q, x: _exact_dist(q, x, metric))(
                    queries, cand
                )
            acc = jnp.where(need, acc_new, s.acc)
            n_acc = s.n_acc + need.sum(axis=1)
        else:
            # no rerank (rank by PQ) / accurate traversal (dists are
            # accurate)
            acc = jnp.where(valid, s.dists, INF)
            n_acc = s.n_acc
    with jax.named_scope("final-topk"):
        key = jnp.where(pass_l, acc, INF)
        neg, idx = jax.lax.top_k(-key, k)
        out_ids = jnp.take_along_axis(s.ids, idx, 1)
        if node_mask is not None:
            # a filter can leave fewer than k admissible candidates: such slots
            # carry +inf keys and must come back as explicit -1 padding
            out_ids = jnp.where(jnp.isinf(neg), -1, out_ids)
    return SearchResult(
        ids=out_ids, dists=-neg, n_hops=s.n_hops, n_pq=s.n_pq, n_acc=n_acc,
        n_hot_hops=s.n_hot, n_free_pq=s.n_free, rounds=s.rounds,
    )


# ---------------------------------------------------------------------------
# Round-stepped traversal — the continuous-batching kernel surface
# ---------------------------------------------------------------------------
# ``graph_search`` runs every lane to its fixpoint inside one while_loop; the
# three kernels below expose the SAME traversal one round at a time so an
# iteration-level scheduler (repro.serve.ServingEngine(continuous=True)) can
# retire finished lanes and refill their slots between rounds:
#
#     state = init_search_state(corpus, queries, cfg, ...)
#     while search_state_active(state, cfg).any():
#         state = graph_search_step(corpus, state, cfg, ...)   # ONE round
#     res = finalize_search(corpus, state, cfg, ...)           # beta rerank
#
# All three are jit-compiled with fixed shapes (Q lanes x list_size) and built
# from the same ``_round_fns``/``_finalize_batch`` pieces as ``graph_search``,
# so iterating the step to quiescence is bit-identical to the while_loop (a
# vmapped while_loop lowers to exactly this select-guarded step).  The
# scheduler dispatches ``graph_search_advance``: the same step, looped on the
# device until the first lane quiesces, so the host syncs once per retire
# point instead of once per round, and retires the quiesced lanes through
# ``finalize_rows``: gather, finalize and pack in one program, one copy.


class SearchState(NamedTuple):
    """Mid-traversal snapshot of a batch of lanes.  ``queries`` are already
    metric-normalized and ``adts`` are the per-lane PQ lookup tables — both
    loop-invariant, carried here so ``graph_search_step`` is a pure
    State -> State function.  A lane is live while
    ``search_state_active(state, cfg)`` holds; rows may be swapped between
    two states with ``jnp.where`` (slot refill) because every leaf's leading
    axis is the lane axis."""

    queries: jnp.ndarray  # (Q, D) normalized query vectors
    adts: jnp.ndarray     # (Q, M, K) ADT lookup tables ((Q,1,1) when !use_pq)
    lanes: _State         # batched per-lane traversal state


@partial(
    jax.jit,
    static_argnames=("cfg", "metric", "bloom_bits", "num_hashes"),
)
def init_search_state(
    corpus: Corpus,
    queries: jnp.ndarray,
    cfg: SearchConfig,
    metric: str = "l2",
    bloom_bits: int = 1 << 17,
    num_hashes: int = 8,
    node_mask: jnp.ndarray | None = None,
) -> SearchState:
    """Round 0 of the traversal for a (Q, D) query batch: normalize, build
    ADTs, seed every lane at the entry point.  ``node_mask`` only matters in
    later rounds but is accepted here for signature symmetry."""
    if metric == "angular":
        queries = l2_normalize(queries)
    adts = _build_adts(corpus, queries, cfg, metric)
    init_one, _, _ = _round_fns(corpus, cfg, metric, bloom_bits, num_hashes,
                                node_mask)
    lanes = jax.vmap(init_one)(queries, adts)
    return SearchState(queries=queries, adts=adts, lanes=lanes)


@partial(
    jax.jit,
    static_argnames=("cfg", "metric", "bloom_bits", "num_hashes"),
)
def graph_search_step(
    corpus: Corpus,
    state: SearchState,
    cfg: SearchConfig,
    metric: str = "l2",
    bloom_bits: int = 1 << 17,
    num_hashes: int = 8,
    node_mask: jnp.ndarray | None = None,
) -> SearchState:
    """ONE traversal round over every lane (vmapped, fixed shapes).  Inactive
    lanes — done, or at ``max_rounds`` — pass through unchanged, exactly like
    the select-guarded iteration a vmapped while_loop lowers to, so stepping
    an all-quiet batch is a no-op and stepping until quiet reproduces
    ``graph_search`` bit-for-bit."""
    _, cond, body = _round_fns(corpus, cfg, metric, bloom_bits, num_hashes,
                               node_mask)
    lanes = jax.vmap(_guarded_round(cond, body))(state.queries, state.adts,
                                                 state.lanes)
    return state._replace(lanes=lanes)


def _guarded_round(cond, body):
    """One lane's select-guarded round — the iteration a vmapped while_loop
    lowers to: a lane that ``cond`` finds inactive keeps its state."""
    def step_one(q, adt, s):
        active = cond(s)
        new = body(q, adt, s)
        with jax.named_scope("state-select"):
            return jax.tree_util.tree_map(
                lambda a, b: jnp.where(active, b, a), s, new
            )

    return step_one


@partial(
    jax.jit,
    static_argnames=("cfg", "metric", "bloom_bits", "num_hashes"),
)
def graph_search_advance(
    corpus: Corpus,
    state: SearchState,
    limit: jnp.ndarray,
    cfg: SearchConfig,
    metric: str = "l2",
    bloom_bits: int = 1 << 17,
    num_hashes: int = 8,
    node_mask: jnp.ndarray | None = None,
) -> tuple[SearchState, jnp.ndarray]:
    """``graph_search_step`` rounds on the device, in one dispatch, until a
    lane that was active on entry quiesces, no lane is active, or ``limit``
    (an int32 scalar, traced: every limit shares one program) rounds have
    run.  Returns ``(state, packed)``: ``packed`` is an int32 ``(Q + 1,)``
    array, the active mask after the last round followed by the number of
    rounds run, so the host reads both in one copy.

    Each round is ``graph_search_step``'s select-guarded round, so every
    lane follows the trajectory one-round stepping gives it; the stop only
    decides when the host regains control — on the round the first lane
    quiesces, which is when a one-round-per-dispatch scheduler would have
    retired it."""
    _, cond, body = _round_fns(corpus, cfg, metric, bloom_bits, num_hashes,
                               node_mask)
    step_one = _guarded_round(cond, body)
    active_of = jax.vmap(cond)
    a0 = active_of(state.lanes)

    def more(carry):
        lanes, n = carry
        a = active_of(lanes)
        return a.any() & ~(a0 & ~a).any() & (n < limit)

    def one_round(carry):
        lanes, n = carry
        return jax.vmap(step_one)(state.queries, state.adts, lanes), n + 1

    lanes, n = jax.lax.while_loop(more, one_round,
                                  (state.lanes, jnp.int32(0)))
    packed = jnp.append(active_of(lanes).astype(jnp.int32), n)
    return state._replace(lanes=lanes), packed


def search_state_active(state: SearchState, cfg: SearchConfig) -> jnp.ndarray:
    """(Q,) bool — lanes that still have rounds to run.  This is the
    while_loop's cond applied batchwise; host code should ``.any()`` it to
    decide whether another ``graph_search_step`` is needed."""
    return (~state.lanes.done) & (state.lanes.rounds < cfg.max_rounds)


@partial(jax.jit, static_argnames=("cfg", "metric"))
def finalize_search(
    corpus: Corpus,
    state: SearchState,
    cfg: SearchConfig,
    metric: str = "l2",
    node_mask: jnp.ndarray | None = None,
) -> SearchResult:
    """Post-traversal beta-margin rerank + top-k (Alg.1 l.19-22) over lanes
    that have quiesced — the same ``_finalize_batch`` the while_loop kernel
    runs.  Queries inside ``state`` are already normalized; do NOT pass them
    through ``init_search_state`` twice."""
    return _finalize_batch(corpus, cfg, metric, node_mask,
                           state.queries, state.lanes)


# the lane fields ``_finalize_batch`` reads; the Bloom bits, ADTs,
# ``evaluated`` and ``prev_topk`` never leave the pool
_FINALIZE_LANE_FIELDS = ("ids", "dists", "acc", "t", "n_hops", "n_pq",
                         "n_acc", "n_hot", "n_free", "rounds")


@partial(jax.jit, static_argnames=("cfg", "metric"))
def finalize_rows(
    corpus: Corpus,
    state: SearchState,
    rows: jnp.ndarray,
    cfg: SearchConfig,
    metric: str = "l2",
    node_mask: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """The continuous scheduler's retire in one program: gather the lanes
    ``rows`` (B,) of ``state`` — only the fields the rerank reads — run
    ``_finalize_batch`` on them, and pack the result into one int32
    ``(B, 2k + 6)`` array, so the host reads it in one copy: ids, then the
    distances' bits (``lax.bitcast_convert_type``, exact), then
    ``n_hops``, ``n_pq``, ``n_acc``, ``n_hot_hops``, ``n_free_pq`` and
    ``rounds``.  ``unpack_rows`` undoes it on the host."""
    lanes = state.lanes._replace(**{f: getattr(state.lanes, f)[rows]
                                    for f in _FINALIZE_LANE_FIELDS})
    r = _finalize_batch(corpus, cfg, metric, node_mask, state.queries[rows],
                        lanes)
    counters = jnp.stack([r.n_hops, r.n_pq, r.n_acc, r.n_hot_hops,
                          r.n_free_pq, r.rounds], axis=1)
    return jnp.concatenate(
        [r.ids, jax.lax.bitcast_convert_type(r.dists, jnp.int32), counters],
        axis=1)


def unpack_rows(packed: np.ndarray, k: int) -> SearchResult:
    """A host copy of ``finalize_rows``' packed array -> ``SearchResult`` of
    NumPy views into it (distances reinterpreted as float32, bit for bit)."""
    c = packed[:, 2 * k:]
    return SearchResult(ids=packed[:, :k],
                        dists=packed[:, k:2 * k].view(np.float32),
                        n_hops=c[:, 0], n_pq=c[:, 1], n_acc=c[:, 2],
                        n_hot_hops=c[:, 3], n_free_pq=c[:, 4],
                        rounds=c[:, 5])


def graph_search_stepped(
    corpus: Corpus,
    queries: jnp.ndarray,
    cfg: SearchConfig,
    metric: str = "l2",
    bloom_bits: int = 1 << 17,
    num_hashes: int = 8,
    node_mask: jnp.ndarray | None = None,
) -> SearchResult:
    """Host-side driver: iterate ``graph_search_step`` to quiescence, then
    finalize.  Semantically (bit-for-bit) equivalent to ``graph_search`` —
    the equivalence suite in tests/test_plan.py pins this; useful as a
    reference for schedulers and for testing the step kernels."""
    state = init_search_state(corpus, queries, cfg, metric, bloom_bits,
                              num_hashes, node_mask)
    while bool(search_state_active(state, cfg).any()):
        state = graph_search_step(corpus, state, cfg, metric, bloom_bits,
                                  num_hashes, node_mask)
    return finalize_search(corpus, state, cfg, metric, node_mask)


def search(
    corpus: Corpus,
    queries,
    cfg: SearchConfig,
    metric: str = "l2",
    bloom_bits: int = 1 << 17,
    num_hashes: int = 8,
    node_mask=None,
) -> SearchResult:
    """DEPRECATED entry point — builds a ``repro.plan.SearchRequest`` and
    delegates to the ``Searcher`` facade (which dispatches back to the
    ``graph_search`` kernel above with identical arguments, so results are
    bit-identical).  ``node_mask`` is passed verbatim to the traversal —
    no selectivity adaptation, exactly the legacy semantics.

    Under an active JAX trace (this name used to be jit-wrapped, so callers
    could compose it inside jit/vmap) the wrapper forwards straight to the
    kernel — the plan layer is host-side and cannot consume tracers."""
    leaves = jax.tree_util.tree_leaves((corpus, queries, node_mask))
    if any(isinstance(x, jax.core.Tracer) for x in leaves):
        return graph_search(corpus, queries, cfg, metric, bloom_bits,
                            num_hashes, node_mask=node_mask)

    from repro.plan import Searcher, SearchRequest
    from repro.plan.searcher import warn_legacy

    warn_legacy("core.search")
    s = Searcher.open(corpus, cfg=cfg, metric=metric, bloom_bits=bloom_bits,
                      num_hashes=num_hashes)
    res = s.search(SearchRequest(queries=queries, node_mask=node_mask,
                                 adaptive=False))
    return res.raw if node_mask is None else res.raw.result


# jit-cache introspection rides along so compile-count regression tests keep
# observing the kernel through the legacy name
if hasattr(graph_search, "_cache_size"):
    search._cache_size = graph_search._cache_size


def jit_cache_sizes() -> dict:
    """Executable-cache entry counts of the stack's jitted kernels — the
    recompile detector's input (``repro.obs.KernelWatch``).  Empty when the
    jax build exposes no ``_cache_size`` introspection."""
    out = {}
    for name, fn in (
        ("graph_search", graph_search),
        ("init_search_state", init_search_state),
        ("graph_search_step", graph_search_step),
        ("graph_search_advance", graph_search_advance),
        ("finalize_search", finalize_search),
        ("finalize_rows", finalize_rows),
    ):
        if hasattr(fn, "_cache_size"):
            out[name] = int(fn._cache_size())
    return out


# ---------------------------------------------------------------------------
# NumPy reference (direct Algorithm-1 transliteration) — the test oracle
# ---------------------------------------------------------------------------

def search_reference(
    adjacency: np.ndarray,
    degrees: np.ndarray,
    codes: np.ndarray,
    base: np.ndarray,
    centroids: np.ndarray,
    entry: int,
    query: np.ndarray,
    cfg: SearchConfig,
    metric: str = "l2",
    hot_count: int = 0,
    trace: np.ndarray | None = None,
    node_mask: np.ndarray | None = None,
):
    """Single-query Python loop implementation of Algorithm 1 with an exact
    visited set (no Bloom false positives). Returns (ids, dists, counters).
    Honours ``cfg.beam_width``: each round pops the E best unevaluated
    candidates and expands them together, deduplicating the combined
    neighbour set in beam order (first occurrence wins) — the same wavefront
    the JAX engine issues, so counters stay comparable at every E.
    If ``trace`` is given, expansion counts are accumulated into it
    (visit-frequency histogram for graph reordering, §IV-E).
    ``node_mask`` mirrors the JAX engine's filtered admission: non-passing
    nodes route but are excluded from the reranked top-k, the beta-margin
    anchor (T-th passing candidate) and the returned results."""
    if metric == "angular":
        # same single normalization point as the JAX path (idempotent if the
        # caller already normalized, as build_index's tracing does); base
        # rows are normalized per fetched slice, never the whole corpus
        query = l2_normalize(query, np)

    def _rows(ids):
        rows = base[ids]
        return l2_normalize(rows, np) if metric == "angular" else rows

    m = centroids.shape[0]
    if cfg.use_pq:
        adt = np.asarray(compute_adt(jnp.asarray(query), jnp.asarray(centroids), metric))

        def tdist(ids):
            return adt[np.arange(m)[None, :], codes[ids].astype(np.int64)].sum(-1)
    else:
        def tdist(ids):
            return _exact_dist(query, _rows(ids), metric)

    def adist(ids):
        return _exact_dist(query, _rows(ids), metric)

    cfg = upgrade_config(cfg)    # pre-beam pickled configs: fill defaults
    L, k = cfg.list_size, cfg.k
    E = max(int(cfg.beam_width), 1)

    def _pass(u: int) -> bool:
        return node_mask is None or bool(node_mask[u])

    counters = {"hops": 0, "pq": 0, "acc": 0, "hot": 0, "free": 0, "rounds": 0}
    d0 = float(tdist(np.asarray([entry]))[0])
    counters["pq" if cfg.use_pq else "acc"] += 1
    lst = [(d0, int(entry))]        # sorted (dist, id)
    visited = {int(entry)}
    evaluated = set()
    acc_cache = {}
    t = cfg.t_init if cfg.early_termination else L
    t_step = cfg.t_step if cfg.early_termination else L
    prev_topk = None
    stable = 0
    while counters["rounds"] < cfg.max_rounds:
        counters["rounds"] += 1
        unev = [(d, v) for d, v in lst if v not in evaluated]
        if not unev:
            break
        beam = [v for _, v in unev[:E]]           # E best unevaluated
        fresh: list[int] = []                     # beam-order, deduped
        fresh_owner_hot: list[bool] = []
        for v in beam:
            evaluated.add(v)
            counters["hops"] += 1
            if trace is not None:
                trace[v] += 1
            is_hot = v < hot_count
            if is_hot:
                counters["hot"] += 1
            neigh = [int(u) for u in adjacency[v, : degrees[v]]]
            for u in dict.fromkeys(neigh):
                if u not in visited:
                    visited.add(u)                # first occurrence owns u
                    fresh.append(u)
                    fresh_owner_hot.append(is_hot)
        if fresh:
            nd = tdist(np.asarray(fresh))
            counters["pq" if cfg.use_pq else "acc"] += len(fresh)
            counters["free"] += sum(fresh_owner_hot)
            for u, du in zip(fresh, nd):
                lst.append((float(du), u))
            lst.sort(key=lambda x: (x[0], ))
            lst = lst[:L]
        top_t = lst[: min(t, len(lst))]
        if top_t and all(v2 in evaluated for _, v2 in top_t):
            # only mask-passing candidates are admitted to the reranked
            # top-k (non-passing ones still route the traversal)
            ids_t = [v2 for _, v2 in top_t if _pass(v2)]
            fresh = [u for u in ids_t if u not in acc_cache]
            if cfg.use_pq and fresh:
                for u, du in zip(fresh, adist(np.asarray(fresh))):
                    acc_cache[u] = float(du)
                counters["acc"] += len(fresh)
            if not cfg.use_pq:
                for dd, u in top_t:
                    if _pass(u):
                        acc_cache[u] = dd
            topk = tuple(sorted(
                [u for u in ids_t][: len(ids_t)],
                key=lambda u: acc_cache[u],
            )[:k])
            topk = tuple(sorted(topk))
            if topk == prev_topk:
                stable += 1
            else:
                stable = 1
            prev_topk = topk
            if cfg.early_termination and stable >= cfg.repetition_rate:
                break
            t += t_step
            if t > L:
                break
    # final beta rerank (filtered: margin anchored at the T-th PASSING entry)
    if node_mask is None:
        t_idx = min(max(t, 1), len(lst)) - 1
        d_t = lst[t_idx][0]
        thr = d_t + (cfg.beta - 1.0) * abs(d_t)
    else:
        pass_list = [d for d, u in lst if _pass(u)]
        tt = max(t, 1)
        d_t = pass_list[tt - 1] if len(pass_list) >= tt else np.inf
        # same beta==1.0 NaN guard as the JAX engine's masked anchor
        thr = np.inf if np.isinf(d_t) else d_t + (cfg.beta - 1.0) * abs(d_t)
    if cfg.use_pq and cfg.rerank:
        need = [u for d, u in lst
                if d <= thr and _pass(u) and u not in acc_cache]
        if need:
            for u, du in zip(need, adist(np.asarray(need))):
                acc_cache[u] = float(du)
            counters["acc"] += len(need)
        scored = sorted(
            ((u, d) for u, d in acc_cache.items() if _pass(u)),
            key=lambda kv: kv[1],
        )
    else:
        scored = sorted(((u, d) for d, u in lst if _pass(u)),
                        key=lambda kv: kv[1])
    ids = np.asarray([u for u, _ in scored[:k]], dtype=np.int32)
    ds = np.asarray([d for _, d in scored[:k]], dtype=np.float32)
    if len(ids) < k:
        ids = np.pad(ids, (0, k - len(ids)), constant_values=-1)
        ds = np.pad(ds, (0, k - len(ds)), constant_values=np.inf)
    return ids, ds, counters
