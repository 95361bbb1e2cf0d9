"""Distributed Proxima search — the paper's NAND-tile/search-engine split
mapped onto a TPU mesh with ``shard_map``.

Mapping (DESIGN.md §2/§5):
  * mesh axis ``data``  = NAND cores: the corpus (adjacency, PQ codes, raw
    vectors) is sharded round-robin — vertex i lives on shard ``i % P`` at
    local row ``i // P`` (paper §IV-E "core-level round-robin address
    mapping ... data with consecutive indices are assigned to consecutive
    cores").
  * mesh axis ``model`` = search queues (N_q): the query batch is sharded so
    each model-group runs an independent search engine.
  * hot nodes (ids < hot_count, after visit-frequency reordering) are
    REPLICATED on every shard — the paper's hot-node repetition, which here
    converts remote fetches into local reads.

Two execution modes (the §Perf baseline/optimized pair):
  * ``mode="fetch"`` — DiskANN-on-a-host style: the search engine psum-gathers
    the PQ *codes* of the frontier from the owning shards, then computes
    distances locally. Collective payload per round: (Q, R, M) uint8 codes
    + (Q, R) int32 adjacency.
  * ``mode="nsp"``   — the paper's near-storage insight: each shard computes
    distances for the frontier ids it OWNS and only the (Q, R) float32
    distances are reduced. Collective payload shrinks by ~M bytes/4 per
    entry (8x for M=32) — compute moves to the data.

Both modes return bit-identical results (tested); only the collective bytes
differ, which the roofline analysis measures.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import SearchConfig, upgrade_config
from repro.core import bloom
from repro.core.pq import compute_adt, pq_distance
from repro.core.search import (
    INF,
    _dedup_round,
    _exact_dist,
    _merge_sort_topl,
    _topk_ids_by,
)


class ShardedCorpus(NamedTuple):
    """Host-side container of round-robin-sharded corpus arrays.

    Sharded arrays have a leading shard axis of size P:
      adjacency (P, N/P, R), codes (P, N/P, M), base (P, N/P, D).
    Replicated: centroids, hot_* (hot-node repetition replicas), entry.
    """
    adjacency: jnp.ndarray
    codes: jnp.ndarray
    base: jnp.ndarray
    centroids: jnp.ndarray
    hot_adjacency: jnp.ndarray   # (H, R) replicated
    hot_codes: jnp.ndarray       # (H, M)
    hot_base: jnp.ndarray        # (H, D)
    entry_point: jnp.ndarray
    hot_count: jnp.ndarray       # () int32 == H
    num_vertices: int
    num_shards: int


def shard_corpus(
    adjacency: np.ndarray,
    codes: np.ndarray,
    base: np.ndarray,
    centroids: np.ndarray,
    entry_point: int,
    hot_count: int,
    num_shards: int,
) -> ShardedCorpus:
    """Round-robin partition: vertex i -> (shard i % P, local row i // P).
    Returns host (NumPy) arrays; :func:`place_corpus` puts them on a mesh."""
    n = adjacency.shape[0]
    pad = (-n) % num_shards
    if pad:
        adjacency = np.concatenate([adjacency, np.zeros((pad, adjacency.shape[1]), adjacency.dtype)])
        codes = np.concatenate([codes, np.zeros((pad, codes.shape[1]), codes.dtype)])
        base = np.concatenate([base, np.zeros((pad, base.shape[1]), base.dtype)])
    npad = n + pad
    order = np.arange(npad).reshape(npad // num_shards, num_shards).T  # (P, N/P)
    h = max(int(hot_count), 1)
    return ShardedCorpus(
        adjacency=np.asarray(adjacency)[order],
        codes=np.asarray(codes)[order],
        base=np.asarray(base)[order],
        centroids=np.asarray(centroids),
        hot_adjacency=np.asarray(adjacency[:h]),
        hot_codes=np.asarray(codes[:h]),
        hot_base=np.asarray(base[:h]),
        entry_point=np.int32(entry_point),
        hot_count=np.int32(hot_count),
        num_vertices=n,
        num_shards=num_shards,
    )


SHARDED_FIELDS = ("adjacency", "codes", "base")
REPLICATED_FIELDS = ("centroids", "hot_adjacency", "hot_codes", "hot_base",
                     "entry_point", "hot_count")


def place_corpus(corpus: ShardedCorpus, mesh: Mesh,
                 data_axis: str = "data") -> ShardedCorpus:
    """Put each shard of the (P, N/P, ·) arrays on its own device along
    ``data_axis`` (replicated over the mesh's other axes) and replicate the
    codebook, hot-node replicas and scalars on every device. Each shard
    goes straight from the host to its device; no device holds the whole
    corpus on the way."""
    sharded = NamedSharding(mesh, P(data_axis))
    replicated = NamedSharding(mesh, P())
    return corpus._replace(
        **{f: jax.device_put(getattr(corpus, f), sharded)
           for f in SHARDED_FIELDS},
        **{f: jax.device_put(getattr(corpus, f), replicated)
           for f in REPLICATED_FIELDS},
    )


def _owned_rows(arr_local, ids, shard_idx, p):
    """Gather rows for global ids from this shard's slice; zeros elsewhere.
    arr_local: (N/P, W); ids: (K,) -> (K, W) with zeros for non-owned."""
    owner = ids % p
    local = ids // p
    rows = arr_local[jnp.clip(local, 0, arr_local.shape[0] - 1)]
    mine = (owner == shard_idx) & (ids >= 0)
    return jnp.where(mine[:, None], rows, jnp.zeros_like(rows))


@partial(
    jax.jit,
    static_argnames=("cfg", "metric", "mode", "mesh", "data_axis",
                     "queue_axis", "bloom_bits", "num_hashes"),
)
def distributed_search_kernel(
    corpus: ShardedCorpus,
    queries: jnp.ndarray,
    cfg: SearchConfig,
    metric: str = "l2",
    mode: str = "nsp",
    mesh: Mesh | None = None,
    data_axis: str = "data",
    queue_axis: str = "model",
    bloom_bits: int = 1 << 17,
    num_hashes: int = 8,
):
    """Batched distributed search KERNEL — the ``distributed`` execution
    spine of a ``repro.plan.QueryPlan``. queries (Q, D) sharded over
    ``queue_axis``; corpus sharded over ``data_axis``. Returns (ids, dists)
    of shape (Q, k).
    """
    assert mesh is not None
    cfg = upgrade_config(cfg)    # pre-beam pickled configs: fill defaults
    if metric == "angular":
        queries = queries / jnp.maximum(
            jnp.linalg.norm(queries, axis=-1, keepdims=True), 1e-12
        )

    L, k = cfg.list_size, cfg.k
    R = corpus.adjacency.shape[2]
    M = corpus.codes.shape[2]
    p = corpus.num_shards
    # beam-parallel traversal (core.search semantics): E expansions per
    # round — one (Qb, E*R) collective wave instead of E serial rounds
    E = min(max(int(cfg.beam_width), 1), L)
    use_pq = cfg.use_pq
    t_init = cfg.t_init if cfg.early_termination else L
    t_step = cfg.t_step if cfg.early_termination else L

    def engine(adj_l, codes_l, base_l, cents, hot_adj, hot_codes, hot_base,
               entry, hot_count, q_block):
        """Runs on one device: full search engine for its query slice, with
        psum-served fetches from the data shards."""
        adj_l, codes_l, base_l = adj_l[0], codes_l[0], base_l[0]
        shard_idx = jax.lax.axis_index(data_axis)

        def fetch_adjacency(v):
            """(Qb,) vertex ids -> (Qb, R) neighbour ids via masked psum,
            hot rows served from the local replica."""
            cold = _owned_rows(adj_l, v, shard_idx, p)
            cold = jax.lax.psum(cold, data_axis)
            hot = hot_adj[jnp.clip(v, 0, hot_adj.shape[0] - 1)]
            return jnp.where((v < hot_count)[:, None], hot, cold)

        def score(ids2d, adts, qb):
            """(Qb, R) ids -> (Qb, R) traversal distances."""
            flat = ids2d.reshape(-1)
            if use_pq:
                if mode == "nsp":
                    # distances computed at the owning shard, psum-merged
                    def one(idv, adt):
                        cold_codes = _owned_rows(codes_l, idv, shard_idx, p)
                        d = pq_distance(cold_codes, adt)
                        mine = (idv % p == shard_idx) & (idv >= 0)
                        return jnp.where(mine, d, 0.0)
                    d = jax.vmap(one)(ids2d, adts)
                    d = jax.lax.psum(d, data_axis)
                    hot_d = jax.vmap(
                        lambda idv, adt: pq_distance(
                            hot_codes[jnp.clip(idv, 0, hot_codes.shape[0] - 1)], adt
                        )
                    )(ids2d, adts)
                    return jnp.where(ids2d < hot_count, hot_d, d)
                # fetch mode: ship the codes, compute at the engine
                cold = _owned_rows(codes_l.astype(jnp.int32), flat, shard_idx, p)
                cold = jax.lax.psum(cold, data_axis).astype(jnp.uint8)
                hot = hot_codes[jnp.clip(flat, 0, hot_codes.shape[0] - 1)]
                codes = jnp.where(
                    (flat < hot_count)[:, None], hot, cold
                ).reshape(*ids2d.shape, M)
                return jax.vmap(pq_distance)(codes, adts)
            # accurate traversal: always NSP-style (ship distances)
            def one(idv, qq):
                rows = _owned_rows(base_l, idv, shard_idx, p)
                d = _exact_dist(qq, rows, metric)
                mine = (idv % p == shard_idx) & (idv >= 0)
                return jnp.where(mine, d, 0.0)
            d = jax.lax.psum(jax.vmap(one)(ids2d, qb), data_axis)
            hot_d = jax.vmap(
                lambda idv, qq: _exact_dist(
                    qq, hot_base[jnp.clip(idv, 0, hot_base.shape[0] - 1)], metric
                )
            )(ids2d, qb)
            return jnp.where(ids2d < hot_count, hot_d, d)

        def fetch_base(ids2d, qb):
            """Accurate distances for rerank: NSP-style psum of distances."""
            def one(idv, qq):
                rows = _owned_rows(base_l, idv, shard_idx, p)
                d = _exact_dist(qq, rows, metric)
                mine = (idv % p == shard_idx) & (idv >= 0)
                return jnp.where(mine, d, 0.0)
            d = jax.lax.psum(jax.vmap(one)(ids2d, qb), data_axis)
            hot_d = jax.vmap(
                lambda idv, qq: _exact_dist(
                    qq, hot_base[jnp.clip(idv, 0, hot_base.shape[0] - 1)], metric
                )
            )(ids2d, qb)
            return jnp.where(ids2d < hot_count, hot_d, d)

        qb = q_block  # (Qb, D)
        nq = qb.shape[0]
        if use_pq:
            adts = jax.vmap(lambda qq: compute_adt(qq, cents, metric))(qb)
        else:
            adts = jnp.zeros((nq, 1, 1))

        d0 = score(jnp.broadcast_to(entry[None, None], (nq, 1)), adts, qb)[:, 0]
        ids0 = jnp.full((nq, L), -1, jnp.int32).at[:, 0].set(entry)
        dists0 = jnp.full((nq, L), INF).at[:, 0].set(d0)
        acc0 = jnp.full((nq, L), INF)
        if not use_pq:
            acc0 = acc0.at[:, 0].set(d0)
        bits0 = jnp.zeros((nq, bloom_bits // 32), jnp.uint32)
        bits0 = jax.vmap(
            lambda b: bloom.insert(b, entry[None], jnp.ones((1,), bool), num_hashes)
        )(bits0)

        state = dict(
            ids=ids0, dists=dists0, acc=acc0,
            evaluated=jnp.zeros((nq, L), bool), bits=bits0,
            t=jnp.full((nq,), min(t_init, L), jnp.int32),
            prev=jnp.full((nq, k), -2, jnp.int32),
            stable=jnp.zeros((nq,), jnp.int32),
            done=jnp.zeros((nq,), bool),
            rounds=jnp.int32(0),
        )

        def cond(s):
            return (~s["done"].all()) & (s["rounds"] < cfg.max_rounds)

        def body(s):
            valid = s["ids"] >= 0
            unev = valid & ~s["evaluated"]
            has = unev.any(axis=1)
            # per-query beam: positions of the E best unevaluated entries
            # (argmax fast path at E=1, like core.search)
            if E == 1:
                sel = jnp.argmax(unev, axis=1)[:, None]            # (Qb, 1)
            else:
                sel = jnp.argsort(~unev, axis=1, stable=True)[:, :E]
            sel_valid = jnp.arange(E)[None, :] < unev.sum(axis=1)[:, None]
            vs = jnp.where(
                sel_valid, jnp.take_along_axis(s["ids"], sel, 1), 0
            )                                                      # (Qb, E)

            neigh = fetch_adjacency(vs.reshape(-1)).reshape(nq, E * R)
            fresh = jax.vmap(_dedup_round)(neigh)
            fresh &= ~jax.vmap(lambda b, n_: bloom.contains(b, n_, num_hashes))(s["bits"], neigh)
            fresh &= jnp.repeat(sel_valid, R, axis=1)
            nd = jnp.where(fresh, score(neigh, adts, qb), INF)  # collective
            bits = jax.vmap(lambda b, n_, m_: bloom.insert(b, n_, m_, num_hashes))(
                s["bits"], neigh, fresh
            )
            evaluated = s["evaluated"].at[jnp.arange(nq)[:, None], sel].set(
                jnp.take_along_axis(s["evaluated"], sel, 1) | sel_valid
            )
            ids, dists, acc, evaluated = jax.vmap(_merge_sort_topl)(
                s["ids"], s["dists"], s["acc"], evaluated,
                jnp.where(fresh, neigh, -1).astype(jnp.int32), nd,
            )

            valid = ids >= 0
            in_t = (jnp.arange(L)[None, :] < s["t"][:, None]) & valid
            all_eval = in_t.any(1) & (~in_t | evaluated).all(1)
            need = in_t & jnp.isinf(acc)
            acc_new = fetch_base(jnp.maximum(ids, 0), qb)     # collective
            acc2 = jnp.where(need & all_eval[:, None], acc_new, acc)
            if not use_pq:
                acc2 = jnp.where(valid, dists, INF)
            rkey = jnp.where(in_t, acc2, INF)
            new_topk = jax.vmap(lambda i_, k_: _topk_ids_by(i_, k_, k))(ids, rkey)
            same = (new_topk == s["prev"]).all(1)
            stable = jnp.where(all_eval, jnp.where(same, s["stable"] + 1, 1), s["stable"])
            prev = jnp.where(all_eval[:, None], new_topk, s["prev"])
            t = jnp.where(all_eval, s["t"] + t_step, s["t"])
            term = cfg.early_termination & all_eval & (stable >= cfg.repetition_rate)
            done = term | ~has | (t > L)

            new = dict(
                ids=ids, dists=dists, acc=acc2, evaluated=evaluated, bits=bits,
                t=jnp.minimum(t, L), prev=prev, stable=stable,
                done=s["done"] | done, rounds=s["rounds"] + 1,
            )
            # frozen lanes keep their state
            out = {}
            for key in new:
                if key == "rounds":
                    out[key] = new[key]
                    continue
                oldv, newv = s[key], new[key]
                d_ = s["done"]
                while d_.ndim < newv.ndim:
                    d_ = d_[..., None]
                out[key] = jnp.where(d_, oldv, newv)
            return out

        s = jax.lax.while_loop(cond, body, state)

        valid = s["ids"] >= 0
        t_idx = jnp.clip(s["t"], 1, L) - 1
        d_t = jnp.take_along_axis(s["dists"], t_idx[:, None], 1)[:, 0]
        thr = d_t + (cfg.beta - 1.0) * jnp.abs(d_t)
        if use_pq and cfg.rerank:
            need = valid & (s["dists"] <= thr[:, None]) & jnp.isinf(s["acc"])
            acc_new = fetch_base(jnp.maximum(s["ids"], 0), qb)
            acc = jnp.where(need, acc_new, s["acc"])
        else:
            # no rerank (rank by traversal distance) / accurate traversal
            acc = jnp.where(valid, s["dists"], INF)
        key_ = jnp.where(valid, acc, INF)
        neg, idx = jax.lax.top_k(-key_, k)
        out_ids = jnp.take_along_axis(s["ids"], idx, 1)
        return out_ids, -neg

    pspec_sharded = P(data_axis, None, None)
    pspec_rep = P()
    q_spec = P(queue_axis, None)
    fn = jax.shard_map(
        engine,
        mesh=mesh,
        in_specs=(
            pspec_sharded, pspec_sharded, pspec_sharded,  # adjacency/codes/base
            pspec_rep, pspec_rep, pspec_rep, pspec_rep,   # centroids + hot_*
            pspec_rep, pspec_rep,                         # entry, hot_count
            q_spec,                                       # queries
        ),
        out_specs=(q_spec, q_spec),
        check_vma=False,
    )
    return fn(
        corpus.adjacency, corpus.codes, corpus.base,
        corpus.centroids, corpus.hot_adjacency, corpus.hot_codes,
        corpus.hot_base, corpus.entry_point, corpus.hot_count, queries,
    )


def distributed_search(
    corpus: ShardedCorpus,
    queries: jnp.ndarray,
    cfg: SearchConfig,
    metric: str = "l2",
    mode: str = "nsp",
    mesh: Mesh | None = None,
    data_axis: str = "data",
    queue_axis: str = "model",
    bloom_bits: int = 1 << 17,
    num_hashes: int = 8,
):
    """DEPRECATED entry point — builds a ``repro.plan.SearchRequest`` over
    the mesh target and delegates to the ``Searcher`` facade (which calls
    ``distributed_search_kernel`` with identical arguments, so results are
    bit-identical). Use ``distributed_search_kernel`` directly for
    ``.lower``/AOT workflows."""
    from repro.plan import Searcher, SearchRequest
    from repro.plan.searcher import warn_legacy

    warn_legacy("core.distributed_search")
    s = Searcher.open(corpus, cfg=cfg, metric=metric, mesh=mesh, mode=mode,
                      data_axis=data_axis, queue_axis=queue_axis,
                      bloom_bits=bloom_bits, num_hashes=num_hashes)
    return s.search(SearchRequest(queries=queries)).raw
