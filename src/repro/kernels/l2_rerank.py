"""Pallas TPU kernel: accurate-distance reranking (paper §III-C / Alg.1
l.12+19 — the "accurate distance" path of the Distance Computation Module).

Given a query batch (Q, D) and per-query gathered candidate vectors
(Q, K, D), emit (Q, K) exact distances:

    l2: sum_d (x_d - q_d)^2      ip/angular: -q.x

The candidates are passed transposed, (Q, D, K), so the candidate axis lies
on the lanes and the reduction over D runs down the sublanes on the VPU;
the result block is then lane-dense with no relayout. The l2 form is the
same difference-of-squares as the jnp path (``core.search._exact_dist``).
Tiling: grid over (query block of 8, candidate block), Q padded to a
multiple of 8 (the sublane tile); VMEM per program = 8*D*(KB+1)*4 bytes
(K=128, D=128 -> ~0.5 MB).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_Q_BLOCK = 8        # queries per program: the f32 sublane tile


def _rerank_kernel(q_ref, x_ref, out_ref, *, metric: str):
    q = q_ref[...]            # (QB, D, 1)
    x = x_ref[...]            # (QB, D, KB)
    if metric == "l2":
        diff = x - q
        out_ref[...] = (diff * diff).sum(axis=1)
    else:
        out_ref[...] = -(x * q).sum(axis=1)


@functools.partial(jax.jit, static_argnames=("metric", "k_block", "interpret"))
def l2_rerank(
    queries: jnp.ndarray,      # (Q, D)
    candidates: jnp.ndarray,   # (Q, K, D) gathered candidate vectors
    metric: str = "l2",
    k_block: int = 0,
    interpret: bool = True,
) -> jnp.ndarray:
    """Returns (Q, K) accurate distances."""
    q, k, d = candidates.shape
    if k_block == 0:
        k_block = k
    assert k % k_block == 0
    qs = queries[:, :, None]                        # (Q, D, 1)
    xt = jnp.swapaxes(candidates, 1, 2)             # (Q, D, K)
    pad = (-q) % _Q_BLOCK
    if pad:
        qs = jnp.pad(qs, ((0, pad), (0, 0), (0, 0)))
        xt = jnp.pad(xt, ((0, pad), (0, 0), (0, 0)))
    qp = q + pad
    out = pl.pallas_call(
        functools.partial(_rerank_kernel, metric=metric),
        grid=(qp // _Q_BLOCK, k // k_block),
        in_specs=[
            pl.BlockSpec((_Q_BLOCK, d, 1), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((_Q_BLOCK, d, k_block), lambda i, j: (i, 0, j)),
        ],
        out_specs=pl.BlockSpec((_Q_BLOCK, k_block), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((qp, k), jnp.float32),
        interpret=interpret,
    )(qs, xt)
    return out[:q]
