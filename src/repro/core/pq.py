"""Product quantization (paper §III-B, Fig. 5-b).

Vectors are split into M subvectors; each subvector is quantized to one of C
k-means centroids. At query time an Asymmetric Distance Table ADT[m, c] holds
the partial distance between query subvector m and centroid c; the PQ distance
of a database point is the sum of M table lookups (Eq. 3).

Codebook training is host-side (offline, like the paper's k-means); encoding,
ADT construction and distance evaluation are JAX (the hot path — Pallas
kernels in ``repro.kernels`` implement the latter two for TPU).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import PQConfig


@dataclass
class PQCodebook:
    centroids: np.ndarray   # (M, C, dsub) float32
    metric: str

    @property
    def num_subvectors(self) -> int:
        return self.centroids.shape[0]

    @property
    def num_centroids(self) -> int:
        return self.centroids.shape[1]

    @property
    def dim(self) -> int:
        return self.centroids.shape[0] * self.centroids.shape[2]

    @property
    def code_bits(self) -> int:
        return self.num_subvectors * int(np.ceil(np.log2(self.num_centroids)))


# f32 matmuls at full precision: the TPU's default rounds f32 operands to
# bf16, which would give the chip other centroids, codes and ADTs than the
# CPU (and the NumPy oracle) compute for the same data
_EXACT = jax.lax.Precision.HIGHEST


def _split(x: jnp.ndarray, m: int) -> jnp.ndarray:
    """(..., D) -> (..., M, dsub)."""
    return x.reshape(*x.shape[:-1], m, x.shape[-1] // m)


# ---------------------------------------------------------------------------
# Training (host-side Lloyd k-means, vmapped over subspaces)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("iters",))
def _kmeans_one(sub: jnp.ndarray, init: jnp.ndarray, iters: int) -> jnp.ndarray:
    """Lloyd iterations for one subspace. sub: (N, dsub), init: (C, dsub)."""

    def step(cent, _):
        d = (
            (sub * sub).sum(-1)[:, None]
            - 2.0 * jnp.matmul(sub, cent.T, precision=_EXACT)
            + (cent * cent).sum(-1)[None, :]
        )
        assign = jnp.argmin(d, axis=1)
        onehot = jax.nn.one_hot(assign, cent.shape[0], dtype=sub.dtype)
        counts = onehot.sum(0)
        sums = jnp.matmul(onehot.T, sub, precision=_EXACT)
        new = jnp.where(counts[:, None] > 0, sums / jnp.maximum(counts, 1)[:, None], cent)
        return new, None

    cent, _ = jax.lax.scan(step, init, None, length=iters)
    return cent


def train_pq(data: np.ndarray, cfg: PQConfig, metric: str = "l2") -> PQCodebook:
    n, d = data.shape
    m, c = cfg.num_subvectors, cfg.num_centroids
    if d % m != 0:
        raise ValueError(f"dim {d} not divisible by M={m}")
    rng = np.random.default_rng(cfg.seed)
    x = np.asarray(data, np.float32)
    if metric == "angular":
        x = x / np.maximum(np.linalg.norm(x, axis=-1, keepdims=True), 1e-12)
    subs = x.reshape(n, m, d // m).transpose(1, 0, 2)          # (M, N, dsub)
    init_idx = np.stack(
        [rng.choice(n, size=min(c, n), replace=n < c) for _ in range(m)]
    )
    init = subs[np.arange(m)[:, None], init_idx]               # (M, C, dsub)
    cents = jax.vmap(lambda s, i: _kmeans_one(s, i, cfg.kmeans_iters))(
        jnp.asarray(subs), jnp.asarray(init)
    )
    return PQCodebook(centroids=np.asarray(cents), metric=metric)


# ---------------------------------------------------------------------------
# Encoding / ADT / distance (JAX reference; Pallas kernels mirror these)
# ---------------------------------------------------------------------------

@jax.jit
def encode(data: jnp.ndarray, centroids: jnp.ndarray) -> jnp.ndarray:
    """(N, D) -> (N, M) uint8 codes (nearest centroid per subspace)."""
    m = centroids.shape[0]
    subs = _split(data, m)                                     # (N, M, dsub)
    d = (
        (subs * subs).sum(-1)[..., None]
        - 2.0 * jnp.einsum("nmd,mcd->nmc", subs, centroids,
                           precision=_EXACT)
        + (centroids * centroids).sum(-1)[None]
    )
    return jnp.argmin(d, axis=-1).astype(jnp.uint8)


@partial(jax.jit, static_argnames=("metric",))
def compute_adt(query: jnp.ndarray, centroids: jnp.ndarray, metric: str = "l2") -> jnp.ndarray:
    """Asymmetric distance table for one query: (M, C).

    l2: ADT[m,c] = ||q_m - cent[m,c]||^2  (sum = squared L2 to the decode)
    ip/angular: ADT[m,c] = -<q_m, cent[m,c]>  (sum = -inner product; angular
    assumes inputs were normalized before PQ training/encoding)
    """
    m = centroids.shape[0]
    qs = _split(query, m)                                      # (M, dsub)
    if metric == "l2":
        return (
            (qs * qs).sum(-1)[:, None]
            - 2.0 * jnp.einsum("md,mcd->mc", qs, centroids,
                               precision=_EXACT)
            + (centroids * centroids).sum(-1)
        )
    return -jnp.einsum("md,mcd->mc", qs, centroids, precision=_EXACT)


@jax.jit
def pq_distance(codes: jnp.ndarray, adt: jnp.ndarray) -> jnp.ndarray:
    """Eq. (3): sum of M ADT lookups. codes (N, M) uint8, adt (M, C) -> (N,)."""
    m = adt.shape[0]
    return adt[jnp.arange(m)[None, :], codes.astype(jnp.int32)].sum(-1)


def decode(codes: np.ndarray, centroids: np.ndarray) -> np.ndarray:
    """Reconstruct approximate vectors from codes (host-side helper)."""
    m, _, dsub = centroids.shape
    out = centroids[np.arange(m)[None, :], codes.astype(np.int64)]  # (N, M, dsub)
    return out.reshape(codes.shape[0], m * dsub)


def calibrate_beta(
    codebook: PQCodebook,
    codes: np.ndarray,
    base: np.ndarray,
    rng: np.random.Generator,
    num_samples: int = 256,
    num_targets: int = 512,
    quantile: float = 0.99,
) -> float:
    """Empirical PQ error ratio beta (paper §III-C: 99% of PQ distances are
    within beta x of accurate distances; SIFT/32B codes -> beta ~= 1.06).

    Samples base vectors as queries, compares PQ vs accurate distances and
    returns the ``quantile`` of accurate/PQ ratio (>=1 means PQ
    underestimates; we guard both sides by taking max(ratio, 1/ratio)).
    """
    from repro.core.dataset import pairwise_dist

    n = base.shape[0]
    qi = rng.choice(n, size=min(num_samples, n), replace=False)
    ti = rng.choice(n, size=min(num_targets, n), replace=False)
    q = base[qi]
    if codebook.metric == "angular":
        q = q / np.maximum(np.linalg.norm(q, axis=-1, keepdims=True), 1e-12)
    acc = pairwise_dist(q, base[ti], codebook.metric)          # (S, T)
    cents = jnp.asarray(codebook.centroids)
    adts = jax.vmap(lambda qq: compute_adt(qq, cents, codebook.metric))(jnp.asarray(q))
    sub_codes = jnp.asarray(codes[ti])
    approx = jax.vmap(lambda a: pq_distance(sub_codes, a))(adts)  # (S, T)
    approx = np.asarray(approx)
    # shift to positive for ratio stability (ip/angular distances are negative)
    shift = min(acc.min(), approx.min())
    acc_s = acc - shift + 1e-3
    app_s = approx - shift + 1e-3
    ratio = np.maximum(acc_s / app_s, app_s / acc_s)
    return float(np.quantile(ratio, quantile))
