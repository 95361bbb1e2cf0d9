"""The benchmark's corpus and query generator.

Both shapes are mixtures with many small clusters (the configuration's
``assumed.num_clusters``), so that a query's neighbours lie near it and a
proximity graph over the corpus connects the clusters:

* ``gaussian_mixture`` (SIFT shape): centres ~ N(0, center_std^2) in R^dim,
  members = centre + N(0, noise_std^2) per dimension, uniform cluster sizes.
* ``sphere_mixture`` (GloVe shape): centres on the unit sphere, power-law
  cluster sizes (weight of cluster i ~ i^-size_exponent), members = the
  normalised sum of the centre and N(0, noise_std^2) per dimension.

Queries come from the same mixture as the corpus. Every array is float32.

The corpus and the query pool are the deployment's data: a pure function of
the configuration (its ``assumed.data_seed``), the same in every run, as a
published dataset is. A run's ``--seed`` draws its traffic: when each
request arrives and which pool query it carries. So seeds offer the same
work in another order, and a cell's runs spread by timing, not by data.
"""
from __future__ import annotations

import numpy as np

# independent random streams of one run, all drawn from --seed
(STREAM_CORPUS, STREAM_QUERIES, STREAM_ARRIVALS, STREAM_ORDER, STREAM_BUILD,
 STREAM_TRACE_ARRIVALS, STREAM_TRACE_ORDER) = range(7)


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """The generator of one stream of a run; any whole number is a seed."""
    return np.random.default_rng([stream, int(seed) % (1 << 64)])


def data_seed(config: dict) -> int:
    return int(config["assumed"]["data_seed"])


def build_seed(config: dict) -> int:
    """A 31-bit seed for the index build's own generators."""
    return int(rng_for(data_seed(config), STREAM_BUILD).integers(0, 1 << 31))


def _normalize(x: np.ndarray) -> np.ndarray:
    return x / np.maximum(np.linalg.norm(x, axis=-1, keepdims=True), 1e-12)


def _mixture(assumed: dict, dim: int, rng, n: int, centers, weights):
    kind = assumed["generator"]
    assign = rng.choice(len(centers), size=n, p=weights)
    noise = assumed["noise_std"] * rng.standard_normal((n, dim))
    x = centers[assign] + noise
    if kind == "sphere_mixture":
        x = _normalize(x)
    return x.astype(np.float32)


def _centers(assumed: dict, dim: int, rng):
    kind = assumed["generator"]
    k = int(assumed["num_clusters"])
    centers = rng.standard_normal((k, dim))
    if kind == "gaussian_mixture":
        return centers * assumed["center_std"], np.full(k, 1.0 / k)
    if kind == "sphere_mixture":
        w = 1.0 / np.arange(1, k + 1) ** assumed["size_exponent"]
        return _normalize(centers), w / w.sum()
    raise ValueError(f"unknown generator {kind!r}")


def make_corpus(config: dict, num_base: int | None = None,
                num_queries: int | None = None):
    """(base (N, dim), query pool (Q, dim)) for a configuration file's
    contents; ``num_base``/``num_queries`` default to the configuration's."""
    assumed, dim = config["assumed"], int(config["dim"])
    seed = data_seed(config)
    n = int(num_base or config["num_base"])
    q = int(num_queries or config["num_queries"])
    rng = rng_for(seed, STREAM_CORPUS)
    centers, weights = _centers(assumed, dim, rng)
    base = _mixture(assumed, dim, rng, n, centers, weights)
    queries = _mixture(assumed, dim, rng_for(seed, STREAM_QUERIES), q,
                       centers, weights)
    return base, queries
