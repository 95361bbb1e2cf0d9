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
With ``assumed.quantize`` ``"uint8"`` the mixture is rounded and clipped to
0..255 (still held as float32); ``assumed.center_mean`` shifts the Gaussian
mixture's centres so that it lies inside that range.

A configuration with a ``labels`` block also has tags (``make_labels``):
each base vector a set of tags from a vocabulary, each pool query a
predicate, the conjunction of one or more tags that an answer's tag set has
to contain. Tags follow the vector's cluster, as image tags follow image
content: a share of each vector's tags comes from a small tag subset of its
cluster, the rest from the global Zipf popularity.

The corpus and the query pool are the deployment's data: a pure function of
the configuration (its ``assumed.data_seed``), the same in every run, as a
published dataset is. A run's ``--seed`` draws its traffic: when each
request arrives and which pool query it carries. So seeds offer the same
work in another order, and a cell's runs spread by timing, not by data.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np

from bench.check import TagSets

# independent random streams of one run, all drawn from --seed; a new
# stream is appended, so that no existing one moves
(STREAM_CORPUS, STREAM_QUERIES, STREAM_ARRIVALS, STREAM_ORDER, STREAM_BUILD,
 STREAM_TRACE_ARRIVALS, STREAM_TRACE_ORDER, STREAM_TAGS,
 STREAM_PREDICATES) = range(9)
# draws of one pool query's predicate before the configuration is refused
PREDICATE_TRIES = 1000


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


def _members(assumed: dict, rng, centers, assign) -> np.ndarray:
    """The mixture's points of clusters ``assign``, drawn from ``rng``."""
    noise = assumed["noise_std"] * rng.standard_normal(
        (len(assign), centers.shape[1]))
    x = centers[assign] + noise
    if assumed["generator"] == "sphere_mixture":
        x = _normalize(x)
    quantize = assumed.get("quantize")
    if quantize == "uint8":
        x = np.clip(np.rint(x), 0, 255)
    elif quantize is not None:
        raise ValueError(f"unknown quantize {quantize!r} (expected 'uint8')")
    return x.astype(np.float32)


def _centers(assumed: dict, dim: int, rng):
    kind = assumed["generator"]
    k = int(assumed["num_clusters"])
    centers = rng.standard_normal((k, dim))
    if kind == "gaussian_mixture":
        centers = centers * assumed["center_std"]
        if "center_mean" in assumed:
            centers = centers + assumed["center_mean"]
        return centers, np.full(k, 1.0 / k)
    if kind == "sphere_mixture":
        w = 1.0 / np.arange(1, k + 1) ** assumed["size_exponent"]
        return _normalize(centers), w / w.sum()
    raise ValueError(f"unknown generator {kind!r}")


def _sizes(config: dict, num_base, num_queries) -> tuple:
    return (int(num_base or config["num_base"]),
            int(num_queries or config["num_queries"]))


def _clusters(config: dict, n: int, q: int):
    """(centres, cluster of each base row, cluster of each pool query, and
    the two generators, each where its points' noise is drawn next)."""
    assumed, dim = config["assumed"], int(config["dim"])
    seed = data_seed(config)
    rng = rng_for(seed, STREAM_CORPUS)
    centers, weights = _centers(assumed, dim, rng)
    assign = rng.choice(len(centers), size=n, p=weights)
    qrng = rng_for(seed, STREAM_QUERIES)
    qassign = qrng.choice(len(centers), size=q, p=weights)
    return centers, assign, qassign, rng, qrng


def make_corpus(config: dict, num_base: int | None = None,
                num_queries: int | None = None):
    """(base (N, dim), query pool (Q, dim)) for a configuration file's
    contents; ``num_base``/``num_queries`` default to the configuration's."""
    centers, assign, qassign, rng, qrng = _clusters(
        config, *_sizes(config, num_base, num_queries))
    assumed = config["assumed"]
    return (_members(assumed, rng, centers, assign),
            _members(assumed, qrng, centers, qassign))


class Labels(NamedTuple):
    """The tags of a configuration with a ``labels`` block."""
    offsets: np.ndarray       # (N + 1,) int64: base row i has the tags
    tags: np.ndarray          # tags[offsets[i]:offsets[i + 1]], int32,
                              # ascending, none twice
    predicates: np.ndarray    # (Q, T) int32: the tags pool query j requires,
                              # ascending, -1 where it requires fewer than T
    admits: np.ndarray        # (Q,) int64: base rows each predicate admits


def _popularity(vocabulary: int, exponent: float) -> np.ndarray:
    w = 1.0 / np.arange(1, vocabulary + 1) ** exponent
    return w / w.sum()


def make_labels(config: dict, num_base: int | None = None,
                num_queries: int | None = None) -> Labels:
    """The tag sets of the base rows and the predicate of each pool query,
    drawn from ``assumed.data_seed`` on their own streams. The ``labels``
    block states:

    * ``vocabulary``: the number of tags;
    * ``tags_per_vector`` ``[lo, hi]``: each base vector draws a number of
      tags uniform in ``lo .. hi``; a tag drawn twice counts once;
    * ``zipf_exponent``: the global popularity of tag t is proportional to
      ``(t + 1) ** -zipf_exponent``;
    * ``cluster_tags`` and ``cluster_share``: each cluster has a subset of
      ``cluster_tags`` tags drawn by popularity; each tag a vector (or a
      query) draws comes from its cluster's subset, uniformly, with
      probability ``cluster_share``, and from the global popularity
      otherwise;
    * ``query_tag_shares``: entry i is the share of pool queries that
      require i + 1 distinct tags, all of them (AND);
    * ``min_matches``: a predicate that admits fewer base rows is drawn
      again, so every pool query has at least that many answers (at least
      ``k``: recall@k keeps its meaning).
    """
    spec = config["labels"]
    n, q = _sizes(config, num_base, num_queries)
    _, assign, qassign, _, _ = _clusters(config, n, q)
    vocabulary = int(spec["vocabulary"])
    lo, hi = (int(v) for v in spec["tags_per_vector"])
    share = float(spec["cluster_share"])
    shares = np.asarray(spec["query_tag_shares"], np.float64)
    min_matches = int(spec["min_matches"])
    if min_matches < int(config["k"]):
        raise ValueError(f"labels.min_matches {min_matches} is under k "
                         f"{config['k']}")
    if not 1 <= lo <= hi or abs(shares.sum() - 1.0) > 1e-9:
        raise ValueError("labels: tags_per_vector needs 1 <= lo <= hi, and "
                         "query_tag_shares has to sum to 1")
    popular = _popularity(vocabulary, float(spec["zipf_exponent"]))
    seed = data_seed(config)

    rng = rng_for(seed, STREAM_TAGS)
    subsets = rng.choice(vocabulary,
                         size=(int(config["assumed"]["num_clusters"]),
                               int(spec["cluster_tags"])), p=popular)
    count = rng.integers(lo, hi + 1, size=n)
    local = rng.random((n, hi)) < share
    from_cluster = subsets[assign[:, None],
                           rng.integers(0, subsets.shape[1], size=(n, hi))]
    drawn = np.where(local, from_cluster,
                     rng.choice(vocabulary, size=(n, hi), p=popular))
    drawn[np.arange(hi)[None, :] >= count[:, None]] = vocabulary
    drawn.sort(axis=1)
    keep = drawn < vocabulary
    keep[:, 1:] &= drawn[:, 1:] != drawn[:, :-1]
    offsets = np.concatenate([[0], np.cumsum(keep.sum(axis=1))])
    tags = drawn[keep].astype(np.int32)
    sets = TagSets(offsets, tags)

    rng = rng_for(seed, STREAM_PREDICATES)
    predicates = np.full((q, len(shares)), -1, np.int32)
    admits = np.zeros(q, np.int64)
    sizes = rng.choice(len(shares), size=q, p=shares) + 1
    for j in range(q):
        for _ in range(PREDICATE_TRIES):
            own = rng.random(sizes[j]) < share
            pick = np.where(
                own, subsets[qassign[j], rng.integers(
                    0, subsets.shape[1], size=sizes[j])],
                rng.choice(vocabulary, size=sizes[j], p=popular))
            if len(set(pick.tolist())) < sizes[j]:
                continue
            pick.sort()
            matches = sets.count(pick)
            if matches >= min_matches:
                predicates[j, :sizes[j]] = pick
                admits[j] = matches
                break
        else:
            raise ValueError(
                f"labels: pool query {j} found no predicate of {sizes[j]} "
                f"tags that admits {min_matches} base rows in "
                f"{PREDICATE_TRIES} draws")
    return Labels(offsets, tags, predicates, admits)
