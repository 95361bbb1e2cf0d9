"""Compile the served path for a described TPU v5e (no chip attached).

The TPU compiler is installed with jaxlib, so it can compile for a 2x2 v5e
topology that is only described: it refuses what the chip would refuse
(unaligned blocks, shape casts Mosaic cannot lay out, programs that do not
fit HBM) while nothing runs. Interpret-mode tests cannot see any of that.

The topology is described inside a fixture and never at import time: only
one process may load the TPU library, and a decision made while test
modules are imported would give pytest-xdist workers different tests.
Everything built from the topology is built in fixtures or tests.
"""
import dataclasses
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.configs.base import SearchConfig
from repro.kernels import ops
from repro.kernels.bitonic_topk import bitonic_sort_pairs
from repro.kernels.l2_rerank import l2_rerank
from repro.kernels.pq_adt import pq_adt
from repro.kernels.pq_lookup import pq_lookup

# SIFT1M served widths: N vectors of D f32, R-regular graph, M x C PQ
N, D, R, M, C = 1_000_000, 128, 32, 32, 256
Q, L, K = 64, 128, 10
CFG = SearchConfig(k=K, list_size=L, t_init=16, t_step=8, repetition_rate=2,
                   beta=1.06)
HBM_BYTES = 16 * 1024**3        # one v5e chip


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep such entries out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture
def compiled_kernels(monkeypatch):
    """The kernel wrappers pick interpret mode from the default backend,
    which is the CPU here; compile them for the described chip instead."""
    monkeypatch.setattr(ops, "interpret_mode", lambda: False)


def _sds(sharding):
    return lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                  sharding=sharding)


# vmapped shapes are what graph_search hands the kernels: one lane's (R, M)
# codes per query, and one (1, next_pow2(L + R)) merged row per query
_KERNEL_CASES = {
    "pq_adt": (
        lambda q, c: pq_adt(q, c, interpret=False),
        [((Q, D), jnp.float32), ((M, C, D // M), jnp.float32)]),
    "pq_lookup": (
        jax.vmap(lambda c, a: pq_lookup(c, a, interpret=False)),
        [((Q, R, M), jnp.uint8), ((Q, M, C), jnp.float32)]),
    "bitonic_sort_pairs": (
        jax.vmap(lambda k, v: bitonic_sort_pairs(k[None], v[None],
                                                 interpret=False)),
        [((Q, 256), jnp.float32), ((Q, 256), jnp.int32)]),
    "l2_rerank": (
        lambda q, x: l2_rerank(q, x, interpret=False),
        [((Q, D), jnp.float32), ((Q, L, D), jnp.float32)]),
}


@pytest.mark.parametrize("name", sorted(_KERNEL_CASES))
def test_kernel_compiles_for_v5e(name, one_chip, no_compile_cache):
    fn, shapes = _KERNEL_CASES[name]
    sds = _sds(one_chip)
    args = [sds(s, dt) for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _corpus_shapes(sds):
    from repro.core.search import Corpus

    return Corpus(
        adjacency=sds((N, R), jnp.int32), codes=sds((N, M), jnp.uint8),
        base=sds((N, D), jnp.float32),
        centroids=sds((M, C, D // M), jnp.float32),
        entry_point=sds((), jnp.int32), hot_count=sds((), jnp.int32),
    )


@pytest.mark.parametrize("use_pallas", [False, True])
def test_graph_search_compiles_for_v5e(use_pallas, one_chip,
                                       no_compile_cache, compiled_kernels):
    from repro.core.search import graph_search

    sds = _sds(one_chip)
    cfg = dataclasses.replace(CFG, use_pallas=use_pallas)
    compiled = graph_search.lower(_corpus_shapes(sds),
                                  sds((Q, D), jnp.float32), cfg,
                                  "l2").compile()
    assert ("tpu_custom_call" in compiled.as_text()) == use_pallas
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES


def _state_shapes(sds, corpus):
    from repro.core.search import init_search_state

    state = jax.eval_shape(lambda q: init_search_state(corpus, q, CFG, "l2"),
                           jax.ShapeDtypeStruct((Q, D), jnp.float32))
    return jax.tree_util.tree_map(lambda a: sds(a.shape, a.dtype), state)


def test_graph_search_advance_compiles_for_v5e(one_chip, no_compile_cache):
    """The continuous scheduler's dispatch: rounds looped on the device,
    the slot pool's state in and out, one packed (Q + 1,) int32 read."""
    from repro.core.search import graph_search_advance

    sds = _sds(one_chip)
    corpus = _corpus_shapes(sds)
    compiled = graph_search_advance.lower(corpus, _state_shapes(sds, corpus),
                                          sds((), jnp.int32), CFG,
                                          "l2").compile()
    assert "while" in compiled.as_text()
    out = compiled.out_info[1]
    assert out.shape == (Q + 1,) and out.dtype == jnp.int32
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES


@pytest.mark.parametrize("rows", [1, Q])
def test_finalize_rows_compiles_for_v5e(rows, one_chip, no_compile_cache):
    """The continuous scheduler's retire: the chosen rows of a 64-lane pool
    state gathered, reranked and packed into one (rows, 2k + 6) int32
    array."""
    from repro.core.search import finalize_rows

    sds = _sds(one_chip)
    corpus = _corpus_shapes(sds)
    compiled = finalize_rows.lower(corpus, _state_shapes(sds, corpus),
                                   sds((rows,), jnp.int32), CFG,
                                   "l2").compile()
    out = compiled.out_info
    assert out.shape == (rows, 2 * K + 6) and out.dtype == jnp.int32
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES


def _program_digest(compiled) -> str:
    """sha256 of a compiled program's HLO text without its metadata: the
    ``metadata={...}`` of each instruction and the tables of file names,
    functions and stack frames they point into."""
    tables = re.compile(r"(\d+ |(FileNames|FunctionNames|FileLocations|"
                        r"StackFrames)$)")
    text = "\n".join(line for line in compiled.as_text().splitlines()
                     if not tables.match(line))
    text = re.sub(r",? metadata=\{[^}]*\}", "", text)
    return hashlib.sha256(text.encode()).hexdigest()[:16]


# digests of the v5e programs of the batch kernel and the continuous
# scheduler's advance before the retire became one program (jax 0.9.0):
# adding ``finalize_rows`` must leave both programs as they were
_SERVED_DIGESTS = {"graph_search": "8cbd1a6834231819",
                   "graph_search_advance": "f9e2dbda8fdd7bfd"}


@pytest.mark.parametrize("name", sorted(_SERVED_DIGESTS))
def test_served_programs_unchanged_for_v5e(name, one_chip, no_compile_cache):
    from repro.core.search import graph_search, graph_search_advance

    sds = _sds(one_chip)
    corpus = _corpus_shapes(sds)
    if name == "graph_search":
        lowered = graph_search.lower(corpus, sds((Q, D), jnp.float32), CFG,
                                     "l2")
    else:
        lowered = graph_search_advance.lower(
            corpus, _state_shapes(sds, corpus), sds((), jnp.int32), CFG,
            "l2")
    assert _program_digest(lowered.compile()) == _SERVED_DIGESTS[name]


def test_distributed_search_compiles_on_4_chip_mesh(topo, no_compile_cache):
    from repro.core.distributed import ShardedCorpus, distributed_search_kernel

    p = 4
    mesh = Mesh(np.asarray(topo.devices).reshape(p, 1), ("data", "model"))
    shard = _sds(NamedSharding(mesh, P("data")))
    rep = _sds(NamedSharding(mesh, P()))
    hot = int(0.03 * N)
    corpus = ShardedCorpus(
        adjacency=shard((p, N // p, R), jnp.int32),
        codes=shard((p, N // p, M), jnp.uint8),
        base=shard((p, N // p, D), jnp.float32),
        centroids=rep((M, C, D // M), jnp.float32),
        hot_adjacency=rep((hot, R), jnp.int32),
        hot_codes=rep((hot, M), jnp.uint8),
        hot_base=rep((hot, D), jnp.float32),
        entry_point=rep((), jnp.int32), hot_count=rep((), jnp.int32),
        num_vertices=N, num_shards=p,
    )
    compiled = distributed_search_kernel.lower(
        corpus, rep((Q, D), jnp.float32), CFG, "l2", mode="nsp", mesh=mesh,
    ).compile()
    text = compiled.as_text()
    assert "all-reduce" in text              # the psum-served fetches
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes < HBM_BYTES
