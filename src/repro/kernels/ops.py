"""Jit'd public wrappers for the Pallas kernels.

Interpret mode follows the backend alone: on a TPU the kernels compile to
Mosaic; on any other backend (the CPU test suite) they run with
``interpret=True``, which evaluates the kernel bodies for correctness only.
``SearchConfig.use_pallas`` gates whether the search layer routes through
the Pallas path or the pure-jnp path.

Observability (``repro.obs``): ``set_observability`` points a module-level
hook at a registry; each wrapper then reports

* ``kernel_wall_ms{kernel=...}`` — wall time of EAGER calls (timed around a
  ``block_until_ready``, so it is realized device time, not dispatch time);
* ``kernel_traces{kernel=...}`` — one count each time the wrapper body runs
  under an active JAX trace.  These wrappers are called from inside jitted
  engines (``graph_search``), so every increment is one (re)trace of the
  enclosing kernel — the Pallas-side recompile-detector signal
  (``obs.KernelWatch`` covers the jit-cache side).

The hook defaults to None and every wrapper checks it with one branch —
zero cost when observability is off.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.bitonic_topk import bitonic_sort_pairs as _bitonic
from repro.kernels.l2_rerank import l2_rerank as _l2_rerank
from repro.kernels.pq_adt import pq_adt as _pq_adt
from repro.kernels.pq_lookup import pq_lookup as _pq_lookup

_obs = None     # Observability bundle (repro.obs) or None — module-wide hook


def set_observability(obs) -> None:
    """Install (or clear, with None) the kernel instrumentation sink.
    Usually called via ``Observability.install_kernel_hooks()``."""
    global _obs
    _obs = obs if obs is not None and getattr(obs, "enabled", False) else None


def _instrumented(name: str, operands, fn):
    """Run ``fn`` with wall-time / retrace accounting when the hook is set."""
    if _obs is None:
        return fn()
    if any(isinstance(x, jax.core.Tracer) for x in operands):
        # inside an enclosing jit trace: timing is meaningless, but the
        # trace itself is the (re)compile event worth counting
        _obs.metrics.counter("kernel_traces", kernel=name)
        return fn()
    t0 = time.perf_counter()
    out = fn()
    jax.block_until_ready(out)
    _obs.metrics.observe("kernel_wall_ms", (time.perf_counter() - t0) * 1e3,
                         kernel=name)
    _obs.metrics.counter("kernel_calls", kernel=name)
    return out


def interpret_mode() -> bool:
    """True on every backend but the TPU; off the TPU the kernels are
    only interpreted, for correctness."""
    return jax.default_backend() != "tpu"


def pq_adt(queries, centroids, metric="l2", interpret=None):
    interpret = interpret_mode() if interpret is None else interpret
    q = queries.shape[0]
    q_block = 8 if q % 8 == 0 else (4 if q % 4 == 0 else 1)
    return _instrumented(
        "pq_adt", (queries, centroids),
        lambda: _pq_adt(queries, centroids, metric=metric, q_block=q_block,
                        interpret=interpret),
    )


def pq_lookup(codes, adt, interpret=None):
    interpret = interpret_mode() if interpret is None else interpret
    return _instrumented(
        "pq_lookup", (codes, adt),
        lambda: _pq_lookup(codes, adt, interpret=interpret),
    )


def bitonic_sort_pairs(keys, vals, interpret=None):
    interpret = interpret_mode() if interpret is None else interpret
    return _instrumented(
        "bitonic_sort_pairs", (keys, vals),
        lambda: _bitonic(keys, vals, interpret=interpret),
    )


def l2_rerank(queries, candidates, metric="l2", interpret=None):
    interpret = interpret_mode() if interpret is None else interpret
    return _instrumented(
        "l2_rerank", (queries, candidates),
        lambda: _l2_rerank(queries, candidates, metric=metric,
                           interpret=interpret),
    )


# re-export oracles for convenience
pq_adt_ref = ref.pq_adt_ref
pq_lookup_ref = ref.pq_lookup_ref
bitonic_sort_pairs_ref = ref.bitonic_sort_pairs_ref
l2_rerank_ref = ref.l2_rerank_ref
