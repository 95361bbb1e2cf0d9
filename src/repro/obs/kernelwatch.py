"""Recompile detector — jit executable-cache accounting per kernel.

JAX recompiles silently: a stray non-bucketed batch shape, a config object
that stopped hashing stably, or a weak-typed scalar can multiply compiles
and turn a serving loop into a trace loop.  ``KernelWatch`` samples each
registered kernel's executable-cache growth into the metrics registry
(``jit_cache_growth{kernel=...}``) and warns — :class:`RecompileWarning` —
when a kernel exceeds its expected entry budget (for the serving engine:
``log2(batch_size)+1`` power-of-two buckets per distinct executed plan
config, the invariant the pow2-bucket compile-count test asserts).

Pallas kernel wrappers (``repro.kernels.ops``) report retraces through the
``kernel_traces`` counter instead — each wrapper body run under a JAX trace
is one (re)trace of that kernel — so both compile-count sources land in the
same registry.
"""
from __future__ import annotations

import warnings
from typing import Callable, Dict, Optional

from repro.obs.registry import MetricsRegistry


class RecompileWarning(UserWarning):
    """A watched kernel's jit cache grew past its expected entry budget."""


def default_kernel_sources() -> Dict[str, Callable[[], int]]:
    """Cache-size probes for the stack's jitted kernels (feature-detected:
    older jax builds without ``_cache_size`` just yield no sources)."""
    import importlib

    # repro.core re-exports a `search` FUNCTION that shadows the submodule
    # on attribute access — resolve the module itself
    core_search = importlib.import_module("repro.core.search")
    sizes = core_search.jit_cache_sizes()
    return {name: (lambda n=name: core_search.jit_cache_sizes().get(n, 0))
            for name in sizes}


class KernelWatch:
    def __init__(self, registry: MetricsRegistry,
                 sources: Optional[Dict[str, Callable[[], int]]] = None,
                 warn: bool = True):
        self.registry = registry
        self.sources = dict(sources) if sources is not None \
            else default_kernel_sources()
        self.warn = warn
        self._warned: set = set()
        # entries present at construction are pre-existing (warm-up compiles
        # by other owners) — budgets apply to growth observed by THIS watch
        self.baseline = {n: int(fn()) for n, fn in self.sources.items()}

    def register(self, name: str, cache_size: Callable[[], int]) -> None:
        self.sources[name] = cache_size
        self.baseline[name] = int(cache_size())

    def sample(self) -> Dict[str, int]:
        """Record every kernel's cache growth since construction as a gauge;
        returns ``{kernel: entries}``."""
        out = {}
        for name, fn in self.sources.items():
            n = int(fn())
            out[name] = n
            self.registry.gauge("jit_cache_growth", n - self.baseline[name],
                                kernel=name)
        return out

    def check(self, expected_growth: int) -> Dict[str, int]:
        """Sample, then warn (once per kernel) if any kernel accumulated
        more than ``expected_growth`` NEW cache entries since this watch was
        constructed.  Returns the sampled sizes."""
        sizes = self.sample()
        for name, n in sizes.items():
            grew = n - self.baseline[name]
            if grew > expected_growth and name not in self._warned:
                self._warned.add(name)
                self.registry.counter("unexpected_recompiles",
                                      grew - expected_growth, kernel=name)
                if self.warn:
                    warnings.warn(
                        f"kernel '{name}' compiled {grew} new executables "
                        f"(expected <= {expected_growth}) — a non-bucketed "
                        f"batch shape or unstable static argument is "
                        f"defeating the compile cache",
                        RecompileWarning, stacklevel=2,
                    )
        return sizes
