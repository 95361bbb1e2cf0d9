"""Pallas kernel microbenchmarks next to the jnp reference path. On a TPU
the kernels run compiled; on any other backend they run in interpret mode,
so those timings measure the interpreter, not a kernel."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from benchmarks.common import timed
from repro.kernels import ops


def main(out=print) -> None:
    rng = np.random.default_rng(0)
    M, C, dsub, N, Q = 32, 256, 4, 4096, 8
    q = jnp.asarray(rng.standard_normal((Q, M * dsub)), jnp.float32)
    cents = jnp.asarray(rng.standard_normal((M, C, dsub)), jnp.float32)
    codes = jnp.asarray(rng.integers(0, C, (N, M)), jnp.uint8)
    adt = jnp.asarray(rng.standard_normal((M, C)), jnp.float32)
    keys = jnp.asarray(rng.standard_normal((64, 256)), jnp.float32)
    vals = jnp.asarray(rng.integers(0, 1 << 20, (64, 256)), jnp.int32)
    qr = jnp.asarray(rng.standard_normal((Q, 128)), jnp.float32)
    cands = jnp.asarray(rng.standard_normal((Q, 128, 128)), jnp.float32)

    pairs = [
        ("pq_adt", lambda: ops.pq_adt(q, cents), lambda: ops.pq_adt_ref(q, cents)),
        ("pq_lookup", lambda: ops.pq_lookup(codes, adt), lambda: ops.pq_lookup_ref(codes, adt)),
        ("bitonic_sort", lambda: ops.bitonic_sort_pairs(keys, vals),
         lambda: ops.bitonic_sort_pairs_ref(keys, vals)),
        ("l2_rerank", lambda: ops.l2_rerank(qr, cands), lambda: ops.l2_rerank_ref(qr, cands)),
    ]
    import jax

    def blocked(f):
        def g():
            r = f()
            jax.block_until_ready(r)
            return r
        return g

    for name, kern, ref in pairs:
        _, us_k = timed(blocked(kern))
        _, us_r = timed(blocked(ref))
        mode = "interp" if ops.interpret_mode() else "mosaic"
        out(f"kernels/{name}_{mode},{us_k:.1f},ref_jnp_us={us_r:.1f}")


if __name__ == "__main__":
    main()
