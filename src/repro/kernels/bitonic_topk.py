"""Pallas TPU kernel: batched bitonic sort of (distance, id) pairs —
the paper's shared 256-point Bitonic Sorter (§IV-D), which sorts the merged
candidate list each traversal round in constant 2*log2(N)^2/... stages.

Every compare-exchange stage is a full-width vector op (VPU-friendly, no
scatter, no reshape): for stride j, each lane's partner is lane ``i ^ j``,
fetched with a lane rotation by +j or -j. Which rotation brings the partner
is read off the rotated lane iota, so the kernel does not depend on the
rotate direction. Both lanes of a pair compute the same swap predicate (the
direction flag is bit ``block`` of the lane index), and ids travel with
their keys on the same predicate. All stages of one (QB, L) tile run in
VMEM in a single program — L=256: QB*L*8 B = 16 kB per tile at QB=8.

Ascending order; pad with +inf keys to a power of two before calling.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _bitonic_stages(keys: jnp.ndarray, vals: jnp.ndarray):
    """Full bitonic sorting network on the last axis (power-of-two length)."""
    q, l = keys.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, (q, l), 1)
    n_stages = l.bit_length() - 1
    for k_stage in range(1, n_stages + 1):
        # ascending if the enclosing 2^k block index is even
        asc = (lane & (1 << k_stage)) == 0
        for j_pow in range(k_stage - 1, -1, -1):
            j = 1 << j_pow
            fwd = pltpu.roll(lane, j, 1) == (lane ^ j)

            def partner(x, fwd=fwd, j=j):
                return jnp.where(fwd, pltpu.roll(x, j, 1),
                                 pltpu.roll(x, l - j, 1))

            pk, pv = partner(keys), partner(vals)
            lower = (lane & j) == 0
            lo_k = jnp.where(lower, keys, pk)
            hi_k = jnp.where(lower, pk, keys)
            # logical ops, not a select between booleans: Mosaic cannot
            # lower the i8 -> i1 truncation such a select produces
            swap = (asc & (lo_k > hi_k)) | (~asc & (lo_k < hi_k))
            keys = jnp.where(swap, pk, keys)
            vals = jnp.where(swap, pv, vals)
    return keys, vals


def _sort_kernel(keys_ref, vals_ref, out_k_ref, out_v_ref):
    keys, vals = _bitonic_stages(keys_ref[...], vals_ref[...])
    out_k_ref[...] = keys
    out_v_ref[...] = vals


@functools.partial(jax.jit, static_argnames=("q_block", "interpret"))
def bitonic_sort_pairs(
    keys: jnp.ndarray,    # (Q, L) float32 — L must be a power of two
    vals: jnp.ndarray,    # (Q, L) int32 payload
    q_block: int = 8,
    interpret: bool = True,
):
    """Sort each row ascending by key, carrying vals. Returns (keys, vals)."""
    q, l = keys.shape
    assert l & (l - 1) == 0, "row length must be a power of two"
    # up to q_block rows form one block spanning the whole array (legal at
    # any row count), so a single row is never padded to q_block
    q_block = min(q_block, q)
    pad = (-q) % q_block
    if pad:
        keys = jnp.pad(keys, ((0, pad), (0, 0)), constant_values=jnp.inf)
        vals = jnp.pad(vals, ((0, pad), (0, 0)), constant_values=-1)
    qp = q + pad
    spec = pl.BlockSpec((q_block, l), lambda i: (i, 0))
    out_k, out_v = pl.pallas_call(
        _sort_kernel,
        grid=(qp // q_block,),
        in_specs=[spec, spec],
        out_specs=[spec, spec],
        out_shape=[
            jax.ShapeDtypeStruct((qp, l), keys.dtype),
            jax.ShapeDtypeStruct((qp, l), vals.dtype),
        ],
        interpret=interpret,
    )(keys, vals)
    return out_k[:q], out_v[:q]
