"""Pallas TPU kernel: PQ distance evaluation, Eq. (3) of the paper.

The ASIC's per-queue "Distance Computation Module" does M SRAM lookups + an
M-term accumulation per candidate. TPUs have no efficient VMEM gather, so
each lookup is a compare-and-select (DESIGN.md §2, hardware adaptation):

    dist[n] = sum_m ADT[m, codes[n, m]]
            = sum_m sum_c [codes[n, m] == c] * ADT[m, c]

Per grid step the kernel holds a (NB, M) code tile and the full (M, C) ADT
in VMEM and accumulates over the M subspaces one (NB, C) select + lane
reduction at a time, so no 3-D one-hot is ever built or reshaped (Mosaic
refuses the (NB, M, C) -> (NB, M*C) shape cast). The output is an (NB, 1)
column per block: its last dim equals the array's, which keeps the block
legal under the vmap that ``core.search`` wraps around the call.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _lookup_kernel(codes_ref, adt_ref, out_ref):
    codes = codes_ref[...].astype(jnp.int32)        # (NB, M)
    adt = adt_ref[...]                              # (M, C)
    nb, m = codes.shape
    lanes = jax.lax.broadcasted_iota(jnp.int32, (nb, adt.shape[1]), 1)
    acc = jnp.zeros((nb, 1), jnp.float32)
    for j in range(m):
        hit = codes[:, j:j + 1] == lanes            # (NB, C)
        acc = acc + jnp.where(hit, adt[j:j + 1, :], 0.0).sum(
            axis=1, keepdims=True)
    out_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("n_block", "interpret"))
def pq_lookup(
    codes: jnp.ndarray,   # (N, M) uint8
    adt: jnp.ndarray,     # (M, C) float32
    n_block: int = 128,
    interpret: bool = True,
) -> jnp.ndarray:
    """Returns (N,) float32 PQ distances."""
    n, m = codes.shape
    _, c = adt.shape
    n_block = min(n_block, -(-n // 8) * 8)      # small N: one 8-row tile
    pad = (-n) % n_block
    if pad:
        codes = jnp.pad(codes, ((0, pad), (0, 0)))
    np_ = n + pad
    out = pl.pallas_call(
        _lookup_kernel,
        grid=(np_ // n_block,),
        in_specs=[
            pl.BlockSpec((n_block, m), lambda i: (i, 0)),
            pl.BlockSpec((m, c), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((n_block, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((np_, 1), jnp.float32),
        interpret=interpret,
    )(codes, adt)
    return out[:n, 0]
