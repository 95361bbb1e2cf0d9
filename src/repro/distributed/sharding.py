"""Logical-axis -> mesh-axis sharding rules (GSPMD / pjit).

Parameters carry *logical* axis names (see models/layers.py); this module
resolves them against a mesh. The default rules implement:

  * tensor parallelism on "model": heads / kv / mlp / vocab / experts dims
  * FSDP (ZeRO-3-style) on "data": the "embed" dim of weight matrices is
    sharded over the data axis — parameters and optimizer state are fully
    sharded; XLA inserts the all-gathers before use and reduce-scatters of
    gradients (the classic MaxText fsdp mapping)
  * "pod" (multi-pod) extends the batch axis only: FSDP stays *within* a pod
    so param all-gathers ride the fast intra-pod ICI; each pod holds a full
    (sharded) replica, gradients all-reduce across pods.

Activations are constrained on the batch dim; everything else propagates.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_HINT_MESH: Optional[Mesh] = None


def abstract_mesh(axis_sizes, axis_names):
    """A device-free ``jax.sharding.AbstractMesh``: only the axis-name ->
    size mapping matters to the sharding rules."""
    from jax.sharding import AbstractMesh

    return AbstractMesh(tuple(axis_sizes), tuple(axis_names))


class activation_hints:
    """Context manager enabling activation sharding constraints during
    tracing/lowering. Model code calls ``hint(x, spec_fn)``; outside this
    context those calls are no-ops (single-device tests stay clean)."""

    def __init__(self, mesh: Mesh):
        self.mesh = mesh

    def __enter__(self):
        global _HINT_MESH
        self._old = _HINT_MESH
        _HINT_MESH = self.mesh
        return self.mesh

    def __exit__(self, *exc):
        global _HINT_MESH
        _HINT_MESH = self._old
        return False


def hint(x, spec_fn):
    """Apply with_sharding_constraint(spec_fn(mesh, x.shape)) if hints are
    enabled. spec_fn returns a PartitionSpec."""
    if _HINT_MESH is None:
        return x
    spec = spec_fn(_HINT_MESH, tuple(x.shape))
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(_HINT_MESH, spec)
    )


import functools


@functools.lru_cache(maxsize=None)
def _grad_sharded_fn(sh: NamedSharding):
    """identity with a sharding constraint on the COTANGENT (one cached
    custom_vjp per sharding — NamedSharding is hashable)."""

    @jax.custom_vjp
    def f(x):
        return x

    def fwd(x):
        return x, None

    def bwd(_, g):
        return (jax.lax.with_sharding_constraint(g, sh),)

    f.defvjp(fwd, bwd)
    return f


def param_hint(x, logical: Tuple[Optional[str], ...]):
    """Constrain a weight (inside a scanned block body) to its logical
    sharding — on the FORWARD value and, via custom_vjp, on its COTANGENT.
    Critical for training memory: without the cotangent constraint, the
    layer-scan backward accumulates per-layer weight gradients into a fully
    REPLICATED stacked buffer (268 GB/device for a 67B model); constraining
    the cotangent forces a reduce-scatter back to the FSDP/TP sharding every
    layer (see EXPERIMENTS.md §Perf)."""
    if _HINT_MESH is None:
        return x
    spec = logical_to_spec(logical, shape=tuple(x.shape), mesh=_HINT_MESH)
    sh = NamedSharding(_HINT_MESH, spec)
    x = jax.lax.with_sharding_constraint(x, sh)
    return _grad_sharded_fn(sh)(x)


def param_hints(p: dict, logical: dict) -> dict:
    """param_hint over a dict of weights (missing keys pass through)."""
    return {
        k: param_hint(v, logical[k]) if k in logical else v
        for k, v in p.items()
    }


def _bspec_axes(mesh: Mesh, dim: int):
    baxes = batch_axes(mesh)
    bsize = int(np.prod([mesh.shape[a] for a in baxes]))
    return baxes if dim % bsize == 0 else None


def qkv_spec(mesh: Mesh, shape) -> P:
    """Grouped-query activations (b, s, nkv, g, hd) / (b, s, h, hd):
    shard batch over (pod, data); shard kv heads over model when divisible,
    else shard the query-group dim (MQA: many groups per kv head)."""
    m = mesh.shape.get("model", 1)
    spec = [_bspec_axes(mesh, shape[0])] + [None] * (len(shape) - 1)
    if len(shape) >= 5:
        if shape[2] % m == 0:
            spec[2] = "model"
        elif shape[3] % m == 0:
            spec[3] = "model"
    elif len(shape) == 4:
        if shape[2] % m == 0:
            spec[2] = "model"
    return P(*spec)


def heads_concat_spec(mesh: Mesh, shape) -> P:
    """(b, s, h*hd) attention output before wo: shard the flattened head dim
    over model (row-parallel input)."""
    m = mesh.shape.get("model", 1)
    last = "model" if shape[-1] % m == 0 else None
    return P(_bspec_axes(mesh, shape[0]), *([None] * (len(shape) - 2)), last)


def residual_spec(mesh: Mesh, shape) -> P:
    """Residual stream (b, s, d): batch-sharded, d replicated."""
    return P(_bspec_axes(mesh, shape[0]), *([None] * (len(shape) - 1)))


def seq_parallel_spec(mesh: Mesh, shape) -> P:
    """Residual stream (b, s, d) with the SEQUENCE dim sharded over the
    model axis (Megatron-style sequence parallelism). Shrinks the per-layer
    saved activation stack (the layer-scan's backward residuals) by the
    model-axis size — the lever that fits 67B+ train cells in HBM."""
    m = mesh.shape.get("model", 1)
    seq = "model" if len(shape) >= 3 and shape[1] % m == 0 else None
    return P(_bspec_axes(mesh, shape[0]), seq, None)


def moe_buffer_spec(mesh: Mesh, shape) -> P:
    """(E*cap, d) expert dispatch buffer: shard slots over data (tokens come
    from data-sharded batch; scatter becomes the expert all-to-all)."""
    d = mesh.shape.get("data", 1)
    return P("data" if shape[0] % d == 0 else None, None)


def moe_hidden_spec(mesh: Mesh, shape) -> P:
    """(E, cap, f) expert hidden activations: capacity slots over data, the
    FFN hidden dim over model — keeps the expert einsum chain consistently
    sharded (without it GSPMD picks expert-dim shardings that force
    involuntary full rematerializations in the backward)."""
    d = mesh.shape.get("data", 1)
    m = mesh.shape.get("model", 1)
    cap = "data" if shape[1] % d == 0 else None
    hid = "model" if shape[2] % m == 0 else None
    return P(None, cap, hid)


def moe_out_spec(mesh: Mesh, shape) -> P:
    """(E, cap, d) expert outputs: capacity over data, d replicated."""
    d = mesh.shape.get("data", 1)
    return P(None, "data" if shape[1] % d == 0 else None, None)


def ssm_state_spec(mesh: Mesh, shape) -> P:
    """(b, s, di, ds) / (b, di, ds) scan tensors: batch + d_inner over model."""
    m = mesh.shape.get("model", 1)
    spec = [_bspec_axes(mesh, shape[0])] + [None] * (len(shape) - 1)
    di_axis = len(shape) - 2
    if shape[di_axis] % m == 0:
        spec[di_axis] = "model"
    return P(*spec)


DEFAULT_RULES: Dict[Optional[str], Optional[Tuple[str, ...]]] = {
    "embed": ("data",),        # FSDP
    "heads": ("model",),
    "kv": ("model",),
    "mlp": ("model",),
    "vocab": ("model",),
    "experts": ("model",),
    None: None,
}


def batch_axes(mesh: Mesh) -> Tuple[str, ...]:
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def logical_to_spec(
    logical: Tuple[Optional[str], ...],
    rules: Dict[Optional[str], Optional[Tuple[str, ...]]] = None,
    shape: Optional[Tuple[int, ...]] = None,
    mesh: Optional[Mesh] = None,
) -> P:
    """Resolve one logical spec tuple to a PartitionSpec. If ``shape``+``mesh``
    are given, axes that don't divide evenly fall back to replication (e.g.
    kv=1 MQA heads can't be sharded 16-ways)."""
    rules = rules or DEFAULT_RULES
    out = []
    used = set()
    for i, name in enumerate(logical):
        mapped = rules.get(name)
        if mapped is None:
            out.append(None)
            continue
        mapped = tuple(m for m in mapped if m not in used)
        if not mapped:
            out.append(None)
            continue
        if shape is not None and mesh is not None:
            size = int(np.prod([mesh.shape[m] for m in mapped]))
            if shape[i] % size != 0:
                out.append(None)
                continue
        used.update(mapped)
        out.append(mapped if len(mapped) > 1 else mapped[0])
    return P(*out)


def param_shardings(
    specs: Any, params_shape: Any, mesh: Mesh, rules=None
) -> Any:
    """specs: pytree of logical tuples; params_shape: matching pytree of
    ShapeDtypeStructs (or arrays). Returns NamedSharding pytree."""

    def resolve(spec, arr):
        return NamedSharding(
            mesh, logical_to_spec(spec, rules, tuple(arr.shape), mesh)
        )

    return jax.tree_util.tree_map(
        resolve, specs, params_shape,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x
        ),
    )


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Batch pytrees: leading dim over (pod, data)."""
    return NamedSharding(mesh, P(batch_axes(mesh)))


def batch_spec(mesh: Mesh) -> P:
    return P(batch_axes(mesh))


def cache_shardings(mesh: Mesh, cache, cfg, seq_shard: bool = False):
    """Decode-cache shardings. KV caches (n_layers, B, cap, Hkv, hd):
    batch over (pod,data) when divisible; kv heads over model when divisible;
    with ``seq_shard`` (long-context, tiny batch) the cap/sequence dim is
    sharded over data instead — sequence-parallel KV."""
    baxes = batch_axes(mesh)
    bsize = int(np.prod([mesh.shape[a] for a in baxes]))
    msize = mesh.shape["model"]

    def spec_for(path, arr):
        if arr.ndim == 0:
            return NamedSharding(mesh, P())
        name = path[-1] if path else ""
        shape = arr.shape
        if name in ("kv_k", "kv_v") and arr.ndim == 5:
            # (n_layers, B, cap, Hkv, hd). Preference order:
            #   batch  -> (pod, data)    when divisible
            #   heads  -> model          when divisible (GQA with enough kv)
            #   cap    -> model          otherwise (MQA / small-kv: shard the
            #            sequence dim — softmax collectives inserted by GSPMD)
            #   cap    -> data           when batch is unshardable (B=1 long
            #            context: sequence-parallel KV)
            b, cap, hkv = shape[1], shape[2], shape[3]
            pb = baxes if b % bsize == 0 else None
            ph = "model" if hkv % msize == 0 else None
            pseq = None
            if ph is None and cap % msize == 0:
                pseq = "model"
            if pb is None and cap % (mesh.shape["data"] * (msize if pseq == "model" else 1)) == 0:
                pseq = ("data", "model") if pseq == "model" else "data"
            return NamedSharding(mesh, P(None, pb, pseq, ph, None))
        if name == "enc_out" and arr.ndim == 3:
            b = shape[0]
            pb = baxes if b % bsize == 0 else None
            return NamedSharding(mesh, P(pb, None, None))
        if arr.ndim >= 2:  # ssm/conv states: (n, B, ...)
            b = shape[1]
            pb = baxes if b % bsize == 0 else None
            rest = [None] * (arr.ndim - 2)
            # shard the widest state dim over model if divisible
            widths = list(shape[2:])
            if widths:
                j = int(np.argmax(widths))
                if widths[j] % msize == 0:
                    rest[j] = "model"
            return NamedSharding(mesh, P(None, pb, *rest))
        return NamedSharding(mesh, P())

    return jax.tree_util.tree_map_with_path(
        lambda path, a: spec_for(tuple(getattr(p, "name", getattr(p, "idx", "")) for p in path), a),
        cache,
    )
