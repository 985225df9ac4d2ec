"""Global device mesh — the TPU-native backbone of the distributed stack.

Reference analogue: ``HybridCommunicateGroup``'s N-D rank mesh in axis order
[dp, pp, sharding, sep, mp] (``python/paddle/distributed/fleet/base/topology.py``,
SURVEY.md §2.3) — but instead of a rank-coordinate bookkeeping object backed by
NCCL comm rings, the mesh IS a ``jax.sharding.Mesh``: every parallelism axis is
a named mesh axis, shardings are ``NamedSharding``/``PartitionSpec`` over those
axes, and XLA emits the collectives over ICI/DCN (SURVEY.md §7.0).

Axis order convention matches the reference: mp innermost (fastest links —
on a TPU torus, the last mesh axis maps to the tightest ICI ring), dp
outermost.
"""
from __future__ import annotations

import math

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

# canonical hybrid axis order (reference: fixed order [dp, pp, sharding, sep, mp])
HYBRID_AXES = ("dp", "pp", "sharding", "sep", "mp")

_global_mesh: Mesh | None = None


def _slice_major(devices):
    """Order devices slice-major for multi-slice (DCN-connected) systems.

    Reference analogue: multi-node Fleet keeps NCCL rings node-local and
    crosses nodes only on the outer (dp) axis. On TPU the slow links are
    DCN between slices; jax exposes slice membership as
    ``device.slice_index``. Returns ``(ordered_devices, n_slices)`` with
    each slice's devices contiguous, so a row-major reshape puts slice
    boundaries on the OUTERMOST mesh axis and every inner axis (mp/sep/
    sharding/pp collectives) rides ICI only.
    """
    by_slice: dict[int, list] = {}
    for d in devices:
        by_slice.setdefault(getattr(d, "slice_index", 0) or 0, []).append(d)
    groups = [by_slice[k] for k in sorted(by_slice)]
    if len(groups) > 1 and len({len(g) for g in groups}) != 1:
        raise ValueError(
            f"uneven DCN slices: {[len(g) for g in groups]} devices per "
            "slice — a hybrid mesh needs equal-size slices")
    return [d for g in groups for d in g], len(groups)


def init_mesh(degrees: dict[str, int] | None = None, devices=None) -> Mesh:
    """Build (and install) the global mesh from parallelism degrees.

    ``degrees`` maps axis name -> size; unspecified hybrid axes get 1. A
    remainder of devices is folded into dp. With no args: 1-D dp mesh over
    all devices. On multi-slice systems devices are ordered slice-major
    and the dp degree must be a multiple of the slice count, so only the
    outermost (DCN) axis crosses slices.
    """
    global _global_mesh
    devices = list(devices) if devices is not None else jax.devices()
    n = len(devices)
    degrees = dict(degrees or {})
    sizes = [int(degrees.get(ax, 1)) for ax in HYBRID_AXES]
    prod = int(np.prod([s for s in sizes if s > 0]))
    if n % max(prod, 1) != 0:
        raise ValueError(f"device count {n} not divisible by degree product {prod} "
                         f"({dict(zip(HYBRID_AXES, sizes))})")
    # fold leftover devices into dp (paddle: dp_degree inferred from world size)
    if degrees.get("dp") in (None, -1):
        sizes[0] = n // (prod // max(sizes[0], 1)) if sizes[0] > 0 else n // prod
    prod = int(np.prod(sizes))
    if prod != n:
        raise ValueError(f"degrees {dict(zip(HYBRID_AXES, sizes))} use {prod} "
                         f"devices, but {n} are available")
    devices, n_slices = _slice_major(devices)
    if n_slices > 1 and sizes[0] % n_slices != 0:
        raise ValueError(
            f"multi-slice mesh: dp degree {sizes[0]} must be a multiple of "
            f"the DCN slice count {n_slices} — inner axes (pp/sharding/sep/"
            "mp) must not straddle slices (their collectives would ride "
            "DCN instead of ICI)")
    arr = np.array(devices).reshape(sizes)
    _global_mesh = Mesh(arr, HYBRID_AXES)
    return _global_mesh


def set_mesh(mesh: Mesh):
    global _global_mesh
    _global_mesh = mesh


def get_mesh() -> Mesh:
    if _global_mesh is None:
        init_mesh()
    return _global_mesh


def has_mesh() -> bool:
    return _global_mesh is not None


def reset_mesh():
    global _global_mesh
    _global_mesh = None


def axis_size(name: str) -> int:
    m = get_mesh()
    return int(m.shape[name]) if name in m.shape else 1


def axis_index(name: str):
    """Trace-time index along a mesh axis (inside shard_map)."""
    return jax.lax.axis_index(name)


def sharding(*spec) -> NamedSharding:
    """NamedSharding over the global mesh for a PartitionSpec."""
    return NamedSharding(get_mesh(), PartitionSpec(*spec))


def replicated() -> NamedSharding:
    return NamedSharding(get_mesh(), PartitionSpec())


def batch_spec(ndim: int = 3):
    """Canonical activation PartitionSpec for a [B, T, ...] tensor on the
    hybrid mesh: batch over the data axes (dp + sharding — ZeRO shards the
    batch over both), sequence over sep, feature dims replicated (mp splits
    happen inside attention/MLP via weight shardings). None when no
    multi-device mesh is active."""
    if not has_mesh():
        return None
    m = get_mesh()
    if len(m.devices.flat) <= 1:
        return None
    data_axes = tuple(ax for ax in ("dp", "sharding")
                      if int(m.shape.get(ax, 1)) > 1)
    sep = "sep" if int(m.shape.get("sep", 1)) > 1 else None
    if not data_axes and sep is None:
        return None
    parts = [data_axes if data_axes else None]
    if ndim >= 2:
        parts.append(sep)
    parts += [None] * (ndim - len(parts))
    return PartitionSpec(*parts)


def shard_attention_kernel(kernel, q, k, v):
    """Run ``kernel(q, k, v)`` ([b, s, heads, d] in and out) on a traced
    call under a multi-device mesh. GSPMD cannot partition a Mosaic kernel
    ("wrap the call in a shard_map"), so the call becomes a fully-manual
    ``shard_map``: batch over the data axes, heads over 'mp' — the layout
    the column-parallel q/k/v projections already produce. A dim an axis
    does not divide stays whole there (each device of that axis computes
    all of it); the sequence stays whole (context parallelism over 'sep'
    is ring attention's job). Eager or mesh-less calls run ``kernel``
    as is."""
    if not (has_mesh() and isinstance(q, jax.core.Tracer)):
        return kernel(q, k, v)
    m = get_mesh()
    if len(m.devices.flat) <= 1:
        return kernel(q, k, v)

    data = tuple(ax for ax in ("dp", "sharding")
                 if int(m.shape.get(ax, 1)) > 1)
    if q.shape[0] % math.prod(int(m.shape[ax]) for ax in data):
        data = ()
    n_mp = int(m.shape.get("mp", 1))
    mp = "mp" if n_mp > 1 and not (q.shape[2] % n_mp
                                   or k.shape[2] % n_mp) else None
    spec = PartitionSpec(data or None, None, mp, None)
    return jax.shard_map(kernel, mesh=m, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


def strip_axis(spec: PartitionSpec, axis: str) -> PartitionSpec:
    """Remove ``axis`` from every dim entry of a PartitionSpec."""
    parts = []
    for e in tuple(spec):
        if e == axis:
            parts.append(None)
        elif isinstance(e, tuple):
            kept = tuple(a for a in e if a != axis)
            parts.append(kept if len(kept) > 1 else (kept[0] if kept else None))
        else:
            parts.append(e)
    return PartitionSpec(*parts)


def unshard_for_compute(arrs, specs, fsdp_axis="sharding"):
    """ZeRO all-gather at step entry (reference semantics:
    ``GroupShardedStage3`` gathers each param before forward and
    reduce-scatters its grad after backward — SURVEY.md §2.3 sharding).

    Constrains every array to its PartitionSpec with ``fsdp_axis``
    stripped: XLA materializes that as an all-gather over the fsdp axis,
    and the constraint's transpose reduce-scatters the cotangent back to
    the sharded layout — grads land already fsdp-sharded for the (also
    sharded) optimizer update. Being explicit here keeps GSPMD from ever
    propagating the storage-layout 'sharding' split into activations
    (the "Involuntary full rematerialization" failure)."""
    if not has_mesh() or axis_size(fsdp_axis) <= 1:
        return list(arrs)
    out = []
    for a, s in zip(arrs, specs):
        stripped = strip_axis(s, fsdp_axis)
        out.append(jax.lax.with_sharding_constraint(a, sharding(*stripped)))
    return out
