"""Pallas kernels under a device mesh: one shard_map per kernel entry.

XLA's SPMD partitioner cannot split a Mosaic custom call — lowering a
`pl.pallas_call` inside a GSPMD-partitioned program stops with "Mosaic
kernels cannot be automatically partitioned. Please wrap the call in a
shard_map". So inside the Trainer's mesh-partitioned step every Pallas
entry point (flash attention, blockwise CE, fused norm / RoPE) runs PER
SHARD: the entry asks `kernel_mesh()` for the ambient mesh and wraps its
local computation in `per_shard(...)` over the axes that shard its
batch, head or row dimension. The jnp fallbacks need none of this (XLA
partitions plain jnp itself), so the wrappers sit on the Pallas branch
only, and off a mesh — or already inside somebody's shard_map — the
entries run exactly as before.
"""
from __future__ import annotations

import math

import jax

from paddle_tpu.core.jax_compat import shard_map

__all__ = ["kernel_mesh", "batch_axes", "head_axis", "row_axes",
           "per_shard"]


def kernel_mesh():
    """The mesh a kernel entry should shard over: the one on jax's mesh
    context stack (the Trainer enters its mesh around step dispatch and
    lowering), when it has more than one device and the trace is not
    already inside a shard_map (whose manual axes cannot be mapped
    again). None otherwise. The paddle_tpu global ProcessMesh is NOT
    consulted: a mesh somebody set earlier says nothing about where the
    arrays of this trace live."""
    if jax.sharding.get_abstract_mesh().manual_axes:
        return None
    from jax._src.mesh import thread_resources
    mesh = thread_resources.env.physical_mesh
    return mesh if not mesh.empty and mesh.devices.size > 1 else None


def _live(mesh, names):
    return tuple(a for a in names
                 if a in mesh.axis_names and mesh.shape[a] > 1)


def _size(mesh, axes):
    return math.prod(mesh.shape[a] for a in axes)


def batch_axes(mesh, n):
    """PartitionSpec entry for a batch dim of size n: the axes
    parallel/plan.batch_spec shards batches over (dp, fsdp) when they
    divide it, else None (replicated)."""
    axes = _live(mesh, ("dp", "fsdp"))
    return axes if axes and n % _size(mesh, axes) == 0 else None


def head_axis(mesh, *head_counts):
    """'mp' when it divides every head count (the plan's column-parallel
    q/k/v projections shard heads over it), else None."""
    if _live(mesh, ("mp",)) and all(h % mesh.shape["mp"] == 0
                                    for h in head_counts):
        return "mp"
    return None


def row_axes(mesh, n):
    """PartitionSpec entry for n independent rows (tokens): spread over
    every live mesh axis — batch axes outermost, so a batch-major
    (B*S, D) activation that is replicated over 'mp' slices without
    moving — else over the batch axes alone, else None."""
    outer = _live(mesh, ("dp", "fsdp"))
    every = outer + tuple(a for a in _live(mesh, mesh.axis_names)
                          if a not in outer)
    for axes in (every, outer):
        if axes and n % _size(mesh, axes) == 0:
            return axes
    return None


def per_shard(fn, mesh, in_specs, out_specs):
    """`fn` over local shards; replication is the caller's statement
    (check_vma=False), as in the repo's other shard_maps."""
    return shard_map(fn, mesh=mesh, in_specs=in_specs,
                     out_specs=out_specs, check_vma=False)
