"""Pallas kernels under a mesh of more than one device.

GSPMD cannot partition a Mosaic kernel ("Mosaic kernels cannot be
automatically partitioned"), so a jitted step that holds one does not lower
for a sharded mesh. Kernels whose work is independent along some dims (batch
rows, attention heads) are instead run once per device through
`jax.shard_map`, each device on its own block; what the specs leave
unmentioned is gathered by the compiler.
"""
import jax
from jax.sharding import PartitionSpec as P

__all__ = ["kernel_mesh", "dim_axes", "per_device", "BATCH_AXES", "P"]

BATCH_AXES = ("dp", "fsdp")


def kernel_mesh():
    """The global mesh a kernel has to be mapped over, or None: no mesh, a
    mesh of one device, or a trace that is already inside a shard_map."""
    from ..distributed.mesh import get_mesh

    mesh = get_mesh(create_default=False)
    if mesh is None or mesh.size == 1:
        return None
    if jax.sharding.get_abstract_mesh().manual_axes:
        return None
    return mesh


def dim_axes(mesh, size, axes):
    """Those of `axes` that are larger than one and together divide `size`:
    the PartitionSpec entry for a dim of that size (None when none do)."""
    kept, n = [], 1
    for a in axes:
        s = mesh.shape.get(a, 1)
        if s > 1 and size % (n * s) == 0:
            kept.append(a)
            n *= s
    return tuple(kept) or None


def per_device(fn, mesh, in_specs, out_specs):
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
