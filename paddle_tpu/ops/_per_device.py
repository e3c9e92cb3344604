"""Pallas kernels under a mesh of more than one device.

GSPMD cannot partition a Mosaic kernel ("Mosaic kernels cannot be
automatically partitioned"), so a jitted step that holds one does not lower
for a sharded mesh. Kernels whose work is independent along some dims (batch
rows, attention heads) are instead run once per device through
`jax.shard_map`, each device on its own block; what the specs leave
unmentioned is gathered by the compiler.
"""
import jax

__all__ = ["kernel_mesh", "dim_axes", "BATCH_AXES"]

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
    """Those of `axes` that are larger than one and together divide `size`,
    as a tuple: the PartitionSpec entry for a dim of that size (None when
    none do). The rule is `sharding_utils.feasible_spec`'s."""
    from ..distributed.sharding_utils import feasible_spec

    kept = feasible_spec((size,), (tuple(axes),), mesh)[0]
    return (kept,) if isinstance(kept, str) else kept
