"""Global device-mesh state.

Replaces the reference's process-group world (paddle/fluid/distributed +
ProcessGroupNCCL) with a jax.sharding.Mesh. Axis vocabulary:

  dp    — data parallel (batch dim)
  fsdp  — sharded-parameter data parallel (ZeRO-3 ≈ fleet sharding stage 3)
  pp    — pipeline stages
  tp    — tensor (model) parallel, reference fleet "mp"
  sp    — sequence/context parallel (ring attention)
  ep    — expert parallel (MoE)

On TPU pods, axes laid out in this order ride ICI for the inner axes; DCN
only ever sees 'dp'/'pp' traffic — same layout discipline the scaling
playbook prescribes.
"""
import contextlib

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

__all__ = ["get_mesh", "set_mesh", "build_mesh", "mesh_axis_size", "PartitionSpec",
           "NamedSharding", "Mesh", "named_sharding", "current_axis_context",
           "in_shard_map", "axis_scope"]


_state = {"mesh": None, "axis_context": ()}


def build_mesh(dp=1, fsdp=1, pp=1, tp=1, sp=1, ep=1, devices=None):
    """Create a Mesh over `devices` with only the >1 axes materialized (axes
    of size 1 are kept too so PartitionSpecs stay valid)."""
    devices = devices if devices is not None else jax.devices()
    sizes = {"dp": dp, "fsdp": fsdp, "pp": pp, "tp": tp, "sp": sp, "ep": ep}
    total = int(np.prod(list(sizes.values())))
    if total != len(devices):
        if dp == 1 and len(devices) % total == 0:
            # dp left at its default of 1: absorb the remaining devices
            sizes["dp"] = len(devices) // total
        else:
            raise ValueError(
                f"mesh axes {sizes} multiply to {total} but {len(devices)} "
                "devices were given; make the product match (dp=1 may be "
                "left unset to absorb the remainder)")
    arr = np.asarray(devices).reshape([sizes[a] for a in ("dp", "fsdp", "pp", "tp", "sp", "ep")])
    mesh = Mesh(arr, ("dp", "fsdp", "pp", "tp", "sp", "ep"))
    set_mesh(mesh)
    return mesh


def set_mesh(mesh):
    _state["mesh"] = mesh


def get_mesh(create_default=True):
    if _state["mesh"] is None and create_default:
        build_mesh(dp=len(jax.devices()))
    return _state["mesh"]


def mesh_axis_size(axis):
    mesh = get_mesh()
    return mesh.shape.get(axis, 1)


def mesh_axis_sizes():
    """{axis: size} of the current global mesh (empty dict when none is
    built). The Graph Doctor's collective analyzer uses this to
    attribute each lowered collective's replica-group size to a mesh
    axis (per-axis payload accounting, T3-style)."""
    mesh = get_mesh(create_default=False)
    if mesh is None:
        return {}
    return dict(mesh.shape)


def named_sharding(*spec):
    return NamedSharding(get_mesh(), PartitionSpec(*spec))


@contextlib.contextmanager
def axis_scope(*axes):
    """Marks that we're inside a shard_map over `axes` (collectives use this
    to decide between lax collectives and no-ops)."""
    prev = _state["axis_context"]
    _state["axis_context"] = prev + tuple(axes)
    try:
        yield
    finally:
        _state["axis_context"] = prev


def current_axis_context():
    return _state["axis_context"]


def in_shard_map():
    return bool(_state["axis_context"])
