"""Ulysses-style all-to-all sequence parallelism.

The second sequence-parallel scheme SURVEY §2 item 12 calls for (ring
attention being the first): activations arrive sharded on the SEQUENCE axis;
an all_to_all over the 'sp' mesh axis re-shards them on the HEAD axis so each
device runs ordinary (full-sequence) attention for H/sp heads, and a reverse
all_to_all restores sequence sharding. Two collectives per attention instead
of sp ppermute hops — cheaper than the ring when H >= sp and the sequence
fits per-device HBM after the head split.

Reference analogue: fleet sep (sequence-parallel) alltoall path over NCCL;
here both all_to_alls ride the ICI via XLA's all_to_all.

Layout: [batch, seq_local, heads, head_dim] in and out (inside shard_map).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["ulysses_attention_local", "ulysses_attention"]


def _seq_to_heads(x, axis_name):
    """[B, L/sp, H, D] -> [B, L, H/sp, D] via all_to_all over 'sp'."""
    # split the head axis into sp groups, exchange so each device keeps one
    # group but gathers every sequence shard
    return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)


def _heads_to_seq(x, axis_name):
    """[B, L, H/sp, D] -> [B, L/sp, H, D] — inverse all_to_all."""
    return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)


def ulysses_attention_local(q, k, v, axis_name="sp", causal=True, scale=None,
                            attention_fn=None):
    """Runs INSIDE shard_map. q,k,v: [B, L_local, H, D] (sequence-sharded).

    attention_fn(q, k, v, causal, scale) runs the per-device full-sequence
    attention; defaults to the Pallas flash kernel path (GQA-capable since
    the head split divides Hq and Hkv alike).
    """
    scale = scale if scale is not None else 1.0 / np.sqrt(q.shape[-1])
    qg = _seq_to_heads(q, axis_name)      # [B, L, H/sp, D]
    kg = _seq_to_heads(k, axis_name)
    vg = _seq_to_heads(v, axis_name)
    if attention_fn is None:
        from .attention import _flash
        out = _flash(qg, kg, vg, causal, scale)
    else:
        out = attention_fn(qg, kg, vg, causal, scale)
    return _heads_to_seq(out, axis_name)  # [B, L_local, H, D]


def ulysses_attention(q, k, v, mesh=None, axis_name="sp", causal=True,
                      batch_axes=("dp", "fsdp"), scale=None):
    """shard_map wrapper: q,k,v GLOBAL [B, L, H, D], sequence dim split over
    `axis_name`. Requires H % sp == 0."""
    from jax.sharding import PartitionSpec as P

    from ..distributed.mesh import get_mesh

    mesh = mesh or get_mesh()
    sp = mesh.shape[axis_name]
    for name, t in (("query", q), ("key", k), ("value", v)):
        if t.shape[2] % sp != 0:
            raise ValueError(f"{name} heads ({t.shape[2]}) must be divisible "
                             f"by the '{axis_name}' axis size ({sp}) for "
                             "Ulysses SP; use ring_attention otherwise")
    spec = P(batch_axes, axis_name, None, None)
    fn = functools.partial(ulysses_attention_local, axis_name=axis_name,
                           causal=causal, scale=scale)
    # check_vma=False: the vma checker can't see through pallas_call's
    # out_shape, so it would force the flash kernel onto the fallback path
    return jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                            out_specs=spec, check_vma=False)(q, k, v)
