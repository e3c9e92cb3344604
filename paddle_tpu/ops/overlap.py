"""Chunked collective-matmul primitives — hide the tensor-parallel wire
behind the MXU (T3-style compute/collective decomposition, arxiv
2401.16677).

A bulk tensor-parallel matmul serializes: the psum/all-gather cannot
start until the whole dot finishes, and nothing computes while the wire
drains — exactly what the Schedule Doctor's COLL-SERIALIZED lint
convicts.  These primitives split the matmul's FREE (non-contracted)
dimension into ``n_chunks`` tiles and ring-step each tile's transfer
(``lax.ppermute``) while the NEXT tile's matmul runs: chunk *t*'s
permutes and chunk *t+1*'s dot share no data edge, so the two-stream
schedule (and the real chip) overlap them.

Bit-identity contract (the repo's twin discipline): every element's
reduction keeps the identical participant order as the bulk collective,
so the chunked result is **bit-identical** to the bulk twin, per dtype.
The facts this leans on, pinned by tests/test_overlap.py:

* XLA CPU's ``psum``/``psum_scatter`` reduce in ascending device-index
  order; an explicit ring that reorders received pieces by source
  index and left-folds ascending reproduces it bit-exactly.
* sub-f32 floats (bf16/f16) accumulate in f32 with ONE final cast —
  per-step narrow adds do NOT match the bulk collective.  For the
  MATMUL reductions XLA goes further: it fuses ``psum(x @ w)`` so the
  all-reduce consumes the dot's UNROUNDED f32 partials (no bf16
  rounding between dot and reduce) — so the chunked paths compute
  their partial dots with ``preferred_element_type=f32``, exchange f32
  tiles, and cast once after the fold.  That doubles the sub-f32 wire
  payload versus a narrow-wire collective: the price of exactness.
* a column- or row-tiled matmul is bit-identical to the full matmul
  (the K-contraction order per output element is tile-independent).

Wire accounting: the divisible-free-dim path decomposes the all-reduce
into reduce-scatter + all-gather rings — per-device wire is exactly the
bulk psum's ring cost, 2(p-1)/p x payload, now in n_chunks x p
schedulable pieces.  The indivisible fallback exchanges full partials
((p-1) x payload): correct, but wire-heavier — keep free dims divisible
by the axis size where throughput matters.

Public wrappers (``overlap_*``) take GLOBAL arrays and wrap
``jax.shard_map`` over one named axis; the
``chunked_*`` bodies are usable directly inside an existing shard_map
(or a ``make_jaxpr(axis_env=...)`` capture).  ``impl="bulk"`` keeps the
jnp bulk reference as the A/B path behind a flag.
"""
import jax
import jax.numpy as jnp

__all__ = [
    "overlap_matmul_all_reduce", "overlap_matmul_reduce_scatter",
    "overlap_all_gather_matmul", "chunked_matmul_all_reduce",
    "chunked_matmul_reduce_scatter", "chunked_all_gather_matmul",
    "chunked_all_reduce",
]


def _axis_size(axis):
    """Participant count of a named axis (concrete at trace time)."""
    return int(jax.lax.psum(1, axis))


def _acc_dtype(dtype):
    """Accumulation dtype matching the bulk collective: sub-f32 floats
    widen to f32 (one final cast back), everything else is exact."""
    dtype = jnp.dtype(dtype)
    if jnp.issubdtype(dtype, jnp.floating) and dtype.itemsize < 4:
        return jnp.float32
    return dtype


def _tile_bounds(n, n_chunks):
    """n_chunks contiguous tile boundaries over ``n`` columns; ragged
    tails allowed (last tiles absorb the remainder), clamped so every
    tile is non-empty."""
    n_chunks = max(1, min(int(n_chunks), int(n)))
    return [(i * n) // n_chunks for i in range(n_chunks + 1)]


def _shift_perm(p, s):
    """ppermute pairs sending each device's value s hops up the ring
    (device d receives from (d - s) % p)."""
    return [(i, (i + s) % p) for i in range(p)]


def _ring_pieces(x, axis, p):
    """All participants' values of ``x``, collected by p-1 single-hop
    ring rotations. pieces[s] arrived from device (idx - s) % p."""
    perm = _shift_perm(p, 1)
    pieces = [x]
    buf = x
    for _ in range(p - 1):
        buf = jax.lax.ppermute(buf, axis, perm)
        pieces.append(buf)
    return pieces


def _by_source(pieces, axis):
    """Reorder ring pieces (pieces[s] from device (idx - s) % p) into
    ascending SOURCE-device order — the participant order the bulk
    collective reduces in."""
    p = len(pieces)
    stacked = jnp.stack(pieces)
    order = (jax.lax.axis_index(axis) - jnp.arange(p)) % p
    return jnp.take(stacked, order, axis=0)


def _ascending_sum(by_src, out_dtype):
    """Left-fold ``by_src`` ([p, ...], source-ascending) exactly the way
    the bulk collective does: f32 accumulation for sub-f32 floats, one
    final cast."""
    acc_dt = _acc_dtype(out_dtype)
    acc = by_src[0].astype(acc_dt)
    for j in range(1, by_src.shape[0]):
        acc = acc + by_src[j].astype(acc_dt)
    return acc.astype(out_dtype)


def _rs_tiles(x, w, axis, p, n_chunks):
    """Chunked matmul + reduce-scatter over the free (last) dim.

    The free dim N is first split into the p destination blocks the
    bulk ``psum_scatter(..., tiled=True)`` hands out (device j keeps
    columns [j*N/p, (j+1)*N/p)), then each block into n_chunks
    sub-tiles.  Per sub-tile every device computes its partial for ALL
    p destinations (one dot over the p strided column groups — tile
    t+1's dot overlaps tile t's exchange), exchanges partials so each
    destination receives every source's contribution, and left-folds
    them in ascending source order.  Returns the list of this device's
    reduced sub-tiles ([..., wt] each; concatenated they are its
    destination block)."""
    nfree = w.shape[-1]
    nb = nfree // p
    bounds = _tile_bounds(nb, n_chunks)
    idx = jax.lax.axis_index(axis)
    out_dtype = jnp.result_type(x.dtype, w.dtype)
    acc_dt = _acc_dtype(out_dtype)
    out_tiles = []
    for t in range(len(bounds) - 1):
        t0, t1 = bounds[t], bounds[t + 1]
        wcols = jnp.concatenate(
            [jax.lax.slice_in_dim(w, j * nb + t0, j * nb + t1, axis=-1)
             for j in range(p)], axis=-1)
        # partials stay in the accumulation dtype end-to-end: the bulk
        # twin's fused dot+psum reduces UNROUNDED f32 partials
        y = jnp.dot(x, wcols, preferred_element_type=acc_dt)
        blocks = jnp.stack(jnp.split(y, p, axis=-1))    # [p, ..., wt]
        # step s: send my partial for destination (idx+s), receive
        # source (idx-s)'s partial for me
        recvs = [jax.lax.dynamic_index_in_dim(blocks, idx, 0,
                                              keepdims=False)]
        for s in range(1, p):
            send = jax.lax.dynamic_index_in_dim(
                blocks, (idx + s) % p, 0, keepdims=False)
            recvs.append(jax.lax.ppermute(send, axis, _shift_perm(p, s)))
        out_tiles.append(_ascending_sum(_by_source(recvs, axis),
                                        out_dtype))
    return out_tiles


# ----------------------------------------------------------- body level


def chunked_all_reduce(x, axis, impl="ring"):
    """``psum(x, axis)`` as an explicit full-exchange ring with the
    ascending source-order fold — the per-bucket building block of the
    Trainer's dp grad reduction (each bucket's ring steps overlap the
    optimizer update consuming the previous bucket).  Bit-identical to
    the bulk psum; wire is (p-1) x payload (== the psum ring's
    2(p-1)/p at p=2, heavier above)."""
    p = _axis_size(axis)
    if p == 1:
        return x
    if impl == "bulk":
        return jax.lax.psum(x, axis)
    return _ascending_sum(_by_source(_ring_pieces(x, axis, p), axis),
                          x.dtype)


def chunked_matmul_all_reduce(x, w, axis, n_chunks=4, impl="ring"):
    """``psum(x @ w, axis)`` with the wire decomposed into per-chunk
    ring steps that overlap the neighbouring chunks' matmuls.  Call
    inside a shard_map over ``axis``: x [..., K_local] (contraction dim
    sharded), w [K_local, N]; the result is the full [..., N],
    replicated over ``axis``, bit-identical to the bulk psum."""
    y_dtype = jnp.result_type(x.dtype, w.dtype)
    p = _axis_size(axis)
    if impl == "bulk":
        y = x @ w
        return jax.lax.psum(y, axis) if p > 1 else y
    if p == 1:
        return x @ w                      # 1-participant: zero wire
    nfree = w.shape[-1]
    if nfree % p == 0:
        # reduce-scatter + all-gather rings: bulk-psum ring wire
        # (2(p-1)/p x payload) in n_chunks x p schedulable pieces.
        # All-gather each reduced sub-tile as soon as its fold lands,
        # then reassemble the bulk column order (block j's sub-tile t
        # sits at columns [j*N/p + t0, j*N/p + t1)).
        tiles = _rs_tiles(x, w, axis, p, n_chunks)
        cols = [[] for _ in range(p)]
        for red in tiles:
            by_src = _by_source(_ring_pieces(red, axis, p), axis)
            for j in range(p):
                cols[j].append(by_src[j])
        return jnp.concatenate([piece for j in range(p)
                                for piece in cols[j]], axis=-1)
    # indivisible free dim: ONE bulk dot (XLA CPU's gemm remainder
    # micro-kernel makes column-tiled dots of odd widths drift by a
    # ulp, so tiling the dot here would break the twin pin), then
    # exchange full per-chunk partial SLICES — the transfers still
    # decompose and overlap other compute, at (p-1) x payload wire
    # (heavier than the ring pair; keep free dims divisible by the
    # axis size where throughput matters)
    y = jnp.dot(x, w, preferred_element_type=_acc_dtype(y_dtype))
    bounds = _tile_bounds(nfree, n_chunks)
    tiles = []
    for t in range(len(bounds) - 1):
        yt = jax.lax.slice_in_dim(y, bounds[t], bounds[t + 1], axis=-1)
        tiles.append(_ascending_sum(
            _by_source(_ring_pieces(yt, axis, p), axis), y_dtype))
    return jnp.concatenate(tiles, axis=-1)


def chunked_matmul_reduce_scatter(x, w, axis, n_chunks=4, impl="ring"):
    """``psum_scatter(x @ w, axis, scatter_dimension=-1, tiled=True)``
    with per-chunk ring exchange.  Requires the free dim divisible by
    the axis size (as the tiled bulk twin does); returns this device's
    [..., N/p] destination block, bit-identical to the bulk twin."""
    p = _axis_size(axis)
    if p == 1:
        return x @ w
    nfree = w.shape[-1]
    if nfree % p:
        raise ValueError(
            f"reduce_scatter free dim {nfree} not divisible by "
            f"axis '{axis}' size {p}")
    if impl == "bulk":
        y = x @ w
        return jax.lax.psum_scatter(y, axis, scatter_dimension=y.ndim - 1,
                                    tiled=True)
    return jnp.concatenate(_rs_tiles(x, w, axis, p, n_chunks), axis=-1)


def chunked_all_gather_matmul(x, w, axis, n_chunks=4, impl="ring"):
    """``all_gather(x, axis, axis=0, tiled=True) @ w`` with the gather
    decomposed into ring hops whose transfers overlap the previous
    piece's matmul.  x [M_local, ..., K] (dim 0 sharded), w local;
    returns [p*M_local, ..., N].  Row tiles beyond the p ring pieces
    (n_chunks > p) ride separate rings so transfer granularity keeps
    shrinking."""
    p = _axis_size(axis)
    if impl == "bulk":
        xg = (jax.lax.all_gather(x, axis, axis=0, tiled=True)
              if p > 1 else x)
        return xg @ w
    if p == 1:
        return x @ w
    m = x.shape[0]
    q = max(1, -(-int(n_chunks) // p))          # row tiles per ring piece
    bounds = _tile_bounds(m, q)
    rows = [[] for _ in range(p)]               # [source][tile] outputs
    for t in range(len(bounds) - 1):
        xt = jax.lax.slice_in_dim(x, bounds[t], bounds[t + 1], axis=0)
        outs = [piece @ w for piece in _ring_pieces(xt, axis, p)]
        by_src = _by_source(outs, axis)
        for j in range(p):
            rows[j].append(by_src[j])
    return jnp.concatenate([piece for j in range(p)
                            for piece in rows[j]], axis=0)


# -------------------------------------------------------- global level


def _resolve_mesh(mesh):
    if mesh is not None:
        return mesh
    from ..distributed.mesh import get_mesh
    return get_mesh()


def _wrap(body, mesh, axis, in_specs, out_specs):
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                            out_specs=out_specs, axis_names={axis},
                            check_vma=False)


def overlap_matmul_all_reduce(x, w, axis="tp", n_chunks=4, mesh=None,
                              impl="ring"):
    """Row-parallel matmul + all-reduce over ``axis`` (the tp GPT
    proj/fc2 sites): x [..., K] with K sharded over ``axis``, w [K, N]
    row-sharded; returns the full [..., N] replicated over ``axis``,
    bit-identical to GSPMD's dot+psum.  ``impl="bulk"`` is the
    serialized A/B twin."""
    from jax.sharding import PartitionSpec as P
    mesh = _resolve_mesh(mesh)
    if int(mesh.shape.get(axis, 1)) == 1:
        return x @ w
    in_specs = (P(*([None] * (x.ndim - 1) + [axis])), P(axis, None))
    out_specs = P(*([None] * x.ndim))
    return _wrap(
        lambda xs, ws: chunked_matmul_all_reduce(
            xs, ws, axis, n_chunks=n_chunks, impl=impl),
        mesh, axis, in_specs, out_specs)(x, w)


def overlap_matmul_reduce_scatter(x, w, axis="tp", n_chunks=4, mesh=None,
                                  impl="ring"):
    """Row-parallel matmul + reduce-scatter over ``axis``: like the
    all-reduce twin but each device keeps only its [..., N/p] block of
    the free dim (sequence-parallel boundaries)."""
    from jax.sharding import PartitionSpec as P
    mesh = _resolve_mesh(mesh)
    if int(mesh.shape.get(axis, 1)) == 1:
        return x @ w
    in_specs = (P(*([None] * (x.ndim - 1) + [axis])), P(axis, None))
    out_specs = P(*([None] * (x.ndim - 1) + [axis]))
    return _wrap(
        lambda xs, ws: chunked_matmul_reduce_scatter(
            xs, ws, axis, n_chunks=n_chunks, impl=impl),
        mesh, axis, in_specs, out_specs)(x, w)


def overlap_all_gather_matmul(x, w, axis="tp", n_chunks=4, mesh=None,
                              impl="ring"):
    """All-gather x along dim 0 over ``axis`` then matmul with the
    column-sharded w: x [M, ..., K] dim-0 sharded, w [K, N] with N
    sharded; returns [M_global, ..., N/p] per device."""
    from jax.sharding import PartitionSpec as P
    mesh = _resolve_mesh(mesh)
    if int(mesh.shape.get(axis, 1)) == 1:
        return x @ w
    in_specs = (P(axis, *([None] * (x.ndim - 1))), P(None, axis))
    out_specs = P(*([None] * (x.ndim - 1) + [axis]))
    return _wrap(
        lambda xs, ws: chunked_all_gather_matmul(
            xs, ws, axis, n_chunks=n_chunks, impl=impl),
        mesh, axis, in_specs, out_specs)(x, w)
