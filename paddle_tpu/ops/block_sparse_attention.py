"""Block-sparse (blocked-CSR) attention Pallas kernel.

Reference: python/paddle/nn/functional/sparse_attention.py backed by
paddle/fluid/operators/sparse_attention_op.cu (per-row CSR softmax(QK^T)V
on CUDA).  TPU-native design: sparsity at MXU-tile granularity — each
q-block row carries a padded list of nonzero kv-block indices, and the
flash-style online-softmax inner loop visits ONLY those blocks via
dynamic VMEM slices, so compute and VMEM traffic scale with nnz blocks
instead of L^2.  The blocked-CSR indices ride in as scalar-prefetch
operands (same pattern as ops/paged_attention.py).

Layout matches the reference op: q/k/v are [B, H, L, D].

  block_cols   : [G, nq, max_nnz] int32, kv-block ids per q-block row
                 (right-padded; pad value arbitrary in [0, nk))
  block_counts : [G, nq]          int32, valid entries per row
  G = B*H for per-(batch,head) patterns, or 1 for a shared pattern.

Backward runs a dense-masked recompute in jnp (the sparsity mask is
rebuilt from the same blocked CSR), so training through the kernel is
exact; a block-sparse backward kernel can replace it without API change.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["block_sparse_attention", "block_mask_from_csr",
           "csr_to_block_layout", "dense_mask_sparse_attention"]

_NEG = -1e30


def _bs_fwd_kernel(cols_ref, cnt_ref, q_ref, k_ref, v_ref, o_ref, *,
                   block_size, max_nnz, scale, gs_b, gs_h):
    from jax.experimental import pallas as pl

    b, h, i = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    g = b * gs_b + h * gs_h
    bs = block_size
    q = q_ref[0, 0].astype(jnp.float32) * scale          # [bq, D]
    bq, D = q.shape
    m0 = jnp.full((bq, 1), _NEG, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    a0 = jnp.zeros((bq, D), jnp.float32)
    n_valid = cnt_ref[g, i]

    def body(j, carry):
        m, l, acc = carry
        c = cols_ref[g, i, j]
        kb = k_ref[0, 0, pl.ds(c * bs, bs), :].astype(jnp.float32)
        vb = v_ref[0, 0, pl.ds(c * bs, bs), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        valid = j < n_valid
        s = jnp.where(valid, s, _NEG)                    # padded slot
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        # explicit zero for padded slots: when no valid block has been
        # seen yet, s == m_new == _NEG and exp(s - m_new) would be 1
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + jax.lax.dot_general(
            p, vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m, l, acc = jax.lax.fori_loop(0, max_nnz, body, (m0, l0, a0))
    # fully-masked row (count 0): emit zeros rather than NaN
    o_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _bs_fwd(q, k, v, block_cols, block_counts, block_size, scale,
            interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    B, H, L, D = q.shape
    bs = block_size
    G, nq, max_nnz = block_cols.shape
    assert L % bs == 0 and nq == L // bs, (L, bs, nq)
    gs_b = H if G == B * H else 0
    gs_h = 1 if G == B * H else 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,          # block_cols, block_counts
        grid=(B, H, nq),
        in_specs=[
            pl.BlockSpec((1, 1, bs, D), lambda b, h, i, *_: (b, h, i, 0)),
            pl.BlockSpec((1, 1, L, D), lambda b, h, i, *_: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, L, D), lambda b, h, i, *_: (b, h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bs, D),
                               lambda b, h, i, *_: (b, h, i, 0)),
    )
    return pl.pallas_call(
        functools.partial(_bs_fwd_kernel, block_size=bs, max_nnz=max_nnz,
                          scale=scale, gs_b=gs_b, gs_h=gs_h),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, L, D), q.dtype),
        interpret=interpret,
        name="block_sparse_attention_fwd",
    )(block_cols.astype(jnp.int32), block_counts.astype(jnp.int32),
      q, k, v)


def block_mask_from_csr(block_cols, block_counts, nk):
    """[G, nq, nk] bool block mask from the padded blocked-CSR arrays."""
    G, nq, max_nnz = block_cols.shape
    valid = (jnp.arange(max_nnz)[None, None, :]
             < block_counts[:, :, None])                      # [G,nq,nnz]
    onehot = jax.nn.one_hot(block_cols, nk, dtype=jnp.bool_)  # [G,nq,nnz,nk]
    return jnp.any(onehot & valid[..., None], axis=2)


def _dense_recompute(q, k, v, block_cols, block_counts, block_size, scale):
    """Dense-masked attention with the SAME sparsity (golden path + the
    backward rule's recompute)."""
    B, H, L, D = q.shape
    nk = L // block_size
    bm = block_mask_from_csr(block_cols, block_counts, nk)    # [G,nq,nk]
    em = jnp.repeat(jnp.repeat(bm, block_size, axis=1),
                    block_size, axis=2)                       # [G, L, L]
    em = em.reshape((B, H, L, L)) if bm.shape[0] == B * H \
        else em[:, None, :, :]                                # broadcast H
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    s = jnp.where(em, s, _NEG)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    p = p / jnp.maximum(l, 1e-30)
    # fully-masked rows: all-equal logits would give uniform weights
    p = jnp.where(em, p, 0.0)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _bs_attention(q, k, v, block_cols, block_counts, block_size, scale,
                  interpret):
    return _bs_fwd(q, k, v, block_cols, block_counts, block_size, scale,
                   interpret)


def _bs_attention_fwd(q, k, v, block_cols, block_counts, block_size, scale,
                      interpret):
    out = _bs_fwd(q, k, v, block_cols, block_counts, block_size, scale,
                  interpret)
    return out, (q, k, v, block_cols, block_counts)


def _bs_attention_bwd(block_size, scale, interpret, res, g):
    q, k, v, block_cols, block_counts = res
    grads = jax.vjp(
        lambda qq, kk, vv: _dense_recompute(qq, kk, vv, block_cols,
                                            block_counts, block_size,
                                            scale),
        q, k, v)[1](g)
    return grads + (None, None)


_bs_attention.defvjp(_bs_attention_fwd, _bs_attention_bwd)


def block_sparse_attention(q, k, v, block_cols, block_counts, block_size,
                           scale=None, interpret=None):
    """softmax(QK^T / sqrt(d)) V restricted to the given kv blocks per
    q-block row.  q/k/v: [B, H, L, D]; see module docstring for the
    blocked-CSR layout.  Differentiable (dense-masked recompute bwd)."""
    if scale is None:
        scale = 1.0 / float(np.sqrt(q.shape[-1]))
    return _bs_attention(q, k, v, jnp.asarray(block_cols, jnp.int32),
                         jnp.asarray(block_counts, jnp.int32),
                         int(block_size), float(scale), interpret)


def dense_mask_sparse_attention(q, k, v, mask, key_padding_mask=None,
                                attn_mask=None, scale=None):
    """Reference-semantics fallback: element-level mask [B, H, L, L]
    (True = attend), optional key_padding_mask [B, L] and attn_mask
    [L, L] with 0 = masked (reference sparse_attention args)."""
    if scale is None:
        scale = 1.0 / float(np.sqrt(q.shape[-1]))
    if key_padding_mask is not None:
        mask = mask & (key_padding_mask[:, None, None, :] != 0)
    if attn_mask is not None:
        mask = mask & (attn_mask[None, None, :, :] != 0)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    s = jnp.where(mask, s, _NEG)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    p = jnp.where(mask, p / jnp.maximum(l, 1e-30), 0.0)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def csr_element_mask(offset, columns, seq_len):
    """[B, H, L, L] bool mask from an element-level CSR pattern
    (traceable — used by the dense fallback when the CSR arrays are
    traced or not block-aligned)."""
    offset = jnp.asarray(offset)
    columns = jnp.asarray(columns)
    B, H, _ = offset.shape
    nnz = columns.shape[-1]
    idx = jnp.arange(nnz)

    def rows_of(off):
        return jnp.searchsorted(off, idx, side="right") - 1

    rows = jax.vmap(jax.vmap(rows_of))(offset)            # [B, H, nnz]
    bi = jnp.arange(B)[:, None, None]
    hi = jnp.arange(H)[None, :, None]
    mask = jnp.zeros((B, H, seq_len, seq_len), bool)
    # entries past offset[-1] resolve to row==L and are dropped
    return mask.at[bi, hi, rows, columns].set(True, mode="drop")


def csr_to_block_layout(offset, columns, seq_len, block_sizes=(128, 64, 32, 16, 8)):
    """Detect whether a CONCRETE element-level CSR pattern (reference
    sparse_attention layout: offset [B,H,L+1], columns [B,H,nnz]) is
    exactly block-aligned for some block size; if so return
    (block_size, block_cols [B*H,nq,max_nnz], block_counts [B*H,nq]),
    else None.  numpy-only — call outside jit."""
    offset = np.asarray(offset)
    columns = np.asarray(columns)
    B, H, Lp1 = offset.shape
    L = seq_len
    dense = np.zeros((B * H, L, L), bool)
    off = offset.reshape(B * H, Lp1)
    cols = columns.reshape(B * H, -1)
    for g in range(B * H):
        for r in range(L):
            dense[g, r, cols[g, off[g, r]:off[g, r + 1]]] = True
    for bs in block_sizes:
        if L % bs:
            continue
        nb = L // bs
        blocks = dense.reshape(B * H, nb, bs, nb, bs)
        anyb = blocks.any(axis=(2, 4))
        allb = blocks.all(axis=(2, 4))
        if not (anyb == allb).all():
            continue   # partially-filled block: not aligned at this size
        counts = anyb.sum(axis=-1).astype(np.int32)          # [G, nb]
        max_nnz = max(1, int(counts.max()))
        colsb = np.zeros((B * H, nb, max_nnz), np.int32)
        for g in range(B * H):
            for r in range(nb):
                idx = np.nonzero(anyb[g, r])[0]
                colsb[g, r, :len(idx)] = idx
        return bs, colsb, counts
    return None
