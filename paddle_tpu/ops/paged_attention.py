"""Paged KV cache + paged attention for inference decode.

TPU-native analogue of the reference's paged attention path (vLLM-style
block KV management the reference exposes through fused decode ops). KV
lives in fixed-size pages in HBM; each sequence owns a list of page ids
(page_table). Decode-time attention gathers only that sequence's pages.

Shapes:
  k_pages/v_pages : (num_pages, page_size, H, D)
  page_table      : (B, max_pages)  int32 page ids (-1 = unused)
  seq_lens        : (B,)            int32 current lengths
  q               : (B, 1, H, D)    single decode step

The compute path is jnp (XLA fuses the gather + masked softmax well on TPU
for decode's tiny FLOP count — latency is HBM-bound on page reads). The
opt-in Pallas kernel (`use_kernel=True`) uses scalar-prefetch paging: the
page pool stays in HBM and the prefetched page_table drives the BlockSpec
index maps, so exactly one page of K/V is in VMEM per grid step regardless
of pool size (semantics verified against the jnp path in interpret mode;
note: some remote-compile toolchains are slow to build the
PrefetchScalarGridSpec lowering — the jnp default avoids that).
"""
import functools

import jax
import jax.numpy as jnp

from ._fallback import kernel_fallback
import numpy as np

__all__ = ["PagedKVCache", "paged_attention"]


class PagedKVCache:
    """Fixed-pool paged KV storage with host-side page allocation."""

    def __init__(self, num_pages, page_size, num_heads, head_dim,
                 dtype=jnp.bfloat16):
        self.page_size = page_size
        self.k_pages = jnp.zeros((num_pages, page_size, num_heads, head_dim), dtype)
        self.v_pages = jnp.zeros((num_pages, page_size, num_heads, head_dim), dtype)
        self._free = list(range(num_pages - 1, -1, -1))
        self.page_tables = {}   # seq id -> list of page ids
        self.seq_lens = {}

    def new_seq(self, seq_id):
        self.page_tables[seq_id] = []
        self.seq_lens[seq_id] = 0

    def _ensure_capacity(self, seq_id, new_len):
        need = (new_len + self.page_size - 1) // self.page_size
        table = self.page_tables[seq_id]
        while len(table) < need:
            if not self._free:
                raise RuntimeError("PagedKVCache out of pages")
            table.append(self._free.pop())

    def append(self, seq_id, k, v):
        """Append one step's K/V (1, H, D) for a sequence."""
        pos = self.seq_lens[seq_id]
        self._ensure_capacity(seq_id, pos + 1)
        page = self.page_tables[seq_id][pos // self.page_size]
        slot = pos % self.page_size
        self.k_pages = self.k_pages.at[page, slot].set(
            jnp.asarray(k, self.k_pages.dtype).reshape(self.k_pages.shape[2:]))
        self.v_pages = self.v_pages.at[page, slot].set(
            jnp.asarray(v, self.v_pages.dtype).reshape(self.v_pages.shape[2:]))
        self.seq_lens[seq_id] = pos + 1

    def free_seq(self, seq_id):
        self._free.extend(reversed(self.page_tables.pop(seq_id, [])))
        self.seq_lens.pop(seq_id, None)

    def batch_view(self, seq_ids):
        """Dense (page_table, seq_lens) arrays for a batch of sequences."""
        max_pages = max((len(self.page_tables[s]) for s in seq_ids), default=1)
        max_pages = max(max_pages, 1)
        table = np.full((len(seq_ids), max_pages), -1, np.int32)
        lens = np.zeros((len(seq_ids),), np.int32)
        for i, s in enumerate(seq_ids):
            ids = self.page_tables[s]
            table[i, :len(ids)] = ids
            lens[i] = self.seq_lens[s]
        return jnp.asarray(table), jnp.asarray(lens)


def _paged_attention_ref(q, k_pages, v_pages, page_table, seq_lens, scale):
    b, _, h, d = q.shape
    page_size = k_pages.shape[1]
    max_pages = page_table.shape[1]
    safe_table = jnp.maximum(page_table, 0)
    # gather this batch's pages: (B, max_pages, page_size, H, D)
    k = k_pages[safe_table].reshape(b, max_pages * page_size, h, d)
    v = v_pages[safe_table].reshape(b, max_pages * page_size, h, d)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    pos = jnp.arange(max_pages * page_size)
    valid = pos[None, :] < seq_lens[:, None]          # (B, K)
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def _paged_kernel(pt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, s_scr, acc_scr, *, scale, page_size, max_pages):
    """Grid (B, H, max_pages): ONE page of K/V in VMEM per step — the page
    pool stays in HBM and the scalar-prefetched page_table drives the
    BlockSpec index maps, so pallas pipelines page fetches with compute
    (no whole-pool VMEM blowup; the previous kernel mapped the entire pool
    per grid cell and silently fell back for any realistic pool size).
    Online-softmax state lives in VMEM scratch across the page steps."""
    from jax.experimental import pallas as pl

    b = pl.program_id(0)
    j = pl.program_id(2)
    seq_len = len_ref[b]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -1e30)
        s_scr[...] = jnp.zeros_like(s_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0, 0].astype(jnp.float32).reshape(1, -1) * scale  # (1, D)
    k = k_ref[0, :, 0, :].astype(jnp.float32)                      # (P, D)
    v = v_ref[0, :, 0, :].astype(jnp.float32)
    logits = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))   # (1, P)
    pos = j * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (1, page_size), 1)
    logits = jnp.where(pos < seq_len, logits, -1e30)

    m_prev, s_prev, acc_prev = m_scr[...], s_scr[...], acc_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
    p = jnp.exp(logits - m_new)
    corr = jnp.exp(m_prev - m_new)
    m_scr[...] = m_new
    s_scr[...] = s_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_prev * corr + p @ v

    @pl.when(j == max_pages - 1)
    def _emit():
        out = acc_scr[...] / jnp.maximum(s_scr[...], 1e-30)
        o_ref[0, 0, 0] = out[0].astype(o_ref.dtype)


def paged_attention(q, k_pages, v_pages, page_table, seq_lens, scale=None,
                    use_kernel=False, interpret=None):
    """Decode attention over a paged KV cache. q: (B, 1, H, D)."""
    if scale is None:
        scale = 1.0 / np.sqrt(q.shape[-1])
    if not use_kernel:
        return _paged_attention_ref(q, k_pages, v_pages, page_table,
                                    seq_lens, scale)
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    try:
        return _paged_kernel_call(q, k_pages, v_pages, page_table, seq_lens,
                                  scale, interpret)
    except Exception as e:
        kernel_fallback("paged_attention", e)
        return _paged_attention_ref(q, k_pages, v_pages, page_table,
                                    seq_lens, scale)


def _paged_kernel_call(q, k_pages, v_pages, page_table, seq_lens, scale,
                       interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, _, h, d = q.shape
    n_pages, page_size = k_pages.shape[:2]
    max_pages = page_table.shape[1]

    def page_map(bi, hi, j, pt, lens):
        return (jnp.maximum(pt[bi, j], 0), 0, hi, 0)  # -1 (unused) -> page 0, masked

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,   # page_table, seq_lens
        grid=(b, h, max_pages),
        in_specs=[
            pl.BlockSpec((1, 1, 1, d), lambda bi, hi, j, pt, lens: (bi, 0, hi, 0)),
            pl.BlockSpec((1, page_size, 1, d), page_map),
            pl.BlockSpec((1, page_size, 1, d), page_map),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, 1, d), lambda bi, hi, j, pt, lens: (bi, 0, hi, 0)),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, d), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_paged_kernel, scale=scale, page_size=page_size,
                          max_pages=max_pages),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, 1, h, d), q.dtype),
        interpret=interpret,
        name="paged_attention",
    )(page_table.astype(jnp.int32), seq_lens.astype(jnp.int32),
      q, k_pages, v_pages)
