"""Ragged paged attention: ONE attention primitive for mixed
chunked-prefill + decode batches over the paged KV pool.

TPU-native port of the Ragged Paged Attention design (arxiv 2604.15464):
every batch row carries (already-cached length, new-token count) —
a decode row is new_len=1, a prefill chunk new_len=W — so a single
kernel invocation serves both kinds of row with no length-bucketed
dispatch. Query j of row i sits at absolute position ``start[i] + j``
and attends causally over the row's own pages (kpos <= qpos); rows are
fully independent, so a token's attention output does not depend on the
window width W, the batch composition, or whether it was computed as a
decode tick or inside a prefill chunk — the schedule-independence the
serving engine's byte-identical equivalence tests pin.

Shapes:
  q               : (n, W, H, D)   new-token queries (row-local window)
  k_pages/v_pages : (P, page_size, H, D)  one layer's page pool — or,
                    with `layer=` given, the WHOLE pool (L, P, page_size,
                    H, D), of which that layer is read through the page
                    gather itself (`pool[layer, pages]`: never a slice
                    of the layer, which would copy it) — OR an
                    int8 pool as the tuple (pages int8, scales f32
                    (P, page_size)) — per-token write-time scales (the
                    serving decoder's kv_quant="int8" layout), OR an
                    int4 pool as the tuple (nibble-packed uint8
                    (P, page_size, PB), per-GROUP scales f32
                    (P, page_size, G)) — the kv_quant="int4" layout
                    (`serving.decoder._quantize_kv_int4`). Dequant
                    happens per step next to the shared update (a
                    block of pages in the reference, a page in the
                    kernel), so the dequantized pool never
                    materializes in HBM
  page_table      : (n, max_pages) int32 page ids per row
  start           : (n,)           already-cached length per row

Math: an online-softmax (flash) accumulation over the row's keys, in
f32, by ONE update function (`_page_update`) that the jnp reference, the
Pallas kernel and the materialised latent walk share. The jnp reference
(`use_kernel=False`, the path the chip runs) walks BLOCKS of
`KEY_BLOCK_PAGES` pages, as many as the deepest row of the batch holds:
a `fori_loop` whose trip count is read from the queries' positions, each
step copying its own block of every row out of the pool (`_ragged_ref`).
The Pallas kernel keeps the page pool in HBM and streams ONE page of K/V
per grid step through VMEM via the scalar-prefetched page table (the
`paged_attention` scalar-prefetch pattern), with online-softmax state in
VMEM scratch across the page steps: the same keys in the same order,
grouped by page and not by block, so it agrees with the reference to
float32 rounding (the tests hold it to the oracle's tolerance) and not
to the bit. The TPU lowering refuses its per-page grid (ROADMAP D3); it
runs in interpret mode on the CPU alone.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from ._fallback import kernel_fallback

__all__ = ["ragged_paged_attention", "ragged_paged_attention_packed",
           "mla_paged_attention_packed"]

# softmax-denominator floor shared by reference and kernel: a row whose
# every key is masked (possible only for padded queries past true_len —
# row-local garbage by design) divides by this instead of 0
_DENOM_EPS = 1e-30
_MASK = -1e30


def _page_update(m, s, acc, logits, v, kpos, qpos, k_scale=None,
                 v_scale=None):
    """ONE step's online-softmax update over `ps` keys — the shared
    math of the jnp reference (a block of `KEY_BLOCK_PAGES` pages a
    step), the Pallas kernel (a page a step) and the materialised
    latent walk: they call this same function, so the paths cannot
    drift in anything but the grouping of the keys.

    m/s/acc: running max [..., W, 1], denominator [..., W, 1], value
    accumulator [..., W, D]. logits [..., W, ps] this step's scores
    (q*scale @ k^T), v [..., ps, D] its values, kpos [ps] its keys'
    absolute positions, qpos [..., W] the queries' absolute
    positions. Causal: a query attends to kpos <= qpos only.

    k_scale/v_scale (optional): the keys' per-token dequant scales,
    broadcastable to [..., ps] — the int8 KV pool's write-time scales.
    Applied HERE, so the reference and the kernel share one dequant
    exactly like they share the softmax math: logits computed from raw
    int8 keys pick up the key scale (q·(k_q·s) == (q·k_q)·s), values
    dequantize before the accumulator dot, and the dequantized pool
    never exists outside this step's working set."""
    if k_scale is not None:
        logits = logits * k_scale[..., None, :]
    if v_scale is not None:
        v = v * v_scale[..., :, None]
    mask = kpos[..., None, :] <= qpos[..., :, None]       # [..., W, ps]
    logits = jnp.where(mask, logits, _MASK)
    m_new = jnp.maximum(m, jnp.max(logits, axis=-1, keepdims=True))
    p = jnp.exp(logits - m_new)
    corr = jnp.exp(m - m_new)
    s_new = s * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_new = acc * corr + jax.lax.dot_general(
        p, v, (((p.ndim - 1,), (v.ndim - 2,)),
               (tuple(range(p.ndim - 2)), tuple(range(v.ndim - 2)))),
        preferred_element_type=jnp.float32)
    return m_new, s_new, acc_new


def _dequant_page_int4(packed, gscale, heads):
    """int4 dequant of one step's keys (a page, or a block of pages) —
    shared by the jnp reference and the Pallas kernel exactly like
    `_page_update` (both call this same function immediately before
    it, so the two paths cannot drift on the nibble-packed pool).

    packed [..., ps, PB] uint8 nibble pairs (low nibble = element 2i —
    `serving.decoder._pack_int4`'s layout), gscale [..., ps, G] f32
    per-group write-time scales, heads = (H, D). Returns f32
    [..., ps, H, D].

    Unlike the int8 pool's per-TOKEN scale — a scalar that commutes out
    of the q·k contraction, so `_page_update` can apply it to the
    finished logits — an int4 group scale varies ALONG the contraction
    (groups tile the flattened H*D axis), so K must dequantize before
    the logits dot and V before the accumulator dot. Everything here is
    elementwise and exact in f32 (integer unpack, one cast, one
    multiply): both paths see the same dequantized values."""
    H, D = int(heads[0]), int(heads[1])
    PB = packed.shape[-1]
    G = gscale.shape[-1]
    lo = (packed & 0xF).astype(jnp.int8)
    hi = ((packed >> 4) & 0xF).astype(jnp.int8)
    lo = jnp.where(lo >= 8, lo - 16, lo)
    hi = jnp.where(hi >= 8, hi - 16, hi)
    q = jnp.stack([lo, hi], axis=-1).reshape(
        packed.shape[:-1] + (2 * PB,)).astype(jnp.float32)
    # stored group width: 2*PB == G*group except when the pack-parity
    # nibble padded an odd G*group — only possible at G == 1, where the
    # wider pseudo-group is harmless (the pad nibble is 0 and the H*D
    # slice below drops it)
    group = (2 * PB) // G
    g = q.reshape(packed.shape[:-1] + (G, group)) * gscale[..., None]
    flat = g.reshape(packed.shape[:-1] + (G * group,))[..., :H * D]
    return flat.reshape(packed.shape[:-1] + (H, D))


# pages of one block of keys (128 keys at pages of 16): the step of the
# reference's walk (`_ragged_ref`) and of the materialised latent walk
# (`mla_paged_attention_packed`). A module constant, not an option: a
# step's working set is a block of every row, and 128 keys fill the
# contraction of probabilities x values that one page of 16 left idle.
KEY_BLOCK_PAGES = 8


def _ragged_ref(q, k_pages, v_pages, page_table, qpos, scale,
                k_scale=None, v_scale=None, int4=False, layer=None):
    """jnp reference: an online-softmax walk over BLOCKS of
    `KEY_BLOCK_PAGES` pages, as many as the deepest row of the batch
    holds. `qpos` [n, W] is every query's absolute position: `start +
    arange(W)` for the dense entry point, the stream's own `pos` laid
    out by row for the packed one — the ONE program both layouts run.

    The trip count is read from `qpos` (a traced scalar): block j holds
    keys `kb*j .. kb*j + kb - 1` (kb = KEY_BLOCK_PAGES * page_size), so
    the walk ends after block `max(qpos) // kb`. A garbage window slot
    holds another row's real position or the padded tail's, so the
    maximum is the deepest live row's. Step j copies its OWN block of
    every row out of the pool (`pool[layer, table[:, 8j:8j+8]]`: one
    index, so a layer of a whole pool is never sliced out) and makes
    ONE `_page_update` over its kb keys; blocks past the bound are
    neither copied nor read, so the bytes a tick moves follow the
    context and not the table's width.

    Every step has one shape: a table of any width is padded to a
    multiple of the block with columns no query can see (their keys
    are given a position past every query's). A block wholly past a
    query's position is an exact no-op for it (`m` unmoved, `p` = 0.0,
    `corr` = 1.0), so a token's bits depend on its row's keys alone —
    not on the table's width, the batch's other rows or the window.

    With an int8 pool, `k_scale`/`v_scale` [P, ps] carry the per-token
    write-time scales; the copy stays int8 and only the step's block
    dequantizes. With an int4 pool (`int4=True`) the payload is
    nibble-packed [P, ps, PB] and `k_scale`/`v_scale` [P, ps, G] carry
    per-GROUP scales; the block dequantizes through the shared
    `_dequant_page_int4` before its update — the copy stays packed.
    `layer` (a traced scalar; None = the pools are one layer's) names
    the layer of a whole [L, P, ...] pool to read.

    GROUPED (`v_pages` None): `k_pages` is ONE pool whose last axis holds
    a token's keys and then its values, `Hk` heads of D each ([..., ps,
    2 x Hk x D]), with Hk dividing H: query head h reads key/value head
    h // (H / Hk) (`ops.attention._fold_gqa`'s order). The G = H / Hk
    query heads of a key/value head are folded into its window (G x W
    queries over the block's keys), so a block is gathered and
    contracted once per key/value head and no key is repeated."""
    n, W, H, D = q.shape
    ps = k_pages.shape[1 if layer is None else 2]
    kb = KEY_BLOCK_PAGES * ps
    MP = page_table.shape[1]
    blocks = -(-MP // KEY_BLOCK_PAGES)
    safe = jnp.pad(jnp.maximum(page_table, 0),
                   ((0, 0), (0, blocks * KEY_BLOCK_PAGES - MP)))
    quantized = k_scale is not None and not int4
    fused = v_pages is None
    Hk = k_pages.shape[-1] // (2 * D) if fused else H
    G = H // Hk
    qf = (q.astype(jnp.float32) * scale).transpose(0, 2, 1, 3)  # [n,H,W,D]
    trips = jnp.minimum(blocks, jnp.max(qpos) // kb + 1)
    qpos = qpos[:, None, :]                                     # [n,1,W]
    if G > 1:
        # [n, H, W, D] -> [n, Hk, G x W, D]: head h = kv head h // G
        qf = qf.reshape(n, Hk, G * W, D)
        qpos = jnp.tile(qpos, (1, 1, G))                        # [n,1,GW]

    def block_step(j, carry):
        # named for the trace: ONE copy of each row's block of pages,
        # whatever the number of queries in the row's window
        with jax.named_scope("paged_gather"):
            cols = jax.lax.dynamic_slice_in_dim(
                safe, j * KEY_BLOCK_PAGES, KEY_BLOCK_PAGES, axis=1)

            def block_of(pool):
                # [n, 8, ps, ...] -> the block's keys in order [n, kb, ...]
                rows = pool[cols] if layer is None else pool[layer, cols]
                return rows.reshape((n, kb) + rows.shape[3:])

            if fused:
                kv = block_of(k_pages)                  # [n, kb, 2 Hk D]
                kj = kv[..., :Hk * D].reshape(n, kb, Hk, D)
                vj = kv[..., Hk * D:].reshape(n, kb, Hk, D)
            else:
                kj, vj = block_of(k_pages), block_of(v_pages)
            if k_scale is not None:
                ksj, vsj = block_of(k_scale), block_of(v_scale)
        if int4:
            # barrier: the dequantized block must MATERIALIZE before
            # the dot. Without it XLA fuses the group-scale multiply
            # into the contraction and the fused gemm's rounding shifts
            # with the window shape (observed: last-ulp drift at G > 1)
            # — breaking the W-independence the schedule-equivalence
            # tests pin.
            kj = jax.lax.optimization_barrier(
                _dequant_page_int4(kj, ksj, (H, D)))
            vj = jax.lax.optimization_barrier(
                _dequant_page_int4(vj, vsj, (H, D)))
        logits = jax.lax.dot_general(
            qf, kj.astype(jnp.float32),
            (((3,), (3,)), ((0, 1), (0, 2))),
            preferred_element_type=jnp.float32)              # [n, H, W, kb]
        kpos = j * kb + jnp.arange(kb)
        if MP % KEY_BLOCK_PAGES:
            # the padded columns' keys: masked for every query, also a
            # padded one that sits past the table's last position
            kpos = jnp.where(kpos < MP * ps, kpos, jnp.iinfo(jnp.int32).max)
        return _page_update(
            *carry, logits,
            vj.astype(jnp.float32).transpose(0, 2, 1, 3),    # [n, H, kb, D]
            kpos, qpos,
            # [n, kb] -> [n, 1, kb]: broadcast over the head axis
            k_scale=ksj[:, None] if quantized else None,
            v_scale=vsj[:, None] if quantized else None)

    with jax.named_scope("paged_attention"):
        m, s, acc = jax.lax.fori_loop(
            0, trips, block_step,
            (jnp.full((n, Hk, G * W, 1), _MASK, jnp.float32),
             jnp.zeros((n, Hk, G * W, 1), jnp.float32),
             jnp.zeros((n, Hk, G * W, D), jnp.float32)))
        out = acc / jnp.maximum(s, _DENOM_EPS)           # [n, H, W, D]
        if G > 1:
            out = out.reshape(n, H, W, D)
        return out.transpose(0, 2, 1, 3).astype(q.dtype)  # [n, W, H, D]


# the reference always executes COMPILED, even when the caller is
# eager: op-by-op dispatch rounds a hair differently from XLA's fused
# lowering, and the bit-identity of a position across windows, tables
# and layouts is pinned at the compiled semantics.
# Inside a jitted caller (the decoder's programs) this inlines away.
@functools.partial(jax.jit, static_argnames=("scale", "int4"))
def _dense_ref(q, k_pages, v_pages, page_table, start, scale,
               k_scale=None, v_scale=None, int4=False, layer=None):
    """The dense entry point's reference: row i's window sits at
    positions start[i] .. start[i] + W - 1."""
    qpos = start[:, None] + jnp.arange(q.shape[1], dtype=jnp.int32)
    return _ragged_ref(q, k_pages, v_pages, page_table, qpos, scale,
                       k_scale=k_scale, v_scale=v_scale, int4=int4,
                       layer=layer)


@functools.partial(jax.jit, static_argnames=("scale", "window", "int4"))
def _packed_ref(q, k_pages, v_pages, page_table, row_ids, pos, scale,
                window, k_scale=None, v_scale=None, int4=False, layer=None):
    """The packed entry point's reference: lay the stream out BY ROW and
    run the dense reference once. A row's tokens are contiguous in the
    stream, so row r's window is the `window` stream slots from its
    first token on: two gathers of q-sized arrays (in, out) and ONE
    copy of each block of the row's pages, shared by every token of the
    row — never a per-token copy of the page table or of the pages. Window slots
    past a row's last token hold the stream's next tokens (another
    row's, or the padded tail): row-local garbage, like the dense
    path's padded queries, that no token gathers back."""
    T = q.shape[0]
    n = page_table.shape[0]
    # first stream slot of each row (0 for a row with no token: its
    # window is computed and never read)
    first = jnp.argmax(row_ids[None, :] == jnp.arange(n)[:, None],
                       axis=1).astype(jnp.int32)               # [n]
    slot = jnp.minimum(first[:, None] + jnp.arange(window), T - 1)
    out = _ragged_ref(q[slot], k_pages, v_pages, page_table, pos[slot],
                      scale, k_scale=k_scale, v_scale=v_scale, int4=int4,
                      layer=layer)
    within = jnp.clip(jnp.arange(T) - first[row_ids], 0, window - 1)
    return out[row_ids, within]


def _ragged_kernel(pt_ref, start_ref, q_ref, k_ref, v_ref, *rest,
                   scale, page_size, max_pages, quant, heads=None):
    """Grid (n, H, max_pages): one page of K/V in VMEM per step, online
    softmax in scratch — the scalar-prefetched page_table drives the
    K/V BlockSpec index maps, so the pool never leaves HBM whole.
    `quant` is the pool's mode (None | "int8" | "int4"). int8: two more
    page-indexed refs carry the [ps] per-token scales; dequant runs
    inside `_page_update`, on the one VMEM-resident page — the f32
    pool never exists. int4: the page block is the WHOLE packed page
    (nibbles mix heads — the [ps, PB] payload plus [ps, G] group-scale
    refs stream via their own page-indexed BlockSpecs), the nibble
    unpack + group dequant run in VMEM through the shared
    `_dequant_page_int4`, and the body slices its own head (grid axis
    1; `heads` = (H, D) — every grid step along H re-reads the same
    packed page, an interpret-mode correctness cost a production TPU
    kernel would fold into a head-blocked grid)."""
    from jax.experimental import pallas as pl

    if quant:
        ks_ref, vs_ref, o_ref, m_scr, s_scr, acc_scr = rest
    else:
        o_ref, m_scr, s_scr, acc_scr = rest
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _MASK)
        s_scr[...] = jnp.zeros_like(s_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, :, 0, :].astype(jnp.float32) * scale        # [W, D]
    if quant == "int4":
        hi = pl.program_id(1)
        kd = _dequant_page_int4(k_ref[0], ks_ref[0], heads)  # [ps, H, D]
        vd = _dequant_page_int4(v_ref[0], vs_ref[0], heads)
        k = jax.lax.dynamic_index_in_dim(kd, hi, 1, keepdims=False)
        v = jax.lax.dynamic_index_in_dim(vd, hi, 1, keepdims=False)
    else:
        k = k_ref[0, :, 0, :].astype(jnp.float32)            # [ps, D]
        v = v_ref[0, :, 0, :].astype(jnp.float32)
    logits = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    kpos = j * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (1, page_size), 1)[0]                     # [ps]
    W = q.shape[0]
    qpos = start_ref[b] + jax.lax.broadcasted_iota(
        jnp.int32, (W, 1), 0)[:, 0]                          # [W]
    m_new, s_new, acc_new = _page_update(
        m_scr[...], s_scr[...], acc_scr[...], logits, v, kpos, qpos,
        k_scale=ks_ref[0, :] if quant == "int8" else None,
        v_scale=vs_ref[0, :] if quant == "int8" else None)
    m_scr[...] = m_new
    s_scr[...] = s_new
    acc_scr[...] = acc_new

    @pl.when(j == max_pages - 1)
    def _emit():
        out = acc_scr[...] / jnp.maximum(s_scr[...], _DENOM_EPS)
        o_ref[0, :, 0, :] = out.astype(o_ref.dtype)


def _ragged_kernel_call(q, k_pages, v_pages, page_table, start, scale,
                        interpret, k_scale=None, v_scale=None,
                        int4=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, W, H, D = q.shape
    page_size = k_pages.shape[1]
    max_pages = page_table.shape[1]
    quant = "int4" if int4 else ("int8" if k_scale is not None else None)

    def page_map(bi, hi, j, pt, st):
        return (jnp.maximum(pt[bi, j], 0), 0, hi, 0)

    def scale_map(bi, hi, j, pt, st):
        return (jnp.maximum(pt[bi, j], 0), 0)

    def packed_map(bi, hi, j, pt, st):
        # int4 blocks carry the whole page (nibble groups mix heads):
        # page-indexed on axis 0, full ps x PB/G extent
        return (jnp.maximum(pt[bi, j], 0), 0, 0)

    in_specs = [
        pl.BlockSpec((1, W, 1, D),
                     lambda bi, hi, j, pt, st: (bi, 0, hi, 0)),
    ]
    if int4:
        PB = k_pages.shape[-1]
        G = k_scale.shape[-1]
        in_specs += [pl.BlockSpec((1, page_size, PB), packed_map),
                     pl.BlockSpec((1, page_size, PB), packed_map),
                     pl.BlockSpec((1, page_size, G), packed_map),
                     pl.BlockSpec((1, page_size, G), packed_map)]
        operands = (q, k_pages, v_pages, k_scale, v_scale)
    else:
        in_specs += [pl.BlockSpec((1, page_size, 1, D), page_map),
                     pl.BlockSpec((1, page_size, 1, D), page_map)]
        operands = (q, k_pages, v_pages)
        if quant:
            in_specs += [pl.BlockSpec((1, page_size), scale_map),
                         pl.BlockSpec((1, page_size), scale_map)]
            operands += (k_scale, v_scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,   # page_table, start
        grid=(n, H, max_pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, W, 1, D), lambda bi, hi, j, pt, st: (bi, 0, hi, 0)),
        scratch_shapes=[
            pltpu.VMEM((W, 1), jnp.float32),
            pltpu.VMEM((W, 1), jnp.float32),
            pltpu.VMEM((W, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_ragged_kernel, scale=scale,
                          page_size=page_size, max_pages=max_pages,
                          quant=quant, heads=(H, D)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, W, H, D), q.dtype),
        interpret=interpret,
        name="ragged_paged_attention",
    )(page_table.astype(jnp.int32), start.astype(jnp.int32),
      *operands)


def _packed_kernel_call(q2, k_pages, v_pages, page_table, row_ids, pos,
                        scale, interpret, k_scale=None, v_scale=None,
                        int4=False):
    """Pallas call for the PACKED layout: grid (T, H, max_pages) — one
    token's one page per step. The page/scale BlockSpec index maps
    indirect through TWO scalar-prefetched vectors: `row_ids[t]` picks
    the token's page-table ROW, `page_table[row, j]` the page — so the
    [n, max_pages] table never gets gathered to a [T, max_pages] copy
    in HBM; the indirection lives entirely in the prefetched scalars.
    The kernel BODY is `_ragged_kernel` itself (pos plays the dense
    path's start role; the rid prefetch is consumed only by the index
    maps), so the per-page math cannot drift from the dense kernel."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T, W, H, D = q2.shape
    page_size = k_pages.shape[1]
    max_pages = page_table.shape[1]
    quant = "int4" if int4 else ("int8" if k_scale is not None else None)

    def page_map(ti, hi, j, pt, rid, ps_):
        return (jnp.maximum(pt[rid[ti], j], 0), 0, hi, 0)

    def scale_map(ti, hi, j, pt, rid, ps_):
        return (jnp.maximum(pt[rid[ti], j], 0), 0)

    def packed_map(ti, hi, j, pt, rid, ps_):
        return (jnp.maximum(pt[rid[ti], j], 0), 0, 0)

    in_specs = [
        pl.BlockSpec((1, W, 1, D),
                     lambda ti, hi, j, pt, rid, ps_: (ti, 0, hi, 0)),
    ]
    if int4:
        PB = k_pages.shape[-1]
        G = k_scale.shape[-1]
        in_specs += [pl.BlockSpec((1, page_size, PB), packed_map),
                     pl.BlockSpec((1, page_size, PB), packed_map),
                     pl.BlockSpec((1, page_size, G), packed_map),
                     pl.BlockSpec((1, page_size, G), packed_map)]
        operands = (q2, k_pages, v_pages, k_scale, v_scale)
    else:
        in_specs += [pl.BlockSpec((1, page_size, 1, D), page_map),
                     pl.BlockSpec((1, page_size, 1, D), page_map)]
        operands = (q2, k_pages, v_pages)
        if quant:
            in_specs += [pl.BlockSpec((1, page_size), scale_map),
                         pl.BlockSpec((1, page_size), scale_map)]
            operands += (k_scale, v_scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,   # page_table, row_ids, pos
        grid=(T, H, max_pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, W, 1, D), lambda ti, hi, j, pt, rid, ps_: (ti, 0, hi, 0)),
        scratch_shapes=[
            pltpu.VMEM((W, 1), jnp.float32),
            pltpu.VMEM((W, 1), jnp.float32),
            pltpu.VMEM((W, D), jnp.float32),
        ],
    )

    def body(pt_ref, rid_ref, pos_ref, *args):
        # rid_ref is consumed by the index maps only; the body is the
        # dense kernel with `pos` in the start slot
        return _ragged_kernel(pt_ref, pos_ref, *args, scale=scale,
                              page_size=page_size, max_pages=max_pages,
                              quant=quant, heads=(H, D))

    return pl.pallas_call(
        body, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, W, H, D), q2.dtype),
        interpret=interpret,
        name="ragged_paged_attention_packed",
    )(page_table.astype(jnp.int32), row_ids.astype(jnp.int32),
      pos.astype(jnp.int32), *operands)


def _one_layer(layer, *pools):
    """The pools the Pallas kernels walk: one layer's. The kernel path
    runs in interpret mode on the CPU alone, where slicing the layer
    out of a whole pool costs nothing that is measured; the reference,
    which is what the chip runs, never does (`_ragged_ref`)."""
    if layer is None:
        return pools
    return tuple(None if p is None else p[layer] for p in pools)


def ragged_paged_attention_packed(q, k_pages, v_pages, page_table,
                                  row_ids, pos, scale=None,
                                  use_kernel=False, interpret=None,
                                  window=None, layer=None):
    """PACKED-layout causal attention over paged KV: q [T, H, D] is a
    flat stream of new tokens — token t belongs to batch row
    `row_ids[t]` (its row in `page_table` [n, max_pages]) and sits at
    absolute position `pos[t]`. No [n, W] window padding exists in the
    layout at all: a pure-decode batch pays exactly n tokens, a mixed
    batch pays exactly its token total (the Ragged Paged Attention
    layout, arxiv 2604.15464 — pay for tokens, not windows).

    The stream is ROW-CONTIGUOUS: a row's tokens follow one another
    (rows in any order, a padded tail under any row id). `window`
    (static; default T) bounds the tokens one row may hold — a token
    further than that from its row's first gets garbage, which is what
    the decoder's padded tail is for.

    Per-token math is EXACTLY the dense path's: the reference lays a
    row's tokens out as that row's window and runs the dense
    reference's walk over blocks of the ROW's pages, each copied once
    whatever the row's tokens (`_packed_ref`; a 1-wide window is padded to the same 2-wide one
    the dense W=1 path uses), so a token's output is bit-identical to
    the dense `ragged_paged_attention` computing the same position
    inside any window width — the packed/dense byte-identity the
    serving engine's A/B twin pins. The Pallas kernel walks per token
    instead: it scalar-prefetches `row_ids` and `pos` next to the page
    table and resolves `page_table[row_ids[t], j]` inside the BlockSpec
    index maps (see `_packed_kernel_call`). int8/int4 pools pass as
    (pages, scales) tuples exactly like the dense entry point; with
    `layer` given the pools are whole ([L, P, ...]) and that layer is
    read, as in the dense entry point. With `v_pages` None the pool is
    GROUPED: `k_pages` holds each token's keys and then its values of
    fewer key/value heads than q has heads (`_ragged_ref` says how the
    query heads fold onto them); the reference alone walks it. Returns
    [T, H, D]."""
    if scale is None:
        scale = 1.0 / float(np.sqrt(q.shape[-1]))
    row_ids = jnp.asarray(row_ids, jnp.int32)
    pos = jnp.asarray(pos, jnp.int32)
    ks = vs = None
    int4 = False
    if isinstance(k_pages, tuple):
        k_pages, ks = k_pages
        v_pages, vs = v_pages
        int4 = k_pages.dtype == jnp.uint8    # nibble-packed payload
    T = q.shape[0]
    # at least 2 wide: the degenerate matvec lowering of a 1-wide
    # window drifts a ulp, so the dense W=1 path pads to 2 as well and
    # both layouts run the identical program shape per position
    window = max(2, T if window is None else min(int(window), T))

    def reference():
        return _packed_ref(q, k_pages, v_pages, page_table, row_ids,
                           pos, scale=float(scale), window=window,
                           k_scale=ks, v_scale=vs, int4=int4, layer=layer)

    if not use_kernel or v_pages is None:
        return reference()
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    # the kernel's grid is per token: one zero query beside each, for
    # the same 2-wide shape
    q2 = jnp.stack([q, jnp.zeros_like(q)], axis=1)      # [T, 2, H, D]
    try:
        kl, vl, ksl, vsl = _one_layer(layer, k_pages, v_pages, ks, vs)
        return _packed_kernel_call(q2, kl, vl, page_table,
                                   row_ids, pos, scale, interpret,
                                   k_scale=ksl, v_scale=vsl,
                                   int4=int4)[:, 0]
    except Exception as e:
        kernel_fallback("ragged_paged_attention_packed", e)
        return reference()


def ragged_paged_attention(q, k_pages, v_pages, page_table, start,
                           scale=None, use_kernel=False, interpret=None,
                           layer=None):
    """Causal attention of ragged new-token windows over paged KV.

    q (n, W, H, D): row i's new tokens at positions start[i]..start[i]+
    W-1 (pad the window past the row's true new_len — padded queries
    produce row-local garbage the caller discards, exactly like padded
    positions in the chunked prefill). Decode rows are simply W=1 (or a
    width-W window with one real query). Returns (n, W, H, D).

    `k_pages`/`v_pages` may each be a quantized pool tuple: int8 as
    (pages int8, scales f32 [P, ps]) — the serving decoder's
    kv_quant="int8" layout — or int4 as (nibble-packed uint8
    [P, ps, PB], per-group scales f32 [P, ps, G]) — kv_quant="int4".
    Both paths dequantize per step next to the shared `_page_update`
    (int8 inside it, int4 through `_dequant_page_int4` right before
    it — group scales cannot be folded post-dot).

    `layer` (optional, a traced scalar): the pools are then the WHOLE
    pool, [L, P, ...] in every leaf, and layer `layer` of it is read —
    through the page gather (`pool[layer, pages]`), so that a layer
    loop which carries the pool neither slices nor copies it
    (`mla_paged_attention_packed` reads its latent pool the same
    way)."""
    if scale is None:
        scale = 1.0 / float(np.sqrt(q.shape[-1]))
    start = jnp.asarray(start, jnp.int32)
    if q.shape[1] == 1:
        # degenerate single-query windows (the all-decode batch) tickle
        # a different XLA CPU matvec lowering in the reference program
        # than in the interpret-mode kernel (observed: last-ulp drift at
        # W=1, bit-identical at W>=2). Pad with one discarded zero query
        # on BOTH paths — queries are row-local, so row 0's math is
        # unchanged and the two paths stay bit-identical everywhere.
        q2 = jnp.concatenate([q, jnp.zeros_like(q)], axis=1)
        return ragged_paged_attention(q2, k_pages, v_pages, page_table,
                                      start, scale=scale,
                                      use_kernel=use_kernel,
                                      interpret=interpret,
                                      layer=layer)[:, :1]
    ks = vs = None
    int4 = False
    if isinstance(k_pages, tuple):
        k_pages, ks = k_pages
        v_pages, vs = v_pages
        int4 = k_pages.dtype == jnp.uint8    # nibble-packed payload

    def reference():
        return _dense_ref(q, k_pages, v_pages, page_table, start,
                          scale=float(scale), k_scale=ks, v_scale=vs,
                          int4=int4, layer=layer)

    if not use_kernel:
        return reference()
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    try:
        kl, vl, ksl, vsl = _one_layer(layer, k_pages, v_pages, ks, vs)
        return _ragged_kernel_call(q, kl, vl, page_table,
                                   start, scale, interpret,
                                   k_scale=ksl, v_scale=vsl, int4=int4)
    except Exception as e:
        kernel_fallback("ragged_paged_attention", e)
        return reference()


# ------------------------------------------------- latent (MLA) attention
def _mla_key_block(max_pages, page_size):
    """Keys of one block of the materialised walk: `KEY_BLOCK_PAGES`
    pages, or the largest part of them that divides the gathered
    context."""
    import math
    return math.gcd(int(max_pages), KEY_BLOCK_PAGES) * int(page_size)


def mla_paged_attention_packed(q_nope, q_rope, latent_pages, layer, w_kvb,
                               page_table, row_ids, pos, row_new,
                               materialise, scale, window=None):
    """PACKED-layout causal multi-head LATENT attention over a paged
    latent cache: H query heads over ONE cached head a token, whose row
    [rank + dr] holds the normed latent (what every head's keys and
    values are projected from) beside the rotary key all heads share.
    The same function of the same cache in two forms, chosen BY ROW:

    * `materialise[row]` False — ABSORBED, for a row with one new token
      (a decode row): the key up-projection is folded into the query
      (`q~ = q_nope W_uk^T`, [H, rank]) so scores are `q~ . latent +
      q_rope . k_rope` over the latent rows themselves, the weighted sum
      is taken over the latent rows too and only then projected up
      through W_uv. Nothing per head is built for the context; the
      row's `rank + dr` bytes a key are all that is read.
    * `materialise[row]` True — MATERIALISED, for a row with a chunk of
      new tokens (a prefill row): the row's latent context is projected
      up, a block of keys at a time, to per-head keys [dn] and values
      [dv], and the row's `window` of queries runs ordinary
      online-softmax attention over them (`_page_update`, the math the
      GPT walk uses). The projection is paid once a block whatever the
      number of queries, and a row's walk stops at its last position.

    q_nope [T, H, dn], q_rope [T, H, dr] (already rotated): the flat
    token stream, ROW-CONTIGUOUS as in `ragged_paged_attention_packed`
    (rows in increasing order; `row_new[r]` tokens of row r from its
    first stream slot on, at most `window`). latent_pages
    [L, P, page_size, rank + dr] with `layer` the layer to read (the
    new tokens' rows already written); w_kvb [rank, H, dn + dv];
    page_table [n, max_pages]; pos [T] absolute positions. Returns
    [T, H, dv]; a token past its row's `row_new` gets garbage."""
    T, H, dn = q_nope.shape
    dr = q_rope.shape[-1]
    n, MP = page_table.shape
    ps, C = latent_pages.shape[-2:]
    r = C - dr
    dv = w_kvb.shape[-1] - dn
    ctx = MP * ps
    dt = q_nope.dtype
    W = max(1, T if window is None else min(int(window), T))
    row_ids = jnp.asarray(row_ids, jnp.int32)
    pos = jnp.asarray(pos, jnp.int32)
    with jax.named_scope("paged_gather"):
        # ONE copy of each row's latent pages, shared by both forms
        lat = latent_pages[layer, jnp.maximum(page_table, 0)].reshape(
            n, ctx, C)
    first = jnp.argmax(row_ids[None, :] == jnp.arange(n)[:, None],
                       axis=1).astype(jnp.int32)               # [n]
    kpos = jnp.arange(ctx)

    with jax.named_scope("mla_absorbed"):
        qn, qr, qp = q_nope[first], q_rope[first], pos[first]  # [n, H, .]
        qt = jnp.einsum("nhd,rhd->nhr", qn, w_kvb[..., :dn],
                        preferred_element_type=jnp.float32).astype(dt)
        s = (jnp.einsum("nhr,ncr->nhc", qt, lat[..., :r],
                        preferred_element_type=jnp.float32)
             + jnp.einsum("nhd,ncd->nhc", qr, lat[..., r:],
                          preferred_element_type=jnp.float32)) * scale
        s = jnp.where((kpos[None, :] <= qp[:, None])[:, None, :], s, _MASK)
        p = jax.nn.softmax(s, axis=-1)
        ol = jnp.einsum("nhc,ncr->nhr", p.astype(dt), lat[..., :r],
                        preferred_element_type=jnp.float32).astype(dt)
        out_abs = jnp.einsum("nhr,rhd->nhd", ol, w_kvb[..., dn:],
                             preferred_element_type=jnp.float32).astype(dt)

    with jax.named_scope("mla_materialised"):
        kb = _mla_key_block(MP, ps)
        # padded by a window, so that a row's slice never clamps
        pad = ((0, W), (0, 0), (0, 0))
        qn_p, qr_p = jnp.pad(q_nope, pad), jnp.pad(q_rope, pad)
        pos_p = jnp.pad(pos, (0, W))

        def one_row(out, row):
            def run(out):
                lo = first[row]
                qn = jax.lax.dynamic_slice_in_dim(qn_p, lo, W, 0)
                qr = jax.lax.dynamic_slice_in_dim(qr_p, lo, W, 0)
                qpos = jax.lax.dynamic_slice_in_dim(pos_p, lo, W, 0)
                qn = (qn.astype(jnp.float32) * scale).transpose(1, 0, 2)
                qr = (qr.astype(jnp.float32) * scale).transpose(1, 0, 2)
                c = lat[row]                                 # [ctx, C]
                last = pos[lo] + row_new[row] - 1

                def block(j, carry):
                    cb = jax.lax.dynamic_slice_in_dim(c, j * kb, kb, 0)
                    kv = jnp.einsum("cr,rhd->hcd", cb[:, :r], w_kvb,
                                    preferred_element_type=jnp.float32)
                    logits = (jnp.einsum("hwd,hcd->hwc", qn, kv[..., :dn])
                              + jnp.einsum("hwd,cd->hwc", qr,
                                           cb[:, r:].astype(jnp.float32)))
                    return _page_update(*carry, logits, kv[..., dn:],
                                        j * kb + jnp.arange(kb), qpos)

                m, s_, acc = jax.lax.fori_loop(
                    0, jnp.minimum(last // kb + 1, ctx // kb), block,
                    (jnp.full((H, W, 1), _MASK, jnp.float32),
                     jnp.zeros((H, W, 1), jnp.float32),
                     jnp.zeros((H, W, dv), jnp.float32)))
                o = (acc / jnp.maximum(s_, _DENOM_EPS)).transpose(1, 0, 2)
                return jax.lax.dynamic_update_slice_in_dim(
                    out, o.astype(dt), lo, 0)
            return jax.lax.cond(materialise[row], run, lambda o: o, out), \
                None

        out_mat, _ = jax.lax.scan(one_row, jnp.zeros((T + W, H, dv), dt),
                                  jnp.arange(n))
    return jnp.where(materialise[row_ids][:, None, None], out_mat[:T],
                     out_abs[row_ids])
