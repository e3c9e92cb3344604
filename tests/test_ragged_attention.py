"""The ragged paged attention primitive (ops/ragged_paged_attention):
semantics against a direct-softmax oracle; the jnp reference (a walk
over blocks of 8 pages, as deep as the deepest row) BIT-IDENTICAL to
itself across window widths, table widths, batch composition and the
two layouts; and the interpret-mode Pallas kernel (a per-page grid the
chip refuses, ROADMAP D3) held to the reference within the tolerance
of the oracle — including every degenerate row shape the serving
engine can produce (all-decode, all-prefill, single row, page-exact
chunks, zero-length suffixes)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.ragged_paged_attention import (
    KEY_BLOCK_PAGES, _dequant_page_int4, ragged_paged_attention,
    ragged_paged_attention_packed)


def _pool(rng, P, ps, H, D):
    kp = jnp.asarray(rng.randn(P, ps, H, D).astype(np.float32))
    vp = jnp.asarray(rng.randn(P, ps, H, D).astype(np.float32))
    return kp, vp


def _oracle(q, kp, vp, table, start, scale=None):
    """Direct masked softmax per (row, query, head) — the semantics the
    online-softmax accumulation must reproduce."""
    q, kp, vp, table = map(np.asarray, (q, kp, vp, table))
    n, W, H, D = q.shape
    ps = kp.shape[1]
    MP = table.shape[1]
    scale = scale or 1.0 / np.sqrt(D)
    kg = kp[np.maximum(table, 0)].reshape(n, MP * ps, H, D)
    vg = vp[np.maximum(table, 0)].reshape(n, MP * ps, H, D)
    out = np.zeros_like(q)
    for i in range(n):
        for w in range(W):
            pos = int(start[i]) + w
            for h in range(H):
                s = (q[i, w, h] * scale) @ kg[i, :, h].T
                s[np.arange(MP * ps) > pos] = -1e30
                p = np.exp(s - s.max())
                p /= p.sum()
                out[i, w, h] = p @ vg[i, :, h]
    return out


def _kernel_tracks(ref, ker, msg=None):
    """The kernel accumulates a page of keys a step, the reference a
    block of eight pages: the same float32 online softmax over the same
    keys in another grouping, so equal to the oracle's tolerance and
    not to the bit."""
    np.testing.assert_allclose(np.asarray(ker, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=2e-5, atol=2e-5, err_msg=str(msg))


def _both(q, kp, vp, table, start):
    ref = np.asarray(ragged_paged_attention(q, kp, vp, table, start))
    ker = np.asarray(ragged_paged_attention(q, kp, vp, table, start,
                                            use_kernel=True))
    return ref, ker


def test_matches_direct_softmax_oracle():
    rng = np.random.RandomState(0)
    n, W, H, D, P, ps, MP = 3, 4, 2, 8, 12, 4, 6
    kp, vp = _pool(rng, P, ps, H, D)
    q = jnp.asarray(rng.randn(n, W, H, D).astype(np.float32))
    table = jnp.asarray(rng.randint(0, P, (n, MP)).astype(np.int32))
    start = jnp.asarray([0, 5, 13], jnp.int32)
    ref, ker = _both(q, kp, vp, table, start)
    np.testing.assert_allclose(
        ref, _oracle(q, kp, vp, table, start), atol=1e-5)
    _kernel_tracks(ref, ker)


# Degenerate row shapes, each holding the interpret kernel to the
# reference: all-decode (every row W=1 — the pure decode tick),
# all-prefill (every row a full W chunk), a single row, a chunk exactly
# filling a page (W == page_size, page-aligned start), and a
# zero-length uncached suffix (full prefix hit: the row's queries are
# ALL padding — row-local garbage, but the same garbage on both
# paths).
@pytest.mark.parametrize("case", ["all_decode", "all_prefill",
                                  "single_row", "page_exact",
                                  "zero_suffix"])
def test_degenerate_shapes_bit_identical(case):
    import zlib
    # crc32, not hash(): PYTHONHASHSEED would randomize the data per
    # process and make any failure unreproducible
    rng = np.random.RandomState(zlib.crc32(case.encode()) % (2 ** 31))
    H, D, P, ps, MP = 2, 8, 10, 4, 5
    kp, vp = _pool(rng, P, ps, H, D)

    if case == "all_decode":
        n, W = 4, 1
        start = [3, 0, 11, 7]
    elif case == "all_prefill":
        n, W = 3, 8
        start = [0, 4, 8]
    elif case == "single_row":
        n, W = 1, 4
        start = [6]
    elif case == "page_exact":
        n, W = 2, ps                 # chunk exactly fills one page
        start = [0, ps]              # page-aligned starts
    else:                            # zero_suffix: full prefix hit —
        n, W = 2, 4                  # row 1's window is pure padding
        start = [2, 17]
    q = jnp.asarray(rng.randn(n, W, H, D).astype(np.float32))
    table = jnp.asarray(rng.randint(0, P, (n, MP)).astype(np.int32))
    start = jnp.asarray(start, jnp.int32)
    ref, ker = _both(q, kp, vp, table, start)
    _kernel_tracks(ref, ker, case)
    assert np.isfinite(ref).all(), case
    # real (non-padding) queries also match the direct-softmax oracle
    oracle = _oracle(q, kp, vp, table, start)
    valid = np.asarray(start)[:, None] + np.arange(W)[None, :] < MP * ps
    np.testing.assert_allclose(np.where(valid[..., None, None], ref, 0),
                               np.where(valid[..., None, None], oracle,
                                        0), atol=1e-5)


def test_decode_row_equals_chunk_row_per_position():
    """Schedule independence, the property the engine equivalences ride
    on: position p computed as a W=1 decode window equals position p
    computed inside a wider chunk window, bit for bit (queries are
    row-local; the walk over key blocks is identical)."""
    rng = np.random.RandomState(7)
    H, D, P, ps, MP = 2, 8, 10, 4, 5
    kp, vp = _pool(rng, P, ps, H, D)
    table = jnp.asarray(rng.randint(0, P, (1, MP)).astype(np.int32))
    W = 4
    qw = jnp.asarray(rng.randn(1, W, H, D).astype(np.float32))
    start = 6
    chunk = np.asarray(ragged_paged_attention(
        qw, kp, vp, table, jnp.asarray([start], jnp.int32)))
    for j in range(W):
        one = np.asarray(ragged_paged_attention(
            qw[:, j:j + 1], kp, vp, table,
            jnp.asarray([start + j], jnp.int32)))
        assert np.array_equal(one[0, 0], chunk[0, j]), j


def test_kernel_scalar_prefetch_routes_pages():
    """The kernel reads pages THROUGH the prefetched table: permuting
    the pool while permuting the table identically leaves the output
    unchanged (the page indirection really is honored)."""
    rng = np.random.RandomState(9)
    H, D, P, ps, MP = 2, 8, 8, 4, 4
    kp, vp = _pool(rng, P, ps, H, D)
    q = jnp.asarray(rng.randn(2, 2, H, D).astype(np.float32))
    table = jnp.asarray([[0, 1, 2, 3], [4, 5, 6, 7]], jnp.int32)
    start = jnp.asarray([5, 9], jnp.int32)
    base = np.asarray(ragged_paged_attention(q, kp, vp, table, start,
                                             use_kernel=True))
    perm = np.asarray([3, 5, 7, 1, 0, 2, 4, 6])
    inv = np.argsort(perm)
    kp2 = jnp.asarray(np.asarray(kp)[perm])
    vp2 = jnp.asarray(np.asarray(vp)[perm])
    table2 = jnp.asarray(inv[np.asarray(table)].astype(np.int32))
    moved = np.asarray(ragged_paged_attention(q, kp2, vp2, table2, start,
                                              use_kernel=True))
    np.testing.assert_array_equal(base, moved)


def test_int8_pool_kernel_bit_identical_and_tracks_oracle():
    """An int8 pool ((pages, per-token scales) tuples): the interpret
    Pallas kernel — scale planes riding their own page-indexed
    BlockSpecs — tracks the jnp reference (dequant shared inside
    _page_update), and both track the dense oracle run on the
    dequantized pool to f32 accumulation tolerance."""
    rng = np.random.RandomState(11)
    P, ps, H, D, n, W, MP = 12, 8, 2, 16, 3, 4, 6
    kq = jnp.asarray(rng.randint(-127, 128, (P, ps, H, D))
                     .astype(np.int8))
    vq = jnp.asarray(rng.randint(-127, 128, (P, ps, H, D))
                     .astype(np.int8))
    ks = jnp.asarray((rng.rand(P, ps) * 0.05 + 1e-3).astype(np.float32))
    vs = jnp.asarray((rng.rand(P, ps) * 0.05 + 1e-3).astype(np.float32))
    q = jnp.asarray(rng.randn(n, W, H, D).astype(np.float32))
    table = jnp.asarray(rng.randint(0, P, (n, MP)).astype(np.int32))
    start = jnp.asarray(rng.randint(0, MP * ps - W, n).astype(np.int32))

    ref = ragged_paged_attention(q, (kq, ks), (vq, vs), table, start,
                                 use_kernel=False)
    ker = ragged_paged_attention(q, (kq, ks), (vq, vs), table, start,
                                 use_kernel=True, interpret=True)
    _kernel_tracks(ref, ker)

    # semantics: == attention over the explicitly dequantized pool
    kf = np.asarray(kq, np.float32) * np.asarray(ks)[..., None, None]
    vf = np.asarray(vq, np.float32) * np.asarray(vs)[..., None, None]
    want = _oracle(q, jnp.asarray(kf), jnp.asarray(vf), table, start)
    np.testing.assert_allclose(np.asarray(ref), want, rtol=2e-5,
                               atol=2e-5)

    # W=1 decode rows (the padded degenerate path) carry tuples too
    r1 = ragged_paged_attention(q[:, :1], (kq, ks), (vq, vs), table,
                                start, use_kernel=False)
    k1 = ragged_paged_attention(q[:, :1], (kq, ks), (vq, vs), table,
                                start, use_kernel=True, interpret=True)
    _kernel_tracks(r1, k1)
    np.testing.assert_array_equal(np.asarray(r1),
                                  np.asarray(ref)[:, :1])


@pytest.mark.parametrize("H,D", [(2, 16), (4, 32), (3, 16)])
def test_int4_pool_kernel_bit_identical_and_tracks_oracle(H, D):
    """A nibble-packed int4 pool ((uint8 pages, f32 GROUP scales)): the
    interpret Pallas kernel — packed pages and group-scale planes each
    riding their own page-indexed BlockSpecs — tracks the jnp
    reference (dequant shared via _dequant_page_int4), and both
    track the dense oracle run on the dequantized pool. Shapes cover
    G=1 (hd == group), G>1 even (hd = 4 groups), and a ragged tail
    group (hd = 48 -> groups of 32 + 16)."""
    from paddle_tpu.serving.decoder import (_dequantize_kv_int4,
                                            _quantize_kv_int4)
    rng = np.random.RandomState(13)
    P, ps, n, W, MP = 12, 8, 3, 4, 6
    kp = _quantize_kv_int4(
        jnp.asarray(rng.randn(P, ps, H, D).astype(np.float32)))
    vp = _quantize_kv_int4(
        jnp.asarray(rng.randn(P, ps, H, D).astype(np.float32)))
    q = jnp.asarray(rng.randn(n, W, H, D).astype(np.float32))
    table = jnp.asarray(rng.randint(0, P, (n, MP)).astype(np.int32))
    start = jnp.asarray(rng.randint(0, MP * ps - W, n).astype(np.int32))

    ref = ragged_paged_attention(q, kp, vp, table, start,
                                 use_kernel=False)
    ker = ragged_paged_attention(q, kp, vp, table, start,
                                 use_kernel=True, interpret=True)
    _kernel_tracks(ref, ker)

    # semantics: == attention over the explicitly dequantized pool
    kf = _dequantize_kv_int4(kp[0], kp[1], (H, D))
    vf = _dequantize_kv_int4(vp[0], vp[1], (H, D))
    want = _oracle(q, jnp.asarray(kf), jnp.asarray(vf), table, start)
    np.testing.assert_allclose(np.asarray(ref), want, rtol=2e-5,
                               atol=2e-5)

    # W=1 decode rows (the padded degenerate path) carry tuples too.
    # The format's own guarantee is pinned to the bit: each int4 path is
    # bit-identical to a plain f32 pool holding the same dequantized
    # values — pack/unpack adds ZERO drift on top of f32 behavior.
    kff, vff = jnp.asarray(np.asarray(kf)), jnp.asarray(np.asarray(vf))
    r1 = ragged_paged_attention(q[:, :1], kp, vp, table, start,
                                use_kernel=False)
    k1 = ragged_paged_attention(q[:, :1], kp, vp, table, start,
                                use_kernel=True, interpret=True)
    r1f = ragged_paged_attention(q[:, :1], kff, vff, table, start,
                                 use_kernel=False)
    k1f = ragged_paged_attention(q[:, :1], kff, vff, table, start,
                                 use_kernel=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(r1), np.asarray(r1f))
    np.testing.assert_array_equal(np.asarray(k1), np.asarray(k1f))


# --------------------------------------------------------------------------
# Packed layout: flat [total_new_tokens] streams with per-token row ids
# --------------------------------------------------------------------------

def _pack(layout):
    """[(row, start, n_tokens), ...] -> (rows, pos) flat vectors."""
    rows, pos = [], []
    for r, start, n in layout:
        rows.extend([r] * n)
        pos.extend(start + j for j in range(n))
    return np.asarray(rows, np.int32), np.asarray(pos, np.int32)


def _pools(case_seed, P, ps, H, D, pool):
    rng = np.random.RandomState(case_seed)
    if pool == "int8":
        kp = (jnp.asarray(rng.randint(-127, 128, (P, ps, H, D))
                          .astype(np.int8)),
              jnp.asarray((rng.rand(P, ps) * 0.05 + 1e-3)
                          .astype(np.float32)))
        vp = (jnp.asarray(rng.randint(-127, 128, (P, ps, H, D))
                          .astype(np.int8)),
              jnp.asarray((rng.rand(P, ps) * 0.05 + 1e-3)
                          .astype(np.float32)))
    elif pool == "int4":
        # nibble-packed (uint8 pages, f32 group scales), via the one
        # write-time quantizer the wired pool uses
        from paddle_tpu.serving.decoder import _quantize_kv_int4
        kp = _quantize_kv_int4(
            jnp.asarray(rng.randn(P, ps, H, D).astype(np.float32)))
        vp = _quantize_kv_int4(
            jnp.asarray(rng.randn(P, ps, H, D).astype(np.float32)))
    else:                                     # bf16 pool
        kp = jnp.asarray(rng.randn(P, ps, H, D)).astype(jnp.bfloat16)
        vp = jnp.asarray(rng.randn(P, ps, H, D)).astype(jnp.bfloat16)
    return kp, vp


# every degenerate stream shape the packed serving path can produce,
# each holding the packed kernel to the packed reference on a bf16,
# an int8 AND a nibble-packed int4 pool, and packed == dense per
# position BIT-FOR-BIT (the A/B-twin guarantee: the same position computed inside
# any dense window is the same bytes): a single token (T=1 — the
# one-live-slot tick), pure decode (every row one token), pure prefill
# (one row's whole chunk), a chunk exactly filling a page, a stream
# exactly at its pow2 bucket boundary with zero padding slack, and the
# late joiners' tick: five decode rows and three chunk rows of unequal
# length in one stream, no chunk page-aligned, every row laid out in the
# window of 8 the decoder would key the program by (`packed_window`).
@pytest.mark.parametrize("pool", ["bf16", "int8", "int4"])
@pytest.mark.parametrize("case", ["single_token", "all_decode",
                                  "all_prefill", "page_exact",
                                  "bucket_boundary", "late_joiners"])
def test_packed_degenerate_shapes_bit_identical(case, pool):
    import zlib
    rng = np.random.RandomState(zlib.crc32(case.encode()) % (2 ** 31))
    H, D, P, ps, MP = 2, 8, 10, 4, 5
    kp, vp = _pools(zlib.crc32((case + pool).encode()) % (2 ** 31),
                    P, ps, H, D, pool)
    n = 8 if case == "late_joiners" else 3
    table = jnp.asarray(rng.randint(0, P, (n, MP)).astype(np.int32))
    window = None
    if case == "late_joiners":
        layout = [(0, 3, 1), (1, 9, 1), (2, 0, 1), (3, 17, 1), (4, 6, 1),
                  (5, 1, 5), (6, 2, 7), (7, 3, 6)]
        window = 8
    elif case == "single_token":
        layout = [(1, 7, 1)]
    elif case == "all_decode":
        layout = [(0, 3, 1), (1, 0, 1), (2, 11, 1)]
    elif case == "all_prefill":
        layout = [(1, 0, 8)]
    elif case == "page_exact":
        layout = [(0, 0, ps), (2, ps, ps)]    # page-aligned full pages
    else:                                     # bucket_boundary: T = 8
        layout = [(0, 2, 4), (1, 6, 3), (2, 9, 1)]   # exactly pow2
    rows, pos = _pack(layout)
    q = jnp.asarray(rng.randn(len(rows), H, D).astype(np.float32))
    if pool == "bf16":
        q = q.astype(jnp.bfloat16)

    ref = np.asarray(ragged_paged_attention_packed(
        q, kp, vp, table, rows, pos, window=window).astype(jnp.float32))
    ker = np.asarray(ragged_paged_attention_packed(
        q, kp, vp, table, rows, pos, use_kernel=True,
        interpret=True).astype(jnp.float32))
    _kernel_tracks(ref, ker, (case, pool))
    assert np.isfinite(ref).all(), (case, pool)

    if pool == "int4":
        # Cross-shape (packed vs dense-window) bit-identity is a
        # property of the VALUE dtype, not the pool format: full-
        # mantissa f32 dequant products round shape-dependently on XLA
        # CPU (bf16/int8 survive because their products are near-exact
        # — the documented W=1 matvec story). Pin the format's own
        # guarantee instead: the nibble-packed pool is bit-identical
        # to a plain f32 pool holding the same dequantized values, on
        # BOTH the packed and the dense path — the pack/unpack
        # machinery adds zero drift on top of f32 behavior.
        kf = jnp.asarray(np.asarray(_dequant_page_int4(kp[0], kp[1],
                                                       (H, D))))
        vf = jnp.asarray(np.asarray(_dequant_page_int4(vp[0], vp[1],
                                                       (H, D))))
        twin = np.asarray(ragged_paged_attention_packed(
            q, kf, vf, table, rows, pos, window=window
        ).astype(jnp.float32))
        np.testing.assert_array_equal(ref, twin, err_msg=str(case))
        t0 = 0
        for r, start, cnt in layout:
            qw = q[t0:t0 + cnt][None]
            d4 = np.asarray(ragged_paged_attention(
                qw, kp, vp, table[r:r + 1],
                jnp.asarray([start], jnp.int32)).astype(jnp.float32))[0]
            df = np.asarray(ragged_paged_attention(
                qw, kf, vf, table[r:r + 1],
                jnp.asarray([start], jnp.int32)).astype(jnp.float32))[0]
            np.testing.assert_array_equal(d4, df, err_msg=str((case, r)))
            t0 += cnt
        return

    # packed == dense per position: each (row, start, n) block computed
    # as ONE dense window must reproduce the packed stream's bytes
    t0 = 0
    for r, start, cnt in layout:
        qw = q[t0:t0 + cnt][None]             # [1, cnt, H, D]
        dense = np.asarray(ragged_paged_attention(
            qw, kp, vp, table[r:r + 1],
            jnp.asarray([start], jnp.int32)).astype(jnp.float32))[0]
        assert np.array_equal(dense, ref[t0:t0 + cnt]), (case, pool, r)
        t0 += cnt


def test_packed_kernel_scalar_prefetch_routes_rows_and_pages():
    """The packed kernel resolves pages through TWO prefetched
    indirections (row_ids -> table row -> page): permuting the pool
    with an inverse-permuted table, and renumbering the table rows
    with matching row_ids, both leave the output unchanged."""
    rng = np.random.RandomState(13)
    H, D, P, ps, MP = 2, 8, 8, 4, 4
    kp = jnp.asarray(rng.randn(P, ps, H, D).astype(np.float32))
    vp = jnp.asarray(rng.randn(P, ps, H, D).astype(np.float32))
    table = jnp.asarray([[0, 1, 2, 3], [4, 5, 6, 7]], jnp.int32)
    rows = jnp.asarray([0, 1, 1], jnp.int32)
    pos = jnp.asarray([5, 9, 10], jnp.int32)
    q = jnp.asarray(rng.randn(3, H, D).astype(np.float32))
    base = np.asarray(ragged_paged_attention_packed(
        q, kp, vp, table, rows, pos, use_kernel=True))
    # pool permutation behind the table
    perm = np.asarray([3, 5, 7, 1, 0, 2, 4, 6])
    inv = np.argsort(perm)
    moved = np.asarray(ragged_paged_attention_packed(
        q, jnp.asarray(np.asarray(kp)[perm]),
        jnp.asarray(np.asarray(vp)[perm]),
        jnp.asarray(inv[np.asarray(table)].astype(np.int32)),
        rows, pos, use_kernel=True))
    np.testing.assert_array_equal(base, moved)
    # table-row renumbering behind row_ids
    swapped = np.asarray(ragged_paged_attention_packed(
        q, kp, vp, jnp.asarray(np.asarray(table)[::-1].copy()),
        jnp.asarray([1, 0, 0], jnp.int32), pos, use_kernel=True))
    np.testing.assert_array_equal(base, swapped)


def test_packed_attention_int8_tracks_dense_oracle():
    """int8 (pages, scales) pools flow through the packed entry point
    unchanged: packed output == the dense int8 path per position."""
    rng = np.random.RandomState(17)
    H, D, P, ps, MP = 2, 16, 12, 8, 6
    kp, vp = _pools(17, P, ps, H, D, "int8")
    table = jnp.asarray(rng.randint(0, P, (2, MP)).astype(np.int32))
    rows, pos = _pack([(0, 4, 3), (1, 20, 1)])
    q = jnp.asarray(rng.randn(len(rows), H, D).astype(np.float32))
    packed = np.asarray(ragged_paged_attention_packed(
        q, kp, vp, table, rows, pos))
    dense0 = np.asarray(ragged_paged_attention(
        q[:3][None], kp, vp, table[:1], jnp.asarray([4], jnp.int32)))[0]
    dense1 = np.asarray(ragged_paged_attention(
        q[3:][None], kp, vp, table[1:], jnp.asarray([20], jnp.int32)))[0]
    assert np.array_equal(packed[:3], dense0)
    assert np.array_equal(packed[3:], dense1)


def _sub_jaxprs(jaxpr):
    """`jaxpr` and every jaxpr nested in its equations' parameters."""
    yield jaxpr
    for eqn in jaxpr.eqns:
        for v in eqn.params.values():
            for x in (v if isinstance(v, (tuple, list)) else (v,)):
                inner = getattr(x, "jaxpr", x)
                if hasattr(inner, "eqns"):
                    yield from _sub_jaxprs(inner)


@pytest.mark.parametrize("pool", ["bf16", "int8", "int4"])
@pytest.mark.parametrize("window", [None, 32])
def test_packed_reference_gathers_by_row_not_by_token(pool, window):
    """The packed reference copies a block of a ROW's pages once a step
    and every token of the row reads that copy: the largest values of
    the traced program are the step's own (a block of 8 pages of every
    row; its float32 scores, one row of them a query of the window). A
    per-token page table (`page_table[row_ids]`: T x width x ps x H x D,
    four times the bound at this shape) cannot come back unseen, nor the
    copy of the whole table (n x width pages) before the walk."""
    T, n, MP, ps, H, D, P = 64, 4, 16, 4, 2, 8, 40
    rng = np.random.RandomState(29)
    kp, vp = _pools(29, P, ps, H, D, pool)
    table = jnp.asarray(rng.randint(0, P, (n, MP)).astype(np.int32))
    rows, pos = _pack([(0, 0, 20), (1, 3, 1), (2, 5, 30), (3, 9, 13)])
    q = jnp.asarray(rng.randn(T, H, D).astype(np.float32))
    jaxpr = jax.make_jaxpr(
        lambda *a: ragged_paged_attention_packed(*a, window=window))(
        q, kp, vp, table, rows, pos).jaxpr
    kb = KEY_BLOCK_PAGES * ps
    block_copy = n * kb * H * D
    scores = n * H * (window or T) * kb
    sizes = [(int(np.prod(v.aval.shape)), eqn.primitive.name, v.aval.shape)
             for j in _sub_jaxprs(jaxpr) for eqn in j.eqns
             for v in eqn.outvars if hasattr(v.aval, "shape")]
    assert max(sizes)[0] == max(block_copy, scores), max(sizes)
    # no value carries the stream's T and the table's width together,
    # and none is a row's pages by the table's width
    assert not [s for s in sizes if len(s[2]) > 2
                and s[2][:2] in ((T, MP), (n, MP))], sizes


# ------------------------------------------- the walk ends at the deepest row

def _poisoned(pool, pages):
    """`pool` with NaN in every one of `pages`: in the payload of a
    float pool, in the scales of a quantized one (a key or value read
    from such a page is NaN whatever its mask)."""
    if isinstance(pool, tuple):
        return pool[0], pool[1].at[pages].set(jnp.nan)
    return pool.at[pages].set(jnp.nan)


def _as_float(pool, H, D):
    if not isinstance(pool, tuple):
        return pool.astype(jnp.float32)
    payload, scales = pool
    if payload.dtype == jnp.uint8:
        return _dequant_page_int4(payload, scales, (H, D))
    return payload.astype(jnp.float32) * scales[..., None, None]


def _entry(entry, q, kp, vp, table, layout):
    """The rows of `layout` ((row, start, tokens)) through the dense
    entry (one window of the widest row's tokens) or the packed one."""
    if entry == "dense":
        W = max(cnt for _, _, cnt in layout)
        start = jnp.asarray([st for _, st, _ in layout], jnp.int32)
        return np.asarray(ragged_paged_attention(
            q[:len(layout) * W].reshape(len(layout), W, *q.shape[1:]),
            kp, vp, table, start).astype(jnp.float32))
    rows, pos = _pack(layout)
    return np.asarray(ragged_paged_attention_packed(
        q[:len(rows)], kp, vp, table, rows, pos).astype(jnp.float32))


@pytest.mark.parametrize("pool", ["bf16", "int8", "int4"])
@pytest.mark.parametrize("entry", ["dense", "packed"])
def test_blocks_past_the_deepest_row_are_never_read(pool, entry):
    """The walk's trip count follows the deepest row of the batch, not
    the table: with NaN in every page of the blocks past it the output
    is finite and the bits of the clean pool's. A block that is read
    poisons (a masked key's value still meets `p` = 0.0): a row one
    block deeper shows it."""
    H, D, ps, MP, n = 2, 8, 4, 32, 3
    kb = KEY_BLOCK_PAGES * ps                     # 32 keys: 4 blocks
    kp, vp = _pools(53, n * MP, ps, H, D, pool)
    table = jnp.arange(n * MP, dtype=jnp.int32).reshape(n, MP)
    rng = np.random.RandomState(53)
    q = jnp.asarray(rng.randn(16, H, D).astype(np.float32))
    # the deepest query sits at 43: blocks 0 and 1 hold it
    layout = [(0, 3, 1), (1, 40, 4), (2, 17, 2)]
    assert max(st + cnt - 1 for _, st, cnt in layout) // kb == 1
    past = table[:, 2 * KEY_BLOCK_PAGES:].reshape(-1)
    clean = _entry(entry, q, kp, vp, table, layout)
    dirty = _entry(entry, q, _poisoned(kp, past), _poisoned(vp, past),
                   table, layout)
    assert np.isfinite(dirty).all()
    np.testing.assert_array_equal(clean, dirty)
    deeper = [(0, 3, 1), (1, 2 * kb + 8, 4), (2, 17, 2)]
    assert not np.isfinite(_entry(
        entry, q, _poisoned(kp, past), _poisoned(vp, past), table,
        deeper)).all()


@pytest.mark.parametrize("pool", ["bf16", "int8", "int4"])
@pytest.mark.parametrize("entry", ["dense", "packed"])
def test_table_width_does_not_move_a_bit(pool, entry):
    """The same rows through tables of 4, 8, 32 and 64 columns (what
    `engine._table_width` may hand over for one context) give the same
    bits: a step is always a block of 8 pages, narrower tables padded
    with columns no query sees, and a block past a query is an exact
    no-op."""
    H, D, ps, P = 2, 8, 4, 40
    kp, vp = _pools(59, P, ps, H, D, pool)
    rng = np.random.RandomState(59)
    table = jnp.asarray(rng.randint(0, P, (3, 64)).astype(np.int32))
    q = jnp.asarray(rng.randn(12, H, D).astype(np.float32))
    layout = [(0, 0, 4), (1, 5, 3), (2, 11, 4)]      # within 4 pages
    outs = [_entry(entry, q, kp, vp, table[:, :w], layout)
            for w in (4, 8, 32, 64)]
    for out in outs[1:]:
        np.testing.assert_array_equal(outs[0], out)


@pytest.mark.parametrize("pool", ["bf16", "int8", "int4"])
def test_a_table_of_five_columns_matches_the_oracle(pool):
    """A width that is no multiple of the block is padded to one: the
    five real columns give the oracle's attention."""
    H, D, ps, P, MP, W = 2, 8, 4, 12, 5, 4
    kp, vp = _pools(61, P, ps, H, D, pool)
    rng = np.random.RandomState(61)
    table = jnp.asarray(rng.randint(0, P, (3, MP)).astype(np.int32))
    q = jnp.asarray(rng.randn(3, W, H, D).astype(np.float32))
    start = jnp.asarray([0, 5, 16], jnp.int32)
    got = np.asarray(ragged_paged_attention(q, kp, vp, table, start))
    want = _oracle(q, _as_float(kp, H, D), _as_float(vp, H, D), table, start)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("pool", ["bf16", "int8", "int4"])
@pytest.mark.parametrize("entry", ["dense", "packed"])
def test_trip_count_is_read_from_the_positions(pool, entry):
    """One loop, whose bound is a value of the program: no `scan` (a
    loop of a length fixed when traced, the table's) and one `while`
    whose trip count is computed from the positions, in the jaxpr and
    in the lowered text."""
    H, D, ps, P, MP = 2, 8, 4, 40, 64
    kp, vp = _pools(67, P, ps, H, D, pool)
    rng = np.random.RandomState(67)
    table = jnp.asarray(rng.randint(0, P, (3, MP)).astype(np.int32))
    if entry == "dense":
        q = jnp.asarray(rng.randn(3, 4, H, D).astype(np.float32))
        fn = lambda start: ragged_paged_attention(q, kp, vp, table, start)
        arg = jnp.asarray([0, 5, 16], jnp.int32)
    else:
        rows, pos = _pack([(0, 0, 4), (1, 5, 3), (2, 11, 4)])
        q = jnp.asarray(rng.randn(len(rows), H, D).astype(np.float32))
        fn = lambda pos: ragged_paged_attention_packed(
            q, kp, vp, table, rows, pos)
        arg = jnp.asarray(pos)
    eqns = [e for j in _sub_jaxprs(jax.make_jaxpr(fn)(arg).jaxpr)
            for e in j.eqns]
    names = [e.primitive.name for e in eqns]
    assert "scan" not in names and names.count("while") == 1
    loop = eqns[names.index("while")]
    # (lower, upper, carry...) after the constants: the upper bound is a
    # variable of the program, not a literal of the trace
    upper = loop.invars[loop.params["cond_nconsts"]
                        + loop.params["body_nconsts"] + 1]
    assert not hasattr(upper, "val"), upper       # a Var, not a Literal
    text = jax.jit(fn).lower(arg).as_text()
    assert text.count("stablehlo.while") == 1


# ------------------------------------------------ the layer of a whole pool

def _whole_pools(seed, L, P, ps, H, D, pool):
    """Whole [L, P, ...] pools whose layers are drawn apart, and the list
    of the one-layer pools they were stacked from."""
    layers = [_pools(seed + 101 * li, P, ps, H, D, pool) for li in range(L)]

    def stack(parts):
        return jax.tree_util.tree_map(lambda *x: jnp.stack(x), *parts)

    return (stack([kp for kp, _ in layers]),
            stack([vp for _, vp in layers]), layers)


@pytest.mark.parametrize("pool", ["bf16", "int8", "int4"])
@pytest.mark.parametrize("entry", ["dense", "packed", "packed_kernel"])
def test_layer_of_a_whole_pool_reads_that_layer_bit_for_bit(pool, entry):
    """`layer=` on a whole [L, P, ...] pool is the one-layer call on that
    layer, bit for bit, for every layer, on all three pool layouts
    (payload and scales alike), through the dense and the packed entry
    point and through the interpret-mode kernel — with the layer a TRACED
    scalar, as the decoder's layer loop hands it over."""
    L, n, MP, ps, H, D, P = 3, 2, 4, 4, 2, 16, 10
    rng = np.random.RandomState(41)
    kw, vw, layers = _whole_pools(41, L, P, ps, H, D, pool)
    table = jnp.asarray(rng.randint(0, P, (n, MP)).astype(np.int32))
    if entry == "dense":
        q = jnp.asarray(rng.randn(n, 3, H, D).astype(np.float32))
        rest = (table, jnp.asarray([2, 9], jnp.int32))
        fn, kw_args = ragged_paged_attention, {}
    else:
        rows, pos = _pack([(0, 4, 3), (1, 11, 1)])
        q = jnp.asarray(rng.randn(len(rows), H, D).astype(np.float32))
        rest = (table, rows, pos)
        fn = ragged_paged_attention_packed
        kw_args = {"use_kernel": entry == "packed_kernel"}
    by_layer = jax.jit(lambda li: fn(q, kw, vw, *rest, layer=li, **kw_args))
    outs = []
    for li, (kp, vp) in enumerate(layers):
        alone = np.asarray(fn(q, kp, vp, *rest, **kw_args)
                           .astype(jnp.float32))
        whole = np.asarray(by_layer(jnp.int32(li)).astype(jnp.float32))
        assert np.array_equal(alone, whole), (pool, entry, li)
        outs.append(whole)
    assert not np.array_equal(outs[0], outs[1])      # the layers differ


@pytest.mark.parametrize("pool", ["bf16", "int8", "int4"])
@pytest.mark.parametrize("entry", ["dense", "packed"])
def test_reference_reads_the_layer_through_the_gather(pool, entry):
    """The reference never slices its layer out of the whole pool: no
    value of the traced program has the size of a layer of the pool (the
    largest is the per-row gather), and no `dynamic_slice` is taken of a
    pool leaf. `pool[layer][pages]` would copy the layer once a layer and
    tick — what the layer loop's carry exists to avoid."""
    L, n, MP, ps, H, D, P = 3, 2, 4, 4, 2, 16, 64
    rng = np.random.RandomState(43)
    kw, vw, _ = _whole_pools(43, L, P, ps, H, D, pool)
    table = jnp.asarray(rng.randint(0, P, (n, MP)).astype(np.int32))
    if entry == "dense":
        q = jnp.asarray(rng.randn(n, 3, H, D).astype(np.float32))
        call = lambda li: ragged_paged_attention(
            q, kw, vw, table, jnp.asarray([2, 9], jnp.int32), layer=li)
    else:
        rows, pos = _pack([(0, 4, 3), (1, 11, 1)])
        q = jnp.asarray(rng.randn(len(rows), H, D).astype(np.float32))
        call = lambda li: ragged_paged_attention_packed(
            q, kw, vw, table, rows, pos, layer=li)
    jaxpr = jax.make_jaxpr(call)(jnp.int32(1)).jaxpr
    payload = jax.tree_util.tree_leaves(kw)[0]
    layer_elems = int(np.prod(payload.shape[1:]))
    eqns = [e for j in _sub_jaxprs(jaxpr) for e in j.eqns]
    sizes = [(int(np.prod(v.aval.shape)), e.primitive.name)
             for e in eqns for v in e.outvars if hasattr(v.aval, "shape")]
    assert max(sizes)[0] < layer_elems, max(sizes)
    leaf_shapes = {leaf.shape for leaf in jax.tree_util.tree_leaves((kw, vw))}
    sliced = [e for e in eqns
              if e.primitive.name in ("dynamic_slice", "slice", "squeeze")
              and e.invars[0].aval.shape in leaf_shapes]
    assert not sliced, sliced


# ------------------------------------------- the grouped (GQA) packed walk

def _grouped_case(seed, H, Hk, D=8, P=12, ps=4, MP=6, L=2):
    """A whole [L, P, ps, 2 x Hk x D] grouped pool (keys, then values), its
    keys and values split out, a table, and the late joiners' stream: two
    decode rows and two chunk rows of unequal length."""
    rng = np.random.RandomState(seed)
    kv = jnp.asarray(rng.randn(L, P, ps, 2 * Hk * D).astype(np.float32))
    k = kv[..., :Hk * D].reshape(L, P, ps, Hk, D)
    v = kv[..., Hk * D:].reshape(L, P, ps, Hk, D)
    table = jnp.asarray(rng.permutation(P)[:4 * MP].reshape(4, MP)
                        if 4 * MP <= P else
                        rng.randint(0, P, (4, MP)).astype(np.int32),
                        jnp.int32)
    rows, pos = _pack([(0, 9, 1), (1, 0, 7), (2, 14, 1), (3, 5, 6)])
    q = jnp.asarray(rng.randn(len(rows), H, D).astype(np.float32))
    return kv, k, v, table, rows, pos, q


@pytest.mark.parametrize("H,Hk", [(4, 2), (8, 2), (4, 1)])
def test_grouped_walk_matches_the_oracle(H, Hk):
    """Query head h over key/value head h // (H / Hk) of one grouped pool
    entry, against the direct-softmax oracle over keys and values repeated
    to every query head; a grouping h % Hk is not what it computes."""
    kv, k, v, table, rows, pos, q = _grouped_case(7 + H + Hk, H, Hk)
    ours, swapped = np.arange(H) // (H // Hk), np.arange(H) % Hk
    got = np.asarray(ragged_paged_attention_packed(
        q, kv, None, table, rows, pos, window=8, layer=jnp.int32(1)))
    for r in range(4):
        sel = np.asarray(rows) == r
        start = int(np.asarray(pos)[sel][0])
        for grouping in (ours, swapped):
            want = _oracle(np.asarray(q)[sel][None], k[1][..., grouping, :],
                           v[1][..., grouping, :], table[r:r + 1],
                           np.asarray([start]))[0]
            close = np.allclose(got[sel], want, atol=2e-5)
            # one key/value head: both groupings are the same
            assert close == (grouping is ours
                             or np.array_equal(ours, swapped)), (r, grouping)


def test_grouped_walk_at_equal_heads_is_the_split_walk_bit_for_bit():
    """With as many key/value heads as query heads, the grouped form (one
    pool, keys then values) gives the bytes of the existing walk over the
    two split pools: the fold is the identity and the block's keys and
    values are the same numbers."""
    H = 2
    kv, k, v, table, rows, pos, q = _grouped_case(3, H, H)
    for window in (None, 8):
        grouped = ragged_paged_attention_packed(
            q, kv, None, table, rows, pos, window=window, layer=jnp.int32(0))
        split = ragged_paged_attention_packed(
            q, k, v, table, rows, pos, window=window, layer=jnp.int32(0))
        assert np.array_equal(np.asarray(grouped), np.asarray(split))


def test_grouped_walk_is_one_walk_for_every_group():
    """The G query heads of a key/value head ride one walk: each step
    gathers the block once, [n, block, 2 x Hk x D] (no key repeated to
    the query heads), and its product has G x W rows."""
    H, Hk, W = 4, 2, 8
    kv, _, _, table, rows, pos, q = _grouped_case(5, H, Hk)
    jaxpr = jax.make_jaxpr(lambda li: ragged_paged_attention_packed(
        q, kv, None, table, rows, pos, window=W, layer=li))(
        jnp.int32(0)).jaxpr
    eqns = [e for j in _sub_jaxprs(jaxpr) for e in j.eqns]
    gathers = [e.outvars[0].aval.shape for e in eqns
               if e.primitive.name == "gather"
               and e.outvars[0].aval.shape[-1] == kv.shape[-1]]
    assert gathers and all(s[-1] == 2 * Hk * 8 for s in gathers)
    dots = [e.outvars[0].aval.shape for e in eqns
            if e.primitive.name == "dot_general"]
    assert (4, Hk, (H // Hk) * W, KEY_BLOCK_PAGES * 4) in dots, dots
