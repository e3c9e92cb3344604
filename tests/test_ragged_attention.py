"""The ragged paged attention primitive (ops/ragged_paged_attention):
semantics against a direct-softmax oracle, and the jnp reference
pinned BIT-IDENTICAL to the interpret-mode Pallas kernel — including
every degenerate row shape the serving engine can produce (all-decode,
all-prefill, single row, page-exact chunks, zero-length suffixes)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.ragged_paged_attention import (
    ragged_paged_attention, ragged_paged_attention_packed)


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
    assert np.array_equal(ref, ker), "kernel != reference bit-for-bit"


# Degenerate row shapes, each pinned ref == interpret-kernel BIT-FOR-BIT
# (the serving equivalence guarantees ride on the two paths never
# diverging): all-decode (every row W=1 — the pure decode tick),
# all-prefill (every row a full W chunk), a single row, a chunk exactly
# filling a page (W == page_size, page-aligned start), and a
# zero-length uncached suffix (full prefix hit: the row's queries are
# ALL padding — row-local garbage, but identical garbage on both
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
    assert np.array_equal(ref, ker), case
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
    row-local; the page loop is identical)."""
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
    BlockSpecs — is BIT-IDENTICAL to the jnp reference (dequant shared
    inside _page_update), and both track the dense oracle run on the
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
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(ker))

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
    np.testing.assert_array_equal(np.asarray(r1), np.asarray(k1))
    np.testing.assert_array_equal(np.asarray(r1),
                                  np.asarray(ref)[:, :1])


@pytest.mark.parametrize("H,D", [(2, 16), (4, 32), (3, 16)])
def test_int4_pool_kernel_bit_identical_and_tracks_oracle(H, D):
    """A nibble-packed int4 pool ((uint8 pages, f32 GROUP scales)): the
    interpret Pallas kernel — packed pages and group-scale planes each
    riding their own page-indexed BlockSpecs — is BIT-IDENTICAL to the
    jnp reference (dequant shared via _dequant_page_int4), and both
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
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(ker))

    # semantics: == attention over the explicitly dequantized pool
    kf = _dequantize_kv_int4(kp[0], kp[1], (H, D))
    vf = _dequantize_kv_int4(vp[0], vp[1], (H, D))
    want = _oracle(q, jnp.asarray(kf), jnp.asarray(vf), table, start)
    np.testing.assert_allclose(np.asarray(ref), want, rtol=2e-5,
                               atol=2e-5)

    # W=1 decode rows (the padded degenerate path) carry tuples too.
    # W=1 ref==kernel bit-identity at full-mantissa f32 values is
    # data-dependent on XLA CPU (the documented matvec story — a plain
    # f32 pool with these very values drifts identically), so the
    # format's own guarantee is pinned instead: each int4 path is
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
# each pinned packed-kernel == packed-reference BIT-FOR-BIT on a bf16,
# an int8 AND a nibble-packed int4 pool, and packed == dense per
# position (the A/B-twin guarantee: the same position computed inside
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
    assert np.array_equal(ref, ker), (case, pool)
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
        from paddle_tpu.ops.ragged_paged_attention import \
            _dequant_page_int4
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
    """The packed reference copies a ROW's pages once and every token of
    the row reads that copy: no value of the traced program is larger
    than the per-row gather (n x width x ps x H x D elements) times 2 —
    the float32 window of queries a row-wide stream needs is the one
    thing of that order (here `T` queries a row, bounded by `window`).
    A per-token page table (`page_table[row_ids]`: T x width x ps x H x D,
    16 times the bound at this shape) cannot come back unseen."""
    T, n, MP, ps, H, D, P = 64, 4, 8, 4, 2, 8, 40
    rng = np.random.RandomState(29)
    kp, vp = _pools(29, P, ps, H, D, pool)
    table = jnp.asarray(rng.randint(0, P, (n, MP)).astype(np.int32))
    rows, pos = _pack([(0, 0, 20), (1, 3, 1), (2, 5, 30), (3, 9, 13)])
    q = jnp.asarray(rng.randn(T, H, D).astype(np.float32))
    jaxpr = jax.make_jaxpr(
        lambda *a: ragged_paged_attention_packed(*a, window=window))(
        q, kp, vp, table, rows, pos).jaxpr
    row_gather = n * MP * ps * H * D
    sizes = [(int(np.prod(v.aval.shape)), eqn.primitive.name, v.aval.shape)
             for j in _sub_jaxprs(jaxpr) for eqn in j.eqns
             for v in eqn.outvars if hasattr(v.aval, "shape")]
    assert max(sizes)[0] >= row_gather, "the per-row gather is gone?"
    assert max(sizes)[0] <= 2 * row_gather, max(sizes)
    # and no value carries the stream's T and the table's width together
    assert not [s for s in sizes if len(s[2]) >= 2
                and s[2][0] == T and s[2][1] == MP], sizes


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
