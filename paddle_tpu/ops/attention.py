"""Flash attention for TPU.

Replaces the reference's fused_attention CUDA op
(paddle/fluid/operators/fused/fused_attention_op.cu) with a Pallas kernel
tiled for MXU/VMEM. The jnp fallback keeps CPU tests and odd shapes working;
`flash_attention` dispatches.

Layout convention is paddle's: [batch, seq, heads, head_dim].

Feature set (all with the fused online-softmax kernel, fwd + bwd):
- causal masking (block-skip on the k loop)
- GQA (fewer K/V heads; query heads folded into the row axis)
- additive or boolean attention masks: padding-style masks ([B, Lk]-ish)
  stream as an O(L) bias; general masks broadcastable to [B, H, Lq, Lk]
  stream blockwise from HBM (the mask is O(L^2) wherever it lives, but the
  probability matrix is never materialized and the matmuls stay fused)
- dropout on the attention probabilities, computed inside the kernel from a
  counter-based hash of (seed, batch, kv-head, row, col) — the backward pass
  regenerates the identical mask, nothing is stored
- sequence lengths that are not multiples of 128 (padded + masked here, so
  callers always hit the kernel)
"""
import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
import numpy as np

from ..framework.core import Tensor, apply_op
from ._fallback import kernel_fallback
from ._per_device import BATCH_AXES, dim_axes, kernel_mesh

__all__ = ["flash_attention", "flash_attention_available", "mha_reference"]

import os

# Tile sizes for the flash kernel grid; overridable via env or
# incubate.autotune.tune_flash_attention (multiples of 128 — the MXU/VREG
# lane width). 512x512 measured 4% faster than 256x256 on GPT-1.3B
# bs4/seq1024 (v5e); sweeps clamp to the actual sequence length.


def _env_block(name, default):
    try:
        v = int(os.environ.get(name, default))
    except ValueError:
        return default
    # normalize to a positive multiple of 128 so _block's descending walk
    # always reaches the 128 fallback
    return max(128, (v // 128) * 128)


_BLOCK_Q = _env_block("PADDLE_TPU_FLASH_BLOCK_Q", 512)
_BLOCK_K = _env_block("PADDLE_TPU_FLASH_BLOCK_K", 512)
_NEG = -1e30


def _on_tpu():
    try:
        return jax.default_backend() not in ("cpu",)
    except Exception:
        return False


def flash_attention_available(query, attn_mask, dropout_p):
    """Masks and dropout now run inside the kernel; the only remaining gate
    is the head_dim tiling and the backend."""
    D = query.shape[3]
    return _on_tpu() and D in (64, 128, 256)


# ---------------------------------------------------------------------------
# Deterministic dropout hash — identical math inside the Pallas kernels, the
# jnp reference, and (for tests) numpy. lowbias32 finalizer on a position
# counter; keep iff hash >= rate * 2^32.
# ---------------------------------------------------------------------------
_K_ROW = 0x9E3779B1
_K_COL = 0x85EBCA77
_K_B = 0xC2B2AE3D
_K_H = 0x27D4EB2F


def _hash32(x):
    x = x.astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    return x


def _drop_salt(seed_u32, b, h):
    return _hash32(seed_u32
                   ^ (jnp.uint32(b) * jnp.uint32(_K_B))
                   ^ (jnp.uint32(h) * jnp.uint32(_K_H)))


def _rate_thresh(rate):
    return jnp.uint32(min(int(float(rate) * 4294967296.0), 4294967295))


def _keep_tile(salt, rows, cols, rate):
    """Boolean keep-mask [len(rows), len(cols)] from absolute positions."""
    r = rows.astype(jnp.uint32)[:, None] * jnp.uint32(_K_ROW)
    c = cols.astype(jnp.uint32)[None, :] * jnp.uint32(_K_COL)
    return _hash32(r ^ c ^ salt) >= _rate_thresh(rate)


def mha_reference(q, k, v, causal=False, scale=None, attn_mask=None,
                  dropout_rate=0.0, dropout_seed=0):
    """jnp reference (fp32 softmax) — [B,L,H,D] in/out. Supports GQA
    (fewer K/V heads: Hq % Hkv == 0), an additive attn_mask broadcastable
    to [B, H, Lq, Lk] (bool masks: True = keep), and hash-based dropout that
    reproduces the Pallas kernel's pattern exactly (same seed ⇒ same mask)."""
    scale = scale if scale is not None else 1.0 / np.sqrt(q.shape[-1])
    hq, hkv = q.shape[2], k.shape[2]
    if hq != hkv:
        rep = hq // hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    qh, kh, vh = (jnp.swapaxes(t, 1, 2) for t in (q, k, v))
    logits = (qh @ jnp.swapaxes(kh, -1, -2)).astype(jnp.float32) * scale
    if attn_mask is not None:
        if attn_mask.dtype == jnp.bool_:
            logits = jnp.where(attn_mask, logits, _NEG)
        else:
            logits = logits + attn_mask.astype(jnp.float32)
    if causal:
        L, S = logits.shape[-2], logits.shape[-1]
        logits = jnp.where(jnp.tril(jnp.ones((L, S), bool)), logits, _NEG)
    probs = jax.nn.softmax(logits, axis=-1)
    if dropout_rate:
        B, H, Lq, Lk = probs.shape
        g = hq // hkv
        lq_real = Lq
        seed_u = jnp.asarray(dropout_seed).astype(jnp.int32).astype(jnp.uint32)
        # kernel coordinates: (b, h_kv, folded_row = (h % g) * Lq + row, col)
        bidx = jnp.arange(B, dtype=jnp.uint32)[:, None, None, None]
        hidx = jnp.arange(H, dtype=jnp.uint32)[None, :, None, None]
        rows = jnp.arange(Lq, dtype=jnp.uint32)[None, None, :, None]
        cols = jnp.arange(Lk, dtype=jnp.uint32)[None, None, None, :]
        hkv_idx = hidx // jnp.uint32(g)
        frow = (hidx % jnp.uint32(g)) * jnp.uint32(lq_real) + rows
        salt = _hash32(seed_u
                       ^ (bidx * jnp.uint32(_K_B))
                       ^ (hkv_idx * jnp.uint32(_K_H)))
        keep = _hash32(frow * jnp.uint32(_K_ROW)
                       ^ cols * jnp.uint32(_K_COL)
                       ^ salt) >= _rate_thresh(dropout_rate)
        probs = probs * keep.astype(probs.dtype) / (1.0 - dropout_rate)
    probs = probs.astype(q.dtype)
    return jnp.swapaxes(probs @ vh, 1, 2)


def _block(L, pref):
    """Largest multiple-of-128 tile <= pref dividing L, else L itself — the
    grids below use exact tiling (L // block), so the block MUST divide L.
    Descending multiples (not just {pref, 128}) so e.g. L=768 still tiles
    at 256 when pref is 512."""
    cand = pref
    while cand >= 128:
        if L % cand == 0:
            return cand
        cand -= 128
    return L


def _fold_gqa(qh, hkv):
    """(B, Hq, Lq, D) -> (B, Hkv, G*Lq, D): query heads sharing a KV head are
    stacked along the row axis (rows are independent in attention). Head
    ordering is h = h_kv * G + g, matching repeat-interleave GQA."""
    B, Hq, Lq, D = qh.shape
    g = Hq // hkv
    return qh.reshape(B, hkv, g * Lq, D), Lq


def _unfold_gqa(out, hq, lq):
    B, hkv, gl, D = out.shape
    return out.reshape(B, hq, lq, D)


# ---------------------------------------------------------------------------
# Pallas kernels: online-softmax flash attention (fwd + lse) with custom VJP
# (bwd recomputes probabilities blockwise — standard flash backward). All
# three kernels share the same optional-ref convention: after q/k/v (and
# do/lse/delta for the backward) come, in order and only when enabled:
#   kvb_ref  — (1, Lk) f32 additive bias broadcast over rows (padding masks)
#   fb_ref   — blockwise tile of a full additive bias [Bm, Hm, fb_rows, Lk]
#   seed_ref — (1, 1) f32 dropout seed
# ---------------------------------------------------------------------------


def _fwd_kernel(*refs, scale, causal, block_k, seq_k, seq_q_real,
                has_kvb, has_fb, fb_rows, rate):
    from jax.experimental import pallas as pl

    refs = list(refs)
    q_ref, k_ref, v_ref = refs[:3]
    rest = refs[3:]
    kvb_ref = rest.pop(0) if has_kvb else None
    fb_ref = rest.pop(0) if has_fb else None
    seed_ref = rest.pop(0) if rate else None
    o_ref, lse_ref = rest

    q = q_ref[0, 0].astype(jnp.float32) * scale  # [bq, d]
    bq, d = q.shape
    q_idx = pl.program_id(2)
    row0_f = q_idx * bq                               # absolute folded row
    # with GQA the group is folded into the row axis; causal positions are
    # modulo the real sequence length (blocks never straddle heads: bq | Lq)
    row0 = row0_f if seq_q_real is None else row0_f % seq_q_real
    if rate:
        seed_u = seed_ref[0, 0].astype(jnp.int32).astype(jnp.uint32)
        salt = _drop_salt(seed_u, pl.program_id(0), pl.program_id(1))

    m = jnp.full((bq, 1), _NEG, jnp.float32)
    l = jnp.zeros((bq, 1), jnp.float32)
    acc = jnp.zeros((bq, d), jnp.float32)
    n_k = seq_k // block_k

    def body(i, carry):
        m, l, acc = carry
        k = k_ref[0, 0, pl.dslice(i * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, 0, pl.dslice(i * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # [bq, bk]
        if has_kvb:
            s = s + kvb_ref[0, 0, pl.dslice(i * block_k, block_k)][None, :]
        if has_fb:
            s = s + fb_ref[0, 0, :, pl.dslice(i * block_k, block_k)]
        if causal:
            q_pos = row0 + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)
            k_pos = i * block_k + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        if causal:
            p = jnp.where(q_pos >= k_pos, p, 0.0)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        if rate:
            rows = row0_f + jnp.arange(bq, dtype=jnp.int32)
            cols = i * block_k + jnp.arange(block_k, dtype=jnp.int32)
            keep = _keep_tile(salt, rows, cols, rate)
            p_use = p * keep.astype(jnp.float32) / (1.0 - rate)
        else:
            p_use = p
        acc_new = acc * corr + p_use @ v
        return m_new, l_new, acc_new

    if causal:
        # only k-blocks at or before this q-block's end participate
        q_end = row0 + bq
        n_live = jnp.minimum((q_end + block_k - 1) // block_k, n_k)
        m, l, acc = jax.lax.fori_loop(0, n_live, body, (m, l, acc))
    else:
        m, l, acc = jax.lax.fori_loop(0, n_k, body, (m, l, acc))
    lsafe = jnp.maximum(l, 1e-30)
    o_ref[0, 0] = (acc / lsafe).astype(o_ref.dtype)
    lse_ref[0, 0] = m + jnp.log(lsafe)          # (bq, 1) trailing unit lane


def _bwd_dq_kernel(*refs, scale, causal, block_k, seq_k, seq_q_real,
                   has_kvb, has_fb, fb_rows, rate):
    """dQ = sum_k dS @ K with dS = P * (D·dP - delta) * scale, P recomputed
    blockwise from the saved logsumexp (standard flash backward; D is the
    regenerated dropout keep/(1-rate) factor)."""
    from jax.experimental import pallas as pl

    refs = list(refs)
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = refs[:6]
    rest = refs[6:]
    kvb_ref = rest.pop(0) if has_kvb else None
    fb_ref = rest.pop(0) if has_fb else None
    seed_ref = rest.pop(0) if rate else None
    dq_ref, = rest

    q = q_ref[0, 0].astype(jnp.float32)
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0].astype(jnp.float32)          # (bq, 1)
    delta = delta_ref[0, 0].astype(jnp.float32)      # (bq, 1)
    bq, d = q.shape
    q_idx = pl.program_id(2)
    row0_f = q_idx * bq
    row0 = row0_f if seq_q_real is None else row0_f % seq_q_real
    if rate:
        seed_u = seed_ref[0, 0].astype(jnp.int32).astype(jnp.uint32)
        salt = _drop_salt(seed_u, pl.program_id(0), pl.program_id(1))
    n_k = seq_k // block_k
    dq = jnp.zeros((bq, d), jnp.float32)

    def body(i, dq):
        k = k_ref[0, 0, pl.dslice(i * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, 0, pl.dslice(i * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
        if has_kvb:
            s = s + kvb_ref[0, 0, pl.dslice(i * block_k, block_k)][None, :]
        if has_fb:
            s = s + fb_ref[0, 0, :, pl.dslice(i * block_k, block_k)]
        if causal:
            q_pos = row0 + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)
            k_pos = i * block_k + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())))
        if rate:
            rows = row0_f + jnp.arange(bq, dtype=jnp.int32)
            cols = i * block_k + jnp.arange(block_k, dtype=jnp.int32)
            keep = _keep_tile(salt, rows, cols, rate)
            dp = dp * keep.astype(jnp.float32) / (1.0 - rate)
        ds = p * (dp - delta) * scale
        return dq + ds @ k

    if causal:
        q_end = row0 + bq
        n_live = jnp.minimum((q_end + block_k - 1) // block_k, n_k)
        dq = jax.lax.fori_loop(0, n_live, body, dq)
    else:
        dq = jax.lax.fori_loop(0, n_k, body, dq)
    dq_ref[0, 0] = dq.astype(dq_ref.dtype)


def _bwd_dkv_kernel(*refs, scale, causal, block_q, seq_q, seq_q_real,
                    has_kvb, has_fb, fb_rows, rate):
    """dK/dV for one k block, looping over q blocks."""
    from jax.experimental import pallas as pl

    refs = list(refs)
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = refs[:6]
    rest = refs[6:]
    kvb_ref = rest.pop(0) if has_kvb else None
    fb_ref = rest.pop(0) if has_fb else None
    seed_ref = rest.pop(0) if rate else None
    dk_ref, dv_ref = rest

    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    bk, d = k.shape
    k_idx = pl.program_id(2)
    if rate:
        seed_u = seed_ref[0, 0].astype(jnp.int32).astype(jnp.uint32)
        salt = _drop_salt(seed_u, pl.program_id(0), pl.program_id(1))
    n_q = seq_q // block_q
    dk = jnp.zeros((bk, d), jnp.float32)
    dv = jnp.zeros((bk, d), jnp.float32)

    def body(i, carry):
        dk, dv = carry
        q = q_ref[0, 0, pl.dslice(i * block_q, block_q), :].astype(jnp.float32)
        do = do_ref[0, 0, pl.dslice(i * block_q, block_q), :].astype(jnp.float32)
        lse = lse_ref[0, 0, pl.dslice(i * block_q, block_q), :].astype(jnp.float32)
        delta = delta_ref[0, 0, pl.dslice(i * block_q, block_q), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale  # [bq, bk]
        if has_kvb:
            s = s + kvb_ref[0, 0, pl.dslice(k_idx * bk, bk)][None, :]
        if has_fb:
            r0m = (i * block_q) % fb_rows
            s = s + fb_ref[0, 0, pl.dslice(r0m, block_q), :]
        if causal:
            r0 = i * block_q if seq_q_real is None else (i * block_q) % seq_q_real
            q_pos = r0 + jax.lax.broadcasted_iota(jnp.int32, (block_q, bk), 0)
            k_pos = k_idx * bk + jax.lax.broadcasted_iota(jnp.int32, (block_q, bk), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())))
        if rate:
            rows = i * block_q + jnp.arange(block_q, dtype=jnp.int32)
            cols = k_idx * bk + jnp.arange(bk, dtype=jnp.int32)
            keep = _keep_tile(salt, rows, cols, rate).astype(jnp.float32)
            scale_keep = keep / (1.0 - rate)
            p_drop = p * scale_keep
            dp = dp * scale_keep
        else:
            p_drop = p
        dv_new = dv + jax.lax.dot_general(p_drop, do, (((0,), (0,)), ((), ())))
        ds = p * (dp - delta) * scale
        dk_new = dk + jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())))
        return dk_new, dv_new

    if causal and seq_q_real is None:
        # only q blocks at or after this k block's start participate
        q_start = (k_idx * bk) // block_q
        dk, dv = jax.lax.fori_loop(q_start, n_q, body, (dk, dv))
    else:
        # folded GQA rows repeat positions; masking handles the skips
        dk, dv = jax.lax.fori_loop(0, n_q, body, (dk, dv))
    dk_ref[0, 0] = dk.astype(dk_ref.dtype)
    dv_ref[0, 0] = dv.astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# Impl wrappers: fold GQA, normalize biases to block specs, run pallas_call.
# cfg = (causal, scale, rate, has_kvb, kvb_b, has_fb, fb_b, fb_h)
# ---------------------------------------------------------------------------


def _bias_specs(cfg, B, H, bq, Lk, fb_rows, kvb, fb, seed, for_dkv=False, bk=None):
    """Extra in_specs + inputs for (kvb?, fb?, seed?) in kernel order."""
    from jax.experimental import pallas as pl

    causal, scale, rate, has_kvb, kvb_b, has_fb, fb_b, fb_h = cfg
    specs, args = [], []
    if has_kvb:
        # [Bm, 1, Lk]: the unit middle dim keeps the last-two block dims
        # (1, Lk) equal to the array dims — TPU tiling requirement
        specs.append(pl.BlockSpec(
            (1, 1, Lk), lambda b, h, i, _kb=kvb_b: (b if _kb else 0, 0, 0)))
        args.append(kvb)
    if has_fb:
        n_rb = fb_rows // bq
        if for_dkv:
            specs.append(pl.BlockSpec(
                (1, 1, fb_rows, bk),
                lambda b, h, j, _fb=fb_b, _fh=fb_h: (b if _fb else 0, h if _fh else 0, 0, j)))
        else:
            specs.append(pl.BlockSpec(
                (1, 1, bq, Lk),
                lambda b, h, i, _fb=fb_b, _fh=fb_h, _n=n_rb: (b if _fb else 0, h if _fh else 0, i % _n, 0)))
        args.append(fb)
    if rate:
        specs.append(pl.BlockSpec((1, 1), lambda b, h, i: (0, 0)))
        args.append(seed)
    return specs, args


def _fwd_lse_impl(q, k, v, kvb, fb, seed, cfg, interpret=None):
    from jax.experimental import pallas as pl

    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    causal, scale, rate, has_kvb, kvb_b, has_fb, fb_b, fb_h = cfg
    B, Lq, Hq, D = q.shape
    Lk, Hkv = k.shape[1], k.shape[2]
    qh, kh, vh = (jnp.swapaxes(t, 1, 2) for t in (q, k, v))
    seq_q_real = None
    if Hq != Hkv:
        qh, seq_q_real = _fold_gqa(qh, Hkv)
    H = Hkv
    Lq_f = qh.shape[2]
    # blocks must never straddle a folded head boundary: bq | real Lq
    bq = _block(Lq if seq_q_real is None else seq_q_real, _BLOCK_Q)
    bk = _block(Lk, _BLOCK_K)
    fb_rows = fb.shape[2] if has_fb else Lq_f
    if has_kvb and kvb.ndim == 2:
        kvb = kvb[:, None, :]
    grid = (B, H, Lq_f // bq)
    extra_specs, extra_args = _bias_specs(cfg, B, H, bq, Lk, fb_rows, kvb, fb, seed)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          block_k=bk, seq_k=Lk, seq_q_real=seq_q_real,
                          has_kvb=has_kvb, has_fb=has_fb, fb_rows=fb_rows,
                          rate=rate),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, Lk, D), lambda b, h, i: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, Lk, D), lambda b, h, i: (b, h, 0, 0)),
        ] + extra_specs,
        out_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, i: (b, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Lq_f, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, Lq_f, 1), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(qh, kh, vh, *extra_args)
    if seq_q_real is not None:
        out = _unfold_gqa(out, Hq, Lq)
    return jnp.swapaxes(out, 1, 2), lse


def _bwd_impl(q, k, v, lse, g, out, kvb, fb, seed, cfg, interpret=None):
    from jax.experimental import pallas as pl

    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    causal, scale, rate, has_kvb, kvb_b, has_fb, fb_b, fb_h = cfg
    B, Lq, Hq, D = q.shape
    Lk, Hkv = k.shape[1], k.shape[2]
    qh, kh, vh = (jnp.swapaxes(t, 1, 2) for t in (q, k, v))
    doh = jnp.swapaxes(g, 1, 2)
    oh = jnp.swapaxes(out, 1, 2)
    seq_q_real = None
    if Hq != Hkv:
        qh, seq_q_real = _fold_gqa(qh, Hkv)
        doh, _ = _fold_gqa(doh, Hkv)
        oh, _ = _fold_gqa(oh, Hkv)
        # lse from the folded forward is already (B, Hkv, G*Lq, 1)
    H = Hkv
    Lq_f = qh.shape[2]
    bq = _block(Lq if seq_q_real is None else seq_q_real, _BLOCK_Q)
    bk = _block(Lk, _BLOCK_K)
    fb_rows = fb.shape[2] if has_fb else Lq_f
    if has_kvb and kvb.ndim == 2:
        kvb = kvb[:, None, :]
    delta = jnp.sum(doh.astype(jnp.float32) * oh.astype(jnp.float32),
                    axis=-1, keepdims=True)           # (B, H, Lq_f, 1)

    extra_specs, extra_args = _bias_specs(cfg, B, H, bq, Lk, fb_rows, kvb, fb, seed)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_k=bk, seq_k=Lk, seq_q_real=seq_q_real,
                          has_kvb=has_kvb, has_fb=has_fb, fb_rows=fb_rows,
                          rate=rate),
        grid=(B, H, Lq_f // bq),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, Lk, D), lambda b, h, i: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, Lk, D), lambda b, h, i: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, i: (b, h, i, 0)),
        ] + extra_specs,
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, i: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Lq_f, D), q.dtype),
        interpret=interpret,
        name="flash_bwd_dq",
    )(qh, kh, vh, doh, lse, delta, *extra_args)

    extra_specs, extra_args = _bias_specs(cfg, B, H, bq, Lk, fb_rows, kvb, fb, seed,
                                          for_dkv=True, bk=bk)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=bq, seq_q=Lq_f, seq_q_real=seq_q_real,
                          has_kvb=has_kvb, has_fb=has_fb, fb_rows=fb_rows,
                          rate=rate),
        grid=(B, H, Lk // bk),
        in_specs=[
            pl.BlockSpec((1, 1, Lq_f, D), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, Lq_f, D), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, Lq_f, 1), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, Lq_f, 1), lambda b, h, j: (b, h, 0, 0)),
        ] + extra_specs,
        out_specs=[
            pl.BlockSpec((1, 1, bk, D), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, j: (b, h, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Lk, D), k.dtype),
            jax.ShapeDtypeStruct((B, H, Lk, D), v.dtype),
        ],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(qh, kh, vh, doh, lse, delta, *extra_args)
    if seq_q_real is not None:
        dq = _unfold_gqa(dq, Hq, Lq)
    return (jnp.swapaxes(dq, 1, 2), jnp.swapaxes(dk, 1, 2),
            jnp.swapaxes(dv, 1, 2))


# ---------------------------------------------------------------------------
# More than one device: attention is independent per batch row and per head,
# so each device runs the kernels on its own rows (data axes) and heads
# ('tp'). A full bias that carries heads keeps the heads together.
# ---------------------------------------------------------------------------

def _flash_shard_specs(mesh, cfg, q, k):
    _, _, _, has_kvb, kvb_b, has_fb, fb_b, _ = cfg
    b = dim_axes(mesh, q.shape[0], BATCH_AXES)
    h = None if has_fb else dim_axes(mesh, k.shape[2], ("tp",))
    return (P(b, None, h, None),                    # q, k, v, g, out
            P(b, h, None, None),                    # lse
            P(b if has_kvb and kvb_b else None),    # kvb
            P(b if has_fb and fb_b else None),      # fb
            (b or ()) + (h or ()))


def _shard_seed(seed, cfg, axes):
    """Inside the shard_map: the dropout hash counts batch rows and heads
    from the kernel's own grid, so give every shard its own seed."""
    if not cfg[2]:
        return seed
    idx = jnp.zeros((), jnp.int32)
    for a in axes:
        idx = idx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
    return seed + idx.astype(seed.dtype)


def _fwd_lse(q, k, v, kvb, fb, seed, cfg):
    mesh = kernel_mesh()
    if mesh is None:
        return _fwd_lse_impl(q, k, v, kvb, fb, seed, cfg)
    blhd, lse, kvb_s, fb_s, axes = _flash_shard_specs(mesh, cfg, q, k)
    return jax.shard_map(
        lambda q, k, v, kvb, fb, seed: _fwd_lse_impl(
            q, k, v, kvb, fb, _shard_seed(seed, cfg, axes), cfg),
        mesh=mesh, in_specs=(blhd, blhd, blhd, kvb_s, fb_s, P()),
        out_specs=(blhd, lse), check_vma=False,
    )(q, k, v, kvb, fb, seed)


def _bwd(q, k, v, lse, g, out, kvb, fb, seed, cfg):
    mesh = kernel_mesh()
    if mesh is None:
        return _bwd_impl(q, k, v, lse, g, out, kvb, fb, seed, cfg)
    blhd, lse_s, kvb_s, fb_s, axes = _flash_shard_specs(mesh, cfg, q, k)
    return jax.shard_map(
        lambda q, k, v, lse, g, out, kvb, fb, seed: _bwd_impl(
            q, k, v, lse, g, out, kvb, fb, _shard_seed(seed, cfg, axes), cfg),
        mesh=mesh,
        in_specs=(blhd, blhd, blhd, lse_s, blhd, blhd, kvb_s, fb_s, P()),
        out_specs=(blhd, blhd, blhd), check_vma=False,
    )(q, k, v, lse, g, out, kvb, fb, seed)


# ---------------------------------------------------------------------------
# custom_vjp core. Extras (kvb, fb, seed) are always passed (dummy (1, 1)
# zeros when unused — cfg flags gate both the kernels and the specs), so one
# function covers every feature combination without None-pytree contortions.
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash_core(cfg, q, k, v, kvb, fb, seed):
    out, _ = _flash_core_fwd(cfg, q, k, v, kvb, fb, seed)
    return out


def _ref_with_extras(cfg, q, k, v, kvb, fb, seed):
    causal, scale, rate, has_kvb, kvb_b, has_fb, fb_b, fb_h = cfg
    mask = None
    if has_kvb:
        mask = kvb[:, None, None, :]
    if has_fb:
        m = fb  # [Bm, Hm', rows, Lk]
        if fb_h and q.shape[2] != k.shape[2]:
            # pre-folded rows: unfold back to [Bm, Hq, Lq, Lk]
            g = q.shape[2] // k.shape[2]
            m = fb.reshape(fb.shape[0], fb.shape[1] * g, fb.shape[2] // g, fb.shape[3])
        mask = m if mask is None else mask + m
    return mha_reference(q, k, v, causal=causal, scale=scale, attn_mask=mask,
                         dropout_rate=rate, dropout_seed=seed.reshape(-1)[0])


def _flash_core_fwd(cfg, q, k, v, kvb, fb, seed):
    try:
        out, lse = _fwd_lse(q, k, v, kvb, fb, seed, cfg)
        return out, (q, k, v, kvb, fb, seed, lse, out)
    except Exception as e:
        kernel_fallback("flash_attention_fwd", e)
        out = _ref_with_extras(cfg, q, k, v, kvb, fb, seed)
        return out, (q, k, v, kvb, fb, seed, None, out)


def _flash_core_bwd(cfg, res, g):
    q, k, v, kvb, fb, seed, lse, out = res
    zeros = (jnp.zeros_like(kvb), jnp.zeros_like(fb), jnp.zeros_like(seed))
    if lse is not None:
        try:
            dq, dk, dv = _bwd(q, k, v, lse, g, out, kvb, fb, seed, cfg)
            return (dq, dk, dv) + zeros
        except Exception as e:
            kernel_fallback("flash_attention_bwd", e)
    # fallback: XLA vjp of the reference (materializes L x L probs)
    def f(q, k, v):
        return _ref_with_extras(cfg, q, k, v, kvb, fb, seed)
    _, vjp = jax.vjp(f, q, k, v)
    return tuple(vjp(g)) + zeros


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)

_DUMMY_CFG_TAIL = (False, False, False, False, False)


def _plain_cfg(causal, scale):
    return (bool(causal), float(scale), 0.0) + _DUMMY_CFG_TAIL


def _dummy():
    return jnp.zeros((1, 1), jnp.float32)


def _flash(q, k, v, causal, scale):
    """Mask-free, dropout-free entry (ulysses + back-compat)."""
    d = _dummy()
    return _flash_core(_plain_cfg(causal, scale), q, k, v, d, d, d)


def flash_raw_or_reference(q, k, v, causal=True, scale=None):
    """Raw-array dispatch for code already inside jit/shard_map (stacked
    GPT blocks, pipeline stages): the Pallas kernel when the backend and
    tiling allow, else the jnp reference — same numerics. Unlike the
    public flash_attention it does NOT pad: non-128-multiple sequence
    lengths would only fail at XLA compile (beyond the trace-time
    except), so they take the reference path instead."""
    scale = scale if scale is not None else 1.0 / np.sqrt(q.shape[-1])
    if flash_attention_available(q, None, 0.0) \
            and q.shape[1] % 128 == 0 and k.shape[1] % 128 == 0:
        try:
            return _flash(q, k, v, causal, scale)
        except Exception as e:
            kernel_fallback("flash_raw", e)
    return mha_reference(q, k, v, causal=causal, scale=scale)


# -- back-compat impl wrappers (tests drive these in interpret mode) --------


def _flash_fwd_impl(q, k, v, causal, scale, interpret=None):
    d = _dummy()
    out, _ = _fwd_lse_impl(q, k, v, d, d, d, _plain_cfg(causal, scale),
                           interpret=interpret)
    return out


def _flash_fwd_lse_impl(q, k, v, causal, scale, interpret=None):
    d = _dummy()
    return _fwd_lse_impl(q, k, v, d, d, d, _plain_cfg(causal, scale),
                         interpret=interpret)


def _flash_bwd_impl(q, k, v, out, lse, g, causal, scale, interpret=None):
    d = _dummy()
    return _bwd_impl(q, k, v, lse, g, out, d, d, d, _plain_cfg(causal, scale),
                     interpret=interpret)


# ---------------------------------------------------------------------------
# Dispatch: mask normalization, seq padding, seed plumbing.
# ---------------------------------------------------------------------------

_seed_counter = [0]


def _next_seed():
    """Per-call dropout seed. Eager calls draw from the paddle global RNG
    (deterministic after paddle.seed); under jit tracing this becomes a
    trace-time constant — pass `dropout_seed` explicitly per step to vary
    the pattern inside a compiled training step."""
    from ..framework import random as _random

    _seed_counter[0] += 1
    try:
        key = _random.next_key()
        return int(jax.random.randint(key, (), 0, 1 << 24))
    except Exception:
        return _seed_counter[0]


def _normalize_mask(attn_mask, B, Hq, Lq, Lk, dtype_neg=_NEG):
    """Split an arbitrary broadcastable mask into (kvb [Bm, Lk]) or
    (fb [Bm, Hm, Lq(m), Lk]) additive fp32 biases."""
    m = attn_mask
    if m.dtype == jnp.bool_:
        m = jnp.where(m, 0.0, dtype_neg).astype(jnp.float32)
    else:
        m = m.astype(jnp.float32)
    while m.ndim < 4:
        m = m[None]
    Bm, Hm, Lqm, Lkm = m.shape
    if Lkm == 1:
        m = jnp.broadcast_to(m, (Bm, Hm, Lqm, Lk))
    if Hm == 1 and Lqm == 1:
        return m.reshape(m.shape[0], m.shape[3]), None
    if Lqm == 1:
        m = jnp.broadcast_to(m, (Bm, Hm, Lq, m.shape[3]))
    return None, m


def flash_attention(query, key, value, causal=False, scale=None,
                    attn_mask=None, dropout_rate=0.0, dropout_seed=None):
    """Public fused attention — Tensor in/out, [B,L,H,D]. Supports GQA
    (key/value with fewer heads), additive or boolean attn_mask, and
    attention-probability dropout, all inside the Pallas kernel."""
    sc = scale if scale is not None else 1.0 / np.sqrt(query.shape[-1])
    B, Lq, Hq, D = query.shape
    Lk, Hkv = key.shape[1], key.shape[2]
    if Hq % Hkv != 0:
        raise ValueError(f"query heads ({Hq}) must be a multiple of "
                         f"key/value heads ({Hkv}) for GQA")
    rate = float(dropout_rate or 0.0)
    seed_val = dropout_seed if dropout_seed is not None else (
        _next_seed() if rate else 0)

    def run(q, k, v, *maybe_mask):
        m = maybe_mask[0] if maybe_mask else None
        lq, lk = q.shape[1], k.shape[1]
        pad_q = (-lq) % 128 if lq % 128 else 0
        pad_k = (-lk) % 128 if lk % 128 else 0
        kvb = fb = None
        if m is not None:
            kvb, fb = _normalize_mask(m, q.shape[0], q.shape[2], lq, lk)
        if pad_q or pad_k:
            q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
            k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
            v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
            if fb is not None:
                fb = jnp.pad(fb, ((0, 0), (0, 0), (0, pad_q), (0, pad_k)),
                             constant_values=_NEG)
            if pad_k:
                if kvb is None:
                    kvb = jnp.zeros((1, lk), jnp.float32)
                kvb = jnp.pad(kvb, ((0, 0), (0, pad_k)), constant_values=_NEG)
        has_kvb = kvb is not None
        has_fb = fb is not None
        if has_fb and fb.shape[1] > 1 and Hq != Hkv:
            # pre-fold the head axis to match the folded row layout
            g = Hq // Hkv
            fb = fb.reshape(fb.shape[0], Hkv, g * fb.shape[2], fb.shape[3])
        cfg = (bool(causal), float(sc), rate,
               has_kvb, has_kvb and kvb.shape[0] > 1,
               has_fb, has_fb and fb.shape[0] > 1,
               has_fb and fb.shape[1] > 1)
        d = _dummy()
        seed_arr = jnp.asarray(seed_val, jnp.float32).reshape(1, 1)
        out = _flash_core(cfg, q, k, v,
                          kvb if has_kvb else d, fb if has_fb else d, seed_arr)
        return out[:, :lq] if pad_q else out

    args = (query, key, value) + ((attn_mask,) if attn_mask is not None else ())
    if isinstance(query, Tensor):
        return apply_op(run, *args)
    return run(*[a._value if isinstance(a, Tensor) else a for a in args])
