"""Weight-only int4 matmul for decode (W4A16).

Decode reads every weight byte each step; int4 halves that traffic vs
int8 (a8w8) and quarters it vs bf16 — the HBM roofline moves up
accordingly (`PagedGPTDecoder.step_hbm_bytes`). Storage: per-out-channel
symmetric int4 (q in [-7, 7], scale = amax/7), two nibbles packed per
int8 byte along the IN dim with a +8 offset (nibble value 1..15).

The Pallas kernel unpacks nibbles in VMEM (VPU int ops) and feeds the
MXU a bf16 tile — the dequantized weight never exists in HBM. The jnp
reference path computes the identical math (used on CPU and as the
fallback, and to verify the kernel bit-for-bit in interpret mode).

Reference counterpart: weight-only quant epilogues in
paddle/phi/kernels fused-matmul int8 paths — the int4 variant is the
TPU-side extension of the same bandwidth story.
"""
import functools

import jax
import jax.numpy as jnp

from ._fallback import kernel_fallback

__all__ = ["quantize_w4", "w4_matmul"]


def quantize_w4(w):
    """w [in, out] float -> (packed [ceil(in/2), out] int8 nibbles,
    scale [out] f32). Odd `in` is zero-padded (nibble 8 == value 0).
    Quantization itself is the shared recipe (quantization.quantize_weight
    with bits=4); only the nibble packing lives here."""
    from ..quantization import quantize_weight
    w = jnp.asarray(w)
    K, N = w.shape
    q, scale = quantize_weight(w, axis=0, bits=4)
    q = (q.astype(jnp.int32) + 8).astype(jnp.uint8)    # 1..15
    if K % 2:
        q = jnp.concatenate([q, jnp.full((1, N), 8, jnp.uint8)], axis=0)
    lo, hi = q[0::2], q[1::2]                # even rows -> low nibble
    return (lo | (hi << 4)).astype(jnp.int8), \
        scale.reshape(-1).astype(jnp.float32)


def _unpack_w4(packed, K):
    """packed [K2, N] int8 -> dequant-ready int [K, N] in [-7, 7]."""
    p = packed.astype(jnp.int32) & 0xFF      # int8 -> raw byte
    lo = (p & 0xF) - 8
    hi = ((p >> 4) & 0xF) - 8
    K2, N = p.shape
    return jnp.stack([lo, hi], axis=1).reshape(2 * K2, N)[:K]


def _w4_ref(x, packed, scale, K):
    w = _unpack_w4(packed, K).astype(jnp.float32) * scale
    return (x.astype(jnp.float32) @ w).astype(x.dtype)


def _w4_kernel(x_ref, p_ref, s_ref, o_ref, *, K):
    x = x_ref[...].astype(jnp.float32)       # [S, K]
    w = _unpack_w4(p_ref[...], K)            # [K, Nt] int
    wf = w.astype(jnp.float32) * s_ref[...].astype(jnp.float32)
    o_ref[...] = jnp.dot(
        x, wf, preferred_element_type=jnp.float32).astype(o_ref.dtype)


def w4_matmul(x, packed, scale, K, block_n=256, block_s=512):
    """x [..., K] @ int4-packed weight -> [..., N]; dequant happens
    per-tile in VMEM (Pallas), never in HBM.

    Every shape tiles: an unaligned N first tries a SMALLER block (the
    largest power-of-two divisor of N >= 64 — the decoder's head-major
    384s and 128s tile exactly, no copies) and only a genuinely odd N
    (vocab projections like 50257) pads up to the block — an int8
    weight copy that is still far cheaper than the old silent fallback,
    which materialized the ENTIRE dequantized f32 weight in HBM. S
    tiles over a grid dimension in `block_s` rows (long prefill rows no
    longer bail at S > 4096; the weight tile streams once per S tile).
    The jnp reference remains the correctness twin and the fallback for
    odd-K packings and kernel failures."""
    from jax.experimental import pallas as pl

    lead = x.shape[:-1]
    xf = x.reshape(-1, K)
    S = xf.shape[0]
    K2, N = packed.shape
    if K % 2:
        return _w4_ref(xf, packed, scale, K).reshape(*lead, N)
    try:
        if N % block_n:
            b = N & -N                   # largest pow2 divisor of N
            if b >= 64:
                block_n = min(b, block_n)
        Np = -(-N // block_n) * block_n
        pk, sc = packed, scale
        if Np != N:
            # zero-padded columns: nibble byte 0 dequantizes to -8 * a
            # zero scale = 0, and the columns are sliced off anyway
            pk = jnp.pad(packed, ((0, 0), (0, Np - N)))
            sc = jnp.pad(scale, (0, Np - N))
        bs = min(block_s, S)
        Sp = -(-S // bs) * bs
        xp = jnp.pad(xf, ((0, Sp - S), (0, 0))) if Sp != S else xf
        out = pl.pallas_call(
            functools.partial(_w4_kernel, K=K),
            grid=(Sp // bs, Np // block_n),
            in_specs=[
                pl.BlockSpec((bs, K), lambda i, j: (i, 0)),
                pl.BlockSpec((K2, block_n), lambda i, j: (0, j)),
                pl.BlockSpec((block_n,), lambda i, j: (j,)),
            ],
            out_specs=pl.BlockSpec((bs, block_n), lambda i, j: (i, j)),
            out_shape=jax.ShapeDtypeStruct((Sp, Np), x.dtype),
            interpret=jax.default_backend() == "cpu",
            name="w4_matmul",
        )(xp, pk, sc)
        return out[:S, :N].reshape(*lead, N)
    except Exception as e:
        kernel_fallback("w4_matmul", e)
        return _w4_ref(xf, packed, scale, K).reshape(*lead, N)
