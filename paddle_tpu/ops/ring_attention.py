"""Ring attention: exact flash attention over a sequence-sharded 'sp' axis.

Replaces the reference's sequence-parallel attention (fleet sep/
sparse_attention CUDA paths) with the TPU-native ring algorithm: K/V shards
rotate around the ICI ring via ppermute while each device accumulates its
queries' online-softmax partials — memory O(L/sp), comms overlap with compute.

Two causal work layouts:

- contiguous (ring_attention_local): shard d holds tokens
  [d·L/S, (d+1)·L/S). Every ring step computes the full Lq×Lk block and
  masks — correct and simple, but ~half the computed blocks are fully
  masked.
- zigzag (zigzag_ring_attention_local): the sequence is split into 2S
  half-chunks and shard d holds chunks (d, 2S-1-d). Step 0 is plain local
  causal attention; every later step needs exactly TWO unmasked
  half-blocks per device (one always qc1×kc0; the other qc0×kc0 when the
  visiting shard is earlier, qc1×kc1 when later) — uniform load, no
  fully-masked matmuls, ~2× less attention compute at large sp. Same
  exact online-softmax math, so results match contiguous bit-for-bit up
  to float reassociation.

Used inside shard_map with q/k/v sharded on the sequence dim:
    out = shard_map(partial(ring_attention_local, axis_name="sp", causal=True),
                    mesh, in_specs=P(dp, "sp", None, None), ...)(q, k, v)
Layout: [batch, seq_local, heads, head_dim].
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["ring_attention_local", "ring_attention",
           "ring_flash_attention_local", "zigzag_ring_attention_local",
           "zigzag_ring_flash_attention_local"]


def ring_flash_attention_local(q, k, v, axis_name="sp", causal=True,
                               scale=None):
    """Flash-kernel ring attention INSIDE shard_map (the long-context
    path): each ring step runs the Pallas flash kernel on the resident
    K/V shard and merges (out, lse) partials by log-sum-exp, so nothing
    of size Lq×Lk is ever materialized — per-device memory stays
    O(L/sp · D). Fully-masked causal steps are skipped via lax.cond.

    The custom VJP is the ring form of the flash backward: gradients for
    a (q-shard, kv-shard) block pair computed with the GLOBAL lse are
    exact partials of the global softmax, so dk/dv accumulators simply
    rotate with their K/V shards and arrive home after the full cycle.
    """
    if scale is None:
        scale = 1.0 / np.sqrt(q.shape[-1])
    if q.shape[2] != k.shape[2]:
        # GQA head-folding inside the per-step impls would break the lse
        # merge bookkeeping; the dense path handles it
        return ring_attention_local(q, k, v, axis_name, causal, scale,
                                    use_flash=False)
    out, _ = _ring_flash(q, k, v, axis_name, causal, float(scale))
    return out


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _ring_flash(q, k, v, axis_name, causal, scale):
    return _ring_flash_fwd_compute(q, k, v, axis_name, causal, scale)


def _ring_flash_fwd_compute(q, k, v, axis_name, causal, scale):
    from .attention import _flash_fwd_lse_impl

    sp = jax.lax.axis_size(axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % sp) for i in range(sp)]

    # step 0: this shard's own block — causal square
    out0, lse0 = _flash_fwd_lse_impl(q, k, v, causal, scale)
    acc0 = jnp.swapaxes(out0, 1, 2).astype(jnp.float32)   # [B,H,Lq,D]
    L0 = lse0                                             # [B,H,Lq,1] f32

    def body(step, carry):
        k_cur, v_cur, acc, L_run = carry
        k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
        v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
        src = (my_idx - step) % sp

        def merge(args):
            acc, L_run = args
            out_i, lse_i = _flash_fwd_lse_impl(q, k_cur, v_cur, False, scale)
            return _lse_merge(acc, L_run, out_i, lse_i)

        if causal:
            # skip blocks where every kv position is in the future
            acc, L_run = jax.lax.cond(src < my_idx, merge, lambda a: a,
                                      (acc, L_run))
        else:
            acc, L_run = merge((acc, L_run))
        return k_cur, v_cur, acc, L_run

    _, _, acc, L_tot = jax.lax.fori_loop(1, sp, body, (k, v, acc0, L0))
    out = jnp.swapaxes(acc, 1, 2).astype(q.dtype)         # [B,Lq,H,D]
    return out, L_tot


def _ring_flash_fwd(q, k, v, axis_name, causal, scale):
    out, lse = _ring_flash_fwd_compute(q, k, v, axis_name, causal, scale)
    return (out, lse), (q, k, v, out, lse)


def _ring_flash_bwd(axis_name, causal, scale, res, cts):
    from .attention import _flash_bwd_impl

    q, k, v, out, lse = res
    g = cts[0].astype(q.dtype)   # lse cotangent is zero in ring use
    sp = jax.lax.axis_size(axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % sp) for i in range(sp)]

    # step 0: own causal block
    dq0, dk0, dv0 = _flash_bwd_impl(q, k, v, out, lse, g, causal, scale)
    dq0 = dq0.astype(jnp.float32)

    def body(step, carry):
        k_cur, v_cur, dk_cur, dv_cur, dq = carry
        k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
        v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
        dk_cur = jax.lax.ppermute(dk_cur, axis_name, perm)
        dv_cur = jax.lax.ppermute(dv_cur, axis_name, perm)
        src = (my_idx - step) % sp

        def compute(args):
            dk_cur, dv_cur, dq = args
            # global lse makes this block's grads exact global partials
            dq_i, dk_i, dv_i = _flash_bwd_impl(q, k_cur, v_cur, out, lse,
                                               g, False, scale)
            return (dk_cur + dk_i.astype(dk_cur.dtype),
                    dv_cur + dv_i.astype(dv_cur.dtype),
                    dq + dq_i.astype(jnp.float32))

        if causal:
            dk_cur, dv_cur, dq = jax.lax.cond(src < my_idx, compute,
                                              lambda a: a,
                                              (dk_cur, dv_cur, dq))
        else:
            dk_cur, dv_cur, dq = compute((dk_cur, dv_cur, dq))
        return k_cur, v_cur, dk_cur, dv_cur, dq

    dk0 = dk0.astype(jnp.float32)
    dv0 = dv0.astype(jnp.float32)
    # after the remaining sp-1 rotations everything is one hop short of
    # home; one final ppermute completes the cycle
    k_f, v_f, dk, dv, dq = jax.lax.fori_loop(
        1, sp, body, (k, v, dk0, dv0, dq0))
    dk = jax.lax.ppermute(dk, axis_name, perm)
    dv = jax.lax.ppermute(dv, axis_name, perm)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_ring_flash.defvjp(_ring_flash_fwd, _ring_flash_bwd)


def _flash_ring_ok(q_shape, kv_heads, block_len):
    """Default-on gate for the flash ring paths: the kernel's head-dim
    tiling (ops/attention.py flash_attention_available), no GQA fold, and
    128-aligned per-step block length (the non-public impls don't pad).
    q_shape: [..., H, D] of the LOCAL q; block_len: rows per flash call
    (L_local for contiguous, L_local/2 for zigzag)."""
    H, D = q_shape[-2], q_shape[-1]
    return (D in (64, 128, 256) and H == kv_heads
            and block_len > 0 and block_len % 128 == 0)


def _lse_merge(acc, L_run, out_i, lse_i):
    """Merge a flash partial (normalized out_i [B,L,H,D], lse_i
    [B,H,L,1]) into the running (acc [B,H,L,D] f32, L_run) pair."""
    oh = jnp.swapaxes(out_i, 1, 2).astype(jnp.float32)
    L_new = jnp.logaddexp(L_run, lse_i)
    acc = acc * jnp.exp(L_run - L_new) + oh * jnp.exp(lse_i - L_new)
    return acc, L_new


def zigzag_ring_flash_attention_local(q, k, v, axis_name="sp", scale=None):
    """Flash-kernel zigzag ring (causal): the load-balanced layout AND
    O(L/sp) attention memory.  Every ring step runs exactly two Lh x Lh
    flash blocks per device (block X: q-half-1 x visiting chunk-0, always
    unmasked; block Y: the early/late where-selected half pair), partials
    merged by lse per query half.  Same custom-VJP scheme as the
    contiguous flash ring: block grads against the global per-half lse
    are exact partials, dk/dv rotate home with their shards."""
    if scale is None:
        scale = 1.0 / np.sqrt(q.shape[-1])
    if q.shape[2] != k.shape[2]:
        # GQA head-folding breaks the per-half lse bookkeeping; dense path
        return _zigzag_dense_local(q, k, v, axis_name, scale)
    out, _ = _zz_ring_flash(q, k, v, axis_name, float(scale))
    return out


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _zz_ring_flash(q, k, v, axis_name, scale):
    return _zz_ring_flash_fwd_compute(q, k, v, axis_name, scale)


def _zz_ring_flash_fwd_compute(q, k, v, axis_name, scale):
    from .attention import _flash_fwd_lse_impl

    sp = jax.lax.axis_size(axis_name)
    d = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % sp) for i in range(sp)]
    Lh = q.shape[1] // 2
    q0, q1 = q[:, :Lh], q[:, Lh:]

    def halves(t):
        return t[:, :Lh], t[:, Lh:]

    k0, k1 = halves(k)
    v0, v1 = halves(v)

    # step 0: local causal in zigzag order = three flash blocks
    o, lse = _flash_fwd_lse_impl(q0, k0, v0, True, scale)     # q0 x c_d
    acc0 = jnp.swapaxes(o, 1, 2).astype(jnp.float32)
    L0 = lse
    o, lse = _flash_fwd_lse_impl(q1, k0, v0, False, scale)    # q1 x c_d
    acc1 = jnp.swapaxes(o, 1, 2).astype(jnp.float32)
    L1 = lse
    o, lse = _flash_fwd_lse_impl(q1, k1, v1, True, scale)     # q1 x c_{2S-1-d}
    acc1, L1 = _lse_merge(acc1, L1, o, lse)

    def body(t, carry):
        k_cur, v_cur, acc0, L0, acc1, L1 = carry
        k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
        v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
        src = (d - t) % sp
        kc0, kc1 = halves(k_cur)
        vc0, vc1 = halves(v_cur)
        # block X: q1 x visiting chunk src — always fully unmasked
        o, lse = _flash_fwd_lse_impl(q1, kc0, vc0, False, scale)
        acc1, L1 = _lse_merge(acc1, L1, o, lse)
        # block Y: early shard -> q0 x kc0, later -> q1 x kc1; select so
        # the flash kernel runs once
        early = src < d
        q_sel = jnp.where(early, q0, q1)
        k_sel = jnp.where(early, kc0, kc1)
        v_sel = jnp.where(early, vc0, vc1)
        a_sel = jnp.where(early, acc0, acc1)
        L_sel = jnp.where(early, L0, L1)
        o, lse = _flash_fwd_lse_impl(q_sel, k_sel, v_sel, False, scale)
        a_new, L_new = _lse_merge(a_sel, L_sel, o, lse)
        acc0 = jnp.where(early, a_new, acc0)
        L0 = jnp.where(early, L_new, L0)
        acc1 = jnp.where(early, acc1, a_new)
        L1 = jnp.where(early, L1, L_new)
        return k_cur, v_cur, acc0, L0, acc1, L1

    _, _, acc0, L0, acc1, L1 = jax.lax.fori_loop(
        1, sp, body, (k, v, acc0, L0, acc1, L1))
    out = jnp.concatenate([jnp.swapaxes(acc0, 1, 2),
                           jnp.swapaxes(acc1, 1, 2)], axis=1).astype(q.dtype)
    lse = jnp.concatenate([L0, L1], axis=2)                   # [B,H,2Lh,1]
    return out, lse


def _zz_ring_flash_fwd(q, k, v, axis_name, scale):
    out, lse = _zz_ring_flash_fwd_compute(q, k, v, axis_name, scale)
    return (out, lse), (q, k, v, out, lse)


def _zz_ring_flash_bwd(axis_name, scale, res, cts):
    from .attention import _flash_bwd_impl

    q, k, v, out, lse = res
    g = cts[0].astype(q.dtype)
    sp = jax.lax.axis_size(axis_name)
    d = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % sp) for i in range(sp)]
    Lh = q.shape[1] // 2

    def halves(t, axis=1):
        if axis == 1:
            return t[:, :Lh], t[:, Lh:]
        return t[:, :, :Lh], t[:, :, Lh:]

    q0, q1 = halves(q)
    k0, k1 = halves(k)
    v0, v1 = halves(v)
    out0, out1 = halves(out)
    g0, g1 = halves(g)
    lse0, lse1 = halves(lse, axis=2)

    # step 0: the three local blocks
    dq_a, dk_a, dv_a = _flash_bwd_impl(q0, k0, v0, out0, lse0, g0, True,
                                       scale)
    dq_b, dk_b, dv_b = _flash_bwd_impl(q1, k0, v0, out1, lse1, g1, False,
                                       scale)
    dq_c, dk_c, dv_c = _flash_bwd_impl(q1, k1, v1, out1, lse1, g1, True,
                                       scale)
    dq0 = dq_a.astype(jnp.float32)
    dq1 = (dq_b + dq_c).astype(jnp.float32)
    dk_own = jnp.concatenate([(dk_a + dk_b), dk_c], axis=1) \
        .astype(jnp.float32)
    dv_own = jnp.concatenate([(dv_a + dv_b), dv_c], axis=1) \
        .astype(jnp.float32)

    def body(t, carry):
        k_cur, v_cur, dk_cur, dv_cur, dq0, dq1 = carry
        k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
        v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
        dk_cur = jax.lax.ppermute(dk_cur, axis_name, perm)
        dv_cur = jax.lax.ppermute(dv_cur, axis_name, perm)
        src = (d - t) % sp
        kc0, kc1 = halves(k_cur)
        vc0, vc1 = halves(v_cur)
        # block X: q1 x chunk src (full)
        dq_i, dk_i, dv_i = _flash_bwd_impl(q1, kc0, vc0, out1, lse1, g1,
                                           False, scale)
        dq1 = dq1 + dq_i.astype(jnp.float32)
        dk_cur = dk_cur.at[:, :Lh].add(dk_i.astype(jnp.float32))
        dv_cur = dv_cur.at[:, :Lh].add(dv_i.astype(jnp.float32))
        # block Y (selected half pair)
        early = src < d
        q_sel = jnp.where(early, q0, q1)
        k_sel = jnp.where(early, kc0, kc1)
        v_sel = jnp.where(early, vc0, vc1)
        o_sel = jnp.where(early, out0, out1)
        l_sel = jnp.where(early, lse0, lse1)
        g_sel = jnp.where(early, g0, g1)
        dq_i, dk_i, dv_i = _flash_bwd_impl(q_sel, k_sel, v_sel, o_sel,
                                           l_sel, g_sel, False, scale)
        dq_i = dq_i.astype(jnp.float32)
        dk_i = dk_i.astype(jnp.float32)
        dv_i = dv_i.astype(jnp.float32)
        zero = jnp.zeros_like(dk_i)
        dq0 = dq0 + jnp.where(early, dq_i, 0.0)
        dq1 = dq1 + jnp.where(early, 0.0, dq_i)
        dk_cur = dk_cur + jnp.concatenate(
            [jnp.where(early, dk_i, zero), jnp.where(early, zero, dk_i)],
            axis=1)
        dv_cur = dv_cur + jnp.concatenate(
            [jnp.where(early, dv_i, zero), jnp.where(early, zero, dv_i)],
            axis=1)
        return k_cur, v_cur, dk_cur, dv_cur, dq0, dq1

    _, _, dk, dv, dq0, dq1 = jax.lax.fori_loop(
        1, sp, body, (k, v, dk_own, dv_own, dq0, dq1))
    # complete the rotation cycle so accumulators land on their owners
    dk = jax.lax.ppermute(dk, axis_name, perm)
    dv = jax.lax.ppermute(dv, axis_name, perm)
    dq = jnp.concatenate([dq0, dq1], axis=1)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_zz_ring_flash.defvjp(_zz_ring_flash_fwd, _zz_ring_flash_bwd)


def ring_attention_local(q, k, v, axis_name="sp", causal=True, scale=None,
                         use_flash=None):
    """Runs INSIDE shard_map. q,k,v: [B, L_local, H, D] (this shard).

    use_flash: route each ring step through the Pallas flash kernel with
    lse-merged partials (O(L/sp) memory — the long-context path). Default:
    on for the TPU backend when the kernel supports the shape
    (_flash_ring_ok); the dense jnp path remains for CPU tests, GQA, and
    unaligned shapes."""
    if use_flash is None:
        use_flash = (jax.default_backend() == "tpu"
                     and _flash_ring_ok(q.shape, k.shape[2], q.shape[1]))
    if use_flash:
        return ring_flash_attention_local(q, k, v, axis_name, causal, scale)
    return _ring_dense_local(q, k, v, axis_name, causal, scale)


def _ring_dense_local(q, k, v, axis_name="sp", causal=True, scale=None):
    """Dense per-step scores (materializes Lq x Lk per ring step)."""
    sp = jax.lax.axis_size(axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    scale = scale if scale is not None else 1.0 / np.sqrt(q.shape[-1])

    qh = jnp.swapaxes(q, 1, 2).astype(jnp.float32) * scale  # [B,H,Lq,D]
    B, H, Lq, D = qh.shape
    Lk = k.shape[1]

    q_pos = my_idx * Lq + jax.lax.broadcasted_iota(jnp.int32, (Lq, Lk), 0)

    # derive from qh so the carry inits inherit its varying-axes type
    m0 = jnp.full_like(qh[..., :1], -1e30)
    l0 = jnp.zeros_like(qh[..., :1])
    acc0 = jnp.zeros_like(qh)
    perm = [(i, (i + 1) % sp) for i in range(sp)]

    def body(step, carry):
        k_cur, v_cur, m, l, acc = carry
        src = (my_idx - step) % sp  # which shard's k/v we hold this step
        kh = jnp.swapaxes(k_cur, 1, 2).astype(jnp.float32)
        vh = jnp.swapaxes(v_cur, 1, 2).astype(jnp.float32)
        s = qh @ jnp.swapaxes(kh, -1, -2)  # [B,H,Lq,Lk]
        if causal:
            k_pos = src * Lk + jax.lax.broadcasted_iota(jnp.int32, (Lq, Lk), 1)
            mask = q_pos >= k_pos
            s = jnp.where(mask, s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        if causal:
            p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * corr + p @ vh
        # rotate k/v to the next device; overlaps with next step's matmul
        k_next = jax.lax.ppermute(k_cur, axis_name, perm)
        v_next = jax.lax.ppermute(v_cur, axis_name, perm)
        return k_next, v_next, m_new, l_new, acc_new

    _, _, m, l, acc = jax.lax.fori_loop(0, sp, body, (k, v, m0, l0, acc0))
    out = acc / jnp.maximum(l, 1e-30)
    return jnp.swapaxes(out, 1, 2).astype(q.dtype)


def _online_update(m, l, acc, s, vh):
    """One online-softmax block update. s: [B,H,Lq,Lk] UNMASKED scores."""
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m - m_new)
    return m_new, l * corr + jnp.sum(p, -1, keepdims=True), \
        acc * corr + p @ vh


def zigzag_ring_attention_local(q, k, v, axis_name="sp", scale=None,
                                use_flash=None):
    """Causal ring attention with the zigzag layout, INSIDE shard_map.

    q,k,v: [B, 2*Lh, H, D] — this shard's two half-chunks, ALREADY in
    zigzag order: rows [:Lh] are global chunk d, rows [Lh:] are global
    chunk 2S-1-d. Output is in the same zigzag order.

    use_flash routes the per-step half-blocks through the Pallas flash
    kernel with lse merging (zigzag_ring_flash_attention_local): balanced
    load AND O(L/sp) memory. Default: on for TPU when the half-chunk
    shape fits the kernel (head-dim tiling, 128-aligned Lh, no GQA)."""
    if use_flash is None:
        use_flash = (jax.default_backend() == "tpu"
                     and _flash_ring_ok(q.shape, k.shape[2],
                                        q.shape[1] // 2))
    if use_flash:
        return zigzag_ring_flash_attention_local(q, k, v, axis_name, scale)
    return _zigzag_dense_local(q, k, v, axis_name, scale)


def _zigzag_dense_local(q, k, v, axis_name="sp", scale=None):
    """Dense zigzag step blocks (materializes Lh x Lh scores per block)."""
    sp = jax.lax.axis_size(axis_name)
    d = jax.lax.axis_index(axis_name)
    scale = scale if scale is not None else 1.0 / np.sqrt(q.shape[-1])

    qh = jnp.swapaxes(q, 1, 2).astype(jnp.float32) * scale   # [B,H,2Lh,D]
    B, H, L2, D = qh.shape
    Lh = L2 // 2
    perm = [(i, (i + 1) % sp) for i in range(sp)]

    # ---- step 0: local causal attention over this shard's own tokens ----
    row = jax.lax.broadcasted_iota(jnp.int32, (L2, L2), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (L2, L2), 1)
    half_of = lambda i: i // Lh                      # 0 -> chunk d, 1 -> 2S-1-d
    pos = lambda i: jnp.where(half_of(i) == 0, d * Lh + i % Lh,
                              (2 * sp - 1 - d) * Lh + i % Lh)
    local_mask = pos(row) >= pos(col)
    kh = jnp.swapaxes(k, 1, 2).astype(jnp.float32)
    vh = jnp.swapaxes(v, 1, 2).astype(jnp.float32)
    s0 = jnp.where(local_mask, qh @ jnp.swapaxes(kh, -1, -2), -1e30)
    m = jnp.max(s0, axis=-1, keepdims=True)
    p0 = jnp.where(local_mask, jnp.exp(s0 - m), 0.0)
    l = jnp.sum(p0, -1, keepdims=True)
    acc = p0 @ vh

    m0, m1 = m[..., :Lh, :], m[..., Lh:, :]
    l0, l1 = l[..., :Lh, :], l[..., Lh:, :]
    a0, a1 = acc[..., :Lh, :], acc[..., Lh:, :]
    q0, q1 = qh[..., :Lh, :], qh[..., Lh:, :]

    def body(t, carry):
        k_cur, v_cur, m0, l0, a0, m1, l1, a1 = carry
        k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
        v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
        src = (d - t) % sp                   # owner of the visiting shard
        kh = jnp.swapaxes(k_cur, 1, 2).astype(jnp.float32)
        vh = jnp.swapaxes(v_cur, 1, 2).astype(jnp.float32)
        kc0, vc0 = kh[..., :Lh, :], vh[..., :Lh, :]   # chunk src
        kc1, vc1 = kh[..., Lh:, :], vh[..., Lh:, :]   # chunk 2S-1-src
        # block X (always needed, fully unmasked): qc1 attends chunk src
        m1, l1, a1 = _online_update(m1, l1, a1, q1 @ jnp.swapaxes(kc0, -1, -2),
                                    vc0)
        # block Y: earlier shard -> qc0 x kc0; later shard -> qc1 x kc1.
        # Gather the target accumulator first so the online update (the
        # expensive p@v matmul + exps) runs ONCE, then scatter back.
        early = src < d
        q_sel = jnp.where(early, q0, q1)
        k_sel = jnp.where(early, kc0, kc1)
        v_sel = jnp.where(early, vc0, vc1)
        m_sel = jnp.where(early, m0, m1)
        l_sel = jnp.where(early, l0, l1)
        a_sel = jnp.where(early, a0, a1)
        s = q_sel @ jnp.swapaxes(k_sel, -1, -2)
        m_new, l_new, a_new = _online_update(m_sel, l_sel, a_sel, s, v_sel)
        m0 = jnp.where(early, m_new, m0)
        l0 = jnp.where(early, l_new, l0)
        a0 = jnp.where(early, a_new, a0)
        m1 = jnp.where(early, m1, m_new)
        l1 = jnp.where(early, l1, l_new)
        a1 = jnp.where(early, a1, a_new)
        return k_cur, v_cur, m0, l0, a0, m1, l1, a1

    _, _, m0, l0, a0, m1, l1, a1 = jax.lax.fori_loop(
        1, sp, body, (k, v, m0, l0, a0, m1, l1, a1))
    out = jnp.concatenate([a0 / jnp.maximum(l0, 1e-30),
                           a1 / jnp.maximum(l1, 1e-30)], axis=2)
    return jnp.swapaxes(out, 1, 2).astype(q.dtype)


def _zigzag_perms(sp):
    """ppermute tables moving contiguous layout <-> zigzag layout.

    Contiguous shard e holds half-chunks (2e, 2e+1). Zigzag shard d wants
    (d, 2S-1-d). Each half-chunk c has contiguous owner c//2 and zigzag
    owner (c if c < S else 2S-1-c); one ppermute per half moves them."""
    owner_z = lambda c: c if c < sp else 2 * sp - 1 - c
    to_z_first = [(e, owner_z(2 * e)) for e in range(sp)]
    to_z_second = [(e, owner_z(2 * e + 1)) for e in range(sp)]
    return to_z_first, to_z_second


def _contig_to_zigzag(x, axis_name, sp):
    """[B, 2Lh, ...] contiguous shard -> zigzag shard, inside shard_map."""
    d = jax.lax.axis_index(axis_name)
    Lh = x.shape[1] // 2
    first, second = _zigzag_perms(sp)
    got_a = jax.lax.ppermute(x[:, :Lh], axis_name, first)
    got_b = jax.lax.ppermute(x[:, Lh:], axis_name, second)
    # zigzag shard d receives chunk d (goes to slot 0) and chunk 2S-1-d
    # (slot 1); chunk d arrives via `first` iff d even... both arrivals are
    # disjoint: exactly one of (got_a, got_b) is chunk d, the other 2S-1-d.
    # chunk d has contiguous owner d//2 sending its half (d%2==0 ? first :
    # second); build the slot choice from that parity.
    a_is_low = (d % 2) == 0          # `first` perm carries even chunks
    low = jnp.where(a_is_low, got_a, got_b)
    high = jnp.where(a_is_low, got_b, got_a)
    return jnp.concatenate([low, high], axis=1)


def _zigzag_to_contig(x, axis_name, sp):
    d = jax.lax.axis_index(axis_name)
    Lh = x.shape[1] // 2
    first, second = _zigzag_perms(sp)
    inv_first = [(b, a) for a, b in first]
    inv_second = [(b, a) for a, b in second]
    # zigzag shard d holds chunk d (slot 0) and 2S-1-d (slot 1); route
    # each back to its contiguous owner/half with the inverse perms.
    send_first = jnp.where((d % 2) == 0, x[:, :Lh], x[:, Lh:])
    send_second = jnp.where((d % 2) == 0, x[:, Lh:], x[:, :Lh])
    got_a = jax.lax.ppermute(send_first, axis_name, inv_first)
    got_b = jax.lax.ppermute(send_second, axis_name, inv_second)
    return jnp.concatenate([got_a, got_b], axis=1)


def ring_attention(q, k, v, mesh=None, axis_name="sp", causal=True,
                   batch_axes=("dp", "fsdp"), scale=None,
                   layout="contiguous", use_flash=None):
    """shard_map wrapper: q,k,v are GLOBAL [B, L, H, D] arrays (or already
    sharded); the sequence dim is split over `axis_name`.

    layout="zigzag" (causal only): re-shards contiguous shards into the
    load-balanced zigzag layout (2 ppermutes of half-shards each way),
    runs zigzag_ring_attention_local, and restores contiguous order —
    ~2x less attention compute at large sp for O(L·D) extra comms.

    use_flash (both layouts): per-ring-step Pallas flash blocks with
    lse-merged partials — O(L/sp) attention memory. None = auto (TPU +
    supported shape; zigzag additionally needs 128-aligned half-chunks).
    """
    from jax.sharding import PartitionSpec as P

    from ..distributed.mesh import get_mesh

    mesh = mesh or get_mesh()
    spec = P(batch_axes, axis_name, None, None)
    sp = mesh.shape.get(axis_name, 1)
    if layout == "zigzag" and causal and sp > 1:
        L = q.shape[1]
        if L % (2 * sp) != 0:
            raise ValueError(
                f"ring_attention(layout='zigzag') needs the sequence length "
                f"divisible by 2*sp = {2 * sp} (two half-chunks per shard); "
                f"got L={L} over sp={sp}")
        if use_flash is None:
            use_flash = (jax.default_backend() == "tpu"
                         and _flash_ring_ok(q.shape, k.shape[2],
                                            q.shape[1] // max(2 * sp, 1)))

        def fn(qv, kv, vv, _uf=use_flash):
            qz = _contig_to_zigzag(qv, axis_name, sp)
            kz = _contig_to_zigzag(kv, axis_name, sp)
            vz = _contig_to_zigzag(vv, axis_name, sp)
            oz = zigzag_ring_attention_local(qz, kz, vz,
                                             axis_name=axis_name,
                                             scale=scale, use_flash=_uf)
            return _zigzag_to_contig(oz, axis_name, sp)
        check_vma = not use_flash
    else:
        if use_flash is None:
            use_flash = (jax.default_backend() == "tpu" and sp > 1
                         and _flash_ring_ok(q.shape, k.shape[2],
                                            q.shape[1] // max(sp, 1)))
        fn = functools.partial(ring_attention_local, axis_name=axis_name,
                               causal=causal, scale=scale,
                               use_flash=use_flash)
        # the vma checker can't see through pallas_call's out_shape (same
        # caveat as ulysses.py); keep it active for the dense paths
        check_vma = not use_flash
    return jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                            out_specs=spec, check_vma=check_vma)(q, k, v)
