"""Pallas kernels vs jnp reference, interpret mode on CPU (SURVEY §4)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.attention import _flash, mha_reference
from paddle_tpu.ops.layer_norm import _ln_ref, _rms_ref, fused_layer_norm, fused_rms_norm


@pytest.mark.parametrize("causal", [True, False])
def test_flash_kernel_matches_reference(causal):
    rng = np.random.RandomState(0)
    B, L, H, D = 2, 512, 2, 64
    q, k, v = (jnp.asarray(rng.randn(B, L, H, D), jnp.float32) for _ in range(3))
    out = _flash(q, k, v, causal, 1.0 / np.sqrt(D))
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_kernel_grad():
    rng = np.random.RandomState(1)
    B, L, H, D = 1, 256, 2, 64
    q, k, v = (jnp.asarray(rng.randn(B, L, H, D), jnp.float32) for _ in range(3))

    g1 = jax.grad(lambda q, k, v: jnp.sum(_flash(q, k, v, True, 0.125) ** 2),
                  argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda q, k, v: jnp.sum(mha_reference(q, k, v, causal=True) ** 2),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_fused_layer_norm():
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(64, 256), jnp.float32)
    w = jnp.asarray(rng.randn(256), jnp.float32)
    b = jnp.asarray(rng.randn(256), jnp.float32)
    out = fused_layer_norm(x, w, b)
    ref = _ln_ref(x, w, b, 1e-5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
    # grads via custom vjp
    g1 = jax.grad(lambda x: jnp.sum(fused_layer_norm(x, w, b) ** 2))(x)
    g2 = jax.grad(lambda x: jnp.sum(_ln_ref(x, w, b, 1e-5) ** 2))(x)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=1e-4)


def test_fused_rms_norm():
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(32, 128), jnp.float32)
    w = jnp.asarray(rng.randn(128), jnp.float32)
    np.testing.assert_allclose(np.asarray(fused_rms_norm(x, w)),
                               np.asarray(_rms_ref(x, w, 1e-6)), atol=1e-5)


def test_fused_ln_odd_shapes_fallback():
    x = jnp.ones((3, 100), jnp.float32)  # h%128 != 0 → reference path
    w = jnp.ones((100,))
    b = jnp.zeros((100,))
    np.testing.assert_allclose(np.asarray(fused_layer_norm(x, w, b)),
                               np.asarray(_ln_ref(x, w, b, 1e-5)), atol=1e-6)


def test_flash_bwd_pallas_matches_xla_vjp():
    """Pallas flash backward (dQ/dKV kernels from saved logsumexp) vs the XLA
    vjp of the jnp reference — both causal and bidirectional."""
    from paddle_tpu.ops import attention as A
    rng = np.random.RandomState(0)
    B, L, H, D = 2, 256, 2, 64
    q = jnp.asarray(rng.randn(B, L, H, D).astype(np.float32)) * 0.1
    k = jnp.asarray(rng.randn(B, L, H, D).astype(np.float32)) * 0.1
    v = jnp.asarray(rng.randn(B, L, H, D).astype(np.float32)) * 0.1
    g = jnp.asarray(rng.randn(B, L, H, D).astype(np.float32))
    for causal in (False, True):
        scale = 1.0 / np.sqrt(D)
        out, lse = A._flash_fwd_lse_impl(q, k, v, causal, scale, interpret=True)
        ref = A.mha_reference(q, k, v, causal=causal, scale=scale)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
        dq, dk, dv = A._flash_bwd_impl(q, k, v, out, lse, g, causal, scale,
                                       interpret=True)
        _, vjp = jax.vjp(lambda q, k, v: A.mha_reference(
            q, k, v, causal=causal, scale=scale), q, k, v)
        rdq, rdk, rdv = vjp(g)
        np.testing.assert_allclose(np.asarray(dq), np.asarray(rdq), atol=1e-4)
        np.testing.assert_allclose(np.asarray(dk), np.asarray(rdk), atol=1e-4)
        np.testing.assert_allclose(np.asarray(dv), np.asarray(rdv), atol=1e-4)


def test_fused_softmax_cross_entropy():
    """ops/fused_ops.py streaming CE kernel vs logsumexp reference, incl. the
    GPT vocab (50304) whose block divisor is 384."""
    from paddle_tpu.ops.fused_ops import (_xent_fwd_impl, _xent_ref,
                                          fused_softmax_cross_entropy)
    rng = np.random.RandomState(0)
    for n, v in [(256, 1024), (256, 50304)]:
        logits = jnp.asarray(rng.randn(n, v).astype(np.float32))
        labels = jnp.asarray(rng.randint(0, v, n).astype(np.int32))
        loss, _ = _xent_fwd_impl(logits, labels, interpret=True)
        np.testing.assert_allclose(np.asarray(loss),
                                   np.asarray(_xent_ref(logits, labels)),
                                   atol=1e-4)
        grad = jax.grad(lambda l: fused_softmax_cross_entropy(l, labels).sum())(logits)
        ref = jax.nn.softmax(logits, axis=-1) - jax.nn.one_hot(labels, v)
        np.testing.assert_allclose(np.asarray(grad), np.asarray(ref), atol=1e-5)


def test_fused_adamw_matches_torch():
    import torch
    from paddle_tpu.ops.fused_ops import fused_adamw
    rng = np.random.RandomState(0)
    p = jnp.asarray(rng.randn(1000).astype(np.float32))
    g = jnp.asarray(rng.randn(1000).astype(np.float32))
    po, mo, vo = fused_adamw(p, g, jnp.zeros(1000), jnp.zeros(1000),
                             step=1, lr=1e-3, interpret=True)
    tp = torch.tensor(np.asarray(p), requires_grad=True)
    opt = torch.optim.AdamW([tp], lr=1e-3, weight_decay=0.01, eps=1e-8)
    tp.grad = torch.tensor(np.asarray(g))
    opt.step()
    np.testing.assert_allclose(np.asarray(po), tp.detach().numpy(), atol=1e-6)


def test_fused_dropout_residual_layer_norm_eval():
    from paddle_tpu.ops.fused_ops import (_dropout_res_ln_ref,
                                          fused_dropout_residual_layer_norm)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(256, 256).astype(np.float32))
    r = jnp.asarray(rng.randn(256, 256).astype(np.float32))
    w = jnp.asarray(rng.randn(256).astype(np.float32))
    b = jnp.asarray(rng.randn(256).astype(np.float32))
    out_k, h_k = fused_dropout_residual_layer_norm(x, r, w, b, p=0.1,
                                                   training=False, interpret=True)
    out_r, h_r = _dropout_res_ln_ref(x, r, w, b, jax.random.PRNGKey(0),
                                     0.1, 1e-5, False)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r), atol=1e-5)
    np.testing.assert_allclose(np.asarray(h_k), np.asarray(h_r), atol=1e-6)


def test_fused_dropout_residual_layer_norm_training_path():
    """The TRAINING (dropout) path of the kernel runs in interpret mode
    (mask bits drawn on the host there — the TPU prng primitives have no
    CPU lowering) and its threshold/scale/LN arithmetic matches a golden
    computed from the same bits."""
    from paddle_tpu.ops.fused_ops import fused_dropout_residual_layer_norm
    rng = np.random.RandomState(1)
    n, h, p, seed = 256, 128, 0.3, 5
    x = jnp.asarray(rng.randn(n, h).astype(np.float32))
    r = jnp.asarray(rng.randn(n, h).astype(np.float32))
    w = jnp.asarray(rng.randn(h).astype(np.float32))
    b = jnp.asarray(rng.randn(h).astype(np.float32))
    out_k, h_k = fused_dropout_residual_layer_norm(
        x, r, w, b, p=p, seed=seed, training=True, interpret=True)

    # golden from the identical host bits + the kernel's threshold rule
    bits = np.asarray(jax.random.bits(jax.random.PRNGKey(seed), (n, h),
                                      jnp.uint32))
    keep = bits <= np.uint32((1.0 - p) * (2 ** 32 - 1))
    xd = np.where(keep, np.asarray(x) / (1.0 - p), 0.0)
    hh = xd + np.asarray(r)
    mu = hh.mean(-1, keepdims=True)
    var = ((hh - mu) ** 2).mean(-1, keepdims=True)
    golden = (hh - mu) / np.sqrt(var + 1e-5) * np.asarray(w) + np.asarray(b)
    # dropout actually dropped something, and kept most of the rest
    assert 0.6 < keep.mean() < 0.8
    np.testing.assert_allclose(np.asarray(h_k), hh, atol=1e-5)
    np.testing.assert_allclose(np.asarray(out_k), golden, atol=1e-4)


def test_paged_attention_matches_dense():
    """ops/paged_attention.py — paged gather+softmax == dense attention over
    the sequence's actual history, jnp and kernel paths."""
    from paddle_tpu.ops.paged_attention import PagedKVCache, paged_attention
    rng = np.random.RandomState(0)
    H, D, P = 2, 64, 4
    cache = PagedKVCache(16, P, H, D, dtype=jnp.float32)
    hist = {}
    for sid, L in enumerate([6, 3]):
        cache.new_seq(sid)
        hist[sid] = []
        for _ in range(L):
            k = rng.randn(1, H, D).astype(np.float32)
            v = rng.randn(1, H, D).astype(np.float32)
            cache.append(sid, k, v)
            hist[sid].append((k, v))
    table, lens = cache.batch_view([0, 1])
    q = jnp.asarray(rng.randn(2, 1, H, D).astype(np.float32))
    out = paged_attention(q, cache.k_pages, cache.v_pages, table, lens)
    for b in range(2):
        ks = np.concatenate([k for k, _ in hist[b]], 0)
        vs = np.concatenate([v for _, v in hist[b]], 0)
        s = np.einsum("hd,lhd->hl", np.asarray(q[b, 0]), ks) / np.sqrt(D)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        ref = np.einsum("hl,lhd->hd", p, vs)
        np.testing.assert_allclose(np.asarray(out[b, 0]), ref, atol=1e-5)
    out_k = paged_attention(q, cache.k_pages, cache.v_pages, table, lens,
                            use_kernel=True, interpret=True)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out), atol=1e-5)


def test_flash_attention_gqa():
    """GQA (Hkv < Hq) via row-folding into the same kernels — fwd + bwd vs
    the repeat-kv reference, causal and bidirectional."""
    from paddle_tpu.ops import attention as A
    rng = np.random.RandomState(0)
    B, L, Hq, Hkv, D = 2, 256, 8, 2, 64
    q = jnp.asarray(rng.randn(B, L, Hq, D).astype(np.float32)) * 0.1
    k = jnp.asarray(rng.randn(B, L, Hkv, D).astype(np.float32)) * 0.1
    v = jnp.asarray(rng.randn(B, L, Hkv, D).astype(np.float32)) * 0.1
    g = jnp.asarray(rng.randn(B, L, Hq, D).astype(np.float32))
    for causal in (False, True):
        sc = 1.0 / np.sqrt(D)
        out, lse = A._flash_fwd_lse_impl(q, k, v, causal, sc, interpret=True)
        ref = A.mha_reference(q, k, v, causal=causal, scale=sc)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
        dq, dk, dv = A._flash_bwd_impl(q, k, v, out, lse, g, causal, sc,
                                       interpret=True)
        _, vjp = jax.vjp(lambda q, k, v: A.mha_reference(
            q, k, v, causal=causal, scale=sc), q, k, v)
        rdq, rdk, rdv = vjp(g)
        np.testing.assert_allclose(np.asarray(dq), np.asarray(rdq), atol=1e-4)
        np.testing.assert_allclose(np.asarray(dk), np.asarray(rdk), atol=1e-4)
        np.testing.assert_allclose(np.asarray(dv), np.asarray(rdv), atol=1e-4)


def test_flash_attention_nondivisible_256():
    """Sequences divisible by 128 but not 256 must tile exactly (L=384)."""
    from paddle_tpu.ops import attention as A
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(1, 384, 2, 64).astype(np.float32)) * 0.1
    k = jnp.asarray(rng.randn(1, 384, 2, 64).astype(np.float32)) * 0.1
    v = jnp.asarray(rng.randn(1, 384, 2, 64).astype(np.float32)) * 0.1
    out = A._flash_fwd_impl(q, k, v, True, 0.125, interpret=True)
    ref = A.mha_reference(q, k, v, causal=True, scale=0.125)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_paged_attention_kernel_path(monkeypatch):
    """The scalar-prefetch paged kernel (one page in VMEM per grid step) must
    run — fallback is a test failure here — and match the jnp reference."""
    from paddle_tpu.ops import paged_attention as P

    def no_fallback(name, err):
        raise AssertionError(f"kernel fell back: {err}")
    monkeypatch.setattr(P, "kernel_fallback", no_fallback)

    rng = np.random.RandomState(1)
    B, H, D, page, n_pages = 2, 2, 128, 8, 12
    k_pages = jnp.asarray(rng.randn(n_pages, page, H, D).astype(np.float32))
    v_pages = jnp.asarray(rng.randn(n_pages, page, H, D).astype(np.float32))
    # seq 0 uses pages [3, 5, 7] (len 20), seq 1 uses [2] (len 5)
    table = jnp.asarray(np.array([[3, 5, 7], [2, -1, -1]], np.int32))
    lens = jnp.asarray(np.array([20, 5], np.int32))
    q = jnp.asarray(rng.randn(B, 1, H, D).astype(np.float32))
    out_k = P.paged_attention(q, k_pages, v_pages, table, lens, use_kernel=True)
    out_r = P.paged_attention(q, k_pages, v_pages, table, lens, use_kernel=False)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=2e-5, atol=2e-5)


# -- more than one device: GSPMD cannot partition a Mosaic kernel, so the
# train step's kernels run once per device through shard_map (ops/_per_device)

@pytest.fixture
def sharded_mesh():
    from paddle_tpu.distributed import build_mesh
    from paddle_tpu.distributed import mesh as mesh_mod
    mesh = build_mesh(fsdp=2, tp=2, devices=jax.devices()[:4])
    yield mesh
    mesh_mod.set_mesh(None)


def _shard_maps(fn, *args):
    return str(jax.make_jaxpr(fn)(*args)).count("shard_map")


def test_flash_maps_over_batch_and_heads(sharded_mesh):
    rng = np.random.RandomState(2)
    B, L, H, D = 2, 256, 2, 64
    q, k, v = (jnp.asarray(rng.randn(B, L, H, D), jnp.float32) for _ in range(3))

    def loss(flash):
        return lambda q, k, v: jnp.sum(flash(q, k, v) ** 2)
    mapped = loss(lambda q, k, v: _flash(q, k, v, True, 0.125))
    ref = loss(lambda q, k, v: mha_reference(q, k, v, causal=True))
    grad = jax.jit(jax.value_and_grad(mapped, argnums=(0, 1, 2)))
    assert _shard_maps(grad, q, k, v) == 2              # forward, backward
    (l1, g1), (l2, g2) = grad(q, k, v), jax.value_and_grad(
        ref, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-5)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_flash_dropout_seed_differs_per_shard(sharded_mesh):
    from paddle_tpu.ops.attention import _fwd_lse
    rng = np.random.RandomState(3)
    B, L, H, D = 2, 128, 2, 64
    row = jnp.asarray(rng.randn(1, L, 1, D), jnp.float32)
    q = k = v = jnp.broadcast_to(row, (B, L, H, D))     # identical rows, heads
    d = jnp.zeros((1, 1), jnp.float32)
    cfg = (True, 0.125, 0.5, False, False, False, False, False)
    out, _ = jax.jit(lambda q, k, v: _fwd_lse(q, k, v, d, d, d + 7.0, cfg))(
        q, k, v)
    out = np.asarray(out)
    assert not np.allclose(out[0, :, 0], out[1, :, 0])  # fsdp shards
    assert not np.allclose(out[0, :, 0], out[0, :, 1])  # tp shards


def test_layer_norm_maps_over_rows(sharded_mesh):
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.randn(4, 16, 256), jnp.float32)
    w, b = (jnp.asarray(rng.randn(256), jnp.float32) for _ in range(2))
    fn = jax.jit(lambda x, w, b: fused_layer_norm(x, w, b))
    assert _shard_maps(fn, x, w, b) == 1
    np.testing.assert_allclose(np.asarray(fn(x, w, b)),
                               np.asarray(_ln_ref(x, w, b, 1e-5)), atol=1e-5)
    rms = jax.jit(lambda x, w: fused_rms_norm(x, w))
    assert _shard_maps(rms, x, w) == 1
    np.testing.assert_allclose(np.asarray(rms(x, w)),
                               np.asarray(_rms_ref(x, w, 1e-6)), atol=1e-5)


def test_cross_entropy_maps_over_row_blocks(sharded_mesh):
    from paddle_tpu.ops.fused_ops import (_xent_ref,
                                          fused_softmax_cross_entropy)
    rng = np.random.RandomState(5)
    n, v = 512, 256
    logits = jnp.asarray(rng.randn(n, v), jnp.float32)
    labels = jnp.asarray(rng.randint(0, v, n), jnp.int32)
    grad = jax.jit(jax.value_and_grad(
        lambda lg: jnp.mean(fused_softmax_cross_entropy(lg, labels))))
    assert _shard_maps(grad, logits) == 2
    (l1, g1) = grad(logits)
    (l2, g2) = jax.value_and_grad(
        lambda lg: jnp.mean(_xent_ref(lg, labels)))(logits)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=1e-6)
