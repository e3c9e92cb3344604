"""Ring attention vs reference attention on the virtual 8-device mesh."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.distributed import build_mesh
from paddle_tpu.ops.attention import mha_reference
from paddle_tpu.ops.ring_attention import ring_attention


@pytest.mark.parametrize("causal", [True, False])
def test_ring_matches_reference(causal):
    build_mesh(dp=2, sp=4)
    rng = np.random.RandomState(0)
    B, L, H, D = 2, 32, 4, 16
    q = jnp.asarray(rng.randn(B, L, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, L, H, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, L, H, D), jnp.float32)
    ref = mha_reference(q, k, v, causal=causal)
    out = ring_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.slow
def test_ring_grads_match():
    build_mesh(sp=8)
    rng = np.random.RandomState(1)
    B, L, H, D = 1, 16, 2, 8
    q = jnp.asarray(rng.randn(B, L, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, L, H, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, L, H, D), jnp.float32)

    def loss_ring(q, k, v):
        return jnp.sum(ring_attention(q, k, v, causal=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=True) ** 2)

    g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_flash_matches_reference(causal):
    """The flash-kernel ring path (per-step Pallas blocks + lse merge):
    fwd AND grads equal the dense reference — the O(L/sp)-memory
    long-context path, exercised here via kernel interpret mode."""
    build_mesh(sp=4)
    rng = np.random.RandomState(2)
    B, L, H, D = 2, 64, 2, 16
    q = jnp.asarray(rng.randn(B, L, H, D), jnp.float32) * 0.3
    k = jnp.asarray(rng.randn(B, L, H, D), jnp.float32) * 0.3
    v = jnp.asarray(rng.randn(B, L, H, D), jnp.float32) * 0.3
    ref = mha_reference(q, k, v, causal=causal)
    out = ring_attention(q, k, v, causal=causal, use_flash=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)

    def loss_flash(q, k, v):
        return jnp.sum(ring_attention(q, k, v, causal=causal,
                                      use_flash=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=causal) ** 2)

    g_f = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_r = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", g_f, g_r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4,
                                   err_msg=f"d{name}")


@pytest.mark.slow
def test_zigzag_flash_matches_reference():
    """Zigzag layout + flash kernel blocks: balanced compute AND O(L/sp)
    memory — fwd and grads equal the dense reference.

    slow-marked (tier-1 wall-clock, PR 15 re-measure: 89 s of the
    1566 s full sweep on the dev box — the 2nd-worst eager loop after
    its zigzag-ring sibling below): grad-of-flash under an sp=4 mesh
    is compile-bound. Tier-1 zigzag coverage stays with
    test_zigzag_layout_roundtrip + test_gpt_zigzag_sp_equals_single_
    device; the kernel-vs-reference grads run in `-m slow` sweeps."""
    build_mesh(sp=4)
    rng = np.random.RandomState(3)
    B, L, H, D = 2, 64, 2, 16          # Lh = 8 per shard
    q = jnp.asarray(rng.randn(B, L, H, D), jnp.float32) * 0.3
    k = jnp.asarray(rng.randn(B, L, H, D), jnp.float32) * 0.3
    v = jnp.asarray(rng.randn(B, L, H, D), jnp.float32) * 0.3
    ref = mha_reference(q, k, v, causal=True)
    out = ring_attention(q, k, v, causal=True, layout="zigzag",
                         use_flash=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)

    def loss_flash(q, k, v):
        return jnp.sum(ring_attention(q, k, v, causal=True, layout="zigzag",
                                      use_flash=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=True) ** 2)

    g_f = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_r = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", g_f, g_r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4,
                                   err_msg=f"d{name}")


@pytest.mark.slow
def test_ulysses_matches_reference():
    """ops/ulysses.py — all-to-all head-resharding SP equals full attention
    (fwd + grad) on the 8-device mesh.

    `slow`: seq-256 fwd x2 + three grad traces under an sp=8 mesh —
    36 s under full-suite load, the next-worst tier-1 entry after the
    PR-15 zigzag marks (docs/performance.md wall-clock table). The
    small fwd smoke below keeps ulysses tier-1-covered."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.distributed import build_mesh
    from paddle_tpu.ops.attention import mha_reference
    from paddle_tpu.ops.ulysses import ulysses_attention
    mesh = build_mesh(sp=8)
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(2, 256, 8, 32).astype(np.float32)) * 0.1
    k = jnp.asarray(rng.randn(2, 256, 8, 32).astype(np.float32)) * 0.1
    v = jnp.asarray(rng.randn(2, 256, 8, 32).astype(np.float32)) * 0.1
    for causal in (True, False):
        out = ulysses_attention(q, k, v, mesh=mesh, causal=causal)
        ref = mha_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
    g = jax.grad(lambda q: jnp.sum(
        ulysses_attention(q, k, v, mesh=mesh, causal=True) ** 2))(q)
    gr = jax.grad(lambda q: jnp.sum(mha_reference(q, k, v, causal=True) ** 2))(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gr), atol=1e-4)


def test_ulysses_smoke_small():
    """Tier-1 ulysses coverage after the reference test went `slow`: a
    seq-64 causal forward against the dense reference — exercises the
    all-to-all head reshard + attention path in a few seconds."""
    from paddle_tpu.distributed import build_mesh
    from paddle_tpu.ops.attention import mha_reference
    from paddle_tpu.ops.ulysses import ulysses_attention
    mesh = build_mesh(sp=8)
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(1, 64, 8, 16).astype(np.float32)) * 0.1
    k = jnp.asarray(rng.randn(1, 64, 8, 16).astype(np.float32)) * 0.1
    v = jnp.asarray(rng.randn(1, 64, 8, 16).astype(np.float32)) * 0.1
    out = ulysses_attention(q, k, v, mesh=mesh, causal=True)
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


@pytest.mark.slow
def test_gpt_ulysses_sp_mode():
    """GPT with sp_mode='ulysses' trains on an sp mesh and matches the
    ring-attention configuration's loss."""
    import paddle_tpu as paddle
    from paddle_tpu.distributed import build_mesh
    from paddle_tpu.models import GPT, GPTPretrainingCriterion
    from paddle_tpu.models.gpt import GPTConfig

    losses = {}
    for mode in ("ring", "ulysses"):
        paddle.seed(0)
        build_mesh(sp=4)
        cfg = GPTConfig(vocab_size=512, hidden_size=64, num_layers=2,
                        num_heads=4, max_seq_len=64, dtype="float32",
                        remat=False, sp_mode=mode)
        model = GPT(cfg)
        crit = GPTPretrainingCriterion()
        rng = np.random.RandomState(0)
        ids = paddle.to_tensor(rng.randint(0, 512, (2, 64)).astype(np.int32))
        lab = paddle.to_tensor(rng.randint(0, 512, (2, 64)).astype(np.int32))
        losses[mode] = float(crit(model(ids), lab))
    assert abs(losses["ring"] - losses["ulysses"]) < 1e-3, losses


@pytest.mark.slow
def test_zigzag_ring_matches_reference():
    """Zigzag (load-balanced) causal ring == plain attention, fwd + grad.

    slow-marked (tier-1 wall-clock, PR 15 re-measure: 139 s of the
    1566 s full sweep on the dev box — the WORST remaining eager
    loop): grad-of-ring under an sp=4 mesh is compile-bound. See the
    zigzag-flash note above for the coverage that stays tier-1."""
    from paddle_tpu.ops.ring_attention import ring_attention

    build_mesh(sp=4)
    rng = np.random.RandomState(3)
    B, L, H, D = 2, 32, 4, 16
    q, k, v = [jnp.asarray(rng.randn(B, L, H, D), jnp.float32) for _ in range(3)]

    ref = mha_reference(q, k, v, causal=True)
    zz = ring_attention(q, k, v, causal=True, layout="zigzag")
    np.testing.assert_allclose(np.asarray(zz), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)

    def loss_zz(q, k, v):
        return jnp.sum(ring_attention(q, k, v, causal=True,
                                      layout="zigzag") ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=True) ** 2)

    g_zz = jax.grad(loss_zz, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_zz, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-5)


def test_zigzag_layout_roundtrip():
    from functools import partial
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.ops.ring_attention import (_contig_to_zigzag,
                                               _zigzag_to_contig)

    mesh = build_mesh(sp=4)
    x = jnp.arange(8 * 16, dtype=jnp.float32).reshape(1, 16, 8)

    def rt(v):
        z = _contig_to_zigzag(v, "sp", 4)
        return _zigzag_to_contig(z, "sp", 4)

    out = jax.shard_map(rt, mesh=mesh, in_specs=P(None, "sp"),
                           out_specs=P(None, "sp"))(x)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x))


@pytest.mark.parametrize("axes", [{"sp": 4}, {"sp": 2, "tp": 2}],
                         ids=["sp4", "sp2xtp2"])
def test_gpt_zigzag_sp_equals_single_device(axes):
    """GPT with sp_mode='zigzag' trains identically to dp=1, alone and
    composed with tensor parallelism."""
    import paddle_tpu as paddle
    from paddle_tpu.distributed.trainer import Trainer
    from paddle_tpu.models import GPT, GPTConfig, GPTPretrainingCriterion

    def cfg():
        return GPTConfig(vocab_size=128, hidden_size=32, num_layers=2,
                         num_heads=4, max_seq_len=32, dtype="float32",
                         remat=False, sp_mode="zigzag")

    crit = GPTPretrainingCriterion()

    def loss_fn(m, b):
        return crit(m(paddle.to_tensor(b["input_ids"])),
                    paddle.to_tensor(b["labels"]))

    rng = np.random.RandomState(0)
    ids = rng.randint(0, 128, (2, 33))
    batch = {"input_ids": ids[:, :-1].astype("int32"),
             "labels": ids[:, 1:].astype("int32")}
    losses = {}
    for mesh_axes in ({"dp": 1}, axes):
        paddle.seed(9)
        build_mesh(**mesh_axes)
        model = GPT(cfg())
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=model.parameters())
        t = Trainer(model, opt, loss_fn)
        losses[tuple(mesh_axes)] = [float(t.step(batch)) for _ in range(3)]
    vals = list(losses.values())
    np.testing.assert_allclose(vals[0], vals[1], rtol=2e-4)
