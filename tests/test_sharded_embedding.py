"""Mesh-sharded embedding (distributed/sharded_embedding.py) — the TPU
answer to reference PS-mode sparse tables
(python/paddle/distributed/ps/the_one_ps.py)."""
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

import paddle_tpu as paddle
from paddle_tpu.distributed import ShardedEmbedding, build_mesh
from paddle_tpu.distributed.trainer import Trainer
from paddle_tpu.nn.layer_base import functional_call


def test_parity_vs_dense_embedding():
    """Same weights -> bit-identical lookups and gradients."""
    paddle.seed(0)
    build_mesh(dp=2, tp=4)
    dense = paddle.nn.Embedding(64, 16)
    sharded = ShardedEmbedding(64, 16, shard_axes=("dp", "tp"))
    sharded.weight._value = dense.weight._value
    assert sharded.shard_axes == ("dp", "tp")
    ids = paddle.to_tensor(np.random.RandomState(0).randint(
        0, 64, (4, 8)).astype("int64"))

    y_d = dense(ids)
    y_s = sharded(ids)
    np.testing.assert_array_equal(np.asarray(y_d._value),
                                  np.asarray(y_s._value))

    def loss(w, emb):
        with functional_call(emb, {"weight": w}):
            return (emb(ids) ** 2).sum()._value
    g_d = jax.grad(loss)(dense.weight._value, dense)
    g_s = jax.grad(loss)(sharded.weight._value, sharded)
    np.testing.assert_allclose(np.asarray(g_d), np.asarray(g_s), rtol=1e-6)


def test_padding_idx_zeroes_rows():
    paddle.seed(0)
    build_mesh(tp=4)
    e = ShardedEmbedding(32, 8, padding_idx=0, shard_axes="tp")
    ids = paddle.to_tensor(np.array([[0, 3], [5, 0]], np.int64))
    out = np.asarray(e(ids)._value)
    assert np.all(out[0, 0] == 0) and np.all(out[1, 1] == 0)
    assert not np.all(out[0, 1] == 0)


def test_nondividing_axes_dropped_at_plan_time():
    """Feasibility resolves against the mesh when the PLAN is built, so
    layers constructed before build_mesh still shard correctly."""
    from paddle_tpu.distributed import plan_shardings
    from paddle_tpu.distributed.mesh import get_mesh
    build_mesh(dp=2, tp=4)
    e = ShardedEmbedding(30, 8, shard_axes=("dp", "tp"))  # 30 % 8 != 0
    assert e.shard_axes == ("dp", "tp")                   # request kept
    spec = plan_shardings(e, get_mesh())["weight"].spec
    assert "dp" in str(spec[0]) and "tp" not in str(spec)  # 30 % 2 == 0

    # layer built BEFORE the mesh it trains on: plan still shards rows
    build_mesh(dp=8)
    e2 = ShardedEmbedding(64, 8, shard_axes=("dp", "tp"))
    build_mesh(dp=2, tp=4)
    spec2 = plan_shardings(e2, get_mesh())["weight"].spec
    assert "dp" in str(spec2[0]) and "tp" in str(spec2[0])


def test_wide_table_trains_row_sharded():
    """PS-scale scenario: the table shards over dp*tp=8, each device
    holding V/8 rows; one Trainer step updates only touched rows."""
    paddle.seed(0)
    mesh = build_mesh(dp=2, tp=4)

    class WideModel(paddle.nn.Layer):
        def __init__(self):
            super().__init__()
            self.emb = ShardedEmbedding(1024, 32, shard_axes=("dp", "tp"))
            self.fc = paddle.nn.Linear(32, 1)

        def forward(self, ids):
            return self.fc(self.emb(ids).mean(axis=1))

    model = WideModel()
    opt = paddle.optimizer.Adam(learning_rate=0.1)

    def loss_fn(m, b):
        out = m(paddle.to_tensor(b["ids"]))
        return ((out - paddle.to_tensor(b["y"])) ** 2).mean()

    trainer = Trainer(model, opt, loss_fn)
    table = trainer.params["emb.weight"]
    # physically sharded: each device holds 1024/8 = 128 rows
    shard_rows = {s.data.shape[0] for s in table.addressable_shards}
    assert shard_rows == {128}, shard_rows
    assert "dp" in str(table.sharding.spec) and "tp" in str(table.sharding.spec)

    rng = np.random.RandomState(0)
    batch = {"ids": rng.randint(0, 1024, (8, 4)).astype("int32"),
             "y": rng.randn(8, 1).astype("float32")}
    before = np.asarray(jax.device_get(table))
    losses = [float(trainer.step(batch)) for _ in range(5)]
    assert losses[-1] < losses[0], losses
    after = np.asarray(jax.device_get(trainer.params["emb.weight"]))
    touched = np.unique(batch["ids"])
    untouched = np.setdiff1d(np.arange(1024), touched)
    # Adam with zero grad leaves untouched rows EXACTLY as they were
    np.testing.assert_array_equal(before[untouched], after[untouched])
    assert not np.allclose(before[touched], after[touched])


def test_manual_shard_map_lookup_matches_dense():
    """Inside a shard_map body the layer runs the explicit recipe:
    local-slice lookup + psum over the shard axis."""
    from paddle_tpu.distributed.mesh import axis_scope, get_mesh
    paddle.seed(0)
    mesh = build_mesh(tp=4)
    V, D = 64, 16
    e = ShardedEmbedding(V, D, padding_idx=3, shard_axes="tp")
    w = e.weight._value
    ids = jnp.asarray(np.random.RandomState(1).randint(0, V, (4, 8)),
                      jnp.int32)

    def body(ids_local, w_local):
        with axis_scope("tp"):
            with functional_call(e, {"weight": w_local}):
                out = e(paddle.Tensor(ids_local))
        return out._value

    out = jax.shard_map(body, mesh=get_mesh(),
                           in_specs=(P(), P("tp", None)),
                           out_specs=P())(ids, w)
    with functional_call(e, {"weight": w}):
        expect = e(paddle.Tensor(ids))._value  # GSPMD/dense path
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-6)
