"""auto_parallel, quantization, inference predictor, meta_parallel layers,
collective API semantics."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.distributed import build_mesh, fleet


def test_auto_parallel_shard_tensor():
    from paddle_tpu.distributed.auto_parallel import ProcessMesh, shard_tensor
    pm = ProcessMesh(np.arange(8).reshape(2, 4), dim_names=["x", "y"])
    t = paddle.rand([8, 16])
    t = shard_tensor(t, pm, ["x", "y"])
    assert len(t._value.sharding.device_set) == 8


def test_column_row_parallel_linear_match_dense():
    paddle.seed(0)
    build_mesh(tp=4, dp=2)
    col = fleet.ColumnParallelLinear(16, 32, gather_output=True)
    row = fleet.RowParallelLinear(32, 16)
    x = paddle.rand([4, 16])
    # same math as plain linears with the same weights
    y = row(col(x))
    wq, bq = col.weight.numpy(), col.bias.numpy()
    wr = row.weight.numpy()
    br = row.bias.numpy()
    expect = (x.numpy() @ wq + bq) @ wr + br
    np.testing.assert_allclose(y.numpy(), expect, rtol=1e-4, atol=1e-5)


def test_vocab_parallel_embedding():
    paddle.seed(0)
    build_mesh(tp=4)
    emb = fleet.VocabParallelEmbedding(128, 32)
    ids = paddle.to_tensor(np.array([[0, 5, 127]], "int32"))
    out = emb(ids)
    np.testing.assert_allclose(out.numpy()[0, 1], emb.weight.numpy()[5], rtol=1e-6)


def test_collectives_inside_shard_map():
    build_mesh(dp=8)
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.distributed import all_reduce, get_mesh
    from paddle_tpu.distributed.mesh import axis_scope

    mesh = get_mesh()

    def local(x):
        with axis_scope("dp"):
            return all_reduce(x)

    x = jnp.arange(8.0)
    out = jax.shard_map(local, mesh=mesh, in_specs=P("dp"),
                           out_specs=P("dp"))(x)
    np.testing.assert_allclose(np.asarray(out), np.full(8, 28.0))


def test_quantized_linear_close_to_dense():
    paddle.seed(0)
    from paddle_tpu.quantization import QuantizedLinear, quantize_model
    lin = nn.Linear(64, 128)
    qlin = QuantizedLinear(lin)
    x = paddle.rand([4, 64])
    dense = x.numpy() @ lin.weight.numpy() + lin.bias.numpy()
    np.testing.assert_allclose(qlin(x).numpy(), dense, rtol=0.05, atol=0.05)

    model = nn.Sequential(nn.Linear(64, 128), nn.ReLU(), nn.Linear(128, 64))
    quantize_model(model)
    assert type(model[0]).__name__ == "QuantizedLinear"
    assert type(model[2]).__name__ == "QuantizedLinear"


def test_inference_predictor():
    from paddle_tpu.inference import Config, create_predictor
    paddle.seed(0)
    m = nn.Linear(8, 4)
    pred = create_predictor(Config().set_model(m))
    x = np.random.rand(2, 8).astype("float32")
    (out,) = pred.run([x])
    np.testing.assert_allclose(out.numpy(), x @ m.weight.numpy() + m.bias.numpy(),
                               rtol=1e-4)


def test_grad_accum_matches_full_batch():
    from paddle_tpu.distributed.trainer import Trainer
    from paddle_tpu.models import GPT, GPTConfig, GPTPretrainingCriterion
    cfg = GPTConfig(vocab_size=128, hidden_size=32, num_layers=1, num_heads=2,
                    max_seq_len=16, dtype="float32", remat=False)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, 128, (8, 17))
    batch = {"input_ids": ids[:, :-1].astype("int32"),
             "labels": ids[:, 1:].astype("int32")}
    crit = GPTPretrainingCriterion()

    def loss_fn(m, b):
        return crit(m(paddle.to_tensor(b["input_ids"])), paddle.to_tensor(b["labels"]))

    results = {}
    for accum in (1, 4):
        paddle.seed(9)
        build_mesh(dp=1)
        model = GPT(cfg)
        opt = paddle.optimizer.SGD(learning_rate=0.1, parameters=model.parameters())
        tr = Trainer(model, opt, loss_fn, grad_accum_steps=accum)
        results[accum] = [float(tr.step(batch)) for _ in range(3)]
    np.testing.assert_allclose(results[1], results[4], rtol=1e-4)


def test_group_sharded_parallel():
    from paddle_tpu.distributed.sharding import group_sharded_parallel
    build_mesh(fsdp=8)
    paddle.seed(0)
    m = nn.Linear(64, 256)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3, parameters=m.parameters())
    m2, opt2, scaler = group_sharded_parallel(m, opt)
    assert scaler is None
    assert len(m2.weight._value.sharding.device_set) == 8
