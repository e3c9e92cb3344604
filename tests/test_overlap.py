"""Chunked collective-matmul overlap (ops/overlap.py): every ring
decomposition must be BIT-IDENTICAL to its bulk-collective twin — the
whole contract that lets tp_overlap/dp_overlap default-off configs and
overlapped configs share golden outputs. Pins the degenerate shapes
(n_chunks=1, ragged tail chunk, 1-participant axis, indivisible free
dim) per dtype, the tp GPT block through the model wiring, and the
dp-overlapped Trainer's losses across seeds."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed import build_mesh
from paddle_tpu.distributed.trainer import Trainer
from paddle_tpu.models import GPT, GPTConfig, GPTPretrainingCriterion
from paddle_tpu.ops.overlap import (chunked_all_gather_matmul,
                                    chunked_all_reduce,
                                    chunked_matmul_all_reduce,
                                    chunked_matmul_reduce_scatter,
                                    overlap_all_gather_matmul,
                                    overlap_matmul_all_reduce,
                                    overlap_matmul_reduce_scatter)

P = 4   # tp participants; the virtual mesh has 8 devices


def tp_mesh(p=P):
    return build_mesh(tp=p, devices=jax.devices()[:p])


def _mats(m, k, n, dtype, seed=0):
    """GLOBAL operands for the row-parallel wrappers: x [m, P*k] (last
    dim tp-sharded), w [P*k, n] (first dim tp-sharded)."""
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(m, P * k), dtype)
    w = jnp.asarray(rng.randn(P * k, n), dtype)
    return x, w


def _bits(a):
    return np.asarray(a).tobytes()


# a bfloat16 ring aborts XLA:CPU's compile on the installed jaxlib ("Fatal
# Python error: Aborted"), which kills the xdist worker and stalls the run
DTYPES = ["float32", pytest.param("bfloat16", marks=pytest.mark.slow)]


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("n_chunks", [1, 3, 4])
def test_matmul_all_reduce_bit_identical(dtype, n_chunks):
    """ring == bulk psum twin, bit for bit — including n_chunks=1 (one
    tile IS the bulk matmul) and n_chunks=3 (ragged tail: 12 cols over
    4 devices -> 3-col dest blocks split 2/1... per chunk)."""
    mesh = tp_mesh()
    x, w = _mats(8, 16, 12, dtype)
    ring = jax.jit(lambda x, w: overlap_matmul_all_reduce(
        x, w, axis="tp", n_chunks=n_chunks, mesh=mesh, impl="ring"))
    bulk = jax.jit(lambda x, w: overlap_matmul_all_reduce(
        x, w, axis="tp", n_chunks=n_chunks, mesh=mesh, impl="bulk"))
    assert _bits(ring(x, w)) == _bits(bulk(x, w))


@pytest.mark.parametrize("dtype", DTYPES)
def test_matmul_reduce_scatter_bit_identical(dtype):
    mesh = tp_mesh()
    x, w = _mats(8, 16, 16, dtype)
    for n_chunks in (1, 2, 4):
        ring = jax.jit(lambda x, w: overlap_matmul_reduce_scatter(
            x, w, axis="tp", n_chunks=n_chunks, mesh=mesh,
            impl="ring"))
        bulk = jax.jit(lambda x, w: overlap_matmul_reduce_scatter(
            x, w, axis="tp", n_chunks=n_chunks, mesh=mesh,
            impl="bulk"))
        assert _bits(ring(x, w)) == _bits(bulk(x, w))


@pytest.mark.parametrize("dtype", DTYPES)
def test_all_gather_matmul_bit_identical(dtype):
    mesh = tp_mesh()
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(8, 16), dtype)      # row-sharded over tp
    w = jnp.asarray(rng.randn(16, 12), dtype)
    for n_chunks in (1, 4, 7):
        ring = jax.jit(lambda x, w: overlap_all_gather_matmul(
            x, w, axis="tp", n_chunks=n_chunks, mesh=mesh, impl="ring"))
        bulk = jax.jit(lambda x, w: overlap_all_gather_matmul(
            x, w, axis="tp", n_chunks=n_chunks, mesh=mesh, impl="bulk"))
        assert _bits(ring(x, w)) == _bits(bulk(x, w))


def test_indivisible_free_dim_all_reduce():
    """N % p != 0: the all-reduce falls back to one bulk dot with a
    chunked exchange — still bit-identical to the psum twin."""
    mesh = tp_mesh()
    x, w = _mats(4, 16, 97, "float32")
    ring = jax.jit(lambda x, w: overlap_matmul_all_reduce(
        x, w, axis="tp", n_chunks=4, mesh=mesh, impl="ring"))
    bulk = jax.jit(lambda x, w: overlap_matmul_all_reduce(
        x, w, axis="tp", n_chunks=4, mesh=mesh, impl="bulk"))
    assert _bits(ring(x, w)) == _bits(bulk(x, w))


def test_reduce_scatter_raises_on_indivisible():
    mesh = tp_mesh()
    x, w = _mats(4, 16, 10, "float32")
    with pytest.raises(ValueError, match="divisible"):
        overlap_matmul_reduce_scatter(x, w, axis="tp", mesh=mesh)


def test_single_participant_axis_is_noop_zero_wire():
    """A 1-participant axis folds to the plain matmul: no collective
    primitive anywhere in the captured body — zero wire, not a
    degenerate ring of self-sends."""
    def body(x, w):
        return chunked_matmul_all_reduce(x, w, "tp", n_chunks=4)
    jx = jax.make_jaxpr(body, axis_env=[("tp", 1)])(
        jnp.zeros((4, 8), jnp.float32), jnp.zeros((8, 12), jnp.float32))

    def prims(j, acc):
        for e in j.eqns:
            acc.add(e.primitive.name)
            for v in e.params.values():
                if hasattr(v, "jaxpr"):
                    prims(v.jaxpr, acc)
        return acc
    names = prims(jx.jaxpr, set())
    assert "dot_general" in names
    assert not names & {"ppermute", "psum", "all_gather",
                        "reduce_scatter", "psum_scatter"}, names


@pytest.mark.parametrize("dtype", DTYPES)
def test_chunked_all_reduce_matches_psum(dtype):
    """The array twin (dp grad buckets ride this): full-exchange ring
    == lax.psum, f32 and bf16."""
    mesh = tp_mesh(8)
    from jax.sharding import PartitionSpec as Spec

    g = jnp.asarray(np.random.RandomState(3).randn(8, 5, 7), dtype)
    ring = jax.shard_map(
        lambda v: chunked_all_reduce(v[0], "tp"), mesh=mesh,
        in_specs=(Spec("tp"),), out_specs=Spec(),
        axis_names={"tp"}, check_vma=False)
    ref = jax.shard_map(
        lambda v: jax.lax.psum(v[0], "tp"), mesh=mesh,
        in_specs=(Spec("tp"),), out_specs=Spec(),
        axis_names={"tp"}, check_vma=False)
    assert _bits(jax.jit(ring)(g)) == _bits(jax.jit(ref)(g))


def _tiny_cfg(**kw):
    base = dict(vocab_size=256, hidden_size=64, num_layers=2,
                num_heads=4, max_seq_len=64, dtype="float32",
                remat=False)
    base.update(kw)
    return GPTConfig(**base)


def _batch(bs=8, L=16, vocab=256, seed=0):
    rng = np.random.RandomState(seed)
    ids = rng.randint(0, vocab, (bs, L + 1))
    return {"input_ids": ids[:, :-1].astype("int32"),
            "labels": ids[:, 1:].astype("int32")}


def _loss_fn(model, batch):
    logits = model(paddle.to_tensor(batch["input_ids"]))
    return GPTPretrainingCriterion()(logits,
                                     paddle.to_tensor(batch["labels"]))


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_gpt_block_tp_overlap_bit_identical(seed):
    """The wired model path: a tp=4 GPT forward with tp_overlap='ring'
    is bit-identical to tp_overlap='bulk' (the GSPMD psum twin) —
    per seed, through embedding/attention/FFN/proj."""
    ids = _batch(bs=2, L=16, seed=seed)["input_ids"]
    logits = {}
    for impl in ("bulk", "ring"):
        paddle.seed(seed)
        tp_mesh()
        model = GPT(_tiny_cfg(tp_overlap=impl, tp_overlap_chunks=2))
        logits[impl] = np.asarray(
            model(paddle.to_tensor(ids))._value)
    assert _bits(logits["ring"]) == _bits(logits["bulk"])


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_trainer_dp_overlap_bit_identical(seed):
    """dp=8 training with the bucketed chunked grad all-reduce: ring
    losses == bulk losses bit for bit over real AdamW steps."""
    losses = {}
    for impl in ("bulk", "ring"):
        paddle.seed(seed)
        build_mesh(dp=8)
        model = GPT(_tiny_cfg())
        opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                     parameters=model.parameters())
        tr = Trainer(model, opt, _loss_fn, dp_overlap=impl,
                     dp_overlap_buckets=3)
        losses[impl] = [float(tr.step(_batch(seed=seed)))
                        for _ in range(2)]
    assert losses["ring"] == losses["bulk"], losses


def test_trainer_dp_overlap_matches_gspmd_path():
    """The overlapped trainer trains the same model: losses allclose
    to the default GSPMD dp path (not bit-pinned — different reduction
    association by construction)."""
    runs = {}
    for mode in ("off", "ring"):
        paddle.seed(7)
        build_mesh(dp=8)
        model = GPT(_tiny_cfg())
        opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                     parameters=model.parameters())
        kw = {} if mode == "off" else dict(dp_overlap=mode,
                                           dp_overlap_buckets=2)
        tr = Trainer(model, opt, _loss_fn, **kw)
        runs[mode] = [float(tr.step(_batch())) for _ in range(2)]
    assert np.allclose(runs["off"], runs["ring"], rtol=1e-5)


def test_trainer_dp_overlap_rejects_grad_transform():
    paddle.seed(0)
    build_mesh(dp=8)
    model = GPT(_tiny_cfg())
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    with pytest.raises(ValueError, match="dp_overlap"):
        Trainer(model, opt, _loss_fn, dp_overlap="ring",
                grad_transform=lambda g: g)


def test_gpt_config_validates_tp_overlap():
    with pytest.raises(ValueError, match="tp_overlap"):
        _tiny_cfg(tp_overlap="nope")
