"""Compile gate: the TPU's own compiler, no TPU.

Every Pallas kernel the GPT-1.3B train step uses, at its real shapes, and
the whole step at gpt_1p3b width, compiled for a *described* v5e
(on-chip-measurement guide section 2.3). Nothing executes: this shows what the
chip's compiler accepts and allocates, not results or times.

The topology is described only inside this file's module-scoped fixture,
after a test of this file has started: the process that describes it loads
the TPU's library and keeps it, so it must be the one worker xdist hands this
file to, and all such tests live in this one file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from chip_expert_path import SHAPES as EXPERT_WALKS

V5E_HBM = 16 << 30
# gpt_1p3b at bs8 x seq1024
B, S, H, D, HIDDEN, VOCAB = 8, 1024, 16, 128, 2048, 50304
ROWS = B * S


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an executable compiled for a described chip can be written to the
    # persistent cache but not read back without one
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def as_tpu(monkeypatch):
    """`ops/` and `models/` pick kernels by `jax.default_backend()`, which
    still says cpu here: the test steers it, the program has no option."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def spec(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def compile_for(fn, *specs):
    return jax.jit(fn).lower(*specs).compile()


def pallas_calls(compiled):
    return compiled.as_text().count("tpu_custom_call")


def _flash_specs(one_chip):
    qkv = spec(one_chip, (B, S, H, D), jnp.bfloat16)
    dummy = spec(one_chip, (1, 1), jnp.float32)
    return qkv, dummy


def _flash_cfg():
    from paddle_tpu.ops.attention import _plain_cfg
    return _plain_cfg(True, D ** -0.5)


def test_flash_forward(one_chip):
    from paddle_tpu.ops.attention import _fwd_lse_impl
    qkv, dummy = _flash_specs(one_chip)
    compiled = compile_for(
        lambda q, k, v, a, b, c: _fwd_lse_impl(q, k, v, a, b, c, _flash_cfg(),
                                               interpret=False),
        qkv, qkv, qkv, dummy, dummy, dummy)
    assert pallas_calls(compiled) == 1


def test_flash_backward_dq_and_dkv(one_chip):
    from paddle_tpu.ops.attention import _bwd_impl
    qkv, dummy = _flash_specs(one_chip)
    lse = spec(one_chip, (B, H, S, 1), jnp.float32)
    compiled = compile_for(
        lambda q, k, v, lse, g, out, a, b, c: _bwd_impl(
            q, k, v, lse, g, out, a, b, c, _flash_cfg(), interpret=False),
        qkv, qkv, qkv, lse, qkv, qkv, dummy, dummy, dummy)
    assert pallas_calls(compiled) == 2          # _bwd_dq_kernel, _bwd_dkv_kernel


def test_fused_layer_norm(one_chip, as_tpu):
    from paddle_tpu.ops.layer_norm import _ln_fwd_impl
    x = spec(one_chip, (B, S, HIDDEN), jnp.bfloat16)
    w = spec(one_chip, (HIDDEN,), jnp.bfloat16)
    assert pallas_calls(compile_for(_ln_fwd_impl, x, w, w)) == 1


def test_cross_entropy_forward(one_chip):
    from paddle_tpu.ops.fused_ops import _xent_fwd_impl
    compiled = compile_for(
        lambda lg, lab: _xent_fwd_impl(lg, lab, interpret=False),
        spec(one_chip, (ROWS, VOCAB), jnp.float32),
        spec(one_chip, (ROWS,), jnp.int32))
    assert pallas_calls(compiled) == 1


def test_cross_entropy_backward(one_chip):
    from paddle_tpu.ops.fused_ops import _xent_bwd_impl
    compiled = compile_for(
        lambda lg, lab, lse, g: _xent_bwd_impl(lg, lab, lse, g,
                                               interpret=False),
        spec(one_chip, (ROWS, VOCAB), jnp.float32),
        spec(one_chip, (ROWS,), jnp.int32),
        spec(one_chip, (ROWS, 1), jnp.float32),
        spec(one_chip, (ROWS,), jnp.float32))
    assert pallas_calls(compiled) == 1


# the held experts' walk at the two MLA cells' real widths (the table of
# `chip_expert_path.py`, which measures the same shapes on the chip), at the
# rows of a decode tick and of the largest chunk bucket
@pytest.mark.parametrize("cell,rows", [
    (c, r) for c, sh in sorted(EXPERT_WALKS.items())
    for r in (sh["rows"][0], sh["rows"][-1])])
def test_held_expert_walk(one_chip, as_tpu, cell, rows):
    """Three grouped products over the whole [layers, held, ...] stacks:
    the chip's compiler takes the tiles `_expert_tiles` chooses, and no
    expert's matrix (nor a layer's experts) is sliced out of a stack."""
    from paddle_tpu.models.deepseek_v2 import held_expert_walk
    sh = EXPERT_WALKS[cell]
    h, f, held, k = sh["h"], sh["f"], sh["held"], sh["k"]
    up = spec(one_chip, (sh["layers"], held, h, f), jnp.bfloat16)
    w = {"gate": up, "up": up,
         "down": spec(one_chip, (sh["layers"], held, f, h), jnp.bfloat16)}
    compiled = compile_for(
        lambda w, x, cw, ei, valid, layer: held_expert_walk(
            w, x, cw, ei, held, 0, valid, layer),
        w, spec(one_chip, (rows, h), jnp.bfloat16),
        spec(one_chip, (rows, k), jnp.float32),
        spec(one_chip, (rows, k), jnp.int32),
        spec(one_chip, (rows,), jnp.bool_), spec(one_chip, (), jnp.int32))
    assert pallas_calls(compiled) == 3
    text = compiled.as_text()
    for shape in (f"bf16[1,1,{h},{f}]", f"bf16[1,1,{f},{h}]",
                  f"bf16[1,{held},{h},{f}]", f"bf16[{held},{h},{f}]"):
        assert shape not in text, shape


# ---------------------------------------------------------------- whole step

def compile_train_step(topo, layers, batch=B, seq=S, **axes):
    """chip_smoke.py's trainer, its step program compiled for described
    v5e chips: one, or a mesh of `axes` (fsdp=2, tp=2). The trainer is
    built on CPU devices (its constructor places real arrays), then pointed
    at the described ones for the trace, every argument keeping the
    PartitionSpec the trainer gave it."""
    import chip_smoke
    from jax.sharding import NamedSharding, PartitionSpec

    import paddle_tpu as paddle
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.io.prefetch import batch_shardings
    from paddle_tpu.models import GPT, gpt_1p3b

    n = int(np.prod(list(axes.values()) or [1]))
    cpu = mesh_mod.build_mesh(devices=jax.devices()[:n], **axes)
    paddle.seed(0)
    model = GPT(gpt_1p3b(max_seq_len=seq, num_layers=layers,
                         remat_policy="full"))
    model.bfloat16()
    trainer = chip_smoke.build_trainer(model, cpu)
    chip = Mesh(np.asarray(topo.devices[:n]).reshape(cpu.devices.shape),
                cpu.axis_names)
    trainer.mesh = chip
    mesh_mod.set_mesh(chip)

    state = jax.tree_util.tree_map(
        lambda v: jax.ShapeDtypeStruct(
            v.shape, v.dtype, sharding=NamedSharding(chip, v.sharding.spec)),
        (trainer.params, trainer.opt_state, trainer.gt_state,
         trainer.consts))
    ids = np.zeros((batch, seq), np.int32)
    tokens = jax.tree_util.tree_map(
        lambda v, sh: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=sh),
        {"input_ids": ids, "labels": ids},
        batch_shardings({"input_ids": ids, "labels": ids}, chip))
    lr = jax.ShapeDtypeStruct((), jnp.float32,
                              sharding=NamedSharding(chip, PartitionSpec()))
    args = state + (lr, tokens)
    in_sh = jax.tree_util.tree_map(lambda s: s.sharding, args)
    return trainer._build(True, in_shardings=in_sh).lower(*args).compile()


def device_bytes(compiled):
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.temp_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes
            + m.generated_code_size_in_bytes)


def test_train_step_two_layers(topo, as_tpu):
    compiled = compile_train_step(topo, layers=2)
    # per layer: flash forward (and its remat twin), dq, dkv, and LayerNorms;
    # plus the cross-entropy pair
    assert pallas_calls(compiled) >= 2 * 3 + 2
    assert device_bytes(compiled) < V5E_HBM


def test_sharded_train_step_two_layers(topo, as_tpu):
    """chip_smoke.py --chips 4: bs4 on fsdp=2 x tp=2. GSPMD cannot partition
    a Mosaic kernel, so every kernel has to arrive inside a shard_map."""
    compiled = compile_train_step(topo, layers=2, batch=4, fsdp=2, tp=2)
    text = compiled.as_text()
    assert pallas_calls(compiled) >= 2 * 3 + 2
    assert "all-gather" in text and "all-reduce" in text
    assert device_bytes(compiled) < V5E_HBM             # bytes on each device


@pytest.mark.slow
def test_train_step_24_layers(topo, as_tpu):
    compiled = compile_train_step(topo, layers=24)
    assert pallas_calls(compiled) >= 24 * 3 + 2
    assert device_bytes(compiled) < V5E_HBM


# ------------------------------------------------------------ serving programs

def _serve_decoder(layers):
    """chip_smoke.py's decoder (constructor defaults, 8 slots x 1,024
    tokens) at gpt_1p3b width, built on the CPU."""
    import paddle_tpu as paddle
    from paddle_tpu.models import GPT, gpt_1p3b
    from paddle_tpu.serving.decoder import PagedGPTDecoder

    paddle.seed(0)
    model = GPT(gpt_1p3b(max_seq_len=S, num_layers=layers))
    model.bfloat16()
    model.eval()
    return PagedGPTDecoder(model, num_pages=B * (S // 16) + 2, page_size=16,
                           max_batch=B)


@pytest.fixture(scope="module")
def two_layer_decoder():
    return _serve_decoder(2)


def compile_horizon(one_chip, d, k, t, width):
    """One packed ragged horizon of decoder `d`, as `ragged_multi` jits it
    for the smoke's chunks of 128 tokens (1 on the decode bucket t=8)."""
    import functools

    from tests._hlo_pool import compile_packed_horizon

    return compile_packed_horizon(d, k, t, width, 1 if t == B else 128,
                                  spec=functools.partial(spec, one_chip))


# (k, t_tokens, table width) of every packed ragged horizon the smoke's eight
# prompts (32-512 tokens) and its lone request dispatch at gpt_1p3b, 128
# prompt tokens per slot per tick. K follows the host sync the engine
# measures: on the chip's host it priced K=3 (horizons of 1 and 2 ticks; my
# chip run, PR 26), in this sandbox K=1.
SMOKE_HORIZONS = [
    (2, 1024, 32), (2, 512, 64), (2, 8, 64), (1, 8, 64), (1, 32, 4),
    (2, 8, 4), (2, 8, 8), (1, 8, 8),                        # K=3
    (1, 1024, 16), (1, 1024, 32), (1, 512, 32), (1, 256, 64), (1, 8, 4),
]                                                           # K=1 adds these
ON_THE_CHIP = 8         # horizons one run of the smoke keeps loaded at once


@pytest.mark.slow
@pytest.mark.parametrize("k,t,width", SMOKE_HORIZONS)
def test_serve_horizon_two_layers(one_chip, two_layer_decoder, k, t, width):
    from tests._hlo_pool import pool_moves

    compiled = compile_horizon(one_chip, two_layer_decoder, k, t, width)
    assert device_bytes(compiled) < V5E_HBM
    assert pool_moves(compiled, two_layer_decoder.k_pages) == []


@pytest.mark.slow
def test_decode_horizon_moves_no_pool(one_chip):
    """The decode horizon (2, 8, 64) as the chip's compiler lays it out:
    the pools ride the layer loop's carry, so nothing copies the pool,
    slices a layer out of it or writes a layer back (`tests/_hlo_pool.py`;
    the CPU twin of this gate is `test_serving.py::
    test_packed_horizon_moves_no_pool`), and the program's temporaries
    stay under the K pool alone: a tick's two gathers are 2/L of it, and
    with the pools as the layer scan's `xs`/`ys` both pools were held
    again (3.76 pools at these 4 layers)."""
    from tests._hlo_pool import pool_moves

    d = _serve_decoder(4)
    compiled = compile_horizon(one_chip, d, 2, 8, 64)
    assert pool_moves(compiled, d.k_pages) == []
    assert compiled.memory_analysis().temp_size_in_bytes < d.k_pages.nbytes


@pytest.mark.slow
def test_largest_serve_horizon_24_layers(one_chip):
    """The one that decides whether the smoke's serve phase fits: weights,
    pool and the temporaries of 1,024 tokens a tick, beside the other
    horizons' programs (each carries the embedding and the head as
    constants)."""
    compiled = compile_horizon(one_chip, _serve_decoder(24), 2, 1024, 32)
    others = (ON_THE_CHIP - 1) * \
        compiled.memory_analysis().generated_code_size_in_bytes
    assert device_bytes(compiled) + others < V5E_HBM


# ---------------------------------------------------------- the latent pool

# the two MLA cells' pools, [entries, slots x table width + 2, 16, 576], and
# the decode horizon each dispatches: (layers, slots, table width, K)
MLA_CELLS = {
    "deepseek_v2": ((6, 4226, 16, 576), 6, 16, 264, 2),
    "longcat_flash": ((8, 4610, 16, 576), 4, 32, 144, 1),
}


def _mla_model(family, **kw):
    """A tiny preset of one family of `mla_decoder.FAMILIES`, built on the
    CPU."""
    import paddle_tpu as paddle
    from paddle_tpu.models import deepseek_v2, lfm2_moe, longcat_flash

    made = {"deepseek_v2": (deepseek_v2.DeepSeekV2,
                            deepseek_v2.deepseek_v2_tiny),
            "longcat_flash": (longcat_flash.LongCatFlash,
                              longcat_flash.longcat_flash_tiny),
            "lfm2_moe": (lfm2_moe.Lfm2Moe, lfm2_moe.lfm2_moe_tiny)}
    model, preset = made[family]
    paddle.seed(0)
    m = model(preset(**kw))
    m.eval()
    return m


def _pool_of(cache):
    return cache[0] if isinstance(cache, tuple) else cache


@pytest.fixture
def no_mesh():
    """The decoder serves on one chip and refuses a tp mesh: the one the
    sharded train step leaves set is not these tests'."""
    from paddle_tpu.distributed import mesh as mesh_mod

    held = mesh_mod.get_mesh(create_default=False)
    mesh_mod.set_mesh(None)
    yield
    mesh_mod.set_mesh(held)


@pytest.mark.parametrize("family", sorted(MLA_CELLS))
def test_mla_decode_horizon_keeps_the_pool_layout(one_chip, as_tpu, no_mesh,
                                                  family):
    """Each MLA cell's decode horizon at its pool's real shape, the latent
    576 wide (512 + 64) and every other width tiny: the pool's entry is
    stored in whole lanes (640), so its default layout is row-major, the
    layer loop's own; it goes in and comes out so, and nothing copies it.
    A 576-wide pool defaults to pages minor-most, and the horizon converted
    the whole pool into the loop's layout and back, two copies an
    execution."""
    import functools

    from paddle_tpu.serving.mla_decoder import PagedMLADecoder
    from tests._hlo_pool import pool_moves

    shape, layers, slots, width, k = MLA_CELLS[family]
    d = PagedMLADecoder(
        _mla_model(family, kv_lora_rank=512, qk_rope_head_dim=64,
                   num_layers=layers, dtype="bfloat16",
                   max_seq_len=width * 16),
        num_pages=18, page_size=16, max_batch=slots,
        max_pages_per_seq=width)
    assert d.kv_token_bytes == 576 * 2
    stored = (shape[0], shape[1], shape[2], 640)
    assert (d.cache.shape[0], d.cache.shape[3]) == (stored[0], stored[3])
    # the cell's pool, described on the chip and not made here
    d.num_pages = shape[1]
    pool = spec(one_chip, stored, jnp.bfloat16)

    def i32(*s):
        return spec(one_chip, s, jnp.int32)

    compiled = jax.jit(
        functools.partial(d._packed_multi_step, k=k, t=slots, window=1),
        donate_argnums=(1,),
    ).lower(
        jax.tree_util.tree_map(lambda v: spec(one_chip, v.shape, v.dtype),
                               d.weights),
        pool, i32(slots), i32(slots), i32(slots, width),
        spec(one_chip, (slots,), jnp.bool_), i32(slots), i32(),
        i32(slots, d.pend_capacity), i32(slots), i32()).compile()
    assert pool_moves(compiled, pool) == []
    row_major = (0, 1, 2, 3)
    assert compiled.input_formats[0][1].layout.major_to_minor == row_major
    assert compiled.output_formats[-1].layout.major_to_minor == row_major


@pytest.mark.parametrize("family", ["deepseek_v2", "longcat_flash",
                                    "lfm2_moe"])
def test_every_mla_program_keeps_the_pool_format(no_mesh, family):
    """On the CPU, where the pool's layout is the default: the horizon, the
    packed prefill (a family without a per-slot state) and the page copy
    each take and return the pool in the format it was made in, and it
    leaves each of them so."""
    from paddle_tpu.serving.mla_decoder import PagedMLADecoder

    d = PagedMLADecoder(_mla_model(family), num_pages=18, page_size=8,
                        max_batch=2, max_pages_per_seq=8)
    made = _pool_of(d.cache).format
    assert made.layout.major_to_minor == (0, 1, 2, 3)
    S, MP = d.max_batch, d.max_pages

    def i32(*s):
        return jax.ShapeDtypeStruct(s, jnp.int32)

    def flags(n):
        return jax.ShapeDtypeStruct((n,), jnp.bool_)

    d.ragged_multi(np.zeros(S), np.zeros(S), np.zeros((S, MP)), 1, 1,
                   np.zeros((S, d.pend_capacity)), np.zeros(S))
    if not d._state_layers:
        d.prefill_suffix_batch([([1, 2, 3], 0, [0])])
    d.copy_page(1, 2)
    assert _pool_of(d.cache).format == made
    built = [(fn, (i32(S), i32(S), i32(S, width), flags(S), i32(S), i32(),
                   i32(S, d.pend_capacity), i32(S), i32()))
             for (_, _, _, width), fn in d._packeds.items()]
    built += [(fn, (i32(t), i32(t), i32(t), flags(t), i32(S, MP), i32(S),
                    flags(S), i32(S)))
              for (t, _), fn in d._packed_prefills.items()]
    assert len(built) == 1 + (not d._state_layers)
    for fn, rest in built:
        c = fn.lower(d.weights, d.cache, *rest).compile()
        assert _pool_of(c.input_formats[0][1]) == made
        assert _pool_of(c.output_formats[-1]) == made
    c = d._copy.lower(d.cache, i32(), i32()).compile()
    assert _pool_of(c.input_formats[0][0]) == made
    assert _pool_of(c.output_formats) == made
