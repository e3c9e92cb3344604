"""Compile a cell's programs at the real size for a described v5e, with no
chip attached, and print what the compiler says of their memory:

    JAX_PLATFORMS=cpu python -m benchmark.rehearse.compile_cell <cell> [--horizon K,T,WIDTH ...]

A training cell: the step `Trainer.step` dispatches. A serving cell: each
`--horizon` (k, token bucket, table width) that `rehearse.horizons` listed.
What the compiler refuses here costs no chip time. Nothing runs: a compile
that passes is not a chip run. Run by hand; `tests/test_chip_compile.py`
stays the only test file that describes a topology.
"""
import argparse
import functools
import json
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax                      # noqa: E402
import jax.numpy as jnp         # noqa: E402
import numpy as np              # noqa: E402
from jax.sharding import (Mesh, NamedSharding, PartitionSpec,   # noqa: E402
                          SingleDeviceSharding)

from .. import cells, generate  # noqa: E402


def report(name, compiled):
    m = compiled.memory_analysis()
    gib = 2.0 ** 30
    print(json.dumps({
        "program": name,
        "arguments_gib": m.argument_size_in_bytes / gib,
        "temporaries_gib": m.temp_size_in_bytes / gib,
        "code_gib": m.generated_code_size_in_bytes / gib,
        "total_gib": (m.argument_size_in_bytes + m.temp_size_in_bytes
                      + m.output_size_in_bytes - m.alias_size_in_bytes
                      + m.generated_code_size_in_bytes) / gib,
        "pallas_calls": compiled.as_text().count("tpu_custom_call")}),
        flush=True)


def train_step(cell, topo):
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.io.prefetch import batch_shardings

    model = cell.family.build_model(cell.config, 0, cell.job)
    trainer = cell.family.build_trainer(model, cell.config, cell.job)
    chip = Mesh(np.asarray(topo.devices[:1]).reshape(
        trainer.mesh.devices.shape), trainer.mesh.axis_names)
    trainer.mesh = chip
    mesh_mod.set_mesh(chip)
    state = jax.tree_util.tree_map(
        lambda v: jax.ShapeDtypeStruct(
            v.shape, v.dtype, sharding=NamedSharding(chip, v.sharding.spec)),
        (trainer.params, trainer.opt_state, trainer.gt_state, trainer.consts))
    batch = next(generate.of(cell.traffic)(cell.traffic, cell.config, 0))
    feed = jax.tree_util.tree_map(
        lambda v, sh: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=sh),
        batch, batch_shardings(batch, chip))
    lr = jax.ShapeDtypeStruct((), jnp.float32,
                              sharding=NamedSharding(chip, PartitionSpec()))
    args = state + (lr, feed)
    in_sh = jax.tree_util.tree_map(lambda s: s.sharding, args)
    report("train_step", trainer._build(True, in_shardings=in_sh)
           .lower(*args).compile())


def horizon(cell, topo, decoder, k, t, width):
    one = SingleDeviceSharding(topo.devices[0])
    slots = cell.job["engine"]["slots"]

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def shapes(tree):
        return jax.tree_util.tree_map(lambda v: spec(v.shape, v.dtype), tree)

    i32 = functools.partial(lambda *s: spec(s, jnp.int32))
    compiled = jax.jit(
        functools.partial(decoder._packed_multi_step, k=k, t=t),
        donate_argnums=(1, 2),
    ).lower(shapes(decoder._w()), shapes(decoder.k_pages),
            shapes(decoder.v_pages), i32(slots), i32(slots),
            i32(slots, width), i32(slots), spec((slots,), jnp.bool_),
            i32(slots), i32(), i32(slots, decoder.pend_capacity), i32(slots),
            i32()).compile()
    report(f"horizon k={k} t={t} width={width}", compiled)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--horizon", action="append", default=[])
    args = ap.parse_args()
    from jax.experimental import topologies

    cell = cells.Cell(args.cell)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # ops/ and models/ pick kernels by jax.default_backend(), which still
    # says cpu here: this script steers it, the program has no option
    jax.default_backend = lambda: "tpu"
    if cell.job["job"] == "pretrain":
        train_step(cell, topo)
    else:
        decoder = cell.family.build_decoder(cell.config, 0, cell.job)
        for h in args.horizon:
            horizon(cell, topo, decoder, *map(int, h.split(",")))


if __name__ == "__main__":
    main()
