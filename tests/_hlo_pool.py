"""What a compiled serving program does to its page pool, read off the
optimised HLO: shared by the CPU gate in `test_serving.py` and the
described-v5e gate in `test_chip_compile.py`."""
import functools
import re

import jax
import jax.numpy as jnp

# `%name = dtype[dims]{layout} opcode(`, in any computation of the module
_INSTR = re.compile(
    r"^\s*(?:ROOT )?%?[\w.\-]+ = (\w+)\[([\d,]*)\]\S* ([\w\-]+)\(")
_MOVES = ("copy", "dynamic-slice", "dynamic-update-slice")
_HLO_DTYPE = {"bfloat16": "bf16", "float32": "f32", "int8": "s8",
              "uint8": "u8"}


def pool_moves(compiled, pool):
    """The instructions of `compiled` that MOVE `pool` ([L, P, ps, ...]):
    a `copy`, `dynamic-slice` or `dynamic-update-slice` whose result has
    the pool's dtype and as many elements as the whole pool or as one
    layer of it (by count, since the chip's compiler also works on the
    pool flattened to rows). Fused computations are read too, so a fusion
    whose root is such an instruction (`dynamic-slice_bitcast_fusion`,
    `bitcast_dynamic-update-slice_fusion`) is found by that root. A
    scatter on the whole pool is the write itself and is no move (and has
    to be there: a text without it is not this program's). Returns the
    matching lines, stripped."""
    dtype = _HLO_DTYPE[str(pool.dtype)]
    sizes = (pool.size, pool.size // pool.shape[0])
    hits, written = [], False
    for line in compiled.as_text().splitlines():
        m = _INSTR.match(line)
        if not m or m.group(1) != dtype:
            continue
        numel = 1
        for d in m.group(2).split(","):
            numel *= int(d or 1)
        written |= m.group(3) == "scatter" and numel == pool.size
        if m.group(3) in _MOVES and numel in sizes:
            hits.append(line.strip()[:160])
    assert hits or written, "no scatter on the whole pool in this text"
    return hits


def compile_packed_horizon(d, k, t, width, w, spec=jax.ShapeDtypeStruct):
    """One packed ragged horizon of decoder `d` (k ticks, token bucket t,
    a table of `width` columns, chunks of `w` tokens a row), compiled as
    `ragged_multi` jits it: `_packed_multi_step` with the pools donated.
    `spec(shape, dtype)` describes an argument (a described chip's test
    gives one that carries its sharding)."""
    from paddle_tpu.serving.decoder import packed_window

    def shapes(tree):
        return jax.tree_util.tree_map(lambda v: spec(v.shape, v.dtype), tree)

    def i32(*shape):
        return spec(shape, jnp.int32)

    S = d.max_batch
    return jax.jit(
        functools.partial(d._packed_multi_step, k=k, t=t,
                          window=packed_window(w, t)),
        donate_argnums=(1, 2),
    ).lower(shapes(d._w()), shapes(d.k_pages), shapes(d.v_pages),
            i32(S), i32(S), i32(S, width), i32(S), spec((S,), jnp.bool_),
            i32(S), i32(), i32(S, d.pend_capacity), i32(S), i32()).compile()
