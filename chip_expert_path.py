#!/usr/bin/env python3
"""The held experts' path alone, on the chip, at the two MLA cells' widths.

    python chip_expert_path.py --seed 0                 # both cells' shapes
    python chip_expert_path.py --seed 0 --tiles         # and a sweep of tiles
    cd <another checkout> && python <here>/chip_expert_path.py --seed 0

The serving cells' one number cannot see the routed experts a chip holds
(PERF.md section 6), so a change to `models.deepseek_v2.held_expert_walk`
is held here: ONE layer's held experts in bfloat16, weights, tokens and
selections from `--seed`, through the walk OF THE CHECKOUT THE COMMAND IS
RUN FROM (the current directory goes first on the path, so the same file
measures a parent commit unpacked elsewhere), against `dense_loop` below:
a plain loop over the held experts in `jax.numpy` with float32 accumulation
that shares nothing with the walk. One JSON line a case:

  * `sound`: the widest absolute difference of the routed sum, the widest
    of a row relative to that row's largest value, and the rows that differ
    by more than one bfloat16 step of their largest value;
  * `down_zeroed` (one held expert's `down` gives nothing) and
    `pair_to_neighbour` (one pair computed by the next expert): the two
    faults a cell's `served_logit_gap` lets through; both must read far
    outside `sound`;
  * `ms`: the walk alone, mean of `--calls` calls that cycle through the
    stacked layers; with `--trace`, the device operations of those calls
    by seconds (no `dynamic-slice` of a stack may be among them).

Observations of one run, not a benchmark. `--tiny` rehearses the same code
under `JAX_PLATFORMS=cpu` at widths of 128.
"""
import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.getcwd())

# hidden, expert width, experts held, expert layers stacked, columns a token
# selects of the router's width, rows of a decode tick and of two chunk
# buckets (the cells' configurations under benchmark/configs)
SHAPES = {
    "longcat_flash_ep32": dict(h=6144, f=2048, held=16, layers=4, k=12,
                               width=768, rows=(32, 256, 4096)),
    "deepseek_v2_ep8": dict(h=5120, f=1536, held=20, layers=5, k=6,
                            width=160, rows=(16, 256, 2048)),
}
TINY = dict(h=128, f=128, held=4, layers=2, k=3, width=16, rows=(8, 64))
STEP = 2.0 ** -8        # one step of bfloat16 at a value's own size


def emit(out, **row):
    line = json.dumps(row)
    print(line, flush=True)
    out.write(line + "\n")
    out.flush()


def draw_weights(sh, seed):
    import jax
    import jax.numpy as jnp

    def stack(key, shape):
        return (jax.random.normal(key, shape, jnp.float32) * 0.02) \
            .astype(jnp.bfloat16)

    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    L, E, h, f = sh["layers"], sh["held"], sh["h"], sh["f"]
    make = jax.jit(stack, static_argnums=1)
    return {"gate": make(keys[0], (L, E, h, f)),
            "up": make(keys[1], (L, E, h, f)),
            "down": make(keys[2], (L, E, f, h))}


def draw_tokens(sh, rows, seed):
    """Normed tokens, and for each `k` distinct columns of the router's
    width drawn evenly, so that held / width of the pairs land on the
    experts held here (columns 0 .. held), each weighing 0.05-0.4."""
    import jax
    import jax.numpy as jnp
    kx, ke, kw = jax.random.split(jax.random.PRNGKey(seed + rows), 3)
    x = jax.random.normal(kx, (rows, sh["h"]), jnp.float32) \
        .astype(jnp.bfloat16)
    ei = jnp.argsort(jax.random.uniform(ke, (rows, sh["width"])),
                     axis=-1)[:, :sh["k"]].astype(jnp.int32)
    cw = jax.random.uniform(kw, (rows, sh["k"]), jnp.float32, 0.05, 0.4)
    return x, cw, ei


def dense_loop(w, x, cw, ei, held, layer):
    """The routed sum of the held experts, one expert after another over
    ALL tokens with the combine weight of the tokens that did not select
    it at 0: float32 accumulation, the activation rounded to the tokens'
    type before `down`, as the model states it."""
    import jax
    import jax.numpy as jnp
    out = jnp.zeros(x.shape, jnp.float32)
    for e in range(held):
        weight = jnp.sum(jnp.where(ei == e, cw, 0.0), -1, keepdims=True)
        g = jnp.dot(x, w["gate"][layer, e],
                    preferred_element_type=jnp.float32)
        u = jnp.dot(x, w["up"][layer, e], preferred_element_type=jnp.float32)
        a = (jax.nn.silu(g) * u * weight).astype(x.dtype)
        out = out + jnp.dot(a, w["down"][layer, e],
                            preferred_element_type=jnp.float32)
    return out


def differences(got, want):
    import numpy as np
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    off = np.abs(got - want).max(-1)
    size = np.abs(want).max(-1)
    routed = size > 0
    return {"abs": float(off.max()),
            "rel": float((off[routed] / size[routed]).max())
            if routed.any() else 0.0,
            "rows_over_a_step": int((off > STEP * size).sum()),
            "rows_routed": int(routed.sum()),
            "widest_value": float(size.max())}


def top_ops(logdir, n=12):
    from benchmark import trace
    loaded = trace.load(trace.find_xplane(logdir))
    ops = [op for plane in loaded["devices"].values() for op in plane]
    return {"busy_s": trace.busy_seconds(ops), "ops": trace.top_ops(ops, n)}


def measure(out, cell, sh, seed, calls, traced, tiles):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models import deepseek_v2 as ds

    held, last = sh["held"], sh["layers"] - 1
    w = draw_weights(sh, seed)
    reference = jax.jit(lambda w, x, cw, ei: dense_loop(w, x, cw, ei, held,
                                                        last))

    def walk_of():
        return jax.jit(lambda w, x, cw, ei, layer: ds.held_expert_walk(
            w, x, cw, ei, held, 0, None, layer))

    def ms(walk, args):
        jax.block_until_ready(walk(w, *args, jnp.int32(0)))
        t = time.perf_counter()
        for i in range(calls):
            r = walk(w, *args, jnp.int32(i % sh["layers"]))
        jax.block_until_ready(r)
        return (time.perf_counter() - t) / calls * 1e3

    walk = walk_of()
    for rows in sh["rows"]:
        x, cw, ei = args = draw_tokens(sh, rows, seed)
        want = reference(w, *args)
        got, counts = walk(w, *args, jnp.int32(last))
        row = dict(kind="sound", cell=cell, rows=rows, seed=seed,
                   pairs_held=int(counts.sum()),
                   experts_hit=int((counts > 0).sum()),
                   **differences(got, want), ms=ms(walk, args))
        if traced:
            logdir = tempfile.mkdtemp(prefix="expert_path_")
            with jax.profiler.trace(logdir):
                row["ms_traced"] = ms(walk, args)
            row["trace"] = top_ops(logdir)
        emit(out, **row)

        # one held expert's `down` gives nothing (the busiest, so that
        # some row selected it)
        e = int(jnp.argmax(counts))
        zeroed = dict(w, down=jax.jit(
            lambda d: d.at[last, e].set(0))(w["down"]))
        got, _ = walk(zeroed, *args, jnp.int32(last))
        del zeroed
        emit(out, kind="down_zeroed", cell=cell, rows=rows, seed=seed,
             expert=e, rows_selected=int((ei == e).any(-1).sum()),
             **differences(got, want))
        # one pair is computed by its neighbour's expert
        t, j = (int(v[0]) for v in jnp.nonzero(ei == e))
        got, _ = walk(w, x, cw, ei.at[t, j].set((e + 1) % held),
                      jnp.int32(last))
        emit(out, kind="pair_to_neighbour", cell=cell, rows=rows, seed=seed,
             token=t, **differences(got, want))

        for tile in tiles if hasattr(ds, "_expert_tiles") else ():
            chosen = ds._expert_tiles
            ds._expert_tiles = lambda *a, tile=tile: tile(*chosen(*a))
            try:
                swept = walk_of()
                got, _ = swept(w, *args, jnp.int32(last))
                emit(out, kind="tiles", cell=cell, rows=rows,
                     tiles=tile(*chosen(rows * sh["k"], sh["h"], sh["f"],
                                        x.dtype)),
                     abs=differences(got, want)["abs"], ms=ms(swept, args))
            except Exception as err:    # a tile the chip's compiler refuses
                emit(out, kind="tiles", cell=cell, rows=rows,
                     error=repr(err)[:300])
            finally:
                ds._expert_tiles = chosen


def tile_sweep():
    """Other tiles than the walk chooses, as functions of its choice (row
    tile, (k, n) of gate and up, (k, n) of down)."""
    def rows_of(tm):
        return lambda m, up, down: (tm, up, down)

    def halved(m, up, down):            # weight tiles of half the columns
        return m, (up[0], up[1] // 2), (down[0], down[1] // 2)

    def whole_columns(m, up, down):     # whole rows of a matrix, some of k
        return m, (512, down[0]), (256, up[0])

    return [rows_of(16), rows_of(32), rows_of(128), rows_of(256), halved,
            whole_columns]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--cell", choices=sorted(SHAPES), action="append")
    ap.add_argument("--trace", action="store_true")
    ap.add_argument("--tiles", action="store_true")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--tag", default="change")
    args = ap.parse_args(argv)

    import jax
    platform = jax.devices()[0].platform
    if platform != "tpu" and not args.tiny:
        sys.exit(f"no TPU here ({platform}): times and differences of this "
                 "script are the chip's; --tiny rehearses it on the CPU")
    os.makedirs("chiprun_out", exist_ok=True)
    path = os.path.join("chiprun_out", f"expert_path.{args.tag}.jsonl")
    with open(path, "a") as out:
        emit(out, kind="device", platform=platform,
             device_kind=jax.devices()[0].device_kind, tree=os.getcwd())
        for cell in args.cell or sorted(SHAPES):
            sh = dict(SHAPES[cell], **TINY) if args.tiny else SHAPES[cell]
            measure(out, cell, sh, args.seed, args.calls, args.trace,
                    tile_sweep() if args.tiles else ())


if __name__ == "__main__":
    main()
