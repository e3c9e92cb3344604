"""List the horizons a wave dispatches, on the CPU, before a chip is held:

    JAX_PLATFORMS=cpu python -m benchmark.rehearse.horizons <cell> [--layers 2] [--seeds 12] [--k-max K]

Plays the cell's wave through the real engine on a model of the cell's width
and `--layers` layers (the schedule depends on lengths and on K, not on
depth) and prints every distinct (k, token bucket, table width) the decoder
was asked for, for each seed: each is one compiled program on the chip, and
all of a seed's programs are loaded at once beside the largest. K is priced
from the host sync the cell fixes and from the decoder's bytes, so pass
`--k-max` with the K the chip's run printed (`measured["k_max"]`) when the
depth here is not the cell's. Run by hand; no test collects it.
"""
import argparse
import contextlib
import json

from .. import cells
from ..jobs import serve_waves


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--k-max", type=int, default=None)
    args = ap.parse_args()
    cell = cells.Cell(args.cell)
    cfg = dict(cell.config, num_hidden_layers=args.layers)
    seen_all = set()
    for seed in range(args.seeds):
        decoder = cell.family.build_decoder(cfg, seed, cell.job)
        seen = []
        inner = decoder.ragged_multi

        def spy(tokens, lens, table, k, w, *a, **kw):
            seen.append((k, kw.get("t_tokens"), int(table.shape[1])))
            return inner(tokens, lens, table, k, w, *a, **kw)

        decoder.ragged_multi = spy
        engine = cell.family.build_engine(decoder, cell.job)
        if args.k_max:
            engine.k_max = engine.scheduler.k_max = args.k_max
        serve_waves.play_wave(engine, cell.traffic, cfg, seed, 0,
                              serve_waves.WaveLog(),
                              lambda name: contextlib.nullcontext())
        distinct = sorted(set(seen))
        seen_all |= set(distinct)
        print(json.dumps({"seed": seed, "k_max": engine.k_max,
                          "horizons": len(seen), "programs": distinct}),
              flush=True)
    print(json.dumps({"all_seeds": sorted(seen_all)}))


if __name__ == "__main__":
    main()
