"""The readings a cell's limits are set from, in one process on the chip:

    python -m benchmark.rehearse.readings <cell> --seeds 12 --controls 3 [--first-seed N]

For each seed: the program's numbers against the plain reference (the lower
readings). For the first `--controls` seeds also the control, the reference
computed one precision down and put in the program's place, and the faults
a cell of this kind can have, planted in the program's feed (the upper
readings). One JSON line each, on standard output and in
chiprun_out/readings.<cell>.jsonl. PERF.md gives the readings beside each
limit.
"""
import argparse
import contextlib
import gc
import json
import os

from .. import cells, compare, generate
from ..faults import half_batch_left_out
from ..run import check_device, place_compile_cache

CONTROL = {"bfloat16": "fp8", "float32": "bf16"}


def emit(out, **row):
    line = json.dumps(row)
    print(line, flush=True)
    out.write(line + "\n")
    out.flush()


def pretrain(cell, seeds, controls, out):
    import jax

    from ..jobs import pretrain as job
    from ..reference import train as ref_train

    fam, cfg, jobcfg, traffic = (cell.family, cell.config, cell.job,
                                 cell.traffic)

    def program(seed, batches, step):
        model = fam.build_model(cfg, seed, jobcfg)
        trainer = fam.build_trainer(model, cfg, jobcfg)
        del model
        got = job._first_steps(trainer, batches, cell, seed, step)
        trainer.params = trainer.opt_state = trainer.consts = None
        del trainer
        gc.collect()
        return job.with_change(got, cell, seed)

    for i, seed in enumerate(seeds):
        gen = generate.of(traffic)(traffic, cfg, seed)
        batches = [next(gen) for _ in range(jobcfg["checked_steps"])]
        prog = program(seed, batches, job.default_step)
        faulty = program(seed, batches, half_batch_left_out) \
            if i < controls else None
        jax.clear_caches()
        ref = ref_train.follow(fam.reference, cfg, jobcfg["optimizer"], seed,
                               batches,
                               row_block=jobcfg["reference_row_block"])
        emit(out, kind="program", seed=seed,
             numbers=compare.train_numbers(prog, ref),
             losses=prog["losses"], ref_losses=ref["losses"],
             idle=sorted(compare.idle_leaves(ref["grad_norms"])))
        if i < controls:
            emit(out, kind="fault.half_batch", seed=seed,
                 numbers=compare.train_numbers(faulty, ref))
            ctl = ref_train.follow(
                fam.reference, cfg, jobcfg["optimizer"], seed, batches,
                prec=CONTROL[cfg["dtype"]],
                row_block=jobcfg["reference_row_block"])
            emit(out, kind="control." + CONTROL[cfg["dtype"]], seed=seed,
                 numbers=compare.train_numbers(ctl, ref))
            del ctl
        del ref
        gc.collect()


def serve_waves(cell, seeds, controls, out, waves=2):
    import numpy as np

    from ..jobs import serve_waves as job

    fam, cfg, jobcfg, traffic = (cell.family, cell.config, cell.job,
                                 cell.traffic)
    answer = traffic["answer_tokens"]
    # a decoder's programs are its own, so every weight seed builds them
    # all again: a few weight seeds, several traffic seeds each
    per_weights = max(1, len(seeds) // max(controls, 1))
    for w in range(0, len(seeds), per_weights):
        wseed = seeds[w]
        decoder = fam.build_decoder(cfg, wseed, jobcfg)
        logs = []
        for seed in seeds[w:w + per_weights]:
            log = job.WaveLog()
            engine = fam.build_engine(decoder, jobcfg)
            for i in range(waves):
                # traffic from `seed`, weights from `wseed`
                job.play_wave(engine, traffic, cfg, seed, i, log,
                              lambda name: contextlib.nullcontext())
            logs.append((seed, log))
            emit(out, kind="schedule", seed=seed, k_max=engine.k_max,
                 horizons=len(log.horizons), programs=sorted(
                     {(e["k"], e["t_tokens"]) for e in log.horizons}))
        del engine, decoder
        gc.collect()
        params = fam.reference.init_params(cfg, wseed)
        pad_to = max(max(g["prompt_lengths"]) for g in traffic["groups"]) \
            + answer
        for seed, log in logs:
            gaps = {k: np.asarray(fam.reference.served_gaps(
                cfg, params, log.prompts[k], log.outputs[k], pad_to))
                for k in sorted(log.outputs)}
            emit(out, kind="program", seed=seed, weights=wseed,
                 numbers={"served_logit_gap":
                          float(max(g.max() for g in gaps.values()))},
                 per_request=[float(g.max()) for g in gaps.values()],
                 requests=len(gaps))
        ctl = CONTROL[cfg["dtype"]]
        for seed, log in logs:
            # the control needs no program: the reference one precision
            # down, over the same prompts and tokens
            gaps = [float(np.asarray(fam.reference.served_gaps(
                cfg, params, log.prompts[k], log.outputs[k], pad_to,
                control=ctl)).max()) for k in sorted(log.outputs)]
            emit(out, kind="control." + ctl, seed=seed, weights=wseed,
                 numbers={"served_logit_gap": max(gaps)}, per_request=gaps)
        del params
        gc.collect()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=3000000019)
    args = ap.parse_args()
    cell = cells.Cell(args.cell)
    place_compile_cache()
    check_device(cell.chips)
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", f"readings.{args.cell}.jsonl"),
              "a") as out:
        {"pretrain": pretrain, "serve_waves": serve_waves}[
            cell.job["job"]](cell, seeds, args.controls, out)


if __name__ == "__main__":
    main()
