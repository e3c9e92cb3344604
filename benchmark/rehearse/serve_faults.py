"""The readings a serving cell's limits are set from, with the faults its
family plants, in one process on the chip:

    python -m benchmark.rehearse.serve_faults <cell> --weights 3 --traffic 4 --checked 6 [--faults a,b] [--first-seed N] [--trace-ops]

For each of `--weights` weight seeds and `--traffic` traffic seeds of one
wave each: the program's numbers against the plain reference over
`--checked` of the wave's requests (the longest and a draw of the others, as
the cell's own check picks them), and the control (the reference one
precision down, in the program's place) over the same requests; for a
bfloat16 configuration also the reference in the program's own precision
("reference.bf16": what rounding alone gives). Then each fault of the
family's `FAULTS` (or those `--faults` names), played on the first weight
seed and its first traffic seed: a fault is a context manager around
building and playing a decoder, and what it yields alters the decoder once
built. One JSON line a reading, on standard output and appended to
chiprun_out/readings.<cell>.jsonl, in `rehearse/readings.py`'s rows
("schedule", "program", "control.<precision>") beside "fault.<name>"; each
judged token's gap and rank under the reference's logits go to
chiprun_out/tokens.<cell>.jsonl. `--trace-ops` profiles the first seconds of
one more wave of the first weight seed, as a traced run does, and writes
its device operations and the harness's spans to
chiprun_out/trace_ops.<cell>.json.gz, with the horizons of that wave. Run by
hand; no test collects it.
"""
import argparse
import contextlib
import gc
import gzip
import json
import os
import shutil
import tempfile
import time

import numpy as np

from .. import cells
from .. import trace as trace_mod
from ..jobs import serve_waves as job
from ..run import SPANS, check_device, place_compile_cache
from .readings import CONTROL, emit

# the program's own precision, where the reference can be rounded to it
OWN = {"bfloat16": "bf16"}


def _play(cell, decoder, seed, out, after_sync=None, span=None):
    log = job.WaveLog()
    engine = cell.family.build_engine(decoder, cell.job)
    job.play_wave(engine, cell.traffic, cell.config, seed, 0, log,
                  span or (lambda name: contextlib.nullcontext()),
                  after_sync=after_sync)
    emit(out, kind="schedule", seed=seed, k_max=engine.k_max,
         horizons=len(log.horizons), programs=sorted(
             {(e["k"], e["t_tokens"]) for e in log.horizons}))
    return log


def _picked(log, seed, n):
    keys = sorted(log.outputs)
    longest = max(keys, key=lambda k: (len(log.prompts[k]), -k[1]))
    rest = [k for k in keys if k != longest]
    rng = np.random.default_rng([seed % (2 ** 63), 0xFA17])
    return [longest] + [rest[i] for i in rng.permutation(len(rest))[:n - 1]]


def _pad_to(cell):
    return max(max(g["prompt_lengths"]) for g in cell.traffic["groups"]) \
        + cell.traffic["answer_tokens"]


def _judged(cell, params, prompt, served, precs):
    """{kind: (gaps, ranks)} of one request: under the float32 reference's
    logits at each served position, how far the judged token lies below
    the best and how many tokens lie above it. The program's judged token
    is the served one; a precision's ("control.fp8") the one it puts
    first over the same prompt and tokens."""
    import jax.numpy as jnp

    ref, cfg = cell.family.reference, cell.config
    n, g = len(prompt), len(served)
    ids = list(prompt) + list(served)
    ids = ids + [0] * (_pad_to(cell) - len(ids))
    logits = ref.served_rows_logits(cfg, params, ids, n - 1, g)
    best = jnp.max(logits, axis=-1)
    out = {}
    for kind, prec in precs:
        judged = jnp.asarray(served, jnp.int32) if prec is None else \
            jnp.argmax(ref.served_rows_logits(cfg, params, ids, n - 1, g,
                                              prec=prec), axis=-1)
        at = jnp.take_along_axis(logits, judged[:, None], axis=-1)
        out[kind] = (np.asarray(best - at[:, 0], np.float64),
                     np.asarray(jnp.sum(logits > at, axis=-1), np.int64))
    return out


def _emit_judged(out, tokens, kind, seed, wseed, per_request):
    """One reading row (the numbers `jobs/serve_waves_token_gaps.py` judges
    and each request's widest gap) and the tokens' rows."""
    gaps = [float(g.max()) for _, g, _ in per_request]
    every = np.concatenate([g for _, g, _ in per_request])
    emit(out, kind=kind, seed=seed, weights=wseed,
         numbers={"served_logit_gap": max(gaps),
                  "served_logit_gap_mean": float(every.mean())},
         per_request=gaps)
    for key, g, r in per_request:
        tokens.write(json.dumps({
            "kind": kind, "seed": seed, "weights": wseed,
            "request": list(key), "gap": [round(float(v), 5) for v in g],
            "rank": [int(v) for v in r]}) + "\n")
    tokens.flush()


def _trace_ops(cell, decoder, seed, out, path):
    """The device operations of one more wave's first seconds, as the
    harness's traced run keeps them (`trace.reduce`'s `all_ops`), the
    spans, and that wave's horizons."""
    import jax

    logdir = tempfile.mkdtemp(prefix="trace_ops_")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    log = job.WaveLog()
    engine = cell.family.build_engine(decoder, cell.job)
    jax.profiler.start_trace(logdir, profiler_options=options)
    t0 = time.perf_counter()

    class Enough(Exception):
        pass

    def cut(now):
        if now - t0 >= job.TRACED_S:
            raise Enough

    try:
        job.play_wave(engine, cell.traffic, cell.config, seed, 0, log,
                      jax.profiler.TraceAnnotation, after_sync=cut)
    except Enough:
        pass
    jax.profiler.stop_trace()
    tr = trace_mod.load(trace_mod.find_xplane(logdir), span_names=SPANS)
    shutil.rmtree(logdir, ignore_errors=True)
    texts, ops = {}, []
    devs = sorted(tr["devices"])
    for start, end, _, text in tr["devices"][devs[0]] if devs else ():
        ops.append((start, end, texts.setdefault(text, len(texts))))
    horizons = [{k: v for k, v in ev.items()
                 if isinstance(v, (int, float, str))}
                for ev in engine.serve_schedule()]
    with gzip.open(path, "wt") as f:
        json.dump({"texts": list(texts), "ops": ops, "spans": tr["spans"],
                   "horizons": horizons}, f)
    emit(out, kind="trace_ops", seed=seed, ops=len(ops), texts=len(texts),
         horizons=len(horizons))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--weights", type=int, default=3)
    ap.add_argument("--traffic", type=int, default=2)
    ap.add_argument("--checked", type=int, default=12)
    ap.add_argument("--faults", default=None)
    ap.add_argument("--first-seed", type=int, default=3000000019)
    ap.add_argument("--trace-ops", action="store_true")
    args = ap.parse_args()
    cell = cells.Cell(args.cell)
    fam, cfg = cell.family, cell.config
    place_compile_cache()
    check_device(cell.chips)
    faults = getattr(fam, "FAULTS", {})
    if args.faults is not None:
        faults = {k: faults[k] for k in args.faults.split(",") if k}
    ctl = CONTROL[cfg["dtype"]]
    precs = [("program", None), ("control." + ctl, ctl)]
    if cfg["dtype"] in OWN:
        precs.append(("reference." + OWN[cfg["dtype"]], OWN[cfg["dtype"]]))
    seeds = [args.first_seed + 7919 * i
             for i in range(args.weights * args.traffic)]
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", f"readings.{args.cell}.jsonl"),
              "a") as out, \
            open(os.path.join("chiprun_out", f"tokens.{args.cell}.jsonl"),
                 "a") as tokens:
        for w in range(args.weights):
            wseed = seeds[w * args.traffic]
            decoder = fam.build_decoder(cfg, wseed, cell.job)
            logs = [(s, _play(cell, decoder, s, out))
                    for s in seeds[w * args.traffic:(w + 1) * args.traffic]]
            if w == 0 and args.trace_ops:
                _trace_ops(cell, decoder, wseed, out, os.path.join(
                    "chiprun_out", f"trace_ops.{args.cell}.json.gz"))
            del decoder
            gc.collect()
            _read(cell, wseed, logs, args.checked, ctl, precs, out, tokens)
            if w:
                continue
            # each fault after the sound readings, so that a run cut short
            # keeps what it read
            for name, fault in faults.items():
                with fault() as alter:
                    decoder = fam.build_decoder(cfg, wseed, cell.job)
                    alter(decoder)
                    log = _play(cell, decoder, wseed, out)
                del decoder
                gc.collect()
                params = fam.reference.init_params(cfg, wseed)
                per_request = [
                    (k,) + _judged(cell, params, log.prompts[k],
                                   log.outputs[k], precs[:1])["program"]
                    for k in _picked(log, wseed, args.checked)]
                _emit_judged(out, tokens, "fault." + name, wseed, wseed,
                             per_request)
                del params
                gc.collect()


def _read(cell, wseed, logs, checked, ctl, precs, out, tokens):
    """The program's and the controls' readings of the sound waves, and
    how much of the routing the selection bias decides and rounding to
    each precision moves."""
    fam, cfg = cell.family, cell.config
    params = fam.reference.init_params(cfg, wseed)
    ref = fam.reference
    if hasattr(ref, "bias_moves_selection"):
        seed, log = logs[0]
        ids = list(log.prompts[_picked(log, seed, 1)[0]])
        emit(out, kind="selection", seed=seed, weights=wseed,
             bias_moves=ref.bias_moves_selection(cfg, params, ids),
             **{prec + "_moves": ref.selection_differs(cfg, params, ids, prec)
                for _, prec in precs[1:]})
    for seed, log in logs:
        judged = {k: _judged(cell, params, log.prompts[k], log.outputs[k],
                             precs) for k in _picked(log, seed, checked)}
        for kind, _ in precs:
            _emit_judged(out, tokens, kind, seed, wseed,
                         [(k,) + j[kind] for k, j in judged.items()])
    del params
    gc.collect()


if __name__ == "__main__":
    main()
