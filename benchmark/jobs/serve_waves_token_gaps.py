"""`serve_waves`, judged on the gaps of every checked token as well as on
the widest.

`serve_waves.check` holds a cell to `served_logit_gap`, the widest gap of
the checked requests' served tokens under the plain reference's logits.
Over some 1,500 served tokens a few of a bfloat16 program's misses can
lie as far under the reference's best as the widest of the precision below
it: the widest gap then cannot part the two (PERF.md section 6). Over all
checked tokens they part: this job's check adds `served_logit_gap_mean`,
the mean gap over every checked served token, and everything else (the
waves, the window, the metrics, the trace) is `serve_waves`' own, run by
`serve_waves.run` itself. A cell whose sound runs' widest gap reaches over
the control's gives that number no limit (null): it is shown, and the mean
is what is held.
"""
import contextlib

import numpy as np

from . import serve_waves as base


def check(cell, seed, log, answer):
    """`serve_waves.check`'s numbers, with the mean gap over the same
    requests' served tokens."""
    fam, cfg, job = cell.family, cell.config, cell.job
    ref = fam.reference
    keys = sorted(log.outputs)
    rng = np.random.default_rng([seed % (2 ** 63), 0xC4EC])
    longest = max(keys, key=lambda k: (len(log.prompts[k]), -k[0], -k[1]))
    others = [k for k in keys if k != longest]
    picked = [longest] + [others[i] for i in rng.permutation(len(others))[
        :max(0, job["checked_requests"] - 1)]]
    params = ref.init_params(cfg, seed)
    pad_to = max(len(log.prompts[k]) for k in keys) + answer
    gaps, vocab_ok = [], True
    for k in picked:
        served = log.outputs[k]
        vocab_ok &= all(0 <= t < cfg["vocab_size"] for t in served)
        safe = [min(max(t, 0), cfg["vocab_size"] - 1) for t in served]
        gaps.append(np.asarray(ref.served_gaps(
            cfg, params, log.prompts[k], safe, pad_to), np.float64))
    every = np.concatenate(gaps)
    unfinished = sum(len(v) != answer for v in log.outputs.values())
    return {"served_logit_gap": float(every.max()),
            "served_logit_gap_mean": float(every.mean()),
            "tokens_outside_vocab": 0 if vocab_ok else 1,
            "requests_unfinished": unfinished,
            "checked_tokens": int(every.size)}


@contextlib.contextmanager
def _judged_here():
    """`serve_waves.run` calls its module's `check`: this one for the
    run's length."""
    kept = base.check
    base.check = check
    try:
        yield
    finally:
        base.check = kept


def run(ctx):
    with _judged_here():
        base.run(ctx)
