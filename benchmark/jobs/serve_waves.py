"""A serving cell in waves: a closed loop whose callers wait for their
batch. Each wave submits the mix's groups (the first at the wave's start,
the others when their trigger is met at a host sync) and the next wave
starts when every request of this one has its answer.

Set-up plays one wave, which compiles every program the window uses (the
lengths, and so the schedule counted in ticks, are the same in every wave).
The window is made of whole waves: as many as come nearest to `--seconds`.
Deliveries are seen at `on_sync`, on the
harness's own clock. Once the window has closed, a sample of the requests
it served is held against the plain reference.

The end-to-end numbers are taken over the whole window: every token over
every second, a percentile of every request's first token and of every
later token's gap. A horizon that the machine or the program stalls is in
them (`metrics/stall_share.serve.py` says how much of a window such
horizons were). The first token's percentile is the 75th and not the
median: a wave's first tokens come in clusters (the GPT cell's 8 requests:
four at 34 ms, one at 55, the late three at 91), and the median of 8 x W
requests falls between the first two, the mean of the LARGEST of 4 x W
times and the smallest of W: one pause in any wave's first horizon moves
it by 1-10 ms of 45. The 75th lies inside the late joiners' cluster.
"""
import gc
import time

import numpy as np

from .. import generate


WARM_UP = 1 << 20     # the index of the wave that set-up plays
TRACED_S = 3.0        # of one more wave, in a traced run (see `trace_part`)


class _TracedEnough(Exception):
    pass


def trace_part(ctx, engine, traffic, cfg, seed, index):
    """A traced run's device trace: the first seconds of one more wave,
    played after the window and cut off there. A whole wave is some 1.6
    million device events, which the profiler takes a minute to hand over,
    and a run has to end within six; and the window's own wave, from which
    the host-clock metrics are read, runs with no profiler beside it."""
    with ctx.profiled():
        t0 = time.perf_counter()

        def cut(now):
            if now - t0 >= TRACED_S:
                raise _TracedEnough

        try:
            play_wave(engine, traffic, cfg, seed, index, WaveLog(), ctx.span,
                      after_sync=cut)
        except _TracedEnough:
            pass          # the engine is dropped with its wave unfinished


class WaveLog:
    """Send and delivery times of every request of the window."""

    def __init__(self):
        self.sent = {}          # key -> seconds
        self.prompts = {}       # key -> prompt ids
        self.deliveries = {}    # key -> [(seconds, tokens delivered)]
        self.outputs = {}       # key -> generated ids
        self.syncs = []         # [(seconds, wave)] of every on_sync
        self.run_starts = []    # [seconds] of each engine.run
        self.horizons = []      # per horizon: the scheduler's event


def play_wave(engine, traffic, cfg, seed, index, log, span, alter=None,
              after_sync=None):
    """One wave through `engine.submit` and `engine.run`. `after_sync(now)`
    is called at the end of every host sync."""
    groups = generate.of(traffic)(traffic, cfg, seed, index)
    specs = traffic["groups"]
    rids = {}                   # group name -> [rid]
    seen = {}

    def send(gi):
        with span("request_submitted"):
            now = time.perf_counter()
            got = []
            for prompt in groups[gi]:
                rid = engine.submit(prompt)
                got.append(rid)
                seen[rid] = 0
                log.sent[(index, rid)] = now
                log.prompts[(index, rid)] = prompt
                log.deliveries[(index, rid)] = []
            rids[specs[gi]["name"]] = got

    waiting = []
    for gi, g in enumerate(specs):
        if g["send"] == "wave_start":
            send(gi)
        else:
            waiting.append(gi)

    def on_sync(eng):
        with span("on_sync"):
            now = time.perf_counter()
            log.syncs.append((now, index))
            for rid in seen:
                n = len(eng._outputs[rid])
                if n > seen[rid]:
                    log.deliveries[(index, rid)].append((now, n - seen[rid]))
                    seen[rid] = n
            for gi in list(waiting):
                trig = specs[gi]["send"]
                group = rids.get(trig["when_group"])
                if group and all(seen[r] >= trig["has_tokens"]
                                 for r in group):
                    waiting.remove(gi)
                    send(gi)
            if after_sync is not None:
                after_sync(now)

    n_before = len(engine.serve_schedule())
    log.run_starts.append(time.perf_counter())
    with span("engine_run"):
        out = engine.run(on_sync=on_sync)
    log.horizons.extend(engine.serve_schedule()[n_before:])
    for rid in seen:
        toks = [int(t) for t in out[rid]]
        if alter is not None:
            toks = alter(toks)
        log.outputs[(index, rid)] = toks
    return sum(len(out[r]) for r in seen)


def latencies(log):
    """(time to first token of each request, gap of every later token), in
    seconds. A gap is the time since the request's previous delivery shared
    among the tokens delivered together; tokens that arrive with the first
    have no gap of their own."""
    ttft, gaps = [], []
    for key, sent in log.sent.items():
        ds = log.deliveries[key]
        if not ds:
            continue
        ttft.append(ds[0][0] - sent)
        for (t_prev, _), (t, n) in zip(ds, ds[1:]):
            gaps.extend([(t - t_prev) / n] * n)
    return ttft, gaps


def horizon_seconds(log):
    """Seconds of each horizon: from the sync before it (or the start of its
    engine.run) to its own sync, beside the scheduler's event for it."""
    out, starts = [], list(log.run_starts)
    prev, wave = None, None
    for (t, w), ev in zip(log.syncs, log.horizons):
        if w != wave:
            prev, wave = starts.pop(0), w
        out.append((t - prev, ev))
        prev = t
    return out


def check(cell, seed, log, answer):
    """The numbers `correct` compares, from a sample of the requests the
    window finished (the longest among them), against the plain reference
    run over each prompt with its served tokens."""
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
    worst, vocab_ok = 0.0, True
    for k in picked:
        served = log.outputs[k]
        vocab_ok &= all(0 <= t < cfg["vocab_size"] for t in served)
        safe = [min(max(t, 0), cfg["vocab_size"] - 1) for t in served]
        gaps = np.asarray(ref.served_gaps(cfg, params, log.prompts[k], safe,
                                          pad_to))
        worst = max(worst, float(gaps.max()))
    unfinished = sum(len(v) != answer for v in log.outputs.values())
    return {"served_logit_gap": worst,
            "tokens_outside_vocab": 0 if vocab_ok else 1,
            "requests_unfinished": unfinished,
            "checked_tokens": sum(len(log.outputs[k]) for k in picked)}


def run(ctx):
    cell, seed = ctx.cell, ctx.seed
    fam, cfg, job, traffic = cell.family, cell.config, cell.job, cell.traffic
    answer = traffic["answer_tokens"]
    if answer != job["engine"]["max_new_tokens"]:
        raise ValueError("the engine has one answer length for all requests: "
                         "traffic.answer_tokens must equal max_new_tokens")

    decoder = fam.build_decoder(cfg, seed, job)
    ctx.mark("decoder_built")
    play_wave(fam.build_engine(decoder, job), traffic, cfg, seed, WARM_UP,
              WaveLog(), ctx.span)
    # What set-up has built (some 300,000 objects: JAX, the program, the
    # traced programs) will live as long as the process: taken out of the
    # collector's sight, as a server does once it is warm. The collector
    # stays on and pays in the window for what the window allocates; one
    # full pass over set-up's heap was 77-128 ms in the middle of a window,
    # once in some windows and not in others (PERF.md section 6, PR 36).
    gc.collect()
    gc.freeze()
    ctx.setup_done()

    engine = fam.build_engine(decoder, job)
    log = WaveLog()
    tokens, index = 0, 0
    with ctx.window(profiled=False) as w:
        t0 = time.perf_counter()
        while True:
            tokens += play_wave(engine, traffic, cfg, seed, index, log,
                                ctx.span, alter=ctx.faults.get("alter"))
            index += 1
            elapsed = time.perf_counter() - t0
            # another wave only if half of it or more lies inside --seconds:
            # the window is the whole number of waves nearest to it, so a
            # host a little faster or slower plays the same number
            if elapsed + 0.5 * elapsed / index >= w.seconds:
                break
    ttft, gaps = latencies(log)
    ctx.attempted = len(log.sent)
    ctx.failed = sum(len(v) != answer for v in log.outputs.values())
    ctx.e2e["serve_tokens_per_s"] = tokens / elapsed
    ctx.e2e["ttft_ms_p75"] = 1e3 * float(np.percentile(ttft, 75))
    ctx.e2e["itl_ms_p99"] = 1e3 * float(np.percentile(gaps, 99))
    stats = engine.stats
    ctx.measured.update(
        serve_seconds=elapsed, waves=index, requests=len(log.sent),
        horizons=horizon_seconds(log), n_gaps=len(gaps),
        tokens_dispatched=stats.tokens_dispatched,
        tokens_padded=stats.tokens_padded, k_max=engine.k_max,
        serve_flops=sum(fam.flops.serve_flops(
            cfg, len(log.prompts[k]), len(log.outputs[k]))
            for k in log.outputs))
    if ctx.trace:
        trace_part(ctx, engine, traffic, cfg, seed, index)
    ctx.read_memory_peak()
    ctx.mark("window_closed")

    del engine, decoder
    gc.unfreeze()
    gc.collect()
    ctx.judge(check(cell, seed, log, answer))
    ctx.mark("compared")
