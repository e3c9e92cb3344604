"""Of the seconds the window's horizons took (sync to sync on the harness's
clock), the share that lies over what the same work takes at its median:
horizons are grouped by the work they did (program, ticks, rows by kind,
live pages, tokens emitted: in a window of waves, a horizon's place in the
wave), and each group of three or more gives its seconds over its size
times its median. The end-to-end numbers are taken over the whole window,
so a horizon that took 0.1 s longer than its twins is in them; this says how
much of the window such horizons were: about 0 in a window with none (the
mean of a group can lie a hair under its median: no clamp), 0.55 for each
0.11 s pause in a window of 20 s. A pause of the whole machine and a
program that stalls now and then both show here and in the rate. Nothing
where no work was done three times: a traced window of 6 s is ONE wave of
the DeepSeek cell, which is why that cell is not listed."""
import statistics

SAME_WORK = ("program", "k", "decode_rows", "prefill_rows", "pages_live",
             "tokens")


def read(run):
    groups = {}
    for seconds, ev in run.measured.get("horizons") or []:
        if "program" not in ev:
            return None
        groups.setdefault(tuple(ev.get(k) for k in SAME_WORK),
                          []).append(seconds)
    groups = [g for g in groups.values() if len(g) >= 3]
    total = sum(map(sum, groups))
    if not total:
        return None
    return 100.0 * sum(sum(g) - len(g) * statistics.median(g)
                       for g in groups) / total
