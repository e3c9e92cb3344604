"""The decode tick's share of the weight-streaming roofline: the weight bytes
a decode tick has to read, over the device seconds a decode tick takes in the
trace x the chip's HBM bandwidth.

Seconds, from the device trace: the step program's executions are told
apart by the packed layout's loop, which each execution runs once, at its
start: a top-level `while` over (s32[], s32[T], s32[T], s32[S], s32[],
s32[]), T the execution's token bucket and S the slots. An execution of the
decode bucket (the T of the window's decode-only horizons) runs from its
loop to the next execution's; its seconds are the device's busy time in
between (the union of the operations, so an idle gap on the host's account
counts for nothing). The last execution of the trace, which has no next,
is left out.

Bytes, from the program's counts in the window's horizon records: every
non-expert weight once a tick (operators, norms, routers, dense MLP, head:
`flops.decode_tick_weight_bytes`) and the three matrices of each expert a
decode tick hits (`experts_hit`, summed over expert layers and ticks),
averaged over the window's decode-only ticks. The window's waves end in ticks
where the late requests decode alone and hit fewer experts than the ticks of
a wave's first seconds that the trace holds, and the pool and the per-slot
state are left out: the bytes are a lower bound, and so is the share.

Nothing is read (the metric is left out) where the program counts no hits
(this family's parent), where the trace shows no decode execution, or
where the decode bucket is not the decode ticks' alone (a horizon of that
bucket with a chunk row) or a decode horizon holds more than one tick."""
import bisect
import re

from benchmark.records import horizons
from benchmark.trace import busy_intervals

LAYOUT_LOOP = re.compile(
    r"= \(s32\[\], s32\[(\d+)\], s32\[\1\], s32\[\d+\], s32\[\], "
    r"/\*index=5\*/s32\[\]\) while\(")


def decode_seconds(ops, bucket):
    """[device seconds] of each execution of token bucket `bucket` that
    the next execution's loop closes, in the trace's operations `ops`
    ([(start_ns, end_ns, name, text)])."""
    ops = sorted(ops, key=lambda o: (o[0], -o[1]))
    loops, reach = [], None
    for start, end, _, text in ops:
        if reach is not None and end <= reach:
            continue                            # inside a top-level op
        reach = end
        m = LAYOUT_LOOP.search(text)
        if m:
            loops.append((start, int(m.group(1))))
    busy = busy_intervals(ops)
    starts = [s for s, _ in busy]
    out = []
    for (lo, t), (hi, _) in zip(loops, loops[1:]):
        if t != bucket:
            continue
        i = max(bisect.bisect_right(starts, lo) - 1, 0)
        took = 0
        for s, e in busy[i:]:
            if s >= hi:
                break
            took += max(0, min(e, hi) - max(s, lo))
        out.append(took / 1e9)
    return out


def read(run):
    events = horizons(run)
    decode = [ev for ev in events if ev["prefill_rows"] == 0]
    if run.traced is None or not decode \
            or not all("experts_hit" in ev for ev in decode):
        return None
    buckets = {ev["t_tokens"] for ev in decode}
    if len(buckets) != 1 or any(ev["k"] != 1 for ev in decode):
        return None
    bucket = buckets.pop()
    if any(ev["t_tokens"] == bucket and ev["prefill_rows"] for ev in events):
        return None
    seconds = decode_seconds(run.traced["all_ops"], bucket)
    if not seconds:
        return None
    flops, cfg = run.cell.family.flops, run.cell.config
    nbytes = flops.decode_tick_weight_bytes(cfg) + flops.expert_bytes(cfg) \
        * sum(ev["experts_hit"] for ev in decode) / len(decode)
    return 100.0 * nbytes * len(seconds) / sum(seconds) \
        / run.peaks["hbm_bytes_per_s"]
