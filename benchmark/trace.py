"""Reduction of a profiler trace (`.xplane.pb`, read with
`jax.profiler.ProfileData`) to what the per-layer metrics read: the seconds
in which an operation ran on the device (the union of the op intervals),
kernel seconds by name, the operations that took most time, and the longest
idle gaps named by the harness's own spans on the host.
"""
import glob
import os
import re

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
TOP = 10


def find_xplane(logdir):
    found = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return found[-1]


_LAYOUT = re.compile(r"\{[^{}]*\}")
_SERIAL = re.compile(r"\.\d+$")


def short_name(text):
    """'convolution_add_fusion bf16[8,1024,8192]' from the HLO instruction
    the profiler gives as an operation's name: the instruction's own name
    without its serial number, and its result's type. Operations of the
    same kind in different layers then add up."""
    text = _LAYOUT.sub("", text)
    if " = " not in text:
        return _SERIAL.sub("", text.lstrip("%"))
    name, rest = text.split(" = ", 1)
    result = rest.split(") ", 1)[0] + ")" if rest.startswith("(") \
        else rest.split(" ", 1)[0]
    return f"{_SERIAL.sub('', name.lstrip('%'))} {result}"


def load(path, span_names=()):
    """{"devices": {plane: [(start_ns, end_ns, name, text)]},
        "spans": [(start_ns, end_ns, name)]} from one trace file. On a TPU
    an operation's name is its whole HLO instruction: `text` is that with
    the layouts taken out (a kernel is told by its operands' and results'
    types there: a Pallas call carries no name of its own), `name` the
    short form of `short_name`."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, spans = {}, []
    wanted = set(span_names)
    names = {}      # an instruction's text -> (short name, text): few differ
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    raw = ev.name
                    if raw not in names:
                        names[raw] = (short_name(raw), _LAYOUT.sub("", raw))
                    ops.append((ev.start_ns, ev.start_ns + ev.duration_ns)
                               + names[raw])
            devices[plane.name] = sorted(ops)
        elif wanted:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in wanted:
                        spans.append((ev.start_ns,
                                      ev.start_ns + ev.duration_ns, ev.name))
    return {"devices": devices, "spans": sorted(spans)}


def busy_intervals(ops):
    """Union of [start, end) intervals, merged: [(start, end)] sorted."""
    merged = []
    for start, end, *_ in sorted(ops):
        if end <= start:
            continue
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1] = (merged[-1][0], end)
        else:
            merged.append((start, end))
    return merged


def busy_seconds(ops):
    return sum(e - s for s, e in busy_intervals(ops)) / 1e9


def kernel_seconds(ops, patterns):
    """{kernel: (seconds, calls)} of the events whose text matches the
    kernel's regular expression (`patterns`: {kernel: regex}); an event
    counts for the first kernel it matches."""
    compiled = {k: re.compile(p) for k, p in patterns.items()}
    out = {k: [0.0, 0] for k in patterns}
    for start, end, _, text in ops:
        for k, rx in compiled.items():
            if rx.search(text):
                out[k][0] += (end - start) / 1e9
                out[k][1] += 1
                break
    return {k: tuple(v) for k, v in out.items()}


def leaf_ops(ops):
    """The operations that hold no other: a `while` or a conditional spans
    the operations of its body, which the trace lists too, and would count
    their time twice."""
    leaves, open_ = [], []          # open_: [[op, has a child]]
    for op in sorted(ops, key=lambda o: (o[0], -o[1])):
        while open_ and open_[-1][0][1] <= op[0]:
            done, parent = open_.pop()
            if not parent:
                leaves.append(done)
        if open_ and op[0] < op[1] <= open_[-1][0][1]:
            open_[-1][1] = True
        open_.append([op, False])
    leaves.extend(op for op, parent in open_ if not parent)
    return leaves


def top_ops(ops, n=TOP):
    total = {}
    for start, end, name, _ in leaf_ops(ops):
        total[name] = total.get(name, 0.0) + (end - start) / 1e9
    return [[k, v] for k, v in sorted(total.items(),
                                      key=lambda kv: -kv[1])[:n]]


def idle_gaps(ops, spans, window=None, n=TOP):
    """The idle time between device operations, by the host span that
    covers the middle of each gap ("(no span)" where none does):
    [[name, seconds]] with most idle time first. `window`: (start_ns,
    end_ns) to count the edges too."""
    busy = busy_intervals(ops)
    gaps = [(a[1], b[0]) for a, b in zip(busy, busy[1:])]
    if window and busy:
        gaps += [(window[0], busy[0][0]), (busy[-1][1], window[1])]
    total = {}
    for s, e in gaps:
        if e <= s:
            continue
        mid = (s + e) / 2
        # the innermost (shortest) span that covers the middle
        cover = [sp for sp in spans if sp[0] <= mid < sp[1]]
        name = min(cover, key=lambda sp: sp[1] - sp[0])[2] if cover \
            else "(no span)"
        total[name] = total.get(name, 0.0) + (e - s) / 1e9
    return [[k, v] for k, v in sorted(total.items(),
                                      key=lambda kv: -kv[1])[:n]]


def reduce(path, span_names, window_s, chips):
    """What the harness keeps of a trace."""
    tr = load(path, span_names=span_names)
    devs = sorted(tr["devices"])[:chips] if chips else sorted(tr["devices"])
    if not devs:
        return None
    all_ops = [op for d in devs for op in tr["devices"][d]]
    first = tr["devices"][devs[0]]
    return {
        "busy_s": sum(busy_seconds(tr["devices"][d]) for d in devs)
        / len(devs),
        "window_s": window_s,
        "all_ops": all_ops,
        "device_ops": top_ops(first),
        "idle_gaps": idle_gaps(first, tr["spans"]),
        "n_events": len(all_ops),
    }
