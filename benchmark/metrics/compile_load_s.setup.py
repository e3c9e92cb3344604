"""Seconds of set-up in which some program was being compiled by XLA or read
from the persistent compilation cache: the union of the "compile" and "load"
entries of the program's compile log (paddle_tpu.profiler.compile_log). The
four set-up readers share the two functions below, each its own copy."""
MAKING = ("compile", "load")


def setup_entries(run):
    """(phase, start, end) of the compile log's entries clipped to set-up
    (run.t0 to run.t0 + run.setup_s); None where the program keeps no log
    or the log let go of entries that set-up may have held."""
    try:
        from paddle_tpu.profiler import compile_log
    except ImportError:                      # a program that keeps no log
        return None
    lo, hi = run.t0, run.t0 + run.setup_s
    entries, dropped = compile_log(since=lo)
    if dropped:
        return None
    return [(e["phase"], max(e["start"], lo), min(e["end"], hi))
            for e in entries if e["start"] < hi]


def busy_s(entries, phases):
    """The seconds that the entries of `phases` cover, each second once."""
    total, reach = 0.0, float("-inf")
    for start, end in sorted((s, e) for p, s, e in entries if p in phases):
        if end > reach:
            total += end - max(start, reach)
            reach = end
    return total


def read(run):
    entries = setup_entries(run)
    return None if entries is None else busy_s(entries, MAKING)
