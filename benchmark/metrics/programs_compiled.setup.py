"""How many programs set-up compiled, rather than loaded from the persistent
compilation cache: the compile log's "compile" entries inside set-up
(paddle_tpu.profiler.compile_log). A warm run pays for each of them again."""


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


def read(run):
    entries = setup_entries(run)
    if entries is None:
        return None
    return sum(phase == "compile" for phase, _, _ in entries)
