"""The engine's own record of each horizon, as the serving readers take it.

`jobs/serve_waves.py` hands the scheduler's events through as they are
(`run.measured["horizons"]`: (seconds on the harness's clock, event)). Since
the engine keeps one record a horizon (paddle_tpu/serving/engine.py,
`_begin_round`), an event carries the horizon's identity, its phase times on
the host's clock, read inside the engine where the work happens, and its
counts. A program that keeps no such record gives nothing to read here.
"""
HOST_PHASES = ("admit_s", "plan_s", "dispatch_s", "book_s")
PHASES = HOST_PHASES + ("fetch_wait_s", "on_sync_s")


def horizons(run):
    """The window's horizon records in the order of their dispatch; none
    where the program's events are not such records."""
    events = [ev for _, ev in run.measured.get("horizons") or []]
    if not all("t_fetched" in ev for ev in events):
        return []
    return events


def tick_seconds(events):
    """[(seconds of one tick, event)]: from one horizon's block landing on
    the host to the next one's (`t_fetched`; a wave's first horizon from the
    start of its own round), over the horizon's ticks."""
    out, prev = [], None
    for ev in events:
        start = ev["t_round"] if prev is None else max(prev, ev["t_round"])
        prev = ev["t_fetched"]
        out.append(((ev["t_fetched"] - start) / ev["k"], ev))
    return out
