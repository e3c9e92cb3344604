"""Rows that held a request, decoding or taking prompt chunks, of all the
slots, over the ticks of every dispatched horizon (counts, exact)."""
from benchmark.records import horizons


def read(run):
    events = horizons(run)
    slots = sum(ev["k"] * ev["slots"] for ev in events)
    if not slots:
        return None
    return 100.0 * sum(ev["k"] * (ev["decode_rows"] + ev["prefill_rows"])
                       for ev in events) / slots
