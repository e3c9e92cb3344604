"""Of the pages the serving attention copies out of the pool and walks, the
share that held context: over the ticks of every dispatched horizon, the
pages the rows' contexts fill (`pages_live`, the engine's host-side lengths
at dispatch) over the page copies a tick's program makes for K in one layer
(`pages_gathered`: slots x the columns of the table it was handed). The rest
is the table's power-of-two width beyond the live pages and the slots that
hold no request (counts; a program that does not count them gives nothing)."""
from benchmark.records import horizons


def read(run):
    events = horizons(run)
    if not events or not all("pages_gathered" in ev for ev in events):
        return None
    gathered = sum(ev["k"] * ev["pages_gathered"] for ev in events)
    if not gathered:
        return None
    return 100.0 * sum(ev["k"] * ev["pages_live"] for ev in events) / gathered
