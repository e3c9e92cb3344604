"""Of the pages the serving attention copies out of the pool and walks, the
share that held context: over the ticks of every dispatched horizon, the
pages the rows' contexts fill (`pages_live`, the engine's host-side lengths
at dispatch) over the page copies a tick's program makes for K in one layer
(`pages_gathered`: slots x the table columns one tick's attention walks;
since PR 34, for a decoder whose walk ends at the deepest row, the whole
blocks of 8 pages that hold the deepest position, at most the table's width;
for one that copies the table it is handed, that width). The rest is the
deepest row's depth against the others', the slots that hold no request and
the last block's tail (counts; a program that does not count them gives
nothing)."""
from benchmark.records import horizons


def read(run):
    events = horizons(run)
    if not events or not all("pages_gathered" in ev for ev in events):
        return None
    gathered = sum(ev["k"] * ev["pages_gathered"] for ev in events)
    if not gathered:
        return None
    return 100.0 * sum(ev["k"] * ev["pages_live"] for ev in events) / gathered
