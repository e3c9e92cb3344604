"""Of the (token, selected expert) pairs of the window, the share that the
experts held here took: `expert_assignments` (the horizon record's count,
summed over expert layers and ticks) over the real tokens processed x
`num_experts_per_tok` x the expert layers. An even router gives held over
router width (12.5% for one group of eight); it is what the held experts'
load is measured by. A program that does not count gives nothing.

A description, not a score: the router and the tokens set it, so no change to
the program moves it unless the mathematics changes. `better` in
BENCHMARK.json has to name a direction; a move of this number between two
commits says the routing changed, which is a fault, in either direction."""
from benchmark.records import horizons


def read(run):
    events = horizons(run)
    if not events or not all("expert_assignments" in ev for ev in events):
        return None
    cfg = run.cell.config
    layers = run.cell.family.flops.expert_layers(cfg)
    real = sum(ev["tokens_dispatched"] - ev["tokens_padded"]
               for ev in events)
    if not real or not layers:
        return None
    return 100.0 * sum(ev["expert_assignments"] for ev in events) / (
        real * cfg["num_experts_per_tok"] * layers)
