"""Of the experts held here, the share that at least one token selected in
a tick, over the horizons in which every row decodes: `experts_hit` (summed
over expert layers and ticks) over held x expert layers x ticks. It is how
much of the held experts' weight a decode tick has to read. A program that
does not count gives nothing.

A description, not a score: the router and the tokens set it, so no change to
the program moves it unless the mathematics changes. `better` in
BENCHMARK.json has to name a direction; a move of this number between two
commits says the routing changed, which is a fault, in either direction."""
from benchmark.records import horizons


def read(run):
    events = [ev for ev in horizons(run) if ev["prefill_rows"] == 0]
    if not events or not all("experts_hit" in ev for ev in events):
        return None
    cfg = run.cell.config
    layers = run.cell.family.flops.expert_layers(cfg)
    if not layers:
        return None
    return 100.0 * sum(ev["experts_hit"] for ev in events) / (
        cfg["n_routed_experts"] * layers * sum(ev["k"] for ev in events))
