"""Of the (token, selected column) pairs of the window, the share that are
identity (zero-compute) experts: `zero_assignments` (the horizon record's
count, summed over layers and ticks) over the real tokens processed x
`moe_topk` x the expert layers. An even router gives zero_expert_num over
router width (33.3% for 256 of 768); it is the share of a token's routed
weight that costs no expert pass anywhere. A program that does not count
(a family with no identity experts, this family's parent) gives nothing.

A description, not a score: the router and the tokens set it, so no change to
the program moves it unless the mathematics changes. `better` in
BENCHMARK.json has to name a direction; a move of this number between two
commits says the routing changed, which is a fault, in either direction."""
from benchmark.records import horizons


def read(run):
    events = horizons(run)
    if not events or not all("zero_assignments" in ev for ev in events):
        return None
    cfg = run.cell.config
    layers = run.cell.family.flops.expert_layers(cfg)
    real = sum(ev["tokens_dispatched"] - ev["tokens_padded"]
               for ev in events)
    if not real or not layers or not cfg.get("moe_topk"):
        return None
    return 100.0 * sum(ev["zero_assignments"] for ev in events) / (
        real * cfg["moe_topk"] * layers)
