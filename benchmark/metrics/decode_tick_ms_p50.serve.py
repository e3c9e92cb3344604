"""Horizons in which every row decodes: seconds from the previous horizon's
block landing on the host to this one's (the engine's own `t_fetched`) over
its ticks, median."""
from benchmark.readers import median_ms
from benchmark.records import horizons, tick_seconds


def read(run):
    return median_ms([s for s, ev in tick_seconds(horizons(run))
                      if ev["prefill_rows"] == 0])
