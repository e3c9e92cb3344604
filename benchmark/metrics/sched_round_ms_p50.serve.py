"""The host's own work for one horizon: admission, plan and table, the
dispatch call and the bookkeeping of its block (the engine's phase times;
the wait for the device and the caller's callback left out), median."""
from benchmark.readers import median_ms
from benchmark.records import HOST_PHASES, horizons


def read(run):
    return median_ms([sum(ev[p] for p in HOST_PHASES)
                      for ev in horizons(run)])
