"""Horizons that carried prompt chunks beside rows that decode: seconds of
the horizon (sync to sync, on the harness's clock) over its ticks, median."""
from benchmark.readers import median_ms


def read(run):
    hs = run.measured.get("horizons") or []
    return median_ms([s / ev["k"] for s, ev in hs
                      if ev["prefill_rows"] > 0 and ev["decode_rows"] > 0])
