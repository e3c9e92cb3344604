"""Horizons that carried prompt chunks and no decoding row: seconds of the
horizon over its ticks, median."""
from benchmark.readers import median_ms


def read(run):
    hs = run.measured.get("horizons") or []
    return median_ms([s / ev["k"] for s, ev in hs
                      if ev["prefill_rows"] > 0 and ev["decode_rows"] == 0])
