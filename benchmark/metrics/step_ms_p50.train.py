"""Median time between the ends of consecutive steps, on the host's clock."""
import statistics


def read(run):
    ends = run.measured.get("step_ends")
    if not ends or len(ends) < 3:
        return None
    return 1e3 * statistics.median(b - a for a, b in zip(ends, ends[1:]))
