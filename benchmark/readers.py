"""Arithmetic shared by several per-layer metrics' readers."""
import statistics

from .flops.common import roofline_seconds
from .trace import kernel_seconds


def kernel_times(run):
    """{kernel: (seconds, calls)} of the job's kernels that the trace shows
    (told by their signatures, flops/common.py)."""
    calls = run.measured.get("kernel_calls") or {}
    if run.traced is None or not calls:
        return {}
    return kernel_seconds(run.traced["all_ops"],
                          {k: c[2] for k, c in calls.items()})


def roofline_share(run, kernels):
    """The least time the chip could take for the calls of `kernels` that
    the trace shows, over the time they took, in %. None (the metric is
    left out) where the trace shows no such call: never 0."""
    seen = kernel_times(run)
    least = took = 0.0
    for k in kernels:
        seconds, n = seen.get(k, (0.0, 0))
        ops, nbytes, _ = run.measured["kernel_calls"][k] if n else (0, 0, 0)
        least += n * roofline_seconds(ops, nbytes, run.peaks)
        took += seconds
    if took <= 0.0:
        return None
    return 100.0 * least / took


def idle_share(run):
    t = run.traced
    if t is None or not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def median_ms(seconds):
    return 1e3 * statistics.median(seconds) if seconds else None
