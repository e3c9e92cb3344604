"""One run of one cell:

    python -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Finds the cell's files by the names in BENCHMARK.json (cells.py), checks
that the machine holds the chips the cell asks for, lets the cell's job set
up, measure and compare, and prints one JSON object as the last line of
standard output. Without the chips, on a device that peaks.json does not
know, or with a compilation inside the window it exits non-zero and prints
no result.
"""
import time

_PROCESS_T0 = time.perf_counter()

import argparse           # noqa: E402
import contextlib         # noqa: E402
import importlib          # noqa: E402
import json               # noqa: E402
import shutil             # noqa: E402
import sys                # noqa: E402
import tempfile           # noqa: E402

from . import cells, compare, readers, trace as trace_mod   # noqa: E402
from .cells import BenchmarkError                   # noqa: E402

SPANS = ("bench_window", "batch_made", "step_dispatched", "step_waited",
         "loss_fetched", "request_submitted", "engine_run", "on_sync",
         "drain",
         # the engine's own, around a round and its phases (inside
         # `engine_run`): an idle gap is named by the innermost
         "engine.round", "engine.admit", "engine.plan", "engine.dispatch",
         "engine.fetch", "engine.bookkeep", "engine.on_sync")
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")
TRACED_WINDOW_S = 6.0     # a traced run's window is short: traces are large


class _Window:
    def __init__(self, seconds):
        self.seconds = seconds


class Run:
    """What a job is given, and where it leaves its readings."""

    def __init__(self, cell, seed, seconds, trace, peaks, faults=None):
        self.cell, self.seed, self.trace = cell, int(seed), bool(trace)
        self.seconds = float(seconds)
        self.peaks = peaks
        self.faults = faults or {}
        self.t0 = _PROCESS_T0
        self.setup_s = None
        self.e2e, self.measured = {}, {}
        self.attempted = self.failed = 0
        self.memory_peak_bytes = None
        self.compiles_in_window = 0
        self.traced = None
        self.correct, self.compared = False, {}
        self._in_window = False
        self._listening = False
        self.phases = []

    def mark(self, name):
        """Name the part of the run that has just ended."""
        self.phases.append([name, time.perf_counter() - self.t0])

    # ---------------------------------------------------------- set-up
    def setup_done(self):
        self.setup_s = time.perf_counter() - self.t0
        self.e2e["setup_s"] = self.setup_s

    def _listen(self):
        if self._listening:
            return
        from jax import monitoring

        def on_event(name, *_a, **_k):
            if self._in_window and name in _COMPILE_EVENTS:
                self.compiles_in_window += 1

        monitoring.register_event_duration_secs_listener(on_event)
        self._listening = True

    # ---------------------------------------------------------- window
    def window_seconds(self):
        if self.trace:
            return min(self.seconds, TRACED_WINDOW_S)
        return self.seconds

    @contextlib.contextmanager
    def profiled(self):
        """The profiler around what runs inside; on the way out the trace
        is reduced to what the per-layer metrics read (`self.traced`; its
        `window_s` is the time spent inside)."""
        import jax

        logdir = tempfile.mkdtemp(prefix="bench_trace_")
        # The harness's spans are TraceAnnotations, so Python's own calls
        # need no events; and the programs' HLO stays out of the file.
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.enable_hlo_proto = False
        jax.profiler.start_trace(logdir, profiler_options=options)
        t0 = time.perf_counter()
        try:
            yield
        finally:
            traced_s = time.perf_counter() - t0
            jax.profiler.stop_trace()
            self.mark("trace_stopped")
            try:
                self.traced = trace_mod.reduce(
                    trace_mod.find_xplane(logdir), SPANS, traced_s,
                    self.cell.chips)
            finally:
                shutil.rmtree(logdir, ignore_errors=True)
            self.mark("trace_reduced")

    @contextlib.contextmanager
    def window(self, profiled=True):
        """The measured window; a traced run's is short and, unless the job
        profiles a part of its own (`profiled=False`), under the profiler."""
        self._listen()
        with self.profiled() if self.trace and profiled \
                else contextlib.nullcontext():
            self._in_window = True
            try:
                with self.span("bench_window"):
                    yield _Window(self.window_seconds())
            finally:
                self._in_window = False

    def span(self, name):
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def read_memory_peak(self):
        import jax
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in jax.local_devices()]
        peaks = [p for p in peaks if p is not None]
        self.memory_peak_bytes = max(peaks) if peaks else None

    # ------------------------------------------------------------ judge
    def judge(self, numbers):
        self.correct, self.compared = compare.judge(
            numbers, self.cell.job.get("limits", {}))
        if self.failed:
            self.correct = False


def device_dict():
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "device_kind": d.device_kind, "count": len(jax.devices())}


def check_device(chips):
    """The machine has to hold the chips the cell asks for."""
    dev = device_dict()
    if dev["platform"] != "tpu":
        raise BenchmarkError(f"no accelerator: JAX's platform is "
                             f"{dev['platform']!r}")
    if dev["count"] < chips:
        raise BenchmarkError(f"the cell asks for {chips} chip(s) and JAX "
                             f"sees {dev['count']}")
    return dev


def place_compile_cache():
    """The program's own placement (JAX_COMPILATION_CACHE_DIR, else
    <checkout>/.jax_cache: a fixed path), with the machine's own cap on
    the cache's size lifted."""
    try:
        from paddle_tpu.sysconfig import use_compile_cache
    except ImportError as e:
        raise BenchmarkError(f"the program is not in this checkout: {e}")
    path = use_compile_cache()
    import jax
    jax.config.update("jax_compilation_cache_max_size", -1)
    return path


def run_cell(name, seed, seconds, trace, device_check=True, faults=None,
             root=cells.ROOT, here=cells.HERE):
    """Drive one run; returns (exit code, result dict or None)."""
    cell = cells.Cell(name, root=root, here=here)
    if device_check and cell.env_late:
        raise BenchmarkError(f"JAX was imported before the cell's env "
                             f"{cell.env_late} was placed")
    place_compile_cache()
    dev = check_device(cell.chips) if device_check else device_dict()
    peaks = cells.load_peaks(dev["kind"], here=here) if device_check \
        else {"bf16_flops": float("nan"), "hbm_bytes_per_s": float("nan")}
    run = Run(cell, seed, seconds, trace, peaks, faults=faults)
    run.mark("device_ready")
    job = importlib.import_module(f"benchmark.jobs.{cell.job['job']}")
    job.run(run)
    if run.compiles_in_window:
        raise BenchmarkError(f"{run.compiles_in_window} compilation(s) "
                             "inside the measured window")
    return result_of(run, dev)


def result_of(run, dev):
    cell = run.cell
    metrics = {}
    if run.trace:
        for m in cell.per_layer():
            value = cell.reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in cell.end_to_end():
            if m["name"] not in run.e2e:
                raise BenchmarkError(f"the job did not measure {m['name']}")
            metrics[m["name"]] = {"value": run.e2e[m["name"]],
                                  "unit": m["unit"]}
    device = dict(dev, memory_peak_bytes=run.memory_peak_bytes)
    result = {"correct": bool(run.correct), "attempted": run.attempted,
              "failed": run.failed, "metrics": metrics, "device": device}
    if run.trace:
        if run.traced is None:
            raise BenchmarkError("the trace holds no device operations")
        device["busy_s"] = run.traced["busy_s"]
        device["window_s"] = run.traced["window_s"]
        result["breakdown"] = {"device_ops": run.traced["device_ops"],
                               "idle_gaps": run.traced["idle_gaps"]}
        result["setup_s"] = run.setup_s
        result["traced_events"] = run.traced["n_events"]
        result["kernel_seconds"] = readers.kernel_times(run)
    result["phases"] = run.phases    # [name, seconds since the process began]
    result["compared"] = run.compared
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds, args.trace)
    except BenchmarkError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    for name, shown in result["compared"].items():
        print(f"compared {name}: {json.dumps(shown)}", file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
