"""Profiler — reference python/paddle/profiler (profiler.py, timer.py,
profiler_statistic.py).

Three measurement layers, all real:

- device traces: jax.profiler start/stop_trace (perfetto, viewable in
  XProf/TensorBoard) around the RECORD states of the scheduler;
- host timers: per-step durations (Profiler.step), named regions
  (RecordEvent), and — while a profiler is active — per-op eager dispatch
  timings hooked into framework.core.apply_op (the TPU rendering of the
  reference's op-level CPU/GPU time tables);
- summary()/export(): aggregated statistics table / chrome-trace JSON;
- `span(name, **ids)`: the program's own regions on the host's timeline of
  the JAX profiler's trace, live exactly when a profiler session is;
  `read_spans(log_dir)` reads them back from the session's `.xplane.pb`;
- `compile_log()`: every program's tracing, lowering and compile (or load
  from the persistent cache), and the constructors that build a decoder,
  an engine or a trainer, on the host's clock, always on.
"""
import collections
import contextlib
import functools
import json
import os
import threading
import time

import jax

__all__ = ["Profiler", "ProfilerTarget", "RecordEvent", "span",
           "read_spans", "compile_log", "profiler_guard",
           "export_chrome_tracing", "make_scheduler", "ProfilerState",
           "SortedKeys", "export_protobuf", "load_profiler_result"]


class ProfilerTarget:
    CPU = "cpu"
    GPU = "tpu"  # alias: reference name kept for API parity
    TPU = "tpu"


class ProfilerState:
    """Reference python/paddle/profiler/profiler.py:ProfilerState."""
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


class SortedKeys:
    """Reference python/paddle/profiler/profiler.py:SortedKeys."""
    CPUTotal = 0
    CPUAvg = 1
    CPUMax = 2
    CPUMin = 3
    GPUTotal = 4
    GPUAvg = 5
    GPUMax = 6
    GPUMin = 7


def make_scheduler(*, closed, ready, record, repeat=0, skip_first=0):
    """Build a step-state schedule fn — reference profiler.make_scheduler."""
    period = closed + ready + record

    def scheduler(step):
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        if repeat > 0 and s >= repeat * period:
            return ProfilerState.CLOSED
        pos = s % period
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == period - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD
    return scheduler


class _Stat:
    __slots__ = ("count", "total", "mx", "mn")

    def __init__(self):
        self.count, self.total = 0, 0.0
        self.mx, self.mn = 0.0, float("inf")

    def add(self, dt):
        self.count += 1
        self.total += dt
        self.mx = max(self.mx, dt)
        self.mn = min(self.mn, dt)


_tls = threading.local()


def _event_stack():
    if not hasattr(_tls, "events"):
        _tls.events = []
    return _tls.events


_active_profiler = None    # host-timer sink (independent of the op hook)


class Profiler:
    """Measures while active: step durations, RecordEvent regions, per-op
    eager dispatch times; optionally records a jax device trace."""

    def __init__(self, targets=None, scheduler=None, on_trace_ready=None,
                 timer_only=False, log_dir="./profiler_log", record_ops=True):
        self.log_dir = log_dir
        self.timer_only = timer_only
        self.record_ops = record_ops
        self.on_trace_ready = on_trace_ready
        if isinstance(scheduler, tuple):
            lo, hi = scheduler
            scheduler = make_scheduler(closed=max(lo, 0), ready=0,
                                       record=hi - lo, repeat=1)
        self._scheduler = scheduler
        self._step_idx = 0
        self._state = ProfilerState.CLOSED
        self._tracing = False
        self._started = False
        self._step_stat = _Stat()
        self._event_stats = {}
        self._op_stats = {}
        self._timeline = []         # (name, start_s, dur_s) host events
        self._step_t0 = None
        self._num_samples = 0
        self._pending_cycle = False    # recorded data not yet handed to handler

    # -- op hook (called from framework.core.apply_op) --------------------
    def _record_op(self, name, t0, t1):
        stack = _event_stack()
        if stack:
            name = f"{stack[-1]}::{name}"
        self._op_stats.setdefault(name, _Stat()).add(t1 - t0)

    def _record_event(self, name, t0, t1):
        if not self._recording_now():
            return
        self._event_stats.setdefault(name, _Stat()).add(t1 - t0)
        self._timeline.append((name, t0, t1 - t0))

    # -- lifecycle --------------------------------------------------------
    def start(self):
        global _active_profiler
        self._started = True
        self._wall0 = time.perf_counter()
        self._step_t0 = time.perf_counter()
        _active_profiler = self
        if self._scheduler is None:
            self._pending_cycle = True
            self._set_op_hook(True)
            if not self.timer_only:
                self._start_trace()
        else:
            self._apply_state(self._scheduler(self._step_idx))

    def _recording_now(self):
        return self._scheduler is None or self._state in (
            ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN)

    def stop(self):
        global _active_profiler
        if not self._started:
            return
        self._set_op_hook(False)
        if _active_profiler is self:
            _active_profiler = None
        if self._tracing:
            self._stop_trace()
        self._started = False
        if self.on_trace_ready is not None and self._pending_cycle:
            self.on_trace_ready(self)
            self._pending_cycle = False

    def _set_op_hook(self, on):
        """The op hook syncs the device per dispatch (honest timings), so it
        is only installed while the scheduler is in a RECORD state."""
        from ..framework import core
        if on and self.record_ops and not self.timer_only:
            core._op_profiler = self
        elif core._op_profiler is self:
            core._op_profiler = None

    def _start_trace(self):
        try:
            jax.profiler.start_trace(self.log_dir)
            self._tracing = True
            self._trace_ran = True
        except Exception:
            self._tracing = False

    def _stop_trace(self):
        try:
            jax.profiler.stop_trace()
        except Exception:
            pass
        self._tracing = False

    def _apply_state(self, state):
        recording = state in (ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN)
        self._set_op_hook(recording)
        if recording and not self._tracing and not self.timer_only:
            self._start_trace()
        elif not recording and self._tracing:
            self._stop_trace()
        if recording:
            self._pending_cycle = True
        if self._state == ProfilerState.RECORD_AND_RETURN and not recording \
                and self.on_trace_ready is not None:
            self.on_trace_ready(self)      # cycle boundary (reference behavior)
            self._pending_cycle = False
        self._state = state

    def step(self, num_samples=None):
        """Marks a training-step boundary: times the step, advances the
        trace scheduler."""
        now = time.perf_counter()
        if self._step_t0 is not None and self._recording_now():
            self._step_stat.add(now - self._step_t0)
            self._timeline.append((f"step#{self._step_idx}", self._step_t0,
                                   now - self._step_t0))
        self._step_t0 = now
        if num_samples:
            self._num_samples += num_samples
        self._step_idx += 1
        if self._scheduler is not None:
            self._apply_state(self._scheduler(self._step_idx))

    def step_info(self, unit=None):
        s = self._step_stat
        if s.count == 0:
            return "no steps recorded"
        avg = s.total / s.count
        ips = (self._num_samples / s.total) if s.total and self._num_samples else 0.0
        return (f"batch_cost: {avg * 1000:.2f} ms, ips: {ips:.2f} samples/s"
                if ips else f"batch_cost: {avg * 1000:.2f} ms")

    # -- reporting --------------------------------------------------------
    def summary(self, sorted_by=SortedKeys.CPUTotal, op_detail=True,
                thread_sep=False, time_unit="ms"):
        unit = {"s": 1.0, "ms": 1e3, "us": 1e6}.get(time_unit, 1e3)

        def table(title, stats):
            if not stats:
                return ""
            rows = sorted(stats.items(), key=lambda kv: -kv[1].total)
            w = max(28, max(len(k) for k in stats) + 2)
            head = (f"\n{title}\n" + "-" * (w + 48) + "\n"
                    + f"{'Name':<{w}}{'Calls':>7}{'Total':>12}{'Avg':>10}"
                    + f"{'Max':>10}{'Min':>9}  ({time_unit})\n")
            body = "".join(
                f"{k:<{w}}{st.count:>7}{st.total * unit:>12.3f}"
                f"{st.total / st.count * unit:>10.3f}{st.mx * unit:>10.3f}"
                f"{st.mn * unit:>9.3f}\n"
                for k, st in rows[:60])
            return head + body

        out = ["Profiler summary"]
        if self._step_stat.count:
            out.append(table("Steps", {"train_step": self._step_stat}))
            out.append(self.step_info() + "\n")
        out.append(table("Events (RecordEvent)", self._event_stats))
        if op_detail:
            out.append(table("Ops (eager dispatch, host)", self._op_stats))
        if getattr(self, "_trace_ran", False):
            out.append(f"device trace dir: {self.log_dir}\n")
        return "".join(o for o in out if o)

    def timeline_events(self):
        """The host timeline (RecordEvent regions, step marks) as
        chrome-trace event dicts, ts-sorted so the (0, 0) track is
        monotonic (a nested region is APPENDED at its end time, so raw
        timeline order is end-time order — a child's later start would
        precede its parent's earlier one). Timestamps are raw
        perf_counter microseconds, the same base
        `serving.trace.FlightRecorder` stamps — `serving.trace
        .export_chrome_trace` merges both onto one timeline."""
        return sorted(
            ({"name": n, "ph": "X", "ts": t0 * 1e6, "dur": d * 1e6,
              "pid": 0, "tid": 0} for n, t0, d in self._timeline),
            key=lambda e: e["ts"])

    def export(self, path=None, format="json"):
        """Writes the host timeline as a chrome-trace JSON (load with
        load_profiler_result / chrome://tracing); returns the path."""
        path = path or os.path.join(self.log_dir, "host_trace.json")
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "w") as f:
            json.dump({"traceEvents": self.timeline_events()}, f)
        return path

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False


def span(name, **ids):
    """A region of host code on the JAX profiler's own timeline: a
    `jax.profiler.TraceAnnotation`, so it lands in the `.xplane.pb` host
    plane on the clock of the device operations whenever a profiler
    session is running (`Profiler`, `jax.profiler.trace`, a benchmark's
    traced run) and costs about half a microsecond when none is. A span
    is name, start, end; its parent is the span that encloses it on the
    thread. `ids` (`seq` of a serving round, `rid` of a request, `step`
    of a training step, ...) become the event's stats and leave its name
    clean, so spans join to each other and to the engine's horizon
    records by id. The engine's and the trainer's vocabulary is in
    docs/observability.md."""
    return jax.profiler.TraceAnnotation(name, **ids)


def read_spans(log_dir, prefixes=("engine.", "trainer.")):
    """The spans a finished profiler session under `log_dir` holds, read
    back from its newest `.xplane.pb`: [{"name", "start_ns", "end_ns",
    **ids}] in order of start, a parent before its children. `prefixes`
    picks them by name (default: the engine's and the trainer's
    vocabulary; `("",)` takes every event of every plane, device
    operations included). How the `span`s are read without a profile
    viewer: which phase of which `seq` ran when, on the device
    operations' clock."""
    import glob
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    prefixes = tuple(prefixes)
    spans = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(prefixes):
                    spans.append(dict(
                        ev.stats, name=ev.name, start_ns=ev.start_ns,
                        end_ns=ev.start_ns + ev.duration_ns))
    return sorted(spans, key=lambda s: (s["start_ns"], -s["end_ns"]))


# ------------------------------------------------------------ compile log

# JAX's events for the three phases of making a program; the backend's
# phase is a "load" where the persistent cache answered inside it
_PHASE_OF = {"/jax/core/compile/jaxpr_trace_duration": "trace",
             "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
             "/jax/core/compile/backend_compile_duration": "compile"}
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
# what a window that must not compile counts (the harness's two events)
_COMPILING = ("lower", "compile", "load")
# a cell's set-up, nested traces included, holds a few thousand entries
_LOG_SIZE = 1 << 16
_log = collections.deque(maxlen=_LOG_SIZE)   # (program, phase, start, end)
_log_lock = threading.Lock()
_dropped = 0
_cache_hit = threading.local()


def _program(fun_name):
    """One program, one name: JAX calls a jitted `f` "f" while it traces
    it and "jit(f)" while it lowers and compiles it."""
    head, paren, inner = fun_name.partition("(")
    if paren and head in ("jit", "pmap") and inner.endswith(")"):
        return inner[:-1]
    return fun_name


def _note(program, phase, seconds):
    """Append one entry that ends now, on `time.perf_counter()`'s clock
    (JAX times its phases on `time.time()`: only the length is taken)."""
    global _dropped
    with _log_lock:
        end = time.perf_counter()
        if len(_log) == _LOG_SIZE:
            _dropped += 1
        _log.append((program, phase, end - seconds, end))


def _on_duration(event, seconds, fun_name="", **_):
    phase = _PHASE_OF.get(event)
    if phase is None:
        return
    if phase == "compile":
        if getattr(_cache_hit, "seen", False):
            phase = "load"
        _cache_hit.seen = False
    _note(_program(str(fun_name)), phase, seconds)


def _on_event(event, **_):
    if event == _CACHE_HIT:
        _cache_hit.seen = True


# once a process, at import: `import paddle_tpu` brings this module in
# before any of its programs compiles. JAX calls these only while it makes
# a program; a dispatch of a program already made calls neither.
jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)


def compile_log(since=None):
    """(entries, dropped): what the process has spent making programs and
    building what runs them, oldest first, on `time.perf_counter()`'s
    clock (the engine's record's and a benchmark's `setup_s`'s).

    An entry is {"program", "phase", "start", "end"}. `phase` is "trace"
    (Python to a jaxpr), "lower" (jaxpr to an MLIR module), "compile"
    (XLA made the executable) or "load" (the persistent compilation cache
    held it), with `program` the jitted function's name (a serving
    program's key: `packed_multi_k2_t8_w1_p64`); or "build", with
    `program` the class whose constructor ran inside a `decoder.build`,
    `engine.build` or `trainer.build` span. A trace nests the traces of
    the jitted functions it calls, and a build the programs it makes.

    `since` keeps the entries that ended at or after it. The log holds
    the newest 65,536 entries; `dropped` counts the older ones it let go
    that may have ended at or after `since` (0 where the log reaches back
    before it), so a reading that would come out short can be refused."""
    out = []
    with _log_lock:
        dropped = _dropped
        for program, phase, start, end in reversed(_log):
            if since is not None and end < since:
                dropped = 0
                break
            out.append({"program": program, "phase": phase,
                        "start": start, "end": end})
    out.reverse()
    return out, dropped


def _compiles_since(t):
    """How many lowerings, compiles and cache loads ended at or after `t`:
    what a measured window that must not compile counts. A round that
    made nothing (the log's newest entry ended before `t`) pays one look
    at it."""
    if not _log or _log[-1][3] < t:
        return 0
    return sum(e["phase"] in _COMPILING for e in compile_log(since=t)[0])


def build_span(name):
    """Decorator of the constructor of what serves or trains: each
    construction is a `span(name, program=<class>)` on the profiler's
    timeline and a "build" entry of the compile log."""
    def wrap(init):
        @functools.wraps(init)
        def built(self, *args, **kwargs):
            program = type(self).__name__
            t0 = time.perf_counter()
            with span(name, program=program):
                init(self, *args, **kwargs)
            _note(program, "build", time.perf_counter() - t0)
        return built
    return wrap


class RecordEvent:
    """Named region, three ways: a `span` on the profiler's host timeline
    (in the `.xplane.pb` whenever a profiler session is running, eager
    code included), a `jax.named_scope` (which names the operations
    TRACED under it in their HLO metadata and marks nothing on the
    host's timeline), and a host-timed row in the active `Profiler`'s
    event table."""

    def __init__(self, name, event_type=None):
        self.name = name
        self._scope = jax.named_scope(name)
        self._span = span(name)

    def begin(self):
        self._t0 = time.perf_counter()
        _event_stack().append(self.name)
        self._span.__enter__()
        self._scope.__enter__()

    def end(self):
        self._scope.__exit__(None, None, None)
        self._span.__exit__(None, None, None)
        stack = _event_stack()
        if stack and stack[-1] == self.name:
            stack.pop()
        t1 = time.perf_counter()
        if _active_profiler is not None and hasattr(self, "_t0"):
            _active_profiler._record_event(self.name, self._t0, t1)

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()
        return False


@contextlib.contextmanager
def profiler_guard(log_dir="./profiler_log"):
    p = Profiler(log_dir=log_dir)
    p.start()
    try:
        yield p
    finally:
        p.stop()


def export_chrome_tracing(dir_name, worker_name=None):
    def handler(prof):
        return prof.export(os.path.join(dir_name, "host_trace.json"))
    return handler


def export_protobuf(dir_name, worker_name=None):
    """Exporter callback (jax.profiler writes its own pb into log_dir)."""
    def handler(prof):
        os.makedirs(dir_name, exist_ok=True)
        return dir_name
    return handler


def load_profiler_result(filename):
    """Loads a chrome-trace JSON written by Profiler.export."""
    with open(filename) as f:
        return json.load(f)
