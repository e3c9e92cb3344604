"""The compile log of `paddle_tpu.profiler`: every program's tracing,
lowering and compile or cache load, and the constructors' build entries,
on the host's `time.perf_counter()` clock (docs/observability.md)."""
import collections
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

from paddle_tpu import profiler
from paddle_tpu.profiler import compile_log
from paddle_tpu.serving.decoder import _named_jit

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _unique_program(name):
    """A `_named_jit` program no cache holds: a constant of its own."""
    c = float(int.from_bytes(os.urandom(3), "little"))
    return _named_jit(lambda x: x * c + 1.0, name)


def _since(t):
    return [(e["program"], e["phase"]) for e in compile_log(since=t)[0]]


def test_the_listener_is_on_before_the_first_program_compiles():
    """`import paddle_tpu` registers the listeners once, before any of
    its programs compiles: every backend compile JAX reports in a fresh
    process is in the log."""
    code = (
        "import jax\n"
        "from jax._src import monitoring as m\n"
        "seen = []\n"
        "m.register_event_duration_secs_listener(lambda e, d, **k: "
        "seen.append(e) if e.endswith('backend_compile_duration') "
        "else None)\n"
        "import paddle_tpu\n"
        "from paddle_tpu import profiler\n"
        "import jax.numpy as jnp\n"
        "(jnp.ones(3) * 2).block_until_ready()\n"
        "ours = [f for f in m.get_event_duration_listeners()"
        " if f is profiler._on_duration]\n"
        "hits = [f for f in m.get_event_listeners()"
        " if f is profiler._on_event]\n"
        "made = [e for e in profiler.compile_log()[0]"
        " if e['phase'] in ('compile', 'load')]\n"
        "print(len(ours), len(hits), len(seen), len(made))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    ours, hits, seen, made = map(int, out.stdout.split()[-4:])
    assert (ours, hits) == (1, 1)
    assert seen >= 1 and made == seen


def test_a_named_programs_first_call_traces_lowers_and_compiles():
    fn = _unique_program("compile_log_first_call")
    t = time.perf_counter()
    fn(jnp.ones(3)).block_until_ready()
    mine = [phase for program, phase in _since(t)
            if program == "compile_log_first_call"]
    assert mine == ["trace", "lower", "compile"]
    entries = [e for e in compile_log(since=t)[0]
               if e["program"] == "compile_log_first_call"]
    assert all(t <= e["start"] <= e["end"] <= time.perf_counter()
               for e in entries)
    # one phase after the other
    assert all(a["end"] <= b["start"] for a, b in zip(entries, entries[1:]))


def test_a_warm_call_leaves_no_entry():
    fn = _unique_program("compile_log_warm")
    x = jnp.ones(3)
    fn(x).block_until_ready()
    t = time.perf_counter()
    for _ in range(3):
        fn(x).block_until_ready()
    assert _since(t) == []
    assert profiler._compiles_since(t) == 0


def test_a_program_the_persistent_cache_holds_is_a_load(tmp_path):
    """After `jax.clear_caches()` the backend's phase of a program the
    persistent cache wrote is a load, and not a compile."""
    from jax._src import compilation_cache
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs")
    saved = {n: getattr(jax.config, n) for n in names}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compilation_cache.reset_cache()
    try:
        fn = _unique_program("compile_log_cached")
        t = time.perf_counter()
        fn(jnp.ones(3)).block_until_ready()
        assert ("compile_log_cached", "compile") in _since(t)
        jax.clear_caches()
        t = time.perf_counter()
        fn(jnp.ones(3)).block_until_ready()
        mine = [phase for program, phase in _since(t)
                if program == "compile_log_cached"]
        assert mine == ["trace", "lower", "load"]
        assert profiler._compiles_since(t) >= 2
    finally:
        for n, v in saved.items():
            jax.config.update(n, v)
        compilation_cache.reset_cache()


def test_one_program_keeps_one_name():
    """JAX says "f" while it traces and "jit(f)" while it lowers and
    compiles: the log says "f" for all three."""
    assert profiler._program("jit(packed_multi_k2_t8_w1_p64)") == \
        "packed_multi_k2_t8_w1_p64"
    assert profiler._program("packed_multi_k2_t8_w1_p64") == \
        "packed_multi_k2_t8_w1_p64"
    assert profiler._program("jit(<lambda>)") == "<lambda>"
    assert profiler._program("step(x)") == "step(x)"


def test_a_build_span_is_a_span_and_an_entry():
    class Built:
        @profiler.build_span("thing.build")
        def __init__(self, x, y=2):
            """The constructor's own."""
            self.xy = x * y
            time.sleep(0.01)

    t = time.perf_counter()
    assert Built(3).xy == 6
    e, = [e for e in compile_log(since=t)[0] if e["phase"] == "build"]
    assert e["program"] == "Built" and e["end"] - e["start"] >= 0.01
    assert Built.__init__.__doc__ == "The constructor's own."


def test_the_bounded_log_counts_what_it_let_go(monkeypatch):
    """The log keeps the newest entries; `dropped` is counted against a
    reading only where the entries let go may have lain after `since`."""
    monkeypatch.setattr(profiler, "_LOG_SIZE", 4)
    monkeypatch.setattr(profiler, "_log", collections.deque(maxlen=4))
    monkeypatch.setattr(profiler, "_dropped", 0)
    for i in range(6):
        profiler._note(f"p{i}", "compile", 0.0)
    entries, dropped = compile_log()
    assert [e["program"] for e in entries] == ["p2", "p3", "p4", "p5"]
    assert dropped == 2
    # the log reaches back before `since`: nothing it let go was after it
    entries, dropped = compile_log(since=entries[1]["end"])
    assert [e["program"] for e in entries] == ["p3", "p4", "p5"]
    assert dropped == 0
    # the oldest entry kept ends after `since`: the log may be short
    assert compile_log(since=0.0)[1] == 2


@pytest.mark.parametrize("cls", ["decoder", "engine", "trainer"])
def test_the_constructors_are_build_spans(cls):
    from paddle_tpu.distributed.trainer import Trainer
    from paddle_tpu.serving import ContinuousBatchingEngine, PagedGPTDecoder
    from paddle_tpu.serving.mla_decoder import PagedMLADecoder
    inits = {"decoder": (PagedGPTDecoder, PagedMLADecoder),
             "engine": (ContinuousBatchingEngine,),
             "trainer": (Trainer,)}[cls]
    for c in inits:
        assert c.__init__.__wrapped__.__qualname__ == f"{c.__name__}.__init__"
