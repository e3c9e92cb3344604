"""Test harness: the CPU backend with an 8-device virtual mesh.

Must run before any jax backend initialization.
"""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

from paddle_tpu.sysconfig import use_compile_cache  # noqa: E402

# Persistent XLA compilation cache: the suite is compile-bound (hundreds of
# jitted programs), so re-runs pick up every executable from disk instead
# of recompiling. Must be configured BEFORE the first backend touch or it
# is silently ignored. The loader's machine-feature E-logs only flag
# scheduling-preference pseudo-features (prefer-no-scatter/gather), not ISA
# differences.
use_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

assert jax.default_backend() == "cpu"

# DONATING multi-device executables must never come back from the
# persistent cache on this jaxlib/CPU combo. PR 1 observed deserialized
# sharded+donated step programs mis-executing nondeterministically —
# silently wrong losses, then heap corruption (`malloc(): unsorted
# double linked list corrupted`) / SIGSEGV killing the whole pytest
# process (tests/test_cross_mesh_resume.py was the canary) — and banned
# ALL multi-device programs from the cache. The real defect is narrower:
# the ASYNC CPU client can release a donated input buffer while a host
# read of an output aliased into it is still in flight (reproduced with
# NO deserialization at all — in-process-compiled hapi fit steps
# segfault ~1 in 3 under donate_argnums, 0/10 without; see
# hapi/model.py). Deserialize merely widened the race window by removing
# the compile wait. So: programs whose StableHLO carries input→output
# aliasing (`tf.aliasing_output` / `jax.buffer_donor`) stay quarantined
# — compiled once per process and memoized IN-PROCESS by cache key —
# while non-donating multi-device programs (ring attention, MoE,
# pipeline reference tests: the bulk of multi-device compile time, ~3
# min/run cold) ride the persistent cache like everything else. Their
# numerics are self-checked: every one is a matches-reference test, so a
# bad deserialize fails loudly rather than silently.
import jax._src.compiler as _compiler  # noqa: E402
from jax._src import compilation_cache as _cc  # noqa: E402

_orig_compile_or_get_cached = _compiler.compile_or_get_cached
_multi_device_memo = {}


def _module_donates(computation):
    try:
        asm = computation.operation.get_asm(large_elements_limit=16)
    except Exception:
        asm = str(computation)
    return "tf.aliasing_output" in asm or "jax.buffer_donor" in asm


def _compile_memo_multidevice(backend, computation, devices,
                              compile_options, host_callbacks,
                              executable_devices, *args, **kwargs):
    if getattr(devices, "size", 1) <= 1 or not _module_donates(computation):
        return _orig_compile_or_get_cached(backend, computation, devices,
                                           compile_options, host_callbacks,
                                           executable_devices,
                                           *args, **kwargs)
    try:
        key = _cc.get_cache_key(computation, devices, compile_options,
                                backend)
    except Exception:
        key = None
    if key is not None and key in _multi_device_memo:
        return _multi_device_memo[key]
    executable = _compiler.backend_compile_and_load(
        backend, computation, executable_devices, compile_options,
        host_callbacks)
    if key is not None:
        _multi_device_memo[key] = executable
    return executable


_compiler.compile_or_get_cached = _compile_memo_multidevice

import pytest  # noqa: E402

# GC tuning for the late-suite degradation (ROADMAP "tier-1 wall-clock
# health"): eager-heavy tests late in the sweep degrade ~10x in-process
# (8+ GB RSS, generational GC re-walking MILLIONS of long-lived objects
# — jaxprs, compiled executables, module state — on every gen2 pass).
# Two levers, both after the heavy imports above so they cover the bulk
# of the permanent object graph:
#   * gc.freeze(): move everything currently alive into the permanent
#     generation, so collections never traverse it again (the objects
#     are process-lifetime anyway: modules, jax registries, the
#     executable memo);
#   * threshold bump: gen0 700 -> 50_000 cuts collection FREQUENCY in
#     allocation-heavy eager loops; gen1/gen2 multipliers raised so
#     full passes stay rare as the suite accumulates state.
# A second freeze after the session's lazily-built fixtures would help
# more but there is no single post-fixture point; the module-scoped
# fixture below re-freezes at each module boundary instead, absorbing
# whatever the previous module permanently cached (compiled programs,
# baseline lowerings). Opt out with PADDLE_TPU_NO_GC_TUNE=1 (the A/B
# knob; measured on this container, eager-heavy 4-module block
# autograd+tensor_ops+nn_layers+transformer_seq2seq: 37.6s without ->
# 34.0s with, same 68 tests — the full-sweep effect is larger since
# gen2 passes late in the suite walk millions more live objects).
import gc as _gc  # noqa: E402

_GC_TUNE = not os.environ.get("PADDLE_TPU_NO_GC_TUNE")
if _GC_TUNE:
    _gc.collect()
    _gc.freeze()
    _gc.set_threshold(50_000, 25, 25)


def _trim_compiled_memos():
    """Per-module compiled-step cache retention (ROADMAP 'tier-1
    wall-clock health'): live Trainer / PagedGPTDecoder instances keep
    per-signature compiled-program memos (`_placed_steps`,
    `_placed_multis`, fused decode loops, ...) that pin executables +
    their jaxpr/HLO object graphs long after the module that built
    them finished. Clearing them at module boundaries — right before
    the collect+freeze below — lets the collector reclaim those
    graphs instead of freezing them into permanent, process-lifetime
    RSS. Anything genuinely still live just recompiles on its next
    step; in practice trainers/decoders are module-scoped at most."""
    import sys
    for name, fn in (("paddle_tpu.distributed.trainer",
                      "clear_compiled_step_memos"),
                     ("paddle_tpu.serving.decoder",
                      "clear_compiled_memos")):
        mod = sys.modules.get(name)      # only if already imported —
        if mod is None:                  # never force the import here
            continue
        try:
            getattr(mod, fn)()
        except Exception:
            pass                         # keep the suite usable mid-bootstrap


@pytest.fixture(autouse=True, scope="module")
def _refreeze_gc():
    """Re-freeze at module boundaries: anything the previous module left
    permanently cached (in-process compiled executables, baseline
    lowerings, dataset caches) stops being re-walked by every later
    module's collections. Before freezing, trim the compiled-step
    memos of surviving trainers/decoders and collect — frozen objects
    are excluded from every later collection, so garbage frozen here
    would otherwise live (and pay RSS) until process exit. Freezing
    true survivors stays safe as before."""
    if _GC_TUNE:
        if not os.environ.get("PADDLE_TPU_NO_MEMO_TRIM"):   # A/B knob
            _trim_compiled_memos()
            _gc.collect()
        _gc.freeze()
    yield


@pytest.fixture(autouse=True, scope="module")
def _fresh_global_mesh():
    """Each test module starts and ends with no global mesh, so sharding
    state (e.g. a dp=8 mesh from a distributed module) can't leak into
    later modules' eager constraints."""
    from paddle_tpu.distributed import mesh as _mesh

    _mesh._state["mesh"] = None
    _mesh._state["axis_context"] = ()
    yield
    _mesh._state["mesh"] = None
    _mesh._state["axis_context"] = ()
