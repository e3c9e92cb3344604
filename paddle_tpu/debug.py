"""Failure detection & numerics debugging.

Reference counterpart: FLAGS_check_nan_inf + paddle/fluid/framework/details/
nan_inf_utils (per-op NaN/Inf scan). TPU-native: a jit-compatible checker
based on jax error-checking semantics — `check_numerics` inserts a device-side
assert-like guard; `enable_check_nan_inf` flips a global that the Trainer and
eager dispatch honor on loss/grads.
"""
import statistics

import jax
import jax.numpy as jnp

from .framework.core import Tensor, apply_op
from .profiler import compile_log

__all__ = ["check_numerics", "enable_check_nan_inf", "check_nan_inf_enabled",
           "assert_finite_pytree", "TensorCheckerConfig", "diagnose",
           "input_pipeline_stats", "memory_report", "schedule_report",
           "determinism_report",
           "autotune", "serving_stats", "serving_report"]


def serving_stats():
    """Telemetry of every live serving engine
    (`serving.ContinuousBatchingEngine` / `SpeculativeEngine`): queue
    wait, slot occupancy, tokens/s, per-token p50/p99 latency, and —
    the multi-step decode headline — host syncs per generated token
    (1.0 on the per-tick path, ≤ 1/K with a K-tick horizon). The
    observability half of device-resident decode: when
    `host_syncs_per_token` is near 1 on a model whose tick roofline is
    tiny, the host round-trip (not the chip) is the decode bottleneck —
    raise the engine's `k_max` or let `cost_model.decode_horizon` price
    it. Returns one summary dict per engine."""
    from .serving import serving_stats as _stats
    return _stats()


def _horizon_summary(events):
    """(programs, schedule entries, pad ledger) from an engine's horizon
    records (`serve_schedule()`, always on: no recorder needed). Per
    `program` (the name its compiled program carries in a trace) the
    count of horizons, the seconds the process spent compiling it or
    loading it from the persistent cache (`profiler.compile_log`, every
    signature under that name) and the median tick — sync to sync: from
    the later of the previous horizon's `t_fetched` and its own
    `t_round` to its own `t_fetched`, over its k ticks. For the
    schedule: the median round of host work (`admit_s + plan_s +
    dispatch_s + book_s`), the deepest queue an admission pass left
    behind and the median submit-to-admit wait. Which program is slow,
    how much host work a round hides behind the device, whether
    requests queue: what a device trace alone cannot answer. The pad
    ledger is the recent-horizon view of `stats.pad_fraction`: how much
    of the dispatched token layout was padding."""
    by_program, host, depth, waits = {}, [], [], []
    disp = padded = 0
    prev_fetched = None
    for ev in events:
        if ev.get("kind") != "horizon" or "t_fetched" not in ev:
            continue
        start = ev["t_round"] if prev_fetched is None \
            else max(prev_fetched, ev["t_round"])
        prev_fetched = ev["t_fetched"]
        by_program.setdefault(ev["program"], []).append(
            (ev["t_fetched"] - start) / ev["k"])
        host.append(ev["admit_s"] + ev["plan_s"] + ev["dispatch_s"]
                    + ev["book_s"])
        depth.append(ev["queue_depth"])
        waits.extend(ev["admit_waits_s"])
        disp += ev["tokens_dispatched"]
        padded += ev.get("tokens_padded") or 0
    made = {}
    for e in compile_log()[0]:
        if e["phase"] in ("compile", "load"):
            made[e["program"]] = made.get(e["program"], 0.0) \
                + e["end"] - e["start"]
    programs = [{"program": name, "n": len(ticks),
                 "compile_load_s": made.get(name, 0.0),
                 "tick_ms_p50": 1e3 * statistics.median(ticks)}
                for name, ticks in sorted(by_program.items())]
    schedule = {}
    if host:
        schedule = {"round_host_ms_p50": 1e3 * statistics.median(host),
                    "queue_depth_max": max(depth),
                    "queue_wait_ms_p50": (1e3 * statistics.median(waits)
                                          if waits else None)}
    pad = {"tokens_dispatched": disp, "tokens_padded": padded,
           "pad_fraction": round(padded / disp, 4)} if disp else None
    return programs, schedule, pad


def serving_report(drift_factor=None, print_report=False):
    """Deep serving observability, one dict per live engine (sorted by
    engine name/id like `serving_stats`): the `ServeStats` summary,
    the recent scheduling-decision trace summarized (horizons,
    prefill syncs, stalls, the median round of host work, the deepest
    queue and the median queue wait), per `program` the count and the
    median sync-to-sync tick, and the pad ledger (`_horizon_summary`,
    from the engine's always-on horizon records: no recorder
    needed), and — when the engine carries a flight
    recorder (`ContinuousBatchingEngine(trace=...)`) — the rolling
    roofline-drift ledger per dispatch shape with its mispriced
    shapes flagged (`serving.trace.FlightRecorder.drift_report`; the
    CI face of the same data is the `ROOFLINE-DRIFT` Graph Doctor
    rule). When `host_syncs_per_token` says the host interposes too
    often, this report says WHICH horizon shapes and WHY: drift > 1
    means ticks run slower than priced (the scheduler fuses too few
    ticks per sync), drift < 1 that the model overprices and leaves
    capacity scheduled idle."""
    from .serving.stats import live_engines
    report = []
    for eng in live_engines():
        entry = {"stats": eng.stats.summary()}
        if hasattr(eng, "tenancy_summary"):
            # multi-tenant engines: per-tenant ledgers, per-class p99
            # vs roofline targets, fairness, preemption counts
            entry["tenancy"] = eng.tenancy_summary()
        events = eng.serve_schedule() if hasattr(eng, "serve_schedule") \
            else []
        if events:
            entry["schedule"] = {
                "horizons": sum(ev.get("kind") == "horizon"
                                for ev in events),
                "prefill_syncs": sum(ev.get("kind") == "prefill_sync"
                                     for ev in events),
                "stalled_prefill_syncs": sum(
                    ev.get("kind") == "prefill_sync"
                    and ev.get("decode_active", 0) > 0 for ev in events),
            }
            programs, schedule, pad = _horizon_summary(events)
            entry["schedule"].update(schedule)
            if programs:
                entry["programs"] = programs
            if pad:
                entry["pad"] = pad
        rec = getattr(eng, "trace", None)
        if rec is not None:
            drift = rec.drift_report(factor=drift_factor)
            entry["drift"] = drift
            entry["drifting_shapes"] = [d["shape"] for d in drift
                                        if d["drifting"]]
            entry["trace_events"] = len(rec.events)
        report.append(entry)
    if print_report:
        for entry in report:
            s = entry["stats"]
            print(f"== {s['engine']}#{s['engine_id']} ==")
            for key in ("stats", "schedule", "pad"):
                if key in entry:
                    print(f"  {key}: {entry[key]}")
            for pr in entry.get("programs", ()):
                print(f"  program {pr['program']}: n={pr['n']} "
                      f"(compile/load {pr['compile_load_s']:.3f} s) "
                      f"tick p50 {pr['tick_ms_p50']:.3f} ms")
            for d in entry.get("drift", ()):
                flag = "  << DRIFTING" if d["drifting"] else ""
                print(f"  drift {d['shape']}: predicted "
                      f"{d['predicted_s'] * 1e3:.3f} ms measured "
                      f"{d['measured_s'] * 1e3:.3f} ms ratio "
                      f"{d['ratio']:.2f} (n={d['n']}){flag}")
    return report


def fleet_report(router, print_report=False):
    """Fleet-wide serving observability (`serving.FleetRouter`): the
    `ServeStats.merge()` summary over every replica (counters summed,
    latency windows pooled in the deterministic replica order — the
    fleet p50/p99 is the pooled math, not an average of averages),
    the merged per-tenant/SLO ledgers, per-replica one-line stats,
    and the shared host tier's occupancy. The fleet face of
    `serving_report`: when the merged `prefix_hit_rate` sits below a
    single replica's, the affinity split is fragmenting the template
    working set; when `tier.n_entries` grows while hit rate holds,
    the shared tier is absorbing an HBM cliff (docs/serving.md
    "Fleet serving")."""
    merged = router.merged_stats().summary()
    tier = getattr(router.engines[0], "cache", None)
    tier = getattr(tier, "tier", None)
    report = {
        "stats": merged,
        "tenancy": router.tenancy_summary(),
        "replicas": [e.stats.summary() for e in router.engines],
    }
    if tier is not None and getattr(tier, "shared", False):
        report["shared_tier"] = {"entries": tier.n_entries,
                                 "bytes": tier.bytes_used,
                                 "path": str(tier.path)}
    if print_report:
        print(f"== fleet of {len(router.engines)} ==")
        print(f"  merged: {merged}")
        for i, r in enumerate(report["replicas"]):
            print(f"  replica{i}: requests {r.get('requests', 0)}, "
                  f"tokens {r.get('tokens', 0)}, hit_rate "
                  f"{r.get('prefix_hit_rate', 0.0)}")
        if "shared_tier" in report:
            print(f"  shared_tier: {report['shared_tier']}")
    return report


def autotune(target, *example_inputs, batch=None, hbm_budget=None,
             print_report=True, **kw):
    """Static (microbatch, remat) autotuner — the front door of
    `paddle_tpu.analysis.autotune`. No compile, no device execution:
    one no-remat CPU trace per candidate batch size, a what-if liveness
    replay per remat policy (what the Memory Doctor's peak becomes when
    the policy's checkpointed intermediates are dropped), and a
    roofline step-time ranking (max of compute/HBM/wire time).

    `target` may be a `distributed.Trainer` (pass the training
    `batch=`; candidates cover microbatch x policy for the REAL
    compiled step) or an `nn.Layer` (pass example inputs; policy sweep
    over a synthetic grad program). Returns an
    `analysis.AutotuneReport`: `.best` is the config to measure first,
    `.advice` the per-policy "peak X → Y per device, +Z% recompute
    FLOPs" lines. `hbm_budget` (bytes) prunes configs that don't fit —
    default is the chip's HBM capacity."""
    from .analysis.autotune import autotune as _autotune, autotune_layer
    from .nn.layer_base import Layer

    # Trainer-shaped = analysis_program AND step: PagedGPTDecoder also
    # exposes analysis_program (for memory_report/lints) but has no
    # train step to tune — it must fall through to the clear TypeError
    if hasattr(target, "analysis_program") and hasattr(target, "step"):
        if batch is None:
            raise ValueError("debug.autotune(trainer) needs batch=...")
        report = _autotune(target, batch, hbm_budget=hbm_budget, **kw)
    elif isinstance(target, Layer):
        args = [x._value if isinstance(x, Tensor) else x
                for x in example_inputs]
        report = autotune_layer(target, *args, hbm_budget=hbm_budget,
                                **kw)
    else:
        raise TypeError("debug.autotune wants a Trainer or an nn.Layer, "
                        f"got {type(target).__name__}")
    if print_report:
        print(report)
    return report


def memory_report(target, *example_inputs, batch=None, lr=0.0, top_k=8,
                  print_report=True, axis_host_counts=None):
    """Static per-device HBM report, before a chip sees the program.

    `target` may be a `distributed.Trainer` (pass the training `batch`;
    the report covers the FULL compiled step — fwd+bwd+optimizer, with
    the real shardings and donation), an `nn.Layer` (pass example
    inputs; forward only), or any jittable callable. Returns the
    `analysis.MemoryEstimate`: per-device peak live bytes, the
    args/transient split, the donation credit, and the top-k live
    tensors at the peak with their defining ops — the "what do I shard,
    remat or donate to fit" answer.  Estimates use native dtype widths
    (the TPU numbers), chip-independent: lowering happens on CPU.

    `axis_host_counts` ({axis: hosts}, the schedule pass's convention)
    marks a multi-host mesh: the report then also prices the
    DISTINCT-bytes-per-host peak (dp shards replicated within a host
    counted once) — the per-host checkpoint/offload footprint of a
    dp-over-hosts layout."""
    from .analysis import estimate_jaxpr_memory
    from .analysis.lowering import lower_callable, lower_layer
    from .nn.layer_base import Layer

    if hasattr(target, "analysis_program"):
        if hasattr(target, "step"):                # Trainer-shaped
            if batch is None:
                raise ValueError("memory_report(trainer) needs batch=...")
            program = target.analysis_program(batch, lr=lr)
        else:            # decoder-shaped (PagedGPTDecoder): the program
            program = target.analysis_program()    # is self-contained
    elif isinstance(target, Layer):
        args = [x._value if isinstance(x, Tensor) else x
                for x in example_inputs]
        program = lower_layer(target, *args)
    else:
        args = [x._value if isinstance(x, Tensor) else x
                for x in example_inputs]
        program = lower_callable(target, *args)
    n_hosts = 1
    for h in (axis_host_counts or {}).values():
        n_hosts *= max(int(h), 1)
    est = estimate_jaxpr_memory(program.jaxpr,
                                arg_infos=program.arg_infos, top_k=top_k,
                                n_hosts=n_hosts)
    if print_report:
        print(f"== memory report: {program.name} ==")
        print(est)
    return est


def schedule_report(target, *example_inputs, batch=None, lr=0.0,
                    hide_frac=0.5, chip="v5e", print_report=True):
    """Overlap-aware schedule report: the two-stream (compute vs
    collective) critical path of the lowered program, before a chip
    sees it.

    `target` may be a `distributed.Trainer` (pass the training
    `batch=`; the report covers the SAME specialized step `step()`
    dispatches — real shardings, real collectives), an `nn.Layer`
    (pass example inputs), or any jittable callable. Returns the
    `analysis.ScheduleEstimate`: the bracketed step time (roofline
    max <= overlap-aware <= serial sum), the fraction of collective
    wire time the schedule hides under compute, the critical path
    with per-op source attribution, and the COLL-SERIALIZED evidence
    — collectives the lowered program cannot overlap with anything
    (`hide_frac` is the bar). The same estimate feeds
    `debug.autotune`'s step pricing and the schedule manifests the
    `lint_schedule` gate pins."""
    from .analysis import estimate_schedule
    from .analysis.lowering import lower_callable, lower_layer
    from .nn.layer_base import Layer

    if hasattr(target, "analysis_program"):
        if hasattr(target, "step"):                # Trainer-shaped
            if batch is None:
                raise ValueError(
                    "schedule_report(trainer) needs batch=...")
            program = target.analysis_program(batch, lr=lr)
        else:            # decoder-shaped (PagedGPTDecoder)
            program = target.analysis_program()
    elif isinstance(target, Layer):
        args = [x._value if isinstance(x, Tensor) else x
                for x in example_inputs]
        program = lower_layer(target, *args)
    else:
        args = [x._value if isinstance(x, Tensor) else x
                for x in example_inputs]
        program = lower_callable(target, *args)
    mesh_axes = None
    try:
        from .distributed import mesh_axis_sizes
        mesh_axes = mesh_axis_sizes()
    except Exception:
        pass
    est = estimate_schedule(program, mesh_axes=mesh_axes,
                            hide_frac=hide_frac, chip=chip)
    if print_report:
        print(f"== schedule report: {program.name} ==")
        print(est)
    return est


def determinism_report(target=None, print_report=True, thread_paths=None,
                       **program_kw):
    """Determinism Doctor front door: prove (or refute) the
    byte-identical-stream invariant statically, before a request ever
    reaches a chip.

    `target` may be a serving decoder — anything with
    `analysis_program`, e.g. `serving.PagedGPTDecoder`; `program_kw`
    forwards, so `determinism_report(dec, k=4)` audits the same fused
    multi-step program the engine dispatches — or an already-lowered
    `analysis.LoweredProgram`. The graph side runs the write-site
    taint analysis (KV-WRITE-NONCANONICAL, RNG-KEY-TAINT), the
    scatter-race prover (SCATTER-WRITE-OVERLAP) and the donation
    audit (DONATE-HOST-ALIAS). The host side always runs the
    thread-discipline lint (SERVE-UNLOCKED-SHARED, SERVE-LOCK-ORDER)
    over serving/ + io/ (or `thread_paths`). With `target=None` only
    the host-side lint runs. Returns
    ``{"findings": [Finding...], "graph": {...}, "threads": {...}}``;
    the same data the CLI's ``--determinism`` flag prints and
    determinism_manifests/*.json pins per serving config (the
    `lint_determinism` gate)."""
    from .analysis.determinism import analyze_determinism
    from .analysis.lowering import LoweredProgram
    from .analysis.threads import lint_thread_discipline

    findings, graph = [], {}
    program = None
    if target is not None:
        if isinstance(target, LoweredProgram):
            program = target
        elif hasattr(target, "analysis_program"):
            program = target.analysis_program(**program_kw)
        else:
            raise TypeError(
                "determinism_report wants a serving decoder (an object "
                "with .analysis_program) or a LoweredProgram, got "
                f"{type(target).__name__}")
        res = analyze_determinism(program)
        findings += res.findings
        graph = res.metrics
    tfound, threads = lint_thread_discipline(paths=thread_paths)
    findings += tfound
    if print_report:
        if graph:
            print(f"== determinism: {program.name} ==")
            print(f"  pool writes {graph['n_canonical_writes']}/"
                  f"{graph['n_pool_writes']} canonical over "
                  f"{graph['n_pool_buffers']} buffer(s); "
                  f"{graph['n_rng_sites']} RNG site(s); overlap pairs "
                  f"{graph['n_proven_disjoint']}/"
                  f"{graph['n_overlap_pairs']} proven disjoint; "
                  f"{graph['n_alias_outputs']} alias output(s) of "
                  f"{graph['n_donated_args']} donated arg(s)")
        print(f"== threads: {threads['n_threaded_classes']}/"
              f"{threads['n_classes']} classes threaded, "
              f"{threads['n_shared_paths']} unlocked shared path(s) ==")
        if findings:
            for f in findings:
                print(f"  {f}")
        else:
            print("  clean (0 findings)")
    return {"findings": findings, "graph": graph, "threads": threads}


def input_pipeline_stats():
    """Aggregate telemetry of every live `io.DeviceLoader`/prefetcher:
    batches prefetched, current/max queue depth, host time blocked
    waiting on input, H2D enqueue time. The observability half of the
    async input pipeline — when `time_blocked_on_input_s` grows with
    step count, the pipeline (not the chip) is the bottleneck: raise
    `depth`, add DataLoader workers, or cheapen the transform."""
    from .io.prefetch import prefetch_stats
    return prefetch_stats()


def diagnose(model_or_fn, *example_inputs, context=None, print_report=True):
    """Graph Doctor house call: lower `model_or_fn` on CPU, run the full
    paddle_tpu.analysis pass catalog (layout, dtype, host-transfer,
    graph-shape, collective, dy2static AST lint), and return the Report.
    The numerics checkers above catch *runtime* failures; this catches
    the *structural* ones (activation transposes, f32 upcasts, host
    callbacks) before a chip ever sees the program."""
    from .analysis import analyze, analyze_layer
    from .nn.layer_base import Layer
    args = [x._value if isinstance(x, Tensor) else x
            for x in example_inputs]
    if isinstance(model_or_fn, Layer):
        report = analyze_layer(model_or_fn, *args, context=context)
    else:
        report = analyze(model_or_fn, *args, context=context)
    if print_report:
        print(report)
    return report

_state = {"enabled": False}


def enable_check_nan_inf(enable=True):
    _state["enabled"] = bool(enable)


def check_nan_inf_enabled():
    return _state["enabled"]


class TensorCheckerConfig:  # reference paddle.amp.debugging API parity
    def __init__(self, enable=True, debug_mode=None, **kw):
        self.enable = enable

    def __enter__(self):
        self._prev = _state["enabled"]
        _state["enabled"] = self.enable
        return self

    def __exit__(self, *exc):
        _state["enabled"] = self._prev
        return False


def check_numerics(x, name="tensor"):
    """Returns x unchanged; poisons it to NaN-free guarantee by erroring the
    step if non-finite values appear. Works inside jit via jnp.where +
    debug check: non-finite → replaced with inf-signal that callers assert on
    host; eagerly raises immediately."""
    def _f(v):
        finite = jnp.all(jnp.isfinite(v.astype(jnp.float32)))
        # keep a data dependency so XLA can't DCE the check
        return jax.lax.cond(finite, lambda t: t,
                            lambda t: t * jnp.float32(jnp.nan).astype(t.dtype), v)
    if isinstance(x, Tensor):
        out = apply_op(_f, x)
        # host-side readback only outside tracing (tracers poison via the
        # lax.cond above instead)
        if isinstance(out._value, jax.Array) and \
                not isinstance(out._value, jax.core.Tracer):
            import numpy as np
            if not np.isfinite(np.asarray(out._value.astype(jnp.float32))).all():
                raise FloatingPointError(f"non-finite values detected in {name}")
        return out
    return _f(x)


def assert_finite_pytree(tree, name="pytree"):
    """Host-side assertion over a pytree of concrete arrays (post-step)."""
    import numpy as np
    bad = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        arr = np.asarray(leaf._value if isinstance(leaf, Tensor) else leaf)
        if arr.dtype.kind == "f" and not np.isfinite(arr).all():
            bad.append(jax.tree_util.keystr(path))
    if bad:
        raise FloatingPointError(f"non-finite values in {name}: {bad[:8]}")
