"""Flight recorder: per-request spans, per-tick scheduler trace and
roofline-drift accounting (serving/trace.py), merged chrome-trace
export with the profiler, and the non-perturbation contract — traced
streams byte-identical, untraced engines pay a dead branch.
"""
import json

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import GPT, gpt_tiny
from paddle_tpu.serving import (ContinuousBatchingEngine, FlightRecorder,
                                PagedGPTDecoder, PrefixCache,
                                export_chrome_trace, validate_chrome_trace)


@pytest.fixture(scope="module")
def tiny_model():
    paddle.seed(7)
    from paddle_tpu.distributed import build_mesh
    build_mesh(dp=1)
    cfg = gpt_tiny(max_seq_len=128, dtype="float32", remat=False)
    model = GPT(cfg)
    model.eval()
    return model


def _stream(model, prompts, max_new, eos=None, dec_kw=None, **eng_kw):
    dec = PagedGPTDecoder(model, num_pages=48, page_size=16,
                          max_batch=2, **(dec_kw or {}))
    eng = ContinuousBatchingEngine(dec, eos_token_id=eos,
                                   max_new_tokens=max_new, **eng_kw)
    rids = [eng.submit(np.asarray(p, np.int32)) for p in prompts]
    res = eng.run()
    assert len(eng._free) == dec.num_pages - 1, "page leak"
    return [res[r] for r in rids], eng


# --------------------------------------------------------------------------
# Non-perturbation: the acceptance contract
# --------------------------------------------------------------------------

@pytest.mark.parametrize("seed", range(3))
def test_traced_streams_byte_identical_under_churn(tiny_model, seed):
    """THE tracing acceptance bar: the byte-identical-stream fuzz
    (sampled config + EOS churn + chunked prompts) holds with tracing
    ENABLED on both the ragged and blocking engines — the recorder
    only reads host-side values the engine already fetched, so it
    cannot move a draw."""
    rng = np.random.RandomState(400 + seed)
    V = tiny_model.cfg.vocab_size
    prompts = [list(rng.randint(0, V, rng.randint(1, 40)).astype(int))
               for _ in range(4)]
    eos = int(rng.randint(0, V))
    max_new = int(rng.randint(3, 14))
    dec_kw = dict(temperature=0.8, top_k=40, seed=11)
    base, _ = _stream(tiny_model, prompts, max_new, eos, dec_kw, k_max=1)
    for k_max in (4, 8):
        blocking, eb = _stream(tiny_model, prompts, max_new, eos, dec_kw,
                               k_max=k_max, ragged=False,
                               trace=FlightRecorder())
        assert blocking == base, (seed, k_max, "blocking traced")
        ragged, er = _stream(tiny_model, prompts, max_new, eos, dec_kw,
                             k_max=k_max, chunk_tokens=8,
                             trace=FlightRecorder())
        assert ragged == base, (seed, k_max, "ragged traced")
        # the recorders really recorded: full lifecycles + priced
        # ticks (a serving tick is the engine's own horizon record)
        for eng in (eb, er):
            kinds = {ev["kind"] for ev in eng.trace.events}
            assert {"submit", "admit", "first_token",
                    "retire", "horizon"} <= kinds


def test_tracing_off_is_dead_branch(tiny_model):
    """With tracing off the engine does ZERO trace work per tick: no
    FlightRecorder exists and no record() call runs anywhere in a full
    drain (class-level event counter pinned across the run)."""
    before = FlightRecorder.total_events
    outs, eng = _stream(tiny_model, [[3, 141, 59], list(range(1, 30))],
                        8, k_max=4, chunk_tokens=8)
    assert eng.trace is None
    assert FlightRecorder.total_events == before
    # per-tick and blocking paths too
    _stream(tiny_model, [[3, 141, 59]], 4, k_max=1)
    _stream(tiny_model, [[3, 141, 59]], 4, k_max=4, ragged=False)
    assert FlightRecorder.total_events == before


# --------------------------------------------------------------------------
# Request lifecycle spans
# --------------------------------------------------------------------------

def test_request_spans_cover_lifecycle(tiny_model):
    """Every request's span hits the milestones in causal order:
    submit -> admit -> first_token -> retire, with progress marks
    every progress_every tokens; admit carries the prompt size."""
    rec = FlightRecorder(progress_every=4)
    prompts = [list(range(1, 30)), [5, 6, 7]]
    outs, eng = _stream(tiny_model, prompts, 9, k_max=4, chunk_tokens=8,
                        trace=rec)
    by_rid = {}
    for ev in rec.events:
        if "rid" in ev:
            by_rid.setdefault(ev["rid"], []).append(ev)
    assert sorted(by_rid) == [0, 1]
    for rid, evs in by_rid.items():
        marks = {ev["kind"]: ev for ev in evs}
        for kind in ("submit", "admit", "first_token", "retire"):
            assert kind in marks, (rid, sorted(marks))
        assert (marks["submit"]["ts"] <= marks["admit"]["ts"]
                <= marks["first_token"]["ts"] <= marks["retire"]["ts"])
        assert marks["submit"]["prompt_tokens"] == len(prompts[rid])
        assert marks["admit"]["slot"] in (0, 1)
        assert marks["retire"]["tokens"] == 9
        # 9 tokens at progress_every=4 -> marks at 4 and 8
        assert [ev["tokens"] for ev in evs
                if ev["kind"] == "progress"] == [4, 8]
    # token VALUES never recorded (traces are shareable)
    assert not any("token" == k or k == "ids" for ev in rec.events
                   for k in ev)


def test_admit_records_prefix_cache_mount(tiny_model):
    """With a prefix cache, a repeat prompt's admit event carries the
    mount detail: cached span length and hit blocks — the WHY of a
    fast TTFT, per request."""
    dec = PagedGPTDecoder(tiny_model, num_pages=48, page_size=16,
                          max_batch=2)
    cache = PrefixCache(16, salt=dec.cache_fingerprint())
    rec = FlightRecorder()
    prompt = list(range(1, 37))              # 2 full blocks + tail
    eng = ContinuousBatchingEngine(dec, max_new_tokens=4, k_max=4,
                                   chunk_tokens=8, prefix_cache=cache,
                                   trace=rec)
    r0 = eng.submit(np.asarray(prompt, np.int32))
    eng.run()
    r1 = eng.submit(np.asarray(prompt + [9, 9], np.int32))
    eng.run()
    admits = {ev["rid"]: ev for ev in rec.events
              if ev["kind"] == "admit"}
    assert admits[r0]["cached_tokens"] == 0
    assert admits[r1]["cached_tokens"] == 32    # two mounted blocks
    assert admits[r1]["hit_blocks"] == 2
    assert eng.stats.prefix_hits >= 2


# --------------------------------------------------------------------------
# Tick records + drift accounting
# --------------------------------------------------------------------------

def test_tick_records_price_and_measure(tiny_model):
    """Every dispatched horizon leaves one tick record: row
    composition (k/w/decode/prefill rows), a positive roofline-priced
    predicted_s, the measured wall seconds, and the pool-event fold —
    and the per-shape drift windows aggregate them."""
    rec = FlightRecorder()
    # 24 tokens: the pure-decode horizon shape repeats in steady state
    # (a shape's first — compiling — dispatch, and any window another
    # cold dispatch compiled inside, stay OUT of the drift ledger)
    outs, eng = _stream(tiny_model, [list(range(1, 30)), [3, 4, 5]],
                        24, k_max=4, chunk_tokens=8, trace=rec)
    ticks = [ev for ev in rec.events if ev["kind"] == "horizon"]
    assert ticks
    # one horizon, one dict: the recorder's tick IS the engine's record
    assert all(a is b for a, b in zip(ticks, eng.serve_schedule()))
    for ev in ticks:
        assert ev["track"] == "serve"
        # the default engine dispatches the PACKED token-stream layout
        # (shape keyed by the total-token bucket)
        assert ev["shape"][0] == "packed"
        assert ev["tokens_dispatched"] >= ev["tokens_padded"] >= 0
        assert ev["measured_s"] > 0
        assert ev["predicted_s"] > 0
        assert ev["k"] >= 1 and ev["w"] >= 1
        assert ev["decode_rows"] + ev["prefill_rows"] >= 1
        assert "cow" in ev["pool"] and "evictions" in ev["pool"]
    assert any(ev["prefill_rows"] for ev in ticks), \
        "chunked prompt never showed as a prefill row"
    drift = rec.drift_report()
    assert drift and all(d["n"] >= 1 and d["ratio"] > 0 for d in drift)
    assert {tuple(d["shape"]) for d in drift} <= \
        {tuple(ev["shape"]) for ev in ticks}
    # summary view
    s = rec.summary()
    assert s["events"] == len(rec.events)
    assert s["kinds"]["horizon"] == len(ticks)
    assert s["meta"]["engine"] == "ContinuousBatchingEngine"


@pytest.mark.parametrize("loop", ["multi", "per_tick"])
def test_drift_ledger_excludes_prefill_polluted_blocks(tiny_model, loop):
    """Blocking-path discipline: a horizon whose measured window
    contained a blocking prefill stays OUT of the drift ledger (same
    exclusion as the token percentiles), so drift compares decode
    ticks against the decode roofline only. On the per-tick loop such a
    tick is recorded unpriced, and the ledger counts exactly the others
    (three requests on two slots: the third's prefill blocks a tick
    while a slot still decodes)."""
    rec = FlightRecorder()
    if loop == "multi":
        outs, eng = _stream(tiny_model, [[3, 141, 59], [7, 8, 9, 10]],
                            12, k_max=4, ragged=False, trace=rec)
    else:
        outs, eng = _stream(tiny_model,
                            [[3, 141, 59], [7, 8, 9, 10], [5, 6]], 6,
                            k_max=1, trace=rec)
    ticks = [ev for ev in rec.events if ev["kind"] == "horizon"]
    kind = {"multi": "decode", "per_tick": "tick"}[loop]
    assert ticks and all(ev["shape"][0] == kind for ev in ticks)
    ledger_n = sum(d["n"] for d in rec.drift_report())
    assert ledger_n < len(ticks) or eng.stats.prefill_syncs == 0
    if loop == "per_tick":
        assert eng.stats.prefill_syncs >= 2
        polluted = [ev for ev in ticks if ev["predicted_s"] is None]
        assert len(polluted) == eng.stats.prefill_syncs
        assert all(ev["measured_s"] > 0 for ev in polluted)
        # the decode program's first use is the first tick, itself
        # polluted
        assert ledger_n == len(ticks) - len(polluted)


def test_serving_report_front_door(tiny_model):
    """debug.serving_report(): stats + schedule summary + drift per
    live engine, deterministically ordered, drifting shapes flagged."""
    from paddle_tpu import debug
    rec = FlightRecorder(drift_factor=1.0 + 1e-9)   # CPU vs priced
    # chip: everything drifts — the flagging path is exercised. 24
    # tokens at k_max=4 repeat the pure-decode horizon shape several
    # times: the ledger only collects WARM dispatches (a shape's first,
    # compiling, dispatch is excluded), so the workload must revisit
    # shapes
    outs, eng = _stream(tiny_model, [list(range(1, 20))], 24, k_max=4,
                        chunk_tokens=8, trace=rec)
    report = debug.serving_report()
    mine = [e for e in report
            if e["stats"]["engine_id"] == eng.stats.engine_id]
    assert len(mine) == 1
    entry = mine[0]
    assert entry["stats"]["tokens"] == 24
    assert entry["schedule"]["horizons"] >= 1
    assert entry["schedule"]["stalled_prefill_syncs"] == 0
    assert entry["drift"] and entry["drifting_shapes"]
    assert entry["trace_events"] == len(rec.events)
    # the pad ledger rides the engine's horizon records into the
    # report: the before/after evidence for the packed ragged layout
    # comes from our own records
    assert entry["pad"]["tokens_dispatched"] > 0
    assert entry["pad"]["pad_fraction"] == pytest.approx(
        entry["pad"]["tokens_padded"] / entry["pad"]["tokens_dispatched"],
        abs=1e-4)
    assert entry["stats"]["pad_fraction"] >= 0
    ids = [e["stats"]["engine_id"] for e in report]
    names = [e["stats"]["engine"] for e in report]
    assert sorted(zip(names, ids)) == list(zip(names, ids))


def test_trainer_step_multi_tick_records():
    """Trainer.attach_recorder: every fused N-step horizon lands one
    "train" tick record with measured wall seconds, and a priced
    predicted_s feeds the shared drift ledger."""
    from paddle_tpu.distributed import Trainer, build_mesh
    paddle.seed(0)
    build_mesh(dp=1)
    net = paddle.nn.Sequential(paddle.nn.Linear(8, 16), paddle.nn.ReLU(),
                               paddle.nn.Linear(16, 4))
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=net.parameters())

    def loss_fn(m, b):
        pred = m(paddle.to_tensor(b["x"]))
        return ((pred - paddle.to_tensor(b["y"])) ** 2).mean()

    tr = Trainer(net, opt, loss_fn)
    rec = tr.attach_recorder(True, predicted_step_s=1e-3)
    rng = np.random.RandomState(0)
    batch = {"x": rng.randn(4, 8).astype(np.float32),
             "y": rng.randn(4, 4).astype(np.float32)}
    for _ in range(3):
        tr.step_multi([batch] * 4)
    ticks = [ev for ev in rec.events if ev["kind"] == "tick"]
    assert len(ticks) == 3
    for ev in ticks:
        assert ev["track"] == "train"
        assert ev["shape"] == ["train", 4]
        assert ev["measured_s"] > 0
        assert ev["predicted_s"] == pytest.approx(4e-3)
    # first horizon (cold compile, no previous dispatch) is excluded
    # from the ledger; the two steady-state ones feed it
    drift = rec.drift_report()
    assert len(drift) == 1 and drift[0]["n"] == 2
    assert rec.meta["engine"] == "Trainer"
    # mark_recorder_idle: the next horizon is excluded again
    tr.mark_recorder_idle()
    tr.step_multi([batch] * 4)
    assert rec.drift_report()[0]["n"] == 2
    tr.step_multi([batch] * 4)
    assert rec.drift_report()[0]["n"] == 3
    # untraced trainers stay a dead branch (fresh net: the donated
    # params of `tr` may alias `net`'s arrays on single-device CPU)
    paddle.seed(1)
    net2 = paddle.nn.Sequential(paddle.nn.Linear(8, 16),
                                paddle.nn.ReLU(), paddle.nn.Linear(16, 4))
    opt2 = paddle.optimizer.SGD(learning_rate=0.1,
                                parameters=net2.parameters())
    before = FlightRecorder.total_events
    tr2 = Trainer(net2, opt2, loss_fn)
    tr2.step_multi([batch] * 2)
    assert FlightRecorder.total_events == before


def test_speculative_engine_traces_lifecycle_and_ticks(tiny_model):
    """SpeculativeEngine(trace=...): the per-tick loop it inherits
    records the same lifecycle spans and priced tick records (its
    verify cadence rides the ("tick", 1, 1) shape)."""
    from paddle_tpu.serving import SpeculativeEngine
    rec = FlightRecorder()
    dec = PagedGPTDecoder(tiny_model, num_pages=32, page_size=16,
                          max_batch=2)
    draft = PagedGPTDecoder(tiny_model, num_pages=32, page_size=16,
                            max_batch=2)
    eng = SpeculativeEngine(dec, draft, max_new_tokens=8, k=3, trace=rec)
    rid = eng.submit(np.asarray([3, 141, 59], np.int32))
    res = eng.run()
    assert len(res[rid]) == 8
    kinds = {ev["kind"] for ev in rec.events}
    assert {"submit", "admit", "first_token", "retire",
            "horizon"} <= kinds
    ticks = [ev for ev in rec.events if ev["kind"] == "horizon"]
    assert ticks and all(ev["measured_s"] > 0 for ev in ticks)
    assert rec.meta["engine"] == "SpeculativeEngine"
    # a spec step is priced as its REAL work (k draft ticks + one
    # (k+1)-wide verify + two syncs), strictly above a plain decode
    # tick's price — not the single-tick price the inherited per-tick
    # loop would otherwise use
    plain = ContinuousBatchingEngine(
        PagedGPTDecoder(tiny_model, num_pages=32, page_size=16,
                        max_batch=2), max_new_tokens=4, k_max=1,
        trace=FlightRecorder())
    assert all(ev["predicted_s"] > plain._price_horizon(1, 1, 0)
               for ev in ticks if ev["predicted_s"])


def test_hapi_fit_multi_step_tick_records():
    """Model.flight_recorder: every full fit(multi_step=N) horizon
    records a "train" tick (the tail falls back to per-step and
    records none), same schema as the Trainer's."""
    from paddle_tpu import nn

    class Toy(paddle.io.Dataset):
        def __init__(self, n=24):
            rng = np.random.RandomState(0)
            self.x = rng.randn(n, 8).astype(np.float32)
            self.y = rng.randint(0, 4, n).astype(np.int64)

        def __len__(self):
            return len(self.x)

        def __getitem__(self, i):
            return self.x[i], self.y[i]

    paddle.seed(0)
    net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
    model = paddle.Model(net)
    opt = paddle.optimizer.SGD(learning_rate=0.01,
                               parameters=model.parameters())
    model.prepare(opt, paddle.nn.CrossEntropyLoss())
    rec = model.flight_recorder = FlightRecorder()
    # 24/8 = 3 batches: one N=2 horizon + a 1-step per-step tail
    model.fit(Toy(), batch_size=8, epochs=1, shuffle=False, verbose=0,
              multi_step=2)
    ticks = [ev for ev in rec.events if ev["kind"] == "tick"]
    assert len(ticks) == 1
    assert ticks[0]["track"] == "train"
    assert ticks[0]["shape"] == ["fit", 2]
    assert ticks[0]["measured_s"] > 0


# --------------------------------------------------------------------------
# Chrome-trace export: one timeline, schema-gated
# --------------------------------------------------------------------------

def test_chrome_export_merges_recorder_and_profiler(tiny_model, tmp_path):
    """ACCEPTANCE: one chrome-trace export from a mixed ragged run
    contains request spans + tick records + profiler RecordEvent
    regions on ONE timeline (shared perf_counter base), and the
    export passes the schema gate."""
    from paddle_tpu.profiler import Profiler, RecordEvent
    rec = FlightRecorder()
    with Profiler(timer_only=True) as p:
        with RecordEvent("client_batch"):
            outs, eng = _stream(tiny_model,
                                [list(range(1, 30)), [3, 4, 5]], 8,
                                k_max=4, chunk_tokens=8, trace=rec)
        p.step()
    path = export_chrome_trace(str(tmp_path / "flight.json"),
                               recorders=rec, profiler=p)
    data = json.load(open(path))
    assert validate_chrome_trace(data) == []
    names = [e["name"] for e in data["traceEvents"]]
    assert "client_batch" in names                  # profiler region
    assert any(n.startswith("req0:") for n in names)      # spans
    assert any(n.startswith("req0:decode") for n in names)
    assert any(n.startswith("tick packed") for n in names)  # ticks
    # spans and profiler region share the clock: the client_batch
    # region must CONTAIN the first request's decode span
    region = next(e for e in data["traceEvents"]
                  if e["name"] == "client_batch")
    span = next(e for e in data["traceEvents"]
                if e["name"] == "req0:decode")
    assert region["ts"] <= span["ts"]
    assert span["ts"] + span["dur"] <= region["ts"] + region["dur"] + 1
    # round-trips through the profiler loader too
    from paddle_tpu.profiler import load_profiler_result
    assert load_profiler_result(path)["traceEvents"]


def test_validate_chrome_trace_schema(tmp_path):
    """The tier-1 schema gate: well-formed traces pass; missing keys,
    negative durations and non-monotonic per-track timestamps are each
    reported."""
    good = {"traceEvents": [
        {"name": "a", "ph": "X", "ts": 1.0, "dur": 2.0, "pid": 1, "tid": 0},
        {"name": "b", "ph": "X", "ts": 3.0, "dur": 0.0, "pid": 1, "tid": 0},
        {"name": "m", "ph": "M", "pid": 1, "tid": 0, "args": {}},
        {"name": "other-track", "ph": "i", "ts": 0.5, "pid": 2, "tid": 7},
    ]}
    assert validate_chrome_trace(good) == []
    assert validate_chrome_trace({"x": 1}) \
        == ["top-level object must carry a 'traceEvents' list"]
    missing = {"traceEvents": [{"ph": "X", "ts": 1.0, "dur": 1.0,
                                "pid": 1, "tid": 0}]}
    assert any("missing required key 'name'" in p
               for p in validate_chrome_trace(missing))
    bad_dur = {"traceEvents": [{"name": "a", "ph": "X", "ts": 1.0,
                                "dur": -1.0, "pid": 1, "tid": 0}]}
    assert any("non-negative 'dur'" in p
               for p in validate_chrome_trace(bad_dur))
    non_mono = {"traceEvents": [
        {"name": "a", "ph": "X", "ts": 5.0, "dur": 1.0, "pid": 1, "tid": 0},
        {"name": "b", "ph": "X", "ts": 4.0, "dur": 1.0, "pid": 1, "tid": 0},
    ]}
    assert any("monotonic" in p for p in validate_chrome_trace(non_mono))
    # partially overlapping same-track slices (the pipelined-horizon
    # shape the two-lane tick export exists to avoid): caught; nested
    # and exactly-abutting slices: clean
    overlap = {"traceEvents": [
        {"name": "a", "ph": "X", "ts": 0.0, "dur": 10.0, "pid": 1, "tid": 0},
        {"name": "b", "ph": "X", "ts": 5.0, "dur": 10.0, "pid": 1, "tid": 0},
    ]}
    assert any("overlaps" in p for p in validate_chrome_trace(overlap))
    nested = {"traceEvents": [
        {"name": "a", "ph": "X", "ts": 0.0, "dur": 10.0, "pid": 1, "tid": 0},
        {"name": "b", "ph": "X", "ts": 2.0, "dur": 3.0, "pid": 1, "tid": 0},
        {"name": "c", "ph": "X", "ts": 10.0, "dur": 4.0, "pid": 1, "tid": 0},
    ]}
    assert validate_chrome_trace(nested) == []
    # different tracks never cross-contaminate the monotonic check
    two_tracks = {"traceEvents": [
        {"name": "a", "ph": "X", "ts": 5.0, "dur": 1.0, "pid": 1, "tid": 0},
        {"name": "b", "ph": "X", "ts": 4.0, "dur": 1.0, "pid": 1, "tid": 1},
    ]}
    assert validate_chrome_trace(two_tracks) == []
    # path form
    path = tmp_path / "t.json"
    path.write_text(json.dumps(good))
    assert validate_chrome_trace(str(path)) == []


def test_mixed_ragged_export_is_schema_clean(tiny_model, tmp_path):
    """Tier-1 CI gate: a REAL mixed ragged run (chunked long prompt +
    decode rows + prefix cache churn) exports a schema-clean chrome
    trace — required keys present, every (pid, tid) track
    ts-monotonic."""
    rec = FlightRecorder(progress_every=4)
    outs, eng = _stream(tiny_model, [list(range(1, 41)), [3, 141, 59]],
                        9, k_max=4, chunk_tokens=8, trace=rec)
    path = export_chrome_trace(str(tmp_path / "ragged.json"),
                               recorders=rec)
    problems = validate_chrome_trace(path)
    assert problems == [], problems
    data = json.load(open(path))
    kinds = {e["ph"] for e in data["traceEvents"]}
    assert {"X", "M"} <= kinds
    assert any(e["ph"] == "i" for e in data["traceEvents"]), \
        "progress instants missing"


# --------------------------------------------------------------------------
# Stats satellites riding this PR
# --------------------------------------------------------------------------

def test_serving_stats_sorted_and_tail_percentiles(tiny_model):
    """serving_stats() output is deterministically ordered by (engine
    name, creation id), and summaries expose tail TTFT / queue wait
    (ttft_p99_ms, queue_wait_p99_ms) next to the p50s."""
    from paddle_tpu import debug
    engines = []
    for _ in range(3):
        outs, eng = _stream(tiny_model, [[3, 141, 59]], 5, k_max=2)
        engines.append(eng)                  # keep alive
    stats = debug.serving_stats()
    keys = [(s["engine"], s["engine_id"]) for s in stats]
    assert keys == sorted(keys)
    ids = [s["engine_id"] for s in stats
           if s["engine"] == "ContinuousBatchingEngine"]
    assert ids == sorted(ids) and len(set(ids)) == len(ids)
    s = engines[-1].stats.summary()
    for key in ("ttft_p50_ms", "ttft_p99_ms", "queue_wait_p50_ms",
                "queue_wait_p99_ms"):
        assert key in s, s
    assert s["ttft_p99_ms"] >= s["ttft_p50_ms"]
    assert s["queue_wait_p99_ms"] >= s["queue_wait_p50_ms"]
