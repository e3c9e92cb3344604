"""The engine's and the trainer's own tracing: `profiler.span`s on the JAX
profiler's timeline (live exactly when a profiler session is), the ONE
record a horizon (always on), programs and kernels that carry their names,
and `jax.named_scope`s inside the serving and the training programs.
docs/observability.md has the vocabulary.
"""
import ast
import glob
import os
import time

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import GPT, gpt_tiny
from paddle_tpu.serving import (ContinuousBatchingEngine, FlightRecorder,
                                PagedGPTDecoder)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PROMPTS = ([1, 2, 3], list(range(1, 30)), [7, 8])
LOOPS = {"ragged": dict(k_max=4, chunk_tokens=8),
         "multi": dict(k_max=4, ragged=False),
         "per_tick": dict(k_max=1)}
PHASES = ("admit_s", "plan_s", "dispatch_s", "fetch_wait_s", "book_s",
          "on_sync_s")
# every field of an untraced engine's record; PERF.md section 3 names the
# reader of each (a metric, `debug.serving_report()` or the engine itself)
FIELDS = {"kind", "seq", "k", "w", "t_tokens", "decode_rows", "prefill_rows",
          "slots", "program", "compiles", "queue_depth", "admit_waits_s",
          "t_round", "t_fetched", "tokens", "tokens_dispatched",
          "tokens_padded", "pages_gathered", "pages_live"} | set(PHASES)
CHILDREN = {"engine.admit", "engine.plan", "engine.dispatch",
            "engine.fetch", "engine.bookkeep", "engine.on_sync"}
# (k, w, t_tokens, decode_rows, prefill_rows) of every horizon of `_serve`,
# as the parent commit's schedule (ragged) and recorder ticks (multi,
# per_tick) gave them before the two records became one
WHAT_THEY_WERE = {
    "ragged": [(4, 8, 16, 0, 2), (2, 1, 2, 2, 0), (2, 1, 2, 2, 0),
               (1, 2, 4, 1, 1), (4, 1, 2, 2, 0), (1, 1, 2, 1, 0)],
    "multi": [(4, 1, None, 2, 0), (1, 1, None, 2, 0), (4, 1, None, 1, 0),
              (1, 1, None, 1, 0)],
    "per_tick": [(1, 1, None, 2, 0)] * 5 + [(1, 1, None, 1, 0)] * 5,
}


# (pages_gathered, pages_live) of the same horizons, counted by hand: two
# slots, pages of 16, prompts of 3, 29 and 2 tokens, 6 tokens an answer.
# Gathered is slots x the table's columns the attention walks: here the
# columns handed to the program (the whole table of 8 off the ragged loop,
# its live power-of-two width on it), since the walk's block of 8 columns
# covers them (a wider table: `test_pages_gathered_counts_...` below).
# Live is the host's `_lens` at dispatch: per tick the contexts are 3+i
# and 29+i (1 + 2 pages; 7 and 33 at the fifth tick: 1 + 3), then the
# third request alone (2-6 tokens: 1 page). The ragged loop dispatches
# horizon r before it books block r-1, and books a row's prompt with its
# first token: the first two horizons see nothing booked; the third the
# first block (3+3 and 29 tokens: 1 + 2); the fourth the second (row 0
# retired and given to the third request, nothing cached: 0, beside 29+2:
# 2); the fifth 0 beside 29+4 (3); the last, row 1 retired, the third
# request's 2 tokens (1) in a table 2 wide.
WHAT_PAGES = {
    "ragged": [(8, 0), (8, 0), (8, 3), (8, 2), (8, 3), (4, 1)],
    "multi": [(16, 3), (16, 3), (16, 1), (16, 1)],
    "per_tick": [(16, 3)] * 4 + [(16, 4)] + [(16, 1)] * 5,
}


@pytest.fixture(scope="module")
def tiny_model():
    paddle.seed(7)
    from paddle_tpu.distributed import build_mesh
    build_mesh(dp=1)
    model = GPT(gpt_tiny(max_seq_len=128, dtype="float32", remat=False))
    model.eval()
    return model


def _serve(model, loop, dec=None, **kw):
    dec = dec or PagedGPTDecoder(model, num_pages=48, page_size=16,
                                 max_batch=2)
    eng = ContinuousBatchingEngine(dec, max_new_tokens=6, **LOOPS[loop],
                                   **kw)
    rids = [eng.submit(np.asarray(p, np.int32)) for p in PROMPTS]
    t0 = time.perf_counter()
    out = eng.run(on_sync=lambda e: None)
    return [out[r] for r in rids], eng, time.perf_counter() - t0


def _profiled(tmp_path, fn):
    """(fn's result, [(name, start_ns, end_ns, ids)] of the host plane's
    `engine.*` and `trainer.*` spans, as `profiler.read_spans` gives them)
    under a profiler session."""
    from paddle_tpu.profiler import read_spans
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        result = fn()
    finally:
        jax.profiler.stop_trace()
    return result, [(s.pop("name"), s.pop("start_ns"), s.pop("end_ns"), s)
                    for s in read_spans(str(tmp_path))]


def _children(spans, parent):
    return [s for s in spans if s is not parent
            and parent[1] <= s[1] and s[2] <= parent[2]]


@pytest.mark.parametrize("loop", list(LOOPS))
def test_profiled_engine_nests_its_spans_under_the_round(tiny_model, loop,
                                                         tmp_path):
    """Under `jax.profiler.trace` the `.xplane.pb` holds `engine.round`
    with its six children nested and carrying one `seq`, after the
    engine's own `engine.build`; what the profiled engine serves is what
    the unprofiled one does."""
    base, _, _ = _serve(tiny_model, loop)
    (served, eng, _), spans = _profiled(
        tmp_path, lambda: _serve(tiny_model, loop))
    assert served == base
    build, = [s for s in spans if s[0] == "engine.build"]
    assert build[3]["program"] == "ContinuousBatchingEngine"
    spans = [s for s in spans if s is not build]
    rounds = [s for s in spans if s[0] == "engine.round"]
    assert build[2] <= rounds[0][1]
    assert [r[3]["seq"] for r in rounds] == list(range(1, len(rounds) + 1))
    inside = [c for r in rounds for c in _children(spans, r)]
    assert len(inside) == len(spans) - len(rounds), "a span outside a round"
    full = 0
    for r in rounds:
        kids = _children(spans, r)
        assert {k[0] for k in kids} <= CHILDREN
        assert all(k[3]["seq"] == r[3]["seq"] for k in kids), (r, kids)
        full += {k[0] for k in kids} == CHILDREN
    assert full >= 2, "no round shows all six phases"
    records = {ev["seq"]: ev for ev in eng.serve_schedule()
               if ev["kind"] == "horizon"}
    for name, _, _, stats in spans:
        if name == "engine.dispatch":
            # the round that dispatched a horizon gave it its seq, and
            # span, record and jitted program carry one name
            ev = records[stats["seq"]]
            assert stats["program"] == ev["program"]
            assert ev["program"].startswith({
                "ragged": f"packed_multi_k{ev['k']}_t{ev['t_tokens']}_w",
                "multi": f"decode_multi_k{ev['k']}",
                "per_tick": "decode_step"}[loop])
        if name in ("engine.fetch", "engine.bookkeep", "engine.on_sync"):
            # what they process is the horizon of an earlier round (the
            # pipelined loops: the one before) or of this one (per tick)
            assert stats["horizon"] in records
            assert stats["horizon"] == stats["seq"] - (loop != "per_tick")


@pytest.mark.parametrize("loop", list(LOOPS))
def test_one_record_a_horizon(tiny_model, loop):
    """The record is always on, its phase times add up to the loop's wall
    time, `k`, `w`, `t_tokens`, `decode_rows`, `prefill_rows` are what the
    parent's two records said, and with a recorder attached the tick IS
    that dict with the price added."""
    rec = FlightRecorder()
    plain, eng0, wall = _serve(tiny_model, loop)
    traced, eng, _ = _serve(tiny_model, loop, trace=rec)
    assert plain == traced
    for e in (eng0, eng):
        hz = [ev for ev in e.serve_schedule() if ev["kind"] == "horizon"]
        assert [(ev["k"], ev["w"], ev["t_tokens"], ev["decode_rows"],
                 ev["prefill_rows"]) for ev in hz] == WHAT_THEY_WERE[loop]
    hz = [ev for ev in eng0.serve_schedule() if ev["kind"] == "horizon"]
    assert "predicted_s" not in hz[0] and eng0.trace is None
    # every second of the run loop is in one phase of one record: only
    # the loop's own few lines between the phases are not
    phases = sum(ev[p] for ev in hz for p in PHASES)
    assert phases <= wall and phases == pytest.approx(wall, rel=0.05)
    for ev in hz:
        # nothing is stamped that nothing reads
        assert set(ev) == FIELDS
        assert ev["t_round"] + ev["admit_s"] + ev["plan_s"] \
            + ev["dispatch_s"] <= ev["t_fetched"]
        assert ev["slots"] == 2 and ev["queue_depth"] >= 0
        assert ev["program"] in {fn.__name__ for memo in (
            eng0.d._packeds, eng0.d._multis) for fn in memo.values()} \
            | {eng0.d._decode.__name__}
        assert 0 <= ev["tokens_padded"] <= ev["tokens_dispatched"]
        assert all(p >= 0.0 for p in (ev[k] for k in PHASES))
    assert sorted(w for ev in hz for w in ev["admit_waits_s"]) == \
        sorted(eng0.stats.queue_wait_s)
    assert sum(ev["tokens"] for ev in hz) == \
        sum(map(len, plain)) - (0 if loop == "ragged" else len(PROMPTS))
    assert [(ev["pages_gathered"], ev["pages_live"]) for ev in hz] == \
        WHAT_PAGES[loop]
    assert [ev["seq"] for ev in hz] == sorted({ev["seq"] for ev in hz})
    # the recorder's tick is the same dict, the price fields added
    ticks = [ev for ev in rec.events if ev["kind"] == "horizon"]
    mine = [ev for ev in eng.serve_schedule() if ev["kind"] == "horizon"]
    assert len(ticks) == len(mine) and all(a is b
                                           for a, b in zip(ticks, mine))
    assert all({"track", "shape", "measured_s", "pool"} <= set(ev)
               for ev in ticks)
    # lifecycle events carry the seq of the round they happened in
    seqs = {ev["seq"] for ev in mine}
    for ev in rec.events:
        if ev["kind"] in ("admit", "first_token", "retire"):
            assert 1 <= ev["seq"] <= eng._seq
        if ev["kind"] == "admit":
            assert ev["seq"] in seqs


@pytest.mark.parametrize("loop", list(LOOPS))
def test_compiles_counts_what_a_round_made(tiny_model, loop):
    """`compiles` counts the lowerings, compiles and cache loads of the
    compile log that ended inside the horizon's round: at least one on
    each program's first horizon on a fresh decoder, none once the same
    traffic finds every program made."""
    dec = PagedGPTDecoder(tiny_model, num_pages=48, page_size=16,
                          max_batch=2)
    runs = []
    for _ in range(2):
        _, eng, _ = _serve(tiny_model, loop, dec=dec)
        runs.append([(ev["program"], ev["compiles"])
                     for ev in eng.serve_schedule()
                     if ev["kind"] == "horizon"])
    first, again = runs
    programs = {p for p, _ in first}
    assert len(programs) >= (1 if loop == "per_tick" else 2)
    for p in programs:
        counts = [n for q, n in first if q == p]
        assert counts[0] >= 1, (p, counts)
    assert [n for _, n in again] == [0] * len(again)
    assert {p for p, _ in again} == programs


def test_compiles_sees_a_second_signature_under_one_name(tiny_model):
    """A program made again under a name it already dispatched (a weak-
    typed scalar where a typed one was: a second signature, as a carry
    that is committed once and uncommitted once makes) reads `compiles`
    at least 1; the memo of names dispatched so far read it as warm."""
    dec = PagedGPTDecoder(tiny_model, num_pages=48, page_size=16,
                          max_batch=2)
    plain, eng, _ = _serve(tiny_model, "ragged", dec=dec)
    warm = {ev["program"] for ev in eng.serve_schedule()}

    def weak(fn):
        return lambda *args: fn(*args[:-1], int(args[-1]))

    for key, fn in list(dec._packeds.items()):
        dec._packeds[key] = weak(fn)
    served, eng, _ = _serve(tiny_model, "ragged", dec=dec)
    assert served == plain
    hz = [ev for ev in eng.serve_schedule() if ev["kind"] == "horizon"]
    assert {ev["program"] for ev in hz} <= warm
    assert hz[0]["compiles"] >= 1
    from paddle_tpu.profiler import compile_log
    made = {e["program"] for e in compile_log(since=hz[0]["t_round"])[0]
            if e["phase"] == "compile" or e["phase"] == "load"}
    assert hz[0]["program"] in made


def test_serving_report_speaks_by_program(tiny_model):
    from paddle_tpu import debug
    _, eng, _ = _serve(tiny_model, "ragged")
    entry, = [e for e in debug.serving_report()
              if e["stats"]["engine_id"] == eng.stats.engine_id]
    hz = eng.serve_schedule()
    assert [p["program"] for p in entry["programs"]] == \
        sorted({ev["program"] for ev in hz})
    assert sum(p["n"] for p in entry["programs"]) == len(hz)
    assert all(p["tick_ms_p50"] > 0 for p in entry["programs"])
    # a fresh decoder made each program: the compile log has its seconds
    assert all(p["compile_load_s"] > 0 for p in entry["programs"])
    assert entry["schedule"]["round_host_ms_p50"] > 0


def test_serving_report_reads_queue_and_pad_off_the_records(tiny_model):
    """No recorder: the queue an admission pass left behind, the
    submit-to-admit waits and the pad ledger come from the engine's own
    always-on records."""
    from paddle_tpu import debug
    _, eng, _ = _serve(tiny_model, "ragged")
    assert eng.trace is None
    entry, = [e for e in debug.serving_report()
              if e["stats"]["engine_id"] == eng.stats.engine_id]
    hz = eng.serve_schedule()
    # three prompts to two slots: the third waits in the queue
    assert entry["schedule"]["queue_depth_max"] == 1 == \
        max(ev["queue_depth"] for ev in hz)
    waits = sorted(w for ev in hz for w in ev["admit_waits_s"])
    assert len(waits) == len(PROMPTS)
    assert entry["schedule"]["queue_wait_ms_p50"] == \
        pytest.approx(1e3 * waits[1])
    assert entry["pad"]["tokens_dispatched"] == \
        sum(ev["tokens_dispatched"] for ev in hz) == \
        eng.stats.tokens_dispatched
    assert entry["pad"]["tokens_padded"] == eng.stats.tokens_padded


def test_a_program_name_is_made_once(tiny_model):
    """One key, one name: the round looks the name up (`lru_cache`), the
    record, the span and the compile log share the very string."""
    name = PagedGPTDecoder.program_name("packed", 2, 256, 64, 128)
    assert name == "packed_multi_k2_t256_w128_p64"
    assert PagedGPTDecoder.program_name("packed", 2, 256, 64, 128) is name
    assert PagedGPTDecoder.program_name("decode", 4, 1, 8) == \
        "decode_multi_k4"
    assert PagedGPTDecoder.program_name("tick", 1, 1, 8) == "decode_step"


def test_every_serving_program_carries_its_key(tiny_model):
    """The "XLA Modules" line of a trace tells the programs apart: each
    is jitted under a name made of its key."""
    _, eng, _ = _serve(tiny_model, "ragged")
    dec = eng.d
    assert dec._packeds
    for (k, t, window, width), fn in dec._packeds.items():
        assert fn.__name__ == f"packed_multi_k{k}_t{t}_w{window}_p{width}"
        assert fn.__name__ == dec.program_name("packed", k, t, width,
                                               window)
    _, eng, _ = _serve(tiny_model, "multi")
    assert {fn.__name__ for fn in eng.d._multis.values()} == \
        {"decode_multi_k4", "decode_multi_k1"}
    assert eng.d._decode.__name__ == "decode_step"
    (k, t, window, width), fn = next(iter(dec._packeds.items()))
    module = fn.lower(*_packed_args(dec, t, width)).as_text().split(
        "\n", 1)[0]
    assert f"@jit_packed_multi_k{k}_t{t}_w{window}_p{width}" in module


def _packed_args(dec, t, width):
    import jax.numpy as jnp
    S = dec.max_batch
    return (dec._w(), dec.k_pages, dec.v_pages, jnp.zeros(S, jnp.int32),
            jnp.zeros(S, jnp.int32), jnp.zeros((S, width), jnp.int32),
            jnp.arange(S, dtype=jnp.int32), jnp.zeros(S, bool),
            jnp.full(S, 2, jnp.int32), jnp.asarray(-1, jnp.int32),
            jnp.zeros((S, dec.pend_capacity), jnp.int32),
            jnp.zeros(S, jnp.int32), jnp.asarray(8, jnp.int32))


def test_every_pallas_call_carries_a_name():
    calls = []
    for path in sorted(glob.glob(os.path.join(REPO, "paddle_tpu", "ops",
                                              "*.py"))):
        for node in ast.walk(ast.parse(open(path).read())):
            if isinstance(node, ast.Call) and \
                    getattr(node.func, "attr", "") == "pallas_call":
                name = [kw.value.value for kw in node.keywords
                        if kw.arg == "name"]
                assert name, f"{path}:{node.lineno} has no name="
                calls += name
    assert len(calls) >= 14 and len(set(calls)) == len(calls)


def test_serving_program_names_its_parts(tiny_model):
    """`jax.named_scope` around the page-table gather, the attention over
    the gathered pages, the KV write and the layer loop."""
    dec = PagedGPTDecoder(tiny_model, num_pages=48, page_size=16,
                          max_batch=2)
    fn = jax.jit(lambda *a: dec._packed_multi_step(*a, k=2, t=16))
    text = fn.lower(*_packed_args(dec, 16, dec.max_pages)).as_text(
        debug_info=True)
    for scope in ("paged_gather", "paged_attention", "kv_write", "layers"):
        assert scope in text, scope


@pytest.fixture(scope="module")
def mla_decoder():
    from paddle_tpu.models.deepseek_v2 import DeepSeekV2, deepseek_v2_tiny
    from paddle_tpu.serving.mla_decoder import PagedMLADecoder
    model = DeepSeekV2(deepseek_v2_tiny(experts_held=4, expert_offset=4))
    return PagedMLADecoder(model, num_pages=2 * 8 + 2, page_size=8,
                           max_batch=2, max_pages_per_seq=8)


def test_the_latent_decoders_record_adds_its_four_counters(mla_decoder):
    """The MLA decoder's horizons carry the GPT record's fields and the
    four counters that ride its block (`horizon_counters`), and nothing
    else; the GPT decoder's record has none of them (FIELDS above)."""
    eng = ContinuousBatchingEngine(mla_decoder, max_new_tokens=4,
                                   chunk_tokens=8)
    for p in PROMPTS:
        eng.submit(p)
    eng.run()
    hz = eng.serve_schedule()
    assert mla_decoder.horizon_counters == (
        "expert_assignments", "experts_hit", "absorbed_rows",
        "materialised_tokens")
    assert PagedGPTDecoder.horizon_counters == ()
    for ev in hz:
        assert set(ev) == FIELDS | set(mla_decoder.horizon_counters)
        assert ev["program"].startswith("mla_packed_multi_k")
        assert all(isinstance(ev[c], int) and ev[c] >= 0
                   for c in mla_decoder.horizon_counters)
    assert sum(ev["materialised_tokens"] for ev in hz) == \
        sum(map(len, PROMPTS))
    assert sum(ev["absorbed_rows"] for ev in hz) == 3 * len(PROMPTS)


# `pages_gathered` where the table is wider than the walk: two slots, pages
# of 4, 32 columns a row, prompts of 70 and 3 tokens, 6 tokens an answer.
# (table width, pages_gathered) of every horizon, counted by hand. On the
# ragged loop the long prompt goes in chunks of 8: after the first horizon
# (k=4) the bound on its position is 32 + 4 + 1 = 37 (10 pages and one:
# 11 columns needed, a table of 16), after the second (k=2) 51 (14: 16),
# after the third 67 (18: a table of 32), then 72 and, decoding, 77-78
# (19-21: 32). `PagedGPTDecoder`'s walk goes in blocks of 8 columns as far
# as the bound: 16, 16, then 24 of the 32; `PagedMLADecoder` copies every
# column it is handed. The loops with a blocking prefill hand over the
# whole table of 32 and walk 24 of it (contexts of 70-76 tokens).
WIDE_PROMPTS = (list(range(1, 71)), [1, 2, 3])
WHAT_IS_WALKED = {
    ("gpt", "ragged"): [(16, 32), (16, 32)] + [(32, 48)] * 4,
    ("mla", "ragged"): [(16, 32), (16, 32)] + [(32, 64)] * 4,
    ("gpt", "multi"): [(32, 48)] * 2,
    ("gpt", "per_tick"): [(32, 48)] * 5,
}


@pytest.mark.parametrize("which,loop", list(WHAT_IS_WALKED))
def test_pages_gathered_counts_what_the_walk_copies(tiny_model, which, loop):
    if which == "gpt":
        dec = PagedGPTDecoder(tiny_model, num_pages=80, page_size=4,
                              max_batch=2)
    else:
        from paddle_tpu.models.deepseek_v2 import (DeepSeekV2,
                                                   deepseek_v2_tiny)
        from paddle_tpu.serving.mla_decoder import PagedMLADecoder
        dec = PagedMLADecoder(
            DeepSeekV2(deepseek_v2_tiny(experts_held=4, expert_offset=4)),
            num_pages=2 * 32 + 2, page_size=4, max_batch=2,
            max_pages_per_seq=32)
    assert dec.max_pages == 32
    assert dec.walk_block_pages == (8 if which == "gpt" else None)
    eng = ContinuousBatchingEngine(dec, max_new_tokens=6, **LOOPS[loop])
    for p in WIDE_PROMPTS:
        eng.submit(np.asarray(p, np.int32))
    eng.run()
    hz = [ev for ev in eng.serve_schedule() if ev["kind"] == "horizon"]
    widths = [int(ev["program"].rsplit("_p", 1)[1]) if loop == "ragged"
              else dec.max_pages for ev in hz]
    assert list(zip(widths, (ev["pages_gathered"] for ev in hz))) == \
        WHAT_IS_WALKED[which, loop]
    assert all(0 <= ev["pages_live"] <= ev["pages_gathered"] for ev in hz)


def test_the_latent_program_names_its_parts_and_its_kind(mla_decoder):
    import jax.numpy as jnp
    dec, S = mla_decoder, 2
    fn = dec._packeds and next(iter(dec._packeds.values()))
    name = dec.program_name("packed", 2, 16, 8, 8)
    assert name == "mla_packed_multi_k2_t16_w8_p8"
    text = jax.jit(lambda *a: dec._packed_multi_step(
        *a, k=2, t=16, window=8)).lower(
        dec.weights, dec.cache, jnp.zeros(S, jnp.int32),
        jnp.zeros(S, jnp.int32), jnp.zeros((S, 8), jnp.int32),
        jnp.zeros(S, bool), jnp.full(S, 2, jnp.int32),
        jnp.asarray(-1, jnp.int32),
        jnp.zeros((S, dec.pend_capacity), jnp.int32),
        jnp.zeros(S, jnp.int32), jnp.asarray(8, jnp.int32)).as_text(
        debug_info=True)
    for scope in ("layers", "mla_q", "latent_write", "paged_gather",
                  "mla_absorbed", "mla_materialised", "moe_router",
                  "moe_experts", "moe_shared", "lm_head"):
        assert scope in text, scope
    assert fn is None or fn.__name__.startswith("mla_packed_multi_")


@pytest.fixture(scope="module")
def double_layer_decoder():
    from paddle_tpu.models.longcat_flash import (LongCatFlash,
                                                 longcat_flash_tiny)
    from paddle_tpu.serving.mla_decoder import PagedMLADecoder
    model = LongCatFlash(longcat_flash_tiny(experts_held=4, expert_offset=2))
    return PagedMLADecoder(model, num_pages=2 * 8 + 2, page_size=8,
                           max_batch=2, max_pages_per_seq=8)


def test_the_double_layer_familys_record_adds_its_five_counters(
        double_layer_decoder):
    """The same decoder over the LongCat-Flash family: the record's
    fields, the latent decoder's four counters and `zero_assignments`
    (the identity pairs selected), and nothing else. Every real token
    selects 3 columns in each of 2 layers: the held experts' pairs and
    the identity pairs together are at most that."""
    dec = double_layer_decoder
    eng = ContinuousBatchingEngine(dec, max_new_tokens=4, chunk_tokens=8)
    for p in PROMPTS:
        eng.submit(p)
    eng.run()
    hz = eng.serve_schedule()
    assert dec.horizon_counters == (
        "expert_assignments", "experts_hit", "zero_assignments",
        "absorbed_rows", "materialised_tokens")
    for ev in hz:
        assert set(ev) == FIELDS | set(dec.horizon_counters)
        assert ev["program"].startswith("mla_packed_multi_k")
        assert all(isinstance(ev[c], int) and ev[c] >= 0
                   for c in dec.horizon_counters)
        real = ev["tokens_dispatched"] - ev["tokens_padded"]
        assert ev["expert_assignments"] + ev["zero_assignments"] \
            <= real * 3 * 2
        assert ev["experts_hit"] <= 4 * 2 * ev["k"]
    assert sum(ev["zero_assignments"] for ev in hz) > 0
    assert sum(ev["materialised_tokens"] for ev in hz) == \
        sum(map(len, PROMPTS))
    assert sum(ev["absorbed_rows"] for ev in hz) == 3 * len(PROMPTS)


def test_the_double_layer_program_names_its_parts(double_layer_decoder):
    import jax.numpy as jnp
    dec, S = double_layer_decoder, 2
    text = jax.jit(lambda *a: dec._packed_multi_step(
        *a, k=2, t=16, window=8)).lower(
        dec.weights, dec.cache, jnp.zeros(S, jnp.int32),
        jnp.zeros(S, jnp.int32), jnp.zeros((S, 8), jnp.int32),
        jnp.zeros(S, bool), jnp.full(S, 2, jnp.int32),
        jnp.asarray(-1, jnp.int32),
        jnp.zeros((S, dec.pend_capacity), jnp.int32),
        jnp.zeros(S, jnp.int32), jnp.asarray(8, jnp.int32)).as_text(
        debug_info=True)
    for scope in ("layers", "attn0", "attn1", "mla_q", "latent_write",
                  "paged_gather", "mla_absorbed", "mla_materialised",
                  "mlp0", "mlp1", "moe_router", "moe_experts", "moe_zero",
                  "shortcut_join", "lm_head"):
        assert scope in text, scope
    assert "moe_shared" not in text


@pytest.fixture(scope="module")
def conv_attention_decoder():
    from paddle_tpu.models.lfm2_moe import Lfm2Moe, lfm2_moe_tiny
    from paddle_tpu.serving.mla_decoder import PagedMLADecoder
    model = Lfm2Moe(lfm2_moe_tiny(experts_held=4, expert_offset=2))
    return PagedMLADecoder(model, num_pages=2 * 8 + 2, page_size=8,
                           max_batch=2, max_pages_per_seq=8)


def test_the_conv_attention_familys_record_adds_its_four_counters(
        conv_attention_decoder):
    """The same decoder over the LFM2-MoE family (a conv dense layer, then
    attention and conv expert layers): the record's fields and the four
    counters, the held experts' under the names the latent families use,
    and nothing else. Every real token selects 3 experts in each of 4
    expert layers; of them the 4 held (2-5) take at most all."""
    dec = conv_attention_decoder
    eng = ContinuousBatchingEngine(dec, max_new_tokens=4, chunk_tokens=8)
    for p in PROMPTS:
        eng.submit(p)
    eng.run()
    hz = eng.serve_schedule()
    assert dec.horizon_counters == (
        "expert_assignments", "experts_hit", "absorbed_rows",
        "materialised_tokens")
    for ev in hz:
        assert set(ev) == FIELDS | set(dec.horizon_counters)
        assert ev["program"].startswith("mla_packed_multi_k")
        assert all(isinstance(ev[c], int) and ev[c] >= 0
                   for c in dec.horizon_counters)
        real = ev["tokens_dispatched"] - ev["tokens_padded"]
        assert ev["expert_assignments"] <= real * 3 * 4
        assert ev["experts_hit"] <= 4 * 4 * ev["k"]
    assert sum(ev["expert_assignments"] for ev in hz) > 0
    assert sum(ev["materialised_tokens"] for ev in hz) == \
        sum(map(len, PROMPTS))
    assert sum(ev["absorbed_rows"] for ev in hz) == 3 * len(PROMPTS)


def test_the_conv_attention_program_names_its_parts(conv_attention_decoder):
    import jax.numpy as jnp
    dec, S = conv_attention_decoder, 2
    text = jax.jit(lambda *a: dec._packed_multi_step(
        *a, k=2, t=16, window=8)).lower(
        dec.weights, dec.cache, jnp.zeros(S, jnp.int32),
        jnp.zeros(S, jnp.int32), jnp.zeros((S, 8), jnp.int32),
        jnp.zeros(S, bool), jnp.full(S, 2, jnp.int32),
        jnp.asarray(-1, jnp.int32),
        jnp.zeros((S, dec.pend_capacity), jnp.int32),
        jnp.zeros(S, jnp.int32), jnp.asarray(8, jnp.int32)).as_text(
        debug_info=True)
    for scope in ("layers", "short_conv", "conv_state", "gqa_attention",
                  "qk_norm", "kv_write", "paged_gather", "paged_attention",
                  "mlp", "moe_router", "moe_experts", "lm_head"):
        assert scope in text, scope
    for scope in ("mla_q", "latent_write", "mla_absorbed", "moe_shared"):
        assert scope not in text, scope


def _lowered_step(model, loss_fn, batch):
    from paddle_tpu.distributed import Trainer, build_mesh
    build_mesh(dp=1)
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=model.parameters())
    return Trainer(model, opt, loss_fn).lower_step(batch).as_text(
        debug_info=True)


def test_gpt_train_step_names_its_layers():
    from paddle_tpu.models import GPTPretrainingCriterion
    paddle.seed(3)
    model = GPT(gpt_tiny(max_seq_len=32, dtype="float32", remat=False))
    crit = GPTPretrainingCriterion()
    ids = np.arange(64, dtype=np.int32).reshape(2, 32) % 1000
    text = _lowered_step(
        model, lambda m, b: crit(m(paddle.to_tensor(b["x"])),
                                 paddle.to_tensor(b["y"])),
        {"x": ids, "y": ids})
    for scope in ("attention", "mlp", "layer_norm", "lm_head", "loss"):
        assert scope in text, scope


def test_bert_train_step_names_its_layers():
    from paddle_tpu.models.bert import (BertForPretraining,
                                        BertPretrainingCriterion, bert_tiny)
    paddle.seed(3)
    cfg = bert_tiny()
    model = BertForPretraining(cfg)
    crit = BertPretrainingCriterion(cfg.vocab_size)
    ids = np.arange(32, dtype=np.int32).reshape(2, 16) % cfg.vocab_size

    def loss_fn(m, b):
        mlm, nsp = m(paddle.to_tensor(b["x"]))
        return crit(mlm, nsp, paddle.to_tensor(b["x"]),
                    paddle.to_tensor(b["n"]))

    text = _lowered_step(model, loss_fn,
                         {"x": ids, "n": np.zeros(2, np.int32)})
    for scope in ("attention", "mlp", "layer_norm", "mlm_head", "nsp_head",
                  "loss"):
        assert scope in text, scope


def test_trainer_step_has_its_spans_and_its_tick(tmp_path):
    """`Trainer.step`, the entry point the training cells drive: under a
    profiler session `trainer.step` > `trainer.place_batch`,
    `trainer.dispatch` with one `step`; with a recorder the tick hook
    `step_multi` has; without one a dead branch."""
    from paddle_tpu.distributed import Trainer, build_mesh
    build_mesh(dp=1)

    def make():
        paddle.seed(0)
        net = paddle.nn.Sequential(paddle.nn.Linear(8, 16),
                                   paddle.nn.ReLU(),
                                   paddle.nn.Linear(16, 4))
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=net.parameters())
        return Trainer(net, opt, lambda m, b: (
            (m(paddle.to_tensor(b["x"])) - paddle.to_tensor(b["y"])) ** 2
        ).mean())

    rng = np.random.RandomState(0)
    batch = {"x": rng.randn(4, 8).astype(np.float32),
             "y": rng.randn(4, 4).astype(np.float32)}
    before = FlightRecorder.total_events
    bare = make()
    losses = [float(bare.step(batch)) for _ in range(3)]
    assert FlightRecorder.total_events == before
    tr = make()
    rec = tr.attach_recorder(True, predicted_step_s=1e-3)
    got, spans = _profiled(
        tmp_path, lambda: [float(tr.step(batch)) for _ in range(3)])
    assert got == losses
    steps = [s for s in spans if s[0] == "trainer.step"]
    assert [s[3]["step"] for s in steps] == [0, 1, 2]
    for s in steps:
        kids = _children(spans, s)
        assert [k[0] for k in kids] == ["trainer.place_batch",
                                        "trainer.dispatch"]
        assert all(k[3]["step"] == s[3]["step"] for k in kids)
    ticks = [ev for ev in rec.events if ev["kind"] == "tick"]
    assert [ev["shape"] for ev in ticks] == [["step", 1]] * 3
    assert all(ev["track"] == "train" and ev["measured_s"] > 0
               and ev["predicted_s"] == pytest.approx(1e-3) for ev in ticks)
    # the first step compiles and has no dispatch before it: not steady
    assert rec.drift_report()[0]["n"] == 2
    # a fused horizon is the same span with the same children
    _, spans = _profiled(tmp_path / "multi",
                         lambda: tr.step_multi([batch] * 2))
    assert [s[0] for s in spans] == ["trainer.step", "trainer.place_batch",
                                     "trainer.dispatch"]
    assert {s[3]["step"] for s in spans} == {3}


def test_record_event_is_on_the_profilers_timeline(tmp_path):
    """A user's region around eager code is in the trace: `RecordEvent`
    enters a span (a `named_scope` alone marks nothing on the host)."""
    from jax.profiler import ProfileData
    from paddle_tpu.profiler import RecordEvent
    jax.profiler.start_trace(str(tmp_path))
    with RecordEvent("my_region"):
        paddle.randn([4, 4]).sum()
    jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    names = {ev.name for plane in ProfileData.from_file(path).planes
             for line in plane.lines for ev in line.events}
    assert "my_region" in names
