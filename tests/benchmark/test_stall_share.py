"""What PR 36 left in the serve job and beside it: the three end-to-end
numbers are taken over the WHOLE window (every token over every second, the
percentile of every request and every gap: a stalled horizon is in them),
`stall_share.serve` says how much of a window such horizons were, set-up's
heap is frozen for the window and thawed after it, and the engine's own
spans name the idle gaps."""
import gc
import importlib
import json
import os

import numpy as np
import pytest

import bench_tiny
from benchmark import cells, trace
from benchmark import run as harness
from benchmark.jobs import serve_waves

CELL = "gpt_tiny.serve_tiny"
NAME = "stall_share.serve"
REAL_CELL = "gpt3_1p3b.serve_wave5_late3"


def _reader():
    return cells.Cell.reader(
        type("C", (), {"here": cells.HERE})(), NAME)


class _Run:
    def __init__(self, horizons):
        self.measured = {"horizons": horizons}


def _waves(n_waves, stalled=(), extra=0.1, every=False):
    """`n_waves` plays of one wave of six horizons (two chunk horizons, four
    decode horizons whose live pages grow), each as (seconds, event);
    `stalled`: (wave, index) pairs that take `extra` seconds more; `every`:
    index 3 of every wave does."""
    base = [0.034, 0.035, 0.0139, 0.0139, 0.0140, 0.0141]
    out = []
    for w in range(n_waves):
        for i, s in enumerate(base):
            ev = {"program": "chunk" if i < 2 else "decode", "k": 2,
                  "decode_rows": 0 if i == 0 else 5, "prefill_rows": 5 - i
                  if i < 2 else 0, "pages_live": 10 * i, "tokens": 2 * i}
            if (w, i) in stalled or (every and i == 3):
                s += extra
            out.append((s, ev))
    return out


WAVE_S = 0.034 + 0.035 + 0.0139 + 0.0139 + 0.0140 + 0.0141

SYNTHETIC = [
    # a window with no stall: nothing lies over the medians
    ("calm-3", _waves(3), 0.0),
    ("calm-29", _waves(29), 0.0),
    # one horizon of one wave 0.1 s longer: 0.1 s of the window's seconds
    ("one-stall-3", _waves(3, {(1, 3)}), 100 * 0.1 / (3 * WAVE_S + 0.1)),
    ("one-stall-29", _waves(29, {(7, 0)}), 100 * 0.1 / (29 * WAVE_S + 0.1)),
    # a stall in another place in each of three waves: each is seen
    ("three-places-29", _waves(29, {(2, 0), (9, 3), (20, 5)}),
     100 * 0.3 / (29 * WAVE_S + 0.3)),
    # the same 0.1 s in that place in EVERY wave is no stall: the work
    # takes that long, and the rate says so
    ("every-wave-29", _waves(29, every=True), 0.0),
    # no work done three times: nothing to read, never 0
    ("two-waves", _waves(2, {(1, 3)}), None),
    ("no-horizons", [], None),
    # events that are no horizon records (a program from before them)
    ("no-records", [(0.01, {"kind": "tick"})] * 9, None),
]


@pytest.mark.parametrize("horizons,expected",
                         [c[1:] for c in SYNTHETIC],
                         ids=[c[0] for c in SYNTHETIC])
def test_stall_share_is_the_seconds_over_the_same_works_median(
        horizons, expected):
    value = _reader()(_Run(horizons))
    if expected is None:
        assert value is None
    else:
        assert value == pytest.approx(expected, abs=1e-9)


def test_the_real_benchmark_lists_it_for_the_gpt_serve_cell():
    bench = cells.load_benchmark()
    entry = next(m for m in bench["per_layer"] if m["name"] == NAME)
    # field by field, its list from the GPT serve cell on: later PRs append
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        "name": NAME, "unit": "%", "better": "lower",
        "source": "host_clock", "layer": "entry points",
        "moves": "serve_tokens_per_s"}
    assert entry["workloads"][0] == REAL_CELL
    assert os.path.exists(os.path.join(cells.HERE, "metrics", NAME + ".py"))
    # the DeepSeek cell's traced window is one wave: it could read nothing
    assert NAME in [m["name"] for m in cells.Cell(REAL_CELL).per_layer()]


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """(cell, run, what the window saw of the collector) of the tiny serve
    cell after its job, `stall_share.serve` listed for it as the real
    benchmark lists it."""
    root, here = bench_tiny.make_root(tmp_path_factory.mktemp("stall"))
    real = {m["name"]: m for m in cells.load_benchmark()["per_layer"]}
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["per_layer"].append(dict(real[NAME], workloads=[CELL]))
    with open(path, "w") as f:
        json.dump(bench, f)
    cell = cells.Cell(CELL, root=root, here=here)
    seen = []

    def look(tokens):              # called for every answer of the window
        seen.append((gc.isenabled(), gc.get_freeze_count()))
        return tokens

    run = harness.Run(cell, bench_tiny.SEED, 0.5, 0,
                      {"bf16_flops": float("nan"),
                       "hbm_bytes_per_s": float("nan")},
                      faults={"alter": look})
    before = gc.get_freeze_count()
    importlib.import_module(f"benchmark.jobs.{cell.job['job']}").run(run)
    assert run.correct
    return cell, run, seen, before


def test_the_window_runs_with_set_ups_heap_frozen_and_the_collector_on(
        served):
    _, _, seen, before = served
    # `before`: what tests/conftest.py had frozen already, for the same
    # reason; the job thaws all of it with its own once the window closed
    assert seen and all(on and frozen > before + 10_000
                        for on, frozen in seen)
    assert gc.get_freeze_count() < 10_000 and gc.isenabled()


def test_the_tiny_cell_reads_it_and_a_short_window_nothing(served):
    """On the records of the engine itself: waves of the tiny cell group
    by their work into places done once a wave, so two waves read nothing
    and three or more a share of the window (how many a busy CPU plays in
    half a second is not this test's to say: the events stand in thrice)."""
    cell, run, _, _ = served
    assert NAME in [m["name"] for m in cell.per_layer()]
    events, waves = run.measured["horizons"], run.measured["waves"]
    value = cell.reader(NAME)(run)
    assert (value is not None) or waves < 3
    per_wave = len(events) // waves
    assert cell.reader(NAME)(_Run(events[:min(2, waves) * per_wave])) is None
    thrice = cell.reader(NAME)(_Run(events * 3))
    assert thrice is not None and -5.0 < thrice < 90.0


E2E = ["serve_tokens_per_s", "ttft_ms_p75", "itl_ms_p99"]


@pytest.mark.parametrize("name", E2E)
def test_end_to_end_numbers_are_over_the_whole_window(served, name):
    """All the work over all the time, the percentile of all requests and
    of all gaps: worked out again here from what the job kept."""
    _, run, _, _ = served
    m = run.measured
    tokens = sum(ev["tokens"] for _, ev in m["horizons"])
    seconds = sum(s for s, _ in m["horizons"])
    if name == "serve_tokens_per_s":
        assert run.e2e[name] == pytest.approx(tokens / m["serve_seconds"])
        # every horizon lies inside the seconds the tokens are held to
        assert 0 < seconds <= m["serve_seconds"]
    elif name == "ttft_ms_p75":
        assert m["requests"] == run.attempted
        assert 0 < run.e2e[name] < 1e3 * m["serve_seconds"]
    else:
        assert m["n_gaps"] == tokens - m["requests"]
        assert 0 < run.e2e[name] < 1e3 * m["serve_seconds"]


STALL = 0.1


def _window(stalled_gap=None, stalled_first=False):
    """A log written by hand: 29 waves of the GPT cell's 8 requests (first
    tokens at 34 ms for four, 55 ms for one, 91 ms for three; 63 gaps each:
    17.5 ms in two mixed horizons, 6.95 ms else). In wave 7, 0.1 s more:
    `stalled_gap`: in that gap of every request; `stalled_first`: before
    the first token of the four that come first."""
    log = serve_waves.WaveLog()
    t, tokens = 0.0, 0
    for w in range(29):
        ends = []
        for rid, first in enumerate([0.034] * 4 + [0.055] + [0.091] * 3):
            log.sent[(w, rid)] = t
            at = t + first + STALL * (stalled_first and w == 7)
            ds = [(at, 1)]
            for g in range(63):
                at += 0.0175 if g in (10, 11) else 0.00695
                if w == 7 and g == stalled_gap:
                    at += STALL
                ds.append((at, 1))
            log.deliveries[(w, rid)] = ds
            tokens += 64
            ends.append(at)
        t = max(ends)
    ttft, gaps = serve_waves.latencies(log)
    pct = {q: 1e3 * float(np.percentile(ttft, q)) for q in (50, 75)}
    return {"serve_tokens_per_s": tokens / t, "seconds": t,
            "ttft_ms_p50": pct[50], "ttft_ms_p75": pct[75],
            "itl_ms_p99": 1e3 * float(np.percentile(gaps, 99)),
            "itl_ms_max": 1e3 * max(gaps)}


@pytest.mark.parametrize("name", ["serve_tokens_per_s", "itl_ms_p99",
                                  "ttft_ms_p75", "ttft_ms_p50"])
def test_a_pause_is_in_the_whole_windows_numbers(name):
    """One pause of 0.1 s in one wave: the rate loses exactly that 0.1 s;
    the stalled gaps are among all gaps (the largest is one of them) and
    the 99th percentile, 146 gaps from the top, stays inside the mixed
    horizons' cluster; before the first tokens of one wave's first four it
    leaves the 75th percentile first token inside the late joiners' cluster
    and throws the median, which sat between two clusters, from one side
    to the other: why the benchmark lists the 75th."""
    calm = _window()
    if name == "serve_tokens_per_s":
        paused = _window(stalled_gap=40)
        assert paused["seconds"] == pytest.approx(calm["seconds"] + STALL)
        assert paused[name] == pytest.approx(
            calm[name] * calm["seconds"] / (calm["seconds"] + STALL))
    elif name == "itl_ms_p99":
        paused = _window(stalled_gap=40)
        assert calm["itl_ms_max"] == pytest.approx(17.5)
        assert paused["itl_ms_max"] == pytest.approx(106.95)
        assert paused[name] == calm[name] == pytest.approx(17.5)
    elif name == "ttft_ms_p75":
        paused = _window(stalled_first=True)
        assert paused[name] == calm[name] == pytest.approx(91.0)
    else:
        paused = _window(stalled_first=True)
        assert calm[name] == pytest.approx((34.0 + 55.0) / 2)
        assert paused[name] == pytest.approx(55.0)


def test_the_witness_notes_a_pause_and_sets_places_it_in_a_window(tmp_path):
    """`rehearse.witness` beside a set of runs: a process that never
    touches the program notes when it woke late; `rehearse.sets` counts
    the pauses that fell into a run's window, which it places by the
    line's own `setup_s` and `phases`."""
    import signal
    import subprocess
    import sys
    import time

    from benchmark.rehearse import sets

    out, stop = str(tmp_path / "seen.jsonl"), str(tmp_path / "stop")
    p = subprocess.Popen([sys.executable, "-m", "benchmark.rehearse.witness",
                          out, stop], cwd=bench_tiny.REPO)
    time.sleep(0.5)
    t_a = time.perf_counter()
    p.send_signal(signal.SIGSTOP)          # the pause, of this one process
    time.sleep(0.2)
    p.send_signal(signal.SIGCONT)
    time.sleep(0.5)
    open(stop, "w").close()
    p.wait(timeout=20)
    with open(out) as f:
        gaps = [json.loads(line) for line in f]
    long = [(t, g) for t, g in gaps if g >= sets.PAUSE_S]
    assert len(long) == 1
    assert long[0][0] == pytest.approx(t_a, abs=0.05)
    assert 0.19 < long[0][1] < 0.5
    line = {"metrics": {"setup_s": {"value": 40.0}},
            "phases": [["device_ready", 20.0], ["window_closed", 61.5]]}
    assert sets.window_of(line, 100.0) == (140.0, 161.5)
    assert sets.window_of(dict(line, setup_s=41.0), 100.0) == (141.0, 161.5)
    assert sets.window_of(None, 100.0) == (100.0, float("inf"))


def test_the_engines_spans_name_the_idle_gaps():
    """`SPANS` holds the engine's own names, so a gap inside `engine_run`
    is named by the phase it sat in."""
    for name in ("engine.round", "engine.admit", "engine.plan",
                 "engine.dispatch", "engine.fetch", "engine.bookkeep",
                 "engine.on_sync"):
        assert name in harness.SPANS
    ms = 1_000_000
    ops = [(0, 10 * ms, "fusion", "fusion"), (15 * ms, 20 * ms, "copy", "c")]
    spans = [(0, 30 * ms, "engine_run"), (9 * ms, 21 * ms, "engine.round"),
             (10 * ms, 16 * ms, "engine.fetch")]
    assert dict(trace.idle_gaps(ops, spans)) == {
        "engine.fetch": pytest.approx(0.005)}
