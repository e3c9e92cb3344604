"""The four serving metrics that read the engine's own horizon records
(`benchmark/records.py`, `benchmark/metrics/*.serve.py`): on the tiny serve
cell on the CPU, with the entries added to an own copy of the tiny
BENCHMARK.json; and on events that are no such records, as a program from
before the record gives them, where each reader returns nothing."""
import importlib
import json
import os

import pytest

import bench_tiny
from benchmark import cells, records
from benchmark import run as harness

CELL = "gpt_tiny.serve_tiny"
FOUR = {"decode_tick_ms_p50.serve": "serve_tokens_per_s",
        "sched_round_ms_p50.serve": "serve_tokens_per_s",
        "device_wait_share.serve": "serve_tokens_per_s",
        "slot_occupancy.serve": "serve_tokens_per_s"}


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """(cell, run) of the tiny serve cell after its job, the four metrics
    listed for it in the copy's BENCHMARK.json as the real one lists them."""
    root, here = bench_tiny.make_root(tmp_path_factory.mktemp("records"))
    real = {m["name"]: m for m in cells.load_benchmark()["per_layer"]}
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    for name in FOUR:
        bench["per_layer"].append(dict(real[name], workloads=[CELL]))
    with open(path, "w") as f:
        json.dump(bench, f)
    cell = cells.Cell(CELL, root=root, here=here)
    run = harness.Run(cell, bench_tiny.SEED, 0.5, 0,
                      {"bf16_flops": float("nan"),
                       "hbm_bytes_per_s": float("nan")})
    importlib.import_module(f"benchmark.jobs.{cell.job['job']}").run(run)
    assert run.correct
    return cell, run


def test_the_real_benchmark_lists_the_four_for_the_serve_cell_alone():
    """Found by name: the driver makes every PR put its new entries at the
    end of `per_layer`, so no entry keeps a place there. PR 31 listed the
    four for its own serve cell too."""
    real = {m["name"]: m for m in cells.load_benchmark()["per_layer"]}
    serve = [w["name"] for w in cells.load_benchmark()["workloads"]
             if "serve" in w["traffic"]]
    for name in FOUR:
        m = real[name]
        assert m["workloads"][0] == "gpt3_1p3b.serve_wave5_late3"
        assert set(m["workloads"]) <= set(serve)
        assert m["moves"] == FOUR[name]
        assert m["source"] == ("program_counter"
                               if name == "slot_occupancy.serve"
                               else "host_clock")


@pytest.mark.parametrize("name", list(FOUR))
def test_reader_gives_a_number_in_the_serve_cell(served, name):
    cell, run = served
    assert name in [m["name"] for m in cell.per_layer()]
    value = cell.reader(name)(run)
    assert isinstance(value, float) and value >= 0.0
    if name.endswith("_share.serve") or name == "slot_occupancy.serve":
        assert 0.0 < value <= 100.0
    if name == "decode_tick_ms_p50.serve":
        # the same horizons on the harness's own clock, sync to sync
        outside = [1e3 * s / ev["k"] for s, ev in run.measured["horizons"]
                   if ev["prefill_rows"] == 0]
        assert value == pytest.approx(sorted(outside)[len(outside) // 2],
                                      rel=0.5)


def test_the_records_are_the_engines_own_and_account_for_the_window(served):
    _, run = served
    events = records.horizons(run)
    assert events and len(events) == len(run.measured["horizons"])
    assert all(ev["kind"] == "horizon" for ev in events)
    # 5 requests a wave, each admitted once; 8 tokens each
    waves = run.measured["waves"]
    assert sum(len(ev["admit_waits_s"]) for ev in events) == 5 * waves
    assert sum(ev["tokens"] for ev in events) == 5 * 8 * waves
    assert sum(ev["tokens_padded"] for ev in events) == \
        run.measured["tokens_padded"]
    # the engine's rounds fill the time the harness saw `engine.run` take
    assert sum(ev[p] for ev in events for p in records.PHASES) <= \
        run.measured["serve_seconds"]
    ticks = records.tick_seconds(events)
    assert all(s > 0 for s, _ in ticks)


@pytest.mark.parametrize("name", list(FOUR))
def test_reader_finds_nothing_in_a_program_without_the_record(served, name):
    """The driver lays these files over the parent's checkout too: there an
    event is the old dict of six fields, and the line leaves the metric out."""
    cell, run = served

    class Parent:
        measured = {"horizons": [
            (s, {k: ev[k] for k in ("kind", "k", "w", "t_tokens",
                                    "decode_rows", "prefill_rows")})
            for s, ev in run.measured["horizons"]]}

    assert cell.reader(name)(Parent()) is None

    class Empty:
        measured = {}

    assert cell.reader(name)(Empty()) is None
