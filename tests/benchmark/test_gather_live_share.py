"""`gather_live_share.serve` (`benchmark/metrics/gather_live_share.serve.py`)
reads the two page counts of the engine's horizon records: on the tiny serve
cell on the CPU, with its entry added to an own copy of the tiny
BENCHMARK.json as the real one lists it; and on records that lack the counts,
as the parent's program gives them, where the reader returns nothing."""
import importlib
import json
import os

import pytest

import bench_tiny
from benchmark import cells, records
from benchmark import run as harness

CELL = "gpt_tiny.serve_tiny"
NAME = "gather_live_share.serve"


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    root, here = bench_tiny.make_root(tmp_path_factory.mktemp("gather"))
    real = {m["name"]: m for m in cells.load_benchmark()["per_layer"]}
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["per_layer"].append(dict(real[NAME], workloads=[CELL]))
    with open(path, "w") as f:
        json.dump(bench, f)
    cell = cells.Cell(CELL, root=root, here=here)
    run = harness.Run(cell, bench_tiny.SEED, 0.5, 0,
                      {"bf16_flops": float("nan"),
                       "hbm_bytes_per_s": float("nan")})
    importlib.import_module(f"benchmark.jobs.{cell.job['job']}").run(run)
    assert run.correct
    return cell, run


def test_the_real_benchmark_lists_it_last_for_the_serve_cell_alone():
    """Found by name and held field by field, its list from the GPT serve
    cell on: later PRs append entries, and cells to its list, after it
    (the name is older than what the test holds)."""
    entry = next(m for m in cells.load_benchmark()["per_layer"]
                 if m["name"] == NAME)
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "kernels",
        "moves": "serve_tokens_per_s"}
    assert entry["workloads"][0] == "gpt3_1p3b.serve_wave5_late3"


def test_reader_is_the_live_pages_over_the_gathered_by_ticks(served):
    cell, run = served
    assert NAME in [m["name"] for m in cell.per_layer()]
    value = cell.reader(NAME)(run)
    events = records.horizons(run)
    # a row's pages are copied once a tick, whatever its tokens: slots x
    # the table's columns (the `_p<width>` of the program's name)
    for ev in events:
        width = int(ev["program"].rsplit("_p", 1)[1])
        assert ev["pages_gathered"] == ev["slots"] * width
        assert 0 <= ev["pages_live"] <= ev["pages_gathered"]
    assert 0.0 < value <= 100.0
    assert value == pytest.approx(
        100.0 * sum(ev["k"] * ev["pages_live"] for ev in events)
        / sum(ev["k"] * ev["pages_gathered"] for ev in events))


@pytest.mark.parametrize("kept", ["parents_six", "all_but_the_counts",
                                  "no_events"])
def test_reader_finds_nothing_in_a_program_without_the_counts(served, kept):
    """The driver lays this file over the parent's checkout too: PR 28's
    record has no page counts, the program before it no record at all."""
    cell, run = served
    keys = {"parents_six": ("kind", "k", "w", "t_tokens", "decode_rows",
                            "prefill_rows"),
            "all_but_the_counts": [k for k in run.measured["horizons"][0][1]
                                   if not k.startswith("pages_")],
            "no_events": None}[kept]

    class Parent:
        measured = {} if keys is None else {"horizons": [
            (s, {k: ev[k] for k in keys})
            for s, ev in run.measured["horizons"]]}

    assert cell.reader(NAME)(Parent()) is None
