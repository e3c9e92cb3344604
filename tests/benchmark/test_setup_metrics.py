"""The four set-up metrics (`benchmark/metrics/*.setup.py`), which read the
program's compile log (`paddle_tpu.profiler.compile_log`): on the tiny train
and serve cells on the CPU, with the entries added to an own copy of the tiny
BENCHMARK.json; and on a program without the log, or with a log that let go
of set-up's entries, where each reader returns nothing."""
import importlib
import json
import os
import time

import jax
import pytest

import bench_tiny
from benchmark import cells
from benchmark import run as harness

CELLS = ("gpt_tiny.pretrain_tiny", "gpt_tiny.serve_tiny")
SECONDS = ("trace_lower_s.setup", "compile_load_s.setup", "build_s.setup")
FOUR = SECONDS + ("programs_compiled.setup",)
LISTED = ["gpt3_1p3b.pretrain_bs8_s1024", "bert_large.pretrain_mlm_s512",
          "gpt3_1p3b.serve_wave5_late3"]


@pytest.fixture(scope="module")
def ran(tmp_path_factory):
    """{cell name: (cell, run)} after each tiny job. The run's clock starts
    where its job does, as a run's starts with its process; the persistent
    compile cache is an empty directory of its own, so that what set-up
    makes is compiled and not loaded."""
    from jax._src import compilation_cache
    root, here = bench_tiny.make_root(tmp_path_factory.mktemp("setup"))
    real = {m["name"]: m for m in cells.load_benchmark()["per_layer"]}
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    for name in FOUR:
        bench["per_layer"].append(dict(real[name], workloads=list(CELLS)))
    with open(path, "w") as f:
        json.dump(bench, f)
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs")
    saved = {n: getattr(jax.config, n) for n in names}
    jax.config.update("jax_compilation_cache_dir",
                      str(tmp_path_factory.mktemp("compile_cache")))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compilation_cache.reset_cache()
    out = {}
    try:
        for name in CELLS:
            cell = cells.Cell(name, root=root, here=here)
            run = harness.Run(cell, bench_tiny.SEED, 0.5, 0,
                              {"bf16_flops": float("nan"),
                               "hbm_bytes_per_s": float("nan")})
            run.t0 = time.perf_counter()
            importlib.import_module(
                f"benchmark.jobs.{cell.job['job']}").run(run)
            assert run.correct
            out[name] = cell, run
    finally:
        for n, v in saved.items():
            jax.config.update(n, v)
        compilation_cache.reset_cache()
    return out


def test_the_real_benchmark_lists_the_four_for_three_cells():
    """Found by name: a later PR appends its own entries after them."""
    real = {m["name"]: m for m in cells.load_benchmark()["per_layer"]}
    for name in FOUR:
        m = real[name]
        assert m["layer"] == "set-up" and m["moves"] == "setup_s"
        assert m["workloads"] == LISTED and m["better"] == "lower"
        assert (m["unit"], m["source"]) == (
            ("programs", "program_counter")
            if name == "programs_compiled.setup" else ("s", "host_clock"))


@pytest.mark.parametrize("name", FOUR)
@pytest.mark.parametrize("cell_name", CELLS)
def test_reader_gives_a_number(ran, cell_name, name):
    cell, run = ran[cell_name]
    assert name in [m["name"] for m in cell.per_layer()]
    value = cell.reader(name)(run)
    assert isinstance(value, (int, float)) and value >= 0


@pytest.mark.parametrize("cell_name", CELLS)
def test_the_seconds_are_disjoint_and_inside_setup(ran, cell_name):
    """Each of the three is some of set-up, no second is counted twice,
    and the job's set-up compiled at least one program of its own."""
    cell, run = ran[cell_name]
    seconds = [cell.reader(name)(run) for name in SECONDS]
    assert all(s > 0 for s in seconds), seconds
    assert sum(seconds) <= run.setup_s
    assert cell.reader("programs_compiled.setup")(run) >= 1


@pytest.mark.parametrize("name", FOUR)
def test_reader_finds_nothing_in_a_program_without_the_log(ran, name,
                                                           monkeypatch):
    """A program from before the log (these benchmark files laid over an
    older checkout): its profiler has no `compile_log`, and the result
    line leaves the metric out."""
    from paddle_tpu import profiler
    cell, run = ran[CELLS[0]]
    monkeypatch.delattr(profiler, "compile_log")
    assert cell.reader(name)(run) is None


@pytest.mark.parametrize("name", FOUR)
def test_reader_refuses_a_log_that_let_go_of_setup(ran, name, monkeypatch):
    """A log that dropped entries set-up may have held would read low: the
    metric is left out rather than short."""
    from paddle_tpu import profiler
    cell, run = ran[CELLS[1]]
    entries, dropped = profiler.compile_log(since=run.t0)
    assert dropped == 0
    monkeypatch.setattr(profiler, "compile_log",
                        lambda since=None: (entries[1:], 1))
    assert cell.reader(name)(run) is None
