"""What the harness refuses, and how it finds a cell's files by name."""
import json
import os
import subprocess
import sys

import pytest

import bench_tiny
from benchmark import cells, compare, generate


@pytest.fixture(scope="module")
def root_here(tmp_path_factory):
    return bench_tiny.make_root(tmp_path_factory.mktemp("bench"))


def _cli(cwd, *args, env=None):
    return subprocess.run(
        [sys.executable, "-m", "benchmark.run", *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


def test_cli_fails_at_the_device_check_and_nowhere_earlier():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    bench = cells.load_benchmark()
    p = _cli(bench_tiny.REPO, "--workload", bench["workloads"][0]["name"],
             "--seed", str(bench_tiny.SEED), "--seconds", "1", "--trace", "0",
             env=env)
    assert p.returncode != 0
    assert "no accelerator" in p.stderr, p.stderr[-2000:]
    assert p.stdout.strip() == ""


def test_cli_fails_without_the_program(tmp_path):
    root, _ = bench_tiny.make_root(tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = _cli(root, "--workload", "gpt_tiny.pretrain_tiny", "--seed", "1",
             "--seconds", "1", "--trace", "0", env=env)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "not in this checkout" in p.stderr, p.stderr[-2000:]


def test_unknown_workload_and_unknown_device_are_errors(root_here):
    root, here = root_here
    with pytest.raises(cells.BenchmarkError, match="no workload"):
        cells.Cell("gpt_tiny.no_such_mix", root=root, here=here)
    with pytest.raises(cells.BenchmarkError, match="not in benchmark/peaks"):
        cells.load_peaks("TPU v9 imaginary")
    with pytest.raises(cells.BenchmarkError):
        cells.load_peaks("_source")
    assert cells.load_peaks("TPU v5 lite")["bf16_flops"] == 197e12


def test_a_cell_a_configuration_and_a_metric_are_found_as_files(root_here):
    """A later PR adds files and entries and edits none."""
    root, here = root_here
    with open(os.path.join(here, "configs", "gpt_wee.json"), "w") as f:
        cfg = json.load(open(os.path.join(here, "configs", "gpt_tiny.json")))
        json.dump(dict(cfg, num_hidden_layers=1), f)
    with open(os.path.join(here, "traffic", "pretrain_wee.json"), "w") as f:
        json.dump({"generator": "train_batches", "batch": 2, "seq": 64,
                   "labels": "next_token", "lengths": None}, f)
    with open(os.path.join(here, "workloads",
                           "gpt_wee.pretrain_wee.json"), "w") as f:
        json.dump(json.load(open(os.path.join(
            here, "workloads", "gpt_tiny.pretrain_tiny.json"))), f)
    with open(os.path.join(here, "metrics", "steps.train.py"), "w") as f:
        f.write("def read(run):\n    return run.measured.get('steps')\n")
    bench = json.load(open(os.path.join(root, "BENCHMARK.json")))
    bench["configs"].append({"name": "gpt_wee", "source": "test",
                             "file": "benchmark/configs/gpt_wee.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "gpt_wee.pretrain_wee",
                               "config": "gpt_wee", "traffic": "pretrain_wee",
                               "chips": 1, "why": "test"})
    bench["end_to_end"][0]["workloads"].append("gpt_wee.pretrain_wee")
    bench["per_layer"].append(
        {"name": "steps.train", "unit": "steps", "better": "higher",
         "source": "program_counter", "layer": "entry points",
         "moves": "train_tokens_per_s", "workloads": ["gpt_wee.pretrain_wee"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    cell = cells.Cell("gpt_wee.pretrain_wee", root=root, here=here)
    assert cell.config["num_hidden_layers"] == 1
    assert cell.traffic["seq"] == 64
    assert [m["name"] for m in cell.per_layer()] == ["steps.train"]

    class FakeRun:
        measured = {"steps": 7}

    assert cell.reader("steps.train")(FakeRun()) == 7
    with pytest.raises(cells.BenchmarkError, match="no reader"):
        cell.reader("not_there.train")


def test_every_metric_of_the_benchmark_has_its_reader_and_files():
    bench = cells.load_benchmark()
    for w in bench["workloads"]:
        cell = cells.Cell(w["name"])
        assert cell.per_layer() and len(cell.end_to_end()) >= 2
        for m in cell.per_layer():
            assert callable(cell.reader(m["name"]))
        for c in bench["configs"]:
            assert c["file"].startswith("benchmark/")


def test_same_seed_same_inputs_and_every_seed_the_same_sizes():
    cfg = {"vocab_size": 1024}
    tr = {"generator": "train_batches", "batch": 4, "seq": 32,
          "labels": "mlm", "mask_share": 0.15,
          "nsp": True, "lengths": [16, 24, 28, 32]}
    a = next(generate.of(tr)(tr, cfg, 2 ** 31 + 5))
    b = next(generate.of(tr)(tr, cfg, 2 ** 31 + 5))
    c = next(generate.of(tr)(tr, cfg, 9))
    assert all((a[k] == b[k]).all() for k in a)
    assert (a["input_ids"] != c["input_ids"]).any()
    assert a["attention_mask"].sum() == c["attention_mask"].sum() == 100
    assert ((a["labels"] >= 0) <= (a["attention_mask"] > 0)).all()
    mix = {"generator": "waves",
           "groups": [{"name": "g", "prompt_lengths": [3, 5, 9],
                       "send": "wave_start"}]}
    w0 = generate.of(mix)(mix, cfg, 11, 0)
    w1 = generate.of(mix)(mix, cfg, 11, 1)
    assert [len(p) for p in w0[0]] == [len(p) for p in w1[0]]
    assert sorted(len(p) for p in w0[0]) == [3, 5, 9]
    assert w0 != w1 and w0 == generate.of(mix)(mix, cfg, 11, 0)
    with pytest.raises(ValueError):
        generate.of({"generator": "of"})


def test_judge_holds_each_number_to_its_own_limit():
    ok, shown = compare.judge({"a": 0.1, "b": 5, "at": "leaf"},
                              {"a": 0.2})
    assert ok and shown["b"] == {"value": 5, "limit": None}
    assert not compare.judge({"a": 0.3}, {"a": 0.2})[0]
    assert not compare.judge({"a": float("nan")}, {"a": 0.2})[0]
    assert not compare.judge({"b": 0.0}, {"a": 0.2})[0]     # number missing
    assert not compare.judge({"a": 0.0}, {})[0]             # nothing held


def test_worst_leaf_gap_measures_against_the_median_leaf():
    ref = {"a": 1.0, "b": 2.0, "c": 1e-9}
    prog = {"a": 1.0, "b": 2.2, "c": 2e-9}
    gap, at = compare.worst_leaf_gap(prog, ref)
    assert at == "b" and abs(gap - 0.1) < 1e-12
    assert compare.idle_leaves(ref) == {"c"}


def test_a_cells_env_is_placed_before_jax_and_a_late_one_is_refused(
        tmp_path, monkeypatch):
    from benchmark import run as harness

    root, here = bench_tiny.make_root(tmp_path)
    path = os.path.join(here, "workloads", "gpt_tiny.serve_tiny.json")
    with open(path) as f:
        job = json.load(f)
    job["env"] = {"BENCH_TEST_CELL_ENV": "1"}
    with open(path, "w") as f:
        json.dump(job, f)
    monkeypatch.delenv("BENCH_TEST_CELL_ENV", raising=False)
    import jax  # noqa: F401  (tests/conftest.py has it: the env comes late)
    cell = cells.Cell("gpt_tiny.serve_tiny", root=root, here=here)
    assert "BENCH_TEST_CELL_ENV" not in os.environ
    assert cell.env_late == ["BENCH_TEST_CELL_ENV"]
    with pytest.raises(cells.BenchmarkError, match="imported before"):
        harness.run_cell("gpt_tiny.serve_tiny", 1, 1, 0, root=root, here=here)
    # already in the environment when JAX came in: not late
    monkeypatch.setenv("BENCH_TEST_CELL_ENV", "1")
    assert cells.Cell("gpt_tiny.serve_tiny", root=root,
                      here=here).env_late == []
    # the real serve cell states the flag under which its programs take
    # the embedding as an argument, so that the compile cache holds them
    real = cells._json(os.path.join(cells.HERE, "workloads",
                                    "gpt3_1p3b.serve_wave5_late3.json"))
    assert real["env"] == {"JAX_USE_SIMPLIFIED_JAXPR_CONSTANTS": "1"}
    p = subprocess.run(
        [sys.executable, "-c",
         "from benchmark import cells\n"
         "cells.Cell('gpt3_1p3b.serve_wave5_late3')\n"
         "import jax\n"
         "assert jax.config.jax_use_simplified_jaxpr_constants\n"],
        cwd=bench_tiny.REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
