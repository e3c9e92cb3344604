"""The harness end to end on the CPU at the tiny cells kept in `tiny/`:
every job kind runs and proves correct against its plain reference, and the
same run with the timed path broken underneath comes out not correct."""
import pytest

import bench_tiny
from benchmark import faults


@pytest.fixture(scope="module")
def root_here(tmp_path_factory):
    return bench_tiny.make_root(tmp_path_factory.mktemp("bench"))


CASES = [
    ("gpt_tiny.pretrain_tiny", None, True),
    ("gpt_tiny.pretrain_tiny", {"step": faults.state_unchanged}, False),
    ("gpt_tiny.pretrain_tiny", {"step": faults.half_batch_left_out},
     False),
    ("bert_tiny.mlm_tiny", None, True),
    ("bert_tiny.mlm_tiny", {"step": faults.state_unchanged}, False),
    ("bert_tiny.mlm_tiny", {"step": faults.half_batch_left_out}, False),
    ("gpt_tiny.serve_tiny", None, True),
    ("gpt_tiny.serve_tiny", {"alter": faults.token_altered}, False),
]


@pytest.mark.parametrize(
    "cell,faults,correct", CASES,
    ids=[f"{c}-{'sound' if f is None else next(iter(f.values())).__name__}"
         for c, f, _ in CASES])
def test_cell_runs_and_is_judged(root_here, cell, faults, correct):
    result = bench_tiny.run(root_here, cell, faults=faults)
    assert result["correct"] is correct, result["compared"]
    assert list(result)[-1] == "compared"
    assert result["attempted"] > 0 and result["failed"] == 0
    metrics = result["metrics"]
    assert metrics["setup_s"]["value"] > 0
    assert len(metrics) >= 2 and all(m["value"] > 0 for m in metrics.values())
    for shown in result["compared"].values():
        if isinstance(shown, dict):
            assert set(shown) == {"value", "limit"}
    assert result["device"]["platform"] == "cpu"


def test_serve_reports_its_end_to_end_metrics(root_here):
    result = bench_tiny.run(root_here, "gpt_tiny.serve_tiny", seed=7)
    assert set(result["metrics"]) == {"serve_tokens_per_s", "ttft_ms_p75",
                                      "itl_ms_p99", "setup_s"}
    # whole waves: 5 requests of 8 tokens each
    assert result["attempted"] % 5 == 0


@pytest.mark.parametrize("cell", ["gpt_tiny.pretrain_tiny",
                                  "bert_tiny.mlm_tiny",
                                  "gpt_tiny.serve_tiny"])
def test_a_traced_run_without_device_operations_gives_no_result(
        root_here, cell, monkeypatch):
    """Here the profiler sees no TPU: the traced run goes all its way (the
    serve cell's one more wave is cut off under the profiler) and is then
    refused, since no operation ran on a device."""
    from benchmark.cells import BenchmarkError
    from benchmark.jobs import serve_waves

    monkeypatch.setattr(serve_waves, "TRACED_S", 0.0)
    with pytest.raises(BenchmarkError, match="no device operations"):
        bench_tiny.run(root_here, cell, trace=1)
