"""The reduction from a trace to busy time, kernel sums and named gaps: on
intervals made by hand, and on a small trace recorded on a TPU v5e
(`data/small.xplane.pb`, three steps of a jitted matrix product under the
harness's spans; `benchmark/rehearse/record_fixture.py` records it)."""
import os

import pytest

import bench_tiny
from benchmark import readers, trace

MS = 1_000_000


FWD = ("%checkpoint.3 = (bf16[2,4,8,16]{3,2,1,0:T(8,128)(2,1)}, "
       "f32[2,4,8,1]{3,2,1,0}) custom-call(bf16[2,4,8,16]{3,2,1,0} %a, "
       "bf16[2,4,8,16]{3,2,1,0} %b, bf16[2,4,8,16]{3,2,1,0} %c), "
       'custom_call_target="tpu_custom_call"')
DQ = ("%jvp.9 = bf16[2,4,8,16]{3,2,1,0} custom-call(bf16[2,4,8,16]{3,2,1,0} "
      "%a, bf16[2,4,8,16]{3,2,1,0} %b, f32[2,4,8,1]{3,2,1,0} %l), "
      'custom_call_target="tpu_custom_call"')
FUSION = "%fusion.1 = bf16[8,16]{1,0:T(8,128)(2,1)} fusion(bf16[8,16]{1,0} %x)"


def _op(start, end, hlo):
    return (start, end, trace.short_name(hlo), trace._LAYOUT.sub("", hlo))


def _ops():
    return [_op(0, 4 * MS, FUSION), _op(2 * MS, 6 * MS, FWD),
            _op(10 * MS, 12 * MS, DQ), _op(20 * MS, 21 * MS, FUSION)]


def _signatures():
    from benchmark.flops.common import flash_signature, xent_signature
    return {"_fwd_kernel": flash_signature("fwd", 2, 4, 8, 16),
            "_bwd_dq_kernel": flash_signature("bwd_dq", 2, 4, 8, 16),
            "_bwd_dkv_kernel": flash_signature("bwd_dkv", 2, 4, 8, 16),
            "_xent_fwd_kernel": xent_signature("fwd", 64, 1024)}


def test_short_names_add_up_over_layers():
    assert trace.short_name(FUSION) == "fusion bf16[8,16]"
    assert trace.short_name(FWD) == \
        "checkpoint (bf16[2,4,8,16], f32[2,4,8,1])"
    assert trace.short_name("copy-start.12") == "copy-start"


def test_busy_is_the_union_not_the_sum():
    assert trace.busy_intervals(_ops()) == [
        (0, 6 * MS), (10 * MS, 12 * MS), (20 * MS, 21 * MS)]
    assert trace.busy_seconds(_ops()) == pytest.approx(0.009)


def test_kernel_sums_by_signature():
    """A Pallas call has no name of its own in a TPU trace: it is told by
    the types of what it takes and gives."""
    got = trace.kernel_seconds(_ops(), _signatures())
    assert got["_fwd_kernel"] == (pytest.approx(0.004), 1)
    assert got["_bwd_dq_kernel"] == (pytest.approx(0.002), 1)
    assert got["_bwd_dkv_kernel"] == (0.0, 0)
    assert got["_xent_fwd_kernel"] == (0.0, 0)


def test_top_operations_leave_out_what_spans_others():
    """A `while` spans its body's operations, which the trace lists too."""
    ops = [(0, 100, "while", "w"), (0, 40, "a", "a"), (40, 100, "while", "w"),
           (40, 70, "b", "b"), (70, 70, "copy-start", "c"),
           (70, 100, "b", "b"), (100, 120, "d", "d")]
    assert trace.leaf_ops(ops) == [op for op in ops if op[2] != "while"]
    assert trace.top_ops(ops)[0] == ["b", pytest.approx(60e-9)]
    assert trace.busy_seconds(ops) == pytest.approx(120e-9)


def test_gaps_are_named_by_the_innermost_span():
    spans = [(0, 30 * MS, "bench_window"), (6 * MS, 10 * MS, "batch_made")]
    gaps = dict(trace.idle_gaps(_ops(), spans))
    assert gaps == {"batch_made": pytest.approx(0.004),
                    "bench_window": pytest.approx(0.008)}
    assert dict(trace.idle_gaps(_ops(), []))["(no span)"] == \
        pytest.approx(0.012)
    assert trace.top_ops(_ops())[0] == ["fusion bf16[8,16]",
                                        pytest.approx(0.005)]


def test_a_reader_that_finds_nothing_returns_nothing():
    class Run:
        traced = {"all_ops": _ops(), "busy_s": 0.009, "window_s": 0.03}
        measured = {"kernel_calls": {
            "_fwd_kernel": (1e9, 1e6, _signatures()["_fwd_kernel"]),
            "_xent_fwd_kernel": (1e6, 1e9,
                                 _signatures()["_xent_fwd_kernel"])}}
        peaks = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e12}

    assert readers.roofline_share(Run, ["_xent_fwd_kernel"]) is None
    assert readers.roofline_share(Run, ["_fwd_kernel"]) == pytest.approx(
        100 * 1e-3 / 4e-3)
    assert readers.idle_share(Run) == pytest.approx(70.0)
    Run.traced = None
    assert readers.roofline_share(Run, ["_fwd_kernel"]) is None
    assert readers.idle_share(Run) is None


def test_recorded_tpu_trace_reduces():
    path = os.path.join(bench_tiny.HERE, "data", "small.xplane.pb")
    red = trace.reduce(path, ("bench_window", "step_dispatched",
                              "step_waited"), window_s=1.0, chips=1)
    assert red is not None and red["n_events"] >= 3
    assert 0 < red["busy_s"] < 1.0
    assert red["device_ops"] and len(red["device_ops"]) <= 10
    names = {n for n, _ in red["idle_gaps"]}
    assert names <= {"bench_window", "step_dispatched", "step_waited",
                     "(no span)"}
