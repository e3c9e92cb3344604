"""The kept tests that guard the real BENCHMARK.json hold their own cell's
and their own metric's entries, found by name. A PR that adds a
configuration appends a config, a serve cell, a per-layer metric and the
cell's name at the end of the metrics' lists: every guard stays green on
such a copy, and turns red on a copy that edits what it holds. The guards
read the benchmark through `cells.load_benchmark` alone, which is where the
copies are handed to them."""
import copy

import pytest

import test_deepseek_v2_cell as deepseek
import test_gather_live_share as gather
import test_longcat_flash_cell as longcat
import test_stall_share as stall
from benchmark import cells
from test_benchmark_records import (
    test_the_real_benchmark_lists_the_four_for_the_serve_cell_alone as records)

GUARDS = {
    "longcat_cell": longcat.test_real_cell_is_the_issues,
    "longcat_config":
        longcat.test_real_configuration_keeps_every_published_key,
    "deepseek_cell": deepseek.test_real_cell_is_the_issues,
    "deepseek_config":
        deepseek.test_real_configuration_keeps_every_published_key,
    "gather":
        gather.test_the_real_benchmark_lists_it_last_for_the_serve_cell_alone,
    "stall": stall.test_the_real_benchmark_lists_it_for_the_gpt_serve_cell,
    "records": records,
}
DEEPSEEK = "deepseek_v2_ep8.serve_wave12_late4_ctx4k"
LONGCAT = longcat.REAL
NEW_CONFIG = "conv_probe_ep1"
NEW_CELL = NEW_CONFIG + ".serve_probe"
PROBE = "conv_probe.serve"


def _appended():
    """The real benchmark with what a configuration's PR adds, all of it at
    the end of a list: the new cell on every metric that lists a serve
    cell, and a metric of its own."""
    bench = cells.load_benchmark()
    bench["configs"].append({
        "name": NEW_CONFIG, "source": "test", "reduced": [],
        "file": f"benchmark/configs/{NEW_CONFIG}.json", "why": "test"})
    bench["workloads"].append({
        "name": NEW_CELL, "config": NEW_CONFIG, "traffic": "serve_probe",
        "chips": 1, "why": "test"})
    serving = {w["name"] for w in bench["workloads"]
               if w["traffic"].startswith("serve")}
    for m in bench["end_to_end"] + bench["per_layer"]:
        if serving & set(m.get("workloads", ())):
            m["workloads"].append(NEW_CELL)
    bench["per_layer"].append({
        "name": PROBE, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "step program",
        "moves": "serve_tokens_per_s", "workloads": [NEW_CELL]})
    return bench


def _metric(bench, name):
    return next(m for m in bench["end_to_end"] + bench["per_layer"]
                if m["name"] == name)


def _handed(monkeypatch, bench):
    monkeypatch.setattr(cells, "load_benchmark",
                        lambda root=cells.ROOT: copy.deepcopy(bench))


def test_the_copy_appends_what_a_configurations_pr_appends():
    bench = _appended()
    assert bench["configs"][-1]["name"] == NEW_CONFIG
    assert bench["workloads"][-1]["name"] == NEW_CELL
    assert bench["per_layer"][-1]["name"] == PROBE
    for name in ("serve_tokens_per_s", "pad_share.serve",
                 "gather_live_share.serve", "stall_share.serve",
                 "experts_hit_share.serve", "routed_here_share.serve",
                 "zero_routed_share.serve_waves"):
        assert _metric(bench, name)["workloads"][-1] == NEW_CELL, name
    # nothing the benchmark had moved: the copy, its additions taken out,
    # is the real benchmark
    for key in ("configs", "workloads"):
        bench[key].pop()
    bench["per_layer"].pop()
    for m in bench["end_to_end"] + bench["per_layer"]:
        if NEW_CELL in m.get("workloads", ()):
            m["workloads"].remove(NEW_CELL)
    assert bench == cells.load_benchmark()


@pytest.mark.parametrize("guard", list(GUARDS))
def test_every_guard_holds_after_the_appends(monkeypatch, guard):
    _handed(monkeypatch, _appended())
    GUARDS[guard]()


def _edit_longcat(bench, **fields):
    next(w for w in bench["workloads"] if w["name"] == LONGCAT).update(fields)


def _lead_with(name):
    def edit(bench):
        m = _metric(bench, name)
        m["workloads"].insert(0, m["workloads"].pop())
    return edit


BROKEN = {
    # (the edit to the appended copy, the guard that has to fail)
    "longcat_chips": (lambda b: _edit_longcat(b, chips=4), "longcat_cell"),
    "longcat_traffic": (lambda b: _edit_longcat(
        b, traffic="serve_wave12_late4_ctx4k"), "longcat_cell"),
    "longcat_led_off_its_reader": (_lead_with("zero_routed_share.serve_waves"),
                                   "longcat_cell"),
    "deepseek_off_pad_share": (
        lambda b: _metric(b, "pad_share.serve")["workloads"].remove(DEEPSEEK),
        "deepseek_cell"),
    "deepseek_on_stall_share": (
        lambda b: _metric(b, "stall_share.serve")["workloads"].append(
            DEEPSEEK), "deepseek_cell"),
    "deepseek_led_off_experts_hit": (_lead_with("experts_hit_share.serve"),
                                     "deepseek_cell"),
    "gather_led_by_another": (_lead_with("gather_live_share.serve"),
                              "gather"),
    "stall_led_by_another": (_lead_with("stall_share.serve"), "stall"),
}


@pytest.mark.parametrize("broken", list(BROKEN))
def test_a_guard_fails_on_a_copy_that_edits_what_it_holds(monkeypatch,
                                                          broken):
    edit, guard = BROKEN[broken]
    bench = _appended()
    edit(bench)
    _handed(monkeypatch, bench)
    with pytest.raises(AssertionError):
        GUARDS[guard]()
