"""The DeepSeek-V2 family through the harness on the CPU: a tiny
configuration (no published width) appended to the copy `make_root` makes,
never to `tiny/BENCHMARK.json`; the real configuration's file against the
published config; the FLOP count against hand counts; the two readers."""
import json
import os
import types

import numpy as np
import pytest

import bench_tiny
from benchmark import cells, faults
from benchmark.flops import deepseek_v2 as flops

CELL = "deepseek_v2_tiny.serve_tiny_mla"
# the published config.json, as the model-configs catalog copies it
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 5120, "intermediate_size": 12288,
    "kv_lora_rank": 512, "max_position_embeddings": 163840,
    "model_type": "deepseek_v2", "moe_intermediate_size": 1536,
    "moe_layer_freq": 1, "n_group": 8, "n_routed_experts": 160,
    "n_shared_experts": 2, "norm_topk_prob": False,
    "num_attention_heads": 128, "num_experts_per_tok": 6,
    "num_hidden_layers": 60, "num_key_value_heads": 128,
    "q_lora_rank": 1536, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 16,
    "scoring_func": "softmax", "seq_aux": True,
    "tie_word_embeddings": False, "topk_group": 3,
    "topk_method": "group_limited_greedy", "v_head_dim": 128,
    "vocab_size": 102400}
TINY_CONFIG = dict(
    PUBLISHED, family="deepseek_v2", hidden_size=64, intermediate_size=96,
    kv_lora_rank=32, moe_intermediate_size=32, n_group=4, topk_group=2,
    n_routed_experts=4, router_width=16, expert_offset=8,
    num_attention_heads=4, num_key_value_heads=4, num_experts_per_tok=3,
    num_hidden_layers=3, q_lora_rank=48, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, vocab_size=256,
    rope_scaling=dict(PUBLISHED["rope_scaling"], factor=4,
                      original_max_position_embeddings=64),
    routed_scaling_factor=4.0, dtype="float32", initializer_range=0.1,
    router_init_std=0.1)
TINY_TRAFFIC = {
    "generator": "waves", "answer_tokens": 8, "greedy": True,
    "groups": [
        {"name": "first", "prompt_lengths": [8, 40, 72], "send": "wave_start"},
        {"name": "late", "prompt_lengths": [24, 56],
         "send": {"when_group": "first", "has_tokens": 4}}]}
TINY_JOB = {
    "job": "serve_waves",
    "engine": {"slots": 5, "page_size": 16, "positions": 96,
               "max_new_tokens": 8, "host_sync_s": 0.001},
    "checked_requests": 3,
    # float32 both sides: the program reads 1e-5 or less, bfloat16 (the
    # control one precision down) some hundredths
    "limits": {"served_logit_gap": 0.001, "tokens_outside_vocab": 0,
               "requests_unfinished": 0}}


@pytest.fixture(scope="module")
def root_here(tmp_path_factory):
    root, here = bench_tiny.make_root(tmp_path_factory.mktemp("bench"))
    for sub, name, data in (
            ("configs", "deepseek_v2_tiny", TINY_CONFIG),
            ("traffic", "serve_tiny_mla", TINY_TRAFFIC),
            ("workloads", CELL, TINY_JOB)):
        with open(os.path.join(here, sub, name + ".json"), "w") as f:
            json.dump(data, f)
    path = os.path.join(root, "BENCHMARK.json")
    bench = json.load(open(path))
    bench["configs"].append({
        "name": "deepseek_v2_tiny", "source": "test",
        "file": "benchmark/configs/deepseek_v2_tiny.json", "reduced": [],
        "why": "CPU test"})
    bench["workloads"].append({
        "name": CELL, "config": "deepseek_v2_tiny",
        "traffic": "serve_tiny_mla", "chips": 1, "why": "CPU test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "gpt_tiny.serve_tiny" in (m.get("workloads") or ()):
            m["workloads"].append(CELL)
    for name in ("routed_here_share.serve", "experts_hit_share.serve"):
        bench["per_layer"].append({
            "name": name, "unit": "%", "better": "higher",
            "source": "program_counter", "layer": "step program",
            "moves": "serve_tokens_per_s", "workloads": [CELL]})
    json.dump(bench, open(path, "w"))
    return root, here


@pytest.mark.parametrize("fault,correct", [
    (None, True), ({"alter": faults.token_altered}, False)],
    ids=["sound", "token_altered"])
def test_cell_runs_and_is_judged(root_here, fault, correct):
    result = bench_tiny.run(root_here, CELL, faults=fault)
    assert result["correct"] is correct, result["compared"]
    assert result["attempted"] % 5 == 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"serve_tokens_per_s", "ttft_ms_p75",
                                      "itl_ms_p99", "setup_s"}
    gap = result["compared"]["served_logit_gap"]
    assert (gap["value"] <= gap["limit"]) is correct


def test_a_dropped_routed_sum_comes_out_not_correct(root_here, monkeypatch):
    """The routed layer's own fault, planted in the decoder (no held
    expert gives anything) and played through the tiny cell, where program
    and reference are both float32 and no selection turns. (At the
    published widths in bfloat16 the cell's one number does not see it, and
    one zeroed expert changes no served token even here: PERF.md section 6;
    `test_a_zeroed_held_expert_moves_the_rows_that_selected_it...` holds
    that fault on values.)"""
    family = cells.Cell(CELL, root=root_here[0], here=root_here[1]).family
    build = family.build_decoder

    def faulty(cfg, seed, job):
        dec = build(cfg, seed, job)
        seg = dec.weights["segments"][1]
        seg["down"] = seg["down"] * 0
        return dec

    monkeypatch.setattr(family, "build_decoder", faulty)
    result = bench_tiny.run(root_here, CELL)
    gap = result["compared"]["served_logit_gap"]
    assert result["correct"] is False and gap["value"] > gap["limit"], gap


def test_control_one_precision_down_reads_over_the_limit(root_here):
    """The reference in bfloat16 operands (float32 is what the tiny
    configuration states) puts tokens first that lie further under the
    float32 reference's best than the limit; float32 against itself 0."""
    root, here = root_here
    cell = cells.Cell(CELL, root=root, here=here)
    ref, cfg = cell.family.reference, cell.config
    params = ref.init_params(cfg, bench_tiny.SEED)
    rng = np.random.default_rng(0)
    gaps = {"f32": 0.0, "bf16": 0.0}
    for _ in range(3):
        prompt = rng.integers(0, cfg["vocab_size"], 40).tolist()
        tokens = rng.integers(0, cfg["vocab_size"], 40).tolist()
        for prec in gaps:
            gaps[prec] = max(gaps[prec], float(np.asarray(ref.served_gaps(
                cfg, params, prompt, tokens, 80, control=prec)).max()))
    assert gaps["f32"] == 0.0
    assert gaps["bf16"] > cell.job["limits"]["served_logit_gap"], gaps
    assert 0.0 < ref.selection_differs(cfg, params, prompt + tokens,
                                       "fp8") <= 1.0


# ----------------------------------------------------- the real files
def _real(sub, name):
    with open(os.path.join(bench_tiny.REPO, "benchmark", sub,
                           name + ".json")) as f:
        return json.load(f)


def test_real_configuration_keeps_every_published_key():
    """Every key of the published config unchanged but the three under
    `reduced`, each with its published value beside it; the deployment
    and the assumed values stated."""
    cfg = _real("configs", "deepseek_v2_ep8")
    entry = next(c for c in cells.load_benchmark()["configs"]
                 if c["name"] == "deepseek_v2_ep8")
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"]) == [
        "n_routed_experts", "num_hidden_layers", "vocab_size"]
    for key, value in PUBLISHED.items():
        if key in cfg["reduced"]:
            assert cfg["reduced"][key]["published"] == value
            assert cfg["reduced"][key]["run"] == cfg[key] != value
        else:
            assert cfg[key] == value, key
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (6, 20, 12800)
    assert cfg["router_width"] == PUBLISHED["n_routed_experts"]
    assert cfg["router_width"] // cfg["n_group"] == cfg["n_routed_experts"]
    assert "8 chips share each layer" in cfg["deployment"]
    assert {"initializer_range", "router_init_std", "rotary_pairing",
            "dtype"} <= set(cfg["assumed"])
    assert entry["source"] == cfg["source"]


def test_real_cell_is_the_issues():
    bench = cells.load_benchmark()
    name = "deepseek_v2_ep8.serve_wave12_late4_ctx4k"
    entry = next(w for w in bench["workloads"] if w["name"] == name)
    assert entry == dict(entry, config="deepseek_v2_ep8", chips=1,
                         traffic="serve_wave12_late4_ctx4k")
    traffic = _real("traffic", "serve_wave12_late4_ctx4k")
    first, late = traffic["groups"]
    assert first["prompt_lengths"] == [256, 512, 768, 1024, 1024, 1536,
                                       2048, 2048, 3072, 3072, 4096, 4096]
    assert late["prompt_lengths"] == [512, 1024, 2048, 4096]
    assert late["send"] == {"when_group": "first", "has_tokens": 32}
    assert sum(first["prompt_lengths"] + late["prompt_lengths"]) == 31232
    job = _real("workloads", name)
    e = job["engine"]
    assert (e["slots"], e["positions"], e["page_size"]) == (16, 4224, 16)
    assert e["max_new_tokens"] == traffic["answer_tokens"]
    assert e["positions"] >= 4096 + traffic["answer_tokens"]
    assert job["checked_requests"] >= 5
    # Entries are found by name, and only the cell's own are held: every PR
    # appends its configs, cells, metrics and names on a metric's list, so
    # no entry keeps a place and no list keeps its length.
    lists = {m["name"]: m.get("workloads") for m in bench["per_layer"]}
    # the cell's own two readers list it first
    for own in ("routed_here_share.serve", "experts_hit_share.serve"):
        assert lists[own][0] == name
    # Not listed on gather_live_share.serve (its page counts follow the GPT
    # decoder's walk; this decoder is handed the whole table, PERF.md
    # section 6) nor on stall_share.serve (a traced window of 6 s is ONE
    # wave here, and the reader needs the same work done three times). Nor
    # on the two tick metrics that move the two tails, which the cell does
    # not report: a window holds three waves here, and one pause of the
    # machine (0.11 s, up to three in 20 s on some machines) moves the 99th
    # percentile gap, the 61st largest of 6,096, across a step from 108 to
    # 126 ms (PERF.md sections 6 and 7).
    for metric in ("gather_live_share.serve", "mixed_tick_ms_p50.serve",
                   "prefill_tick_ms_p50.serve", "stall_share.serve"):
        assert name not in lists[metric], metric
    cell = cells.Cell(name)
    e2e = [m["name"] for m in cell.end_to_end()]
    assert e2e == ["serve_tokens_per_s", "setup_s"]
    assert all(m["moves"] == "serve_tokens_per_s" for m in cell.per_layer())
    assert {"pad_share.serve", "mfu.serve", "device_idle_share.serve",
            "decode_tick_ms_p50.serve", "sched_round_ms_p50.serve",
            "device_wait_share.serve", "slot_occupancy.serve",
            "routed_here_share.serve", "experts_hit_share.serve"} <= {
        m["name"] for m in cell.per_layer()}


def test_limit_parts_the_readings_taken_on_the_chip():
    """`data/readings.<cell>.jsonl` holds what `rehearse/readings.py` read
    on a v5e at the cell's own size (PERF.md section 6): held to the limit
    in the cell's file every sound run of the program is correct and every
    run of the control (operands rounded to fp8) is not. Request by request
    the two overlap (a rounding-turned selection costs the program up to
    0.83, the control's best request reads 0.75), so the control stands on
    the share of its requests over the limit: six checked requests have to
    hold one of them nine times in ten."""
    from benchmark import compare
    name = "deepseek_v2_ep8.serve_wave12_late4_ctx4k"
    job = _real("workloads", name)
    limits, limit = job["limits"], job["limits"]["served_logit_gap"]
    with open(os.path.join(bench_tiny.HERE, "data",
                           f"readings.{name}.jsonl")) as f:
        rows = [r for r in map(json.loads, f) if r["kind"] != "schedule"]
    assert {r["kind"] for r in rows} == {"program", "control.fp8"}
    for r in rows:
        ok, shown = compare.judge(r["numbers"],
                                  {k: limits[k] for k in r["numbers"]})
        assert ok == (r["kind"] == "program"), (r["kind"], r["seed"], shown)
    program = max(r["numbers"]["served_logit_gap"] for r in rows
                  if r["kind"] == "program")
    control = [r for r in rows if r["kind"] == "control.fp8"]
    by_seed = min(r["numbers"]["served_logit_gap"] for r in control)
    assert 1.3 * program < limit < by_seed / 1.3      # room on both sides
    over = np.mean([g > limit for r in control for g in r["per_request"]])
    # the chance that none of the checked requests is over the limit
    assert (1 - over) ** job["checked_requests"] < 0.1, over


# ------------------------------------------------------ FLOPs by hand
def test_published_parameters_a_token_by_hand():
    """MLA 7.86M + 37.75M + 2.95M + 16.78M + 83.89M = 149.23M a layer;
    layer 0's MLP 3 x 5120 x 12288 = 188.74M; an expert layer's router
    0.82M + shared 3 x 5120 x 3072 = 47.19M + 6 x 20/160 of an expert of
    23.59M = 17.69M. Six layers of the first, one of the second, five of
    the third."""
    cfg = _real("configs", "deepseek_v2_ep8")
    mla = (5120 * 1536 + 1536 * 128 * 192 + 5120 * 576 + 512 * 128 * 256
           + 128 * 128 * 5120)
    assert mla == 149_225_472
    one = 3 * 5120 * 1536
    moe = 5120 * 160 + 2 * one + 0.75 * one
    want = 6 * mla + 3 * 5120 * 12288 + 5 * moe
    assert flops.params_per_token(cfg) == want
    assert flops.expert_layers(cfg) == 5
    assert flops.attention_flops_per_key(cfg) == 2 * 128 * (192 + 128)


def test_serve_flops_by_hand():
    """A prompt of 3 and 2 generated: 4 tokens processed (the last is not
    fed), attending over 1 + 2 + 3 + 4 = 10 keys in each of 6 layers, the
    head at 2 positions over the 12,800 columns held."""
    cfg = _real("configs", "deepseek_v2_ep8")
    want = (4 * 2 * flops.params_per_token(cfg) + 6 * 81920 * 10
            + 2 * 2 * 5120 * 12800)
    assert flops.serve_flops(cfg, 3, 2) == want


# ------------------------------------------------------- the readers
def _run_with(events, cfg):
    cell = types.SimpleNamespace(
        config=cfg, family=types.SimpleNamespace(flops=flops))
    return types.SimpleNamespace(
        cell=cell, measured={"horizons": [(0.1, ev) for ev in events]})


def _event(**kw):
    return dict({"t_fetched": 1.0, "t_round": 0.5, "k": 2,
                 "prefill_rows": 0, "tokens_dispatched": 32,
                 "tokens_padded": 12}, **kw)


@pytest.mark.parametrize("metric,want", [
    # 20 + 30 real tokens x 6 selections x 5 layers = 1,500 pairs; 180 here
    ("routed_here_share.serve", 100.0 * 180 / 1500),
    # the decode horizon alone: 2 ticks x 20 experts x 5 layers = 200; 90 hit
    ("experts_hit_share.serve", 100.0 * 90 / 200)])
def test_readers_by_hand(metric, want):
    cfg = _real("configs", "deepseek_v2_ep8")
    read = cells.Cell.reader(types.SimpleNamespace(here=cells.HERE), metric)
    events = [_event(expert_assignments=70, experts_hit=90),
              _event(expert_assignments=110, experts_hit=170,
                     prefill_rows=3, tokens_dispatched=64,
                     tokens_padded=34)]
    assert read(_run_with(events, cfg)) == pytest.approx(want)
    # a program that does not count (the parent's) gives nothing
    assert read(_run_with([_event()], cfg)) is None
    assert read(_run_with([], cfg)) is None
