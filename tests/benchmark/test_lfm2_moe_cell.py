"""The LFM2-MoE family through the harness on the CPU: a tiny configuration
(no published width) appended to the copy `make_root` makes, never to
`tiny/BENCHMARK.json`; the real configuration's file against the published
config; the real cell as specified; the FLOP and byte counts against
hand counts; the new reader."""
import json
import os
import types

import numpy as np
import pytest

import bench_tiny
from benchmark import cells, faults, records
from benchmark.flops import lfm2_moe as flops

CELL = "lfm2_moe_tiny.serve_tiny_conv"
REAL = "lfm2_24b_a2b_pp5.serve_wave48_late16_chat"
READER = "decode_weight_roofline.serve"
# the published config.json, as the model-configs catalog copies it
PUBLISHED_TYPES = (["conv", "conv"] + ["full_attention", "conv", "conv",
                                       "conv"] * 9 + ["full_attention", "conv"])
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 11776, "layer_types": PUBLISHED_TYPES,
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1536, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts": 64, "num_experts_per_tok": 4,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True,
    "vocab_size": 65536}
TINY_CONFIG = dict(
    PUBLISHED, family="lfm2_moe", hidden_size=64, intermediate_size=96,
    moe_intermediate_size=32, num_attention_heads=4, num_key_value_heads=2,
    num_experts=8, n_routed_experts=8, expert_offset=0,
    num_experts_per_tok=3, num_hidden_layers=6, num_dense_layers=1,
    stage_layer_types=PUBLISHED_TYPES[1:7], vocab_size=256, dtype="float32",
    initializer_range=0.1, expert_bias_std=0.05)
TINY_TRAFFIC = {
    "generator": "waves", "answer_tokens": 8, "greedy": True,
    "groups": [
        {"name": "first", "prompt_lengths": [8, 40, 72], "send": "wave_start"},
        {"name": "late", "prompt_lengths": [24, 56],
         "send": {"when_group": "first", "has_tokens": 4}}]}
TINY_JOB = {
    "job": "serve_waves_token_gaps",
    "engine": {"slots": 5, "page_size": 16, "positions": 96,
               "max_new_tokens": 8, "host_sync_s": 0.001},
    "checked_requests": 3,
    # float32 both sides: the program reads 1e-5 or less, bfloat16 (the
    # control one precision down) some hundredths
    "limits": {"served_logit_gap": 0.001, "served_logit_gap_mean": 1e-4,
               "tokens_outside_vocab": 0, "requests_unfinished": 0}}


@pytest.fixture(scope="module")
def root_here(tmp_path_factory):
    root, here = bench_tiny.make_root(tmp_path_factory.mktemp("bench"))
    for sub, name, data in (
            ("configs", "lfm2_moe_tiny", TINY_CONFIG),
            ("traffic", "serve_tiny_conv", TINY_TRAFFIC),
            ("workloads", CELL, TINY_JOB)):
        with open(os.path.join(here, sub, name + ".json"), "w") as f:
            json.dump(data, f)
    path = os.path.join(root, "BENCHMARK.json")
    bench = json.load(open(path))
    bench["configs"].append({
        "name": "lfm2_moe_tiny", "source": "test",
        "file": "benchmark/configs/lfm2_moe_tiny.json", "reduced": [],
        "why": "CPU test"})
    bench["workloads"].append({
        "name": CELL, "config": "lfm2_moe_tiny",
        "traffic": "serve_tiny_conv", "chips": 1, "why": "CPU test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "gpt_tiny.serve_tiny" in (m.get("workloads") or ()):
            m["workloads"].append(CELL)
    for name in (READER, "experts_hit_share.serve"):
        bench["per_layer"].append({
            "name": name, "unit": "%", "better": "higher",
            "source": "device_trace" if name == READER else "program_counter",
            "layer": "step program", "moves": "serve_tokens_per_s",
            "workloads": [CELL]})
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


def test_a_state_reset_every_tick_comes_out_not_correct(root_here,
                                                        monkeypatch):
    """The conv layers' per-slot state read as zeros at every tick (a
    decode row then convolves as if it had just begun, a chunk as if it
    were the prompt's first), played through the tiny cell."""
    from paddle_tpu.models import lfm2_moe as lfm
    real = lfm.packed_conv_taps

    def reset(z, state, rows, pos, row_new):
        return real(z, state * 0, rows, pos, row_new)

    monkeypatch.setattr(lfm, "packed_conv_taps", reset)
    result = bench_tiny.run(root_here, CELL)
    gap = result["compared"]["served_logit_gap"]
    assert result["correct"] is False and gap["value"] > gap["limit"], gap


def test_control_one_precision_down_reads_over_the_limit(root_here):
    """The reference in bfloat16 operands (float32 is what the tiny
    configuration states) puts tokens first that lie further under the
    float32 reference's best than the limit; float32 against itself 0.
    The bias decides a share of the selections, and rounding moves a
    share too."""
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
    assert 0.0 < ref.bias_moves_selection(cfg, params, prompt + tokens) < 1.0


# ----------------------------------------------------- the real files
def _real(sub, name):
    with open(os.path.join(bench_tiny.REPO, "benchmark", sub,
                           name + ".json")) as f:
        return json.load(f)


def test_real_configuration_keeps_every_published_key():
    """Every key of the published config unchanged but the two under
    `reduced`, each with its published value beside it; the stage, the
    deployment and the assumed values stated."""
    cfg = _real("configs", "lfm2_24b_a2b_pp5")
    entry = next(c for c in cells.load_benchmark()["configs"]
                 if c["name"] == "lfm2_24b_a2b_pp5")
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"]) == [
        "num_dense_layers", "num_hidden_layers"]
    for key, value in PUBLISHED.items():
        if key in cfg["reduced"]:
            assert cfg["reduced"][key]["published"] == value
            assert cfg["reduced"][key]["run"] == cfg[key] != value
        else:
            assert cfg[key] == value, key
    assert (cfg["num_hidden_layers"], cfg["num_dense_layers"]) == (9, 1)
    # the stage's nine, which follow from the cut: published layers 1-9,
    # the second dense conv layer and two periods
    from benchmark.reference import lfm2_moe as ref
    assert ref.layer_types(cfg) == cfg["stage_layer_types"] == [
        "conv", "full_attention", "conv", "conv", "conv", "full_attention",
        "conv", "conv", "conv"] == PUBLISHED_TYPES[1:10]
    assert cfg["n_routed_experts"] == cfg["num_experts"] == 64
    assert cfg["expert_offset"] == 0 and cfg["family"] == "lfm2_moe"
    assert cfg["expert_bias_std"] == 0.01
    assert "five chips as pipeline stages" in cfg["deployment"]
    assert {"initializer_range", "expert_bias", "head", "dtype",
            "source_values"} <= set(cfg["assumed"])
    assert entry["source"] == cfg["source"] == (
        "https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json")
    assert entry["file"] == "benchmark/configs/lfm2_24b_a2b_pp5.json"


def test_real_cell_is_as_specified():
    bench = cells.load_benchmark()
    entry = next(w for w in bench["workloads"] if w["name"] == REAL)
    assert entry == dict(entry, config="lfm2_24b_a2b_pp5", chips=1,
                         traffic="serve_wave48_late16_chat")
    assert "64 slots" in entry["why"] and len(entry["why"]) <= 200
    traffic = _real("traffic", "serve_wave48_late16_chat")
    first, late = traffic["groups"]
    assert traffic["generator"] == "waves" and traffic["greedy"] is True
    assert first["send"] == "wave_start"
    assert first["prompt_lengths"] == [
        64, 64, 96, 96, 128, 128, 128, 160, 160, 192, 192, 192, 224, 224,
        256, 256, 256, 256, 320, 320, 320, 384, 384, 384, 448, 448, 512,
        512, 512, 576, 640, 640, 704, 768, 768, 896, 896, 1024, 1024, 1152,
        1280, 1280, 1408, 1536, 1536, 1792, 2048, 2048]
    assert late["prompt_lengths"] == [
        128, 192, 256, 256, 384, 384, 512, 512, 640, 768, 896, 1024, 1024,
        1280, 1536, 2048]
    assert late["send"] == {"when_group": "first", "has_tokens": 32}
    assert (len(first["prompt_lengths"]), sum(first["prompt_lengths"]),
            len(late["prompt_lengths"]), sum(late["prompt_lengths"])) == (
        48, 29632, 16, 11840)
    assert np.median(first["prompt_lengths"]) == 416
    assert traffic["answer_tokens"] == 256
    job = _real("workloads", REAL)
    assert job["job"] == "serve_waves_token_gaps"
    assert job["engine"] == {"slots": 64, "page_size": 16,
                             "positions": 2304, "max_new_tokens": 256,
                             "host_sync_s": 0.001}
    assert job["engine"]["positions"] == 2048 + traffic["answer_tokens"]
    assert job["checked_requests"] >= 5
    assert set(job["limits"]) == {"served_logit_gap", "served_logit_gap_mean",
                                  "tokens_outside_vocab",
                                  "requests_unfinished"}
    cell = cells.Cell(REAL)
    assert [m["name"] for m in cell.end_to_end()] == ["serve_tokens_per_s",
                                                      "setup_s"]
    # The cell's own entries, found by name; what later PRs append (configs,
    # cells, metrics, names on a metric's list) is not this test's to hold
    names = {m["name"] for m in cell.per_layer()}
    assert {"pad_share.serve", "mfu.serve", "device_idle_share.serve",
            "decode_tick_ms_p50.serve", "sched_round_ms_p50.serve",
            "device_wait_share.serve", "slot_occupancy.serve",
            "experts_hit_share.serve", READER} <= names
    assert "routed_here_share.serve" not in names
    # the grouped walk's page copies, counted as the GPT walk's are
    gather = next(m for m in bench["per_layer"]
                  if m["name"] == "gather_live_share.serve")
    assert REAL in gather["workloads"] and "gather_live_share.serve" in names
    assert all(m["moves"] == "serve_tokens_per_s" for m in cell.per_layer())
    own = next(m for m in bench["per_layer"] if m["name"] == READER)
    assert {k: v for k, v in own.items() if k != "workloads"} == {
        "name": READER, "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "step program",
        "moves": "serve_tokens_per_s"}
    assert own["workloads"][0] == REAL


def test_limit_parts_the_readings_taken_on_the_chip():
    """`data/readings.<cell>.jsonl` holds what `rehearse/serve_faults.py`
    and the cell's own runs read on a v5e at the cell's own size (PERF.md
    section 6): held to the limits in the cell's file every sound run of
    the program is correct, and so is the reference in the program's own
    precision (bfloat16 operands); every run of the control (operands
    rounded to fp8) and of each fault played there is not. The mean gap
    parts the program's largest reading from the control's least by 3x or
    more. The widest gap is shown and holds no limit, because no limit
    lies between its two readings: the sound program's largest lies over
    the control's least."""
    from benchmark import compare
    job = _real("workloads", REAL)
    limits = job["limits"]
    with open(os.path.join(bench_tiny.HERE, "data",
                           f"readings.{REAL}.jsonl")) as f:
        rows = [r for r in map(json.loads, f) if "numbers" in r]
    # the first call's readings hold the widest gap alone
    held = [r for r in rows if "served_logit_gap_mean" in r["numbers"]]
    assert {r["kind"] for r in held} == {
        "program", "control.fp8", "reference.bf16", "fault.bias_ignored",
        "fault.kv_head_h_mod_8", "fault.state_reset_every_tick",
        "fault.qk_norm_dropped"}
    for r in held:
        ok, shown = compare.judge(r["numbers"],
                                  {k: limits[k] for k in r["numbers"]})
        assert ok == (r["kind"] in ("program", "reference.bf16")), (
            r["kind"], r["seed"], shown)

    def parted(name):
        read = [r for r in rows if name in r["numbers"]]
        program = max(r["numbers"][name] for r in read
                      if r["kind"] == "program")
        control = min(r["numbers"][name] for r in read
                      if r["kind"] == "control.fp8")
        return program, control, len({r["seed"] for r in read
                                      if r["kind"] == "program"})

    program, control, seeds = parted("served_logit_gap_mean")
    assert program < limits["served_logit_gap_mean"] < control
    assert control >= 3 * program and seeds >= 12
    program, control, seeds = parted("served_logit_gap")
    assert limits["served_logit_gap"] is None
    assert program > control and seeds >= 12


# ------------------------------------------------ FLOPs and bytes by hand
def test_published_parameters_a_token_by_hand():
    """An attention 2 x 2048 x 2048 + 2 x 2048 x 512 = 10.49M; a conv 4 x
    2048^2 + 2048 x 3 = 16.78M; the dense MLP 3 x 2048 x 11,776 = 72.35M;
    the router 2048 x 64; of the 4 experts a token selects all are held
    here, each 3 x 2048 x 1536 = 9.44M. The stage: a dense conv layer, two
    expert attention layers, six expert conv layers."""
    cfg = _real("configs", "lfm2_24b_a2b_pp5")
    attn, conv = 10_485_760, 16_783_360
    dense, one, router = 72_351_744, 9_437_184, 131_072
    assert flops.operator_params(cfg, True) == attn
    assert flops.operator_params(cfg, False) == conv
    assert flops.expert_params(cfg) == one
    want = (conv + dense) + 2 * (attn + router + 4 * one) \
        + 6 * (conv + router + 4 * one)
    assert flops.params_per_token(cfg) == want == 513_845_248
    assert flops.expert_layers(cfg) == 8
    assert flops.attention_layers(cfg) == 2
    assert flops.attention_flops_per_key(cfg) == 2 * 32 * (64 + 64)


def test_serve_flops_by_hand():
    """A prompt of 3 and 2 generated: 4 tokens processed (the last is not
    fed), attending over 1 + 2 + 3 + 4 = 10 keys in each of 2 attention
    layers, the head at 2 positions over 65,536 columns."""
    cfg = _real("configs", "lfm2_24b_a2b_pp5")
    want = (4 * 2 * 513_845_248 + 2 * 8192 * 10 + 2 * 2 * 2048 * 65536)
    assert flops.serve_flops(cfg, 3, 2) == want


def test_the_roofline_bytes_by_hand():
    """What a decode tick reads whatever its rows route to, in bfloat16:
    the head 2048 x 65,536 and the final norm; the dense conv layer
    (its norms, conv, MLP) 89,139,200; an expert attention layer's norms,
    operator, q/k norms and router 10,621,056; an expert conv layer's
    16,918,528; the eight float32 biases of 64. One expert is 18.9 MB."""
    cfg = _real("configs", "lfm2_24b_a2b_pp5")
    n = 2048 + 2048 * 65536 + 89_139_200 + 2 * 10_621_056 \
        + 6 * 16_918_528
    assert flops.decode_tick_weight_bytes(cfg) == 2 * n + 8 * 64 * 4 \
        == 692_226_560
    assert flops.expert_bytes(cfg) == 18_874_368


def test_the_programs_count_is_the_arithmetic_of_the_cut():
    """`num_params()` of the program's config of the real file against the
    arithmetic of the cut: 89,139,200 + 2 x 614,600,832 + 6 x 620,898,304 +
    the embedding and the head 268,435,456 + the final norm 2,048 =
    5,312,168,192, and the eight selection biases of 64 beside them."""
    cell = cells.Cell(REAL)
    pcfg = cell.family.program_config(cell.config)
    assert 89_139_200 + 2 * 614_600_832 + 6 * 620_898_304 \
        + 268_435_456 + 2048 == 5_312_168_192
    assert pcfg.num_params() == 5_312_168_192 + 8 * 64
    assert pcfg.kv_width * 2 * 2 == 4096            # pool bytes a token
    shapes = cell.family.reference.leaf_shapes(cell.config)
    assert sum(int(np.prod(s)) for s in shapes.values()) == pcfg.num_params()


# ------------------------------------------------------- the readers
TRACE = os.path.join(bench_tiny.HERE, "data",
                     "trace_ops.lfm2_decode_ticks.json.gz")


def _recorded_trace():
    """A v5e trace of the real cell (PERF.md section 6):
    the last chunk execution of a wave's first group (bucket 512), three
    decode executions (bucket 64) and the loop that opens the fourth; the
    texts of all but the `while`s cut to their short names. Beside them
    the three executions' device seconds as read off the whole trace."""
    import gzip
    with gzip.open(TRACE, "rt") as f:
        rec = json.load(f)
    ops = [(s, e, rec["texts"][t], rec["texts"][t]) for s, e, t in rec["ops"]]
    return ops, rec["decode_seconds"]


def _run_with(events, cfg, ops=None):
    cell = types.SimpleNamespace(
        config=cfg, family=types.SimpleNamespace(flops=flops))
    return types.SimpleNamespace(
        cell=cell, peaks={"hbm_bytes_per_s": 819e9},
        traced=None if ops is None else {"all_ops": ops},
        measured={"horizons": [(0.1, ev) for ev in events]})


def _event(**kw):
    return dict({"t_round": 0.0, "t_fetched": 0.1, "k": 4,
                 "prefill_rows": 0, "experts_hit": 2000}, **kw)


def _reader(name):
    return cells.Cell.reader(types.SimpleNamespace(here=cells.HERE), name)


def test_readers_by_hand():
    """The roofline over the recorded trace: its three decode executions,
    told apart by the packed layout's loop, took the device seconds read
    off the whole trace, and two decode horizons of the window hit 480 and
    500 experts a tick: (fixed bytes + 490 x one expert) over the mean
    seconds at 819 GB/s. The chunk execution before them is not counted.
    The DeepSeek cell's `experts_hit_share.serve`, unedited, reads 6,000 of
    64 experts x 8 layers x 12 ticks for this configuration."""
    cfg = _real("configs", "lfm2_24b_a2b_pp5")
    ops, seconds = _recorded_trace()
    read = _reader(READER)
    assert len(seconds) == 3 and all(0.018 < s < 0.019 for s in seconds)
    events = [_event(k=1, t_tokens=512, prefill_rows=2, experts_hit=512),
              _event(k=1, t_tokens=64, experts_hit=480),
              _event(k=1, t_tokens=64, experts_hit=500)]
    want = 100.0 * (692_226_560 + 490 * 18_874_368) * 3 / sum(seconds) \
        / 819e9
    assert read(_run_with(events, cfg, ops)) == pytest.approx(want)
    assert 55.0 < want < 70.0
    # nothing to read: no trace; a program that does not count (the
    # parent's); the decode bucket with a chunk row in it; two ticks a
    # horizon; a trace with no execution of the decode bucket
    bare = [{k: v for k, v in ev.items() if k != "experts_hit"}
            for ev in events]
    mixed = events + [_event(k=1, t_tokens=64, prefill_rows=1)]
    twice = [dict(ev, k=2) for ev in events]
    for evs, trace in ((events, None), (bare, ops), (mixed, ops),
                       (twice, ops), ([], ops),
                       (events, [op for op in ops if "while(" not in op[3]])):
        assert read(_run_with(evs, cfg, trace)) is None
    hit = _reader("experts_hit_share.serve")
    events = [_event(),
              _event(t_round=0.05, t_fetched=0.3, k=8, experts_hit=4000),
              _event(t_round=0.3, t_fetched=0.9, prefill_rows=2)]
    assert hit(_run_with(events, cfg)) == pytest.approx(
        100.0 * 6000 / (64 * 8 * 12))


def test_the_tiny_cells_traced_metrics_read_the_record(root_here):
    """The counts' readers give a share of the record the tiny cell's window
    left; the roofline, which takes its seconds from the device trace,
    reads nothing where there is none (a CPU run has no device plane)."""
    import importlib
    from benchmark import run as harness
    root, here = root_here
    cell = cells.Cell(CELL, root=root, here=here)
    run = harness.Run(cell, bench_tiny.SEED, 0.5, 0,
                      {"bf16_flops": float("nan"), "hbm_bytes_per_s": 1e9})
    importlib.import_module(f"benchmark.jobs.{cell.job['job']}").run(run)
    assert run.correct
    names = [m["name"] for m in cell.per_layer()]
    assert READER in names and "experts_hit_share.serve" in names
    assert run.traced is None and cell.reader(READER)(run) is None
    assert 0.0 < cell.reader("experts_hit_share.serve")(run) <= 100.0
    # the grouped walk ends at the deepest row's block of 8 pages, as the
    # GPT walk does: a tick copies slots x whole blocks (at most the
    # table's width, the `_p<width>` of the program's name) a layer
    gather = cells.Cell.reader(types.SimpleNamespace(here=cells.HERE),
                               "gather_live_share.serve")
    events = records.horizons(run)
    for ev in events:
        width = int(ev["program"].rsplit("_p", 1)[1])
        walked = ev["pages_gathered"] // ev["slots"]
        assert walked * ev["slots"] == ev["pages_gathered"]
        assert walked == width or (walked % 8 == 0 and walked < width)
        assert 0 <= ev["pages_live"] <= ev["pages_gathered"]
    assert gather(run) == pytest.approx(
        100.0 * sum(ev["k"] * ev["pages_live"] for ev in events)
        / sum(ev["k"] * ev["pages_gathered"] for ev in events))
    assert 0.0 < gather(run) <= 100.0


def test_every_fault_the_family_plants_reads_over_the_limit(
        root_here, tmp_path, monkeypatch):
    """`rehearse/serve_faults.py` over the tiny cell: the sound program
    and the control read as the cell's limit says (under it, over it),
    and each of the four faults the family plants (the conv state reset
    every tick, key/value head h % kv_heads, the q/k norm dropped, the
    selection bias ignored) reads over it; the bias moves a share of the
    selections."""
    from benchmark.rehearse import serve_faults
    root, here = root_here
    real = cells.Cell
    monkeypatch.setattr(serve_faults.cells, "Cell",
                        lambda name: real(name, root=root, here=here))
    monkeypatch.setattr(serve_faults, "check_device", lambda chips: None)
    monkeypatch.chdir(tmp_path)
    monkeypatch.setattr("sys.argv", ["serve_faults", CELL, "--weights", "1",
                                     "--traffic", "1", "--checked", "3"])
    serve_faults.main()
    with open(tmp_path / "chiprun_out" / f"readings.{CELL}.jsonl") as f:
        rows = {r["kind"]: r for r in map(json.loads, f)}
    limit = TINY_JOB["limits"]["served_logit_gap"]
    gap = {k: r["numbers"]["served_logit_gap"] for k, r in rows.items()
           if "numbers" in r}
    assert gap["program"] <= limit < gap["control.bf16"], gap
    faults_read = {k for k in gap if k.startswith("fault.")}
    assert faults_read == {"fault.state_reset_every_tick",
                           "fault.kv_head_h_mod_8", "fault.qk_norm_dropped",
                           "fault.bias_ignored"}
    assert all(gap[k] > limit for k in faults_read), gap
    assert 0.0 < rows["selection"]["bias_moves"] < 1.0
