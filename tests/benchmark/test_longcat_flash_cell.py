"""The LongCat-Flash family through the harness on the CPU: a tiny
configuration (no published width) appended to the copy `make_root` makes,
never to `tiny/BENCHMARK.json`; the real configuration's file against the
published config; the real cell against its issue; the FLOP count against
hand counts; the new reader."""
import dataclasses
import json
import os
import types

import numpy as np
import pytest

import bench_tiny
from benchmark import cells, faults
from benchmark.flops import longcat_flash as flops

CELL = "longcat_flash_tiny.serve_tiny_double"
REAL = "longcat_flash_ep32.serve_wave24_late8_out256"
READER = "zero_routed_share.serve_waves"
# the published config.json, as the model-configs catalog copies it
PUBLISHED = {
    "attention_bias": False, "vocab_size": 131072, "hidden_size": 6144,
    "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048,
    "num_layers": 28, "num_attention_heads": 64, "kv_lora_rank": 512,
    "q_lora_rank": 1536, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "qk_nope_head_dim": 128, "mla_scale_q_lora": True,
    "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
    "n_routed_experts": 512, "max_position_embeddings": 131072,
    "rms_norm_eps": 1e-05, "rope_theta": 10000000,
    "attention_method": "MLA", "zero_expert_num": 256,
    "zero_expert_type": "identity", "moe_topk": 12}
TINY_CONFIG = dict(
    PUBLISHED, family="longcat_flash", hidden_size=64, ffn_hidden_size=96,
    expert_ffn_hidden_size=32, num_layers=2, num_attention_heads=4,
    kv_lora_rank=32, q_lora_rank=48, qk_rope_head_dim=8, v_head_dim=16,
    qk_nope_head_dim=16, routed_scaling_factor=3.0, n_routed_experts=4,
    expert_offset=4, router_width=24, zero_expert_num=8, moe_topk=4,
    vocab_size=256, rope_theta=10000.0, dtype="float32",
    initializer_range=0.1)
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
            ("configs", "longcat_flash_tiny", TINY_CONFIG),
            ("traffic", "serve_tiny_double", TINY_TRAFFIC),
            ("workloads", CELL, TINY_JOB)):
        with open(os.path.join(here, sub, name + ".json"), "w") as f:
            json.dump(data, f)
    path = os.path.join(root, "BENCHMARK.json")
    bench = json.load(open(path))
    bench["configs"].append({
        "name": "longcat_flash_tiny", "source": "test",
        "file": "benchmark/configs/longcat_flash_tiny.json", "reduced": [],
        "why": "CPU test"})
    bench["workloads"].append({
        "name": CELL, "config": "longcat_flash_tiny",
        "traffic": "serve_tiny_double", "chips": 1, "why": "CPU test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "gpt_tiny.serve_tiny" in (m.get("workloads") or ()):
            m["workloads"].append(CELL)
    for name in (READER, "experts_hit_share.serve"):
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


def _with_faulty_block(root_here, monkeypatch, experts_of):
    """The tiny cell with the family's block replaced in the decoder the
    cell builds: `experts_of(moe, cfg)` -> the expert branch to use in
    place of `moe(u, cfg)` -> (s, counts)."""
    from paddle_tpu.models import longcat_flash as lc

    class Faulty(lc.Serving):
        @staticmethod
        def block(cfg, kind, x, wl, seg, ri, attend, valid):
            w = dict(wl, **{k: seg[k] for k in lc._EXPERT_KEYS})

            def moe(u, cfg_):
                return lc.longcat_moe(w, u, cfg_, valid=valid, layer=ri)

            return lc.longcat_block(cfg, x, wl, attend,
                                    lambda u: experts_of(moe, cfg)(u))

    family = cells.Cell(CELL, root=root_here[0], here=root_here[1]).family
    build = family.build_decoder

    def faulty(cfg, seed, job):
        dec = build(cfg, seed, job)
        dec.family = Faulty
        return dec

    monkeypatch.setattr(family, "build_decoder", faulty)
    return bench_tiny.run(root_here, CELL)


def test_a_dropped_shortcut_branch_comes_out_not_correct(root_here,
                                                         monkeypatch):
    """The layer's own topology fault, planted in the decoder's block (the
    expert branch computed and never added back) and played through the
    tiny cell."""
    def dropped(moe, cfg):
        def experts(u):
            s, counts = moe(u, cfg)
            return s * 0, counts
        return experts

    result = _with_faulty_block(root_here, monkeypatch, dropped)
    gap = result["compared"]["served_logit_gap"]
    assert result["correct"] is False and gap["value"] > gap["limit"], gap


def test_a_dropped_identity_sum_comes_out_not_correct(root_here,
                                                      monkeypatch):
    """The identity experts' `u x sum(w)` left out (a router that takes
    every column for an expert of another chip): the held experts' pairs
    are all there, and the cell still says not correct."""
    def no_identity(moe, cfg):
        blind = dataclasses.replace(cfg, n_routed_experts=cfg.router_width,
                                    zero_expert_num=0)
        return lambda u: moe(u, blind)

    result = _with_faulty_block(root_here, monkeypatch, no_identity)
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
    cfg = _real("configs", "longcat_flash_ep32")
    entry = next(c for c in cells.load_benchmark()["configs"]
                 if c["name"] == "longcat_flash_ep32")
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"]) == [
        "n_routed_experts", "num_layers", "vocab_size"]
    for key, value in PUBLISHED.items():
        if key in cfg["reduced"]:
            assert cfg["reduced"][key]["published"] == value
            assert cfg["reduced"][key]["run"] == cfg[key] != value
        else:
            assert cfg[key] == value, key
    assert (cfg["num_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (4, 16, 16384)
    # the floors: four layers, eight experts, an eighth of the vocabulary
    assert cfg["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert cfg["router_width"] == (PUBLISHED["n_routed_experts"]
                                   + PUBLISHED["zero_expert_num"]) == 768
    assert cfg["expert_offset"] == 0 and cfg["family"] == "longcat_flash"
    assert "32 chips share each layer" in cfg["deployment"]
    assert {"initializer_range", "e_score_correction_bias",
            "norm_topk_prob", "router_bias", "rotary_pairing", "dtype",
            "source_values"} <= set(cfg["assumed"])
    assert entry["source"] == cfg["source"] == (
        "https://huggingface.co/meituan-longcat/LongCat-Flash-Chat/blob/"
        "main/config.json")
    assert entry["file"] == "benchmark/configs/longcat_flash_ep32.json"


def test_real_cell_is_the_issues():
    bench = cells.load_benchmark()
    entry = next(w for w in bench["workloads"] if w["name"] == REAL)
    assert entry == dict(entry, config="longcat_flash_ep32", chips=1,
                         traffic="serve_wave24_late8_out256")
    assert "32 rows" in entry["why"] and len(entry["why"]) <= 200
    traffic = _real("traffic", "serve_wave24_late8_out256")
    first, late = traffic["groups"]
    assert traffic["generator"] == "waves" and traffic["greedy"] is True
    assert first["send"] == "wave_start"
    assert first["prompt_lengths"] == [
        128, 128, 192, 192, 256, 256, 256, 384, 384, 384, 512, 512, 512,
        512, 640, 640, 768, 768, 896, 1024, 1024, 1280, 1536, 2048]
    assert late["prompt_lengths"] == [256, 384, 512, 512, 768, 1024, 1536,
                                      2048]
    assert late["send"] == {"when_group": "first", "has_tokens": 32}
    assert (len(first["prompt_lengths"]), sum(first["prompt_lengths"]),
            len(late["prompt_lengths"]), sum(late["prompt_lengths"])) == (
        24, 15232, 8, 7040)
    assert traffic["answer_tokens"] == 256
    job = _real("workloads", REAL)
    assert job["job"] == "serve_waves"
    assert job["engine"] == {"slots": 32, "page_size": 16,
                             "positions": 2304, "max_new_tokens": 256,
                             "host_sync_s": 0.001}
    assert job["engine"]["positions"] == 2048 + traffic["answer_tokens"]
    assert job["checked_requests"] >= 5
    assert set(job["limits"]) == {"served_logit_gap", "tokens_outside_vocab",
                                  "requests_unfinished"}
    cell = cells.Cell(REAL)
    assert [m["name"] for m in cell.end_to_end()] == ["serve_tokens_per_s",
                                                      "setup_s"]
    # The cell's own entries, found by name; what later PRs append (configs,
    # cells, metrics, names on a metric's list) is not this test's to hold
    assert {"pad_share.serve", "mfu.serve", "device_idle_share.serve",
            "decode_tick_ms_p50.serve", "sched_round_ms_p50.serve",
            "device_wait_share.serve", "slot_occupancy.serve",
            "experts_hit_share.serve", READER} <= {
        m["name"] for m in cell.per_layer()}
    assert all(m["moves"] == "serve_tokens_per_s" for m in cell.per_layer())
    own = next(m for m in bench["per_layer"] if m["name"] == READER)
    assert {k: v for k, v in own.items() if k != "workloads"} == {
        "name": READER, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "step program",
        "moves": "serve_tokens_per_s"}
    assert own["workloads"][0] == REAL


def test_limit_parts_the_readings_taken_on_the_chip():
    """`data/readings.<cell>.jsonl` holds what `rehearse/readings.py` read
    on a v5e at the cell's own size (PERF.md section 6): held to the limit
    in the cell's file every sound run of the program is correct and every
    run of the control (operands rounded to fp8) is not."""
    from benchmark import compare
    job = _real("workloads", REAL)
    limits, limit = job["limits"], job["limits"]["served_logit_gap"]
    with open(os.path.join(bench_tiny.HERE, "data",
                           f"readings.{REAL}.jsonl")) as f:
        rows = [r for r in map(json.loads, f) if r["kind"] != "schedule"]
    assert {r["kind"] for r in rows} == {"program", "control.fp8"}
    for r in rows:
        ok, shown = compare.judge(r["numbers"],
                                  {k: limits[k] for k in r["numbers"]})
        assert ok == (r["kind"] == "program"), (r["kind"], r["seed"], shown)
    program = max(r["numbers"]["served_logit_gap"] for r in rows
                  if r["kind"] == "program")
    control = min(r["numbers"]["served_logit_gap"] for r in rows
                  if r["kind"] == "control.fp8")
    assert program < limit < control


# ------------------------------------------------------ FLOPs by hand
def test_published_parameters_a_token_by_hand():
    """One MLA 9.44M + 18.87M + 3.54M + 8.39M + 50.33M = 90.57M; one dense
    MLP 3 x 6144 x 12288 = 226.49M; the router 6144 x 768 = 4.72M; of the
    12 selected columns an even router puts 12 x 16 / 768 = 0.25 on the 16
    experts held here, each 3 x 6144 x 2048 = 37.75M; identity experts
    nothing. Four layers of two MLAs, two MLPs and one such branch."""
    cfg = _real("configs", "longcat_flash_ep32")
    mla = (6144 * 1536 + 1536 * 64 * 192 + 6144 * 576 + 512 * 64 * 256
           + 64 * 128 * 6144)
    assert mla == 90_570_752
    dense, one = 3 * 6144 * 12288, 3 * 6144 * 2048
    assert (dense, one) == (226_492_416, 37_748_736)
    want = 4 * (2 * mla + 2 * dense + 6144 * 768 + 0.25 * one)
    assert flops.params_per_token(cfg) == want
    assert flops.expert_layers(cfg) == 4
    assert flops.attention_flops_per_key(cfg) == 2 * 64 * (192 + 128)


def test_serve_flops_by_hand():
    """A prompt of 3 and 2 generated: 4 tokens processed (the last is not
    fed), attending over 1 + 2 + 3 + 4 = 10 keys in each of 8 attentions,
    the head at 2 positions over the 16,384 columns held."""
    cfg = _real("configs", "longcat_flash_ep32")
    want = (4 * 2 * flops.params_per_token(cfg) + 8 * 40960 * 10
            + 2 * 2 * 6144 * 16384)
    assert flops.serve_flops(cfg, 3, 2) == want


def test_the_programs_count_is_the_arithmetic_of_the_cut():
    """`num_params()` of the program's config of the real file against the
    issue's arithmetic: a layer outside its experts 638.8M, 16 held
    experts 604.0M, embedding and head 201.3M; the norms' gains and the
    router's bias buffer (29,440 a layer and 6,144) are the difference."""
    cell = cells.Cell(REAL)
    pcfg = cell.family.program_config(cell.config)
    mla, dense, one = 90_570_752, 226_492_416, 37_748_736
    layer = 2 * (mla + dense) + 6144 * 768 + 16 * one
    assert 2 * (mla + dense) + 6144 * 768 == 638_844_928
    assert layer == 1_242_824_704
    small = 4 * (4 * 6144 + 2 * (1536 + 512) + 768) + 6144
    assert pcfg.num_params() == 4 * layer + 2 * 16384 * 6144 + small \
        == 5_172_749_312
    assert pcfg.latent_dim * 2 * 2 * 4 == 9216     # cache bytes a token
    shapes = cell.family.reference.leaf_shapes(cell.config)
    assert sum(int(np.prod(s)) for s in shapes.values()) == pcfg.num_params()


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
    # 20 + 30 real tokens x 12 selections x 4 layers = 2,400 pairs; 780 zero
    (READER, 100.0 * 780 / 2400),
    # the decode horizon alone: 2 ticks x 16 experts x 4 layers = 128; 48 hit
    ("experts_hit_share.serve", 100.0 * 48 / 128)])
def test_readers_by_hand(metric, want):
    """The new reader; and the DeepSeek cell's `experts_hit_share.serve`,
    unedited, gives the hand count for this configuration too (it reads
    `n_routed_experts` as held and `flops.expert_layers`), which is what
    the real cell reads on it."""
    cfg = _real("configs", "longcat_flash_ep32")
    read = cells.Cell.reader(types.SimpleNamespace(here=cells.HERE), metric)
    events = [_event(zero_assignments=300, experts_hit=48),
              _event(zero_assignments=480, experts_hit=170,
                     prefill_rows=3, tokens_dispatched=64,
                     tokens_padded=34)]
    assert read(_run_with(events, cfg)) == pytest.approx(want)
    # a program that does not count (the parent's, another family's)
    # gives nothing
    assert read(_run_with([_event()], cfg)) is None
    assert read(_run_with([], cfg)) is None


def test_the_tiny_cells_traced_metrics_read_the_record(root_here):
    """Both counters' readers give a share of the record the tiny cell's
    window left (no trace here: the readers take the horizon records)."""
    import importlib
    from benchmark import run as harness
    root, here = root_here
    cell = cells.Cell(CELL, root=root, here=here)
    run = harness.Run(cell, bench_tiny.SEED, 0.5, 0,
                      {"bf16_flops": float("nan"),
                       "hbm_bytes_per_s": float("nan")})
    importlib.import_module(f"benchmark.jobs.{cell.job['job']}").run(run)
    assert run.correct
    names = [m["name"] for m in cell.per_layer()]
    assert READER in names and "experts_hit_share.serve" in names
    zero = cell.reader(READER)(run)
    # 8 identity columns of 24: a third under an even router
    assert 15.0 < zero < 55.0
    assert 0.0 < cell.reader("experts_hit_share.serve")(run) <= 100.0
    events = [ev for _, ev in run.measured["horizons"]]
    real = sum(ev["tokens_dispatched"] - ev["tokens_padded"]
               for ev in events)
    assert zero == pytest.approx(100.0 * sum(
        ev["zero_assignments"] for ev in events) / (real * 4 * 2))
