"""The control of each kind of cell comes out not correct: the plain
reference put in the program's place and computed one precision below the
one the configuration states (fp8 for bfloat16), held to the limits the tiny
cells use. And each plain reference agrees with the program's own model at
tiny size."""
import numpy as np
import pytest

import bench_tiny
from benchmark import cells, compare, generate
from benchmark.reference import train as ref_train


@pytest.fixture(scope="module")
def root_here(tmp_path_factory):
    return bench_tiny.make_root(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("name", ["gpt_tiny.pretrain_tiny",
                                  "bert_tiny.mlm_tiny"])
def test_training_control_fails_a_number(root_here, name):
    root, here = root_here
    cell = cells.Cell(name, root=root, here=here)
    ref = cell.family.reference
    gen = generate.of(cell.traffic)(cell.traffic, cell.config, bench_tiny.SEED)
    batches = [next(gen) for _ in range(3)]
    follow = lambda prec: ref_train.follow(           # noqa: E731
        ref, cell.config, cell.job["optimizer"], bench_tiny.SEED, batches,
        prec=prec)
    sound, control = follow("f32"), follow("fp8")
    numbers = compare.train_numbers(control, sound)
    ok, shown = compare.judge(numbers, cell.job["limits"])
    assert not ok, shown
    # and the reference against itself is exact
    again = compare.train_numbers(follow("f32"), sound)
    assert all(v == 0 for v in again.values() if isinstance(v, float))


def test_serving_control_reads_a_gap_over_the_limit(root_here):
    """At each position of the same tokens, the token fp8 puts first lies
    further under the reference's best than the limit allows; bfloat16,
    which the configuration states, stays under it."""
    root, here = root_here
    cell = cells.Cell("gpt_tiny.serve_tiny", root=root, here=here)
    ref, cfg = cell.family.reference, cell.config
    limit = cell.job["limits"]["served_logit_gap"]
    params = ref.init_params(cfg, bench_tiny.SEED)
    rng = np.random.default_rng(0)
    gaps = {"f32": 0.0, "bf16": 0.0, "fp8": 0.0}
    for _ in range(4):
        prompt = rng.integers(0, cfg["vocab_size"], 28).tolist()
        tokens = rng.integers(0, cfg["vocab_size"], 100).tolist()
        for prec in gaps:
            gaps[prec] = max(gaps[prec], float(np.asarray(ref.served_gaps(
                cfg, params, prompt, tokens, 128, control=prec)).max()))
    assert gaps["f32"] == 0.0
    assert gaps["bf16"] <= limit < gaps["fp8"], gaps


def test_gpt_reference_agrees_with_the_programs_model(root_here):
    import jax.numpy as jnp

    import paddle_tpu as paddle

    root, here = root_here
    cell = cells.Cell("gpt_tiny.pretrain_tiny", root=root, here=here)
    cfg = dict(cell.config, dtype="float32")
    model = cell.family.build_model(cfg, 5, cell.job)
    model.eval()
    ids = np.random.default_rng(1).integers(0, cfg["vocab_size"], (1, 48))
    got = np.asarray(model(paddle.to_tensor(ids.astype("int32")))._value)
    ref = cell.family.reference
    want = np.asarray(ref.served_rows_logits(
        cfg, ref.init_params(cfg, 5), ids[0], 0, 48))
    assert jnp.allclose(got[0], want, atol=2e-4), \
        float(np.abs(got[0] - want).max())


def _recorded(cell):
    import json
    import os

    path = os.path.join(bench_tiny.HERE, "data", f"readings.{cell}.jsonl")
    with open(path) as f:
        return [json.loads(line) for line in f]


@pytest.mark.parametrize("cell", ["gpt3_1p3b.pretrain_bs8_s1024",
                                  "bert_large.pretrain_mlm_s512",
                                  "gpt3_1p3b.serve_wave5_late3"])
def test_limits_part_the_readings_taken_on_the_chip(cell):
    """`data/readings.<cell>.jsonl` holds what `rehearse/readings.py` read on
    a v5e at the cell's own size (PERF.md gives them beside each limit).
    Held to the limits in the cell's file, every sound run of the program
    is correct, and every run of the control (fp8) and of the fault (half of
    the batch left out) is not."""
    limits = cells.Cell(cell).job["limits"]
    rows = _recorded(cell)
    kinds = {r["kind"] for r in rows}
    assert "program" in kinds and any(k.startswith("control.") for k in kinds)
    for r in rows:
        held = {k: v for k, v in limits.items() if k in r["numbers"]}
        ok, shown = compare.judge(r["numbers"], held)
        assert ok == (r["kind"] == "program"), (r["kind"], r["seed"], shown)
