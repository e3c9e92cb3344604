"""The FLOP and byte functions against counts made by hand."""
import json
import os

import pytest

import bench_tiny
from benchmark.flops import bert, common, gpt


def _cfg(name):
    with open(os.path.join(bench_tiny.REPO, "benchmark", "configs",
                           name + ".json")) as f:
        return json.load(f)


def test_gpt_1p3b_training_flops_per_token():
    cfg = _cfg("gpt3_1p3b")
    # by hand: 24 layers x (2 x 12 x 2048^2 [qkv, proj, fc1, fc2 hold
    # 12 h^2 weights] + 2 x 1024 x 2048 causal attention) + tied head
    per_layer = 2 * 12 * 2048 ** 2 + 2 * 1024 * 2048
    fwd = 24 * per_layer + 2 * 2048 * 50304
    assert gpt.forward_flops_per_token(cfg, 1024) == fwd
    got = gpt.train_flops_per_token(cfg, {"batch": 8, "seq": 1024})
    assert got == 3 * fwd
    assert 8.1e9 < got < 8.3e9


def test_bert_large_counts_only_what_real_tokens_need():
    cfg = _cfg("bert_large")
    layers = 24 * 2 * 12 * 1024 ** 2
    head = 2 * 1024 ** 2 + 2 * 1024 * 30522
    assert bert.forward_flops_per_token(cfg, 512) == \
        layers + 24 * 4 * 512 * 1024 + head
    # two rows of 256 and 512 tokens that are not padding: 768 tokens, a
    # token attends over its own row (256 x 256 + 512 x 512 pairs), the
    # head at 15% of the tokens and each row's first
    tr = {"batch": 2, "seq": 512, "lengths": [256, 512], "mask_share": 0.15}
    attn = 24 * 4 * 1024 * (256 ** 2 + 512 ** 2) / 768
    labelled = 0.15 * 768 + 0.85 * 2
    assert bert.train_flops_per_token(cfg, tr) == pytest.approx(
        3 * (layers + attn + head * labelled / 768))
    # padding adds positions and no work: the same rows padded further
    assert bert.train_flops_per_token(cfg, dict(tr, seq=1024)) == \
        bert.train_flops_per_token(cfg, tr)


def test_flash_and_xent_calls():
    ops, nbytes = common.flash_call("fwd", 8, 16, 1024, 1024, 128, True)
    assert ops == 2 * 2 * 8 * 16 * 1024 * 1024 * 128 / 2
    assert nbytes == 4 * 8 * 16 * 1024 * 128 * 2
    full, _ = common.flash_call("bwd_dkv", 1, 1, 4, 4, 2, False)
    assert full == 2 * 4 * 16 * 2
    ops, nbytes = common.xent_call("bwd", 8192, 50304)
    assert nbytes == 2 * 8192 * 50304 * 4
    peak = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert common.roofline_seconds(1000.0, 50.0, peak) == 10.0   # compute
    assert common.roofline_seconds(100.0, 50.0, peak) == 5.0     # bytes


def test_serve_flops_counts_every_processed_token_once():
    cfg = _cfg("gpt3_1p3b")
    one = gpt.serve_flops(cfg, 1, 1)       # one token in, one out
    h, f = 2048, 8192
    assert one == 24 * 2 * (4 * h * h + 2 * h * f) + 24 * 4 * h \
        + 2 * h * 50304
