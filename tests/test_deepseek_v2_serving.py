"""DeepSeek-V2 through the serving engine at a tiny preset (no published
width), seeded weights, on the CPU in float32: the latent (MLA) paged
cache, absorbed decode beside materialised prefill, the dropless
group-limited expert layer told which experts it holds, against the plain
reference `benchmark/reference/deepseek_v2.py`.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import deepseek_v2 as family
from benchmark.reference import deepseek_v2 as ref
from paddle_tpu.models.deepseek_v2 import (DeepSeekV2, DeepSeekV2Config,
                                           _block_full, deepseek_v2_tiny,
                                           group_limited_route, moe_ffn,
                                           yarn_inv_freq)
from paddle_tpu.ops.ragged_paged_attention import mla_paged_attention_packed
from paddle_tpu.serving import (ContinuousBatchingEngine, PrefixCache,
                                SpeculativeEngine)
from paddle_tpu.serving.mla_decoder import PagedMLADecoder

YARN = {"type": "yarn", "factor": 4, "beta_fast": 32, "beta_slow": 1,
        "mscale": 0.707, "mscale_all_dim": 0.707,
        "original_max_position_embeddings": 64}
# the configuration file's keys at tiny sizes: 16 experts in 4 groups of 4,
# group 1 (experts 4-7) held here
TINY = {
    "family": "deepseek_v2", "vocab_size": 96, "hidden_size": 32,
    "num_hidden_layers": 3, "num_attention_heads": 4, "q_lora_rank": 24,
    "kv_lora_rank": 16, "qk_nope_head_dim": 8, "qk_rope_head_dim": 4,
    "v_head_dim": 8, "intermediate_size": 48, "moe_intermediate_size": 16,
    "n_shared_experts": 2, "router_width": 16, "n_routed_experts": 4,
    "expert_offset": 4, "num_experts_per_tok": 3, "n_group": 4,
    "topk_group": 2, "routed_scaling_factor": 4.0, "norm_topk_prob": False,
    "first_k_dense_replace": 1, "moe_layer_freq": 1, "rms_norm_eps": 1e-6,
    "rope_theta": 10000, "rope_scaling": YARN,
    "max_position_embeddings": 256, "dtype": "float32",
    "initializer_range": 0.2, "router_init_std": 0.2}
PUBLISHED_YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                  "mscale": 0.707, "mscale_all_dim": 0.707,
                  "original_max_position_embeddings": 4096, "type": "yarn"}
SEED = 3000000019


def _decoder(cfg=TINY, slots=4, page_size=8, pages=8, **kw):
    model = family.build_model(cfg, SEED, {})
    return PagedMLADecoder(model, num_pages=slots * pages + 2,
                           page_size=page_size, max_batch=slots,
                           max_pages_per_seq=pages, **kw)


@pytest.fixture(scope="module")
def served():
    """Five prompts (three at once, two joining rows that decode) through
    the engine's default path: chunks of 16 tokens, then decode."""
    dec = _decoder()
    eng = ContinuousBatchingEngine(dec, max_new_tokens=6, chunk_tokens=16)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, TINY["vocab_size"], n).tolist()
               for n in (5, 17, 40, 9, 33)]
    rids = [eng.submit(p) for p in prompts[:3]]
    late = list(prompts[3:])

    def on_sync(e):
        if late and all(len(e._outputs.get(r, ())) >= 2 for r in rids[:3]):
            rids.extend(e.submit(p) for p in late)
            late.clear()

    out = eng.run(on_sync=on_sync)
    while eng._queue:                   # the late ones, if the run had ended
        out = eng.run()
    return eng, prompts, [out[r] for r in rids]


# ------------------------------------------------ (a) engine vs reference
@pytest.mark.parametrize("request_no", range(5))
def test_served_tokens_are_the_references_best(served, request_no):
    """Chunked prefill (materialised) and then decode (absorbed) through
    the cache against the reference's full forward with no cache, on
    logits: at every position the served token's reference logit is the
    reference's best to 1e-4. Both sides are float32; the orders of
    summation differ (online softmax in blocks, absorbed products), which
    moves a logit of order 1 by some 1e-6; 1e-4 leaves that a hundredfold
    room, and a wrong position, mask or cache row moves logits by tenths."""
    eng, prompts, outs = served
    params = ref.init_params(TINY, SEED)
    gaps = np.asarray(ref.served_gaps(TINY, params, prompts[request_no],
                                      outs[request_no], 48))
    assert len(outs[request_no]) == 6
    assert gaps.max() <= 1e-4, gaps


def test_both_forms_ran_in_one_horizon(served):
    hz = served[0].serve_schedule()
    assert any(ev["absorbed_rows"] and ev["materialised_tokens"]
               for ev in hz), "no mixed horizon: the late prompts joined " \
        "no decoding row"
    assert all(ev["program"].startswith("mla_packed_multi_") for ev in hz)


def test_layer_forward_agrees_with_the_reference():
    """The program's Layer (full forward, materialised, no cache) and the
    reference on the same weights: float32 both, 2e-4 on logits of order 1
    (reduction order only)."""
    import paddle_tpu as paddle
    model = family.build_model(TINY, 5, {})
    ids = np.random.default_rng(1).integers(0, TINY["vocab_size"], (2, 24))
    got = np.asarray(model(paddle.to_tensor(ids.astype("int32")))._value)
    params = ref.init_params(TINY, 5)
    for b in range(2):
        want = np.asarray(ref.served_rows_logits(TINY, params, ids[b], 0, 24))
        assert np.abs(got[b] - want).max() <= 2e-4


# ------------------------------------- (b) the two forms on one cache
@pytest.mark.parametrize("ctx_pages", [1, 3, 8])
def test_absorbed_and_materialised_agree_on_one_cache(ctx_pages):
    """One new token a row over the same latent pool, every row absorbed
    against every row materialised: the same function (1e-5 in float32:
    the products are associated differently)."""
    cfg = deepseek_v2_tiny()
    rng = np.random.default_rng(ctx_pages)
    n, ps, H = 3, 8, cfg.num_heads
    dn, dr, dv, r = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                     cfg.v_head_dim, cfg.kv_lora_rank)
    pool = jnp.asarray(rng.normal(size=(2, n * ctx_pages + 1, ps, r + dr)),
                       jnp.float32)
    table = jnp.asarray(rng.permutation(n * ctx_pages).reshape(n, ctx_pages),
                        jnp.int32)
    pos = jnp.asarray(rng.integers(0, ctx_pages * ps, n), jnp.int32)
    q_nope = jnp.asarray(rng.normal(size=(n, H, dn)), jnp.float32)
    q_rope = jnp.asarray(rng.normal(size=(n, H, dr)), jnp.float32)
    w_kvb = jnp.asarray(rng.normal(size=(r, H, dn + dv)), jnp.float32) * 0.3
    rows = jnp.arange(n, dtype=jnp.int32)
    out = {}
    for name, flag in (("absorbed", False), ("materialised", True)):
        out[name] = np.asarray(mla_paged_attention_packed(
            q_nope, q_rope, pool, 1, w_kvb, table, rows, pos,
            jnp.ones(n, jnp.int32), jnp.full(n, flag), 0.3, window=1))
    assert np.abs(out["absorbed"]).max() > 0.1
    np.testing.assert_allclose(out["absorbed"], out["materialised"],
                               atol=1e-5, rtol=1e-5)


# ------------------------------------------- (c) the shares add up
def test_the_shares_of_all_groups_add_up_to_the_uncut_layer():
    """One expert layer (attention, then experts) held by the four chips of
    a tiny deployment, a routing group each: the parts the four shares
    give, with what every chip computes alike (attention, the shared
    experts, the residual) counted once, add up to what the reference
    gives for the uncut layer (all 16 experts held)."""
    uncut = dict(TINY, n_routed_experts=16, expert_offset=0)
    params = ref.init_params(uncut, 11)
    layer = {k: params[f"layers.1.{k}"] for k in ref.layer_leaves(uncut, 1)}
    x = jnp.asarray(np.random.default_rng(2).normal(size=(40, 32)),
                    jnp.float32)
    want = np.asarray(ref.block(layer, x, uncut, "f32", dense=False))
    # what every chip computes alike: the layer with no routed expert
    f32 = ref.f32(layer)
    h = x + ref.mla(f32, ref.rms_norm(x, f32["input_layernorm.weight"],
                                      1e-6), uncut, "f32")
    y = ref.rms_norm(h, f32["post_attention_layernorm.weight"], 1e-6)
    alike = np.asarray(h + ref.shared(f32, y, "f32"))
    total = alike.copy()
    pos = jnp.arange(40)
    for g in range(4):
        share = dict(TINY, expert_offset=4 * g)
        pcfg = family.program_config(share)
        mine = {f"layers.1.{k}": (v[4 * g:4 * g + 4]
                                  if k.startswith("mlp.experts.") else v)
                for k, v in layer.items()}
        got = np.asarray(_block_full(
            mine, "layers.1.", x, pos, pcfg,
            jnp.asarray(yarn_inv_freq(pcfg), jnp.float32), dense=False))
        total += got - alike
    assert np.abs(want - alike).max() > 0.05     # the experts do matter
    np.testing.assert_allclose(total, want, atol=2e-4)


def _hold_row_tile(monkeypatch, tile):
    """The walk chooses its tiles from its input alone (`_expert_tiles`):
    a test holds the row tile by standing in front of that one place."""
    import paddle_tpu.models.deepseek_v2 as ds
    chosen = ds._expert_tiles
    monkeypatch.setattr(ds, "_expert_tiles",
                        lambda *a: (tile,) + chosen(*a)[1:])


@pytest.mark.parametrize("tile", [4, 16, 256])
def test_no_pair_is_dropped_whatever_the_row_tile(monkeypatch, tile):
    """The held experts' grouped products go over the sorted pairs in
    tiles of rows: with tiles far smaller than an expert's load (4 rows,
    60 tokens x 3 selections over 16 experts), with tiles a few experts
    fit in, and with one tile for all, the share equals the reference's
    (which loops over experts with dense weights): nothing is dropped at
    a tile's edge and no row is counted for its neighbour's expert."""
    import paddle_tpu.models.deepseek_v2 as ds
    _hold_row_tile(monkeypatch, tile)
    cfg = dict(TINY, n_routed_experts=8, expert_offset=4)
    params = ref.init_params(cfg, 3)
    layer = ref.f32({k: params[f"layers.2.{k}"]
                     for k in ref.layer_leaves(cfg, 2)})
    x = jnp.asarray(np.random.default_rng(5).normal(size=(60, 32)),
                    jnp.float32)
    want = np.asarray(ref.moe(layer, x, cfg, "f32"))
    w = {"router": layer["mlp.gate.weight"],
         "gate": layer["mlp.experts.gate_proj"],
         "up": layer["mlp.experts.up_proj"],
         "down": layer["mlp.experts.down_proj"],
         "s_gate": layer["mlp.shared_experts.gate_proj.weight"],
         "s_up": layer["mlp.shared_experts.up_proj.weight"],
         "s_down": layer["mlp.shared_experts.down_proj.weight"]}
    got, assigned, hit = ds.moe_ffn(w, x, family.program_config(cfg))
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-4)
    held = np.asarray(ref.selected(ref.router_scores(layer, x, "f32"),
                                   cfg))[:, 4:12]
    assert int(assigned) == held.sum() > 40
    assert int(hit) == held.any(0).sum()


def test_a_zeroed_held_expert_moves_the_rows_that_selected_it_and_no_other():
    """The fault the chip's one number cannot see (PERF.md section 6) is
    held here, in float32 at 2e-4: with one held expert giving nothing the
    share differs from the reference's on exactly the rows that selected
    that expert."""
    import paddle_tpu.models.deepseek_v2 as ds
    cfg = dict(TINY, n_routed_experts=8, expert_offset=4)
    params = ref.init_params(cfg, 3)
    layer = ref.f32({k: params[f"layers.2.{k}"]
                     for k in ref.layer_leaves(cfg, 2)})
    x = jnp.asarray(np.random.default_rng(5).normal(size=(60, 32)),
                    jnp.float32)
    want = np.asarray(ref.moe(layer, x, cfg, "f32"))
    w = {"router": layer["mlp.gate.weight"],
         "gate": layer["mlp.experts.gate_proj"],
         "up": layer["mlp.experts.up_proj"],
         "down": layer["mlp.experts.down_proj"].at[2].set(0),
         "s_gate": layer["mlp.shared_experts.gate_proj.weight"],
         "s_up": layer["mlp.shared_experts.up_proj.weight"],
         "s_down": layer["mlp.shared_experts.down_proj.weight"]}
    got = np.asarray(ds.moe_ffn(w, x, family.program_config(cfg))[0])
    chose = np.asarray(ref.selected(ref.router_scores(layer, x, "f32"),
                                    cfg))[:, 4 + 2]
    off = np.abs(got - want).max(-1)
    assert 3 <= chose.sum() < 60
    assert (off[chose] > 1e-2).all() and (off[~chose] <= 2e-4).all(), off


# ----------------------------- the grouped product at its edges, by hand
# 4 experts held from column 2 of a router 10 wide; 3 columns a token
_HELD, _OFF, _K = 4, 2, 3


def _walk_against_a_dense_loop(ei, tile, monkeypatch, layers=None, layer=None):
    """`held_expert_walk` over hand-made selections `ei` [T, 3] against a
    loop over the held experts in numpy (float64); returns (routed, counts,
    want, hand counts)."""
    import paddle_tpu.models.deepseek_v2 as ds
    _hold_row_tile(monkeypatch, tile)
    ei = np.asarray(ei, np.int32)
    T = len(ei)
    rng = np.random.default_rng(7)
    lead = (_HELD,) if layers is None else (layers, _HELD)
    w = {"gate": rng.normal(size=lead + (32, 16)) * 0.3,
         "up": rng.normal(size=lead + (32, 16)) * 0.3,
         "down": rng.normal(size=lead + (16, 32)) * 0.3}
    x = rng.normal(size=(T, 32))
    cw = rng.uniform(0.1, 1.0, size=(T, _K))
    routed, counts = ds.held_expert_walk(
        {k: jnp.asarray(v, jnp.float32) for k, v in w.items()},
        jnp.asarray(x, jnp.float32), jnp.asarray(cw, jnp.float32),
        jnp.asarray(ei), _HELD, _OFF, None,
        None if layer is None else jnp.int32(layer))
    mine = w if layer is None else {k: v[layer] for k, v in w.items()}
    want = np.zeros((T, 32))
    for e in range(_HELD):
        weight = np.where(ei == _OFF + e, cw, 0.0).sum(-1, keepdims=True)
        g, u = x @ mine["gate"][e], x @ mine["up"][e]
        want += (g / (1 + np.exp(-g)) * u * weight) @ mine["down"][e]
    hand = [(ei == _OFF + e).sum() for e in range(_HELD)]
    return np.asarray(routed), np.asarray(counts), want, hand


def _selections(loads, others=0):
    """[T, 3] selections in which held expert e has `loads[e]` pairs (one
    a token, in a column that moves from token to token), `others` more
    tokens select no held expert, and every other pair is of a column not
    held (0, 1 or 6-9)."""
    away = [0, 1, 6, 7, 8, 9]
    rows = []
    for e, n in enumerate(loads):
        for _ in range(n):
            i = len(rows)
            row = [away[(i + j) % 6] for j in range(_K)]
            row[i % _K] = _OFF + e
            rows.append(row)
    rows += [[away[(i + j) % 6] for j in range(_K)] for i in range(others)]
    order = np.random.default_rng(1).permutation(len(rows))
    return np.asarray(rows)[order]


# name: (loads of the four held experts, tokens with no held pair, row tile)
_EDGES = {
    "an_expert_with_no_pair_between_two_that_have_some": ((5, 0, 6, 3), 4, 4),
    "every_pair_on_one_expert": ((0, 0, 13, 0), 3, 4),
    "no_pair_held_at_all": ((0, 0, 0, 0), 9, 4),
    "a_group_boundary_inside_a_row_tile": ((3, 2, 5, 1), 5, 8),
    "rows_no_multiple_of_the_tile": ((2, 3, 1, 1), 0, 16),
    "the_first_and_the_last_expert_alone": ((7, 0, 0, 2), 1, 4),
}


@pytest.mark.parametrize("edge", sorted(_EDGES))
def test_the_grouped_product_at_its_edges(monkeypatch, edge):
    """The kernel's grid is built from the counts: an expert with no pair
    is no group to visit (between two that have some, or every expert but
    one), no held pair at all is no tile at all and a sum of exactly zero,
    a group's boundary inside a row tile stores each row for its own
    expert, and pairs that do not fill whole tiles are padded, not lost."""
    loads, others, tile = _EDGES[edge]
    ei = _selections(loads, others)
    if edge == "rows_no_multiple_of_the_tile":
        assert (len(ei) * _K) % tile
    routed, counts, want, hand = _walk_against_a_dense_loop(
        ei, tile, monkeypatch)
    assert list(counts) == hand == list(loads)
    np.testing.assert_allclose(routed, want, atol=2e-4)
    if not sum(loads):
        assert not routed.any()
    else:
        assert np.abs(want).max() > 0.05


@pytest.mark.parametrize("layer", [0, 2])
def test_the_layer_index_picks_its_slice_of_the_stack(monkeypatch, layer):
    """Stacks [3, held, ...] reach the kernel whole; the layer's counts
    stand at `layer x held` among the groups of all three layers, so the
    first and the last layer read their own experts and no other's."""
    ei = _selections((3, 2, 5, 1), 5)
    routed, counts, want, hand = _walk_against_a_dense_loop(
        ei, 8, monkeypatch, layers=3, layer=layer)
    assert list(counts) == hand
    np.testing.assert_allclose(routed, want, atol=2e-4)
    other, *_ = _walk_against_a_dense_loop(ei, 8, monkeypatch, layers=3,
                                           layer=1)
    assert np.abs(other - want).max() > 0.05


# --------------------------------- (d) selection and YaRN by hand
def test_group_limited_selection_by_hand():
    """8 experts in 4 groups of 2, the best 2 groups, the best 3 experts,
    weights 2 x score. Scores (a softmax's, they sum to 1):
      group 0: .05 .20   group 1: .30 .01   group 2: .02 .03   group 3: .25 .14
    Group scores .20 .30 .03 .25: groups 1 and 3 stay (group 0's .20 is
    the third). Among experts 2, 3, 6, 7 the best three are 2 (.30),
    6 (.25), 7 (.14): expert 1 (.20) is larger than .14 but its group is
    shut."""
    cfg = DeepSeekV2Config(n_routed_experts=8, n_group=4, topk_group=2,
                           num_experts_per_tok=3, routed_scaling_factor=2.0)
    scores = jnp.asarray([[.05, .20, .30, .01, .02, .03, .25, .14]])
    got = np.asarray(group_limited_route(scores, cfg))[0]
    np.testing.assert_allclose(got, [0, 0, .60, 0, 0, 0, .50, .28],
                               atol=1e-7)
    want = np.asarray(ref.route(scores, {
        "n_group": 4, "topk_group": 2, "num_experts_per_tok": 3,
        "routed_scaling_factor": 2.0, "norm_topk_prob": False}))[0]
    np.testing.assert_allclose(got, want, atol=1e-7)


@pytest.mark.parametrize("which", ["program", "reference"])
def test_yarn_frequencies_by_hand(which):
    """The published values (dim 64, base 10000, factor 40, original 4096,
    beta_fast 32, beta_slow 1): cd(r) = 64 ln(4096 / (2 pi r)) / (2 ln
    10000); cd(32) = 10.47 so low = 10, cd(1) = 22.51 so high = 23.
    Dimension i <= 10 keeps f_i = 10000^(-2i/64); i >= 23 has f_i / 40;
    i = 16 is 6/13 of the way: f_16 (7/13 + 6/13 / 40)."""
    if which == "program":
        got = yarn_inv_freq(DeepSeekV2Config(rope_scaling=PUBLISHED_YARN))
    else:
        got = ref.yarn_inv_freq({"rope_scaling": PUBLISHED_YARN,
                                 "qk_rope_head_dim": 64,
                                 "rope_theta": 10000})
    f = lambda i: 10000.0 ** (-2.0 * i / 64)          # noqa: E731
    assert math.floor(64 * math.log(4096 / (2 * math.pi * 32))
                      / (2 * math.log(10000))) == 10
    assert math.ceil(64 * math.log(4096 / (2 * math.pi))
                     / (2 * math.log(10000))) == 23
    assert got.shape == (32,)
    for i in (0, 5, 10):
        assert got[i] == pytest.approx(f(i), rel=1e-12)
    for i in (23, 27, 31):
        assert got[i] == pytest.approx(f(i) / 40, rel=1e-12)
    assert got[16] == pytest.approx(f(16) * (7 / 13 + 6 / 13 / 40),
                                    rel=1e-12)


def test_softmax_scale_and_rope_gain_by_hand():
    """scale = 192^-0.5 m^2 with m = 0.1 x 0.707 x ln 40 + 1 = 1.2608;
    cos and sin times mscale(40, .707) / mscale(40, .707) = 1."""
    from paddle_tpu.models.deepseek_v2 import rope_gain, softmax_scale
    cfg = DeepSeekV2Config(rope_scaling=PUBLISHED_YARN)
    m = 0.1 * 0.707 * math.log(40) + 1
    assert softmax_scale(cfg) == pytest.approx(192 ** -0.5 * m * m)
    assert rope_gain(cfg) == 1.0
    full = dict(TINY, rope_scaling=PUBLISHED_YARN, qk_nope_head_dim=128,
                qk_rope_head_dim=64)
    assert ref.softmax_scale(full) == pytest.approx(softmax_scale(cfg))


# ------------------------------------------- (e) the record's counts
def test_record_counters_against_hand_counts():
    """Every expert held and every one of them selected by every token
    (4 groups, all open, 16 of 16 experts a token), 2 expert layers,
    prompts of 5 and 11 tokens, 4 tokens an answer, chunks of 8:
    materialised_tokens are the prompts' 16; absorbed rows are the 2 x 3
    decode steps after each request's first token (which its last chunk
    gives); every token processed (16 + 6 = 22) is 16 assignments in each
    of 2 layers; every tick hits all 16 experts in both layers."""
    cfg = dict(TINY, n_routed_experts=16, expert_offset=0, topk_group=4,
               num_experts_per_tok=16)
    dec = _decoder(cfg, slots=2)
    eng = ContinuousBatchingEngine(dec, max_new_tokens=4, chunk_tokens=8)
    for n in (5, 11):
        eng.submit(list(range(1, n + 1)))
    out = eng.run()
    assert all(len(v) == 4 for v in out.values())
    hz = eng.serve_schedule()
    ticks = sum(ev["k"] for ev in hz)
    total = {k: sum(ev[k] for ev in hz) for k in dec.horizon_counters}
    real = sum(ev["tokens_dispatched"] - ev["tokens_padded"] for ev in hz)
    assert real == 22
    assert total == {"materialised_tokens": 16, "absorbed_rows": 6,
                     "expert_assignments": 22 * 16 * 2,
                     "experts_hit": 16 * 2 * ticks}
    # the first horizon is two ticks: both prompts' first chunks (5 + 8),
    # then the longer prompt's last 3 beside the other's first decode step
    assert (hz[0]["k"], hz[0]["materialised_tokens"],
            hz[0]["absorbed_rows"]) == (2, 16, 1)


def test_counters_count_only_held_experts():
    """moe_ffn by hand: 3 tokens, 8 experts in 4 groups, the chip holds
    experts 2-3 (group 1). Router weights are chosen so that token 0
    selects experts {2, 6}, token 1 {3, 2}, token 2 {0, 7}: held pairs are
    (0,2), (1,3), (1,2): 3 assignments, 2 experts hit; with token 1 not
    valid: 1 assignment, 1 expert hit."""
    cfg = DeepSeekV2Config(hidden_size=8, moe_intermediate_size=4,
                           n_routed_experts=8, n_group=4, topk_group=2,
                           num_experts_per_tok=2, experts_held=2,
                           expert_offset=2, n_shared_experts=1,
                           dtype="float32")
    x = jnp.eye(3, 8, dtype=jnp.float32)            # token t reads row t
    router = np.zeros((8, 8), np.float32)
    router[0, [2, 6]] = [3.0, 2.0]
    router[1, [3, 2]] = [3.0, 2.0]
    router[2, [0, 7]] = [3.0, 2.0]
    rng = np.random.default_rng(0)
    w = {"router": jnp.asarray(router),
         "gate": jnp.asarray(rng.normal(size=(2, 8, 4)), jnp.float32),
         "up": jnp.asarray(rng.normal(size=(2, 8, 4)), jnp.float32),
         "down": jnp.asarray(rng.normal(size=(2, 4, 8)), jnp.float32),
         "s_gate": jnp.zeros((8, 4)), "s_up": jnp.zeros((8, 4)),
         "s_down": jnp.zeros((4, 8))}
    y, assigned, hit = moe_ffn(w, x, cfg)
    assert (int(assigned), int(hit)) == (3, 2)
    assert np.abs(np.asarray(y)[2]).max() == 0.0    # token 2: none held
    assert np.abs(np.asarray(y)[0]).max() > 0.0
    _, assigned, hit = moe_ffn(w, x, cfg,
                               valid=jnp.asarray([True, False, True]))
    assert (int(assigned), int(hit)) == (1, 1)


# ------------------------------------------------ (f) what it refuses
@pytest.mark.parametrize("option", [
    dict(quant="a8w8"), dict(quant="w4a16"), dict(kv_quant="int8"),
    dict(kv_quant="int4"), dict(use_kernel=True), dict(temperature=0.8),
    dict(top_k=5), dict(top_p=0.9), dict(mesh=object()),
    dict(dtype="bfloat16")])
def test_decoder_refuses_at_construction(option):
    with pytest.raises(NotImplementedError, match=next(iter(option))):
        _decoder(**option)


@pytest.fixture(scope="module")
def plain_decoder():
    return _decoder()


@pytest.mark.parametrize("option", [
    dict(prefix_cache=True), dict(prefix_cache=PrefixCache(8)),
    dict(prefix_cache=True, host_tier=True), dict(ragged=False),
    dict(k_max=1)])
def test_engine_refuses_what_the_decoder_cannot_serve(plain_decoder, option):
    with pytest.raises(NotImplementedError, match="does not serve"):
        ContinuousBatchingEngine(plain_decoder, max_new_tokens=4, **option)


def test_speculation_is_refused_on_either_side(plain_decoder):
    with pytest.raises(NotImplementedError, match="speculation"):
        SpeculativeEngine(plain_decoder, plain_decoder)


def test_the_cache_and_the_audit_meet_a_pool_they_do_not_know(
        plain_decoder, tmp_path):
    from paddle_tpu.analysis.memory import audit_kv_scale_planes
    with pytest.raises(NotImplementedError, match="k_pages"):
        PrefixCache(8).save(str(tmp_path), decoder=plain_decoder)
    with pytest.raises(NotImplementedError, match="k_pages"):
        PrefixCache.load(str(tmp_path), plain_decoder)
    assert audit_kv_scale_planes(plain_decoder, [0, 1]) == []


def test_no_adapters_and_no_other_program(plain_decoder):
    assert not hasattr(plain_decoder, "attach_adapters")
    with pytest.raises(NotImplementedError):
        plain_decoder.program_name("ragged", 1, 1, 8)
    with pytest.raises(NotImplementedError):
        plain_decoder.ragged_multi(
            np.zeros(4), np.zeros(4), np.zeros((4, 8)), 1, 1,
            np.zeros((4, 64)), np.zeros(4), aids=np.ones(4))


# ------------------------------------------------- the pool's arithmetic
def test_latent_pool_layout_and_bytes(plain_decoder):
    """[layers, pages, page_size, rank + rope in whole lanes of 128] in
    the compute dtype; a token is latent_dim x itemsize a layer, the
    lanes' padding not counted. At the published widths in bfloat16:
    (512 + 64) x 2 = 1,152 B a layer, stored in 640 values."""
    d, cfg = plain_decoder, plain_decoder.cfg
    assert d.cache.shape == (3, 34, 8, 128)     # 16 + 4 values in a lane
    assert d.kv_token_bytes == 20 * 4
    assert d.kv_page_bytes == 3 * 8 * 80
    assert d.pend_capacity == 64
    assert d.step_hbm_bytes(avg_ctx=10, batch=2) == \
        cfg.num_params() * 4 + 2 * 10 * 3 * 80
    from paddle_tpu.serving.mla_decoder import latent_token_bytes
    assert latent_token_bytes(DeepSeekV2Config()) == 1152
    assert not hasattr(d, "k_pages")


@pytest.mark.parametrize("release", [False, True])
def test_the_decoder_leaves_the_layer_unless_told_to_release_it(release):
    """The caller's Layer stays whole and usable; with `release_model` the
    decoder takes its arrays as it stacks them (one set on the device) and
    the Layer ends empty. The stacks are the Layer's values either way."""
    model = family.build_model(TINY, SEED, {})
    before = {k: np.asarray(p._value) for k, p in model.named_parameters()}
    n = sum(v.size for v in before.values())
    dec = PagedMLADecoder(model, num_pages=10, page_size=8, max_batch=2,
                          max_pages_per_seq=4, release_model=release)
    left = [p._value for _, p in model.named_parameters()]
    if release:
        assert all(v is None for v in left)
    else:
        assert all(v is not None for v in left)
        import paddle_tpu as paddle
        ids = paddle.to_tensor(np.arange(6, dtype=np.int32)[None])
        assert np.isfinite(np.asarray(model(ids)._value)).all()
    held = sum(v.size for v in jax.tree_util.tree_leaves(dec.weights))
    assert held == n == dec.cfg.num_params()
    moe = dec.weights["segments"][1]
    for i in range(moe["gate"].shape[0]):
        np.testing.assert_array_equal(
            np.asarray(moe["gate"][i]),
            before[f"layers.{i + 1}.mlp.experts.gate_proj"])
    np.testing.assert_array_equal(np.asarray(dec.weights["head"]),
                                  before["lm_head.weight"])


def test_copy_page_moves_every_layers_rows(plain_decoder):
    d = plain_decoder
    d.cache = d.cache.at[:, 3].set(7.0)
    d.copy_page(3, 5)
    assert float(jnp.abs(d.cache[:, 5] - 7.0).max()) == 0.0
    assert d.cache_fingerprint() == d.cache_fingerprint()
