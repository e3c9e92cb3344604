"""LongCat-Flash through the serving engine at a tiny preset (no published
width), seeded weights, on the CPU in float32: a double layer of two latent
attentions (two cache entries a layer) and two dense MLPs, the
shortcut-connected expert branch with identity experts in a dropless
softmax top-k router, told which experts it holds, against the plain
reference `benchmark/reference/longcat_flash.py`.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import longcat_flash as family
from benchmark.reference import longcat_flash as ref
from paddle_tpu.models import deepseek_v2 as ds
from paddle_tpu.models.longcat_flash import (LongCatFlashConfig, Serving,
                                             block_full, longcat_moe,
                                             zero_expert_route)
from paddle_tpu.serving import (ContinuousBatchingEngine, PrefixCache,
                                SpeculativeEngine)
from paddle_tpu.serving.mla_decoder import FAMILIES, PagedMLADecoder

# the configuration file's keys at tiny sizes: 8 experts and then 4
# identity experts (12 columns), 3 a token, experts 2-5 held here
TINY = {
    "family": "longcat_flash", "vocab_size": 96, "hidden_size": 32,
    "ffn_hidden_size": 48, "expert_ffn_hidden_size": 16, "num_layers": 2,
    "num_attention_heads": 4, "kv_lora_rank": 16, "q_lora_rank": 24,
    "qk_rope_head_dim": 4, "v_head_dim": 8, "qk_nope_head_dim": 8,
    "mla_scale_q_lora": True, "mla_scale_kv_lora": True,
    "routed_scaling_factor": 3.0, "n_routed_experts": 4, "expert_offset": 2,
    "router_width": 12, "zero_expert_num": 4, "moe_topk": 3,
    "max_position_embeddings": 256, "rms_norm_eps": 1e-5,
    "rope_theta": 10000.0, "dtype": "float32", "initializer_range": 0.2}
SEED = 3000000019


def _decoder(cfg=TINY, slots=4, page_size=8, pages=8, **kw):
    model = family.build_model(cfg, SEED, {})
    return PagedMLADecoder(model, num_pages=slots * pages + 2,
                           page_size=page_size, max_batch=slots,
                           max_pages_per_seq=pages, **kw)


def _serve(dec, prompts, late=(), on_sync=None, new=6):
    """`prompts` at once, `late` once each of them has 2 tokens."""
    eng = ContinuousBatchingEngine(dec, max_new_tokens=new, chunk_tokens=16)
    rids = [eng.submit(p) for p in prompts]
    late = list(late)

    def sync(e):
        if late and all(len(e._outputs.get(r, ())) >= 2 for r in rids):
            rids.extend(e.submit(p) for p in late)
            late.clear()
        if on_sync is not None:
            on_sync(e)

    out = eng.run(on_sync=sync)
    while eng._queue:                   # the late ones, if the run had ended
        out = eng.run()
    return eng, [out[r] for r in rids]


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, TINY["vocab_size"], n).tolist() for n in lengths]


@pytest.fixture(scope="module")
def served():
    """Five prompts (three at once, two joining rows that decode) through
    the engine's default path: chunks of 16 tokens, then decode."""
    prompts = _prompts((5, 17, 40, 9, 33))
    eng, outs = _serve(_decoder(), prompts[:3], prompts[3:])
    return eng, prompts, outs


# ------------------------------------------------ (a) engine vs reference
@pytest.mark.parametrize("request_no", range(5))
def test_served_tokens_are_the_references_best(served, request_no):
    """Chunked prefill (materialised) and then decode (absorbed) through
    the cache's two entries a layer against the reference's full forward
    with no cache, on logits: at every position the served token's
    reference logit is the reference's best to 1e-4 (float32 both sides;
    the orders of summation differ by some 1e-6 on logits of order 1; a
    wrong position, mask, scale or cache entry moves logits by tenths)."""
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
        assert np.abs(want).max() > 0.1
        assert np.abs(got[b] - want).max() <= 2e-4


def test_the_reference_in_parts_is_the_reference_whole():
    """`hidden_states` runs a layer as five programs that convert only the
    leaves they read; `block` is the same layer in one piece."""
    params = ref.init_params(TINY, 7)
    ids = np.random.default_rng(2).integers(0, TINY["vocab_size"], 24)
    x = params["embed_tokens.weight"][jnp.asarray(ids)]
    for i in range(TINY["num_layers"]):
        x = ref.block(ref._layer_params(params, i), x, TINY, "f32")
    np.testing.assert_allclose(
        np.asarray(ref.hidden_states(TINY, params, ids)), np.asarray(x),
        atol=1e-5)


# -------------------------------------------------- (b) the router by hand
def _route_both(logits, bias, topk=3, scaling=2.0, zeros=2):
    """Combine weights [T, width] of the program's router and of the
    reference's over the same logits and bias."""
    logits, bias = jnp.asarray(logits, jnp.float32), jnp.asarray(bias)
    width = logits.shape[-1]
    cfg = LongCatFlashConfig(n_routed_experts=width - zeros,
                             zero_expert_num=zeros, moe_topk=topk,
                             routed_scaling_factor=scaling)
    cw, ei = zero_expert_route(logits, bias, cfg)
    got = np.zeros(logits.shape, np.float32)
    np.put_along_axis(got, np.asarray(ei), np.asarray(cw), -1)
    import jax
    want = np.asarray(ref.route(
        jax.nn.softmax(logits, -1), bias,
        {"moe_topk": topk, "routed_scaling_factor": scaling}))
    return got, want


def test_router_by_hand_with_identity_columns_selected():
    """6 columns (4 experts, then 2 identity experts), 3 a token, weights
    2 x p. Logits ln of (.05, .30, .10, .05 | .35, .15): p is those
    numbers; the best three are columns 4 (.35, identity), 1 (.30) and
    5 (.15, identity): two of the three selected pairs cost no expert."""
    p = np.asarray([[.05, .30, .10, .05, .35, .15]])
    got, want = _route_both(np.log(p), np.zeros(6, np.float32))
    np.testing.assert_allclose(got[0], [0, .60, 0, 0, .70, .30], atol=1e-6)
    np.testing.assert_allclose(got, want, atol=1e-6)


def test_the_bias_moves_a_selection_and_not_a_weight():
    """The same scores with a bias of +.10 on column 2: p + bias is (.05,
    .30, .20, .05, .35, .15), so column 2 (.20) takes column 5's (.15)
    place; its weight is 2 x its SCORE .10, not 2 x .20, and the other
    two weights are as before."""
    p = np.asarray([[.05, .30, .10, .05, .35, .15]])
    bias = np.asarray([0, 0, .10, 0, 0, 0], np.float32)
    got, want = _route_both(np.log(p), bias)
    np.testing.assert_allclose(got[0], [0, .60, .20, 0, .70, 0], atol=1e-6)
    np.testing.assert_allclose(got, want, atol=1e-6)


# ------------------------------------------- (c) the shares add up
def _one_layer(cfg, seed, layer=1):
    params = ref.init_params(cfg, seed)
    return {k: params[f"layers.{layer}.{k}"] for k in ref.layer_leaves()}


def test_the_shares_of_all_chips_add_up_to_the_uncut_layer():
    """One double layer held by the four chips of a tiny deployment, two
    experts each: the parts the four shares give, with what every chip
    computes alike (both attentions, both dense MLPs, the residual, and
    the identity experts' sum) counted once, add up to what the reference
    gives for the uncut layer (all 8 experts held)."""
    uncut = dict(TINY, n_routed_experts=8, expert_offset=0)
    layer = _one_layer(uncut, 11)
    x = jnp.asarray(np.random.default_rng(2).normal(size=(40, 32)),
                    jnp.float32)
    want = np.asarray(ref.block(layer, x, uncut, "f32"))
    # what every chip computes alike: the layer with no routed expert
    alike = np.asarray(ref.block(layer, x, uncut, "f32", held=0))
    no_branch = np.asarray(ref.block(layer, x, uncut, "f32", held=0,
                                     identity=False))
    total = alike.copy()
    pos = jnp.arange(40)
    for c in range(4):
        share = dict(TINY, n_routed_experts=2, expert_offset=2 * c)
        pcfg = family.program_config(share)
        mine = {f"layers.1.{k}": (v[2 * c:2 * c + 2]
                                  if k.startswith("mlp.experts.") else v)
                for k, v in layer.items()}
        got = np.asarray(block_full(
            mine, "layers.1.", x, pos, pcfg,
            jnp.asarray(ds.yarn_inv_freq(pcfg), jnp.float32)))
        total += got - alike
    assert np.abs(want - alike).max() > 0.05     # the experts do matter
    assert np.abs(alike - no_branch).max() > 0.05    # and the identity sum
    np.testing.assert_allclose(total, want, atol=2e-4)


def _moe_weights(layer):
    return {"router": layer["mlp.router.classifier.weight"],
            "bias": layer[ref.BIAS],
            "gate": layer["mlp.experts.gate_proj"],
            "up": layer["mlp.experts.up_proj"],
            "down": layer["mlp.experts.down_proj"]}


@pytest.mark.parametrize("tile", [4, 16, 256])
def test_the_branch_is_the_references_whatever_the_walks_row_tile(
        monkeypatch, tile):
    """The expert branch alone (router, the held experts' walk, the
    identity sum) against the reference's dense loop over experts, with
    row tiles far smaller than an expert's load, with a few experts to a
    tile, and with one tile for all; the counters against the
    reference's selection."""
    chosen = ds._expert_tiles
    monkeypatch.setattr(ds, "_expert_tiles",
                        lambda *a: (tile,) + chosen(*a)[1:])
    cfg = dict(TINY, n_routed_experts=5, expert_offset=3)
    layer = ref.f32(_one_layer(cfg, 3))
    u = jnp.asarray(np.random.default_rng(5).normal(size=(60, 32)),
                    jnp.float32)
    want = np.asarray(ref.moe(layer, u, cfg, "f32"))
    got, (assigned, hit, zero) = longcat_moe(
        _moe_weights(layer), u, family.program_config(cfg))
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-4)
    chose = np.asarray(ref.selected(ref.router_scores(layer, u, "f32"),
                                    layer[ref.BIAS], cfg))
    assert chose.sum() == 60 * 3
    assert int(assigned) == chose[:, 3:8].sum() > 30
    assert int(hit) == chose[:, 3:8].any(0).sum()
    assert int(zero) == chose[:, 8:].sum() > 30


def test_a_zeroed_held_expert_moves_the_rows_that_selected_it_and_no_other():
    """With one held expert giving nothing the branch differs from the
    reference's on exactly the rows that selected that expert."""
    cfg = dict(TINY, n_routed_experts=5, expert_offset=3)
    layer = ref.f32(_one_layer(cfg, 3))
    u = jnp.asarray(np.random.default_rng(5).normal(size=(60, 32)),
                    jnp.float32)
    want = np.asarray(ref.moe(layer, u, cfg, "f32"))
    w = _moe_weights(layer)
    w["down"] = w["down"].at[2].set(0)
    got = np.asarray(longcat_moe(w, u, family.program_config(cfg))[0])
    chose = np.asarray(ref.selected(ref.router_scores(layer, u, "f32"),
                                    layer[ref.BIAS], cfg))[:, 3 + 2]
    off = np.abs(got - want).max(-1)
    assert 3 <= chose.sum() < 60
    assert (off[chose] > 1e-3).all() and (off[~chose] <= 2e-4).all(), off


def test_a_dropped_identity_sum_moves_the_rows_with_an_identity_pair():
    """What the identity experts give is `u x sum(w)`: the branch with
    that sum left out (the reference's `identity=False`) differs from the
    program's on exactly the rows that selected an identity column."""
    layer = ref.f32(_one_layer(TINY, 3))
    u = jnp.asarray(np.random.default_rng(6).normal(size=(60, 32)),
                    jnp.float32)
    got = np.asarray(longcat_moe(_moe_weights(layer), u,
                                 family.program_config(TINY))[0])
    without = np.asarray(ref.moe(layer, u, TINY, "f32", identity=False))
    chose = np.asarray(ref.selected(ref.router_scores(layer, u, "f32"),
                                    layer[ref.BIAS], TINY))[:, 8:].any(-1)
    off = np.abs(got - without).max(-1)
    assert 3 <= chose.sum() < 60
    assert (off[chose] > 1e-3).all() and (off[~chose] <= 2e-4).all(), off


# ------------------------------------- (d) two cache entries a layer
def test_the_pool_has_an_entry_for_each_attention():
    """[2 x layers, pages, page_size, rank + rope]; a token costs
    latent_dim x itemsize an attention, twice that a layer. After a
    prompt of 13 tokens every one of the four entries holds 13 written
    rows at the request's pages, and no two entries hold the same."""
    dec = _decoder()
    assert dec.cache.shape == (4, 34, 8, 128)     # 16 + 4 values in a lane
    assert dec.kv_token_bytes == 20 * 4
    assert dec.kv_token_bytes_by_layer() == [160, 160]
    assert dec.kv_page_bytes == 2 * 2 * 8 * 80
    assert dec.step_hbm_bytes(avg_ctx=10, batch=2) == \
        dec.cfg.num_params() * 4 + 2 * 10 * 2 * 160
    eng = ContinuousBatchingEngine(dec, max_new_tokens=1, chunk_tokens=16)
    eng.submit(_prompts((13,))[0])
    eng.run()
    pool = np.asarray(dec.cache)[:, :-1]     # the last page is scrap
    written = np.abs(pool).max(-1) > 0              # [entries, pages, ps]
    assert (written.sum((1, 2)) == 13).all()
    assert (written == written[0]).all()
    for a in range(4):
        for b in range(a + 1, 4):
            assert np.abs(pool[a] - pool[b]).max() > 1e-2, (a, b)


def test_an_attention_that_reads_its_twins_entry_is_not_the_reference():
    """Mid-run, once every request decodes, entries 0 and 1 (layer 0's two
    attentions) change places: each attention now reads the other's rows
    for the context, and the tokens served from there on are no longer the
    reference's best (the untouched run's are: test (a))."""
    prompts = _prompts((21, 30))
    dec = _decoder()
    swapped = []

    def swap(e):
        if not swapped and all(len(v) >= 2 for v in e._outputs.values()):
            p = dec.cache
            dec.cache = p.at[jnp.asarray([0, 1])].set(
                p[jnp.asarray([1, 0])])
            swapped.append(True)

    _, outs = _serve(dec, prompts, on_sync=swap, new=8)
    assert swapped
    params = ref.init_params(TINY, SEED)
    worst = max(float(np.asarray(ref.served_gaps(
        TINY, params, p, o, 48)).max()) for p, o in zip(prompts, outs))
    assert worst > 1e-2, worst


def test_two_attentions_on_one_entry_are_not_the_reference(monkeypatch):
    """A family whose block sends both of a layer's attentions to entry 0:
    the second overwrites the first's rows, and what is served is not the
    reference's."""
    class OneEntry(Serving):
        @staticmethod
        def block(cfg, kind, x, wl, seg, ri, attend, valid):
            return Serving.block(cfg, kind, x, wl, seg, ri,
                                 lambda j, y, w: attend(0, y, w), valid)

    monkeypatch.setitem(FAMILIES, "longcat_flash", OneEntry)
    prompts = _prompts((21, 30))
    _, outs = _serve(_decoder(), prompts, new=8)
    params = ref.init_params(TINY, SEED)
    worst = max(float(np.asarray(ref.served_gaps(
        TINY, params, p, o, 48)).max()) for p, o in zip(prompts, outs))
    assert worst > 1e-2, worst


# ------------------------------------------------ (e) the LoRA scales
def test_lora_scales_by_hand():
    """Published: (6144 / 1536)^0.5 = 2 on every query, (6144 / 512)^0.5 =
    3.4641 on the normed latent. `mla_project` with them against without:
    both parts of the query double, the latent's normed part is 3.4641 x,
    the shared rotary key is as it was."""
    cfg = LongCatFlashConfig()
    assert cfg.q_lora_scale == 2.0
    assert cfg.kv_lora_scale == pytest.approx(12 ** 0.5)
    assert ref.lora_scales({"hidden_size": 6144, "q_lora_rank": 1536,
                            "kv_lora_rank": 512, "mla_scale_q_lora": True,
                            "mla_scale_kv_lora": True}) == (
        2.0, pytest.approx(12 ** 0.5))
    tiny = family.program_config(TINY)
    plain = family.program_config(dict(TINY, mla_scale_q_lora=False,
                                       mla_scale_kv_lora=False))
    assert (plain.q_lora_scale, plain.kv_lora_scale) == (1.0, 1.0)
    rng = np.random.default_rng(0)
    w = {"q_a": rng.normal(size=(32, 24)), "q_a_ln": np.ones(24),
         "q_b": rng.normal(size=(24, 4 * 12)),
         "kv_a": rng.normal(size=(32, 20)), "kv_a_ln": np.ones(16)}
    w = {k: jnp.asarray(v, jnp.float32) for k, v in w.items()}
    y = jnp.asarray(rng.normal(size=(5, 32)), jnp.float32)
    inv = jnp.asarray(ds.yarn_inv_freq(tiny), jnp.float32)
    pos = jnp.arange(5)
    a = [np.asarray(v) for v in ds.mla_project(w, y, pos, tiny, inv)]
    b = [np.asarray(v) for v in ds.mla_project(w, y, pos, plain, inv)]
    qs, ks = (32 / 24) ** 0.5, (32 / 16) ** 0.5
    np.testing.assert_allclose(a[0], qs * b[0], rtol=1e-5)
    np.testing.assert_allclose(a[1], qs * b[1], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(a[2][:, :16], ks * b[2][:, :16], rtol=1e-5)
    np.testing.assert_allclose(a[2][:, 16:], b[2][:, 16:], rtol=1e-6)


# ------------------------------------------- (f) the record's counts
def test_record_counters_against_hand_counts():
    """Every expert held and every column selected by every token (12 of
    12: 8 experts and 4 identity experts), 2 layers, prompts of 5 and 11
    tokens, 4 tokens an answer, chunks of 8: materialised_tokens are the
    prompts' 16; absorbed rows are the 2 x 3 decode steps after each
    request's first token; every token processed (16 + 6 = 22) is 8
    assignments on held experts and 4 identity pairs in each of 2 layers;
    every tick hits all 8 experts in both layers."""
    cfg = dict(TINY, n_routed_experts=8, expert_offset=0, moe_topk=12)
    dec = _decoder(cfg, slots=2)
    assert dec.horizon_counters == (
        "expert_assignments", "experts_hit", "zero_assignments",
        "absorbed_rows", "materialised_tokens")
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
                     "expert_assignments": 22 * 8 * 2,
                     "zero_assignments": 22 * 4 * 2,
                     "experts_hit": 8 * 2 * ticks}


def test_counters_count_held_experts_identity_pairs_and_real_tokens_only():
    """`longcat_moe` by hand: 3 tokens, 6 experts and then 2 identity
    experts, 2 a token, the chip holds experts 2-3. Router weights are
    chosen so that token 0 selects {2, 6}, token 1 {3, 2}, token 2 {7, 0}
    (6 and 7 are the identity experts): held pairs are (0,2), (1,3),
    (1,2): 3 assignments, 2 experts hit, 2 identity pairs; with token 1
    not valid: 1 assignment, 1 expert hit, 2 identity pairs. Token 2 gets
    its identity share of itself and nothing else."""
    cfg = LongCatFlashConfig(hidden_size=8, expert_ffn_hidden_size=4,
                             n_routed_experts=6, zero_expert_num=2,
                             moe_topk=2, experts_held=2, expert_offset=2,
                             routed_scaling_factor=1.0, dtype="float32")
    u = jnp.eye(3, 8, dtype=jnp.float32)            # token t reads row t
    router = np.zeros((8, 8), np.float32)
    router[0, [2, 6]] = [3.0, 2.0]
    router[1, [3, 2]] = [3.0, 2.0]
    router[2, [7, 0]] = [3.0, 2.0]
    rng = np.random.default_rng(0)
    w = {"router": jnp.asarray(router), "bias": jnp.zeros(8),
         "gate": jnp.asarray(rng.normal(size=(2, 8, 4)), jnp.float32),
         "up": jnp.asarray(rng.normal(size=(2, 8, 4)), jnp.float32),
         "down": jnp.asarray(rng.normal(size=(2, 4, 8)), jnp.float32)}
    s, counts = longcat_moe(w, u, cfg)
    assert tuple(map(int, counts)) == (3, 2, 2)
    p7 = np.exp(3.0) / (np.exp(3.0) + np.exp(2.0) + 6)
    np.testing.assert_allclose(np.asarray(s)[2], p7 * np.asarray(u)[2],
                               atol=1e-6)
    _, counts = longcat_moe(w, u, cfg,
                            valid=jnp.asarray([True, False, True]))
    assert tuple(map(int, counts)) == (1, 1, 2)


# ------------------------------------------------ (g) what it refuses
@pytest.mark.parametrize("option", [
    dict(quant="a8w8"), dict(kv_quant="int8"), dict(use_kernel=True),
    dict(temperature=0.8), dict(top_k=5), dict(top_p=0.9),
    dict(mesh=object()), dict(dtype="bfloat16")])
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


def test_speculation_is_refused(plain_decoder):
    with pytest.raises(NotImplementedError, match="speculation"):
        SpeculativeEngine(plain_decoder, plain_decoder)


def test_the_decoder_walks_the_familys_table_and_knows_no_model():
    """DeepSeek-V2 is the first entry, LongCat-Flash the second and
    LFM2-MoE the third; a config of a family the table lacks is refused
    by name."""
    assert list(FAMILIES) == ["deepseek_v2", "longcat_flash", "lfm2_moe"]
    assert FAMILIES["deepseek_v2"].cache_entries == 1
    assert FAMILIES["longcat_flash"].cache_entries == 2
    model = family.build_model(TINY, SEED, {})
    model.cfg.family = "no_such_family"
    with pytest.raises(KeyError, match="no_such_family"):
        PagedMLADecoder(model, num_pages=10, page_size=8, max_batch=2,
                        max_pages_per_seq=4)


def test_the_decoder_holds_every_parameter_once():
    model = family.build_model(TINY, SEED, {})
    n = sum(int(np.prod(p._value.shape))
            for _, p in model.named_parameters())
    dec = PagedMLADecoder(model, num_pages=10, page_size=8, max_batch=2,
                          max_pages_per_seq=4, release_model=True)
    import jax
    held = sum(v.size for v in jax.tree_util.tree_leaves(dec.weights))
    assert held == n == dec.cfg.num_params()
    assert all(p._value is None for _, p in model.named_parameters())
    seg, = dec.weights["segments"]
    assert seg["kv_b_0"].shape == seg["kv_b_1"].shape == (2, 16, 4, 16)
    assert seg["gate"].shape == (2, 4, 32, 16)
    assert seg["bias"].dtype == jnp.float32
