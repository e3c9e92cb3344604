"""LFM2-MoE through the serving engine at a tiny preset (no published
width), seeded weights, on the CPU in float32: gated short-conv layers whose
two earlier z ride a per-slot state, QK-normed grouped-query attention over
the paged pool, and sigmoid-routed experts with a selection bias, against
the plain reference `benchmark/reference/lfm2_moe.py`.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import lfm2_moe as family
from benchmark.reference import lfm2_moe as ref
from paddle_tpu.models import lfm2_moe as lfm
from paddle_tpu.serving import (ContinuousBatchingEngine, PrefixCache,
                                SpeculativeEngine)
from paddle_tpu.serving.mla_decoder import PagedMLADecoder

# the configuration file's keys at tiny sizes: a stage of five layers from
# published-style layer 1 on (conv dense, attention, conv, conv,
# attention), 8 experts all held, 3 a token, a bias that moves selections
TINY = {
    "family": "lfm2_moe", "vocab_size": 96, "hidden_size": 32,
    "intermediate_size": 48, "moe_intermediate_size": 16,
    "num_hidden_layers": 5, "num_dense_layers": 1,
    "stage_layer_types": ["conv", "full_attention", "conv", "conv",
                          "full_attention"],
    "num_attention_heads": 4, "num_key_value_heads": 2, "num_experts": 8,
    "n_routed_experts": 8, "expert_offset": 0, "num_experts_per_tok": 3,
    "conv_L_cache": 3, "norm_topk_prob": True, "routed_scaling_factor": 1,
    "norm_eps": 1e-5,
    "rope_parameters": {"rope_theta": 10000.0, "rope_type": "default"},
    "max_position_embeddings": 256, "dtype": "float32",
    "initializer_range": 0.2, "expert_bias_std": 0.05}
SEED = 3000000019
EXPERT_LAYERS = 4


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


def _gaps(prompt, served, params=None):
    params = ref.init_params(TINY, SEED) if params is None else params
    return np.asarray(ref.served_gaps(TINY, params, prompt, served, 64))


@pytest.fixture(scope="module")
def served():
    """Five prompts on three slots (three at once, two joining rows that
    decode, into the slots the first finished ones leave) through the
    engine's default path: chunks of 16 tokens, then decode."""
    prompts = _prompts((5, 17, 40, 9, 33))
    slots_of = {}

    def note(e):
        for s, rid in enumerate(e._slot_req):
            if rid is not None:
                slots_of.setdefault(rid, s)

    eng, outs = _serve(_decoder(slots=3), prompts[:3], prompts[3:],
                       on_sync=note)
    return eng, prompts, outs, slots_of


# ------------------------------------------------ (a) engine vs reference
@pytest.mark.parametrize("request_no", range(5))
def test_served_tokens_are_the_references_best(served, request_no):
    """Chunked prefill and then decode through the pool (GQA) and the
    per-slot state (conv) against the reference's full forward with no
    cache, on logits: at every position the served token's reference
    logit is the reference's best to 1e-4 (float32 both sides; a wrong
    tap, grouping, norm or position moves logits by tenths)."""
    _, prompts, outs, _ = served
    assert len(outs[request_no]) == 6
    gaps = _gaps(prompts[request_no], outs[request_no])
    assert gaps.max() <= 1e-4, gaps


def test_the_long_prompt_crossed_chunks_and_late_ones_took_freed_slots(
        served):
    """The 40-token prompt took three chunks of 16 (the state carried
    twice), and the two late requests took slots a finished request had
    left: their conv state started from zero however the slot left it."""
    eng, prompts, outs, slots_of = served
    hz = eng.serve_schedule()
    assert sum(ev["prefill_rows"] > 0 for ev in hz) >= 3
    rids = sorted(slots_of)
    late = [slots_of[r] for r in rids[3:]]
    assert len(late) == 2 and set(late) <= {slots_of[r] for r in rids[:3]}


def test_layer_forward_agrees_with_the_reference():
    """The program's Layer (full forward, no cache) and the reference on
    the same weights: float32 both, 2e-4 on logits of order 1."""
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
    """`hidden_states` runs a layer as two programs; `block` is the same
    layer in one piece."""
    params = ref.init_params(TINY, 7)
    ids = np.random.default_rng(2).integers(0, TINY["vocab_size"], 24)
    x = params["embed_tokens.weight"][jnp.asarray(ids)]
    for i in range(TINY["num_hidden_layers"]):
        x = ref.block({k: params[f"layers.{i}.{k}"]
                       for k in ref.layer_leaves(TINY, i)}, x, TINY, "f32", i)
    np.testing.assert_allclose(
        np.asarray(ref.hidden_states(TINY, params, ids)), np.asarray(x),
        atol=1e-5)


# ------------------------------------------------- (b) the per-slot state
def _taps_in_chunks(z_rows, chunks, state):
    """Feed rows' z through `packed_conv_taps` in the given chunks (one
    list of token counts a call, a count a row; 0: the row is frozen).
    Returns (the taps each row saw, in order, the final state)."""
    seen = [[] for _ in z_rows]
    done = [0] * len(z_rows)
    for counts in chunks:
        zs, rows, pos = [], [], []
        for r, n in enumerate(counts):
            zs.append(z_rows[r][done[r]:done[r] + n])
            rows += [r] * n
            pos += list(range(done[r], done[r] + n))
        z = jnp.asarray(np.concatenate(zs))
        (z2, z1), state = lfm.packed_conv_taps(
            z, state, jnp.asarray(rows, jnp.int32),
            jnp.asarray(pos, jnp.int32), jnp.asarray(counts, jnp.int32))
        t = 0
        for r, n in enumerate(counts):
            seen[r].append((np.asarray(z2[t:t + n]), np.asarray(z1[t:t + n])))
            t += n
            done[r] += n
    return seen, state


def test_the_taps_of_chunks_are_the_whole_sequences():
    """Three rows fed in chunks of every kind (a prompt in pieces of 5 and
    1, a decode row of one a call, a row frozen for a call) against the
    whole sequence shifted by one and two; the stale state the slots
    start with is never read (every row starts at position 0)."""
    rng = np.random.default_rng(0)
    z_rows = [rng.normal(size=(n, 4)).astype(np.float32) for n in (8, 4, 6)]
    stale = jnp.asarray(rng.normal(size=(3, 2, 4)), jnp.float32)
    chunks = [[5, 1, 3], [1, 1, 0], [2, 1, 3], [0, 1, 0]]
    seen, _ = _taps_in_chunks(z_rows, chunks, stale)
    for r, z in enumerate(z_rows):
        z2, z1 = lfm.shifted_taps(jnp.asarray(z))
        np.testing.assert_array_equal(
            np.concatenate([a for a, _ in seen[r]]), np.asarray(z2))
        np.testing.assert_array_equal(
            np.concatenate([b for _, b in seen[r]]), np.asarray(z1))


def test_frozen_rows_leave_their_state_alone():
    """A row with no tokens in a call (frozen, padded or empty) keeps its
    state bit for bit; a row with one token shifts it by one; a row with
    more keeps its last two."""
    rng = np.random.default_rng(1)
    state = jnp.asarray(rng.normal(size=(3, 2, 4)), jnp.float32)
    z = jnp.asarray(rng.normal(size=(4, 4)), jnp.float32)
    _, new = lfm.packed_conv_taps(
        z, state, jnp.asarray([1, 2, 2, 2], jnp.int32),
        jnp.asarray([7, 3, 4, 5], jnp.int32), jnp.asarray([0, 1, 3],
                                                          jnp.int32))
    np.testing.assert_array_equal(np.asarray(new[0]), np.asarray(state[0]))
    np.testing.assert_array_equal(np.asarray(new[1]),
                                  np.stack([state[1, 1], z[0]]))
    np.testing.assert_array_equal(np.asarray(new[2]), np.asarray(z[2:]))


def test_a_state_reset_every_tick_is_seen():
    """The fault the cell plays on the chip: the decoder's taps read a
    zero state (every decode tick then convolves as if the row had just
    begun). Served tokens move away from the reference's best."""
    dec = _decoder()
    real = lfm.packed_conv_taps

    def reset(z, state, rows, pos, row_new):
        return real(z, jnp.zeros_like(state), rows, pos, row_new)

    prompts = _prompts((9, 20), seed=4)
    lfm.packed_conv_taps = reset
    try:
        _, outs = _serve(dec, prompts, new=8)
    finally:
        lfm.packed_conv_taps = real
    gaps = max(_gaps(p, o).max() for p, o in zip(prompts, outs))
    assert gaps > 1e-2, gaps


# ------------------------------------------------- (c) the router by hand
def _cfg(**kw):
    return lfm.lfm2_moe_tiny(**dict(dict(num_experts=6, num_experts_per_tok=2,
                                         routed_scaling_factor=2.0), **kw))


def test_the_bias_moves_a_selection_and_not_a_weight():
    """Scores sigmoid(logits) = (.10, .60, .30, .55, .20, .05): the top two
    are experts 1 (.60) and 3 (.55), weighed .60 / 1.15 and .55 / 1.15,
    times 2. A bias of +.30 on expert 2 puts it (.30 + .30 = .60) above
    expert 3: the selection is then 1 and 2, weighed by their SCORES
    renormalised, .60 / .90 and .30 / .90 (times 2), not by .60."""
    s = np.asarray([[.10, .60, .30, .55, .20, .05]])
    logits = jnp.asarray(np.log(s / (1 - s)), jnp.float32)
    cfg = _cfg()

    def weights(bias):
        cw, ei = lfm.sigmoid_route(logits, jnp.asarray(bias, jnp.float32),
                                   cfg)
        got = np.zeros((1, 6), np.float32)
        np.put_along_axis(got, np.asarray(ei), np.asarray(cw), -1)
        want = np.asarray(ref.route(
            jax.nn.sigmoid(logits), jnp.asarray(bias, jnp.float32),
            {"num_experts_per_tok": 2, "norm_topk_prob": True,
             "routed_scaling_factor": 2.0}))
        np.testing.assert_allclose(got, want, atol=1e-6)
        return got[0]

    np.testing.assert_allclose(weights(np.zeros(6)),
                               [0, 2 * .60 / 1.15, 0, 2 * .55 / 1.15, 0, 0],
                               atol=1e-5)
    np.testing.assert_allclose(weights([0, 0, .30, 0, 0, 0]),
                               [0, 2 * .60 / .90, 2 * .30 / .90, 0, 0, 0],
                               atol=1e-5)


def test_the_weights_are_renormalised_only_where_the_config_says():
    s = np.asarray([[.10, .60, .30, .55, .20, .05]])
    logits = jnp.asarray(np.log(s / (1 - s)), jnp.float32)
    cw, _ = lfm.sigmoid_route(logits, jnp.zeros(6), _cfg(norm_topk_prob=False))
    np.testing.assert_allclose(np.sort(np.asarray(cw[0])), [1.10, 1.20],
                               atol=1e-5)


# --------------------------------------------------- (d) the grouping
def test_a_swapped_grouping_is_seen():
    """Query head h reads key/value head h // 2 here (4 heads over 2). A
    program whose query heads are laid out so that head h reads key/value
    head h % 2 serves tokens that are not the reference's best."""
    dec = _decoder()
    real = dec.family.project

    def swapped(w, y, pos, cfg, inv):
        q, row = real(w, y, pos, cfg, inv)
        # heads (0, 1, 2, 3) -> (0, 2, 1, 3): head 1 now reads what head 2
        # did (kv head 1) and head 2 what head 1 did (kv head 0)
        return q[:, jnp.asarray([0, 2, 1, 3])], row

    dec.family = type("Swapped", (dec.family,),
                      {"project": staticmethod(swapped)})
    prompts = _prompts((12, 25), seed=5)
    _, outs = _serve(dec, prompts, new=6)
    gaps = max(_gaps(p, o).max() for p, o in zip(prompts, outs))
    assert gaps > 1e-2, gaps


# ------------------------------------------------- (e) counters and cache
def test_the_counters_are_the_hand_counts(served):
    """Every real token selects 3 experts in each of the 4 expert layers,
    all held: `expert_assignments` is 12 a token over the run; a decode
    tick of r rows hits at most min(8, 3 r) experts a layer."""
    eng, prompts, outs, _ = served
    hz = eng.serve_schedule()
    real = sum(ev["tokens_dispatched"] - ev["tokens_padded"] for ev in hz)
    assert real == sum(map(len, prompts)) + 5 * 5
    assert sum(ev["expert_assignments"] for ev in hz) == \
        real * 3 * EXPERT_LAYERS
    for ev in hz:
        if ev["prefill_rows"] == 0:
            assert 3 * ev["k"] * EXPERT_LAYERS <= ev["experts_hit"] <= \
                ev["k"] * EXPERT_LAYERS * min(8, 3 * ev["decode_rows"])


def test_the_cache_is_described_by_the_family():
    """One pool entry an attention layer (two here) of 2 x 2 heads x 8
    values, none a conv layer; a state of z at two positions for each of
    the three conv layers and each slot; the bytes follow."""
    dec = _decoder(slots=2)
    pool, state = dec.cache
    assert pool.shape == (2, 2 * 8 + 2, 8, 32)
    assert state.shape == (3, 2, 2, 32)
    assert dec.kv_token_bytes == 32 * 4
    assert dec.kv_token_bytes_by_layer() == [0, 128, 0, 0, 128]
    assert dec.state_slot_bytes == 3 * 2 * 32 * 4
    assert dec.step_hbm_bytes(avg_ctx=10, batch=2) == \
        dec.cfg.num_params() * 4 + 2 * 10 * 256 + 2 * 768
    assert dec.inv_freq.shape == (4,)


def test_the_decoder_holds_every_parameter_once():
    model = family.build_model(TINY, SEED, {})
    n = sum(int(np.prod(p._value.shape))
            for _, p in model.named_parameters())
    dec = PagedMLADecoder(model, num_pages=10, page_size=8, max_batch=2,
                          max_pages_per_seq=4, release_model=True)
    held = sum(v.size for v in jax.tree_util.tree_leaves(dec.weights))
    assert held == n == dec.cfg.num_params()
    shapes = ref.leaf_shapes(TINY)
    assert sum(int(np.prod(s)) for s in shapes.values()) == n
    assert [k for k, _, _ in dec._runs] == [
        "dense_conv", "moe_attn", "moe_conv", "moe_attn"]


def test_what_it_cannot_serve_is_refused():
    dec = _decoder(slots=2)
    with pytest.raises(NotImplementedError, match="state is not a page"):
        ContinuousBatchingEngine(dec, prefix_cache=PrefixCache(page_size=8))
    with pytest.raises(NotImplementedError, match="speculation"):
        SpeculativeEngine(dec, dec)
    with pytest.raises(NotImplementedError, match="rows are not slots"):
        dec.prefill_suffix_batch([([1, 2, 3], 0, [0])])
    model = family.build_model(TINY, SEED, {})
    with pytest.raises(NotImplementedError, match="temperature"):
        PagedMLADecoder(model, num_pages=10, page_size=8, max_batch=2,
                        temperature=0.7)
