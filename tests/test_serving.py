"""Continuous-batching paged-KV decode engine + saved-program Predictor
(reference paddle/fluid/inference/api/paddle_inference_api.h serving
role)."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import GPT, generation, gpt_tiny
from paddle_tpu.serving import ContinuousBatchingEngine, PagedGPTDecoder


@pytest.fixture(scope="module")
def tiny_model():
    paddle.seed(7)
    from paddle_tpu.distributed import build_mesh
    build_mesh(dp=1)
    cfg = gpt_tiny(max_seq_len=128, dtype="float32", remat=False)
    model = GPT(cfg)
    model.eval()
    return model


def _golden_greedy(model, ids, n_new):
    out = generation.generate(model, np.asarray([ids], np.int32),
                              max_new_tokens=n_new, temperature=0.0)
    return [int(t) for t in np.asarray(out._value)[0, len(ids):]]


def test_paged_decoder_matches_dense_greedy(tiny_model):
    dec = PagedGPTDecoder(tiny_model, num_pages=32, page_size=16,
                          max_batch=2)
    eng = ContinuousBatchingEngine(dec, max_new_tokens=8)
    prompt = [3, 141, 59, 26, 535]
    rid = eng.submit(np.asarray(prompt, np.int32))
    outs = eng.run()
    assert outs[rid] == _golden_greedy(tiny_model, prompt, 8)


def test_continuous_batching_more_requests_than_slots(tiny_model):
    dec = PagedGPTDecoder(tiny_model, num_pages=32, page_size=16,
                          max_batch=2)
    eng = ContinuousBatchingEngine(dec, max_new_tokens=6)
    prompts = [[3, 141, 59], [897, 11, 4, 18, 200, 7], [31]]
    rids = [eng.submit(np.asarray(p, np.int32)) for p in prompts]
    outs = eng.run()
    # 3 requests through 2 slots: iteration-level admission; every result
    # must equal its isolated greedy decode
    for rid, p in zip(rids, prompts):
        assert outs[rid] == _golden_greedy(tiny_model, p, 6), p
    # all pages returned to the pool (minus the reserved scratch page)
    assert len(eng._free) == dec.num_pages - 1
    # batching actually happened: fewer ticks than serial decoding
    assert eng.steps < 3 * 6


def test_prefill_batches_same_bucket_admissions(tiny_model):
    """Two same-length-bucket prompts admitted together run as ONE
    batched prefill forward (draw counter advances once), with outputs
    identical to isolated decodes."""
    dec = PagedGPTDecoder(tiny_model, num_pages=32, page_size=16,
                          max_batch=2)
    eng = ContinuousBatchingEngine(dec, max_new_tokens=5)
    prompts = [[3, 141, 59], [897, 11, 4, 18]]     # both bucket Lp=16
    rids = [eng.submit(np.asarray(p, np.int32)) for p in prompts]
    draws_before = dec._draws
    eng.step()                                     # admission happens here
    assert dec._draws == draws_before + 2          # 1 prefill + 1 decode
    outs = eng.run()
    for rid, p in zip(rids, prompts):
        assert outs[rid] == _golden_greedy(tiny_model, p, 5), p


def test_eos_at_prefill_finishes_immediately(tiny_model):
    """A prompt whose first greedy token is EOS emits exactly [eos] and
    frees its pages. On the legacy per-tick path it never occupies a
    decode slot (zero ticks); on the ragged path its prompt rides the
    horizon — the EOS freezes the slot ON DEVICE, so later ticks are
    filler and no token past the EOS ever reaches the output."""
    prompt = [3, 141, 59]
    eos = _golden_greedy(tiny_model, prompt, 1)[0]
    for k_max in (1, 8):
        dec = PagedGPTDecoder(tiny_model, num_pages=32, page_size=16,
                              max_batch=1)
        eng = ContinuousBatchingEngine(dec, eos_token_id=eos,
                                       max_new_tokens=16, k_max=k_max)
        rid = eng.submit(np.asarray(prompt, np.int32))
        outs = eng.run()
        assert outs[rid] == [eos]
        assert eng.stats.tokens == 1
        if k_max == 1:
            assert eng.steps == 0
        assert len(eng._free) == dec.num_pages - 1


def test_engine_rejects_oversized_request(tiny_model):
    dec = PagedGPTDecoder(tiny_model, num_pages=8, page_size=16,
                          max_batch=1)
    eng = ContinuousBatchingEngine(dec, max_new_tokens=200)
    with pytest.raises(ValueError, match="pages"):
        eng.submit(np.arange(20, dtype=np.int32))


def test_a8w8_quantized_decode_runs(tiny_model):
    dec = PagedGPTDecoder(tiny_model, num_pages=32, page_size=16,
                          max_batch=1, quant="a8w8")
    eng = ContinuousBatchingEngine(dec, max_new_tokens=4)
    rid = eng.submit(np.asarray([3, 141, 59], np.int32))
    outs = eng.run()
    toks = outs[rid]
    assert len(toks) == 4
    assert all(0 <= t < tiny_model.cfg.vocab_size for t in toks)


def test_sampled_decode_deterministic_and_varied(tiny_model):
    """temperature>0: sampling is seeded-deterministic per engine run,
    differs across seeds, and top_k restricts the support."""
    prompt = [3, 141, 59]

    def run(seed, temperature=0.8, top_k=0):
        dec = PagedGPTDecoder(tiny_model, num_pages=32, page_size=16,
                              max_batch=1, temperature=temperature,
                              top_k=top_k, seed=seed)
        eng = ContinuousBatchingEngine(dec, max_new_tokens=8)
        rid = eng.submit(np.asarray(prompt, np.int32))
        return eng.run()[rid]

    a1, a2 = run(0), run(0)
    assert a1 == a2, "same seed must reproduce"
    b = run(123)
    assert a1 != b, "different seeds should diverge (w.h.p.)"
    greedy = run(0, temperature=0.0)
    # top_k=1 sampling IS greedy regardless of temperature
    assert run(7, temperature=1.5, top_k=1) == greedy


def test_tensor_parallel_serving_matches_single_device(tiny_model):
    """tp=4 Megatron-sharded decode (head-axis qkv split, row-parallel
    proj/fc2, head-sharded KV pages) produces the exact greedy tokens of
    the single-device engine, with NO all-gather in the step (the
    head-major qkv layout keeps sharding aligned end to end)."""
    import jax as _jax

    from paddle_tpu.distributed import build_mesh
    from paddle_tpu.distributed.mesh import get_mesh, set_mesh
    prompt = [3, 141, 59, 26, 535]

    def run(mesh):
        dec = PagedGPTDecoder(tiny_model, num_pages=32, page_size=16,
                              max_batch=2, mesh=mesh)
        eng = ContinuousBatchingEngine(dec, max_new_tokens=8)
        rid = eng.submit(np.asarray(prompt, np.int32))
        return eng.run()[rid], dec

    prev = get_mesh(create_default=False)
    try:
        single, _ = run(None)
        mesh = build_mesh(tp=4, dp=2)
        sharded, dec = run(mesh)
        assert sharded == single
        # weights really are distributed over tp
        assert "tp" in str(dec.weights["qkv_w"].sharding.spec)
        assert "tp" in str(dec.k_pages.sharding.spec)
        # Megatron layout: all-reduces only, no per-layer all-gather
        import jax.numpy as jnp
        S = dec.max_batch
        lowered = dec._decode.lower(
            dec.weights, dec.k_pages, dec.v_pages,
            jnp.zeros((S,), jnp.int32), jnp.zeros((S,), jnp.int32),
            jnp.zeros((S, dec.max_pages), jnp.int32),
            jnp.asarray(1, jnp.int32))
        hlo = lowered.compile().as_text()
        assert "all-reduce" in hlo
        assert "all-gather" not in hlo, "qkv sharding not head-aligned"
    finally:
        set_mesh(prev)


def test_speculative_equals_target_greedy(tiny_model):
    """Speculative decoding is exact: outputs equal the target's plain
    greedy decode, with FEWER target forwards (the whole point). The
    'draft' here is the same tiny model, so every proposal is accepted
    and each verify round emits k tokens."""
    from paddle_tpu.serving import SpeculativeEngine
    prompt = [3, 141, 59, 26, 535]
    n_new = 12

    golden = _golden_greedy(tiny_model, prompt, n_new)

    dec = PagedGPTDecoder(tiny_model, num_pages=32, page_size=16,
                          max_batch=2)
    draft = PagedGPTDecoder(tiny_model, num_pages=32, page_size=16,
                            max_batch=2)
    eng = SpeculativeEngine(dec, draft, max_new_tokens=n_new, k=4)
    rid = eng.submit(np.asarray(prompt, np.int32))
    outs = eng.run()
    assert outs[rid] == golden
    # perfect-draft case: ceil((n_new-1)/k) verify rounds, not n_new-1
    assert eng.target_calls <= (n_new - 1 + 3) // 4 + 1, eng.target_calls


def test_speculative_with_weak_draft(tiny_model):
    """A DIFFERENT (weaker) draft model must not change the output — only
    the speedup. Also exercises mixed accept/reject rounds and multiple
    slots."""
    from paddle_tpu.models import GPT, gpt_tiny
    from paddle_tpu.serving import SpeculativeEngine
    paddle.seed(123)     # different weights: drafts will often miss
    weak = GPT(gpt_tiny(max_seq_len=128, dtype="float32", remat=False))
    weak.eval()
    prompts = [[3, 141, 59], [897, 11, 4, 18, 200, 7]]
    n_new = 10

    dec = PagedGPTDecoder(tiny_model, num_pages=32, page_size=16,
                          max_batch=2)
    draft = PagedGPTDecoder(weak, num_pages=32, page_size=16, max_batch=2)
    eng = SpeculativeEngine(dec, draft, max_new_tokens=n_new, k=3)
    rids = [eng.submit(np.asarray(p, np.int32)) for p in prompts]
    outs = eng.run()
    for rid, p in zip(rids, prompts):
        assert outs[rid] == _golden_greedy(tiny_model, p, n_new), p
    # pages fully reclaimed on both pools
    assert len(eng._free) == dec.num_pages - 1
    assert len(eng._draft_free) == draft.num_pages - 1


def test_spec_accept_is_unbiased():
    """The rejection-sampling acceptance emits tokens distributed EXACTLY
    as the target distribution, whatever the draft proposes (Monte Carlo
    over the pure host function)."""
    from paddle_tpu.serving import _spec_accept
    p = np.array([[0.5, 0.3, 0.2], [0.1, 0.6, 0.3]])
    q = np.array([[0.2, 0.5, 0.3]])
    rng = np.random.default_rng(0)
    first = np.zeros(3)
    n_trials = 20000
    for _ in range(n_trials):
        d = rng.choice(3, p=q[0])            # draft proposes from q
        a, tok = _spec_accept(p, q, np.array([d]), rng)
        first[d if a == 1 else tok] += 1     # first emitted token
    freq = first / n_trials
    np.testing.assert_allclose(freq, p[0], atol=0.02)


def test_sampled_speculative_deterministic(tiny_model):
    """Sampled speculation: reproducible per seed, near-greedy
    temperature reproduces the greedy golden exactly."""
    from paddle_tpu.models import GPT, gpt_tiny
    from paddle_tpu.serving import SpeculativeEngine
    paddle.seed(55)
    weak = GPT(gpt_tiny(max_seq_len=128, dtype="float32", remat=False))
    weak.eval()
    prompt = [3, 141, 59, 26]
    n_new = 10

    def run(temperature, seed):
        dec = PagedGPTDecoder(tiny_model, num_pages=32, page_size=16,
                              max_batch=1, temperature=temperature,
                              seed=seed)
        draft = PagedGPTDecoder(weak, num_pages=32, page_size=16,
                                max_batch=1, temperature=temperature,
                                seed=seed + 1)
        eng = SpeculativeEngine(dec, draft, max_new_tokens=n_new, k=3)
        rid = eng.submit(np.asarray(prompt, np.int32))
        return eng.run()[rid]

    assert run(0.9, 3) == run(0.9, 3), "same seed must reproduce"
    # temperature -> 0 limit: sampling collapses to greedy
    assert run(1e-4, 0) == _golden_greedy(tiny_model, prompt, n_new)
    # mismatched sampling configs rejected
    dec = PagedGPTDecoder(tiny_model, num_pages=32, page_size=16,
                          max_batch=1, temperature=0.9)
    draft = PagedGPTDecoder(tiny_model, num_pages=32, page_size=16,
                            max_batch=1)
    with pytest.raises(ValueError, match="SAME sampling"):
        SpeculativeEngine(dec, draft)


def test_paged_kernel_path_matches_jnp(tiny_model):
    """use_kernel=True exercises the scalar-prefetch Pallas paged kernel
    (interpret mode on CPU) end-to-end through the engine."""
    prompt = [3, 141, 59, 26]
    outs = {}
    for kernel in (False, True):
        dec = PagedGPTDecoder(tiny_model, num_pages=32, page_size=16,
                              max_batch=1, use_kernel=kernel)
        eng = ContinuousBatchingEngine(dec, max_new_tokens=5)
        rid = eng.submit(np.asarray(prompt, np.int32))
        outs[kernel] = eng.run()[rid]
    assert outs[False] == outs[True]


# --------------------------------------------------------------------------
# Predictor over a saved program (no Python Layer)
# --------------------------------------------------------------------------

def test_predictor_runs_saved_program(tmp_path):
    from paddle_tpu.static import InputSpec

    paddle.seed(0)
    net = paddle.nn.Sequential(paddle.nn.Linear(4, 8), paddle.nn.ReLU(),
                               paddle.nn.Linear(8, 2))
    net.eval()
    x = np.random.RandomState(0).randn(3, 4).astype("float32")
    golden = np.asarray(net(paddle.to_tensor(x))._value)

    path = str(tmp_path / "prog")
    # dynamic batch dim: the exported program must accept ANY batch size
    paddle.jit.save(net, path, input_spec=[InputSpec([None, 4], "float32")])

    # load: executable without rebuilding the Layer
    loaded = paddle.jit.load(path)
    assert loaded.runnable
    out = loaded(paddle.to_tensor(x))
    np.testing.assert_allclose(np.asarray(out._value), golden, rtol=1e-6)
    # a different batch size through the same program
    x7 = np.random.RandomState(1).randn(7, 4).astype("float32")
    out7 = loaded(paddle.to_tensor(x7))
    np.testing.assert_allclose(np.asarray(out7._value),
                               np.asarray(net(paddle.to_tensor(x7))._value),
                               rtol=1e-6)

    # Predictor program-file path (reference create_predictor flow)
    from paddle_tpu.inference import Config, create_predictor
    pred = create_predictor(Config(prog_file=path + ".pdmodel"))
    outs = pred.run([x])
    np.testing.assert_allclose(np.asarray(outs[0]._value), golden,
                               rtol=1e-6)


def test_predictor_clear_error_without_program(tmp_path):
    paddle.seed(0)
    net = paddle.nn.Linear(4, 2)
    path = str(tmp_path / "weights_only")
    paddle.jit.save(net, path)          # no input_spec -> no program
    from paddle_tpu.inference import Config, create_predictor
    with pytest.raises(RuntimeError, match="input_spec"):
        create_predictor(Config(prog_file=path + ".pdmodel"))


def test_inference_config_toggles_map_to_real_choices():
    """switch_ir_optim(False) -> eager op-by-op execution (no XLA
    program); enable_memory_optim -> input-buffer donation. Same
    numerics either way."""
    import numpy as np
    from paddle_tpu.inference import Config, create_predictor
    paddle.seed(0)
    m = paddle.nn.Sequential(paddle.nn.Linear(8, 8), paddle.nn.ReLU())
    x = np.random.RandomState(0).randn(4, 8).astype("float32")

    cfg = Config(); cfg.set_model(m)
    jit_pred = create_predictor(cfg)
    assert jit_pred._jitted
    out_jit = jit_pred.run([x])[0].numpy()

    cfg2 = Config(); cfg2.set_model(m)
    cfg2.switch_ir_optim(False)
    assert cfg2.ir_optim() is False
    eager_pred = create_predictor(cfg2)
    assert not eager_pred._jitted
    np.testing.assert_allclose(eager_pred.run([x])[0].numpy(), out_jit,
                               rtol=1e-6)

    cfg3 = Config(); cfg3.set_model(m)
    cfg3.enable_memory_optim()
    assert cfg3.memory_optim_enabled()
    don_pred = create_predictor(cfg3)
    np.testing.assert_allclose(don_pred.run([x])[0].numpy(), out_jit,
                               rtol=1e-6)
    # donation must not destroy a caller-owned Tensor across repeat runs
    t = paddle.to_tensor(x)
    don_pred.run([t]); don_pred.run([t])
    np.testing.assert_allclose(t.numpy(), x)


# --------------------------------------------------------------------------
# Multi-step device-resident decode (decode_multi + horizon scheduling)
# --------------------------------------------------------------------------

def _run_both(model, prompts, max_new, eos=None, k_max=8, dec_kw=None,
              eng_kw=None):
    """One workload through the per-tick (k_max=1) and multi-step
    (k_max=K) engines on twin decoders; returns (per_tick_outs,
    multi_outs, multi_engine) with outputs keyed by prompt order."""
    outs = {}
    engines = {}
    for k in (1, k_max):
        dec = PagedGPTDecoder(model, num_pages=32, page_size=16,
                              max_batch=2, **(dec_kw or {}))
        eng = ContinuousBatchingEngine(dec, eos_token_id=eos,
                                       max_new_tokens=max_new, k_max=k,
                                       **(eng_kw or {}))
        rids = [eng.submit(np.asarray(p, np.int32)) for p in prompts]
        res = eng.run()
        outs[k] = [res[r] for r in rids]
        engines[k] = eng
        assert len(eng._free) == dec.num_pages - 1, "page leak"
    return outs[1], outs[k_max], engines[k_max]


def test_multi_step_greedy_matches_per_tick(tiny_model):
    """The fused K-tick engine emits byte-identical greedy streams to
    the per-tick engine, with host syncs per token dropping from one
    per decode tick to <= 1/K (the stats-asserted acceptance bar)."""
    prompts = [[3, 141, 59, 26, 535], [897, 11, 4]]
    tick, multi, eng = _run_both(tiny_model, prompts, max_new=33, k_max=8)
    assert multi == tick
    s = eng.stats
    assert s.k_max == 8
    assert s.host_syncs_per_token <= 1 / 8, s.summary()
    # every decode tick still happened, just without a sync each
    assert s.ticks >= 32 and s.decode_syncs <= s.ticks // 8 + 1


def test_multi_step_sampled_matches_per_tick(tiny_model):
    """Seeded temperature/top-k/top-p sampling: draws are keyed by
    (seed, request id, position) — nothing about scheduling — so the
    fused loop emits byte-identical sampled streams to the per-tick
    engine."""
    prompts = [[3, 141, 59], [897, 11, 4, 18, 200, 7]]
    dec_kw = dict(temperature=0.8, top_k=40, top_p=0.9, seed=11)
    tick, multi, _ = _run_both(tiny_model, prompts, max_new=17, k_max=8,
                               dec_kw=dec_kw)
    assert multi == tick


def test_multi_step_sampled_matches_per_tick_under_churn(tiny_model):
    """The hard case: sampled config + admission churn (twice as many
    requests as slots, EOS retiring sequences mid-run). The two engines
    admit and prefill at different tick boundaries and the multi-step
    engine burns filler ticks for frozen slots — none of which may
    shift any request's draws, because keys depend only on (seed,
    request id, position)."""
    rng = np.random.RandomState(7)
    prompts = [list(rng.randint(0, tiny_model.cfg.vocab_size,
                                rng.randint(1, 10)).astype(int))
               for _ in range(4)]
    eos = int(rng.randint(0, tiny_model.cfg.vocab_size))
    dec_kw = dict(temperature=0.8, top_k=40, seed=11)
    tick, multi, _ = _run_both(tiny_model, prompts, max_new=14, eos=eos,
                               k_max=8, dec_kw=dec_kw)
    assert multi == tick


def test_multi_step_eos_mid_horizon(tiny_model):
    """A slot hitting EOS inside a horizon freezes ON DEVICE (lens stop,
    KV writes to scratch) and retires one horizon later with its output
    truncated exactly like the per-tick engine's."""
    prompt = [3, 141, 59, 26, 535]
    golden = _golden_greedy(tiny_model, prompt, 33)
    # an EOS whose FIRST occurrence lands inside the first 8-tick
    # horizon, past tick 0 (greedy on random weights collapses to a
    # repeating token quickly, so index 1 is the mid-horizon choice)
    eos = next(t for i, t in enumerate(golden[1:7], 1)
               if golden.index(t) == i)
    n = golden.index(eos) + 1
    assert 1 <= n - 1 < 8            # EOS on a decode tick mid-block
    tick, multi, eng = _run_both(tiny_model, [prompt], max_new=33,
                                 eos=eos, k_max=8)
    assert multi == tick
    assert multi[0][-1] == eos and len(multi[0]) == n
    # the horizon that contained the EOS was dispatched in full (device
    # ticks are cheap; the sync is what we save) but emitted only n
    assert eng.stats.tokens == n


def test_multi_step_budget_exhaustion_mid_horizon(tiny_model):
    """decode_multi's per-slot `remaining` budget freezes a slot mid
    horizon: emitted tokens and lens stop at the budget, filler ticks
    are flagged in done_before, and the frozen slot's KV pages stay
    byte-identical to a per-tick loop that stops writing at the same
    point (masked writes route to the scratch page)."""
    import jax.numpy as jnp

    def fresh():
        dec = PagedGPTDecoder(tiny_model, num_pages=32, page_size=16,
                              max_batch=2)
        eng = ContinuousBatchingEngine(dec, max_new_tokens=40, k_max=1)
        for p in ([3, 141, 59, 26, 535], [897, 11, 4]):
            eng.submit(np.asarray(p, np.int32))
        eng.step()           # prefill + first decode tick
        return dec, eng
    dec_a, eng_a = fresh()
    dec_b, eng_b = fresh()
    table = eng_a._table(eng_a._slot_pages, dec_a)
    scratch = dec_a.num_pages - 1

    # fused: slot 0 may emit 3 more tokens, slot 1 eight
    out = dec_a.decode_multi(eng_a._tokens, eng_a._lens, table, 8,
                             remaining=np.array([3, 8], np.int32))
    block = np.asarray(out.tokens_block)
    done_before = np.asarray(out.done_before)

    # per-tick twin with host-side freeze (the legacy engine's exact
    # bookkeeping: frozen slots keep their token/len and their table
    # rows route to scratch)
    tokens = eng_b._tokens.copy()
    lens = eng_b._lens.copy()
    rem = np.array([3, 8], np.int32)
    frozen = np.zeros(2, bool)
    ticked = []
    for _ in range(8):
        t = table.copy()
        t[frozen] = scratch
        nxt = np.asarray(dec_b.decode(tokens, lens, t))
        nxt = np.where(frozen, tokens, nxt)
        ticked.append(nxt.copy())
        lens = np.where(frozen, lens, lens + 1)
        rem = np.where(frozen, rem, rem - 1)
        frozen = frozen | (rem <= 0)
        tokens = nxt
    assert np.array_equal(block, np.stack(ticked))
    assert np.array_equal(np.asarray(out.lens), lens)
    # done_before marks exactly the filler ticks of the frozen slot
    assert done_before[:, 0].tolist() == [False] * 3 + [True] * 5
    assert not done_before[:, 1].any()
    # KV pools identical outside the scratch page (masked writes landed
    # there and nowhere else)
    ka = np.asarray(dec_a.k_pages)[:, :scratch]
    kb = np.asarray(dec_b.k_pages)[:, :scratch]
    np.testing.assert_array_equal(ka, kb)


@pytest.mark.parametrize("seed", range(3))
def test_multi_step_fuzz_matches_per_tick(tiny_model, seed):
    """Randomized admission churn (more requests than slots, random EOS
    and budgets): multi-step output byte-identical to per-tick, pages
    reclaimed on both engines."""
    rng = np.random.RandomState(100 + seed)
    eos = int(rng.randint(0, tiny_model.cfg.vocab_size))
    max_new = int(rng.randint(3, 20))
    prompts = [list(rng.randint(0, tiny_model.cfg.vocab_size,
                                rng.randint(1, 12)).astype(int))
               for _ in range(int(rng.randint(3, 6)))]
    tick, multi, _ = _run_both(tiny_model, prompts, max_new=max_new,
                               eos=eos, k_max=8)
    assert multi == tick, (seed, eos, max_new)


def test_speculative_draft_ticks_match_per_tick_decode(tiny_model):
    """The draft's device-resident proposal chain (decode_multi with
    return_logits) equals k sequential decode() ticks on a twin decoder
    — same tokens, same sampling-round keys — so SpeculativeEngine's
    acceptance judges exactly the proposals it judged before."""
    def fresh():
        dec = PagedGPTDecoder(tiny_model, num_pages=32, page_size=16,
                              max_batch=2, temperature=0.7, seed=5)
        eng = ContinuousBatchingEngine(dec, max_new_tokens=40, k_max=1)
        eng.submit(np.asarray([3, 141, 59, 26], np.int32))
        eng.submit(np.asarray([897, 11, 4], np.int32))
        eng.step()
        return dec, eng
    dec_a, eng_a = fresh()
    dec_b, eng_b = fresh()
    table = eng_a._table(eng_a._slot_pages, dec_a)
    k = 4
    out = dec_a.decode_multi(eng_a._tokens, eng_a._lens, table, k,
                             return_logits=True)
    fused = np.asarray(out.tokens_block)

    tokens, lens = eng_b._tokens.copy(), eng_b._lens.copy()
    seq = []
    for _ in range(k):
        tokens = np.asarray(dec_b.decode(tokens, lens, table))
        seq.append(tokens.copy())
        lens = lens + 1
    assert np.array_equal(fused, np.stack(seq))
    assert out.logits_block.shape == (k, 2, tiny_model.cfg.vocab_size)


def test_multi_step_wall_clock_speedup(tiny_model):
    """Pinned CPU benchmark: at K=8 the multi-step engine beats the
    per-tick engine >= 1.5x wall-clock per token on a micro serving
    config (decode tick compute is tiny there, so the per-token host
    round-trip dominates — exactly the serving regime of a fast chip;
    measured ~4x on the dev container, asserted with margin)."""
    import time as _time
    paddle.seed(7)
    cfg = gpt_tiny(hidden_size=64, num_layers=1, num_heads=2,
                   vocab_size=128, max_seq_len=128, dtype="float32",
                   remat=False)
    model = GPT(cfg)
    model.eval()
    dec = PagedGPTDecoder(model, num_pages=32, page_size=16, max_batch=2)

    def run(k_max):
        eng = ContinuousBatchingEngine(dec, max_new_tokens=65, k_max=k_max)
        rng = np.random.RandomState(0)
        rids = [eng.submit(rng.randint(0, cfg.vocab_size, 5)
                           .astype(np.int32)) for _ in range(2)]
        t0 = _time.perf_counter()
        res = eng.run()
        dt = _time.perf_counter() - t0
        n = sum(len(res[r]) for r in rids)
        return res, dt / n, eng

    run(1)
    run(8)                    # warm both paths' compiles
    per_tick = min(run(1)[1] for _ in range(3))
    outs_t, _, _ = run(1)
    multi = min(run(8)[1] for _ in range(3))
    outs_m, _, eng = run(8)
    assert outs_m == outs_t                      # same streams, faster
    assert eng.stats.host_syncs_per_token <= 1 / 8
    speedup = per_tick / multi
    assert speedup >= 1.5, \
        f"multi-step speedup {speedup:.2f}x < 1.5x " \
        f"({per_tick*1e3:.2f} -> {multi*1e3:.2f} ms/token)"


def test_serve_stats_front_door(tiny_model):
    """debug.serving_stats() surfaces every live engine's telemetry:
    requests/tokens/syncs, occupancy, queue wait and per-token
    percentiles."""
    from paddle_tpu import debug
    dec = PagedGPTDecoder(tiny_model, num_pages=32, page_size=16,
                          max_batch=2)
    eng = ContinuousBatchingEngine(dec, max_new_tokens=9, k_max=4)
    eng.submit(np.asarray([3, 141, 59], np.int32))
    eng.run()
    summaries = [s for s in debug.serving_stats()
                 if s["engine"] == "ContinuousBatchingEngine"
                 and s["k_max"] == 4 and s["requests"] == 1]
    assert summaries, debug.serving_stats()
    s = summaries[-1]
    assert s["completed"] == 1 and s["tokens"] == 9
    # ragged scheduling: the prompt streamed into the horizon as
    # chunks — ZERO host-blocking prefill syncs on the decode path
    assert s["prefill_syncs"] == 0
    assert s["prefill_chunks"] >= 1
    assert s["prefill_chunk_tokens"] == 3
    # total host syncs no worse than the legacy split (1 prefill +
    # ceil(8/4) decode): the first-token horizon replaced the prefill
    assert s["decode_syncs"] + s["prefill_syncs"] <= 3
    assert 0 < s["host_syncs_per_token"] <= 1 / 3 + 1e-9
    assert s["tokens_per_sec"] > 0
    assert s["token_p50_ms"] <= s["token_p99_ms"]
    assert 0 < s["mean_slot_occupancy"] <= 1
    assert "queue_wait_p50_ms" in s
    del eng
    import gc
    gc.collect()             # WeakSet registry: dead engines drop out
    assert not [s for s in debug.serving_stats()
                if s["engine"] == "ContinuousBatchingEngine"
                and s["k_max"] == 4 and s["requests"] == 1]


# --------------------------------------------------------------------------
# Ragged serving: mixed chunked-prefill + decode horizons
# --------------------------------------------------------------------------

def _stream(model, prompts, max_new, eos=None, dec_kw=None, **eng_kw):
    dec = PagedGPTDecoder(model, num_pages=48, page_size=16,
                          max_batch=2, **(dec_kw or {}))
    eng = ContinuousBatchingEngine(dec, eos_token_id=eos,
                                   max_new_tokens=max_new, **eng_kw)
    rids = [eng.submit(np.asarray(p, np.int32)) for p in prompts]
    res = eng.run()
    assert len(eng._free) == dec.num_pages - 1, "page leak"
    return [res[r] for r in rids], eng


@pytest.mark.parametrize("seed", range(3))
def test_ragged_streams_byte_identical_under_churn(tiny_model, seed):
    """THE ragged acceptance bar: under randomized admission churn
    (sampled config + EOS retirement + more requests than slots,
    prompts long enough to chunk), the ragged engine's per-request
    streams are byte-identical to the per-tick engine's AND to the
    dispatch-separate (blocking-prefill) baseline's at k_max in
    {4, 8} — chunking a prompt across horizon boundaries must not
    shift a single draw (keys are (seed, request id, position);
    per-position math is window-independent)."""
    rng = np.random.RandomState(400 + seed)
    V = tiny_model.cfg.vocab_size
    prompts = [list(rng.randint(0, V, rng.randint(1, 40)).astype(int))
               for _ in range(4)]
    eos = int(rng.randint(0, V))
    max_new = int(rng.randint(3, 14))
    dec_kw = dict(temperature=0.8, top_k=40, seed=11)
    base, _ = _stream(tiny_model, prompts, max_new, eos, dec_kw, k_max=1)
    for k_max in (4, 8):
        blocking, _ = _stream(tiny_model, prompts, max_new, eos, dec_kw,
                              k_max=k_max, ragged=False)
        assert blocking == base, (seed, k_max, "blocking")
        ragged, eng = _stream(tiny_model, prompts, max_new, eos, dec_kw,
                              k_max=k_max, chunk_tokens=8)
        assert ragged == base, (seed, k_max, "ragged")
        assert eng.stats.prefill_syncs == 0
        assert eng.stats.prefill_chunk_tokens > 0


def test_ragged_greedy_matches_dense_golden(tiny_model):
    """A long prompt split over several chunk ticks emits exactly the
    dense model's greedy continuation, while a short prompt decodes
    alongside it in the same horizons (mixed rows end to end)."""
    long_p = list(range(1, 41))              # ceil(40/8) = 5 chunks
    short_p = [3, 141, 59]
    outs, eng = _stream(tiny_model, [long_p, short_p], 8, k_max=4,
                        chunk_tokens=8)
    assert outs[0] == _golden_greedy(tiny_model, long_p, 8)
    assert outs[1] == _golden_greedy(tiny_model, short_p, 8)
    s = eng.stats
    assert s.prefill_syncs == 0 and s.prefill_stall_syncs == 0
    assert s.prefill_chunks >= 5
    assert s.prefill_chunk_tokens == len(long_p) + len(short_p)
    # the trace really interleaved prefill rows with decode rows
    assert any(ev["kind"] == "horizon" and ev["prefill_rows"]
               and ev["decode_rows"] for ev in eng.serve_schedule())


def test_ragged_ttft_measures_submit_to_first_token(tiny_model):
    """Regression (TTFT window): chunked admission spreads one
    request's prefill over several horizon boundaries — ttft_s must
    stamp ONCE per request at its first token (there is no prefill
    sync to stamp at), so chunked and legacy engines report comparable
    TTFT."""
    prompts = [list(range(1, 41)), [5, 6, 7]]
    outs, eng = _stream(tiny_model, prompts, 4, k_max=4, chunk_tokens=8)
    s = eng.stats
    assert len(s.ttft_s) == len(prompts)     # exactly one stamp each
    assert all(t > 0 for t in s.ttft_s)
    assert s.prefill_syncs == 0
    assert not eng._submit_t                 # drained at first tokens
    assert s.summary()["ttft_p50_ms"] > 0
    # legacy engine, same workload: also one stamp per request, taken
    # at the same milestone (its first token exists at prefill-sync
    # time) — the two engines' TTFT windows are comparable
    outs2, eng2 = _stream(tiny_model, prompts, 4, k_max=1)
    assert len(eng2.stats.ttft_s) == len(prompts)
    assert not eng2._submit_t
    assert outs2 == outs


def test_explicit_ragged_honored_at_k_max_one(tiny_model):
    """Review regression: ContinuousBatchingEngine(ragged=True) must
    engage chunked no-stall admission even when k_max prices to 1 (big
    models legitimately price K=1) — silently downgrading to the
    blocking per-tick loop would betray the explicit opt-in."""
    prompts = [list(range(1, 41)), [5, 6, 7]]
    outs, eng = _stream(tiny_model, prompts, 5, k_max=1, ragged=True,
                        chunk_tokens=8)
    assert eng.ragged and eng.scheduler is not None
    assert eng.stats.prefill_syncs == 0          # no blocking prefill
    assert eng.stats.prefill_chunks >= 5
    # same streams as the default per-tick engine
    tick, _ = _stream(tiny_model, prompts, 5, k_max=1)
    assert outs == tick


@pytest.mark.parametrize("eng_kw, loop", [
    (dict(k_max=1), "per_tick"),
    (dict(k_max=4, ragged=False), "multi"),
    (dict(host_sync_s=1e-12), "ragged"),       # K PRICED to 1
    (dict(k_max=2), "ragged"),
    (dict(), "ragged")])
def test_run_takes_the_loop_the_two_arguments_name(tiny_model, eng_kw,
                                                  loop):
    """`run()` has three loops and two things choose: an explicit
    `k_max=1` runs `_run_per_tick` (blocking prefill, `decode_step`
    ticks), `ragged=False` runs `_run_multi` (blocking prefill,
    `decode_multi` horizons), everything else `_run_ragged`, a horizon
    PRICED to one tick too (big models legitimately price K=1: chunked
    no-stall admission stays). Read off the horizons' records."""
    prompts = [list(range(1, 41)), [5, 6, 7]]
    outs, eng = _stream(tiny_model, prompts, 5, chunk_tokens=8, **eng_kw)
    hz = eng.serve_schedule()
    assert hz and {ev["kind"] for ev in hz} <= {"horizon", "prefill_sync"}
    programs = {ev["program"] for ev in hz if ev["kind"] == "horizon"}
    if loop != "ragged":
        assert not eng.ragged and eng.scheduler is None
        assert programs == {"decode_step"} if loop == "per_tick" else \
            all(p.startswith("decode_multi_k") for p in programs)
        assert eng.stats.prefill_syncs >= 1
        assert any(ev["kind"] == "prefill_sync" for ev in hz)
    else:
        assert eng.ragged and eng.scheduler is not None
        assert all(p.startswith("packed_multi_k") for p in programs)
        assert eng.stats.prefill_syncs == 0      # no blocking prefill
        assert eng.stats.prefill_chunks >= 5
        assert all(ev["kind"] == "horizon" for ev in hz)
    if "host_sync_s" in eng_kw:
        assert eng.k_max == 1
        assert all(ev["k"] == 1 for ev in hz)
    # same streams whichever loop
    tick, _ = _stream(tiny_model, prompts, 5, k_max=1)
    assert outs == tick


@pytest.mark.parametrize("build", ["engine", "tenant_engine", "gpt_decoder",
                                   "mla_decoder"])
def test_the_layout_switch_is_gone(tiny_model, build):
    """`packed=` chose between two layouts of the mixed horizon; the
    packed token stream is the one left, and neither engine nor either
    decoder takes the flag any more (a `TypeError`, not a silent
    acceptance)."""
    from paddle_tpu.serving import TenantEngine
    from paddle_tpu.serving.mla_decoder import PagedMLADecoder

    def decoder(**kw):
        return PagedGPTDecoder(tiny_model, num_pages=16, page_size=16,
                               max_batch=2, **kw)

    make = {
        "engine": lambda **kw: ContinuousBatchingEngine(decoder(), **kw),
        "tenant_engine": lambda **kw: TenantEngine(decoder(), **kw),
        "gpt_decoder": decoder,
        # the flags fail before the model is looked at
        "mla_decoder": lambda **kw: PagedMLADecoder(tiny_model, **kw),
    }[build]
    for value in (False, True, None):
        with pytest.raises(TypeError, match="packed"):
            make(packed=value)


def test_scheduler_chunk_budget_never_exceeded(tiny_model):
    """Review regression: a non-power-of-two chunk_tokens must bound
    the dispatched width from BELOW (normalized down to pow2) — plan()
    buckets widths to powers of two, and rounding UP would exceed the
    per-tick token budget the parameter exists to cap."""
    from paddle_tpu.serving import RaggedScheduler
    dec = PagedGPTDecoder(tiny_model, num_pages=32, page_size=16,
                          max_batch=2)
    sched = RaggedScheduler(dec, chunk_tokens=6)
    assert sched.chunk_tokens == 4
    sched.admit(0, 40)
    plan = sched.plan({0: 0}, {0: 8}, [0, 0])
    assert plan.w <= 4


def test_no_live_references_to_deleted_prefill_buckets():
    """The flash length-bucketed prefill is deleted (ALL prefill runs
    through the ragged body): no live source may still reference the
    old entry points (CHANGES.md history exempt)."""
    import pathlib
    import re as _re
    root = pathlib.Path(__file__).resolve().parent.parent
    # built by concatenation so this test file doesn't match itself
    dead = ["_prefill" + "_fn", "_prefill" + "s"]
    offenders = []
    files = []
    for sub in ("paddle_tpu", "examples", "tests", "docs"):
        files.extend((root / sub).rglob("*"))
    for p in files:
        if p.suffix not in (".py", ".md") or "__pycache__" in str(p):
            continue
        text = p.read_text(errors="ignore")
        for name in dead:
            if _re.search(rf"(?<![\w.]){_re.escape(name)}\b", text):
                offenders.append(f"{p.relative_to(root)}: {name}")
    assert offenders == [], offenders


@pytest.mark.parametrize("seed", range(5))
def test_continuous_batching_fuzz_matches_golden(tiny_model, seed):
    """Randomized admission churn: random prompt lengths and request
    counts (always exceeding the slot count), with EOS enabled so some
    sequences retire early — every request's output must equal its
    isolated golden greedy decode truncated at EOS."""
    rng = np.random.RandomState(seed)
    dec = PagedGPTDecoder(tiny_model, num_pages=48, page_size=16,
                          max_batch=3)
    eos = int(rng.randint(0, tiny_model.cfg.vocab_size))
    max_new = int(rng.randint(3, 9))
    eng = ContinuousBatchingEngine(dec, eos_token_id=eos,
                                   max_new_tokens=max_new)
    n_req = int(rng.randint(4, 8))
    prompts = [list(rng.randint(0, tiny_model.cfg.vocab_size,
                                rng.randint(1, 12)).astype(int))
               for _ in range(n_req)]
    rids = [eng.submit(np.asarray(p, np.int32)) for p in prompts]
    outs = eng.run()
    for rid, p in zip(rids, prompts):
        golden = _golden_greedy(tiny_model, p, max_new)
        if eos in golden:
            golden = golden[:golden.index(eos) + 1]
        assert outs[rid] == golden, (p, eos, max_new)
    assert len(eng._free) == dec.num_pages - 1   # no page leaks


# --------------------------------------------------------------------------
# Packed ragged layout: pay for tokens, not windows
# --------------------------------------------------------------------------

def _stream_kw(model, prompts, max_new, eos=None, dec_kw=None,
               max_batch=2, **eng_kw):
    dec = PagedGPTDecoder(model, num_pages=48, page_size=16,
                          max_batch=max_batch, **(dec_kw or {}))
    eng = ContinuousBatchingEngine(dec, eos_token_id=eos,
                                   max_new_tokens=max_new, **eng_kw)
    rids = [eng.submit(np.asarray(p, np.int32)) for p in prompts]
    res = eng.run()
    return [res[r] for r in rids], eng


@pytest.mark.parametrize("seed", range(3))
def test_packed_streams_byte_identical_under_churn(tiny_model, seed):
    """THE packed acceptance bar: under randomized admission churn
    (sampled config + EOS + chunked prompts + more requests than
    slots), the PACKED token-stream engine's per-request streams are
    byte-identical to the per-tick engine's — with the prefix cache
    on and off, and (seed-rotated) over an int8 KV pool. The packed
    layout changes WHAT is dispatched, never what any position
    computes."""
    rng = np.random.RandomState(700 + seed)
    V = tiny_model.cfg.vocab_size
    prompts = [list(rng.randint(0, V, rng.randint(1, 40)).astype(int))
               for _ in range(4)]
    eos = int(rng.randint(0, V))
    max_new = int(rng.randint(3, 14))
    dec_kw = dict(temperature=0.8, top_k=40, seed=11)
    if seed == 2:                     # int8 pool rides the same twin
        dec_kw["kv_quant"] = "int8"
    base, _ = _stream_kw(tiny_model, prompts, max_new, eos, dec_kw,
                         k_max=1)
    for cache in (None, True):
        packed, ep = _stream_kw(tiny_model, prompts, max_new, eos,
                                dec_kw, k_max=4, chunk_tokens=8,
                                prefix_cache=cache)
        assert packed == base, (seed, cache, "packed")
        assert ep.stats.prefill_syncs == 0
        # the layout claim, weak form at this 2-slot toy scale (the
        # pow2 bucket can tie the [S, w] window grid exactly; the
        # strict win needs decode rows outnumbering chunk rows — pinned
        # in test_packed_pad_ledger_counts_tokens_not_windows)
        hz = ep.serve_schedule()
        assert ep.stats.tokens_dispatched == \
            sum(ev["k"] * ev["t_tokens"] for ev in hz) <= \
            sum(ev["k"] * ev["slots"] * ev["w"] for ev in hz)


def test_packed_pad_ledger_counts_tokens_not_windows(tiny_model):
    """ServeStats pad ledger, pinned on a deterministic mixed workload
    to counts made by hand: a horizon dispatches k x its pow2
    total-token bucket, not the k x S x w window grid, and dispatched
    minus padded is exactly the positions the requests consumed.

    Four slots, chunks of 8, K=4, 8 tokens an answer; prompts of 40, 3,
    2 and 3 tokens sent together. Horizon 1 (k=4): tick 0 streams
    8 + 3 + 2 + 3 = 16 tokens (bucket 16), ticks 1-3 the long prompt's 8
    beside three decode rows (11 of 16): 49 real of 64. Horizon 2 (k=1):
    the prompt's last 8 and three decode rows, 11 of 16. Then four
    decode rows in a bucket of 4: k=2 (8 of 8) and k=1 (4 of 4), after
    which the three short requests have their 8 tokens; the last
    horizon (k=4) still holds their frozen rows (retirement is one
    horizon late) and emits the long request's last 4: 4 of 16."""
    long_p = list(range(1, 41))
    shorts = [[3, 141, 59], [7, 8], [9, 10, 11]]
    outs, eng = _stream_kw(tiny_model, [long_p] + shorts, 8, k_max=4,
                           chunk_tokens=8, max_batch=4)
    assert outs == [_golden_greedy(tiny_model, p, 8)
                    for p in [long_p] + shorts]
    hz = eng.serve_schedule()
    assert [(ev["k"], ev["w"], ev["t_tokens"], ev["tokens_dispatched"],
             ev["tokens_padded"]) for ev in hz] == [
        (4, 8, 16, 64, 15), (1, 8, 16, 16, 5), (2, 1, 4, 8, 0),
        (1, 1, 4, 4, 0), (4, 1, 4, 16, 12)]
    s = eng.stats
    assert (s.tokens_dispatched, s.tokens_padded) == (108, 32)
    assert s.summary()["pad_fraction"] == round(32 / 108, 4)
    # real work: every prompt token and every generated token but each
    # request's last (emitted, never consumed)
    assert s.tokens_dispatched - s.tokens_padded == \
        sum(map(len, [long_p] + shorts)) + 4 * 8 - 4
    # the [S, w] window grid of the same schedule: 128 + 32 + 8 + 4 + 16
    assert sum(ev["k"] * ev["slots"] * ev["w"] for ev in hz) == 188
    # every horizon carries its pow2 bucket, at least a token a slot
    assert all(ev["t_tokens"] & (ev["t_tokens"] - 1) == 0
               and ev["t_tokens"] >= eng.d.max_batch for ev in hz)


def test_packed_prefill_batches_mixed_lengths_in_one_bucket(tiny_model):
    """PACKED chunked prefill: mixed suffix lengths dispatch as ONE
    flat stream per total-token bucket (one jit entry) instead of one
    program per (suffix-width, batch) pair — each first token the dense
    model's greedy choice."""
    dec = PagedGPTDecoder(tiny_model, num_pages=32, page_size=16,
                          max_batch=4)
    reqs = [(list(range(1, 6)), 0, [0]),          # 5 tokens
            (list(range(1, 18)), 0, [1, 2]),      # 17 tokens
            (list(range(1, 3)), 0, [3])]          # 2 tokens
    first = dec.prefill_suffix_batch(reqs, kids=[0, 1, 2])
    assert first == [_golden_greedy(tiny_model, ids, 1)[0]
                     for ids, _, _ in reqs]
    # 5+17+2 = 24 tokens -> ONE t=32 packed program (rows laid out in
    # windows of 32, the longest suffix's bucket); a (W, nb) window
    # grid would take W=8, W=32 and W=4: three programs
    assert list(dec._packed_prefills) == [(32, 32)]


def test_scheduler_plans_pow2_token_buckets(tiny_model):
    """HorizonPlan.t_tokens: pow2, floored at the slot count, covering
    the tick-0 total (decode rows pay 1, prefilling rows min(left, w))."""
    from paddle_tpu.serving import RaggedScheduler
    dec = PagedGPTDecoder(tiny_model, num_pages=32, page_size=16,
                          max_batch=4)
    sched = RaggedScheduler(dec, chunk_tokens=8)
    # pure decode: floored at S
    plan = sched.plan({0: 0, 1: 1}, {0: 8, 1: 8}, [0] * 4)
    assert plan.t_tokens == 4
    # mixed: 3 decode rows + one 20-token suffix at w=8 -> 3+8=11 -> 16
    sched2 = RaggedScheduler(dec, chunk_tokens=8)
    sched2.admit(3, 20)
    plan2 = sched2.plan({0: 0, 1: 1, 2: 2, 3: 3},
                        {0: 8, 1: 8, 2: 8, 3: 8}, [0] * 4)
    assert plan2.w == 8 and plan2.t_tokens == 16


# --------------------------------------------------------------------------
# The page pool rides the layer loop's carry: written and read in place
# --------------------------------------------------------------------------

def _pool_kw(pool):
    """Decoder arguments of the three pool layouts."""
    import jax.numpy as jnp
    return {"bf16": dict(dtype=jnp.bfloat16), "int8": dict(kv_quant="int8"),
            "int4": dict(kv_quant="int4")}[pool]


def _pool_leaves(dec):
    """Every leaf of both pools (payload and, quantised, scales), as
    bytes, without the scratch page (masked writes land there)."""
    import jax
    return [np.asarray(leaf)[:, :dec.num_pages - 1].view(np.uint8)
            for leaf in jax.tree_util.tree_leaves((dec.k_pages,
                                                   dec.v_pages))]


@pytest.mark.parametrize("pool", ["bf16", "int8", "int4"])
def test_pools_byte_identical_across_engines(tiny_model, pool):
    """After the same requests the per-tick engine and the ragged
    packed engine leave THE SAME BYTES in the same pages — payload and
    scales, every layer — and emit the same streams: one write
    (`_kv_set` at the layer's index of the whole pool) behind both
    blocks of the decoder they run."""
    dec_kw = _pool_kw(pool)
    rng = np.random.RandomState(900)
    V = tiny_model.cfg.vocab_size
    # as many requests as slots, sent together: every engine hands them
    # the same pages, so the pools can be compared page for page
    prompts = [list(rng.randint(0, V, n).astype(int)) for n in (23, 5)]
    runs = {}
    for name, eng_kw in (("tick", dict(k_max=1)),
                         ("packed", dict(k_max=4, chunk_tokens=8))):
        streams, eng = _stream_kw(tiny_model, prompts, 9, None, dec_kw,
                                  **eng_kw)
        runs[name] = (streams, _pool_leaves(eng.d))
    assert len(runs["tick"][1]) == (2 if pool == "bf16" else 4)
    assert any(leaf.any() for leaf in runs["tick"][1])
    assert runs["packed"][0] == runs["tick"][0], pool
    for a, b in zip(runs["packed"][1], runs["tick"][1]):
        assert a.shape == b.shape and np.array_equal(a, b), pool


@pytest.mark.parametrize("option", [
    "prefix_cache", "lora", "prefix_cache+lora", "prefix_cache+int8",
    "prefix_cache+int4", "lora+int8", "lora+int4"])
def test_per_tick_reference_and_ragged_engine_agree_under(tiny_model,
                                                          option):
    """The per-tick loop (`k_max=1`: blocking packed prefill, one tick a
    sync) is the plain reference of the ragged loop: with the SAME
    option on both — a prefix cache over prompts that share a block
    (one of them a full hit, which copies on write), a LoRA bank with a
    different adapter a request, a quantised pool — the sampled streams
    are byte-identical under churn (more requests than slots, EOS), and
    both leave every page owned exactly once."""
    from paddle_tpu.serving import make_lora_bank
    parts = option.split("+")
    rng = np.random.RandomState(1200)
    V = tiny_model.cfg.vocab_size
    shared = list(rng.randint(0, V, 16).astype(int))    # one full block
    prompts = [shared + list(rng.randint(0, V, n).astype(int))
               for n in (7, 21, 2)] + [list(shared)]
    eos = int(rng.randint(0, V))
    dec_kw = dict(temperature=0.8, top_k=40, seed=11)
    for pool in ("int8", "int4"):
        if pool in parts:
            dec_kw["kv_quant"] = pool
    adapters = [1, 2, 0, 2] if "lora" in parts else [None] * 4

    def run(**eng_kw):
        dec = PagedGPTDecoder(tiny_model, num_pages=48, page_size=16,
                              max_batch=2, **dec_kw)
        if "lora" in parts:
            dec.attach_adapters(make_lora_bank(tiny_model.cfg, 2, rank=4,
                                               seed=3))
        eng = ContinuousBatchingEngine(
            dec, eos_token_id=eos, max_new_tokens=7,
            prefix_cache=True if "prefix_cache" in parts else None,
            **eng_kw)
        rids = [eng.submit(np.asarray(p, np.int32), adapter=a)
                for p, a in zip(prompts, adapters)]
        out = eng.run()
        assert eng.audit_pages() == []
        return [out[r] for r in rids], eng

    tick, et = run(k_max=1)
    ragged, er = run(k_max=4, chunk_tokens=8)
    assert ragged == tick, option
    assert not et.ragged and er.ragged
    assert et.stats.prefill_syncs >= 2 and er.stats.prefill_syncs == 0
    if "prefix_cache" in parts:
        for eng in (et, er):
            assert eng.stats.prefix_hits >= 1, option
        # the full hit re-consumes its last token on a private copy
        # unless an adapter's salt keeps its pages apart
        if "lora" not in parts:
            assert et.stats.prefix_cow >= 1 and er.stats.prefix_cow >= 1
    if "lora" in parts:
        # adapters 1 and 2 really changed what was served
        base, _ = _stream(tiny_model, prompts[:1], 7, eos,
                          dict(dec_kw), k_max=4, chunk_tokens=8)
        assert len(tick[0]) >= 1 and tick[0] != base[0]


@pytest.mark.parametrize("pool", ["bf16", "int8", "int4"])
def test_kv_write_at_a_layer_leaves_the_other_layers_untouched(tiny_model,
                                                                pool):
    """`_kv_set` at layer l of the whole pool changes the written (page,
    offset) rows of layer l and not one other byte: every other layer,
    and every other row of layer l, keeps what it held — payload and
    scales."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.serving.decoder import _kv_set
    dec = PagedGPTDecoder(tiny_model, num_pages=6, page_size=4,
                          max_batch=1, **_pool_kw(pool))
    rng = np.random.RandomState(901)
    H, D = tiny_model.cfg.num_heads, tiny_model.cfg.head_dim
    L = tiny_model.cfg.num_layers

    def filled(leaf):          # nothing is zero before the write
        raw = rng.randint(1, 100, leaf.shape)
        return jnp.asarray(raw).astype(leaf.dtype)

    before = jax.tree_util.tree_map(filled, dec.k_pages)
    pids = jnp.asarray([4, 1, 4], jnp.int32)
    offs = jnp.asarray([0, 3, 2], jnp.int32)
    val = jnp.asarray(rng.randn(3, H, D).astype(np.float32)) * 50
    for li in range(L):
        after = jax.jit(_kv_set)(before, jnp.int32(li), pids, offs, val)
        for b, a in zip(jax.tree_util.tree_leaves(before),
                        jax.tree_util.tree_leaves(after)):
            b, a = np.asarray(b), np.asarray(a)
            assert a.dtype == b.dtype and a.shape == b.shape
            written = np.zeros(b.shape[:3], bool)
            written[li, np.asarray(pids), np.asarray(offs)] = True
            assert np.array_equal(a[~written], b[~written]), (pool, li)
            assert not np.array_equal(a[written], b[written]), (pool, li)


def test_packed_horizon_moves_no_pool(tiny_model):
    """The compiled packed decode horizon, jitted as the engine jits it
    (`_packed_multi_step`, pools donated), neither copies the page pool
    nor slices a layer out of it nor writes a layer back into it: the
    pools ride the layer loop's carry and the only instruction with a
    pool-shaped result is the in-place scatter of the new tokens. With
    the pools as the layer scan's `xs`/`ys` the program held all three
    (a `dynamic-slice` and a `dynamic-update-slice` of a layer per layer,
    a `copy` of the whole pool per tick) and both pools again as
    temporaries."""
    from tests._hlo_pool import compile_packed_horizon, pool_moves

    paddle.seed(7)
    model = GPT(gpt_tiny(max_seq_len=128, dtype="float32", remat=False,
                         num_layers=4))
    model.eval()
    S = 4
    dec = PagedGPTDecoder(model, num_pages=S * 16 + 2, page_size=16,
                          max_batch=S)
    compiled = compile_packed_horizon(dec, k=2, t=S, width=8, w=1)
    assert pool_moves(compiled, dec.k_pages) == []
    # what is left is a tick's gathers (2/L of a pool) and activations
    assert compiled.memory_analysis().temp_size_in_bytes < \
        dec.k_pages.nbytes
