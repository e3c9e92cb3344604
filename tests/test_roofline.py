"""cost_model's offline pricing: chip-spec resolution, analytic jaxpr
FLOPs, the max(compute, HBM, wire) roofline, and the ICI/DCN wire-byte
split for host-crossing mesh axes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.cost_model import (CHIP_SPECS, ChipSpec, axis_host_count,
                                   chip_spec, collective_wire_bytes,
                                   collective_wire_split, eqn_flops,
                                   jaxpr_flops, roofline_step_time)


class TestChipSpec:
    def test_device_kind_strings_resolve(self):
        assert chip_spec("TPU v5 lite").name == "v5e"
        assert chip_spec("TPU v6 lite").name == "v6e"   # before 'lite'
        assert chip_spec("TPU v5p").name == "v5p"
        assert chip_spec("TPU v4").name == "v4"
        assert chip_spec("v5e") is CHIP_SPECS["v5e"]

    def test_cpu_defaults_to_v5e(self):
        # no-TPU environments price for the campaign's reference chip
        assert chip_spec().name == "v5e"
        assert chip_spec("cpu").name == "v5e"


class TestJaxprFlops:
    def test_matmul_exact(self):
        m, k, n = 8, 16, 32
        jx = jax.make_jaxpr(lambda a, b: a @ b)(
            jnp.zeros((m, k)), jnp.zeros((k, n)))
        assert jaxpr_flops(jx) == 2 * m * k * n

    def test_batched_matmul_counts_batch(self):
        b, m, k, n = 4, 8, 16, 32
        jx = jax.make_jaxpr(
            lambda a, c: jnp.einsum("bmk,bkn->bmn", a, c))(
            jnp.zeros((b, m, k)), jnp.zeros((b, k, n)))
        dot = [e for e in jx.jaxpr.eqns
               if e.primitive.name == "dot_general"][0]
        assert eqn_flops(dot) == 2 * b * m * k * n

    def test_scan_multiplies_by_trip_count(self):
        def body(c, _):
            return c @ c, None

        def f(x):
            out, _ = jax.lax.scan(body, x, None, length=7)
            return out
        jx = jax.make_jaxpr(f)(jnp.zeros((8, 8)))
        assert jaxpr_flops(jx) == 7 * 2 * 8 * 8 * 8

    def test_elementwise_is_cheap(self):
        jx = jax.make_jaxpr(lambda a: a + 1.0)(jnp.zeros((16, 16)))
        assert jaxpr_flops(jx) == 16 * 16


class TestRoofline:
    def test_bound_classification(self):
        chip = ChipSpec("t", peak_flops=1e12, hbm_bw=1e9,
                        hbm_bytes=1 << 30, ici_bw=1e9, dcn_bw=1e8)
        rt = roofline_step_time(1e12, 1e3, chip=chip, mxu_efficiency=1.0)
        assert rt.bound == "compute" and rt.step_s == pytest.approx(1.0)
        rt = roofline_step_time(1e3, 1e9, chip=chip)
        assert rt.bound == "hbm" and rt.step_s == pytest.approx(1.0)
        rt = roofline_step_time(1e3, 1e3, ici_bytes=1e9, chip=chip)
        assert rt.bound == "wire"

    def test_step_time_is_max_of_legs(self):
        rt = roofline_step_time(1e12, 1e9, chip="v5e")
        assert rt.step_s == max(rt.compute_s, rt.hbm_s, rt.wire_s)


class TestWireSplit:
    def test_single_host_is_all_ici(self):
        s = collective_wire_split("all_reduce", 1 << 20, 8, host_count=1)
        assert s["dcn"] == 0
        assert s["ici"] == collective_wire_bytes("all_reduce", 1 << 20, 8)

    def test_two_host_dp_mesh_pin(self):
        """The ROADMAP multi-host item: dp=8 over 2 hosts, all_reduce of
        a 1 MiB payload. Ring wire = 2*(7/8)*P per device; 2 of the 8
        hops cross DCN, so exactly 2/8 of the volume prices at DCN."""
        payload = 1 << 20
        total = collective_wire_bytes("all_reduce", payload, 8)
        assert total == int(2 * (7 / 8) * payload)
        s = collective_wire_split("all_reduce", payload, 8, host_count=2)
        assert s["dcn"] == int(total * 2 / 8)
        assert s["ici"] + s["dcn"] == total
        # jaxpr alias vocabulary works here too
        s2 = collective_wire_split("psum", payload, 8, host_count=2)
        assert s2 == s

    def test_degenerate_groups(self):
        assert collective_wire_split("all_reduce", 1 << 20, 1,
                                     host_count=4) == {"ici": 0, "dcn": 0}
        assert collective_wire_split("all_reduce", 0, 8,
                                     host_count=2) == {"ici": 0, "dcn": 0}

    def test_axis_host_count_duck_typed_mesh(self):
        class Dev:
            def __init__(self, proc):
                self.process_index = proc

        class FakeMesh:
            axis_names = ("dp", "tp")
            # dp=4 spans 2 hosts (2 chips per host); tp=2 chip-local
            devices = np.array(
                [[Dev(0), Dev(0)], [Dev(0), Dev(0)],
                 [Dev(1), Dev(1)], [Dev(1), Dev(1)]])

        m = FakeMesh()
        assert axis_host_count(m, "dp") == 2
        assert axis_host_count(m, "tp") == 1
        assert axis_host_count(m, "ep") == 1      # unknown axis
        assert axis_host_count(None, "dp") == 1   # robustness

    def test_live_single_process_mesh_is_chip_local(self):
        from paddle_tpu.distributed import build_mesh
        mesh = build_mesh(dp=1)
        for a in mesh.axis_names:
            assert axis_host_count(mesh, a) == 1


class TestDecodeHorizon:
    """cost_model.decode_horizon: pricing the multi-step decode K from
    the tick roofline vs the host sync cost."""

    def test_tick_roofline_is_bytes_over_bandwidth(self):
        from paddle_tpu.cost_model import (chip_spec,
                                           decode_tick_roofline_s)
        chip = chip_spec("v5e")
        assert decode_tick_roofline_s(chip.hbm_bw, chip=chip) == \
            pytest.approx(1.0)

    def test_horizon_scales_with_host_overhead_share(self):
        from paddle_tpu.cost_model import chip_spec, decode_horizon
        chip = chip_spec("v5e")
        tick_s = 1e-3
        step_bytes = int(tick_s * chip.hbm_bw)
        # sync cost == 10% of a tick: K=1 already meets the 10% bar
        assert decode_horizon(step_bytes, host_sync_s=1e-4,
                              chip=chip) == 1
        # sync cost == 8 ticks: need K=80 to amortize to 10% -> capped
        assert decode_horizon(step_bytes, host_sync_s=8e-3, chip=chip,
                              k_cap=32) == 32
        # mid-range: h/(K*t) <= 0.1 with h = t -> K = 10
        assert decode_horizon(step_bytes, host_sync_s=1e-3,
                              chip=chip) == 10

    def test_horizon_monotone_in_model_size(self):
        """Bigger models (longer ticks) need smaller K; a micro model
        prices to the cap."""
        from paddle_tpu.cost_model import decode_horizon
        h = 5e-4
        ks = [decode_horizon(b, host_sync_s=h, chip="v5e")
              for b in (10**6, 10**9, 10**11)]
        assert ks == sorted(ks, reverse=True)
        assert ks[0] == 32 and ks[-1] == 1

    def test_measured_host_sync_is_cached_and_sane(self):
        from paddle_tpu.cost_model import measured_host_sync_s
        s = measured_host_sync_s()
        assert 1e-6 <= s < 1.0
        assert measured_host_sync_s() == s        # memoized


class TestRaggedTick:
    """cost_model.ragged_tick_roofline_s / ragged_chunk_tokens /
    the chunk-aware decode_horizon: pricing mixed chunked-prefill +
    decode ticks."""

    def test_mixed_tick_is_max_of_legs(self):
        from paddle_tpu.cost_model import (chip_spec,
                                           decode_tick_roofline_s,
                                           ragged_tick_roofline_s)
        chip = chip_spec("v5e")
        b = int(1e-3 * chip.hbm_bw)          # 1 ms HBM leg
        # no chunk: exactly the decode tick roofline
        assert ragged_tick_roofline_s(b, 0, 0, chip=chip) == \
            decode_tick_roofline_s(b, chip=chip)
        # a chunk hiding under the HBM leg adds NOTHING (why chunked
        # prefill rides 'free' in an HBM-bound tick)
        f = 2.6e9
        per_tok = f / (chip.peak_flops * 0.65)
        w_free = int(0.5e-3 / per_tok)
        assert ragged_tick_roofline_s(b, w_free, f, chip=chip) == \
            decode_tick_roofline_s(b, chip=chip)
        # past the crossover the tick goes compute-bound, linear in W
        w_heavy = int(4e-3 / per_tok)
        t = ragged_tick_roofline_s(b, w_heavy, f, chip=chip)
        assert t == pytest.approx(w_heavy * per_tok)
        assert ragged_tick_roofline_s(b, 2 * w_heavy, f, chip=chip) == \
            pytest.approx(2 * t)

    def test_chunk_budget_hides_under_hbm_leg(self):
        from paddle_tpu.cost_model import (chip_spec,
                                           decode_tick_roofline_s,
                                           ragged_chunk_tokens,
                                           ragged_tick_roofline_s)
        chip = chip_spec("v5e")
        b = int(1e-3 * chip.hbm_bw)
        f = 2.6e9                             # ~1.3B prompt token
        w = ragged_chunk_tokens(b, f, chip=chip, cap=1 << 14)
        assert w & (w - 1) == 0               # power of two
        # the budgeted chunk is free; doubling it would not be
        assert ragged_tick_roofline_s(b, w, f, chip=chip) == \
            decode_tick_roofline_s(b, chip=chip)
        assert ragged_tick_roofline_s(b, 2 * w, f, chip=chip) > \
            decode_tick_roofline_s(b, chip=chip)

    def test_chunk_budget_clamps(self):
        from paddle_tpu.cost_model import ragged_chunk_tokens
        # zero flops (degenerate): everything hides -> the cap
        assert ragged_chunk_tokens(10**9, 0.0, chip="v5e", cap=256) == 256
        # compute-tight model: floor keeps prompts progressing
        assert ragged_chunk_tokens(10**3, 1e12, chip="v5e",
                                   floor=8) == 8

    def test_decode_horizon_is_chunk_aware(self):
        """A mixed tick is never shorter than a pure decode tick, so
        the priced K with a chunk budget is <= the pure-decode K —
        and equal while the chunk hides under the HBM leg."""
        from paddle_tpu.cost_model import chip_spec, decode_horizon
        chip = chip_spec("v5e")
        b = int(1e-3 * chip.hbm_bw)
        f = 2.6e9
        pure = decode_horizon(b, host_sync_s=1e-3, chip=chip)
        free = decode_horizon(b, host_sync_s=1e-3, chip=chip,
                              chunk_tokens=16, flops_per_token=f)
        heavy = decode_horizon(b, host_sync_s=1e-3, chip=chip,
                               chunk_tokens=1 << 16,
                               flops_per_token=f)
        assert free == pure == 10
        assert heavy < pure

    def test_engine_defaults_to_priced_horizon(self):
        """ContinuousBatchingEngine with no k_max asks decode_horizon;
        on a CPU dev box the tiny decoder's tick roofline is far below
        the measured sync cost, so the priced K lands at the cap."""
        import paddle_tpu as paddle
        from paddle_tpu.cost_model import decode_horizon
        from paddle_tpu.distributed import build_mesh
        from paddle_tpu.models import GPT, gpt_tiny
        from paddle_tpu.serving import (ContinuousBatchingEngine,
                                        PagedGPTDecoder)
        paddle.seed(0)
        build_mesh(dp=1)
        model = GPT(gpt_tiny(max_seq_len=64, dtype="float32",
                             remat=False))
        model.eval()
        dec = PagedGPTDecoder(model, num_pages=8, page_size=16,
                              max_batch=2)
        eng = ContinuousBatchingEngine(dec, max_new_tokens=4)
        assert eng.k_max == decode_horizon(dec.step_hbm_bytes())
        assert eng.k_max >= 1


class TestTrainHorizon:
    """cost_model.train_horizon: pricing the multi-step training N from
    the step roofline vs the host sync cost (decode_horizon's twin)."""

    def test_horizon_scales_with_host_overhead_share(self):
        from paddle_tpu.cost_model import train_horizon
        step_s = 1e-3
        # sync cost == 10% of a step: N=1 already meets the 10% bar
        assert train_horizon(step_s, host_sync_s=1e-4) == 1
        # sync cost == 8 steps: need N=80 to amortize to 10% -> capped
        assert train_horizon(step_s, host_sync_s=8e-3, n_cap=32) == 32
        # mid-range: h/(N*t) <= 0.1 with h = t -> N = 10
        assert train_horizon(step_s, host_sync_s=1e-3) == 10

    def test_horizon_monotone_in_step_time(self):
        """Bigger steps need smaller N; a micro-model step prices to
        the cap, a 1.3B-class step prices to 1."""
        from paddle_tpu.cost_model import train_horizon
        h = 5e-4
        ns = [train_horizon(s, host_sync_s=h)
              for s in (1e-6, 1e-4, 1e-2, 0.4)]
        assert ns == sorted(ns, reverse=True)
        assert ns[0] == 32 and ns[-1] == 1

    def test_degenerate_step_time_prices_to_cap(self):
        from paddle_tpu.cost_model import train_horizon
        assert train_horizon(0.0, host_sync_s=1e-3) == 32
        assert train_horizon(None, host_sync_s=1e-3, n_cap=16) == 16

    def test_default_sync_cost_is_the_measured_one(self):
        from paddle_tpu.cost_model import (measured_host_sync_s,
                                           train_horizon)
        h = measured_host_sync_s()
        assert train_horizon(1e-3) == train_horizon(1e-3, host_sync_s=h)

    def test_roofline_step_feeds_horizon(self):
        """The intended composition: roofline_step_time(...).step_s is
        the numerator train_horizon prices against."""
        from paddle_tpu.cost_model import (chip_spec, roofline_step_time,
                                           train_horizon)
        chip = chip_spec("v5e")
        # a compute-bound 1.3B-ish step: ~400 ms — any realistic sync
        # cost is <10% of it, so N=1
        rt = roofline_step_time(6 * 1.3e9 * 6 * 1024, 1.3e9 * 12,
                                chip=chip)
        assert train_horizon(rt.step_s, host_sync_s=4e-4) == 1


class TestPrefillTTFT:
    """prefill_ttft_s: the TTFT pricing that discounts cached-prefix
    prefill (the cost_model half of the prefix cache)."""

    def test_monotone_decreasing_in_hit_rate(self):
        from paddle_tpu.cost_model import prefill_ttft_s
        chip = CHIP_SPECS["v5e"]
        vals = [prefill_ttft_s(512, 2e9, cached_frac=f, chip=chip,
                               host_sync_s=1e-4)
                for f in (0.0, 0.25, 0.5, 0.75, 1.0)]
        assert all(a > b for a, b in zip(vals, vals[1:]))

    def test_full_hit_collapses_to_the_sync_floor(self):
        from paddle_tpu.cost_model import prefill_ttft_s
        chip = CHIP_SPECS["v5e"]
        full = prefill_ttft_s(512, 2e9, cached_frac=1.0, chip=chip,
                              host_sync_s=1e-4)
        assert full == pytest.approx(1e-4)
        # and the discount is linear in the uncached span
        half = prefill_ttft_s(512, 2e9, cached_frac=0.5, chip=chip,
                              host_sync_s=1e-4)
        none = prefill_ttft_s(512, 2e9, cached_frac=0.0, chip=chip,
                              host_sync_s=1e-4)
        assert (none - full) == pytest.approx(2 * (half - full))

    def test_fraction_clamps_and_default_sync(self):
        from paddle_tpu.cost_model import (measured_host_sync_s,
                                           prefill_ttft_s)
        chip = CHIP_SPECS["v5e"]
        assert prefill_ttft_s(512, 2e9, cached_frac=7.0, chip=chip,
                              host_sync_s=1e-4) == pytest.approx(1e-4)
        lo = prefill_ttft_s(512, 2e9, cached_frac=-3.0, chip=chip,
                            host_sync_s=1e-4)
        assert lo == pytest.approx(
            prefill_ttft_s(512, 2e9, chip=chip, host_sync_s=1e-4))
        # host_sync_s=None uses the process-cached measurement
        got = prefill_ttft_s(16, 1e6, cached_frac=1.0, chip=chip)
        assert got == pytest.approx(measured_host_sync_s())


class TestKvQuantRoofline:
    """The int8 KV pool's repricing through cost_model: feeding
    `decode_horizon` / `ragged_chunk_tokens` the int8-pool byte count
    (int8 payload + 4B/token/layer scale planes) moves the priced
    knobs the way the capacity claim needs."""

    # a 1.3B-ish decode tick at long context and a BIG batch (the
    # KV-bound regime the pool quantization targets): weights 2.6 GB,
    # 80 slots' KV legs per the serving byte model (bf16 2B/elem vs
    # int8 1B + 4B/token/layer scale planes per plane)
    W_BYTES = int(2.6e9)
    KV16 = 80 * 24 * 1024 * 2 * 2048 * 2         # S*L*(H*D)*2*ctx*2B
    KV8 = 80 * 24 * 2048 * 2 * (1024 + 4)        # S*L*ctx*2*(H*D+4)

    def test_horizon_k_strictly_increases_with_int8_pool_bytes(self):
        """The int8 byte stream shortens the tick, so the engine must
        fuse MORE ticks per host sync to keep the sync share under the
        bar: decode_horizon strictly increases when step_hbm_bytes is
        fed the int8-pool byte count."""
        from paddle_tpu.cost_model import chip_spec, decode_horizon
        chip = chip_spec("v5e")
        b16 = self.W_BYTES + self.KV16
        b8 = self.W_BYTES + self.KV8
        assert (b16 - self.W_BYTES) / (b8 - self.W_BYTES) >= 1.7
        h = b16 / chip.hbm_bw                    # one bf16 tick's cost
        k16 = decode_horizon(b16, host_sync_s=h, chip=chip)
        k8 = decode_horizon(b8, host_sync_s=h, chip=chip)
        assert k8 > k16, (k8, k16)
        # and the tok/s view: the priced tick itself strictly shrinks
        from paddle_tpu.cost_model import decode_tick_roofline_s
        assert decode_tick_roofline_s(b8, chip=chip) < \
            decode_tick_roofline_s(b16, chip=chip)

    def test_chunk_budget_recovers_at_the_capacity_operating_point(self):
        """ragged_chunk_tokens prices the prompt tokens that hide under
        the tick's HBM leg, so per-SLOT-COUNT the shorter int8 tick
        hides fewer (the capacity win arrives as ~2x slots and a larger
        K, not a wider chunk at fixed batch). At the capacity operating
        point — the int8 pool serving the ~2x slots the fixed per-token
        p99 admits — the tick's byte stream is back at (slightly above,
        by the scale planes) the bf16 level, and the chunk budget
        strictly increases past the fixed-batch int8 budget, back to
        the bf16 one."""
        from paddle_tpu.cost_model import chip_spec, ragged_chunk_tokens
        chip = chip_spec("v5e")
        f = 2.6e9                                # flops per prompt token
        b16 = self.W_BYTES + self.KV16
        b8 = self.W_BYTES + self.KV8
        w16 = ragged_chunk_tokens(b16, f, chip=chip, cap=1 << 14)
        w8 = ragged_chunk_tokens(b8, f, chip=chip, cap=1 << 14)
        assert w8 < w16                          # fixed batch: shorter tick
        b8_cap = self.W_BYTES + 2 * self.KV8     # ~2x admitted slots
        assert b8_cap > b16                      # scale planes: strictly
        w8_cap = ragged_chunk_tokens(b8_cap, f, chip=chip, cap=1 << 14)
        assert w8_cap > w8
        assert w8_cap >= w16

    def test_decoder_reports_the_true_int8_stream(self):
        """step_hbm_bytes on a real decoder pair: the int8 pool's KV
        leg is int8 payload + 8B/token/layer of f32 scales (K and V),
        priced exactly — not an optimistic 2x."""
        import paddle_tpu as paddle
        from paddle_tpu.distributed import build_mesh
        from paddle_tpu.models import GPT, gpt_tiny
        from paddle_tpu.serving import PagedGPTDecoder
        paddle.seed(0)
        build_mesh(dp=1)
        model = GPT(gpt_tiny(max_seq_len=64, dtype="float32",
                             remat=False))
        model.eval()
        cfg = model.cfg
        d8 = PagedGPTDecoder(model, num_pages=8, page_size=16,
                             max_batch=2, kv_quant="int8")
        hd = cfg.num_heads * cfg.head_dim
        assert d8.kv_token_bytes == 2 * (hd + 4)
        ctx = 32
        got = d8.step_hbm_bytes(avg_ctx=ctx)
        want_kv = 2 * cfg.num_layers * ctx * 2 * (hd + 4)
        assert got - d8.step_hbm_bytes(avg_ctx=ctx, batch=0) == want_kv


class TestOverlapRoofline:
    """cost_model.roofline_step_time_overlap — the overlap-aware step
    model the schedule pass, the autotuner's `_price` and the flight
    recorder's serial band all share."""

    def test_bracket_is_provable(self):
        """max() <= overlap <= sum(), for every overlap fraction: the
        acceptance pin. The chip streams (compute, HBM) stay
        overlapped into their max; only the wire leg serializes."""
        from paddle_tpu.cost_model import (roofline_step_time,
                                           roofline_step_time_overlap)
        cases = [(1e12, 1e9, 1e8, 0), (1e10, 5e9, 5e8, 5e8),
                 (0, 1e9, 1e9, 0), (1e12, 1e6, 0, 0)]
        for flops, hbm, ici, dcn in cases:
            rt = roofline_step_time(flops, hbm, ici, dcn)
            serial = max(rt.compute_s, rt.hbm_s) + rt.wire_s
            for frac in (0.0, 0.25, 0.5, 0.75, 1.0):
                o = roofline_step_time_overlap(flops, hbm, ici, dcn,
                                               overlap_frac=frac)
                assert rt.step_s <= o.step_s + 1e-18, (frac, flops)
                assert o.step_s <= serial + 1e-18, (frac, flops)

    def test_full_overlap_is_exactly_todays_max(self):
        from paddle_tpu.cost_model import (roofline_step_time,
                                           roofline_step_time_overlap)
        rt = roofline_step_time(1e12, 2e9, 3e8, 1e7)
        o = roofline_step_time_overlap(1e12, 2e9, 3e8, 1e7,
                                       overlap_frac=1.0)
        assert o.step_s == rt.step_s
        assert o.bound == rt.bound

    def test_zero_overlap_is_chip_plus_wire_and_monotone(self):
        from paddle_tpu.cost_model import roofline_step_time_overlap
        o0 = roofline_step_time_overlap(1e12, 1e9, 1e9,
                                        overlap_frac=0.0)
        assert o0.step_s == pytest.approx(o0.chip_s + o0.wire_s)
        assert o0.bound == "wire-serialized"
        prev = None
        for frac in (0.0, 0.2, 0.4, 0.6, 0.8, 1.0):
            s = roofline_step_time_overlap(1e12, 1e9, 1e9,
                                           overlap_frac=frac).step_s
            if prev is not None:
                assert s <= prev + 1e-18    # more overlap never slower
            prev = s
        # out-of-range fractions clamp instead of extrapolating
        lo = roofline_step_time_overlap(1e12, 1e9, 1e9,
                                        overlap_frac=-3.0)
        hi = roofline_step_time_overlap(1e12, 1e9, 1e9,
                                        overlap_frac=7.0)
        assert lo.overlap_frac == 0.0 and hi.overlap_frac == 1.0

    def test_no_wire_is_invariant_in_frac(self):
        """A wire-free program prices identically at EVERY fraction —
        which is exactly why re-pricing the single-device gpt_1p3b
        probe grid through the overlap model cannot move the
        autotuner's bs6/dots pick (the slow grid test pins the pick
        itself; this pins the invariance that protects it)."""
        from paddle_tpu.cost_model import (roofline_step_time,
                                           roofline_step_time_overlap)
        rt = roofline_step_time(5e12, 3e9)
        for frac in (0.0, 0.37, 1.0):
            o = roofline_step_time_overlap(5e12, 3e9,
                                           overlap_frac=frac)
            assert o.step_s == rt.step_s
            assert o.bound == rt.bound

    def test_price_routes_through_overlap_model(self):
        """autotune._price with wire legs prices at the overlap-aware
        step: frac 1.0 reproduces the old max() exactly (same
        RematWhatIf, same throughput), frac 0 prices slower — the
        serialized candidate honestly loses the ranking."""
        from paddle_tpu.analysis.autotune import _price
        from paddle_tpu.analysis.remat_advisor import RematWhatIf
        from paddle_tpu.cost_model import chip_spec
        w = RematWhatIf(policy="none", peak_bytes=1 << 28,
                        base_peak_bytes=1 << 28, saved_bytes=1 << 24,
                        boundary_bytes=1 << 20, dropped_bytes=0,
                        bump_bytes=0, recompute_flops=0,
                        step_flops=10**13, segments=4)
        chip = chip_spec("v5e")
        args = (w, 1 << 26, 1 << 22, 1 << 26, 4096, "tokens/s", chip)
        peak1, fl1, rt1, thr1 = _price(*args, ici_b=1 << 28,
                                       overlap_frac=1.0)
        peak0, fl0, rt0, thr0 = _price(*args, ici_b=1 << 28,
                                       overlap_frac=0.0)
        assert (peak1, fl1) == (peak0, fl0)
        assert rt1.step_s == max(rt1.compute_s, rt1.hbm_s, rt1.wire_s)
        assert rt0.step_s > rt1.step_s and thr0 < thr1
        # no wire: the fraction is a no-op, bit-identical pricing
        pa = _price(*args, overlap_frac=1.0)
        pb = _price(*args, overlap_frac=0.123)
        assert pa[2].step_s == pb[2].step_s and pa[3] == pb[3]
