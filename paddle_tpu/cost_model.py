"""Cost model — reference python/paddle/cost_model/cost_model.py.

The reference profiles a static Program op-by-op against a benchmark JSON.
TPU-native: XLA's compiled cost analysis gives per-program FLOPs/bytes
analytically, and profile_measure times the real jitted program.

This module also hosts the OFFLINE half of config selection:

  * `ChipSpec` / `chip_spec` — the per-generation peak FLOP/s, HBM
    bandwidth/size and interconnect numbers the pricing below uses,
    in one queryable table;
  * `eqn_flops` / `jaxpr_flops` — analytic FLOPs of a traced jaxpr
    (dot/conv priced exactly from shapes, elementwise at 1 flop/elem,
    scan multiplied by trip count) — the compute numerator no chip is
    needed for;
  * `roofline_step_time` — price one training step as
    max(compute-bound, HBM-bound, wire-bound) time (the T3-style
    compute/collective split, arxiv 2401.16677; static per-program cost
    modeling after TPU-MLIR, arxiv 2210.15016). analysis/autotune.py
    ranks (microbatch, remat) candidates with it before anything
    compiles;
  * `collective_wire_bytes` / `collective_wire_split` — ring-model
    bytes-on-the-wire per collective, with DCN-spanning hops priced
    separately from ICI when the mesh axis crosses hosts.
"""
import time
from dataclasses import dataclass

import numpy as np

__all__ = ["CostModel", "collective_wire_bytes", "collective_wire_split",
           "axis_host_count", "ChipSpec", "chip_spec", "CHIP_SPECS",
           "eqn_flops", "jaxpr_flops", "RooflineTime",
           "roofline_step_time", "OverlapRooflineTime",
           "roofline_step_time_overlap", "decode_tick_roofline_s",
           "ragged_tick_legs", "ragged_tick_roofline_s",
           "ragged_chunk_tokens", "decode_horizon", "train_horizon",
           "measured_host_sync_s", "prefill_ttft_s", "kv_restore_s",
           "SLO_SYNC_FRAC", "slo_horizon", "slo_p99_target_s"]


# ------------------------------------------------------------------ chips
#
# Per-chip peak numbers (bf16 MXU FLOP/s, HBM bytes/s and capacity,
# aggregate one-direction ICI bytes/s, per-chip share of the host DCN
# NIC). The flops/HBM columns are the published peaks (the benchmark's
# own: peaks.json); ICI/DCN are approximate public figures — they feed
# RELATIVE ranking and the wire-bound roofline leg, not accounting.

@dataclass(frozen=True)
class ChipSpec:
    name: str
    peak_flops: float      # bf16 FLOP/s
    hbm_bw: float          # HBM bytes/s
    hbm_bytes: int         # HBM capacity per chip
    ici_bw: float          # aggregate ICI bytes/s per chip (one dir)
    dcn_bw: float          # per-chip share of host DCN bytes/s
    # host<->chip wire (PCIe DMA) bytes/s per chip — the H2D leg the
    # tiered-KV restore pricing (`kv_restore_s`) divides by: a page
    # spilled to pinned host RAM re-mounts at this bandwidth, vs
    # recomputing its span at the MXU roofline. Approximate public
    # figures (PCIe gen3/gen4-class hosts); they feed the RELATIVE
    # restore-vs-recompute decision, not accounting.
    host_bw: float = 1.6e10
    # host-tier READ bytes/s — the extra leg a CROSS-PROCESS shared
    # host tier (serving.fleet.SharedHostKVTier) pays BEFORE the PCIe
    # DMA: the payload lives in an shm-/file-backed store another
    # replica wrote, so a restore first copies it host-RAM -> host-RAM
    # (page-cache read + memcpy, roughly DRAM-copy bandwidth) and only
    # then crosses the wire. Distinct from `host_bw` so
    # `restore_beats_recompute(shared=True)` stays honest for the
    # fleet: the shared read never makes restore cheaper, only
    # costlier, and pricing it at PCIe alone would overclaim the wire.
    host_read_bw: float = 6.4e10


CHIP_SPECS = {
    "v4": ChipSpec("v4", 275e12, 1228e9, 32 << 30, 300e9, 3.1e9,
                   host_bw=1.6e10, host_read_bw=6.4e10),
    "v5e": ChipSpec("v5e", 197e12, 819e9, 16 << 30, 200e9, 3.1e9,
                    host_bw=1.6e10, host_read_bw=6.4e10),
    "v5p": ChipSpec("v5p", 459e12, 2765e9, 95 << 30, 600e9, 3.1e9,
                    host_bw=3.2e10, host_read_bw=1.2e11),
    "v6e": ChipSpec("v6e", 918e12, 1640e9, 32 << 30, 448e9, 3.1e9,
                    host_bw=3.2e10, host_read_bw=1.2e11),
}


def chip_spec(kind=None):
    """Resolve a ChipSpec from an explicit name ("v5e") or a jax
    device_kind string ("TPU v5 lite"). With kind=None, asks the live
    backend; the CPU backend resolves to v5e (the paper's reference
    chip), so static analysis off-chip prices for the chip the campaign
    targets. A kind that is in no spec raises: a live device this table
    cannot price is an error, not a v5e. Branch order matters: 'v6 lite'
    must check before the generic 'lite' clause or it reads as v5e."""
    if kind is None:
        import jax
        d = jax.devices()[0]
        kind = "cpu" if d.platform == "cpu" else d.device_kind
    k = str(kind).lower()
    if k == "cpu":
        return CHIP_SPECS["v5e"]
    if k in CHIP_SPECS:
        return CHIP_SPECS[k]
    if "v6" in k:
        return CHIP_SPECS["v6e"]
    if "v5 lite" in k or "v5e" in k or "lite" in k:
        return CHIP_SPECS["v5e"]
    if "v5p" in k or "v5" in k:
        return CHIP_SPECS["v5p"]
    if "v4" in k:
        return CHIP_SPECS["v4"]
    raise ValueError(
        f"chip_spec: no ChipSpec for device kind {kind!r} "
        f"(known: {', '.join(CHIP_SPECS)})")


# ------------------------------------------------------------ jaxpr flops

def _prod(xs):
    out = 1
    for x in xs:
        out *= int(x)
    return out


def eqn_flops(eqn):
    """Analytic executed FLOPs of one jaxpr eqn. dot_general and
    conv_general_dilated are priced exactly from shapes (2*M*N*K per
    contraction); eqns carrying sub-jaxprs recurse (scan multiplied by
    its trip count, cond priced at its most expensive branch);
    everything else is 1 flop per output element — elementwise ops are
    bandwidth-bound on TPU, so their flop count only needs the right
    order of magnitude."""
    name = eqn.primitive.name
    try:
        if name == "dot_general":
            (lc, _rc), (lb, _rb) = eqn.params["dimension_numbers"]
            lhs = eqn.invars[0].aval
            rhs = eqn.invars[1].aval
            batch = _prod(lhs.shape[i] for i in lb)
            k = _prod(lhs.shape[i] for i in lc)
            m = _prod(d for i, d in enumerate(lhs.shape)
                      if i not in set(lc) | set(lb))
            n = _prod(rhs.shape) // max(batch * k, 1)
            return 2 * batch * m * n * k
        if name == "conv_general_dilated":
            out = eqn.outvars[0].aval
            rhs = eqn.invars[1].aval
            dn = eqn.params["dimension_numbers"]
            out_ch = rhs.shape[dn.rhs_spec[0]]
            # per output element: one MAC per (kernel spatial x in-ch)
            return 2 * _prod(out.shape) * (_prod(rhs.shape) // max(out_ch, 1))
        subs = _eqn_sub_jaxprs(eqn)
        if subs:
            inner = [jaxpr_flops(sj) for sj in subs]
            if name == "scan":
                return int(eqn.params.get("length", 1)) * sum(inner)
            if name == "cond":
                return max(inner)
            return sum(inner)
        return _prod(getattr(eqn.outvars[0].aval, "shape", ()))
    except Exception:
        return 0


def _eqn_sub_jaxprs(eqn):
    found = []
    for v in eqn.params.values():
        vs = v if isinstance(v, (list, tuple)) else (v,)
        for x in vs:
            tn = type(x).__name__
            if tn == "ClosedJaxpr":
                found.append(x.jaxpr)
            elif tn == "Jaxpr":
                found.append(x)
    return found


def jaxpr_flops(jx):
    """Total analytic FLOPs of a (closed) jaxpr, sub-jaxprs included."""
    jx = jx.jaxpr if hasattr(jx, "jaxpr") else jx
    return sum(eqn_flops(eqn) for eqn in jx.eqns)


# -------------------------------------------------------------- roofline

@dataclass
class RooflineTime:
    """One candidate's step-time breakdown: the step takes at least as
    long as its slowest resource (compute, HBM, interconnect) — XLA
    overlaps the three, so the max is the analytic floor."""
    compute_s: float
    hbm_s: float
    wire_s: float

    @property
    def step_s(self):
        return max(self.compute_s, self.hbm_s, self.wire_s)

    @property
    def bound(self):
        return max((self.compute_s, "compute"), (self.hbm_s, "hbm"),
                   (self.wire_s, "wire"))[1]


def roofline_step_time(flops, hbm_bytes, ici_bytes=0, dcn_bytes=0,
                       chip=None, mxu_efficiency=0.65):
    """Analytic step time: max(compute, HBM, wire) seconds.

    `mxu_efficiency` derates peak FLOP/s for the achievable fraction on
    real schedules (the campaign's best measured MFU on compute-bound
    GPT configs is ~0.64 — rankings are insensitive to the constant,
    absolute tok/s predictions are honest with it). DCN hops are priced
    at DCN bandwidth on top of the ICI time: a multi-host ring's wire
    time is gated by its slowest link."""
    chip = chip if isinstance(chip, ChipSpec) else chip_spec(chip)
    compute = flops / (chip.peak_flops * mxu_efficiency)
    hbm = hbm_bytes / chip.hbm_bw
    wire = ici_bytes / chip.ici_bw + dcn_bytes / chip.dcn_bw
    return RooflineTime(compute_s=compute, hbm_s=hbm, wire_s=wire)


@dataclass
class OverlapRooflineTime:
    """Overlap-AWARE step-time breakdown: the chip streams (compute,
    HBM) still overlap into max(compute, hbm), but only
    ``overlap_frac`` of the wire time hides under them — the rest is
    EXPOSED and adds serially (the two-stream schedule model of
    analysis/schedule.py, after T3's compute/collective split, arxiv
    2401.16677).  ``overlap_frac=1`` collapses to `RooflineTime`'s
    max(); ``overlap_frac=0`` is the fully serialized
    max(compute, hbm) + wire.  step_s is bracketed by construction:
    max(compute, hbm, wire) <= step_s <= max(compute, hbm) + wire."""
    compute_s: float
    hbm_s: float
    wire_s: float
    overlap_frac: float = 1.0

    @property
    def chip_s(self):
        return max(self.compute_s, self.hbm_s)

    @property
    def exposed_wire_s(self):
        return (1.0 - self.overlap_frac) * self.wire_s

    @property
    def step_s(self):
        hidden = self.overlap_frac * self.wire_s
        return max(self.chip_s, hidden) + self.exposed_wire_s

    @property
    def bound(self):
        floor = max((self.compute_s, "compute"), (self.hbm_s, "hbm"),
                    (self.wire_s, "wire"))
        if self.step_s > floor[0] * (1 + 1e-12) and \
                self.exposed_wire_s > 0:
            return "wire-serialized"
        return floor[1]


def roofline_step_time_overlap(flops, hbm_bytes, ici_bytes=0,
                               dcn_bytes=0, overlap_frac=1.0,
                               chip=None, mxu_efficiency=0.65):
    """Overlap-aware analytic step time: the same three legs as
    `roofline_step_time`, with the wire leg only ``overlap_frac``
    hidden behind the chip streams.  `analysis/schedule.py`'s
    two-stream list schedule supplies the fraction from the real
    dependency DAG (`ScheduleEstimate.overlap_frac`); with no
    collectives (or frac 1.0) this is EXACTLY `roofline_step_time` —
    which is why re-pricing single-device candidates through it leaves
    the autotuner's ranking untouched."""
    chip = chip if isinstance(chip, ChipSpec) else chip_spec(chip)
    frac = min(max(float(overlap_frac), 0.0), 1.0)
    return OverlapRooflineTime(
        compute_s=flops / (chip.peak_flops * mxu_efficiency),
        hbm_s=hbm_bytes / chip.hbm_bw,
        wire_s=ici_bytes / chip.ici_bw + dcn_bytes / chip.dcn_bw,
        overlap_frac=frac)


# ------------------------------------------------- chunked-overlap leg

# per-chunk dispatch floor: issuing one more async collective-permute +
# matmul tile costs a scalar-core/launch slot even when the payload is
# tiny — the reason n_chunks cannot grow without bound. Order of
# magnitude of one async op issue; rankings are insensitive to the
# constant, the knee location is honest with it.
CHUNK_LAUNCH_OVERHEAD_S = 1e-6


@dataclass
class ChunkedOverlapTime:
    """Step time of ONE overlapped site decomposed into n_chunks tiles
    (ops/overlap.py): chunk t's transfer rides the wire while chunk
    t+1's matmul runs, so the n-1 interior pairs cost max(compute,
    wire) per chunk — but the FIRST chunk's compute and the LAST
    chunk's transfer have nothing to hide behind (the exposed tails),
    and every chunk pays the launch-overhead floor.  n_chunks=1 is the
    bulk serial sum; n_chunks→inf approaches max(compute, wire) with
    the overhead term eventually winning the argmin back down."""
    compute_s: float
    wire_s: float
    n_chunks: int = 1
    launch_overhead_s: float = CHUNK_LAUNCH_OVERHEAD_S

    @property
    def step_s(self):
        n = max(1, int(self.n_chunks))
        c = self.compute_s / n
        w = self.wire_s / n
        return c + (n - 1) * max(c, w) + w + n * self.launch_overhead_s

    @property
    def serial_s(self):
        """The bulk twin: whole matmul, then the whole collective."""
        return self.compute_s + self.wire_s + self.launch_overhead_s

    @property
    def overlap_frac(self):
        """Fraction of the wire this decomposition hides (the same
        quantity the Schedule Doctor reads off the real DAG)."""
        if self.wire_s <= 0.0:
            return 1.0
        hidden = self.serial_s - self.step_s
        return min(max(hidden / self.wire_s, 0.0), 1.0)


def chunked_overlap_time(compute_s, wire_s, n_chunks=1,
                         launch_overhead_s=CHUNK_LAUNCH_OVERHEAD_S):
    """Price one matmul+collective site at a given chunk count."""
    return ChunkedOverlapTime(compute_s=float(compute_s),
                              wire_s=float(wire_s),
                              n_chunks=max(1, int(n_chunks)),
                              launch_overhead_s=launch_overhead_s)


def best_n_chunks(compute_s, wire_s, max_chunks=64,
                  launch_overhead_s=CHUNK_LAUNCH_OVERHEAD_S):
    """Feasible-fastest chunk count for one overlapped site — the same
    argmin the autotuner runs for microbatch, applied to the n_chunks
    knob: walk 1..max_chunks, keep the step-time minimizer (ties break
    LOW — fewer launches, same time).  Returns (n, ChunkedOverlapTime).
    """
    best = chunked_overlap_time(compute_s, wire_s, 1, launch_overhead_s)
    best_n = 1
    for n in range(2, max(1, int(max_chunks)) + 1):
        t = chunked_overlap_time(compute_s, wire_s, n, launch_overhead_s)
        if t.step_s < best.step_s - 1e-15:
            best, best_n = t, n
    return best_n, best


# ------------------------------------------------------- decode horizon

_MEASURED_SYNC = {}


def measured_host_sync_s(force=False):
    """Measure (once per process) the host cost one decode sync pays:
    dispatch a trivial jitted program and fetch its result. This is the
    overhead `decode_horizon` amortizes over K device-resident ticks —
    the 'measured host overhead per sync' leg of the K pricing."""
    if _MEASURED_SYNC and not force:
        return _MEASURED_SYNC["s"]
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: x + 1)
    x = jnp.zeros((8,), jnp.int32)
    np.asarray(f(x))                         # compile outside the timing
    t0 = time.perf_counter()
    n = 10
    for _ in range(n):
        x = f(x)
        np.asarray(x)
    dt = (time.perf_counter() - t0) / n
    _MEASURED_SYNC["s"] = max(dt, 1e-6)
    return _MEASURED_SYNC["s"]


def decode_tick_roofline_s(step_hbm_bytes, chip=None):
    """Analytic floor of ONE decode tick: decode is HBM-bound (the MXU
    idles), so a tick cannot beat its bytes moved / HBM bandwidth.
    `step_hbm_bytes` is every weight byte + the batch's KV prefix
    (serving.PagedGPTDecoder.step_hbm_bytes supplies it)."""
    chip = chip if isinstance(chip, ChipSpec) else chip_spec(chip)
    return step_hbm_bytes / chip.hbm_bw


def ragged_tick_legs(step_hbm_bytes, new_tokens=0, flops_per_token=0.0,
                     chip=None, mxu_efficiency=0.65):
    """(hbm_s, compute_s) legs of one mixed tick — the pair behind
    `ragged_tick_roofline_s`'s max().  Exposed so the flight-recorder
    pricing can record BOTH the overlapped prediction (max of the
    legs) and the serial one (their sum): the ROOFLINE-DRIFT ledger
    compares the measured tick against the band, telling a mispriced
    leg (measured outside even the serial sum) from a serialized
    schedule (measured at the sum while priced at the max)."""
    chip = chip if isinstance(chip, ChipSpec) else chip_spec(chip)
    hbm = step_hbm_bytes / chip.hbm_bw
    compute = (max(float(new_tokens), 0.0) *
               max(float(flops_per_token), 0.0) /
               (chip.peak_flops * mxu_efficiency))
    return hbm, compute


def ragged_tick_roofline_s(step_hbm_bytes, new_tokens=0,
                           flops_per_token=0.0, chip=None,
                           mxu_efficiency=0.65):
    """Analytic floor of ONE MIXED (ragged) tick, priced on its TOTAL
    new-token count — the packed layout's dispatch unit (pay for
    tokens, not windows): the decode rows keep the tick HBM-bound
    (every weight byte + the batch's KV prefix, the
    `decode_tick_roofline_s` leg), and the tick's `new_tokens` new
    positions (one per decode row + the prefill rows' chunk shares)
    add compute at `flops_per_token` (2x params for a GPT block
    stack). The tick cannot beat the slower leg — max(HBM, token
    compute) — which is exactly why chunking works: while the token
    total's compute fits under the HBM leg, prompt tokens stream into
    the pool at ZERO marginal tick time."""
    hbm, compute = ragged_tick_legs(step_hbm_bytes, new_tokens,
                                    flops_per_token, chip=chip,
                                    mxu_efficiency=mxu_efficiency)
    return max(hbm, compute)


def ragged_chunk_tokens(step_hbm_bytes, flops_per_token, chip=None,
                        mxu_efficiency=0.65, cap=256, floor=8):
    """Default per-tick new-token budget for the ragged scheduler: the
    largest power of two whose compute leg hides under the decode
    tick's HBM leg (those tokens ride 'free' inside the HBM-bound tick
    — `ragged_tick_roofline_s(b, W, f) == decode_tick_roofline_s(b)`),
    clamped to [floor, cap]. The scheduler uses it as the per-slot
    chunk cap, and the PACKED dispatch buckets (`HorizonPlan.
    t_tokens`, pow2 totals) inherit the same hide-under-HBM logic:
    a packed tick whose total stays under this budget adds no
    marginal tick time. `cap` bounds per-tick latency jitter for the
    decode rows sharing the tick; `floor` keeps progress on prompts
    even for models whose tick is compute-tight."""
    chip = chip if isinstance(chip, ChipSpec) else chip_spec(chip)
    hbm = step_hbm_bytes / chip.hbm_bw
    per_tok = (max(float(flops_per_token), 0.0) /
               (chip.peak_flops * mxu_efficiency))
    if per_tok <= 0:
        return int(cap)
    w = int(floor)
    while w * 2 <= int(cap) and (w * 2) * per_tok <= hbm:
        w *= 2
    return w


def decode_horizon(step_hbm_bytes, host_sync_s=None, chip=None,
                   k_cap=32, sync_overhead_frac=0.10,
                   chunk_tokens=0, flops_per_token=0.0):
    """Best multi-step decode horizon K — how many device-resident
    ticks to fuse per host sync (serving.ContinuousBatchingEngine's
    default k_max).

    With K ticks fused, per-token time ≈ t_tick + h/K where t_tick is
    the tick roofline and h the host overhead per sync. Pick the
    smallest K that keeps the sync share at or below
    `sync_overhead_frac` of the tick roofline (h/(K·t_tick) ≤ frac),
    capped at `k_cap` (scheduling granularity: retirement/admission
    latency grows with K, and the engine buckets K to powers of two
    for a bounded compile count). Small models on fast chips price to
    the cap — the tick is so short that ANY host interposition
    dominates; models whose tick dwarfs the sync cost price K=1, where
    the fused loop gains nothing.

    The RAGGED extension: with `chunk_tokens`/`flops_per_token` the
    tick is priced as a MIXED tick (`ragged_tick_roofline_s` — decode
    HBM leg plus the prefill chunk's compute leg), so a scheduler that
    admits prompt chunks into the horizon amortizes the same sync cost
    over its slightly longer ticks (a compute-heavy chunk budget prices
    a smaller K)."""
    import math
    if host_sync_s is None:
        host_sync_s = measured_host_sync_s()
    if chunk_tokens:
        t = ragged_tick_roofline_s(step_hbm_bytes, chunk_tokens,
                                   flops_per_token, chip=chip)
    else:
        t = decode_tick_roofline_s(step_hbm_bytes, chip=chip)
    if t <= 0:
        return int(k_cap)
    k = math.ceil(host_sync_s / (sync_overhead_frac * t))
    return int(min(max(k, 1), int(k_cap)))


# --------------------------------------------------------- SLO classes
#
# Per-class sync-overhead budgets for multi-tenant serving
# (serving.tenancy): the LATENCY tier deliberately accepts a much
# larger host-sync share — syncing more often is exactly what shortens
# the queue-wait/TTFT tail, because admission (and preemption) can only
# happen at horizon boundaries. The THROUGHPUT tier keeps the default
# 10% amortization. Both classes price through the SAME mixed-tick
# roofline (`ragged_tick_roofline_s` via `decode_horizon`), so the
# per-class targets are roofline-DERIVED, not hand-tuned constants.

SLO_SYNC_FRAC = {"latency": 0.5, "throughput": 0.10}


def slo_horizon(step_hbm_bytes, slo, host_sync_s=None, chip=None,
                k_cap=32, chunk_tokens=0, flops_per_token=0.0):
    """Per-SLO-class decode horizon K: `decode_horizon` priced with the
    class's sync-overhead budget (`SLO_SYNC_FRAC`). The latency tier's
    smaller K bounds how long a newly arrived latency prompt can sit
    in the queue before the next admission boundary; the throughput
    tier amortizes the sync like the single-tenant engine."""
    frac = SLO_SYNC_FRAC.get(slo)
    if frac is None:
        raise ValueError(f"unknown SLO class {slo!r}; known: "
                         f"{sorted(SLO_SYNC_FRAC)}")
    return decode_horizon(step_hbm_bytes, host_sync_s=host_sync_s,
                          chip=chip, k_cap=k_cap,
                          sync_overhead_frac=frac,
                          chunk_tokens=chunk_tokens,
                          flops_per_token=flops_per_token)


def slo_p99_target_s(step_hbm_bytes, slo, host_sync_s=None, chip=None,
                     k_cap=32, chunk_tokens=0, flops_per_token=0.0):
    """Roofline-derived per-class p99 target for one horizon boundary:
    the class's K ticks at the mixed-tick roofline plus one host sync
    — the longest a request of that class should wait between two
    scheduling opportunities on a correctly composed engine. The
    multi-tenant bench reports measured per-class p99 NEXT to this
    number (serving.tenancy.TenantEngine.tenancy_summary), so a
    violated target points at composition, not at a hand-tuned
    constant."""
    if host_sync_s is None:
        host_sync_s = measured_host_sync_s()
    k = slo_horizon(step_hbm_bytes, slo, host_sync_s=host_sync_s,
                    chip=chip, k_cap=k_cap, chunk_tokens=chunk_tokens,
                    flops_per_token=flops_per_token)
    tick = ragged_tick_roofline_s(step_hbm_bytes, chunk_tokens,
                                  flops_per_token, chip=chip)
    return k * tick + host_sync_s


def prefill_ttft_s(prompt_tokens, flops_per_token, cached_frac=0.0,
                   chip=None, host_sync_s=None, mxu_efficiency=0.65):
    """Analytic time-to-first-token of one prompt: the compute roofline
    of the UNCACHED prompt span plus one host sync.

    `cached_frac` is the prefix-cache hit fraction of the prompt
    (serving.ServeStats.prefix_hit_rate view): cached pages are mounted
    into the page table HOST-side — zero device FLOPs — so prefill
    compute scales with the (1 - cached_frac) remainder. A full hit
    still re-consumes one position for logits, which the one-sync floor
    absorbs. This is the pricing half of the prefix cache: TTFT and
    prefill FLOPs both collapse linearly with hit rate (the bench
    scenario's committed JSON lines measure the same curve)."""
    chip = chip if isinstance(chip, ChipSpec) else chip_spec(chip)
    if host_sync_s is None:
        host_sync_s = measured_host_sync_s()
    frac = min(max(float(cached_frac), 0.0), 1.0)
    uncached = max(float(prompt_tokens), 0.0) * (1.0 - frac)
    compute = (uncached * max(float(flops_per_token), 0.0)
               / (chip.peak_flops * mxu_efficiency))
    return compute + host_sync_s


def kv_restore_s(restore_bytes, chip=None, shared=False):
    """Analytic floor of re-mounting spilled KV pages from pinned host
    RAM: bytes over the host<->chip wire (`ChipSpec.host_bw` — the PCIe
    DMA leg). The tiered-KV admission compares this against the
    recompute price of the same span (`prefill_ttft_s` with no sync
    floor: the ragged path has no extra sync either way) and restores
    only when the wire beats the prefill — big-model pages win (KV
    bytes/token are fixed but recompute FLOPs grow with params), tiny
    models recompute (serving.kv_tier owns the decision; ServeStats
    tier_restores/tier_recomputes make it observable).

    `shared=True` prices the CROSS-PROCESS tier
    (serving.fleet.SharedHostKVTier): the payload sits in an shm-/
    file-backed store another replica wrote, so the restore pays a
    host-RAM read leg (`ChipSpec.host_read_bw`) before the DMA — the
    two legs are serial (read, then enqueue H2D), so they add."""
    chip = chip if isinstance(chip, ChipSpec) else chip_spec(chip)
    b = max(float(restore_bytes), 0.0)
    t = b / chip.host_bw
    if shared:
        t += b / chip.host_read_bw
    return t


def train_horizon(step_s, host_sync_s=None, n_cap=32,
                  sync_overhead_frac=0.10):
    """Best multi-step TRAINING horizon N — how many fused train steps
    `Trainer.step_multi` should scan per host dispatch (the `decode_horizon`
    pricing applied to training: `step_s` is the step's analytic floor,
    normally `roofline_step_time(...).step_s`, though a measured step
    time prices identically).

    With N steps fused, per-step overhead ≈ h/N where h is the host
    cost of one dispatch+fetch sync (`measured_host_sync_s`). Pick the
    smallest N that keeps the sync share at or below
    `sync_overhead_frac` of the step floor (h/(N·step_s) ≤ frac),
    capped at `n_cap` (horizon granularity: logging/checkpoint/callback
    latency grows with N, and each distinct N compiles one scan
    program). Small models price to the cap — eager host overhead
    dominates their step; a 1.3B step dwarfs the sync cost and prices
    N=1, where fusing gains nothing."""
    import math
    if host_sync_s is None:
        host_sync_s = measured_host_sync_s()
    if step_s is None or step_s <= 0:
        return int(n_cap)
    n = math.ceil(host_sync_s / (sync_overhead_frac * step_s))
    return int(min(max(n, 1), int(n_cap)))


# jaxpr primitive names -> the StableHLO collective they lower to, so
# callers can query with either vocabulary (the memory/sharding passes
# walk jaxprs, the HLO analyzers walk StableHLO text)
_COLLECTIVE_ALIASES = {
    "psum": "all_reduce",
    "pmax": "all_reduce",
    "pmin": "all_reduce",
    "ppermute": "collective_permute",
    "pshuffle": "collective_permute",
    "psum_scatter": "reduce_scatter",
    "pbroadcast": "collective_broadcast",
    "all_gather_invariant": "all_gather",
}


def collective_wire_bytes(op, payload_bytes, group_size):
    """Analytic bytes-on-the-wire per participating device for one
    collective, assuming the bandwidth-optimal ring algorithms XLA uses
    on ICI (the offline half of the T3-style compute/collective split;
    paddle_tpu.analysis cross-checks lowered programs against this).

    all_reduce      ring reduce-scatter + all-gather: 2(n-1)/n * payload
    all_gather      (n-1)/n * full gathered payload
    reduce_scatter  (n-1)/n * full pre-scatter payload
    all_to_all      (n-1)/n * payload (each device keeps 1/n)
    collective_permute / broadcast: one payload hop

    `payload_bytes` is the FULL (gathered/unreduced) array size for
    every op. group_size<=1 is a degenerate group (XLA folds the op to
    a copy): 0 wire bytes. jaxpr primitive names (psum, ppermute,
    psum_scatter, ...) are accepted as aliases.
    """
    try:
        n = int(group_size or 1)
    except (TypeError, ValueError):
        n = 1
    if n <= 1 or not payload_bytes or payload_bytes <= 0:
        return 0
    op = _COLLECTIVE_ALIASES.get(op, op)
    frac = (n - 1) / n
    factor = {
        "all_reduce": 2 * frac,
        "all_gather": frac,
        "reduce_scatter": frac,
        "all_to_all": frac,
        "collective_permute": 1.0,
        "collective_broadcast": 1.0,
    }.get(op, 1.0)
    return int(payload_bytes * factor)


def collective_wire_split(op, payload_bytes, group_size, host_count=1):
    """ICI/DCN split of `collective_wire_bytes`: a ring over n devices
    spanning h hosts crosses a host boundary on h of its n hops, so
    h/n of the wire volume rides DCN and the rest stays on ICI (the
    ROADMAP "multi-host memory model" item — every hop used to be
    priced at ICI cost). h<=1 (chip-local axis) puts everything on ICI.
    Returns {"ici": bytes, "dcn": bytes}."""
    total = collective_wire_bytes(op, payload_bytes, group_size)
    try:
        n = max(int(group_size or 1), 1)
        h = max(int(host_count or 1), 1)
    except (TypeError, ValueError):
        n, h = 1, 1
    if total <= 0 or h <= 1 or n <= 1:
        return {"ici": total, "dcn": 0}
    dcn = int(total * min(h, n) / n)
    return {"ici": total - dcn, "dcn": dcn}


def axis_host_count(mesh, axis):
    """How many hosts one line of `axis` spans in this mesh — the h of
    `collective_wire_split`. Walks mesh.devices along the axis with all
    other axes held at 0 and counts distinct process indexes (duck-typed:
    anything with .axis_names and a .devices ndarray of objects carrying
    .process_index works, so multi-host topologies are testable offline).
    Unknown axes or failures fall back to 1 (chip-local)."""
    try:
        names = list(mesh.axis_names)
        if axis not in names:
            return 1
        devs = mesh.devices
        idx = [0] * devs.ndim
        ax = names.index(axis)
        procs = set()
        for i in range(devs.shape[ax]):
            idx[ax] = i
            procs.add(getattr(devs[tuple(idx)], "process_index", 0))
        return max(len(procs), 1)
    except Exception:
        return 1


class CostModel:
    def build_program(self):
        from . import static
        from . import nn, optimizer
        import paddle_tpu as paddle

        paddle.enable_static()
        x = static.data("cost_model_X", [16, 1], "float32")
        lin = nn.Linear(1, 10)
        hidden = lin(x)
        loss = paddle.mean(hidden)
        optimizer.SGD(learning_rate=0.01, parameters=lin.parameters()).minimize(loss)
        self._feed = {"cost_model_X": np.ones((16, 1), np.float32)}
        self._fetch = [loss]
        return static.default_startup_program(), static.default_main_program()

    def profile_measure(self, startup_program=None, main_program=None,
                        device="tpu", fetch_cost_list=("time",)):
        from . import static
        exe = static.Executor()
        exe.run(main_program, feed=self._feed, fetch_list=self._fetch)  # compile
        t0 = time.perf_counter()
        for _ in range(10):
            exe.run(main_program, feed=self._feed, fetch_list=self._fetch)
        dt = (time.perf_counter() - t0) / 10
        return {"time": dt * 1e3}  # ms, like the reference's time cost

    _OP_BENCH = {
        # op -> (builder returning (fn, args)); timed lazily on first query
        "matmul": lambda jnp, rng: (lambda a, b: a @ b,
                                    (rng((256, 256)), rng((256, 256)))),
        "relu": lambda jnp, rng: (lambda a: jnp.maximum(a, 0), (rng((512, 512)),)),
        "softmax": lambda jnp, rng: (lambda a: jnp.exp(a - a.max(-1, keepdims=True))
                                     / jnp.exp(a - a.max(-1, keepdims=True)).sum(-1, keepdims=True),
                                     (rng((512, 512)),)),
        "layer_norm": lambda jnp, rng: (
            lambda a: (a - a.mean(-1, keepdims=True))
            / jnp.sqrt(a.var(-1, keepdims=True) + 1e-5), (rng((512, 512)),)),
        "elementwise_add": lambda jnp, rng: (lambda a, b: a + b,
                                             (rng((512, 512)), rng((512, 512)))),
    }

    def static_cost_data(self):
        """Measured per-op microbenchmark table (reference reads a shipped
        benchmark JSON; here the ops are timed on the live backend once)."""
        if not hasattr(self, "_static_costs"):
            self._static_costs = {
                name: self._time_op(name) for name in self._OP_BENCH}
        return self._static_costs

    def _time_op(self, op_name, forward=True, dtype="float32"):
        import jax
        import jax.numpy as jnp

        rng = np.random.RandomState(0)
        fn, args = self._OP_BENCH[op_name](
            jnp, lambda shape: jnp.asarray(rng.randn(*shape), dtype))
        if not forward:
            fwd = fn
            fn = jax.grad(lambda *a: jnp.sum(fwd(*a)).astype(jnp.float32))
        jfn = jax.jit(fn)
        jax.block_until_ready(jfn(*args))    # compile
        t0 = time.perf_counter()
        for _ in range(20):
            out = jfn(*args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / 20 * 1e3   # ms

    def get_static_op_time(self, op_name, forward=True, dtype="float32"):
        if op_name not in self._OP_BENCH:
            return {"op_time": "0"}
        cache = getattr(self, "_op_cost_cache", None)
        if cache is None:
            cache = self._op_cost_cache = {}
        key = (op_name, forward, dtype)
        if key not in cache:
            cache[key] = self._time_op(op_name, forward=forward, dtype=dtype)
        return {"op_time": str(cache[key])}
