"""Static (microbatch, remat) autotuner — pick the training config
before anything compiles.

The bench campaign used to find GPT-1.3B's operating point (bs=6,
remat=dots, 0.64 MFU) by compiling and timing every (batch, policy)
combination — minutes of chip time per candidate.
This module replaces the brute force with static search:

  1. trace the trainer's REAL step once per candidate microbatch with
     remat disabled (CPU tracing, no compile, no device);
  2. replay every candidate remat policy over that trace
     (remat_advisor.py): per-device peak + recompute FLOPs per policy —
     per-device division uses the fixed-point propagated shard counts
     (analysis/propagation.py) where the lowering pinned per-dim specs,
     the v1 max-operand heuristic elsewhere;
  3. price each (microbatch, policy) with the roofline step-time model
     (cost_model.roofline_step_time): max(compute, HBM, wire) seconds;
  4. prune everything over the HBM budget, rank the rest by predicted
     throughput.

Front doors: `debug.autotune(trainer, batch, hbm_budget=...)`,
`Trainer.suggest_config(batch)`, the CLI
(`python -m paddle_tpu.analysis --autotune`), and
`rank_gpt_candidates`.
"""
import gc
from contextlib import contextmanager
from dataclasses import dataclass, field

__all__ = ["CandidateEstimate", "AutotuneReport", "autotune",
           "autotune_layer", "rank_gpt_candidates", "DEFAULT_POLICIES"]

DEFAULT_POLICIES = ("none", "full", "dots", "dots_with_no_batch_dims")


@dataclass
class CandidateEstimate:
    """One (microbatch, remat policy[, grad accum]) grid point."""
    batch: int
    policy: str
    accum: int
    peak_bytes: int
    feasible: bool
    step_s: float
    bound: str                   # compute | hbm | wire
    throughput: float            # items/s (tokens/s when tokens known)
    unit: str
    flops: int
    recompute_pct: float
    advice: str
    n_chunks: int = 1            # collective-matmul decomposition pick

    def to_dict(self):
        return {"batch": self.batch, "policy": self.policy,
                "accum": self.accum, "peak_bytes": self.peak_bytes,
                "feasible": self.feasible,
                "predicted_step_us": round(self.step_s * 1e6, 3),
                "bound": self.bound,
                "throughput": round(self.throughput, 1),
                "unit": self.unit,
                "recompute_pct": round(self.recompute_pct, 2),
                "n_chunks": self.n_chunks}


@dataclass
class AutotuneReport:
    """Ranked candidates (feasible first, fastest first), the advice
    lines per policy, and the budget that pruned the rest."""
    name: str
    candidates: list
    hbm_budget: int
    chip: str
    advice: list = field(default_factory=list)

    @property
    def best(self):
        for c in self.candidates:
            if c.feasible:
                return c
        return None

    @property
    def top(self):
        return [c for c in self.candidates if c.feasible]

    def __str__(self):
        gib = 1024.0 ** 3
        lines = [f"== autotune: {self.name} (chip {self.chip}, HBM "
                 f"budget {self.hbm_budget / gib:.1f} GiB) =="]
        hdr = (f"{'bs':>4} {'policy':<24} {'accum':>5} {'peak GiB':>9} "
               f"{'step ms':>8} {'bound':>7} {'pred':>10} {'fit':>4}")
        lines.append(hdr)
        for c in self.candidates:
            lines.append(
                f"{c.batch:>4} {c.policy:<24} {c.accum:>5} "
                f"{c.peak_bytes / gib:>9.2f} {c.step_s * 1e3:>8.2f} "
                f"{c.bound:>7} {c.throughput:>10.0f} "
                f"{'ok' if c.feasible else 'OOM':>4}")
        for line in self.advice:
            lines.append("  " + line)
        return "\n".join(lines)


@contextmanager
def _remat_disabled(model):
    """Trace-time switch: flips cfg.remat off so the traced step is the
    no-remat baseline the replay needs. Models without a remat config
    (ResNet & co) pass through untouched."""
    cfg = getattr(model, "cfg", None)
    if cfg is None or not hasattr(cfg, "remat"):
        yield
        return
    old = cfg.remat
    cfg.remat = False
    try:
        yield
    finally:
        cfg.remat = old


def _noremat_program(trainer, batch):
    """Trace the trainer's specialized step with remat disabled, WITHOUT
    poisoning the trainer's compiled-step cache: the placed-step map is
    swapped out for the trace (fresh closures, so jax's trace cache
    can't serve a stale no-remat jaxpr to a later remat'd trace)."""
    saved_steps = trainer._placed_steps
    trainer._placed_steps = {}
    try:
        with _remat_disabled(trainer.model):
            return trainer.analysis_program(batch)
    finally:
        trainer._placed_steps = saved_steps


def _resize_batch(batch, bs):
    """Tile/slice every leaf's leading dim to `bs` (host-side numpy)."""
    import numpy as np
    import jax

    def fix(v):
        a = np.asarray(v)
        if a.ndim == 0:
            return a
        if a.shape[0] == bs:
            return a
        reps = -(-bs // a.shape[0])          # ceil
        return np.concatenate([a] * reps, axis=0)[:bs]
    return jax.tree_util.tree_map(fix, batch)


def _segments_of(model, default=1):
    cfg = getattr(model, "cfg", None)
    n = getattr(cfg, "num_layers", None)
    if n:
        return int(n)
    blocks = getattr(model, "blocks", None)
    try:
        return max(len(blocks), 1)
    except TypeError:
        return default


def _leading_dim(batch):
    """Batch size = leading dim of the first NON-SCALAR leaf (scalar
    leaves, e.g. a loss weight, carry no batch dim — _resize_batch
    passes them through untouched for the same reason)."""
    import numpy as np
    import jax
    for leaf in jax.tree_util.tree_leaves(batch):
        a = np.asarray(leaf)
        if a.ndim:
            return int(a.shape[0])
    return 1


def _batch_items(batch, tokens_per_item=None):
    """(count, unit) for throughput: tokens when a [B, L] integer leaf
    exists (LM batches), else leading-dim items."""
    import numpy as np
    import jax
    leaves = jax.tree_util.tree_leaves(batch)
    b = _leading_dim(batch)
    if tokens_per_item:
        return b * tokens_per_item, "tokens/s"
    for leaf in leaves:
        a = np.asarray(leaf)
        if a.ndim == 2 and a.dtype.kind in "iu" and a.shape[1] > 1:
            return b * int(a.shape[1]), "tokens/s"
    return b, "items/s"


def _wire_bytes(program, mesh=None):
    """(ici, dcn) analytic wire bytes of the program's collectives,
    DCN-priced when a mesh axis spans hosts."""
    from ..cost_model import (axis_host_count, collective_wire_split)
    from .analyzers import COLLECTIVE_OPS
    from .lowering import tensor_type_bytes
    hosts = 1
    if mesh is not None:
        try:
            hosts = max(axis_host_count(mesh, a) for a in mesh.axis_names)
        except (ValueError, TypeError):
            hosts = 1
    ici = dcn = 0
    for op in program.ops_named(*COLLECTIVE_OPS):
        group, _ = op.replica_group_size()
        payload = max(op.operand_bytes(),
                      sum(tensor_type_bytes(t) for t in op.result_types))
        split = collective_wire_split(op.name, payload, group or 1,
                                      host_count=hosts)
        ici += split["ici"]
        dcn += split["dcn"]
    return ici, dcn


def _state_bytes(arg_infos):
    infos = arg_infos or []
    state = sum(i.device_bytes for i in infos
                if i.role in ("param", "opt_state", "gt_state", "const"))
    batch = sum(i.device_bytes for i in infos if i.role == "batch")
    params = sum(i.device_bytes for i in infos if i.role == "param")
    bshard = max([i.shard_count for i in infos if i.role == "batch"]
                 or [1])
    return state, batch, params, bshard


def _price(whatif, state_b, batch_b, params_b, items, unit, chip,
           ici_b=0, dcn_b=0, accum=1, batch_shard=1, overlap_frac=1.0):
    """Roofline-price one replayed policy, PER DEVICE: the replayed
    peak and byte counts are already per-device (shard-count division),
    so the compute leg divides the batch-proportional FLOPs by the
    batch's shard count too (data parallelism splits the fwd/bwd work;
    the optimizer epilogue runs on every device's own shard of state
    and is priced once). Throughput stays GLOBAL items per step. With
    grad accumulation the fwd/bwd repeats `accum` times before one
    epilogue, and a float32 params-shaped gradient accumulator joins
    the peak.

    `overlap_frac` is the schedule pass's wire-hiding fraction
    (`analysis.schedule.estimate_schedule(...).overlap_frac`): the
    step is priced through `roofline_step_time_overlap`, so a program
    whose lowered schedule SERIALIZES its collectives ranks by the
    time it will actually run at, not the full-overlap floor. With no
    wire (every single-device candidate, including the gpt_1p3b probe
    grid) the price is bit-identical to the old max() — rankings
    can't move."""
    from ..cost_model import roofline_step_time_overlap
    opt_flops = 12 * max(params_b // 2, 1)   # ~12 flops/param epilogue
    micro_flops = max(whatif.step_flops + whatif.recompute_flops
                      - opt_flops, 0) // max(batch_shard, 1)
    flops = accum * micro_flops + opt_flops
    act_b = 2 * (whatif.saved_bytes + whatif.boundary_bytes
                 + whatif.dropped_bytes)
    hbm = 2 * state_b + accum * (batch_b + act_b)
    peak = whatif.peak_bytes
    if accum > 1:
        peak += 2 * params_b      # f32 grad accumulator (params are bf16)
    rt = roofline_step_time_overlap(flops, hbm, ici_b * accum,
                                    dcn_b * accum, chip=chip,
                                    overlap_frac=overlap_frac)
    return peak, flops, rt, accum * items / max(rt.step_s, 1e-12)


def _rank_key(c):
    """Feasible first, fastest first; ties (HBM-bound small models make
    policies indistinguishable on time) break toward the least
    recompute, then the smallest peak."""
    return (not c.feasible, -c.throughput, c.recompute_pct, c.peak_bytes)


def autotune(trainer, batch, hbm_budget=None, batch_sizes=None,
             policies=DEFAULT_POLICIES, chip=None, segments=None,
             tokens_per_item=None, print_report=False):
    """Static config search over (microbatch, remat policy) for a
    Trainer: one no-remat trace per batch size, a what-if liveness
    replay per policy, roofline pricing, HBM-budget pruning, and a
    ranked table. No compile, no device execution.

    Returns an AutotuneReport; `report.best` is the config to measure
    first, `report.advice` the per-policy "moves the peak from X to Y
    at +Z% recompute FLOPs" lines for the example batch size."""
    from ..cost_model import chip_spec
    from .remat_advisor import advise_remat

    chip = chip_spec(chip) if not hasattr(chip, "peak_flops") else chip
    budget = int(hbm_budget or chip.hbm_bytes)
    segments = segments or _segments_of(trainer.model)
    b0 = _leading_dim(batch)
    if batch_sizes is None:
        batch_sizes = sorted({max(1, b0 // 2), b0, b0 * 2})

    # advice lines quote the example batch's size when it is in the
    # grid, else the first traced size — .advice must never be empty
    # just because batch_sizes excluded b0
    advice_bs = b0 if b0 in batch_sizes else batch_sizes[0]
    candidates, advice = [], []
    for bs in batch_sizes:
        resized = _resize_batch(batch, bs)
        program = _noremat_program(trainer, resized)
        items, unit = _batch_items(resized, tokens_per_item)
        state_b, batch_b, params_b, bshard = _state_bytes(
            program.arg_infos)
        ici_b, dcn_b = _wire_bytes(program, getattr(trainer, "mesh", None))
        # overlap-aware wire leg: a program WITH collectives prices at
        # the schedule pass's hiding fraction (a serialized psum can't
        # hide behind the MXU); wire-free candidates skip the DAG walk
        # — their price is bit-identical either way
        overlap_frac = 1.0
        if ici_b or dcn_b:
            from .schedule import estimate_schedule
            mesh = getattr(trainer, "mesh", None)
            overlap_frac = estimate_schedule(
                program, chip=chip,
                mesh_axes=(dict(mesh.shape) if mesh is not None
                           else None)).overlap_frac
        for w in advise_remat(program, policies=policies,
                              segments=segments):
            peak, flops, rt, thr = _price(
                w, state_b, batch_b, params_b, items, unit, chip,
                ici_b, dcn_b, batch_shard=bshard,
                overlap_frac=overlap_frac)
            # n_chunks is picked the way microbatch is — feasible-
            # fastest through the chunked-overlap leg: the chip time
            # (max of MXU and HBM legs) is what chunk t+1's matmul can
            # hide chunk t's transfer behind. Wire-free candidates
            # stay at the bulk n=1 (nothing to decompose).
            n_best = 1
            if rt.wire_s > 0.0:
                from ..cost_model import best_n_chunks
                n_best, ct = best_n_chunks(max(rt.compute_s, rt.hbm_s),
                                           rt.wire_s)
                if bs == advice_bs:
                    advice.append(
                        f"[{w.policy}] chunked overlap: n_chunks="
                        f"{n_best} hides {ct.overlap_frac:.0%} of the "
                        f"{rt.wire_s * 1e3:.2f} ms wire "
                        f"(bulk step {ct.serial_s * 1e3:.2f} ms -> "
                        f"{ct.step_s * 1e3:.2f} ms)")
            candidates.append(CandidateEstimate(
                batch=bs, policy=w.policy, accum=1, peak_bytes=peak,
                feasible=peak <= budget, step_s=rt.step_s,
                bound=rt.bound, throughput=thr, unit=unit, flops=flops,
                recompute_pct=w.recompute_pct, advice=w.advice,
                n_chunks=n_best))
            if bs == advice_bs:
                advice.append(w.advice)
        del program
        gc.collect()

    candidates.sort(key=_rank_key)
    report = AutotuneReport(
        name=type(trainer.model).__name__, candidates=candidates,
        hbm_budget=budget, chip=chip.name, advice=advice)
    if print_report:
        print(report)
    return report


def autotune_layer(model, *example_arrays, policies=DEFAULT_POLICIES,
                   segments=None, chip="v5e", name=None,
                   hbm_budget=None):
    """Remat advice for a bare Layer (no Trainer): traces
    value_and_grad of a synthetic mean-square loss over the forward —
    the policy-ranking backbone the BASELINE tuning manifests pin.
    Deterministic: fixed chip, no live-device dependence."""
    import jax
    import jax.numpy as jnp
    from ..framework.core import Tensor
    from ..nn.layer_base import (buffer_pytree, functional_call,
                                 state_pytree)
    from ..cost_model import chip_spec
    from .lowering import LoweredProgram, tree_arg_infos
    from .remat_advisor import advise_remat

    chip = chip_spec(chip) if not hasattr(chip, "peak_flops") else chip
    budget = int(hbm_budget or chip.hbm_bytes)
    segments = segments or _segments_of(model)
    params = state_pytree(model)
    params.update(buffer_pytree(model))

    def objective(p, *args):
        with _remat_disabled(model):
            with functional_call(model, p):
                out = model(*[Tensor(a) for a in args])
        leaves = jax.tree_util.tree_leaves(
            jax.tree_util.tree_map(
                lambda t: t._value if isinstance(t, Tensor) else t, out,
                is_leaf=lambda t: isinstance(t, Tensor)))
        loss = sum(jnp.mean(jnp.square(l.astype(jnp.float32)))
                   for l in leaves if hasattr(l, "dtype"))
        return loss

    with _remat_disabled(model):
        traced = jax.jit(jax.value_and_grad(objective)).trace(
            params, *example_arrays)
    infos = tree_arg_infos(params, "param")
    for i, a in enumerate(example_arrays):
        infos += tree_arg_infos(a, "input", prefix=f"input{i}")
    program = LoweredProgram(traced.lower().as_text(),
                             jaxpr=traced.jaxpr,
                             name=name or type(model).__name__,
                             arg_infos=infos)
    whatifs = advise_remat(program, policies=policies, segments=segments)
    items, unit = _batch_items(list(example_arrays))
    import numpy as np
    leaves = jax.tree_util.tree_leaves(list(example_arrays))
    b0 = int(np.asarray(leaves[0]).shape[0]) if leaves else 1
    state_b, _, params_b, _bshard = _state_bytes(program.arg_infos)
    batch_b = sum(i.device_bytes for i in program.arg_infos
                  if i.role == "input")
    candidates = []
    for w in whatifs:
        peak, flops, rt, thr = _price(w, state_b, batch_b, params_b,
                                      items, unit, chip)
        candidates.append(CandidateEstimate(
            batch=b0,
            policy=w.policy, accum=1, peak_bytes=peak,
            feasible=peak <= budget, step_s=rt.step_s,
            bound=rt.bound, throughput=thr, unit=unit, flops=flops,
            recompute_pct=w.recompute_pct, advice=w.advice))
    candidates.sort(key=_rank_key)
    return AutotuneReport(
        name=name or type(model).__name__, candidates=candidates,
        hbm_budget=budget, chip=chip.name,
        advice=[w.advice for w in whatifs])


# ------------------------------------------------- GPT grid ranking

def rank_gpt_candidates(grid, seq=1024, top=2, probe_layers=(2, 3),
                        chip=None, hbm_budget=None, log=None):
    """Rank a bench-style GPT grid [(cfg_name, bs, remat, accum), ...]
    statically and return the top-`top` entries (advisor order).

    Tracing the full 1.3B model would materialize >2 GB of params just
    to build a jaxpr, so the advisor probes a depth-truncated twin at
    `probe_layers` (two points) and extrapolates peak/FLOPs linearly in
    layer count — every per-block quantity (params, optimizer slots,
    saved/dropped residuals, block FLOPs) is exactly linear in L, and
    the embedding/head/loss ends cancel in the two-point difference.
    Runs entirely on the host: build + trace + replay, no compile."""
    import numpy as np

    from ..cost_model import chip_spec
    from .remat_advisor import BENCH_POLICY_NAMES, replay_remat

    chip = chip_spec(chip) if not hasattr(chip, "peak_flops") else chip
    budget = int(hbm_budget or chip.hbm_bytes)
    names = {g[0] for g in grid}
    if len(names) != 1:
        raise ValueError(f"rank_gpt_candidates wants one config family, "
                         f"got {sorted(names)}")
    cfg_name = names.pop()
    policies = sorted({BENCH_POLICY_NAMES.get(g[2], g[2]) for g in grid})
    micro_bss = sorted({g[1] // max(g[3], 1) for g in grid})

    import paddle_tpu as paddle
    from paddle_tpu.distributed import build_mesh
    from paddle_tpu.distributed.mesh import get_mesh, set_mesh
    from paddle_tpu.distributed.trainer import Trainer
    from paddle_tpu.framework.random import get_rng_state, set_rng_state
    from paddle_tpu.models import GPT, GPTPretrainingCriterion
    from paddle_tpu.models import gpt as gpt_mod

    # probe[(L, mb, policy)] -> (peak, step_flops+recompute, whatif)
    probe = {}
    full_L = None
    state_by_L, params_by_L = {}, {}
    import jax
    # the probes pin the global mesh and reseed the global RNG; both are
    # process-wide state a caller may be mid-use of — restore on exit
    saved_mesh = get_mesh(create_default=False)
    saved_rng = get_rng_state()
    try:
        for L in probe_layers:
            cfg = getattr(gpt_mod, cfg_name)(max_seq_len=seq, remat=False)
            full_L = cfg.num_layers
            cfg.num_layers = L
            paddle.seed(0)
            # probes price ONE chip (the bench/campaign unit), so the mesh
            # is pinned to a single device — on dev hosts with a virtual
            # multi-device CPU platform, the default mesh would silently
            # shard some probe batches and skew the extrapolation
            build_mesh(dp=1, devices=jax.devices()[:1])
            model = GPT(cfg)
            model.bfloat16()
            crit = GPTPretrainingCriterion()
            opt = paddle.optimizer.AdamW(
                learning_rate=2e-4, weight_decay=0.1,
                grad_clip=paddle.nn.ClipGradByGlobalNorm(1.0),
                accumulator_dtype="bfloat16")

            def loss_fn(m, b):
                logits = m(paddle.to_tensor(b["input_ids"]))
                return crit(logits, paddle.to_tensor(b["labels"]))

            trainer = Trainer(model, opt, loss_fn)
            rng = np.random.RandomState(0)
            for mb in micro_bss:
                ids = rng.randint(0, cfg.vocab_size, (mb, seq + 1))
                batch = {"input_ids": ids[:, :-1].astype("int32"),
                         "labels": ids[:, 1:].astype("int32")}
                program = _noremat_program(trainer, batch)
                state_b, batch_b, params_b, _bs = _state_bytes(
                    program.arg_infos)
                state_by_L[L], params_by_L[L] = state_b, params_b
                for pol in policies:
                    w = replay_remat(program, pol,
                                     arg_infos=program.arg_infos,
                                     segments=L)
                    probe[(L, mb, pol)] = (w, batch_b)
                del program
            del trainer, model, opt
            gc.collect()
    finally:
        set_mesh(saved_mesh)
        set_rng_state(saved_rng)

    L0, L1 = probe_layers
    span = L1 - L0

    def lerp(a, b):
        return int(a + (full_L - L0) * (b - a) / span)

    scored = []
    for entry in grid:
        _, bs, rp, accum = entry
        pol = BENCH_POLICY_NAMES.get(rp, rp)
        mb = bs // max(accum, 1)
        w0, batch_b = probe[(L0, mb, pol)]
        w1, _ = probe[(L1, mb, pol)]
        # extrapolate each replayed FIELD linearly in depth, then price
        # the synthetic full-depth what-if through the SAME `_price` the
        # trainer autotuner uses — the 12-flops/param epilogue, the f32
        # grad-merge accumulator and the activation-traffic legs exist
        # in exactly one place (the wire legs stay 0 by design: the
        # probes are pinned single-device)
        from .remat_advisor import RematWhatIf
        w = RematWhatIf(
            policy=pol,
            peak_bytes=lerp(w0.peak_bytes, w1.peak_bytes),
            base_peak_bytes=lerp(w0.base_peak_bytes, w1.base_peak_bytes),
            saved_bytes=lerp(w0.saved_bytes, w1.saved_bytes),
            boundary_bytes=lerp(w0.boundary_bytes, w1.boundary_bytes),
            dropped_bytes=lerp(w0.dropped_bytes, w1.dropped_bytes),
            bump_bytes=lerp(w0.bump_bytes, w1.bump_bytes),
            recompute_flops=lerp(w0.recompute_flops, w1.recompute_flops),
            step_flops=lerp(w0.step_flops, w1.step_flops),
            segments=full_L)
        state_b = lerp(state_by_L[L0], state_by_L[L1])
        params_b = lerp(params_by_L[L0], params_by_L[L1])
        peak, _flops, rt, tok_s = _price(
            w, state_b, batch_b, params_b, mb * seq, "tokens/s", chip,
            accum=accum)
        scored.append((entry, peak, peak <= budget, tok_s))
        if log:
            log(f"advisor {entry}: peak {peak / 2**30:.2f} GiB "
                f"{'ok' if peak <= budget else 'OOM'}, "
                f"predicted {tok_s:.0f} tok/s ({rt.bound}-bound)")
    scored.sort(key=lambda s: (not s[2], -s[3]))
    return [s[0] for s in scored[:top]]
