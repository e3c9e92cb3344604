"""What-if remat replay — concrete remat advice from the liveness walk.

The Memory Doctor (memory.py) names the top live tensors at the peak;
this module answers the follow-up question: *which remat policy moves
the peak where, at what recompute cost* — statically, from ONE no-remat
trace, before anything compiles.

Mechanics (a replay of memory.py's jaxpr-order liveness pass):

  1. Find the fwd/bwd boundary of a grad/train-step jaxpr: the eqn
     defining the loss (the earliest-defined scalar-float output).
     Residuals are the values defined at-or-before the boundary with a
     use after it — exactly what autodiff saves for the backward.
  2. Segment the forward into `segments` checkpoint regions (per-layer
     checkpoint granularity). Cut points target equal droppable bytes
     but snap to local minima of forward-crossing bytes — real block
     boundaries are where almost nothing is live across, so the cuts
     recover the layer structure from a flat jaxpr.
  3. For a candidate policy, classify each residual: *saved* (the
     policy's saveable predicate holds — e.g. dot_general outputs under
     "dots"), *boundary* (a forward use in a later segment: the next
     segment's checkpoint input, always saved), or *dropped* (truncated
     at its last forward use — the liveness walk then frees it in the
     forward, exactly what jax.checkpoint does).
  4. Re-run the liveness walk with those truncated ranges plus a flat
     "recompute working set" bump past the boundary: the largest
     segment's dropped bytes, which rematerialize during that segment's
     backward. The replayed peak is the what-if per-device peak.
  5. Recompute FLOPs = analytic FLOPs (cost_model.eqn_flops) of every
     non-saveable forward eqn — the extra forward the backward pays.
     For "full" that's the whole forward (~+33% of the 3x fwd step);
     for "dots" only the cheap elementwise tail.

Validated against real lowerings: tests/test_remat_advisor.py lowers
the same block stack with and without jax.checkpoint(policy=...) and
pins the replayed peak within 20% of the measured liveness peak of the
actually-rematted program.
"""
from dataclasses import dataclass, field

from .memory import (_aval_bytes, _is_var, estimate_jaxpr_memory,
                     propagate_shard_counts)

__all__ = ["RematWhatIf", "REMAT_POLICIES", "BENCH_POLICY_NAMES",
           "find_boundary", "saveable_predicate", "replay_remat",
           "advise_remat"]

# policy name -> one-line description (the saveable predicates live in
# saveable_predicate; aliases below). "none" is the no-remat baseline.
REMAT_POLICIES = {
    "none": "save every residual (no remat)",
    "full": "nothing_saveable: recompute the whole segment in backward",
    "dots": "dots_saveable: save every dot_general output",
    "dots_with_no_batch_dims": "save dot outputs without batch dims "
                               "(projections, not attention scores)",
}

_ALIASES = {
    "nothing_saveable": "full",
    "dots_saveable": "dots",
    "dots_no_batch": "dots_with_no_batch_dims",
    "dots_with_no_batch_dims_saveable": "dots_with_no_batch_dims",
    "everything_saveable": "none",
}

# GPTConfig.remat_policy vocabulary -> advisor policy names
# (the model's 'dots' maps to jax dots_with_no_batch_dims_saveable —
# see models/gpt._remat_policy)
BENCH_POLICY_NAMES = {
    "full": "full",
    "dots": "dots_with_no_batch_dims",
    "none": "none",
}


def canonical_policy(name):
    name = _ALIASES.get(name, name)
    if name not in REMAT_POLICIES:
        raise KeyError(f"unknown remat policy {name!r}; known: "
                       f"{sorted(REMAT_POLICIES)} (+aliases "
                       f"{sorted(_ALIASES)})")
    return name


def saveable_predicate(policy):
    """eqn -> bool: would `policy` save this eqn's outputs as residuals
    instead of recomputing them in the backward."""
    policy = canonical_policy(policy)
    if policy == "none":
        return lambda eqn: True
    if policy == "full":
        return lambda eqn: False
    if policy == "dots":
        return lambda eqn: eqn.primitive.name == "dot_general"

    def no_batch_dots(eqn):
        if eqn.primitive.name != "dot_general":
            return False
        (_, _), (lb, _rb) = eqn.params["dimension_numbers"]
        return not lb
    return no_batch_dots


def find_boundary(jx):
    """Eqn index of the fwd/bwd boundary: where the loss value is
    defined. Scans the outputs for scalar floating values and takes the
    earliest-defined one (value_and_grad puts the loss first, the
    Trainer step puts it last; grads/opt-state outputs are all defined
    later). Falls back to the midpoint when no scalar output exists."""
    import jax.numpy as jnp
    defs = {}
    for i, eqn in enumerate(jx.eqns):
        for v in eqn.outvars:
            defs[v] = i
    cands = []
    for v in jx.outvars:
        if not _is_var(v) or v not in defs:
            continue
        aval = v.aval
        try:
            if aval.shape == () and jnp.issubdtype(aval.dtype, jnp.floating):
                cands.append(defs[v])
        except Exception:
            continue
    return min(cands) if cands else len(jx.eqns) // 2


@dataclass
class RematWhatIf:
    """One policy's replayed outcome on one program."""
    policy: str
    peak_bytes: int              # replayed per-device peak under policy
    base_peak_bytes: int         # measured peak of the no-remat program
    saved_bytes: int             # residuals the policy keeps (per device)
    boundary_bytes: int          # segment-crossing checkpoints (kept)
    dropped_bytes: int           # residuals dropped + recomputed
    bump_bytes: int              # modeled recompute working set
    recompute_flops: int         # extra fwd FLOPs the backward pays
    step_flops: int              # analytic FLOPs of the no-remat step
    segments: int
    top: list = field(default_factory=list)   # top live buffers at peak

    @property
    def recompute_pct(self):
        """Recompute as % of the full (no-remat) step's FLOPs."""
        if not self.step_flops:
            return 0.0
        return 100.0 * self.recompute_flops / self.step_flops

    @property
    def advice(self):
        gib = 1024.0 ** 3
        return (f"remat={self.policy}: peak "
                f"{self.base_peak_bytes / gib:.2f} GiB → "
                f"{self.peak_bytes / gib:.2f} GiB per device, "
                f"+{self.recompute_pct:.1f}% recompute FLOPs")

    def to_dict(self):
        return {"policy": self.policy, "peak_bytes": self.peak_bytes,
                "saved_bytes": self.saved_bytes,
                "boundary_bytes": self.boundary_bytes,
                "dropped_bytes": self.dropped_bytes,
                "recompute_flops": self.recompute_flops,
                "recompute_pct": round(self.recompute_pct, 2)}


def _collect(jx):
    """(defs, uses, n): def eqn per var, sorted use indices per var
    (program outputs use at n)."""
    n = len(jx.eqns)
    defs, uses = {}, {}
    for i, eqn in enumerate(jx.eqns):
        for v in eqn.invars:
            if _is_var(v):
                uses.setdefault(v, []).append(i)
        for v in eqn.outvars:
            defs[v] = i
    for v in jx.outvars:
        if _is_var(v):
            uses.setdefault(v, []).append(n)
    return defs, uses, n


def _segment_cuts(jx, defs, uses, boundary, droppable, segments):
    """Cut the forward [0, boundary] into `segments` chunks: targets at
    equal cumulative droppable bytes, each snapped to the nearby eqn
    index where the fewest forward-live bytes cross — liveness minima
    are the real block boundaries."""
    total = sum(droppable.values())
    # boundary 0 means the whole forward is one eqn (e.g. a nested-jit
    # call collapsed to a single pjit) — nothing to cut, and the snap
    # window below would be an empty range
    if segments <= 1 or not total or boundary < 1:
        return []
    # fwd-crossing bytes at each cut position c: def < c <= last fwd use
    delta = [0] * (boundary + 3)
    for v, d in defs.items():
        if d > boundary:
            continue
        fwd = [u for u in uses.get(v, []) if u <= boundary]
        if not fwd or max(fwd) <= d:
            continue
        b = _aval_bytes(v.aval)
        if b >= 1024:
            delta[d + 1] += b
            delta[max(fwd) + 1] -= b
    crossing, acc = [0] * (boundary + 2), 0
    for i in range(boundary + 2):
        acc += delta[i]
        crossing[i] = acc
    ideal, accd, k = [], 0, 1
    for i in range(boundary + 1):
        accd += droppable.get(i, 0)
        while k < segments and accd >= total * k / segments:
            ideal.append(i + 1)
            k += 1
    win = max(2, (boundary + 1) // (3 * segments))
    cuts = set()
    for t in ideal:
        lo, hi = max(1, t - win), min(boundary, t + win)
        cuts.add(min(range(lo, hi + 1),
                     key=lambda i: (crossing[i], abs(i - t))))
    return sorted(cuts)


@dataclass
class _ReplayBase:
    """Everything about a no-remat program that is the SAME for every
    candidate policy: the def/use walk, the fwd/bwd boundary, the
    propagated shard counts, the residual list, the base liveness peak,
    the total step FLOPs and the per-eqn forward FLOPs. `advise_remat`
    computes it once and hands it to every `replay_remat` call — the
    policy loop used to redo this walk per policy (~2x advisor host
    time on GPT-sized jaxprs)."""
    jx: object
    arg_infos: object
    defs: dict
    uses: dict
    boundary: int
    counts: dict
    residuals: list              # (var, def_idx, last_fwd_use)
    base_peak_bytes: int
    step_flops: int
    fwd_eqn_flops: list          # analytic FLOPs of eqns [0..boundary]


def _prepare_replay(program_or_jaxpr, arg_infos=None, boundary=None):
    """The policy-independent half of the what-if replay."""
    from ..cost_model import eqn_flops, jaxpr_flops
    program = program_or_jaxpr
    jx = getattr(program, "jaxpr", program)
    if arg_infos is None:
        arg_infos = getattr(program, "arg_infos", None)
    jx = jx.jaxpr if hasattr(jx, "jaxpr") else jx
    defs, uses, _n = _collect(jx)
    if boundary is None:
        boundary = find_boundary(jx)
    # fixed-point counts (analysis/propagation.py): per-dim specs where
    # the lowering pinned them, v1 heuristic everywhere else — so the
    # per-device residual pricing sees the same shards the memory pass
    # prices
    counts = propagate_shard_counts(
        jx, [i.shard_count for i in arg_infos] if arg_infos else None,
        arg_dims=([getattr(i, "dim_shards", None) for i in arg_infos]
                  if arg_infos else None))
    residuals = []
    for v, d in defs.items():
        us = uses.get(v, [])
        if d <= boundary and us and max(us) > boundary:
            fwd = [u for u in us if u <= boundary]
            residuals.append((v, d, max(fwd) if fwd else d))
    base = estimate_jaxpr_memory(jx, arg_infos=arg_infos, top_k=0,
                                 var_counts=counts)
    return _ReplayBase(
        jx=jx, arg_infos=arg_infos, defs=defs, uses=uses,
        boundary=boundary, counts=counts, residuals=residuals,
        base_peak_bytes=base.peak_bytes, step_flops=jaxpr_flops(jx),
        fwd_eqn_flops=[eqn_flops(e) for e in jx.eqns[:boundary + 1]])


def replay_remat(program_or_jaxpr, policy, arg_infos=None, segments=1,
                 boundary=None, top_k=4, base=None):
    """What-if liveness replay of one remat policy over a NO-remat
    grad/train-step program. Returns a RematWhatIf.

    The program must have been traced with checkpointing disabled (the
    autotuner's front doors arrange that); replaying over an
    already-rematted jaxpr would discount the same residuals twice.
    `base` is an optional precomputed `_prepare_replay` result —
    `advise_remat` shares one across its whole policy sweep."""
    if base is None:
        base = _prepare_replay(program_or_jaxpr, arg_infos=arg_infos,
                               boundary=boundary)
    jx, boundary = base.jx, base.boundary
    policy = canonical_policy(policy)
    save = saveable_predicate(policy)
    segments = max(int(segments or 1), 1)
    counts = base.counts

    def dev_bytes(v):
        return _aval_bytes(v.aval) // max(counts.get(v, 1), 1)

    droppable = {}
    for v, d, _ in base.residuals:
        if policy != "none" and not save(jx.eqns[d]):
            droppable[d] = droppable.get(d, 0) + dev_bytes(v)
    cuts = _segment_cuts(jx, base.defs, base.uses, boundary, droppable,
                         segments)

    def chunk_of(i):
        c = 0
        for cp in cuts:
            if i >= cp:
                c += 1
        return c

    overrides = {}
    seg_drop = [0] * (len(cuts) + 1)
    saved_b = bound_b = drop_b = 0
    for v, d, last_fwd in base.residuals:
        b = dev_bytes(v)
        if policy == "none" or save(jx.eqns[d]):
            saved_b += b
            continue
        if chunk_of(last_fwd) > chunk_of(d):
            bound_b += b           # next segment's checkpoint input
            continue
        overrides[v] = last_fwd
        drop_b += b
        seg_drop[chunk_of(d)] += b
    bump = max(seg_drop) if policy != "none" else 0

    est = estimate_jaxpr_memory(jx, arg_infos=base.arg_infos,
                                top_k=top_k,
                                last_use_override=overrides,
                                extra_after=(boundary, bump),
                                var_counts=counts)

    recompute = 0
    if policy != "none":
        recompute = sum(f for f, eqn in
                        zip(base.fwd_eqn_flops, jx.eqns)
                        if not save(eqn))

    return RematWhatIf(
        policy=policy, peak_bytes=est.peak_bytes,
        base_peak_bytes=base.base_peak_bytes, saved_bytes=saved_b,
        boundary_bytes=bound_b, dropped_bytes=drop_b, bump_bytes=bump,
        recompute_flops=recompute, step_flops=base.step_flops,
        segments=len(cuts) + 1, top=est.top)


def advise_remat(program, policies=None, arg_infos=None, segments=1,
                 boundary=None):
    """Replay every candidate policy over one no-remat program; returns
    RematWhatIf results sorted by replayed peak (smallest first). Each
    carries the `.advice` line the autotuner and CLI print:

        remat=dots: peak 12.4 GiB -> 7.9 GiB per device, +3.2% recompute FLOPs

    The base walk (defs/uses, boundary, shard counts, residuals, base
    peak, per-eqn forward FLOPs) is computed ONCE and shared across the
    policy sweep."""
    policies = policies or list(REMAT_POLICIES)
    base = _prepare_replay(program, arg_infos=arg_infos,
                           boundary=boundary)
    out = [replay_remat(program, p, segments=segments, base=base)
           for p in policies]
    return sorted(out, key=lambda r: r.peak_bytes)
