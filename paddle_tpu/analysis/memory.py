"""Static per-device HBM estimation — a jaxpr-order liveness analysis.

The estimator walks the closed jaxpr in equation order and tracks the
set of live buffers: non-donated arguments and constants live for the
whole execution (XLA cannot reuse caller-owned buffers), donated
arguments free at their last use (the buffer is recycled into outputs —
exactly the Trainer's params/opt-state donation), intermediates live
from definition to last use, program outputs to the end.  Each buffer's
per-device cost is its global size divided by its sharding's shard
count (replicated tensors cost full size on EVERY device).  Equations
carrying sub-jaxprs (pjit, scan, while, cond, remat, custom_vjp)
contribute their own recursive transient peak on top of the outer live
set, so inner temporaries aren't silently dropped.

The peak is attributed to the top-k live buffers at the peak program
point with their defining ops — the "what do I shard/remat/donate to
fit" answer, produced on CPU before a chip sees the program
(liveness-as-a-pass after TPU-MLIR, arxiv 2210.15016; the memory half
of MPK-style per-program planning, arxiv 2512.22219).

Cross-check: on jaxlibs whose `compiled.memory_analysis()` works on
CPU, `cpu_calibrated=True` reproduces the XLA CPU buffer model (no
native bf16 MXU there: sub-f32 floats widen to f32 temporaries, and
dot operands get materialized f32 conversion copies) so the estimate
lands within the lint gate's tolerance of XLA's own number.  Manifests
and TPU advice always use the native-width (uncalibrated) estimate.
"""
import re
from dataclasses import dataclass, field

from .findings import Finding, Severity
from .pass_manager import Analyzer, register_analyzer

__all__ = ["MemoryAnalyzer", "MemoryEstimate", "estimate_jaxpr_memory",
           "propagate_shard_counts", "audit_page_ledger",
           "PageRefcountAnalyzer"]

# arg names that identify decode-loop KV-cache state when the capture
# didn't assign an explicit role="cache" (serving front doors do)
_KV_CACHE_RE = re.compile(r"(^|[/.])(k|v|kv)?_?(cache|pages)(s)?([/.]|$)",
                          re.IGNORECASE)


def kv_cache_infos(arg_infos):
    """The args that count as decode-loop KV-cache state: explicit
    role="cache", or cache-looking names on args that aren't
    params/optimizer slots. ONE definition shared by
    MEM-NO-DONATION-KVCACHE and SERVE-HOST-SYNC-DECODE, so the two
    rules can never disagree about what the cache is."""
    return [i for i in arg_infos
            if i.role == "cache"
            or (i.role not in ("param", "opt_state", "gt_state")
                and _KV_CACHE_RE.search(i.name or ""))]

# primitives whose sub-f32 operands XLA CPU materializes as f32 copies
# (no native bf16 matmul path on the host; convolutions lower through a
# different path that fuses the widening and shows no copy)
_CPU_WIDENED_MXU = ("dot_general",)

# ops small enough that attributing the peak to them is noise
_ATTRIBUTION_MIN_BYTES = 1024


def _aval_bytes(aval, widen_sub_f32=False):
    """Byte size of one abstract value; 0 when shape/dtype is unknown.
    `widen_sub_f32` models XLA CPU's f32 compute width for bf16/f16."""
    import numpy as np
    try:
        import jax.numpy as jnp
        itemsize = aval.dtype.itemsize
        if widen_sub_f32 and itemsize < 4 and \
                jnp.issubdtype(aval.dtype, jnp.floating):
            itemsize = 4
        return int(np.prod(aval.shape, dtype=np.int64)) * itemsize
    except Exception:
        return 0


def _sub_jaxprs(eqn):
    """All Jaxprs hiding in an eqn's params (pjit/scan/while/cond/...)."""
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


def _is_var(v):
    return type(v).__name__ != "Literal"


@dataclass
class LiveBuffer:
    """One buffer in the live set at the peak point."""
    op: str                      # defining primitive ("argument" for invars)
    name: str                    # arg name / "eqn12:dot_general output"
    bytes: int                   # global size
    device_bytes: int            # bytes / shard_count
    shard_count: int = 1
    role: str = None             # arg role when the buffer is an argument

    def to_dict(self):
        d = {"op": self.op, "name": self.name, "bytes": self.bytes,
             "device_bytes": self.device_bytes,
             "shard_count": self.shard_count}
        if self.role:
            d["role"] = self.role
        return d


@dataclass
class MemoryEstimate:
    """Static per-device HBM footprint of one lowered program."""
    peak_bytes: int = 0          # per-device peak live bytes
    args_bytes: int = 0          # per-device resident arguments
    out_bytes: int = 0           # per-device program outputs
    temp_peak_bytes: int = 0     # peak minus always-resident args
    donated_bytes: int = 0       # per-device donated-arg bytes (credit)
    peak_eqn: int = -1           # eqn index where the peak occurs
    peak_op: str = ""            # primitive at the peak point
    top: list = field(default_factory=list)   # top-k LiveBuffers at peak
    cpu_calibrated: bool = False
    n_hosts: int = 1             # hosts the mesh spans (1 = single host)
    host_peak_bytes: int = 0     # distinct bytes resident per host at peak
    host_args_bytes: int = 0     # distinct argument bytes per host

    def to_dict(self):
        d = {"peak_bytes": self.peak_bytes,
             "args_bytes": self.args_bytes,
             "out_bytes": self.out_bytes,
             "temp_peak_bytes": self.temp_peak_bytes,
             "donated_bytes": self.donated_bytes,
             "peak_eqn": self.peak_eqn, "peak_op": self.peak_op,
             "top_live": [b.to_dict() for b in self.top]}
        if self.n_hosts > 1:
            d["per_host"] = {"n_hosts": self.n_hosts,
                             "peak_bytes": self.host_peak_bytes,
                             "args_bytes": self.host_args_bytes}
        return d

    def __str__(self):
        gib = 1024.0 ** 3
        resident = self.args_bytes - self.donated_bytes
        lines = [f"per-device peak: {self.peak_bytes / gib:.4f} GiB = "
                 f"resident args {resident / gib:.4f} + working set "
                 f"{self.temp_peak_bytes / gib:.4f} (donation frees "
                 f"{self.donated_bytes / gib:.4f})"]
        if self.n_hosts > 1:
            lines.append(
                f"per-host peak ({self.n_hosts} hosts): "
                f"{self.host_peak_bytes / gib:.4f} GiB distinct bytes "
                f"(args {self.host_args_bytes / gib:.4f}) — dp shards "
                "replicated within a host are counted once")
        for b in self.top:
            lines.append(f"  {b.device_bytes:>12d} B  {b.op:<16} {b.name}")
        return "\n".join(lines)


def _eqn_source(eqn, idx):
    """Short human label for an eqn's output buffer."""
    prim = eqn.primitive.name
    try:
        from jax._src import source_info_util
        frame = source_info_util.user_frame(eqn.source_info)
        if frame is not None:
            import os
            return (f"{prim} @ {os.path.basename(frame.file_name)}:"
                    f"{frame.start_line}")
    except Exception:
        pass
    return f"{prim} #eqn{idx}"


def _inner_transient(jx, widen, memo):
    """Transient extra bytes an eqn's sub-jaxpr adds on top of the outer
    live set (its own peak minus its invars, which are already counted
    as live operands outside)."""
    key = id(jx)
    if key not in memo:
        peak, _, _ = _walk(jx, arg_counts=None, donated=(), widen=widen,
                           pin_invars=False, memo=memo)
        inb = sum(_aval_bytes(v.aval) for v in jx.invars)
        memo[key] = max(0, peak - inb)
    return memo[key]


def _walk(jx, arg_counts, donated, widen, pin_invars, memo, top_k=0,
          arg_infos=None, last_use_override=None, extra_after=None,
          var_counts=None, count_cap=None):
    """Liveness walk of one jaxpr. Returns (peak, peak_eqn_idx,
    top_buffers_at_peak).

    `last_use_override` ({var: eqn_idx}) truncates live ranges — the
    remat advisor's what-if replay drops checkpointed intermediates by
    ending them at their last FORWARD use. `extra_after` ((idx, bytes))
    adds a flat byte bump to every program point past idx — the
    advisor's model of one segment's recompute working set during the
    backward. Output vars are never truncated.

    `var_counts` ({var: shard_count}, typically
    `propagation.PropagationResult.counts`) overrides the inline
    forward propagation per var where present: the fixed-point pass
    sees constraint pins and consumer-implied specs this single
    forward sweep can't, so its counts are used when available and the
    inline `_eqn_out_shard` result is the documented conservative
    fallback for vars the pass left unknown.

    `count_cap` clamps every shard count to at most this value — the
    per-host accounting's knob: divided by min(count, n_hosts), a
    buffer's contribution is its distinct bytes per host."""
    last_use = {}
    for i, eqn in enumerate(jx.eqns):
        for v in eqn.invars:
            if _is_var(v):
                last_use[v] = i
    n = len(jx.eqns)
    for v in jx.outvars:
        if _is_var(v):
            last_use[v] = n
    if last_use_override:
        for v, idx in last_use_override.items():
            if last_use.get(v, n) < n:
                last_use[v] = idx
    bump_after, bump = extra_after if extra_after else (n + 1, 0)
    invars = list(jx.invars)
    if pin_invars:
        # non-donated arguments + baked constants are caller-owned: XLA
        # keeps them resident for the whole execution
        for k, v in enumerate(invars):
            if not (donated and k < len(donated) and donated[k]):
                last_use[v] = n
        for v in jx.constvars:
            last_use[v] = n

    counts = {}          # var -> shard count (propagated)
    dimmap = {}          # var -> per-dim shard counts (None = unknown)
    live = {}            # var -> (device_bytes, LiveBuffer)
    for k, v in enumerate(invars):
        if arg_infos and k < len(arg_infos):
            dimmap[v] = getattr(arg_infos[k], "dim_shards", None)
        if v not in last_use:
            continue
        cnt = arg_counts[k] if arg_counts and k < len(arg_counts) else 1
        if count_cap:
            cnt = min(max(cnt, 1), count_cap)
        counts[v] = cnt
        info = (arg_infos[k] if arg_infos and k < len(arg_infos) else None)
        gb = _aval_bytes(v.aval)
        live[v] = (gb // max(cnt, 1), LiveBuffer(
            op="argument",
            name=info.name if info else f"arg{k}",
            bytes=gb, device_bytes=gb // max(cnt, 1), shard_count=cnt,
            role=info.role if info else None))
    for v in jx.constvars:
        if v in last_use:
            gb = _aval_bytes(v.aval)
            live[v] = (gb, LiveBuffer(op="constant", name="const",
                                      bytes=gb, device_bytes=gb))

    cur = sum(b for b, _ in live.values())
    peak, peak_idx, peak_top = cur, -1, list(live.values())
    for i, eqn in enumerate(jx.eqns):
        inner = 0
        for sj in _sub_jaxprs(eqn):
            inner = max(inner, _inner_transient(sj, widen, memo))
        if widen and eqn.primitive.name in _CPU_WIDENED_MXU:
            # XLA CPU materializes f32 conversion copies of sub-f32
            # dot operands (bf16 has no host MXU path)
            for v in eqn.invars:
                if _is_var(v):
                    w = _aval_bytes(v.aval, widen_sub_f32=True)
                    if w > _aval_bytes(v.aval):
                        inner += w
        # sharding propagation: an op's result is at best as sharded as
        # its most-sharded operand (GSPMD propagates along data paths;
        # a reduction to scalar only shrinks the buffer, so the error
        # is bounded by the tiny result) — refined by _eqn_out_shard
        # where per-dim counts are known (contracted dot_general dims
        # drop their sharding instead of leaking into the output)
        ivs = [v for v in eqn.invars if _is_var(v)]
        out_count, out_dims = _eqn_out_shard(
            eqn, [counts.get(v, 1) for v in ivs],
            [dimmap.get(v) for v in ivs])
        for v in eqn.outvars:
            dimmap[v] = out_dims
            if v in last_use:
                cnt = (var_counts[v]
                       if var_counts is not None and v in var_counts
                       else out_count)
                if count_cap:
                    cnt = min(max(cnt, 1), count_cap)
                counts[v] = cnt
                gb = _aval_bytes(v.aval, widen_sub_f32=widen)
                db = gb // max(cnt, 1)
                live[v] = (db, LiveBuffer(
                    op=eqn.primitive.name, name=_eqn_source(eqn, i),
                    bytes=gb, device_bytes=db, shard_count=cnt))
                cur += db
        extra = bump if i > bump_after else 0
        if cur + inner + extra > peak:
            peak, peak_idx = cur + inner + extra, i
            peak_top = list(live.values())
        for v in list(eqn.invars) + list(eqn.outvars):
            if _is_var(v) and last_use.get(v) == i and v in live:
                cur -= live.pop(v)[0]
    top = []
    if top_k:
        top = sorted((b for _, b in peak_top
                      if b.device_bytes >= _ATTRIBUTION_MIN_BYTES),
                     key=lambda b: -b.device_bytes)[:top_k]
    return peak, peak_idx, top


def _reshape_dim_shards(in_shape, in_dims, out_shape):
    """Per-dim shard counts across a reshape, or None when the mapping
    isn't clean. Contiguous dim groups with equal element products map
    onto each other (the standard reshape factorization); a group's
    shard factor is the product of the factors of its FULLY-SHARDED
    major prefix (every dim before the first partially-sharded one
    contributes — merging dims sharded whole keeps a contiguous
    row-major split) plus at most one trailing partial factor, and is
    peeled onto the group's output dims major-first, WHOLE DIMS at a
    time: an output dim is either covered entirely by the split (its
    full size divides the remaining factor) or carries the remainder
    when that divides it — so a 4-way factor lands on (2, 2, ...) as
    (2, 2) and on (8, ...) as (4,), while a peel that would make one
    shard straddle a tile boundary (neither divides) returns None.
    Also None: a factor on a MINOR input dim (a partially-sharded or
    unsharded non-unit dim more major than it in the group) — a
    row-major merge turns minor-dim sharding into a STRIDED pattern of
    the merged dim, so pinning the factor anywhere would silently
    migrate shard knowledge to the wrong dimension — an
    anti-conservative per-device underestimate, the exact failure the
    conservative cap exists to prevent."""
    n, m = len(in_shape), len(out_shape)
    out = []
    i = j = 0
    while i < n and j < m:
        gi, gj = [i], [j]
        pi, pj = int(in_shape[i]), int(out_shape[j])
        i += 1
        j += 1
        while pi != pj:
            if pi < pj:
                if i >= n:
                    return None
                pi *= int(in_shape[i])
                gi.append(i)
                i += 1
            else:
                if j >= m:
                    return None
                pj *= int(out_shape[j])
                gj.append(j)
                j += 1
        factor = 1
        whole_prefix = True                  # fully-sharded so far?
        for g in gi:                         # major -> minor
            f = int(in_dims[g])
            sh = int(in_shape[g])
            if f > 1:
                if not whole_prefix:         # factor on a minor dim:
                    return None              # strided, unrepresentable
                factor *= f
                if f != sh:                  # partial split ends the
                    whole_prefix = False     # mergeable prefix
            elif sh > 1:
                whole_prefix = False
        group = [1] * len(gj)
        f = factor
        for pos, g in enumerate(gj):         # peel major-first
            if f == 1:
                break
            od = int(out_shape[g])
            if f >= od:
                if f % od:
                    return None              # shard straddles the tile
                group[pos] = od
                f //= od
            else:
                if od % f:
                    return None
                group[pos] = f
                f = 1
        if f != 1:
            return None
        out.extend(group)
    # trailing size-1 dims on either side carry no sharding
    while i < n:
        if int(in_shape[i]) != 1 or int(in_dims[i]) != 1:
            return None
        i += 1
    while j < m:
        if int(out_shape[j]) != 1:
            return None
        out.append(1)
        j += 1
    return tuple(out)


# the reduce family whose output drops shard factors on reduced dims
# (argmax/argmin carry `axes` params exactly like lax.reduce_* eqns)
_REDUCE_PRIMS = frozenset({
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
    "reduce_and", "reduce_or", "reduce_xor", "argmax", "argmin"})

# the scatter family (x.at[idx].set/add/... lowerings): output shape ==
# operand shape, and the operand's dim sharding threads EXCEPT on the
# dynamically indexed dims
_SCATTER_PRIMS = frozenset({
    "scatter", "scatter-add", "scatter-mul", "scatter-min",
    "scatter-max"})


def _eqn_out_shard(eqn, in_counts, in_dims):
    """Shard propagation for one eqn's outputs: (total_count, per-dim
    counts or None). The default heuristic — a result is at best as
    sharded as its most-sharded operand — is refined where per-DIM
    shard counts are known (seeded from ArgInfo.dim_shards):

    * `dot_general` respects contracted dims: sharding on a contracted
      axis does NOT survive into the output (GSPMD all-reduces the
      partial products; the result is replicated over that mesh axis),
      so a tensor-parallel intermediate stops inheriting
      max(operand counts) blindly. Output dims follow the dot layout
      (batch, lhs free, rhs free).
    * the reduce family (`reduce_sum`/`reduce_max`/... and
      `argmax`/`argmin`) drops shard factors on REDUCED dims — a
      reduction over a sharded axis all-reduces the per-shard partials
      (reduce_sum is a contraction against ones), so the output is
      replicated over that mesh axis; kept dims thread through.
    * `reshape` tracks split/merge dims: a sharded dim's factor follows
      its contiguous factor group into the output when divisibility
      holds (`_reshape_dim_shards`), falling back to the conservative
      cap otherwise — so dp/tp knowledge survives the [B, S, H·D] <->
      [B·S, H, D] style reshapes between attention matmuls.
    * `concatenate` / `pad` / `slice` thread factors through UNTOUCHED
      dims and drop them on the structural ones: the concat dim (pieces
      land at per-operand offsets), padded dims (offsets shift), and
      statically under-sliced or strided dims (the kept span crosses
      shard boundaries) — while a dim every operand agrees on, or one
      taken whole at stride 1, keeps its factor. This is what lets
      dp/tp knowledge survive KV-cache style concat-and-slice chains.
    * `gather` / `dynamic_slice` drop shard factors on DYNAMICALLY
      indexed dims (start_index_map / runtime slice starts): rows read
      from dynamic positions admit no static split, so the result is
      at best replicated on that mesh axis — while dims taken whole
      (full slice size, not index-addressed) thread their factor, the
      exact mirror of the scatter rule's write side. Capped at the
      most-sharded operand like every slice above.
    * shape-preserving ops (elementwise chains) inherit the matching
      operand's dim vector, `transpose` permutes it — so dim knowledge
      survives between matmuls instead of dying at the first add/ln.
    """
    name = eqn.primitive.name
    try:
        if name == "dot_general" and len(in_dims) >= 2 and \
                in_dims[0] is not None and in_dims[1] is not None:
            (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
            ld, rd = in_dims[0], in_dims[1]
            batch = [ld[i] for i in lb]
            lfree = [ld[i] for i in range(len(ld))
                     if i not in set(lc) | set(lb)]
            rfree = [rd[i] for i in range(len(rd))
                     if i not in set(rc) | set(rb)]
            dims = tuple(batch + lfree + rfree)
            total = 1
            for d in dims:
                total *= int(d)
            # per-dim counts carry no mesh-axis identity, so the cross
            # product of lhs/rhs free-dim factors can claim more shards
            # than devices exist (both operands sharded on the SAME
            # axis forces GSPMD to reshard one of them). Cap at the
            # most-sharded operand — never claim finer sharding than
            # any input actually had (under-counting shards
            # OVERestimates memory, the safe direction for the gates).
            cap = max(in_counts) if in_counts else 1
            if total > cap:
                return cap, None
            return max(total, 1), dims
        if name in _REDUCE_PRIMS and in_dims and in_dims[0] is not None:
            axes = eqn.params.get("axes")
            if axes is not None:
                ld = in_dims[0]
                # a reduced dim's shard factor does NOT survive: GSPMD
                # all-reduces the per-shard partials over that mesh
                # axis and the result is replicated on it (the exact
                # dot_general contracted-dim rule, applied to the
                # reduce family — reduce_sum IS a contraction against
                # ones). Kept dims thread through unchanged.
                dims = tuple(d for i, d in enumerate(ld)
                             if i not in set(axes))
                total = 1
                for d in dims:
                    total *= int(d)
                cap = max(in_counts) if in_counts else 1
                if total > cap:       # no axis identity: never claim
                    return cap, None  # finer sharding than any input
                return max(total, 1), dims
        if name == "dynamic_slice" and in_dims and \
                in_dims[0] is not None:
            ss = eqn.params.get("slice_sizes")
            ivs0 = [v for v in eqn.invars if _is_var(v)]
            in_shape = tuple(getattr(ivs0[0].aval, "shape", ()))
            if ss is not None and len(ss) == len(in_dims[0]) == \
                    len(in_shape):
                ld = in_dims[0]
                # a dim sliced at a DYNAMIC start loses its factor —
                # the start index is a runtime value, so GSPMD cannot
                # keep a static split over the sliced span without
                # resharding (the scatter indexed-dim rule, read side);
                # a dim taken WHOLE (slice size == operand dim) is
                # statically the identity and threads its factor
                dims = tuple(int(d) if int(ss[i]) == int(in_shape[i])
                             else 1 for i, d in enumerate(ld))
                total = 1
                for d in dims:
                    total *= int(d)
                cap = max(in_counts) if in_counts else 1
                if total > cap:       # no axis identity: never claim
                    return cap, None  # finer sharding than any input
                return max(total, 1), dims
        if name == "concatenate" and in_dims and \
                all(d is not None for d in in_dims) and in_dims:
            axis = eqn.params.get("dimension")
            if axis is not None and all(len(d) == len(in_dims[0])
                                        for d in in_dims):
                # the concat dim loses its factor: pieces land at
                # per-operand offsets, so no single static split of
                # the merged dim covers them without resharding; a
                # NON-concat dim threads only when every operand
                # agrees on its factor (a mixed-factor dim would make
                # the output's split operand-dependent)
                dims = tuple(
                    1 if (i == axis or len({int(d[i])
                                            for d in in_dims}) != 1)
                    else int(in_dims[0][i])
                    for i in range(len(in_dims[0])))
                total = 1
                for d in dims:
                    total *= int(d)
                cap = max(in_counts) if in_counts else 1
                if total > cap:       # no axis identity: never claim
                    return cap, None  # finer sharding than any input
                return max(total, 1), dims
        if name == "pad" and in_dims and in_dims[0] is not None:
            pc = eqn.params.get("padding_config")
            if pc is not None and len(pc) == len(in_dims[0]):
                # a PADDED dim loses its factor: low/high/interior
                # padding shifts element offsets, so the input's
                # even split no longer lands on shard boundaries;
                # untouched dims thread through
                dims = tuple(
                    1 if any(int(x) != 0 for x in pc[i])
                    else int(d) for i, d in enumerate(in_dims[0]))
                total = 1
                for d in dims:
                    total *= int(d)
                cap = max(in_counts) if in_counts else 1
                if total > cap:
                    return cap, None
                return max(total, 1), dims
        if name == "slice" and in_dims and in_dims[0] is not None:
            starts = eqn.params.get("start_indices")
            limits = eqn.params.get("limit_indices")
            strides = eqn.params.get("strides")
            ivs0 = [v for v in eqn.invars if _is_var(v)]
            in_shape = tuple(getattr(ivs0[0].aval, "shape", ()))
            if starts is not None and limits is not None and \
                    len(starts) == len(in_dims[0]) == len(in_shape):
                # a STATICALLY sliced dim (taken below full size, or
                # strided) loses its factor — the kept span crosses
                # shard boundaries at static but non-aligned offsets,
                # which GSPMD resolves by resharding; a dim taken
                # WHOLE at stride 1 is the identity and threads (the
                # static mirror of the dynamic_slice rule above)
                dims = tuple(
                    int(d) if (int(starts[i]) == 0 and
                               int(limits[i]) == int(in_shape[i]) and
                               (strides is None or
                                int(strides[i]) == 1))
                    else 1 for i, d in enumerate(in_dims[0]))
                total = 1
                for d in dims:
                    total *= int(d)
                cap = max(in_counts) if in_counts else 1
                if total > cap:
                    return cap, None
                return max(total, 1), dims
        if name == "gather" and in_dims and in_dims[0] is not None:
            dn = eqn.params.get("dimension_numbers")
            ss = eqn.params.get("slice_sizes")
            ivs0 = [v for v in eqn.invars if _is_var(v)]
            in_shape = tuple(getattr(ivs0[0].aval, "shape", ()))
            out_shape = tuple(getattr(eqn.outvars[0].aval, "shape", ()))
            if dn is not None and ss is not None and \
                    len(in_dims[0]) == len(in_shape) == len(ss):
                ld = in_dims[0]
                dropped = set(getattr(dn, "collapsed_slice_dims",
                                      ()) or ()) | \
                    set(getattr(dn, "operand_batching_dims", ()) or ())
                offset = tuple(getattr(dn, "offset_dims", ()) or ())
                kept = [d for d in range(len(ld)) if d not in dropped]
                if len(offset) == len(kept):
                    indexed = set(getattr(dn, "start_index_map",
                                          ()) or ())
                    # offset output dims map in order onto the
                    # non-collapsed operand dims: a dim addressed by
                    # the gather indices (start_index_map) or sliced
                    # below full size loses its factor — rows land at
                    # DYNAMIC positions, no static split survives (the
                    # scatter rule's read side); whole untouched dims
                    # thread. Batch dims (from the indices operand)
                    # stay at 1 — conservative, the safe direction.
                    dims = [1] * len(out_shape)
                    for pos, d in zip(offset, kept):
                        if 0 <= pos < len(dims) and d not in indexed \
                                and int(ss[d]) == int(in_shape[d]):
                            dims[pos] = int(ld[d])
                    dims = tuple(dims)
                    total = 1
                    for d in dims:
                        total *= int(d)
                    cap = max(in_counts) if in_counts else 1
                    if total > cap:   # no axis identity: never claim
                        return cap, None
                    return max(total, 1), dims
        if name in _SCATTER_PRIMS and in_dims and in_dims[0] is not None:
            dn = eqn.params.get("dimension_numbers")
            if dn is not None:
                ld = in_dims[0]          # operand: output shape == its
                # dims addressed by the scatter indices lose their
                # factor: updates land at DYNAMIC positions along those
                # dims, so GSPMD cannot keep a static split without
                # resharding — the result is at best replicated on that
                # mesh axis (the dot/reduce contracted-dim rule applied
                # to indexed dims). Window dims thread from the operand.
                upd = set(getattr(dn, "scatter_dims_to_operand_dims",
                                  ()) or ()) | \
                    set(getattr(dn, "inserted_window_dims", ()) or ())
                dims = tuple(1 if i in upd else int(d)
                             for i, d in enumerate(ld))
                total = 1
                for d in dims:
                    total *= int(d)
                cap = max(in_counts) if in_counts else 1
                if total > cap:      # no axis identity: never claim
                    return cap, None  # finer sharding than any input
                return max(total, 1), dims
        if name == "transpose" and in_dims and in_dims[0] is not None:
            perm = eqn.params.get("permutation")
            if perm is not None and len(perm) == len(in_dims[0]):
                dims = tuple(in_dims[0][p] for p in perm)
                return max(in_counts) if in_counts else 1, dims
        if name == "reshape" and in_dims and in_dims[0] is not None:
            ivs = [v for v in eqn.invars if _is_var(v)]
            in_shape = tuple(getattr(ivs[0].aval, "shape", ()))
            if len(in_dims[0]) == len(in_shape):
                dims = _reshape_dim_shards(
                    in_shape, in_dims[0],
                    tuple(getattr(eqn.outvars[0].aval, "shape", ())))
                if dims is not None:
                    return max(in_counts) if in_counts else 1, dims
        out_shape = tuple(getattr(eqn.outvars[0].aval, "shape", ()))
        best, best_dims = (max(in_counts) if in_counts else 1), None
        for cnt, dims, v in zip(in_counts, in_dims,
                                [v for v in eqn.invars if _is_var(v)]):
            if dims is not None and cnt == best and \
                    tuple(getattr(v.aval, "shape", ())) == out_shape:
                best_dims = dims
                break
        return best, best_dims
    except Exception:
        return (max(in_counts) if in_counts else 1), None


def propagate_shard_counts(jx, arg_counts=None, arg_dims=None):
    """{var: shard_count} over one jaxpr. Since v2 this is a thin
    wrapper over the fixed-point pass (`propagation.propagate_shardings`
    — forward AND backward sweeps, constraint-eqn seeding, scan/while/
    pjit body recursion): where the fixed point pinned a concrete
    per-dim spec, its product wins; everywhere else the count comes
    from the same single forward sweep of `_eqn_out_shard` as v1
    (max-operand heuristic with conservative caps) — on a program with
    no mid-graph pins and no backward-reachable specs the two are
    identical, so this stays the documented conservative fallback. The
    remat advisor prices dropped/saved residuals per device with it.
    `arg_dims` optionally seeds per-dim shard counts per invar (aligned
    with `arg_counts`; `lowering.ArgInfo.dim_shards` supplies them)."""
    from .propagation import propagate_shardings
    return propagate_shardings(jx, arg_counts=arg_counts,
                               arg_dims=arg_dims).counts


def estimate_jaxpr_memory(closed_jaxpr, arg_infos=None, top_k=8,
                          cpu_calibrated=False, last_use_override=None,
                          extra_after=None, var_counts=None, n_hosts=1):
    """Static per-device HBM estimate of one closed jaxpr.

    `arg_infos`: optional list of `lowering.ArgInfo` aligned with the
    flattened invars — supplies shard counts (per-device division),
    donation flags (donated args free at last use), and names for the
    peak attribution. Without it every arg is assumed replicated and
    non-donated (the single-device forward-program case).

    `last_use_override`/`extra_after` thread through to the liveness
    walk — the remat advisor's what-if replay (remat_advisor.py) re-runs
    the SAME walk with checkpointed intermediates dropped and one
    segment's recompute working set added past the fwd/bwd boundary.

    `var_counts`: optional fixed-point shard counts
    (`propagation.PropagationResult.counts`) overriding the walk's
    inline forward propagation per var — the MemoryAnalyzer passes the
    propagation pass's result so pricing sees mid-graph constraint pins;
    without it the walk's own sweep is the conservative fallback.

    `n_hosts` > 1 prices the dp-over-hosts view too: the SAME liveness
    walk re-run with every shard count clamped to
    `min(shard_count, n_hosts)`, so a buffer's contribution is its
    DISTINCT bytes per host — replicated buffers (and dp shards
    replicated across a host's local devices, host-major device order
    as `build_mesh` lays out) count once per host, buffers sharded at
    least n_hosts ways count 1/n_hosts. That is the per-host
    checkpoint/offload footprint, not n_local_devices x per-device HBM
    (which is just a multiplication the caller can do). Surfaced as
    `host_peak_bytes` / `host_args_bytes` on the estimate.
    """
    jx = closed_jaxpr.jaxpr if hasattr(closed_jaxpr, "jaxpr") else closed_jaxpr
    infos = arg_infos or []
    arg_counts = [i.shard_count for i in infos] or None
    donated = [i.donated for i in infos]
    memo = {}
    peak, peak_idx, top = _walk(
        jx, arg_counts=arg_counts, donated=donated, widen=cpu_calibrated,
        pin_invars=True, memo=memo, top_k=top_k, arg_infos=infos,
        last_use_override=last_use_override, extra_after=extra_after,
        var_counts=var_counts)

    def _arg_db(k, v):
        cnt = arg_counts[k] if arg_counts and k < len(arg_counts) else 1
        return _aval_bytes(v.aval) // max(cnt, 1)

    args_bytes = sum(_arg_db(k, v) for k, v in enumerate(jx.invars))
    out_bytes = 0
    for v in jx.outvars:
        if _is_var(v):
            cnt = 1  # conservative: treat outputs as replicated w/o info
            out_bytes += _aval_bytes(v.aval, widen_sub_f32=cpu_calibrated) \
                // cnt
    donated_bytes = sum(_arg_db(k, v) for k, v in enumerate(jx.invars)
                        if k < len(donated) and donated[k])
    est = MemoryEstimate(
        peak_bytes=peak, args_bytes=args_bytes, out_bytes=out_bytes,
        temp_peak_bytes=max(0, peak - (args_bytes - donated_bytes)),
        donated_bytes=donated_bytes, peak_eqn=peak_idx,
        peak_op=(jx.eqns[peak_idx].primitive.name
                 if 0 <= peak_idx < len(jx.eqns) else ""),
        top=top, cpu_calibrated=cpu_calibrated)
    if n_hosts > 1:
        # same walk, every shard count clamped to the host count: a
        # buffer sharded fewer than n_hosts ways is (partly) replicated
        # across hosts and costs global/min(cnt, n_hosts) distinct
        # bytes on each
        hpeak, _, _ = _walk(
            jx, arg_counts=arg_counts, donated=donated,
            widen=cpu_calibrated, pin_invars=True, memo={},
            arg_infos=infos, last_use_override=last_use_override,
            extra_after=extra_after, var_counts=var_counts,
            count_cap=int(n_hosts))
        est.n_hosts = int(n_hosts)
        est.host_peak_bytes = hpeak
        est.host_args_bytes = sum(
            _aval_bytes(v.aval) // min(
                max(arg_counts[k] if arg_counts and k < len(arg_counts)
                    else 1, 1), int(n_hosts))
            for k, v in enumerate(jx.invars))
    return est


@register_analyzer
class MemoryAnalyzer(Analyzer):
    """Per-device peak-HBM pass: liveness estimate + regression gate.

    Findings:
      MEM-PEAK-REGRESSION  ERROR    fresh peak exceeds the committed
                                    memory manifest beyond tolerance
      MEM-PEAK-IMPROVED    INFO     peak dropped below tolerance — the
                                    manifest is stale, regenerate it
      MEM-OVER-BUDGET      ERROR    peak exceeds ctx.hbm_budget_bytes
      MEM-NO-DONATION      WARNING  params+opt state bigger than the
                                    donation credit — train-step args
                                    are not donated, doubling resident
                                    state
      MEM-NO-DONATION-KVCACHE WARNING  decode-loop program whose KV
                                    cache is not donated — the cache is
                                    the carried state in inference (the
                                    params are read-only there), so a
                                    non-donated cache copies the whole
                                    KV store every decode step
    Metrics feed memory_manifests/<config>.json (peak, breakdown, top-k
    attribution)."""
    name = "memory"

    def run(self, program, ctx):
        if getattr(program, "jaxpr", None) is None:
            self.metrics = {"available": False}
            return []
        # the fixed-point pass ran just before this one (registration
        # order) and stashed its result; result_for recomputes when the
        # pass manager was bypassed or the program changed underneath
        from .propagation import result_for
        prop = result_for(program, ctx)
        n_hosts = 1
        for h in (ctx.extra.get("axis_host_counts") or {}).values():
            n_hosts *= max(int(h), 1)
        est = estimate_jaxpr_memory(
            program.jaxpr, arg_infos=getattr(program, "arg_infos", None),
            top_k=ctx.extra.get("memory_top_k", 8),
            var_counts=prop.counts if prop is not None else None,
            n_hosts=n_hosts)
        self.metrics = {"available": True, **est.to_dict()}
        findings = []
        committed = (ctx.memory_manifest or {})
        want = committed.get("per_device_peak_bytes")
        tol = ctx.memory_tolerance
        if want:
            if est.peak_bytes > want * (1 + tol):
                findings.append(Finding(
                    "MEM-PEAK-REGRESSION", Severity.ERROR,
                    f"per-device peak HBM {est.peak_bytes} exceeds the "
                    f"committed manifest's {want} by more than "
                    f"{tol:.0%} — the step no longer fits the same "
                    "chip headroom",
                    suggested_fix="shard or remat the top live tensors "
                    "(debug.memory_report), or regenerate manifests if "
                    "the growth is intentional: python -m "
                    "paddle_tpu.analysis --write-manifests"))
            elif est.peak_bytes < want * (1 - tol):
                findings.append(Finding(
                    "MEM-PEAK-IMPROVED", Severity.INFO,
                    f"per-device peak HBM {est.peak_bytes} is more than "
                    f"{tol:.0%} below the committed {want} — regenerate "
                    "the manifest to bank the improvement"))
        budget = ctx.hbm_budget_bytes
        if budget and est.peak_bytes > budget:
            findings.append(Finding(
                "MEM-OVER-BUDGET", Severity.ERROR,
                f"per-device peak HBM {est.peak_bytes} exceeds the "
                f"budget {budget}",
                suggested_fix="raise fsdp sharding, enable remat, or "
                "shrink the per-device batch"))
        infos = getattr(program, "arg_infos", None) or []
        state_bytes = sum(i.device_bytes for i in infos
                          if i.role in ("param", "opt_state"))
        if state_bytes and not any(i.donated for i in infos
                                   if i.role in ("param", "opt_state")):
            if ctx.extra.get("expect_donation", True) and \
                    any(i.role == "opt_state" for i in infos):
                findings.append(Finding(
                    "MEM-NO-DONATION", Severity.WARNING,
                    f"{state_bytes} bytes of params/opt-state are not "
                    "donated — the step holds two copies of the model "
                    "state in HBM",
                    suggested_fix="donate params/opt state into the "
                    "compiled step (Trainer(donate=True))"))
        # decode-loop variant: in inference the carried state is the KV
        # cache, not params — jit.save/serving paths never donate params
        # (correctly: they're read-only across steps), but a non-donated
        # cache double-buffers the whole KV store on every step
        cache_infos = kv_cache_infos(infos)
        # per-ARG, not any(): k_pages donated with v_pages forgotten
        # still double-buffers half the store
        undonated = [i for i in cache_infos if not i.donated]
        undonated_bytes = sum(i.device_bytes for i in undonated)
        if undonated_bytes and ctx.extra.get("expect_donation", True):
            names = ", ".join(sorted(i.name or "?" for i in undonated)[:4])
            findings.append(Finding(
                "MEM-NO-DONATION-KVCACHE", Severity.WARNING,
                f"{undonated_bytes} bytes of KV-cache state ({names}) "
                "are not donated into the decode step — XLA must "
                "allocate a second full cache for the updated pages "
                "every step",
                suggested_fix="donate the cache buffers "
                "(jax.jit(step, donate_argnums=...) on the k/v page "
                "arguments, as serving.PagedGPTDecoder does)"))
        return findings


# ------------------------------------------------- shared-pool refcounts


def audit_page_ledger(ledger):
    """MEM-PAGE-REFCOUNT invariant audit of a serving engine's page
    ledger (`ContinuousBatchingEngine.page_ledger()`): with a shared
    (prefix-cached) KV pool, every allocatable page must be owned
    EXACTLY once — on the free list, XOR held by slot(s) under a
    covering cache refcount, XOR parked (refcount 0) in the cache's
    LRU.  Double-frees, leaks, refcount drift and writes-into-shared
    hazards all surface as findings.  Returns a list of Finding
    (empty = consistent)."""
    findings = []

    def bad(msg, fix=None):
        findings.append(Finding("MEM-PAGE-REFCOUNT", Severity.ERROR, msg,
                                analyzer="page-refcount",
                                suggested_fix=fix))

    num_pages = int(ledger.get("num_pages", 0))
    scratch = ledger.get("scratch")
    free = list(ledger.get("free", []))
    slots = {int(s): list(p)
             for s, p in (ledger.get("slots") or {}).items()}
    shared = {int(s): set(p)
              for s, p in (ledger.get("shared") or {}).items()}
    cache = {int(p): dict(e)
             for p, e in (ledger.get("cache") or {}).items()}

    seen = set()
    for p in free:
        if p in seen:
            bad(f"page {p} appears twice in the free list (double free)")
        seen.add(p)
        if scratch is not None and p == scratch:
            bad("the reserved scratch page is on the free list")

    holders = {}                         # page -> [slots holding it]
    for s, pages in slots.items():
        for p in pages:
            holders.setdefault(p, []).append(s)
    # multi-LoRA rows (serving.tenancy): per-slot adapter salts — a
    # page shared across slots whose salts DIFFER means one variant is
    # reading another's KV bytes (the adapter's low-rank delta is part
    # of every write, so cross-variant bytes are simply wrong). The
    # engine prevents this by folding `adapter_salt` into the chain
    # keys; the audit proves it held on the live ledger.
    slot_adapters = {int(s): dict(e) for s, e in
                     (ledger.get("slot_adapters") or {}).items()}
    for p, hs in holders.items():
        if len(hs) > 1 and (p not in cache
                            or int(cache[p].get("refs", 0)) < len(hs)):
            bad(f"page {p} is held by slots {sorted(hs)} without a "
                "covering cache refcount (unaccounted aliasing)",
                fix="mount shared pages through the prefix cache so "
                "refcounts track every holder")
        if len(hs) > 1 and slot_adapters:
            salts = {slot_adapters.get(s, {}).get("salt", "")
                     for s in hs}
            if len(salts) > 1:
                bad(f"page {p} is shared by slots {sorted(hs)} with "
                    f"DIFFERENT adapter fingerprints — a LoRA "
                    "variant is aliasing another variant's KV bytes",
                    fix="fold the request's adapter_salt into the "
                    "prefix-cache chain keys (PrefixCache.block_keys"
                    "(ids, extra_salt=...)) so cross-variant prompts "
                    "never match the same entries")
    for p in seen:
        if p in holders:
            bad(f"page {p} is both free and held by slot(s) "
                f"{sorted(holders[p])} (double free)")
        if p in cache:
            bad(f"page {p} is both free and cache-tracked (double free: "
                "eviction must unmap before returning a page)")

    mounts = {}                          # page -> shared-mount count
    for s, sh in shared.items():
        for p in sh:
            mounts[p] = mounts.get(p, 0) + 1
            if p not in (slots.get(s) or []):
                bad(f"slot {s} marks page {p} shared but does not hold "
                    "it")
            if p not in cache:
                bad(f"slot {s} holds page {p} as shared but the cache "
                    "does not track it")
    for p, e in cache.items():
        refs = int(e.get("refs", 0))
        if refs < 0:
            bad(f"page {p} has negative refcount {refs} (double "
                "release)")
        m = mounts.get(p, 0)
        if refs != m:
            bad(f"page {p} refcount {refs} != {m} mounting slot(s) "
                "(refcount drift — the page would be freed too early "
                "or never)")
        if refs == 0 and p in holders:
            # a parked page is by definition held by NOBODY: a slot
            # still mapping it means a reference was dropped without
            # decref — eviction would hand a live-mapped page to the
            # free list and a later prefill would corrupt the slot's KV
            bad(f"page {p} is parked (refcount 0) but still held by "
                f"slot(s) {sorted(holders[p])} (reference dropped "
                "without decref)")

    owned = set(free) | set(holders) | set(cache)
    for p in range(num_pages):
        if scratch is not None and p == scratch:
            continue
        if p not in owned:
            bad(f"page {p} is unreachable: not free, not slot-held, "
                "not cached (leak)")

    # host-tier rows (tiered KV, serving.kv_tier): a spilled entry is
    # keyed by chain key and owns NO device page — unless it was
    # restored, in which case its device-twin backref must point at a
    # live cache-tracked page. A twin on the free list means the
    # unmount bookkeeping was dropped: a reader could mount the host
    # entry's "device copy" while the free list hands the same page to
    # a prefill (the spill-tier double-free).
    host = {str(k): dict(e)
            for k, e in (ledger.get("host") or {}).items()}
    free_set = set(free)
    for key, e in host.items():
        p = e.get("page")
        if p is None:
            continue
        p = int(p)
        if p in free_set:
            bad(f"host entry {key[:12]} is both host-resident and "
                f"device-free: its device twin (page {p}) sits on the "
                "free list — the unmount/spill bookkeeping dropped the "
                "backref and a later prefill would overwrite a page "
                "the tier still advertises as mounted",
                fix="clear the tier's device-twin backref "
                "(HostKVTier.note_unmounted) in the same eviction that "
                "frees the page")
        elif p not in cache:
            bad(f"host entry {key[:12]} records device twin page {p} "
                "but the cache does not track that page (stale "
                "restore backref)")
    return findings


def audit_kv_scale_planes(decoder, pages):
    """MEM-PAGE-REFCOUNT scale-plane consistency audit of a quantized
    KV pool: for every page in `pages` (slot-held or cache-tracked),
    any position holding nonzero quantized bytes must carry a nonzero
    write-time scale.  The write path stores bytes and scale together
    (`serving.decoder._kv_set`) and the floor scale is positive even
    for an all-zero vector, so a written position ALWAYS has scale > 0
    — a zero scale under live bytes means some copy path (typically a
    copy-on-write that moved page bytes but not the scale plane) split
    the two, and the page dequantizes to garbage.  int8 pools carry
    one scale per (layer, pos); int4 pools (uint8 nibble payload) one
    per (layer, pos, group) — there the check demands EVERY group
    scale positive at a written position, since the write quantizes
    all groups together.  Reads the pool from device; audit-time only,
    never on the serving hot path.  Returns Finding list (empty =
    consistent)."""
    import numpy as np
    findings = []
    if not hasattr(decoder, "k_pages"):
        return findings     # a latent pool (PagedMLADecoder): never quantized
    k_pool, v_pool = decoder.k_pages, decoder.v_pages
    if not isinstance(k_pool, tuple):
        return findings                  # unquantized pool: nothing to check
    for name, (page_arr, scale_arr) in (("k", k_pool), ("v", v_pool)):
        pg = np.asarray(page_arr)
        sc = np.asarray(scale_arr)
        for p in pages:
            if pg.dtype == np.uint8:
                # int4: payload [L, ps, PB], scales [L, ps, G]
                wrote = np.abs(pg[:, p].astype(np.int32)).max(axis=-1) > 0
                orphan = wrote & (sc[:, p].min(axis=-1) <= 0.0)
            else:
                # [L, ps]: any head/dim byte live at (layer, position)?
                wrote = np.abs(pg[:, p].astype(np.int32)).max(
                    axis=(-2, -1)) > 0
                orphan = wrote & (sc[:, p] <= 0.0)
            if orphan.any():
                ls, ps_ = np.nonzero(orphan)
                findings.append(Finding(
                    "MEM-PAGE-REFCOUNT", Severity.ERROR,
                    f"{name}-page {p} holds quantized bytes without "
                    f"write-time scales at (layer, pos) "
                    f"{list(zip(ls.tolist(), ps_.tolist()))[:4]}"
                    f"{'...' if orphan.sum() > 4 else ''} — a copy "
                    "moved the page bytes but not the scale plane; "
                    "the page dequantizes to garbage",
                    analyzer="page-refcount",
                    suggested_fix="copy pages through "
                    "PagedGPTDecoder.copy_page (it tree-maps bytes "
                    "AND scale rows together); never copy pool leaves "
                    "individually"))
    return findings


@register_analyzer
class PageRefcountAnalyzer(Analyzer):
    """MEM-PAGE-REFCOUNT: ownership audit of the shared (prefix-cached)
    KV page pool. Runs only when `ctx.extra["page_ledger"]` carries an
    engine ledger — the `gpt_decode_prefix` PROGRAM config commits one
    captured from a real shared-prefix workload, so the CI gate proves
    on every run that refcounted sharing frees every page exactly once
    (the one-horizon-delayed-retirement discipline extended to shared
    pages). Planted-defect tests corrupt a ledger to prove double-free
    / leak / refcount-drift detection."""
    name = "page-refcount"

    def run(self, program, ctx):
        ledger = ctx.extra.get("page_ledger")
        if not ledger:
            self.metrics = {"checked": False}
            return []
        cache = ledger.get("cache") or {}
        host = ledger.get("host") or {}
        self.metrics = {
            "checked": True,
            "n_pages": int(ledger.get("num_pages", 0)),
            "n_free": len(ledger.get("free", [])),
            "n_held": sum(len(p)
                          for p in (ledger.get("slots") or {}).values()),
            "n_cached": len(cache),
            "n_parked": sum(1 for e in cache.values()
                            if not e.get("refs")),
            "refcount_total": sum(int(e.get("refs", 0))
                                  for e in cache.values()),
            # tiered-KV host rows: spilled entries + their bytes (the
            # warm set that survived the HBM cliff)
            "n_host": len(host),
            "host_bytes": sum(int(e.get("bytes", 0))
                              for e in host.values()),
        }
        return audit_page_ledger(ledger)
