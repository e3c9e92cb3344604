"""Determinism Doctor — static proof obligations for the serving
runtime's byte-identical-stream invariant.

Every serving feature since the paged decoder landed — prefix-cache
CoW, tiered spill/restore, preemption-and-resume, multi-LoRA, packed
ragged horizons — is safe only because of two purity facts the fuzz
tests enforce dynamically:

  * a KV page's bytes are a pure function of (request, position);
  * a sampled stream is a pure function of (seed, rid, position).

This pass proves the *index side* of those facts statically with a
taint-provenance dataflow over the lowered jaxpr (recursing into
scan/while/cond/pjit bodies the way schedule.py and propagation.py
walk them).  Every value is classified against a provenance lattice:

  request-intrinsic   "rid"      sampling-key ids / request ids
                      "position" sequence positions, lengths, starts
                      "prompt"   the request's own token bytes
                      "seed"     explicit seed/key arguments
  layout-tainted      "iota"     batch order / slot index / tick index
                                 (anything minted by an iota)
                      "table"    page-table row order and row routing
  request-extrinsic   "draft"    a speculative draft model's proposals
  constant            {}         consts, params, config scalars

Taints are seeded from the serving capture's `ArgInfo` names/roles and
propagated forward through every equation (union of operand taints)
with ONE deliberate exemption: `select_n` drops its *predicate* taint
and unions only the branch taints.  That is what keeps the committed
programs green through the scratch routing they all share —
`where(done, scratch_page, pids)` routes frozen rows to the reserved
scratch page, and the *routing decision* (batch-composition-dependent)
never contaminates the *canonical index* a live row writes to.

Rules (catalog rows in docs/static_analysis.md):

  KV-WRITE-NONCANONICAL  a scatter into a pool-role buffer whose page
                         index does not route through the page TABLE
                         (or a constant scratch page), or whose
                         in-page offset carries no POSITION
                         provenance — a resume/restore/CoW replay
                         would reproduce different bytes.  Also fires
                         when the written *values* carry "draft"
                         provenance: the speculative verify window
                         writes draft-model bytes into real pages
                         before acceptance (the documented expected
                         red; commit-on-accept must turn it green).
  RNG-KEY-TAINT          an RNG eqn whose key derivation folds in
                         anything beyond (seed, rid, position) — the
                         sampled stream would depend on batch
                         composition or table layout.
  SCATTER-WRITE-OVERLAP  two scatters into the SAME pool buffer
                         within one loop/tick body whose index sets
                         cannot be proven disjoint (disjoint static
                         windows, same-page disjoint offsets, or
                         distinct row-id provenance through the same
                         table) — the device-side write-write race
                         the scratch routing exists to prevent.
  DONATE-HOST-ALIAS      a donated argument (or a pure view of one)
                         is returned as an output — the host may
                         still hold the donated buffer while XLA
                         reuses it (the PR-4/PR-13 segfault class).

`DeterminismAnalyzer` wires the walk into the Graph Doctor catalog;
metrics feed determinism_manifests/<config>.json for the serving
PROGRAM configs (see manifest.py / baseline.DETERMINISM_CONFIGS).
"""
import re
from dataclasses import dataclass, field
from itertools import combinations

from .findings import Finding, Severity
from .memory import (_SCATTER_PRIMS, _eqn_source, _is_var, _sub_jaxprs,
                     kv_cache_infos)
from .pass_manager import Analyzer, register_analyzer

__all__ = ["DeterminismResult", "analyze_determinism",
           "DeterminismAnalyzer", "REQUEST_TAGS", "LAYOUT_TAGS",
           "RNG_ALLOWED_TAGS"]

# the provenance lattice's named classes
REQUEST_TAGS = frozenset({"seed", "rid", "position", "prompt"})
LAYOUT_TAGS = frozenset({"iota", "table"})
# a sampled stream must be a pure function of (seed, rid, position)
RNG_ALLOWED_TAGS = frozenset({"seed", "rid", "position"})

# arg-name (last path component) -> lattice class.  First match wins;
# args matching nothing get a private "arg:<name>" tag so foreign
# provenance is never silently laundered into "constant".
_TAG_PATTERNS = (
    ("rid", re.compile(r"^(kids?|rids?|request(_ids?)?)$")),
    ("position", re.compile(
        r"^(lens?|pos|positions?|starts?|true_len|sample_pos|last_idx|"
        r"remaining|pend_n)$")),
    ("prompt", re.compile(r"^(tokens?|ptok|ids|pend|prompts?|eos)$")),
    ("seed", re.compile(r"^(seeds?|keys?|rng(_keys?)?)$")),
    ("table", re.compile(r"^(tables?|rows?)$")),
    ("draft", re.compile(r"^(draft(_tokens?)?|proposals?)$")),
)

# every primitive of the PRNG lowering families (old-style threefry and
# typed-key random_*): the key-taint rule inspects all of them, so a
# forbidden fold is caught whichever layer it enters at
_RNG_PRIMS = frozenset({
    "threefry2x32", "random_bits", "random_fold_in", "random_seed",
    "random_wrap", "random_unwrap", "random_gamma", "random_clone"})

# shape-only ops a pool buffer's identity survives (buffer roots)
_VIEW_PRIMS = frozenset({
    "reshape", "transpose", "squeeze", "copy", "broadcast_in_dim",
    "convert_element_type"})
# byte-preserving views only: the donation-alias chain
_ALIAS_PRIMS = frozenset({"reshape", "transpose", "squeeze", "copy"})
# wrappers stripped when chasing an index operand to its producer
_STRIP_PRIMS = frozenset({
    "reshape", "broadcast_in_dim", "convert_element_type", "squeeze",
    "copy"})

_EMPTY = frozenset()
_MAX_LOOP_SWEEPS = 16


def _unclosed(j):
    return j.jaxpr if hasattr(j, "jaxpr") else j


@dataclass
class _WriteSite:
    """One scatter into a pool-role buffer."""
    eqn: object
    idx: int
    source: str
    root: str                    # pool buffer name (arg name)
    group: int                   # id() of the enclosing jaxpr body


@dataclass
class DeterminismResult:
    findings: list = field(default_factory=list)
    metrics: dict = field(default_factory=dict)

    def by_rule(self, rule_id):
        return [f for f in self.findings if f.rule_id == rule_id]

    def summary(self):
        m = self.metrics
        return (f"{m.get('n_pool_writes', 0)} pool write(s) "
                f"({m.get('n_canonical_writes', 0)} canonical), "
                f"{m.get('n_rng_sites', 0)} rng site(s), "
                f"{m.get('n_overlap_pairs', 0)} overlap pair(s) "
                f"({m.get('n_proven_disjoint', 0)} proven disjoint), "
                f"{len(self.findings)} finding(s)")


class _TaintEngine:
    """Forward taint/range/buffer-identity dataflow over one jaxpr,
    monotone in the taint lattice (sets only grow), so the scan/while
    carry fixed points terminate."""

    def __init__(self):
        self.taints = {}         # var -> set of tags
        self.roots = {}          # var -> pool buffer name
        self.alias = {}          # var -> donated arg name (view chain)
        self.ranges = {}         # var -> (lo, hi) static int range
        self.defs = {}           # var -> defining eqn
        self.writes = {}         # id(eqn) -> _WriteSite (insertion order)
        self.rng_sites = {}      # id(eqn) -> (eqn, source)
        self.eqn_ids = set()

    # ---------------------------------------------------- lattice ops

    def taint(self, v):
        if not _is_var(v):
            return _EMPTY
        return self.taints.get(v, _EMPTY)

    def _add(self, v, tags):
        if not _is_var(v) or not tags:
            return False
        cur = self.taints.get(v)
        if cur is None:
            self.taints[v] = set(tags)
            return True
        if tags <= cur:
            return False
        cur |= tags
        return True

    def _set_root(self, v, root):
        if not _is_var(v) or root is None or v in self.roots:
            return False
        self.roots[v] = root
        return True

    def _set_alias(self, v, name):
        if not _is_var(v) or name is None or v in self.alias:
            return False
        self.alias[v] = name
        return True

    def rangeof(self, v):
        if not _is_var(v):
            val = getattr(v, "val", None)
            try:
                iv = int(val)
                return (iv, iv)
            except (TypeError, ValueError, OverflowError):
                return None
        return self.ranges.get(v)

    def _set_range(self, v, r):
        # write-once: ranges are not monotone (a carry feedback would
        # widen forever), so the first — pre-feedback — value sticks
        if r is None or not _is_var(v) or v in self.ranges:
            return False
        self.ranges[v] = (int(r[0]), int(r[1]))
        return True

    # ------------------------------------------------------ the sweep

    def sweep(self, jx):
        changed = False
        for idx, eqn in enumerate(jx.eqns):
            changed |= self._transfer(jx, idx, eqn)
        return changed

    def _transfer(self, jx, idx, eqn):
        prim = eqn.primitive.name
        self.eqn_ids.add(id(eqn))
        for o in eqn.outvars:
            if _is_var(o):
                self.defs.setdefault(o, eqn)
        if prim == "scan":
            return self._scan(eqn)
        if prim == "while":
            return self._while(eqn)
        if prim == "cond":
            return self._cond(eqn)
        subs = _sub_jaxprs(eqn)
        if subs:
            return self._call(eqn, subs)

        ins = [self.taint(v) for v in eqn.invars]
        if prim == "select_n" and len(ins) > 1:
            # the predicate picks WHICH branch's bytes flow, it never
            # writes bytes itself: scratch routing / freeze masks stay
            # out of the canonical-index provenance (documented
            # approximation — a data-dependent SELECT of two indexes
            # is judged by the indexes, not the mask)
            out = set().union(*ins[1:])
        elif prim == "iota":
            out = {"iota"}
        else:
            out = set().union(*ins) if ins else set()

        changed = False
        for o in eqn.outvars:
            changed |= self._add(o, out)

        if prim in _RNG_PRIMS:
            self.rng_sites[id(eqn)] = (eqn, _eqn_source(eqn, idx))

        if prim in _SCATTER_PRIMS and eqn.invars and \
                _is_var(eqn.invars[0]):
            root = self.roots.get(eqn.invars[0])
            if root is not None:
                for o in eqn.outvars:
                    changed |= self._set_root(o, root)
                self.writes.setdefault(
                    id(eqn),
                    _WriteSite(eqn, idx, _eqn_source(eqn, idx), root,
                               id(jx)))
        elif prim in _VIEW_PRIMS and eqn.invars and \
                _is_var(eqn.invars[0]) and len(eqn.outvars) == 1:
            changed |= self._set_root(eqn.outvars[0],
                                      self.roots.get(eqn.invars[0]))
            if prim in _ALIAS_PRIMS:
                changed |= self._set_alias(eqn.outvars[0],
                                           self.alias.get(eqn.invars[0]))

        self._range_transfer(prim, eqn)
        return changed

    # ------------------------------------------------ static ranges

    def _range_transfer(self, prim, eqn):
        o = eqn.outvars[0] if eqn.outvars else None
        if o is None or not _is_var(o):
            return
        if prim == "iota":
            shape = eqn.params.get("shape") or getattr(
                getattr(o, "aval", None), "shape", None)
            d = int(eqn.params.get("dimension", 0) or 0)
            if shape and d < len(shape):
                self._set_range(o, (0, max(int(shape[d]) - 1, 0)))
            return
        rs = [self.rangeof(v) for v in eqn.invars]
        if prim == "add" and len(rs) == 2 and all(rs):
            self._set_range(o, (rs[0][0] + rs[1][0],
                                rs[0][1] + rs[1][1]))
        elif prim == "sub" and len(rs) == 2 and all(rs):
            self._set_range(o, (rs[0][0] - rs[1][1],
                                rs[0][1] - rs[1][0]))
        elif prim == "mul" and len(rs) == 2 and all(rs):
            cs = [a * b for a in rs[0] for b in rs[1]]
            self._set_range(o, (min(cs), max(cs)))
        elif prim == "min" and len(rs) == 2 and all(rs):
            self._set_range(o, (min(rs[0][0], rs[1][0]),
                                min(rs[0][1], rs[1][1])))
        elif prim == "max" and len(rs) == 2 and all(rs):
            self._set_range(o, (max(rs[0][0], rs[1][0]),
                                max(rs[0][1], rs[1][1])))
        elif prim == "rem" and len(rs) == 2 and all(rs) and \
                rs[1][0] == rs[1][1] and rs[1][0] > 0 and rs[0][0] >= 0:
            self._set_range(o, (0, min(rs[0][1], rs[1][0] - 1)))
        elif prim == "div" and len(rs) == 2 and all(rs) and \
                rs[1][0] == rs[1][1] and rs[1][0] > 0 and rs[0][0] >= 0:
            n = rs[1][0]
            self._set_range(o, (rs[0][0] // n, rs[0][1] // n))
        elif prim == "clamp" and len(rs) == 3 and all(rs):
            lo, x, hi = rs
            self._set_range(o, (max(lo[0], min(x[0], hi[1])),
                                max(lo[0], min(x[1], hi[1]))))
        elif prim == "concatenate" and rs and all(rs):
            self._set_range(o, (min(r[0] for r in rs),
                                max(r[1] for r in rs)))
        elif prim in ("lt", "le", "gt", "ge") and len(rs) == 2 and \
                all(rs):
            # statically-decided comparisons collapse the `.at[]`
            # negative-index normalization (select_n(lt(i, 0), i,
            # i + n)) back to the live branch
            (alo, ahi), (blo, bhi) = rs
            swap = prim in ("gt", "ge")
            if swap:
                (alo, ahi), (blo, bhi) = (blo, bhi), (alo, ahi)
            strict = prim in ("lt", "gt")
            if (ahi < blo) if strict else (ahi <= blo):
                self._set_range(o, (1, 1))
            elif (alo >= bhi) if strict else (alo > bhi):
                self._set_range(o, (0, 0))
            else:
                self._set_range(o, (0, 1))
        elif prim == "select_n" and len(rs) > 1:
            if rs[0] == (0, 0) and rs[1] is not None:
                self._set_range(o, rs[1])
            elif rs[0] == (1, 1) and len(rs) > 2 and rs[2] is not None:
                self._set_range(o, rs[2])
            elif all(rs[1:]):
                self._set_range(o, (min(r[0] for r in rs[1:]),
                                    max(r[1] for r in rs[1:])))
        elif prim in _STRIP_PRIMS or prim == "transpose":
            if rs and rs[0]:
                self._set_range(o, rs[0])

    # ----------------------------------------------- call boundaries

    def _map_in(self, outer, inner, carry_range=True, with_alias=False):
        changed = self._add(inner, self.taint(outer))
        if _is_var(outer):
            changed |= self._set_root(inner, self.roots.get(outer))
            if with_alias:
                changed |= self._set_alias(inner,
                                           self.alias.get(outer))
        if carry_range:
            changed |= self._set_range(inner, self.rangeof(outer))
        return changed

    def _map_out(self, inner, outer, with_alias=False,
                 carry_range=True):
        changed = self._add(outer, self.taint(inner))
        if _is_var(inner):
            changed |= self._set_root(outer, self.roots.get(inner))
            if with_alias:
                changed |= self._set_alias(outer,
                                           self.alias.get(inner))
            if carry_range:
                changed |= self._set_range(outer, self.rangeof(inner))
        return changed

    def _fixpoint(self, body, feedback):
        """Sweep `body` until the taint state stops changing, feeding
        carry outvars back into carry invars between sweeps."""
        changed = False
        for _ in range(_MAX_LOOP_SWEEPS):
            c = self.sweep(body)
            for src, dst in feedback:
                c |= self._add(dst, self.taint(src))
                if _is_var(src):
                    c |= self._set_root(dst, self.roots.get(src))
            changed |= c
            if not c:
                break
        return changed

    def _scan(self, eqn):
        body = _unclosed(eqn.params["jaxpr"])
        nc = int(eqn.params.get("num_consts", 0))
        ncar = int(eqn.params.get("num_carry", 0))
        ivs = list(eqn.invars)
        changed = False
        for i, iv in enumerate(ivs):
            if i >= len(body.invars):
                break
            # carry ranges are not stable across ticks (lens += 1);
            # consts and xs slices keep theirs
            changed |= self._map_in(
                iv, body.invars[i],
                carry_range=not (nc <= i < nc + ncar),
                with_alias=nc <= i < nc + ncar)
        feedback = [(body.outvars[i], body.invars[nc + i])
                    for i in range(ncar)
                    if i < len(body.outvars)
                    and nc + i < len(body.invars)]
        changed |= self._fixpoint(body, feedback)
        for i, ov in enumerate(eqn.outvars):
            if i >= len(body.outvars):
                break
            changed |= self._map_out(body.outvars[i], ov,
                                     with_alias=i < ncar,
                                     carry_range=i >= ncar)
        return changed

    def _while(self, eqn):
        cn = int(eqn.params.get("cond_nconsts", 0))
        bn = int(eqn.params.get("body_nconsts", 0))
        cond = _unclosed(eqn.params["cond_jaxpr"])
        body = _unclosed(eqn.params["body_jaxpr"])
        ivs = list(eqn.invars)
        changed = False
        for i in range(min(cn, len(cond.invars))):
            changed |= self._map_in(ivs[i], cond.invars[i])
        for i in range(min(bn, len(body.invars))):
            changed |= self._map_in(ivs[cn + i], body.invars[i])
        ncar = len(ivs) - cn - bn
        for i in range(ncar):
            ov = ivs[cn + bn + i]
            if bn + i < len(body.invars):
                changed |= self._map_in(ov, body.invars[bn + i],
                                        carry_range=False,
                                        with_alias=True)
            if cn + i < len(cond.invars):
                changed |= self._map_in(ov, cond.invars[cn + i],
                                        carry_range=False)
        feedback = [(body.outvars[i], body.invars[bn + i])
                    for i in range(min(ncar, len(body.outvars)))
                    if bn + i < len(body.invars)]
        changed |= self._fixpoint(body, feedback)
        changed |= self.sweep(cond)
        for i, ov in enumerate(eqn.outvars):
            if i < len(body.outvars):
                changed |= self._map_out(body.outvars[i], ov,
                                         with_alias=True,
                                         carry_range=False)
        return changed

    def _cond(self, eqn):
        branches = [_unclosed(b)
                    for b in eqn.params.get("branches", ())]
        ivs = list(eqn.invars)[1:]          # drop the branch index
        changed = False
        for br in branches:
            for ov, bv in zip(ivs, br.invars):
                changed |= self._map_in(ov, bv)
            changed |= self.sweep(br)
        for i, ov in enumerate(eqn.outvars):
            tags = set()
            for br in branches:
                if i < len(br.outvars):
                    tags |= self.taint(br.outvars[i])
                    changed |= self._set_root(
                        ov, self.roots.get(br.outvars[i])
                        if _is_var(br.outvars[i]) else None)
            changed |= self._add(ov, tags)
        return changed

    def _call(self, eqn, subs):
        changed = False
        for sub in subs:
            if len(sub.invars) == len(eqn.invars) and \
                    len(sub.outvars) == len(eqn.outvars):
                for ov, bv in zip(eqn.invars, sub.invars):
                    changed |= self._map_in(ov, bv, with_alias=True)
                changed |= self.sweep(sub)
                for bv, ov in zip(sub.outvars, eqn.outvars):
                    changed |= self._map_out(bv, ov, with_alias=True)
            else:
                changed |= self.sweep(sub)
        return changed

    # ------------------------------------------- index introspection

    def strip(self, v):
        for _ in range(32):
            if not _is_var(v):
                return v
            e = self.defs.get(v)
            if e is None or e.primitive.name not in _STRIP_PRIMS or \
                    not e.invars or not _is_var(e.invars[0]):
                return v
            v = e.invars[0]
        return v

    def index_components(self, idx_var):
        """The per-operand-dim index columns of a scatter's indices
        operand, when it is structurally a `concatenate` of broadcast
        columns (the `.at[li, pids, offs].set` lowering); None
        otherwise. Column order follows `scatter_dims_to_operand_dims`,
        so for pool buffers the last column is the in-page OFFSET, the
        one before it the PAGE id (`_page_column`), and what comes
        before those the LAYER of the whole [L, P, ps, ...] pool (no
        such column where a program writes one layer's slice)."""
        v = self.strip(idx_var)
        e = self.defs.get(v) if _is_var(v) else None
        if e is not None and e.primitive.name == "concatenate":
            return [self.strip(iv) for iv in e.invars]
        return None


# ------------------------------------------------------------ seeding


def _arg_tag(name):
    base = (name or "").split("/")[-1].split(".")[-1].lower()
    for tag, pat in _TAG_PATTERNS:
        if pat.match(base):
            return tag
    return f"arg:{base}" if base else None


def _seed(program):
    """(jaxpr, engine, donated) — taints from ArgInfo names/roles, pool
    buffer roots from `kv_cache_infos` (ONE cache definition shared
    with the memory pass), donation aliases, and integer const
    ranges."""
    import numpy as np
    jxc = program.jaxpr
    jx = _unclosed(jxc)
    infos = list(getattr(program, "arg_infos", None) or [])
    cache_ids = {id(i) for i in kv_cache_infos(infos)}
    eng = _TaintEngine()
    donated = []
    for k, v in enumerate(jx.invars):
        info = infos[k] if k < len(infos) else None
        if info is None:
            continue
        if getattr(info, "donated", False):
            name = info.name or f"arg{k}"
            donated.append(name)
            eng._set_alias(v, name)
        if id(info) in cache_ids:
            eng._set_root(v, info.name or f"arg{k}")
        elif info.role not in ("param", "opt_state", "gt_state",
                               "const", "lr"):
            tag = _arg_tag(info.name) or f"arg:{k}"
            eng._add(v, {tag})
    consts = list(getattr(jxc, "consts", None) or [])
    for cv, cval in zip(jx.constvars, consts):
        try:
            a = np.asarray(cval)
            if a.dtype.kind in "iu" and 0 < a.size <= (1 << 22):
                eng._set_range(cv, (int(a.min()), int(a.max())))
        except Exception:
            pass
    return jx, eng, donated


# ------------------------------------------------------- rule checks


def _const_only(tags):
    """Purely constant-derived: no request, layout, or foreign arg
    provenance at all (the scratch-page literal qualifies; an iota
    does not — it mints the "iota" tag)."""
    return not tags


def _page_column(comps):
    """The PAGE-id column of a pool scatter's index columns: the one
    before the offset (a lone column is the page)."""
    return comps[-2] if len(comps) >= 2 else comps[0]


def _check_kv_write(eng, site, findings):
    """KV-WRITE-NONCANONICAL for one pool scatter.  Returns True when
    the write is canonical."""
    eqn = site.eqn
    idx_op = eqn.invars[1] if len(eqn.invars) > 1 else None
    upd_op = eqn.invars[2] if len(eqn.invars) > 2 else None
    problems = []
    comps = eng.index_components(idx_op) if idx_op is not None else None
    if comps and len(comps) >= 2:
        page_t = eng.taint(_page_column(comps))
        off_t = eng.taint(comps[-1])
        for layer in comps[:-2]:
            # the layer loop's own counter (an iota) or a constant:
            # which layer is written is the program's, never a
            # request's or the table's
            layer_t = eng.taint(layer)
            if layer_t - {"iota"}:
                problems.append(
                    f"layer index carries {sorted(layer_t)}: only the "
                    "layer loop's counter may pick the layer")
        if "table" not in page_t and not _const_only(page_t):
            problems.append(
                f"page index carries {sorted(page_t)} without routing "
                "through the page table (or a constant scratch page)")
        if "position" not in off_t and not _const_only(off_t):
            problems.append(
                f"in-page offset carries {sorted(off_t)} with no "
                "POSITION provenance")
    elif idx_op is not None:
        t = eng.taint(idx_op)
        if not _const_only(t) and \
                not ("table" in t and "position" in t):
            problems.append(
                f"write index carries {sorted(t)} — canonical pool "
                "indexing derives the page from the TABLE and the "
                "offset from the POSITION")
    if upd_op is not None and "draft" in eng.taint(upd_op):
        problems.append(
            "written values carry DRAFT provenance: speculative "
            "proposals land in real pages before acceptance (the "
            "verify-window expected red — commit-on-accept turns "
            "this green)")
    for p in problems:
        findings.append(Finding(
            "KV-WRITE-NONCANONICAL", Severity.ERROR,
            f"{site.source} writes pool buffer '{site.root}' but {p} "
            "— a resume/restore/CoW replay of this request would "
            "reproduce different page bytes",
            op=site.source,
            suggested_fix="derive the page id from the request's page "
            "table row and the offset from its sequence position; "
            "route masked/frozen rows to the reserved scratch page "
            "instead of folding layout into the index"))
    return not problems


def _ranges_disjoint(a, b):
    return a is not None and b is not None and \
        (a[1] < b[0] or b[1] < a[0])


def _page_operand(eng, site):
    eqn = site.eqn
    idx_op = eqn.invars[1] if len(eqn.invars) > 1 else None
    if idx_op is None:
        return None
    comps = eng.index_components(idx_op)
    return _page_column(comps) if comps else eng.strip(idx_op)


def _offset_operand(eng, site):
    comps = eng.index_components(site.eqn.invars[1]) \
        if len(site.eqn.invars) > 1 else None
    return comps[-1] if comps and len(comps) >= 2 else None


def _proven_disjoint(eng, a, b):
    """Three provers, any one suffices:
    (1) disjoint static page windows; (2) the same page-id vector with
    disjoint static offsets; (3) distinct row-id provenance — both
    page ids gathered from the SAME table with disjoint static gather
    windows."""
    pa, pb = _page_operand(eng, a), _page_operand(eng, b)
    if pa is None or pb is None:
        return False
    if _ranges_disjoint(eng.rangeof(pa), eng.rangeof(pb)):
        return True
    if pa is pb:
        oa, ob = _offset_operand(eng, a), _offset_operand(eng, b)
        if oa is not None and ob is not None and \
                _ranges_disjoint(eng.rangeof(oa), eng.rangeof(ob)):
            return True
    ga = eng.defs.get(pa) if _is_var(pa) else None
    gb = eng.defs.get(pb) if _is_var(pb) else None
    if ga is not None and gb is not None and \
            ga.primitive.name == "gather" and \
            gb.primitive.name == "gather" and \
            len(ga.invars) > 1 and len(gb.invars) > 1 and \
            ga.invars[0] is gb.invars[0]:
        ra = eng.rangeof(eng.strip(ga.invars[1]))
        rb = eng.rangeof(eng.strip(gb.invars[1]))
        if _ranges_disjoint(ra, rb):
            return True
    return False


# ------------------------------------------------------- entry point


def analyze_determinism(program, ctx=None):
    """Run the full determinism dataflow over one `LoweredProgram` and
    evaluate every rule.  Deterministic: one cached CPU trace walks to
    the same fixed point on every machine."""
    jx, eng, donated = _seed(program)
    for _ in range(_MAX_LOOP_SWEEPS):
        if not eng.sweep(jx):
            break

    res = DeterminismResult()
    findings = res.findings

    # rule 1: canonical pool writes (+ the draft-value expected red)
    n_canonical = 0
    sites = list(eng.writes.values())
    for site in sites:
        if _check_kv_write(eng, site, findings):
            n_canonical += 1

    # rule 2: RNG key provenance
    for eqn, source in eng.rng_sites.values():
        tags = set()
        for v in eqn.invars:
            tags |= eng.taint(v)
        extra = tags - RNG_ALLOWED_TAGS
        if extra:
            findings.append(Finding(
                "RNG-KEY-TAINT", Severity.ERROR,
                f"{source} folds {sorted(extra)} into a sampling key "
                "— the stream would depend on batch composition or "
                "table layout, not only on (seed, rid, position)",
                op=source,
                suggested_fix="derive every per-request key as "
                "fold_in(fold_in(PRNGKey(seed), rid), position); "
                "never fold slot indexes, batch order, or table rows"))

    # rule 3: write-write overlap inside one loop/tick body
    groups = {}
    for site in sites:
        groups.setdefault((site.root, site.group), []).append(site)
    n_pairs = n_proven = 0
    for (root, _gid), group in sorted(
            groups.items(), key=lambda kv: (kv[0][0], kv[0][1])):
        for a, b in combinations(group, 2):
            n_pairs += 1
            if _proven_disjoint(eng, a, b):
                n_proven += 1
                continue
            findings.append(Finding(
                "SCATTER-WRITE-OVERLAP", Severity.ERROR,
                f"two scatters into pool buffer '{root}' in one body "
                f"({a.source} and {b.source}) have index sets that "
                "cannot be proven disjoint — a device-side "
                "write-write race; which bytes land is "
                "schedule-dependent",
                op=f"{a.source} / {b.source}",
                suggested_fix="give each writer its own page window, "
                "route one side to the scratch page, or key both "
                "through disjoint rows of the page table"))

    # rule 4: donated buffer aliased straight to an output
    n_alias = 0
    for ov in jx.outvars:
        if _is_var(ov) and ov in eng.alias:
            n_alias += 1
            findings.append(Finding(
                "DONATE-HOST-ALIAS", Severity.ERROR,
                f"donated argument '{eng.alias[ov]}' is returned as "
                "an output without an intervening defining write — "
                "the host still holds the donated buffer while XLA "
                "reuses it (the PR-4/PR-13 segfault class)",
                op=eng.alias[ov],
                suggested_fix="drop the donation for pass-through "
                "leaves, or materialize the output with an actual "
                "update (scatter/dynamic_update_slice) so XLA emits "
                "a fresh buffer"))

    findings.sort(key=lambda f: (f.rule_id, f.op or "", f.message))
    rules = {}
    for f in findings:
        rules[f.rule_id] = rules.get(f.rule_id, 0) + 1
    res.metrics = {
        "n_eqns": len(eng.eqn_ids),
        "n_pool_buffers": len({s.root for s in sites})
        if sites else len(kv_cache_infos(
            list(getattr(program, "arg_infos", None) or []))),
        "n_pool_writes": len(sites),
        "n_canonical_writes": n_canonical,
        "n_rng_sites": len(eng.rng_sites),
        "n_overlap_pairs": n_pairs,
        "n_proven_disjoint": n_proven,
        "n_donated_args": len(donated),
        "n_alias_outputs": n_alias,
        "rules": rules,
    }
    return res


@register_analyzer
class DeterminismAnalyzer(Analyzer):
    """Determinism Doctor graph pass: taint-provenance dataflow +
    KV-WRITE-NONCANONICAL / RNG-KEY-TAINT / SCATTER-WRITE-OVERLAP /
    DONATE-HOST-ALIAS (rule docs in the module docstring and
    docs/static_analysis.md).  Metrics feed
    determinism_manifests/<config>.json for the serving PROGRAM
    configs."""
    name = "determinism"

    def run(self, program, ctx):
        if getattr(program, "jaxpr", None) is None:
            self.metrics = {"available": False}
            return []
        res = analyze_determinism(program, ctx)
        self.metrics = {"available": True, **res.metrics}
        return res.findings
