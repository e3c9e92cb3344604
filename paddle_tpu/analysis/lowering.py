"""Lowering front-end for the Graph Doctor: turn any nn.Layer or jitted
callable into a `LoweredProgram` — pre-optimization StableHLO text plus
the closed jaxpr — on the CPU platform (chip-independent; no TPU
needed), then give analyzers a cheap structured view of the ops.

The parser is deliberately line-oriented: StableHLO's pretty printer
emits one op per line except for region-carrying generic ops
(all_reduce, reduce, sort, ...), whose type signature lands on the
closing `}) : (...) -> ...` line — those are stitched by brace
balancing. This matches (and replaces) the regex counting the old
tests/test_hlo_regression.py did inline.
"""
import re
from collections import Counter
from dataclasses import dataclass, field

__all__ = ["ArgInfo", "HloOp", "LoweredProgram", "lower_layer",
           "lower_callable", "tensor_type_bytes", "sharding_shard_count",
           "sharding_dim_counts", "spec_dim_axes", "sharding_dim_axes",
           "tree_arg_infos",
           "parse_hlo_sharding", "harvest_hlo_shardings"]

_OP_RE = re.compile(r'"?stablehlo\.([a-zA-Z0-9_]+)"?')
_TENSOR_RE = re.compile(r"tensor<([^>]*)>")
_WEIGHT_TRANSPOSE_RE = re.compile(r"transpose %arg\d+, dims = ")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2,
    "f8E4M3FN": 1, "f8E5M2": 1,
    "i64": 8, "ui64": 8, "i32": 4, "ui32": 4,
    "i16": 2, "ui16": 2, "i8": 1, "ui8": 1, "i1": 1,
    "c64": 8, "c128": 16,
}


def tensor_type_bytes(type_str):
    """Byte size of one `tensor<2x4xf32>`-style type string (0 when the
    element type is unknown or a dim is symbolic)."""
    m = _TENSOR_RE.search(type_str)
    body = m.group(1) if m else type_str
    parts = body.split("x")
    elem = parts[-1]
    n = 1
    for d in parts[:-1]:
        if not d.isdigit():
            return 0
        n *= int(d)
    return n * _DTYPE_BYTES.get(elem, 0)


@dataclass
class ArgInfo:
    """Per-argument metadata of a lowered program's flattened calling
    convention (one entry per %arg of the main function, jaxpr invar
    order). Carries the sharding/donation facts the memory & sharding
    passes need but the HLO text alone can't recover: what the arg IS
    (param vs optimizer slot vs batch), how many shards its sharding
    splits it into, and whether the buffer is donated."""
    name: str                    # pytree path, e.g. "params/fc.weight"
    role: str                    # param|opt_state|gt_state|const|lr|batch|input
    shape: tuple = ()
    dtype: str = ""
    bytes: int = 0               # global (unsharded) size
    spec: tuple = None           # PartitionSpec entries, None when unknown
    shard_count: int = 1         # devices one shard of this arg lands on
    dim_shards: tuple = None     # per-dim shard counts, None when unknown
    donated: bool = False

    @property
    def device_bytes(self):
        """Per-device footprint: global bytes split over the shard count
        (replicated args cost their full size on EVERY device)."""
        return self.bytes // max(self.shard_count, 1)


def sharding_shard_count(sharding):
    """How many ways a NamedSharding/PositionalSharding splits a value
    (1 = fully replicated). Robust to plain specs and None."""
    if sharding is None:
        return 1
    mesh = getattr(sharding, "mesh", None)
    spec = getattr(sharding, "spec", None)
    if mesh is None or spec is None:
        return max(int(getattr(sharding, "num_devices", 1) or 1), 1)
    count = 1
    for entry in spec:
        if entry is None:
            continue
        axes = entry if isinstance(entry, (tuple, list)) else (entry,)
        for a in axes:
            count *= int(mesh.shape.get(a, 1))
    return max(count, 1)


def sharding_dim_counts(sharding, ndim):
    """Per-DIMENSION shard counts of a NamedSharding over an
    `ndim`-rank value, or None when unknown. Feeds the memory pass's
    dim-aware propagation (`memory._eqn_out_shard`): knowing WHICH dim
    carries the sharding lets contracted `dot_general` dims drop their
    factor instead of leaking it into the output."""
    if sharding is None or ndim is None:
        return None
    mesh = getattr(sharding, "mesh", None)
    spec = getattr(sharding, "spec", None)
    if mesh is None or spec is None:
        return None
    dims = [1] * int(ndim)
    for i, entry in enumerate(spec):
        if i >= len(dims) or entry is None:
            continue
        axes = entry if isinstance(entry, (tuple, list)) else (entry,)
        for a in axes:
            dims[i] *= int(mesh.shape.get(a, 1))
    return tuple(dims)


def spec_dim_axes(spec, ndim):
    """Per-dim mesh-axis NAMES from PartitionSpec entries over an
    `ndim`-rank value: a tuple of tuples of axis-name strings (empty
    tuple = the dim is unsharded), or None when the spec itself is
    unknown. The identity half of `sharding_dim_counts` — knowing a
    dim is split 2-ways says how many shards, knowing it is split over
    "dp" says WHICH 2-way split, so two specs naming distinct axes are
    known to compose (their count product is exact, not a cap)."""
    if spec is None or ndim is None:
        return None
    out = [()] * int(ndim)
    for i, entry in enumerate(spec):
        if i >= int(ndim) or entry is None:
            continue
        axes = entry if isinstance(entry, (tuple, list)) else (entry,)
        out[i] = tuple(str(a) for a in axes if a is not None)
    return tuple(out)


def sharding_dim_axes(sharding, ndim):
    """`spec_dim_axes` lifted off a NamedSharding (constraint eqns carry
    one in params["sharding"]); None for shardings without a spec."""
    spec = getattr(sharding, "spec", None)
    if spec is None:
        return None
    return spec_dim_axes(tuple(spec), ndim)


_MHLO_SHARDING_RE = re.compile(r'mhlo\.sharding\s*=\s*"([^"]*)"')
_HLO_TILE_RE = re.compile(r"devices=\[([0-9,]+)\]")
_HLO_SUBGROUP_RE = re.compile(r"last_tile_dims=\{([^}]*)\}")


def parse_hlo_sharding(sharding_str, rank):
    """Per-dim shard counts from an HLO sharding string over a
    `rank`-dim value, or None when unknown/unrepresentable.

    Handles the forms XLA emits in `mhlo.sharding` attrs:
    `{replicated}` and `{maximal device=k}` (one full copy per device
    -> all-ones), `{devices=[2,2]0,1,2,3}` (V1 explicit device list)
    and `{devices=[2,2]<=[4]}` (V2 iota, incl. transposed
    `<=[2,2]T(1,0)` reshapes — the device ASSIGNMENT is irrelevant to
    per-dim counts, only the tile shape matters), with trailing
    replication (`last_tile_dim_replicate`) or subgroup dims
    (`last_tile_dims={...}`) stripped off the tile shape. `{manual}`
    and sdy-dialect attrs return None (counted as unmapped by the
    propagation cross-check)."""
    if sharding_str is None or rank is None:
        return None
    body = sharding_str.strip()
    if body.startswith("{") and body.endswith("}"):
        body = body[1:-1].strip()
    if body.startswith("replicated") or body.startswith("maximal"):
        return (1,) * int(rank)
    m = _HLO_TILE_RE.match(body)
    if m is None:
        return None
    tile = [int(x) for x in m.group(1).split(",") if x]
    sub = _HLO_SUBGROUP_RE.search(body)
    if sub is not None:
        k = len([p for p in sub.group(1).split(",") if p.strip()])
        tile = tile[:len(tile) - k] if k else tile
    elif "last_tile_dim_replicate" in body:
        tile = tile[:-1]
    if len(tile) != int(rank):
        return None
    return tuple(tile)


def harvest_hlo_shardings(text):
    """The per-tensor sharding annotations XLA actually lowered into a
    StableHLO module: `{"args": {argno: raw_string}, "constraints":
    [raw_string_or_None, ...]}`.

    * entry args: `mhlo.sharding` attrs on the `@main` signature
      (paren-balanced, so tensor types and nested attrs don't confuse
      the split);
    * constraints: every `stablehlo.custom_call @Sharding` — the
      lowered form of a `sharding_constraint` eqn — in document order.
      The propagation cross-check matches them to depth-first jaxpr
      eqn order, which coincides for inlined bodies (scan/while lower
      into the same function); constraints inside out-of-line private
      funcs that XLA reordered are caught by the rank sanity check and
      counted unmapped rather than mismatched.

    Raw strings are returned unparsed (sdy attrs included) —
    `parse_hlo_sharding` decides representability."""
    args = {}
    m = re.search(r"@main\s*\(", text)
    if m is not None:
        i, depth, start = m.end(), 1, m.end()
        while i < len(text) and depth:
            c = text[i]
            if c == "(":
                depth += 1
            elif c == ")":
                depth -= 1
            i += 1
        sig = text[start:i - 1]
        arg_marks = list(re.finditer(r"%arg(\d+):", sig))
        for j, am in enumerate(arg_marks):
            seg_end = (arg_marks[j + 1].start()
                       if j + 1 < len(arg_marks) else len(sig))
            sm = _MHLO_SHARDING_RE.search(sig[am.end():seg_end])
            if sm is not None:
                args[int(am.group(1))] = sm.group(1)
    constraints = []
    for line in text.splitlines():
        if "custom_call" in line and "@Sharding" in line:
            sm = _MHLO_SHARDING_RE.search(line)
            constraints.append(sm.group(1) if sm is not None else None)
    return {"args": args, "constraints": constraints}


@dataclass
class HloOp:
    """One stablehlo op occurrence (nested region ops included, matching
    whole-text regex-count semantics)."""
    name: str                    # "dot_general", "all_reduce", ...
    line_no: int                 # 1-based line in the module text
    line: str                    # the op's first line, stripped
    operand_types: list = field(default_factory=list)
    result_types: list = field(default_factory=list)
    attrs: str = ""              # full text slice incl. closing sig line

    @property
    def is_weight_transpose(self):
        """A transpose applied directly to a parameter argument (OIHW->
        HWIO and friends): folds into XLA's free parameter-layout
        assignment, so layout lint must not count it as activation
        traffic. NOTE: textual heuristic only — a program that knows
        which %arg ids are model INPUTS (LoweredProgram.input_arg_ids)
        refines this via LoweredProgram.is_weight_transpose, since an
        input-image transpose is exactly the layout bug to catch."""
        return (self.name == "transpose"
                and _WEIGHT_TRANSPOSE_RE.search(self.line) is not None)

    def arg_operand_id(self):
        """The N of a direct `%argN` first operand, or None."""
        m = re.search(r"transpose %arg(\d+)\b", self.line)
        return int(m.group(1)) if m else None

    def operand_bytes(self):
        return sum(tensor_type_bytes(t) for t in self.operand_types)

    def replica_group_size(self):
        """(group_size, num_groups) from a replica_groups attr, or
        (None, None) when absent."""
        m = re.search(r"replica_groups\s*=\s*dense<(\[\[.*?\]\]|\[\]|"
                      r"[0-9]+)>\s*:\s*tensor<(\d+)x(\d+)", self.attrs,
                      re.S)
        if not m:
            return None, None
        return int(m.group(3)), int(m.group(2))

    def replica_groups(self):
        """The replica_groups device-id lists, e.g. [[0, 2], [1, 3]],
        or None when absent (lets the collective analyzer attribute a
        group to a mesh AXIS by id stride, not just by size — two axes
        of equal size are otherwise indistinguishable)."""
        m = re.search(r"replica_groups\s*=\s*dense<(\[\[.*?\]\])>",
                      self.attrs, re.S)
        if not m:
            return None
        try:
            import json
            return json.loads(m.group(1).replace(" ", "")
                              .replace("\n", ""))
        except ValueError:
            return None


def _split_signature(line):
    """Parse the trailing ` : (operands) -> results` / ` : type` section
    of a one-line op. Returns (operand_types, result_types)."""
    idx = line.rfind(" : ")
    if idx < 0:
        return [], []
    sig = line[idx + 3:]
    if "->" in sig:
        left, right = sig.split("->", 1)
        return _TENSOR_RE.findall(left), _TENSOR_RE.findall(right)
    tys = _TENSOR_RE.findall(sig)
    # shorthand form: operand and result share the type
    return list(tys), list(tys)


def parse_hlo_ops(text):
    """All stablehlo op occurrences in a module's textual form.
    `stablehlo.return` is skipped (region plumbing, not computation)."""
    lines = text.splitlines()
    ops = []
    for i, raw in enumerate(lines):
        m = _OP_RE.search(raw)
        if m is None:
            continue
        name = m.group(1)
        if name == "return":
            continue
        line = raw.strip()
        attrs = line
        if f'"stablehlo.{name}"' in raw:
            # generic (quoted) form: a region op whose type signature is
            # on the closing `}) : ...` line — stitch by brace balance
            depth = raw.count("{") - raw.count("}")
            j = i
            while depth > 0 and j + 1 < len(lines):
                j += 1
                depth += lines[j].count("{") - lines[j].count("}")
            attrs = "\n".join(lines[i:j + 1])
            sig_line = lines[j] if j > i else raw
            operand_types, result_types = _split_signature(sig_line)
        else:
            operand_types, result_types = _split_signature(line)
        ops.append(HloOp(name=name, line_no=i + 1, line=line,
                         operand_types=operand_types,
                         result_types=result_types, attrs=attrs))
    return ops


def tree_arg_infos(tree, role, prefix="", donated=False, shardings=None):
    """Flatten one pytree argument into ArgInfo entries (jaxpr invar
    order). `shardings` is an optional parallel pytree of shardings; a
    leaf's shard count comes from it (or from the value's own committed
    .sharding when absent)."""
    import jax
    import numpy as np
    leaves_p = jax.tree_util.tree_flatten_with_path(tree)[0]
    sh_leaves = (jax.tree_util.tree_leaves(
        shardings, is_leaf=lambda x: x is None)
        if shardings is not None else [None] * len(leaves_p))
    infos = []
    for (path, leaf), sh in zip(leaves_p, sh_leaves):
        name = jax.tree_util.keystr(path).strip("[]'\"").replace(
            "']['", "/").replace("][", "/") or role
        if prefix:
            name = f"{prefix}/{name}" if name != role else prefix
        if sh is None:
            sh = getattr(leaf, "sharding", None)
        shape = tuple(getattr(leaf, "shape", ()))
        dtype = getattr(leaf, "dtype", None)
        itemsize = getattr(dtype, "itemsize", np.dtype(type(leaf)).itemsize
                           if np.isscalar(leaf) else 0)
        spec = getattr(sh, "spec", None)
        infos.append(ArgInfo(
            name=name, role=role, shape=shape,
            dtype=str(dtype) if dtype is not None else "",
            bytes=int(np.prod(shape, dtype=np.int64)) * int(itemsize or 0),
            spec=tuple(spec) if spec is not None else None,
            shard_count=sharding_shard_count(sh),
            dim_shards=sharding_dim_counts(sh, len(shape)),
            donated=donated))
    return infos


class LoweredProgram:
    """StableHLO text + jaxpr of one lowered callable, with a parsed op
    view. `jaxpr` is produced from the same single trace as the HLO (no
    double tracing). `arg_infos`, when given, aligns one ArgInfo with
    each flattened jaxpr invar (sharding + donation capture)."""

    def __init__(self, text, jaxpr=None, name="program", platform="cpu",
                 input_arg_ids=None, arg_infos=None):
        self.text = text
        self.jaxpr = jaxpr
        self.name = name
        self.platform = platform
        # %arg indices of the main function that are model INPUTS (vs
        # parameters/buffers); None when unknown (raw-text programs)
        self.input_arg_ids = (None if input_arg_ids is None
                              else frozenset(input_arg_ids))
        self.arg_infos = arg_infos
        self.ops = parse_hlo_ops(text)

    def is_weight_transpose(self, op):
        """Argument transposes are free parameter-layout moves ONLY for
        parameter args — a transpose of an INPUT arg is real activation
        traffic (the NHWC-defeating bug itself)."""
        if not op.is_weight_transpose:
            return False
        if self.input_arg_ids is None:
            return True
        return op.arg_operand_id() not in self.input_arg_ids

    def ops_named(self, *names):
        wanted = set(names)
        return [op for op in self.ops if op.name in wanted]

    def count(self, op_name):
        return sum(1 for op in self.ops if op.name == op_name)

    @property
    def op_histogram(self):
        return Counter(op.name for op in self.ops)

    def activation_transposes(self):
        return [op for op in self.ops
                if op.name == "transpose"
                and not self.is_weight_transpose(op)]

    def __repr__(self):
        return (f"LoweredProgram({self.name!r}, {len(self.ops)} ops, "
                f"{len(self.text.splitlines())} lines)")


def _untensor(tree):
    from ..framework.core import Tensor
    import jax
    return jax.tree_util.tree_map(
        lambda t: t._value if isinstance(t, Tensor) else t, tree,
        is_leaf=lambda t: isinstance(t, Tensor))


def lower_callable(fn, *example_args, name="program", input_arg_ids=None,
                   arg_infos=None, in_shardings=None):
    """Trace `fn` once; return StableHLO + jaxpr as a LoweredProgram.
    `in_shardings` (a per-arg tuple of sharding pytrees, None entries =
    unspecified) threads into `jax.jit` so the lowered text carries real
    `mhlo.sharding` annotations, and seeds the auto-built ArgInfos'
    dim_shards — the propagation pass's cross-check needs both sides."""
    import jax
    jitted = (jax.jit(fn, in_shardings=in_shardings)
              if in_shardings is not None else jax.jit(fn))
    traced = jitted.trace(*example_args)
    if arg_infos is None:
        arg_infos = []
        shardings = (in_shardings if in_shardings is not None
                     else [None] * len(example_args))
        for i, (a, sh) in enumerate(zip(example_args, shardings)):
            arg_infos.extend(tree_arg_infos(a, "input", prefix=f"arg{i}",
                                            shardings=sh))
    return LoweredProgram(traced.lower().as_text(), jaxpr=traced.jaxpr,
                          name=name, input_arg_ids=input_arg_ids,
                          arg_infos=arg_infos)


def lower_layer(model, *example_arrays, name=None):
    """Lower a Layer's forward (functional form: params/buffers as
    arguments) at the given example inputs — the same pure-call shape
    the Trainer and jit.save use, so lint sees the graph that ships."""
    from ..framework.core import Tensor
    from ..nn.layer_base import (buffer_pytree, functional_call,
                                 state_pytree)
    params = state_pytree(model)
    params.update(buffer_pytree(model))

    def pure(p, *args):
        with functional_call(model, p):
            out = model(*[Tensor(a) for a in args])
        return _untensor(out)

    # flattened calling convention: params-dict leaves first, then the
    # example arrays — so the inputs are the TRAILING %arg ids, letting
    # the layout analyzer tell a free param-layout transpose from an
    # input-activation transpose
    import jax
    n_params = len(jax.tree_util.tree_leaves(params))
    n_inputs = len(jax.tree_util.tree_leaves(list(example_arrays)))
    infos = tree_arg_infos(params, "param")
    for i, a in enumerate(example_arrays):
        infos.extend(tree_arg_infos(a, "input", prefix=f"input{i}"))
    return lower_callable(
        pure, params, *example_arrays,
        name=name or type(model).__name__,
        input_arg_ids=range(n_params, n_params + n_inputs),
        arg_infos=infos)
