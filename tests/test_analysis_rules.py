"""Planted-defect proofs for the Graph Doctor rules: each test builds a
program WITH a known performance defect and asserts the right analyzer
catches it (and that the healthy twin stays clean) — the acceptance
bar for trusting the lint gate's green.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.analysis import (AnalysisContext, LoweredProgram,
                                 PassManager, Severity, lower_callable,
                                 lower_layer)
from paddle_tpu.distributed import build_mesh
from paddle_tpu.framework.core import apply_op


def _graph_pm():
    return PassManager(["layout", "dtype", "host-transfer",
                        "graph-shape", "collective"])


# ---------------------------------------------------------------- layout

class _ConvNet(nn.Layer):
    """NHWC conv stack; with `defect` an NCHW round-trip is planted
    between the convs (the exact pattern that cost ~15x on ResNet)."""

    def __init__(self, defect):
        super().__init__()
        self.c1 = nn.Conv2D(3, 8, 3, padding=1, data_format="NHWC")
        self.c2 = nn.Conv2D(8, 8, 3, padding=1, data_format="NHWC")
        self._defect = defect

    def forward(self, x):
        x = self.c1(x)
        if self._defect:
            x = apply_op(lambda v: jnp.transpose(v, (0, 3, 1, 2)), x)
            x = apply_op(lambda v: jnp.transpose(v, (0, 2, 3, 1)), x)
        return self.c2(x)


def test_layout_rule_catches_planted_body_transpose():
    paddle.seed(0)
    build_mesh(dp=1)
    x = jnp.zeros((2, 16, 16, 3), jnp.float32)
    ctx = AnalysisContext(name="convnet", data_format="NHWC")

    clean = _graph_pm().run(lower_layer(_ConvNet(False), x), ctx)
    assert clean.by_rule("LAYOUT-ACT-TRANSPOSE") == []

    bad = _graph_pm().run(lower_layer(_ConvNet(True), x), ctx)
    hits = bad.by_rule("LAYOUT-ACT-TRANSPOSE")
    assert len(hits) == 2, [str(f) for f in bad.findings]
    assert all(f.severity == Severity.ERROR for f in hits)
    assert "NHWC" in hits[0].suggested_fix


class _InputTransposeNet(nn.Layer):
    """The sneakiest layout defect: transposing the INPUT image itself.
    In the lowered functional form the input is also a %arg, so a
    naive applied-to-%arg exemption would misread it as a free weight-
    layout move — the program's input_arg_ids must catch it."""

    def __init__(self):
        super().__init__()
        self.c1 = nn.Conv2D(3, 8, 3, padding=1, data_format="NHWC")

    def forward(self, x):
        x = apply_op(lambda v: jnp.transpose(v, (0, 2, 1, 3)), x)
        return self.c1(x)


def test_layout_rule_catches_input_arg_transpose():
    paddle.seed(0)
    build_mesh(dp=1)
    x = jnp.zeros((2, 16, 16, 3), jnp.float32)
    program = lower_layer(_InputTransposeNet(), x)
    assert program.input_arg_ids, "lower_layer lost input arg tracking"
    report = _graph_pm().run(program, AnalysisContext(
        name="input_t", data_format="NHWC"))
    hits = report.by_rule("LAYOUT-ACT-TRANSPOSE")
    assert hits and hits[0].severity == Severity.ERROR, \
        [str(f) for f in report.findings]
    # the jit front door sees it too: to_static(lint=True) must thread
    # input arg ids through to the same classification
    paddle.seed(0)
    sf = paddle.jit.to_static(_InputTransposeNet(), lint=True)
    with pytest.warns(UserWarning):
        sf(paddle.to_tensor(np.zeros((2, 16, 16, 3), "float32")))
    assert sf.lint_report.by_rule("LAYOUT-ACT-TRANSPOSE")


# ----------------------------------------------------------------- dtype

class _MatNet(nn.Layer):
    """bf16 linear; the defect runs the matmul in f32 via a raw jnp op
    (the amp_compute_cast rule would neutralize a plain astype before
    nn.Linear — which is itself worth knowing: the planted defect must
    bypass amp exactly like a hand-rolled kernel would)."""

    def __init__(self, defect):
        super().__init__()
        self.fc = nn.Linear(16, 16)
        self._defect = defect

    def forward(self, x):
        if self._defect:
            return apply_op(
                lambda v, w: (v.astype(jnp.float32)
                              @ w.astype(jnp.float32)),
                x, self.fc.weight)
        return self.fc(x)


def test_dtype_rule_catches_planted_f32_upcast():
    paddle.seed(0)
    build_mesh(dp=1)
    ctx = AnalysisContext(name="matnet", policy_dtype="bfloat16")
    x = jnp.zeros((4, 16), jnp.bfloat16)

    clean_model = _MatNet(False)
    clean_model.bfloat16()
    clean = _graph_pm().run(lower_layer(clean_model, x), ctx)
    assert clean.by_rule("DTYPE-F32-MATMUL") == []

    bad_model = _MatNet(True)
    bad_model.bfloat16()
    bad = _graph_pm().run(lower_layer(bad_model, x), ctx)
    hits = bad.by_rule("DTYPE-F32-MATMUL")
    # the planted upcast promotes the matmul: amp_compute_cast would
    # normally down-cast, so the defect plants the cast INSIDE the op's
    # operand set — at least the poisoned dot must be flagged
    assert hits, [str(f) for f in bad.findings]
    assert all(f.severity == Severity.ERROR for f in hits)


def test_dtype_rule_honors_router_exemption():
    """An f32 dot is an ERROR unless the context's f32_dot_allow
    blesses it (the MoE router rule)."""
    def f(x, w):
        return x.astype(jnp.float32) @ w.astype(jnp.float32)

    program = lower_callable(f, jnp.zeros((4, 8), jnp.bfloat16),
                             jnp.zeros((8, 4), jnp.bfloat16),
                             name="router")
    strict = _graph_pm().run(program, AnalysisContext(
        policy_dtype="bfloat16"))
    assert strict.by_rule("DTYPE-F32-MATMUL")
    lax_ctx = AnalysisContext(policy_dtype="bfloat16",
                              f32_dot_allow=lambda op: True)
    blessed = _graph_pm().run(program, lax_ctx)
    assert blessed.by_rule("DTYPE-F32-MATMUL") == []
    assert blessed.by_rule("DTYPE-F32-ALLOWED")


# --------------------------------------------------------- host transfer

def test_host_transfer_rule_catches_debug_callback():
    def bad(x):
        jax.debug.print("x={x}", x=x)
        return x * 2

    program = lower_callable(bad, jnp.zeros((4,)), name="cb")
    report = _graph_pm().run(program, AnalysisContext())
    hits = report.by_rule("HOST-CALLBACK")
    assert hits and hits[0].severity == Severity.ERROR
    assert report.metrics["host-transfer"]["n_host_callbacks"] >= 1

    def clean(x):
        return x * 2

    report = _graph_pm().run(lower_callable(clean, jnp.zeros((4,))),
                             AnalysisContext())
    assert report.by_rule("HOST-CALLBACK") == []


# ----------------------------------------------------------- graph shape

def test_graph_shape_rule_catches_opcount_and_double_forward():
    def once(x, w):
        return x @ w

    def twice(x, w):
        # the duplicate-forward defect: the same matmul materialized
        # twice (lost CSE / broken remat shows up exactly like this)
        return x @ w + jnp.sin(x @ w + 1.0)

    args = (jnp.zeros((4, 8)), jnp.zeros((8, 4)))
    p1 = lower_callable(once, *args, name="once")
    p2 = lower_callable(twice, *args, name="twice")

    ok = _graph_pm().run(p1, AnalysisContext(
        expected_counts={"dot_general": 1}))
    assert ok.by_rule("GRAPH-OPCOUNT-DRIFT") == []

    drift = _graph_pm().run(p2, AnalysisContext(
        expected_counts={"dot_general": 1}))
    assert drift.by_rule("GRAPH-OPCOUNT-DRIFT")

    # manifest drift + the doubled-MXU-op heuristic
    manifest = {"op_counts": {"dot_general": 1}}
    rep = _graph_pm().run(p2, AnalysisContext(manifest=manifest))
    assert rep.by_rule("GRAPH-MANIFEST-DRIFT")
    assert rep.by_rule("GRAPH-DOUBLE-FORWARD")


# ------------------------------------------------------------ collective

def test_collective_rule_counts_payload_and_cross_checks_cost_model():
    from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.cost_model import collective_wire_bytes

    n_dev = len(jax.devices())
    mesh = build_mesh(dp=n_dev)   # conftest pins an 8-device CPU mesh

    def allreduce(x):
        return jax.lax.psum(x, "dp")

    fn = shard_map(allreduce, mesh=mesh, in_specs=P("dp"),
                   out_specs=P())
    program = lower_callable(fn, jnp.zeros((n_dev, 4), jnp.float32),
                             name="psum")
    report = _graph_pm().run(program, AnalysisContext(
        mesh_axes={"dp": n_dev}))
    coll = report.metrics["collective"]
    assert coll["n_collectives"] == 1
    entry = coll["collectives"][0]
    assert entry["op"] == "all_reduce"
    # per-shard payload: 1x4 f32 = 16 bytes
    assert entry["payload_bytes"] == 16
    assert entry["group_size"] == n_dev
    assert entry["wire_bytes"] == collective_wire_bytes(
        "all_reduce", 16, n_dev)
    assert entry["mesh_axis"] == "dp"
    assert report.metrics["collective"]["per_mesh_axis"]["dp"]["count"] == 1
    # tiny payload -> bucketing advice
    assert report.by_rule("COLL-TINY-PAYLOAD")

    # the same program pinned single-device is an ERROR
    pinned = _graph_pm().run(program, AnalysisContext(
        expect_collectives=False))
    assert pinned.by_rule("COLL-UNEXPECTED")
    assert pinned.errors

    # all_gather: the OPERAND is the 1/n shard but the ring moves
    # (n-1)/n of the FULL gathered payload — the analyzer must feed the
    # result (full) size into the cost model, not the shard size
    def gather(x):
        return jax.lax.all_gather(x, "dp")

    g_fn = shard_map(gather, mesh=mesh, in_specs=P("dp"),
                     out_specs=P("dp"))
    g_prog = lower_callable(g_fn, jnp.zeros((n_dev, 4), jnp.float32),
                            name="gather")
    g_rep = _graph_pm().run(g_prog, AnalysisContext())
    entries = [e for e in g_rep.metrics["collective"]["collectives"]
               if e["op"] == "all_gather"]
    assert entries, g_rep.metrics["collective"]
    e = entries[0]
    full = n_dev * 4 * 4          # gathered [n_dev, 4] f32
    assert e["wire_bytes"] == collective_wire_bytes(
        "all_gather", full, n_dev) == int(full * (n_dev - 1) / n_dev)


def test_collective_axis_attribution_disambiguates_equal_sizes():
    """On a square mesh two axes share a group SIZE; only the device-id
    stride of the replica groups tells them apart — tp (innermost,
    stride 1) vs dp (stride = tp size)."""
    from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P

    mesh = build_mesh(dp=2, tp=2, devices=jax.devices()[:4])

    def body(x):
        a = jax.lax.psum(x, "tp")
        return jax.lax.psum(a, "dp")

    fn = shard_map(body, mesh=mesh, in_specs=P("dp", "tp"),
                   out_specs=P())
    program = lower_callable(fn, jnp.zeros((4, 8), jnp.float32),
                             name="square")
    report = _graph_pm().run(program, AnalysisContext(
        mesh_axes={"dp": 2, "tp": 2}))
    axes = [e["mesh_axis"] for e in
            report.metrics["collective"]["collectives"]]
    assert sorted(a for a in axes if a) == ["dp", "tp"], (
        axes, report.metrics["collective"]["collectives"])


def test_collective_wire_bytes_model():
    from paddle_tpu.cost_model import collective_wire_bytes
    # ring all-reduce moves 2(n-1)/n of the payload per device
    assert collective_wire_bytes("all_reduce", 1024, 8) == \
        int(1024 * 2 * 7 / 8)
    assert collective_wire_bytes("all_gather", 1024, 8) == \
        int(1024 * 7 / 8)
    assert collective_wire_bytes("all_reduce", 1024, 1) == 0


def test_collective_wire_bytes_edge_cases_and_aliases():
    """Degenerate groups are free; reduce_scatter/all_to_all have ring
    formulas; jaxpr primitive names alias to their HLO collectives so
    the sharding pass can price every collective either walk emits."""
    from paddle_tpu.cost_model import collective_wire_bytes as w
    # group_size=1 (or absent/invalid) folds to a copy: zero wire bytes
    assert w("all_gather", 4096, 1) == 0
    assert w("reduce_scatter", 4096, None) == 0
    assert w("all_to_all", 4096, 0) == 0
    assert w("all_reduce", 0, 8) == 0
    assert w("all_reduce", None, 8) == 0
    # full-payload ring formulas
    assert w("reduce_scatter", 4096, 8) == int(4096 * 7 / 8)
    assert w("all_to_all", 4096, 8) == int(4096 * 7 / 8)
    assert w("collective_permute", 4096, 8) == 4096
    # jaxpr-name aliases agree with their HLO lowerings
    assert w("psum", 4096, 8) == w("all_reduce", 4096, 8)
    assert w("ppermute", 4096, 8) == w("collective_permute", 4096, 8)
    assert w("psum_scatter", 4096, 8) == w("reduce_scatter", 4096, 8)


# -------------------------------------------------------------- sharding

def _info(name, role, shape, shard_count, itemsize=4):
    import numpy as np
    from paddle_tpu.analysis import ArgInfo
    return ArgInfo(name=name, role=role, shape=tuple(shape),
                   dtype="float32",
                   bytes=int(np.prod(shape)) * itemsize,
                   shard_count=shard_count)


def _sharding_pm():
    return PassManager(["sharding"])


def test_sharding_rule_catches_replicated_param_under_fsdp():
    """A big replicated param on an fsdp mesh is the ZeRO promise broken
    — ERROR; the sharded twin stays clean."""
    program = LoweredProgram("", name="synthetic")
    ctx = AnalysisContext(name="synthetic", mesh_axes={"fsdp": 8})

    program.arg_infos = [_info("w", "param", (1024, 1024), 1)]
    bad = _sharding_pm().run(program, ctx)
    hits = bad.by_rule("SHARD-REPLICATED-BIG")
    assert hits and hits[0].severity == Severity.ERROR
    assert bad.metrics["sharding"]["n_replicated_big"] == 1

    program.arg_infos = [_info("w", "param", (1024, 1024), 8)]
    clean = _sharding_pm().run(program, ctx)
    assert clean.by_rule("SHARD-REPLICATED-BIG") == []
    # small replicated tensors never fire (below the threshold)
    program.arg_infos = [_info("b", "param", (128,), 1)]
    small = _sharding_pm().run(program, ctx)
    assert small.by_rule("SHARD-REPLICATED-BIG") == []
    # replication under a dp-only mesh is by design — no finding
    program.arg_infos = [_info("w", "param", (1024, 1024), 1)]
    dp_only = _sharding_pm().run(
        program, AnalysisContext(mesh_axes={"dp": 8}))
    assert dp_only.by_rule("SHARD-REPLICATED-BIG") == []


def test_sharding_rule_catches_unsharded_opt_state():
    """Optimizer slots replicated while their same-shape param is
    sharded: the silent 2-3x HBM leak the ZeRO configs exist to kill."""
    program = LoweredProgram("", name="synthetic")
    ctx = AnalysisContext(name="synthetic", mesh_axes={"fsdp": 8})

    program.arg_infos = [
        _info("w", "param", (1024, 1024), 8),
        _info("slots/w/moment1", "opt_state", (1024, 1024), 1),
    ]
    bad = _sharding_pm().run(program, ctx)
    hits = bad.by_rule("SHARD-OPT-STATE-UNSHARDED")
    assert hits and hits[0].severity == Severity.ERROR
    assert "moment1" in hits[0].message

    program.arg_infos = [
        _info("w", "param", (1024, 1024), 8),
        _info("slots/w/moment1", "opt_state", (1024, 1024), 8),
    ]
    clean = _sharding_pm().run(program, ctx)
    assert clean.by_rule("SHARD-OPT-STATE-UNSHARDED") == []


def test_sharding_rule_catches_mid_program_reshard():
    """A planted ppermute lowers to collective_permute — the signature
    of a GSPMD spec mismatch; the exemption regex silences by-design
    dispatch."""
    from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P

    n_dev = len(jax.devices())
    mesh = build_mesh(dp=n_dev)

    def shift(x):
        perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]
        return jax.lax.ppermute(x, "dp", perm)

    fn = shard_map(shift, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"))
    program = lower_callable(fn, jnp.zeros((n_dev, 8), jnp.float32),
                             name="shift")
    report = _sharding_pm().run(program, AnalysisContext(
        mesh_axes={"dp": n_dev}))
    hits = report.by_rule("SHARD-MID-PROGRAM-RESHARD")
    assert hits and hits[0].severity == Severity.WARNING
    assert report.metrics["sharding"]["n_mid_program_reshards"] == 1

    blessed = _sharding_pm().run(program, AnalysisContext(
        mesh_axes={"dp": n_dev},
        allowed_resharding=(r"collective_permute",)))
    assert blessed.by_rule("SHARD-MID-PROGRAM-RESHARD") == []

    # a collective-free program never fires
    clean_prog = lower_callable(lambda x: x * 2,
                                jnp.zeros((8,), jnp.float32))
    clean = _sharding_pm().run(clean_prog, AnalysisContext())
    assert clean.by_rule("SHARD-MID-PROGRAM-RESHARD") == []


def test_sharding_rule_catches_wire_byte_regression():
    """Total analytic wire bytes above the committed memory manifest's
    pin is an ERROR (a collective grew or a new one appeared)."""
    from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.cost_model import collective_wire_bytes

    n_dev = len(jax.devices())
    mesh = build_mesh(dp=n_dev)

    def allreduce(x):
        return jax.lax.psum(x, "dp")

    fn = shard_map(allreduce, mesh=mesh, in_specs=P("dp"), out_specs=P())
    program = lower_callable(fn, jnp.zeros((n_dev, 1024), jnp.float32),
                             name="psum")
    fresh = _sharding_pm().run(program, AnalysisContext())
    wire = fresh.metrics["sharding"]["total_wire_bytes"]
    # per-shard [1,1024] f32 is both operand and result of the psum
    assert wire == collective_wire_bytes("all_reduce", 1024 * 4, n_dev)

    # committed manifest pinned half the volume -> regression fires
    ctx = AnalysisContext(memory_manifest={
        "collectives": {"total_wire_bytes": wire // 2}})
    bad = _sharding_pm().run(program, ctx)
    assert bad.by_rule("SHARD-WIRE-REGRESSION")
    # pinned at the current volume -> clean
    ctx = AnalysisContext(memory_manifest={
        "collectives": {"total_wire_bytes": wire}})
    ok = _sharding_pm().run(program, ctx)
    assert ok.by_rule("SHARD-WIRE-REGRESSION") == []


# ----------------------------------------------------- jit / to_static

def test_to_static_lint_populates_report(tmp_path):
    """to_static(lint=True): graph findings appear on .lint_report after
    the first call (the planted f32 upcast is visible through the jit
    wrapper too)."""
    paddle.seed(0)
    build_mesh(dp=1)
    model = _MatNet(True)
    model.bfloat16()
    sf = paddle.jit.to_static(model, lint=True)
    with pytest.warns(UserWarning):
        sf(paddle.to_tensor(np.zeros((4, 16), "float32")).astype(
            "bfloat16"))
    assert sf.lint_report is not None
    assert sf.lint_report.by_rule("DTYPE-F32-MATMUL")


def test_debug_diagnose_entry_point():
    paddle.seed(0)
    build_mesh(dp=1)
    model = _ConvNet(True)
    report = paddle.debug.diagnose(
        model, jnp.zeros((2, 16, 16, 3), jnp.float32),
        context=AnalysisContext(name="convnet", data_format="NHWC"),
        print_report=False)
    assert report.by_rule("LAYOUT-ACT-TRANSPOSE")


# -------------------------------------------------- serving decode loop

def test_serving_rule_catches_undonated_cache_in_fused_loop():
    """SERVE-HOST-SYNC-DECODE planted defect: the fused decode_multi
    program with cache donation dropped (analysis_program(donate=False,
    k=...)) is an ERROR — every K-tick horizon would copy the whole
    paged KV store. The real capture (donated) stays clean, and the
    rule is scoped: without extra["serving_decode"] it never fires."""
    from paddle_tpu.models import GPT, gpt_tiny
    from paddle_tpu.serving import PagedGPTDecoder

    paddle.seed(0)
    build_mesh(dp=1)
    model = GPT(gpt_tiny(max_seq_len=64, dtype="float32", remat=False))
    model.eval()
    dec = PagedGPTDecoder(model, num_pages=8, page_size=16, max_batch=2)
    pm = PassManager(["serving"])
    ctx = AnalysisContext(name="decode", extra={"serving_decode": True})

    good = dec.analysis_program(donate=True, k=2)
    report = pm.run(good, ctx)
    assert report.by_rule("SERVE-HOST-SYNC-DECODE") == []
    assert report.metrics["serving"]["cache_donated"]
    assert report.metrics["serving"]["n_device_loops"] >= 1

    bad = dec.analysis_program(donate=False, k=2)
    report2 = pm.run(bad, ctx)
    hits = report2.by_rule("SERVE-HOST-SYNC-DECODE")
    assert hits and hits[0].severity == Severity.ERROR
    assert "KV-cache" in hits[0].message

    # scope: the same defective program outside a serving context is
    # not this rule's business (MEM-NO-DONATION-KVCACHE still warns)
    report3 = pm.run(bad, AnalysisContext(name="decode"))
    assert report3.by_rule("SERVE-HOST-SYNC-DECODE") == []
    assert report3.metrics["serving"] == {"checked": False}


def test_serving_rule_catches_host_callback_in_fused_loop():
    """A host callback smuggled into a device-resident decode loop is
    the per-tick round-trip the fused program exists to kill."""
    def fused_loop_with_callback(tokens, k_pages):
        def tick(carry, _):
            t, kp = carry
            jax.debug.print("tick {t}", t=t)     # the planted defect
            t = t + 1
            kp = kp + 1.0
            return (t, kp), t
        (tokens, k_pages), _ = jax.lax.scan(
            tick, (tokens, k_pages), jnp.arange(4))
        return tokens, k_pages

    program = lower_callable(fused_loop_with_callback,
                             jnp.zeros((2,), jnp.int32),
                             jnp.zeros((4, 8), jnp.float32),
                             name="decode_multi")
    pm = PassManager(["serving"])
    ctx = AnalysisContext(name="decode", extra={"serving_decode": True})
    report = pm.run(program, ctx)
    hits = report.by_rule("SERVE-HOST-SYNC-DECODE")
    assert hits and any("host transfer" in h.message for h in hits)
    assert report.metrics["serving"]["n_host_transfers"] >= 1

    def clean_loop(tokens, k_pages):
        def tick(carry, _):
            t, kp = carry
            return (t + 1, kp + 1.0), t
        (tokens, k_pages), _ = jax.lax.scan(
            tick, (tokens, k_pages), jnp.arange(4))
        return tokens, k_pages

    clean = lower_callable(clean_loop, jnp.zeros((2,), jnp.int32),
                           jnp.zeros((4, 8), jnp.float32),
                           name="decode_multi")
    report2 = pm.run(clean, ctx)
    # name-matched k_pages arg is undonated in this raw capture — only
    # the cache finding may fire, never a host-transfer one
    assert all("KV-cache" in h.message
               for h in report2.by_rule("SERVE-HOST-SYNC-DECODE"))
    assert report2.metrics["serving"]["n_host_transfers"] == 0


def test_roofline_drift_rule_planted_mispricing():
    """ROOFLINE-DRIFT planted defect: a drift report whose measured
    horizon times track the priced roofline audits clean; a
    deliberately MISPRICED dispatch shape (measured 10x the priced
    max(compute, HBM, wire)) is the silent-scheduling-error class and
    an ERROR; an overpriced shape (capacity left idle) is a WARNING.
    Without extra["roofline_drift"] the rule never fires."""
    program = lower_callable(lambda x: x + 1.0,
                             jnp.zeros((2,), jnp.float32), name="decode")
    pm = PassManager(["roofline-drift"])

    def entry(shape, pred, meas, n=8):
        return {"shape": list(shape), "n": n, "predicted_s": pred,
                "measured_s": meas, "ratio": meas / pred}

    clean = [entry(("ragged", 8, 16), 1e-3, 1.4e-3),
             entry(("decode", 8, 1), 8e-4, 9e-4),
             # under the sample floor: one cold tick is noise
             entry(("ragged", 1, 1), 1e-3, 99.0, n=1)]
    report = pm.run(program, AnalysisContext(
        name="s", extra={"roofline_drift": clean}))
    assert report.by_rule("ROOFLINE-DRIFT") == []
    m = report.metrics["roofline-drift"]
    assert m["checked"] and m["n_checked"] == 2 and m["n_over"] == 0

    planted = clean + [entry(("ragged", 8, 64), 1e-3, 1e-2)]
    report2 = pm.run(program, AnalysisContext(
        name="s", extra={"roofline_drift": planted}))
    hits = report2.by_rule("ROOFLINE-DRIFT")
    assert hits and hits[0].severity == Severity.ERROR
    assert "ragged" in hits[0].message and "10.0x over" in hits[0].message
    assert report2.metrics["roofline-drift"]["n_over"] == 1

    # overpriced: schedulable capacity left on the table -> WARNING
    over = [entry(("train", 4), 1e-2, 1e-3)]
    report3 = pm.run(program, AnalysisContext(
        name="s", extra={"roofline_drift": over}))
    hits3 = report3.by_rule("ROOFLINE-DRIFT")
    assert hits3 and hits3[0].severity == Severity.WARNING
    assert "UNDER" in hits3[0].message

    # the factor is configurable: the same mispriced shape passes a
    # loose factor
    report4 = pm.run(program, AnalysisContext(
        name="s", extra={"roofline_drift": planted, "drift_factor": 20}))
    assert report4.by_rule("ROOFLINE-DRIFT") == []

    # scope: no drift report on the context -> not this rule's business
    report5 = pm.run(program, AnalysisContext(name="s"))
    assert report5.by_rule("ROOFLINE-DRIFT") == []
    assert report5.metrics["roofline-drift"] == {"checked": False}


def test_roofline_drift_fires_on_live_recorder_ledger():
    """The rule consumes exactly what the flight recorder emits: a
    FlightRecorder fed a mispriced dispatch (tick_complete measured far
    over predicted_s) produces a drift_report() the analyzer flags,
    red→green once the pricing is fixed."""
    from paddle_tpu.serving import FlightRecorder
    program = lower_callable(lambda x: x + 1.0,
                             jnp.zeros((2,), jnp.float32), name="decode")
    pm = PassManager(["roofline-drift"])

    def ledger(pred):
        rec = FlightRecorder()
        for _ in range(4):
            rec.tick("serve", ("ragged", 4, 8), measured_s=4e-3,
                     predicted_s=pred, k=4, w=8)
        return rec.drift_report()

    bad = pm.run(program, AnalysisContext(
        name="s", extra={"roofline_drift": ledger(1e-4)}))
    assert bad.by_rule("ROOFLINE-DRIFT"), "mispriced ledger not caught"
    good = pm.run(program, AnalysisContext(
        name="s", extra={"roofline_drift": ledger(3e-3)}))
    assert good.by_rule("ROOFLINE-DRIFT") == []


def test_prefill_stall_rule_audits_schedule_trace():
    """SERVE-PREFILL-STALL planted defect: a scheduling trace whose
    prompts all streamed in as horizon chunks (or whose only blocking
    prefill found an idle batch) audits clean; a host-blocking prefill
    dispatched while decode slots were live is the stall and an ERROR.
    Without extra["serve_schedule"] the rule never fires."""
    program = lower_callable(lambda x: x + 1.0,
                             jnp.zeros((2,), jnp.float32), name="decode")
    pm = PassManager(["prefill-stall"])
    clean = [
        {"kind": "horizon", "k": 4, "w": 8, "decode_rows": 1,
         "prefill_rows": 1},
        {"kind": "horizon", "k": 8, "w": 1, "decode_rows": 2,
         "prefill_rows": 0},
        # a blocking prefill into an EMPTY batch stalls nobody — the
        # cold-start case every engine pays once
        {"kind": "prefill_sync", "decode_active": 0, "rows": 2},
    ]
    report = pm.run(program, AnalysisContext(
        name="s", extra={"serve_schedule": clean}))
    assert report.by_rule("SERVE-PREFILL-STALL") == []
    m = report.metrics["prefill-stall"]
    assert m["checked"] and m["n_mixed_horizons"] == 1
    assert m["n_stalled_prefill_syncs"] == 0

    planted = clean + [{"kind": "prefill_sync", "decode_active": 3,
                        "rows": 1}]
    report2 = pm.run(program, AnalysisContext(
        name="s", extra={"serve_schedule": planted}))
    hits = report2.by_rule("SERVE-PREFILL-STALL")
    assert hits and hits[0].severity == Severity.ERROR
    assert "3 running decode slot" in hits[0].message
    assert report2.metrics["prefill-stall"]["n_stalled_prefill_syncs"] == 1

    # scope: no trace on the context -> not this rule's business
    report3 = pm.run(program, AnalysisContext(name="s"))
    assert report3.by_rule("SERVE-PREFILL-STALL") == []
    assert report3.metrics["prefill-stall"] == {"checked": False}


def test_prefill_stall_traces_from_real_engines():
    """The engines emit the traces the rule audits: the dispatch-
    separate baseline admitting a prompt while another slot decodes
    logs a stalled prefill_sync (the rule fires on its trace); the
    ragged engine's trace for the same workload has chunked horizons
    and audits clean."""
    from paddle_tpu.models import GPT, gpt_tiny
    from paddle_tpu.serving import ContinuousBatchingEngine, PagedGPTDecoder

    paddle.seed(3)
    build_mesh(dp=1)
    model = GPT(gpt_tiny(max_seq_len=64, dtype="float32", remat=False))
    model.eval()
    pm = PassManager(["prefill-stall"])
    program = lower_callable(lambda x: x + 1.0,
                             jnp.zeros((2,), jnp.float32), name="decode")

    # the canonical stall, staged deterministically on the blocking
    # path: one slot is mid-decode when a long prompt arrives and its
    # whole prefill dispatches as ONE blocking forward
    dec = PagedGPTDecoder(model, num_pages=16, page_size=16, max_batch=2)
    base = ContinuousBatchingEngine(dec, max_new_tokens=10, k_max=1)
    base.submit(np.asarray([1, 2, 3], np.int32))
    base.step()
    base.step()                      # slot 0 decoding
    base.submit(np.asarray(list(range(1, 25)), np.int32))
    base.step()                      # blocking prefill, decode live
    report = pm.run(program, AnalysisContext(
        name="s", extra={"serve_schedule": base.serve_schedule()}))
    assert report.by_rule("SERVE-PREFILL-STALL"), \
        base.serve_schedule()
    assert base.stats.prefill_stall_syncs >= 1

    def run(ragged):
        dec = PagedGPTDecoder(model, num_pages=16, page_size=16,
                              max_batch=2)
        eng = ContinuousBatchingEngine(dec, max_new_tokens=10, k_max=4,
                                       ragged=ragged, chunk_tokens=8)
        for p in ([1, 2, 3], list(range(1, 25)), [7, 8]):
            eng.submit(np.asarray(p, np.int32))
        eng.run()
        return eng

    ragged = run(ragged=True)
    report2 = pm.run(program, AnalysisContext(
        name="s", extra={"serve_schedule": ragged.serve_schedule()}))
    assert report2.by_rule("SERVE-PREFILL-STALL") == [], \
        ragged.serve_schedule()
    m = report2.metrics["prefill-stall"]
    assert m["n_prefill_syncs"] == 0 and m["n_mixed_horizons"] >= 1
    assert ragged.stats.prefill_syncs == 0
    assert ragged.stats.prefill_stall_syncs == 0


# ---------------------------------------------- fused multi-step training


def _tiny_trainer(donate=True):
    from paddle_tpu.distributed.trainer import Trainer

    paddle.seed(0)
    build_mesh(dp=1)
    model = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))

    def loss_fn(m, b):
        return ((m(paddle.to_tensor(b["x"]))) ** 2).mean()

    opt = paddle.optimizer.AdamW(learning_rate=1e-3)
    return Trainer(model, opt, loss_fn, donate=donate)


def test_training_rule_clean_on_real_fused_step():
    """The REAL Trainer.step_multi capture (analysis_program(n=4)) is
    fully device-resident: zero host transfers, donated carry, the N
    ticks lowered to a device loop."""
    tr = _tiny_trainer()
    batch = {"x": np.zeros((4, 8), np.float32)}
    program = tr.analysis_program(batch, n=4)
    pm = PassManager(["training"])
    ctx = AnalysisContext(name="train", extra={"train_multi": True})
    report = pm.run(program, ctx)
    assert report.by_rule("HOST-SYNC-TRAIN") == [], \
        [str(f) for f in report.findings]
    m = report.metrics["training"]
    assert m["checked"] and m["carry_donated"]
    assert m["n_host_transfers"] == 0
    assert m["n_device_loops"] >= 1

    # scope: the same program outside a train-multi context never fires
    report2 = pm.run(program, AnalysisContext(name="train"))
    assert report2.by_rule("HOST-SYNC-TRAIN") == []
    assert report2.metrics["training"] == {"checked": False}


def test_training_rule_catches_host_fetch_in_scan_body():
    """HOST-SYNC-TRAIN planted defect: a host callback smuggled into the
    fused train scan is the per-step round-trip the device-resident
    horizon exists to kill."""
    def fused_with_callback(params, batches):
        def tick(p, b):
            loss = ((b @ p) ** 2).mean()
            jax.debug.print("loss {l}", l=loss)     # the planted defect
            return p - 0.1 * b.T @ (b @ p), loss
        params, losses = jax.lax.scan(tick, params, batches)
        return params, losses

    program = lower_callable(fused_with_callback,
                             jnp.zeros((8, 4), jnp.float32),
                             jnp.zeros((4, 2, 8), jnp.float32),
                             name="train_multi")
    pm = PassManager(["training"])
    ctx = AnalysisContext(name="train", extra={"train_multi": True})
    report = pm.run(program, ctx)
    hits = report.by_rule("HOST-SYNC-TRAIN")
    assert hits and any("host transfer" in h.message for h in hits)
    assert all(h.severity == Severity.ERROR for h in hits)
    assert report.metrics["training"]["n_host_transfers"] >= 1

    def clean(params, batches):
        def tick(p, b):
            loss = ((b @ p) ** 2).mean()
            return p - 0.1 * b.T @ (b @ p), loss
        return jax.lax.scan(tick, params, batches)

    program2 = lower_callable(clean, jnp.zeros((8, 4), jnp.float32),
                              jnp.zeros((4, 2, 8), jnp.float32),
                              name="train_multi")
    report2 = pm.run(program2, ctx)
    assert report2.by_rule("HOST-SYNC-TRAIN") == []
    assert report2.metrics["training"]["n_host_transfers"] == 0


def test_training_rule_catches_undonated_carry():
    """Trainer(donate=False)'s fused capture double-buffers the whole
    model state every horizon — an ERROR in the hot loop (the MEM-NO-
    DONATION warning composes the same way SERVE-HOST-SYNC-DECODE
    composes with MEM-NO-DONATION-KVCACHE)."""
    tr = _tiny_trainer(donate=False)
    batch = {"x": np.zeros((4, 8), np.float32)}
    program = tr.analysis_program(batch, n=4)
    pm = PassManager(["training"])
    ctx = AnalysisContext(name="train", extra={"train_multi": True})
    report = pm.run(program, ctx)
    hits = report.by_rule("HOST-SYNC-TRAIN")
    assert hits and hits[0].severity == Severity.ERROR
    assert any("not donated" in h.message for h in hits)
    assert not report.metrics["training"]["carry_donated"]


# ----------------------------------------------------- page refcounts


def _consistent_ledger():
    """8-page pool, scratch=7: pages 0-1 free, slot 0 holds [2, 3]
    with 2 cache-shared (refs 1), slot 1 holds [4, 5], page 6 parked
    (refcount 0) in the cache. Host-tier rows (tiered KV): one
    host-only spilled entry, and one restored entry whose device twin
    is the parked page 6."""
    return {"num_pages": 8, "scratch": 7, "free": [0, 1],
            "slots": {0: [2, 3], 1: [4, 5]},
            "shared": {0: [2]},
            "cache": {2: {"refs": 1, "parked": False},
                      6: {"refs": 0, "parked": True}},
            "host": {"aa01": {"bytes": 4096, "page": None},
                     "bb02": {"bytes": 4096, "page": 6}}}


def test_page_refcount_rule_clean_on_consistent_ledger():
    """MEM-PAGE-REFCOUNT stays silent when every allocatable page is
    owned exactly once (free XOR slot-held XOR parked), and is scoped:
    without extra["page_ledger"] the analyzer never fires."""
    pm = PassManager(["page-refcount"])
    prog = LoweredProgram("", name="ledger")
    ctx = AnalysisContext(name="ledger",
                          extra={"page_ledger": _consistent_ledger()})
    report = pm.run(prog, ctx)
    assert report.by_rule("MEM-PAGE-REFCOUNT") == [], str(report)
    m = report.metrics["page-refcount"]
    assert m["checked"] and m["n_pages"] == 8
    assert m["n_cached"] == 2 and m["n_parked"] == 1
    assert m["refcount_total"] == 1
    assert m["n_host"] == 2 and m["host_bytes"] == 8192
    # scope: no ledger -> not this analyzer's business
    report2 = pm.run(prog, AnalysisContext(name="ledger"))
    assert report2.metrics["page-refcount"] == {"checked": False}


@pytest.mark.parametrize("mutate, expect", [
    # double free: a page returned to the pool twice
    (lambda lg: lg["free"].append(0), "twice in the free list"),
    # double free: freed while a slot still holds it
    (lambda lg: lg["free"].append(3), "both free and held"),
    # double free: evicted page returned to free without unmapping
    (lambda lg: lg["free"].append(6), "both free and cache-tracked"),
    # leak: a held page vanishes from every ledger column
    (lambda lg: lg["slots"][1].remove(5), "leak"),
    # refcount drift: cache thinks two holders, only one slot mounts it
    (lambda lg: lg["cache"][2].update(refs=2), "refcount drift"),
    # aliasing: two slots hold one page with no covering refcount
    (lambda lg: lg["slots"][1].append(3), "unaccounted aliasing"),
    # shared-marked page the cache never tracked
    (lambda lg: lg["shared"][0].append(3), "does not track"),
    # reference dropped without decref: slot still maps a parked page
    (lambda lg: lg["slots"][1].append(6), "reference dropped"),
    # tiered KV: a host entry's device twin sits on the free list —
    # the eviction freed the page but dropped the tier's unmount
    # bookkeeping (a later prefill would overwrite an "advertised"
    # mounted twin)
    (lambda lg: lg["host"].update(
        cc03={"bytes": 4096, "page": 1}),
     "both host-resident and device-free"),
    # tiered KV: a host entry's twin backref points at a page the
    # cache no longer tracks (stale restore backref)
    (lambda lg: lg["host"].update(
        dd04={"bytes": 4096, "page": 3}),
     "stale restore backref"),
])
def test_page_refcount_rule_catches_planted_defects(mutate, expect):
    """Each corruption of the shared-pool ledger — double free, leak,
    refcount drift, unaccounted aliasing — is an ERROR (the
    prove-the-auditor half of the refcounted prefix cache)."""
    lg = _consistent_ledger()
    mutate(lg)
    pm = PassManager(["page-refcount"])
    report = pm.run(LoweredProgram("", name="ledger"),
                    AnalysisContext(name="ledger",
                                    extra={"page_ledger": lg}))
    hits = report.by_rule("MEM-PAGE-REFCOUNT")
    assert hits and all(h.severity == Severity.ERROR for h in hits)
    assert any(expect in h.message for h in hits), \
        (expect, [h.message for h in hits])


# ------------------------------------------------------- kv-quant rules

def _kv8_decoder(num_pages=64):
    from paddle_tpu.models import GPT, gpt_tiny
    from paddle_tpu.serving import PagedGPTDecoder
    paddle.seed(0)
    build_mesh(dp=1)
    model = GPT(gpt_tiny(max_seq_len=64, dtype="float32", remat=False))
    model.eval()
    return PagedGPTDecoder(model, num_pages=num_pages, page_size=16,
                           max_batch=2, kv_quant="int8")


def _kv8_ctx(dec):
    cfg = dec.cfg
    return AnalysisContext(
        name="decode_kv8",
        extra={"serving_decode": True, "kv_quant": "int8",
               "kv_pool_block_elems": (dec.num_pages * dec.page_size *
                                       cfg.num_heads * cfg.head_dim)})


def test_kv_quant_rule_catches_dequantized_pool_in_hbm():
    """DTYPE-KV-DEQUANT-HBM planted defect: a decode step that
    dequantizes the WHOLE int8 pool up front (convert + scale multiply
    at full pool shape) re-materializes the bf16-width byte stream the
    int8 pool exists to delete. The real capture — dequant inside the
    shared attention update, a block of 8 pages of each row a step, in
    a pool larger than that — stays clean."""
    dec = _kv8_decoder()
    ctx = _kv8_ctx(dec)
    pm = PassManager(["kv-quant"])

    good = dec.analysis_program(k=2)
    report = pm.run(good, ctx)
    assert report.by_rule("DTYPE-KV-DEQUANT-HBM") == []
    assert report.by_rule("DTYPE-KV-SCALE-WIDTH") == []
    m = report.metrics["kv-quant"]
    assert m["checked"] and m["n_pool_dequants"] == 0
    assert m["n_scale_planes"] == 2          # K and V planes

    def bad_step(weights, k_pages, v_pages, tokens, lens, table, kids):
        (kq, ks), (vq, vs) = k_pages, v_pages
        kf = kq.astype(jnp.float32) * ks[..., None, None]  # FULL pool
        vf = vq.astype(jnp.float32) * vs[..., None, None]  # in HBM
        return dec._decode_step(weights, kf, vf, tokens, lens, table,
                                kids)

    from paddle_tpu.analysis.lowering import tree_arg_infos
    S = dec.max_batch
    args = (dec.weights, dec.k_pages, dec.v_pages,
            jnp.zeros((S,), jnp.int32), jnp.zeros((S,), jnp.int32),
            jnp.zeros((S, dec.max_pages), jnp.int32),
            jnp.arange(S, dtype=jnp.int32))
    traced = jax.jit(bad_step).trace(*args)   # donation is irrelevant
    # to this rule (arg_infos below still mark the cache donated)
    infos = tree_arg_infos(dec.weights, "param")
    infos += tree_arg_infos(dec.k_pages, "cache", prefix="k_pages",
                            donated=True)
    infos += tree_arg_infos(dec.v_pages, "cache", prefix="v_pages",
                            donated=True)
    bad = LoweredProgram(traced.lower().as_text(), jaxpr=traced.jaxpr,
                         name="bad_dequant", arg_infos=infos)
    report2 = pm.run(bad, ctx)
    hits = report2.by_rule("DTYPE-KV-DEQUANT-HBM")
    assert hits and all(h.severity == Severity.ERROR for h in hits)
    assert report2.metrics["kv-quant"]["n_pool_dequants"] >= 2  # K and V

    # scope: without extra["kv_quant"] the rule never fires
    report3 = pm.run(bad, AnalysisContext(name="decode"))
    assert report3.by_rule("DTYPE-KV-DEQUANT-HBM") == []
    assert report3.metrics["kv-quant"] == {"checked": False}


def test_kv_quant_rule_catches_non_f32_scale_plane():
    """DTYPE-KV-SCALE-WIDTH planted defect: a scale plane stored at any
    width other than f32 (f64 doubles the metadata stream; bf16
    quantizes the scales themselves) is an ERROR on the cache args."""
    dec = _kv8_decoder()
    ctx = _kv8_ctx(dec)
    pm = PassManager(["kv-quant"])
    # corrupt the live pool: K scale plane left bf16 (f64 is spelled
    # the same way in the rule — any non-f32 floating cache leaf)
    kq, ks = dec.k_pages
    dec.k_pages = (kq, ks.astype(jnp.bfloat16))
    bad = dec.analysis_program(k=2)
    report = pm.run(bad, ctx)
    hits = report.by_rule("DTYPE-KV-SCALE-WIDTH")
    assert hits and hits[0].severity == Severity.ERROR
    assert "bfloat16" in hits[0].message
    assert report.metrics["kv-quant"]["n_bad_scale_planes"] == 1


def _kv4_decoder(num_pages=64):
    from paddle_tpu.models import GPT, gpt_tiny
    from paddle_tpu.serving import PagedGPTDecoder
    paddle.seed(0)
    build_mesh(dp=1)
    model = GPT(gpt_tiny(max_seq_len=64, dtype="float32", remat=False))
    model.eval()
    return PagedGPTDecoder(model, num_pages=num_pages, page_size=16,
                           max_batch=2, kv_quant="int4")


def _kv4_ctx(dec):
    cfg = dec.cfg
    return AnalysisContext(
        name="decode_kv4",
        extra={"serving_decode": True, "kv_quant": "int4",
               "kv_pool_block_elems": (dec.num_pages * dec.page_size *
                                       cfg.num_heads * cfg.head_dim)})


def test_kv_quant_dequant_rule_reproves_on_packed_int4_pool():
    """DTYPE-KV-DEQUANT-HBM re-proven on the nibble-packed layout: a
    whole-pool int4 dequant still funnels through an i8 -> wide-float
    convert at full pool shape (the nibble unpack lands in int8 BEFORE
    the float convert; the uint8 bit-twiddling itself is integer-only
    and can never match), so the same regex catches it. The real
    capture — per-block unpack next to the shared attention update, a
    convert of 8 pages a row — stays clean."""
    from paddle_tpu.serving.decoder import _dequantize_kv_int4
    dec = _kv4_decoder()
    ctx = _kv4_ctx(dec)
    pm = PassManager(["kv-quant"])

    good = dec.analysis_program(k=2)
    report = pm.run(good, ctx)
    assert report.by_rule("DTYPE-KV-DEQUANT-HBM") == []
    assert report.by_rule("DTYPE-KV-SCALE-WIDTH") == []
    m = report.metrics["kv-quant"]
    assert m["checked"] and m["kv_quant"] == "int4"
    assert m["n_pool_dequants"] == 0
    assert m["n_scale_planes"] == 2          # K and V group planes

    hd = (dec.cfg.num_heads, dec.cfg.head_dim)

    def bad_step(weights, k_pages, v_pages, tokens, lens, table, kids):
        (kq, ks), (vq, vs) = k_pages, v_pages
        kf = _dequantize_kv_int4(kq, ks, hd)     # FULL pool in HBM
        vf = _dequantize_kv_int4(vq, vs, hd)
        return dec._decode_step(weights, kf, vf, tokens, lens, table,
                                kids)

    from paddle_tpu.analysis.lowering import tree_arg_infos
    S = dec.max_batch
    args = (dec.weights, dec.k_pages, dec.v_pages,
            jnp.zeros((S,), jnp.int32), jnp.zeros((S,), jnp.int32),
            jnp.zeros((S, dec.max_pages), jnp.int32),
            jnp.arange(S, dtype=jnp.int32))
    traced = jax.jit(bad_step).trace(*args)
    infos = tree_arg_infos(dec.weights, "param")
    infos += tree_arg_infos(dec.k_pages, "cache", prefix="k_pages",
                            donated=True)
    infos += tree_arg_infos(dec.v_pages, "cache", prefix="v_pages",
                            donated=True)
    bad = LoweredProgram(traced.lower().as_text(), jaxpr=traced.jaxpr,
                         name="bad_dequant4", arg_infos=infos)
    report2 = pm.run(bad, ctx)
    hits = report2.by_rule("DTYPE-KV-DEQUANT-HBM")
    assert hits and all(h.severity == Severity.ERROR for h in hits)
    assert report2.metrics["kv-quant"]["n_pool_dequants"] >= 2


def test_kv_quant_scale_rule_reproves_on_packed_int4_pool():
    """DTYPE-KV-SCALE-WIDTH re-proven on the packed layout: an int4
    GROUP-scale plane cast to bf16 (quantizing the scales themselves)
    is an ERROR on the cache args, exactly like the int8 per-token
    plane."""
    dec = _kv4_decoder()
    ctx = _kv4_ctx(dec)
    pm = PassManager(["kv-quant"])
    kq, ks = dec.k_pages
    dec.k_pages = (kq, ks.astype(jnp.bfloat16))
    bad = dec.analysis_program(k=2)
    report = pm.run(bad, ctx)
    hits = report.by_rule("DTYPE-KV-SCALE-WIDTH")
    assert hits and hits[0].severity == Severity.ERROR
    assert "bfloat16" in hits[0].message
    assert report.metrics["kv-quant"]["n_bad_scale_planes"] == 1


def test_page_refcount_audit_catches_cow_without_scales():
    """MEM-PAGE-REFCOUNT scale audit planted defect: a copy-on-write
    that moves a page's int8 BYTES but not its scale plane leaves the
    private copy dequantizing against zero scales (garbage tokens).
    The engine's audit_pages() cross-checks bytes against scales on
    every held page; the healthy CoW (copy_page tree-maps bytes AND
    scales) audits clean. Audited MID-RUN (run(on_sync=...)): after
    the drain the CoW'd page is back on the free list and out of the
    audit's held set — exactly when the garbage has already been
    served."""
    from paddle_tpu.serving import ContinuousBatchingEngine, PrefixCache

    def run_workload(break_cow):
        dec = _kv8_decoder(num_pages=16)
        if break_cow:
            def bytes_only_copy(src, dst):
                (kq, ks), (vq, vs) = dec.k_pages, dec.v_pages
                kq = kq.at[:, dst].set(kq[:, src])
                vq = vq.at[:, dst].set(vq[:, src])
                dec.k_pages = (kq, ks)       # scales left behind
                dec.v_pages = (vq, vs)
            dec.copy_page = bytes_only_copy
        eng = ContinuousBatchingEngine(
            dec, max_new_tokens=2, k_max=2,
            prefix_cache=PrefixCache(16, salt=dec.cache_fingerprint()))
        base = list(range(1, 17))            # one full shareable block
        hits = []
        for tail in ([21, 22], []):          # insert, then a FULL hit
            eng.submit(np.asarray(base + tail, np.int32))
            eng.run(on_sync=lambda e: hits.extend(e.audit_pages()))
        return eng, hits

    clean, clean_hits = run_workload(break_cow=False)
    assert clean.stats.prefix_cow >= 1       # the CoW really happened
    assert clean_hits == []
    assert clean.audit_pages() == []         # drained state clean too

    broken, broken_hits = run_workload(break_cow=True)
    assert broken.stats.prefix_cow >= 1
    assert broken_hits
    assert all(h.severity == Severity.ERROR for h in broken_hits)
    assert any("scale plane" in h.message for h in broken_hits)


# ------------------------------------------------------ schedule doctor


def _sched_program(fn, *args, axes=(("tp", 8),)):
    """LoweredProgram over a jaxpr traced under a named-axis env (the
    schedule pass consumes the jaxpr only; the HLO text stays empty)."""
    jx = jax.make_jaxpr(fn, axis_env=list(axes))(*args)
    return LoweredProgram("", jaxpr=jx,
                          name=getattr(fn, "__name__", "sched"))


def test_coll_serialized_rule_planted_defect_and_overlappable_twin():
    """COLL-SERIALIZED planted defect: a psum whose ONLY compute is its
    own producer (psum-after-dot, nothing else in flight) sits on the
    critical path with zero concurrently-schedulable compute — ERROR.
    The overlappable twin (an independent dot big enough to hide the
    wire) stays silent, and its schedule estimate prices the step at
    the roofline max while the serialized one prices toward the serial
    sum — bracketed either way."""
    from paddle_tpu.analysis import estimate_schedule

    def serialized(x, w):
        return jax.lax.psum(x @ w, "tp")

    def overlappable(x, w, w2):
        y = jax.lax.psum(x @ w, "tp")
        z = (x @ w2).sum()            # independent: schedulable DURING
        return y, z                   # the psum's wire time

    x = jnp.zeros((128, 256), jnp.float32)
    w = jnp.zeros((256, 128), jnp.float32)
    w2 = jnp.zeros((256, 2048), jnp.float32)
    pm = PassManager(["schedule"])

    bad = pm.run(_sched_program(serialized, x, w),
                 AnalysisContext(name="ser", mesh_axes={"tp": 8}))
    hits = bad.by_rule("COLL-SERIALIZED")
    assert hits and hits[0].severity == Severity.ERROR
    assert "psum" in hits[0].message and "serial" in hits[0].message
    m = bad.metrics["schedule"]
    assert m["n_collectives"] == 1
    assert m["n_serialized_collectives"] == 1
    # nothing overlaps: the overlap-aware step sits at the serial sum
    assert m["overlap_step_us"] == m["serial_step_us"]
    assert m["overlap_frac"] == 0.0

    good = pm.run(_sched_program(overlappable, x, w, w2),
                  AnalysisContext(name="ov", mesh_axes={"tp": 8}))
    assert good.by_rule("COLL-SERIALIZED") == []
    mg = good.metrics["schedule"]
    assert mg["n_collectives"] == 1
    assert mg["overlap_frac"] == 1.0
    assert mg["overlap_step_us"] == mg["ideal_step_us"]

    # the bracket is definitional on BOTH programs
    for est in (estimate_schedule(_sched_program(serialized, x, w),
                                  mesh_axes={"tp": 8}),
                estimate_schedule(_sched_program(overlappable, x, w, w2),
                                  mesh_axes={"tp": 8})):
        assert est.ideal_step_s <= est.overlap_step_s \
            <= est.serial_step_s + 1e-18


def test_coll_serialized_threshold_and_degenerate_group():
    """The hide bar is a context knob: compute covering 30% of the wire
    flags at the default 50% bar but passes a 20% bar. A degenerate
    1-participant psum has no wire leg at all — never a collective
    stream node, never a finding."""

    def partial(x, w, w2):
        y = jax.lax.psum(x @ w, "tp")     # wire >> the small free dot
        z = (x[:8] @ w2).sum()
        return y, z

    x = jnp.zeros((256, 64), jnp.float32)
    w = jnp.zeros((64, 1024), jnp.float32)
    w2 = jnp.zeros((64, 32), jnp.float32)
    pm = PassManager(["schedule"])
    program = _sched_program(partial, x, w, w2)

    strict = pm.run(program, AnalysisContext(name="p",
                                             mesh_axes={"tp": 8}))
    assert strict.by_rule("COLL-SERIALIZED")
    loose = pm.run(program, AnalysisContext(
        name="p", mesh_axes={"tp": 8}, schedule_hide_frac=0.001))
    assert loose.by_rule("COLL-SERIALIZED") == []

    def degenerate(x, w):
        return jax.lax.psum(x @ w, "one")

    deg = pm.run(_sched_program(degenerate, x, w, axes=(("one", 1),)),
                 AnalysisContext(name="d", mesh_axes={"one": 1}))
    assert deg.by_rule("COLL-SERIALIZED") == []
    assert deg.metrics["schedule"]["n_collectives"] == 0
    assert deg.metrics["schedule"]["overlap_frac"] == 1.0


def test_coll_serialized_scan_body_collective_attributed_to_source():
    """A collective INSIDE a scan body is still found (the DAG walk
    recurses like the memory pass's liveness walk), its cost scales
    with the trip count, and the finding attributes it to the source
    line of the psum call — not to the scan eqn that hides it."""
    from paddle_tpu.analysis import estimate_schedule

    def body(c, xs):
        y = c @ xs
        y = jax.lax.psum(y, "tp")     # <-- the line the rule must name
        return y, y.sum()
    psum_line = body.__code__.co_firstlineno + 2

    def f(c0, xs):
        return jax.lax.scan(body, c0, xs)

    c0 = jnp.zeros((64, 64), jnp.float32)
    xs = jnp.zeros((6, 64, 64), jnp.float32)
    pm = PassManager(["schedule"])
    report = pm.run(_sched_program(f, c0, xs),
                    AnalysisContext(name="scan", mesh_axes={"tp": 8}))
    hits = report.by_rule("COLL-SERIALIZED")
    assert hits, "scan-body collective not found"
    assert f"test_analysis_rules.py:{psum_line}" in hits[0].op, \
        (hits[0].op, psum_line)
    # trip scaling: the same body over 12 steps prices exactly 2x wire
    est6 = estimate_schedule(_sched_program(f, c0, xs),
                             mesh_axes={"tp": 8})
    est12 = estimate_schedule(
        _sched_program(f, c0, jnp.zeros((12, 64, 64), jnp.float32)),
        mesh_axes={"tp": 8})
    assert est12.wire_s == pytest.approx(2 * est6.wire_s)


def test_roofline_drift_verdict_splits_serialized_from_mispriced():
    """The drift ledger's serialized-vs-mispriced verdict: ticks that
    carry predicted_serial_s (engines/Trainer stamp the serial sum of
    the priced legs next to the overlapped max) let the analyzer tell
    a schedule that SERIALIZED its streams (measured inside the serial
    sum — fix the schedule, not the pricing inputs) from a genuinely
    mispriced leg (measured outside even the sum). Ticks without the
    serial band keep the legacy re-fit message."""
    from paddle_tpu.serving import FlightRecorder
    program = lower_callable(lambda x: x + 1.0,
                             jnp.zeros((2,), jnp.float32), name="decode")
    pm = PassManager(["roofline-drift"])

    def ledger(meas, serial):
        rec = FlightRecorder()
        for _ in range(4):
            rec.tick("serve", ("ragged", 4, 8), measured_s=meas,
                     predicted_s=1e-4, predicted_serial_s=serial)
        return rec.drift_report()

    # measured 10x the overlapped price but INSIDE the serial sum
    serialized = ledger(1e-3, 1.1e-3)
    assert serialized[0]["verdict"] == "serialized"
    rep = pm.run(program, AnalysisContext(
        name="s", extra={"roofline_drift": serialized}))
    hits = rep.by_rule("ROOFLINE-DRIFT")
    assert hits and hits[0].severity == Severity.ERROR
    assert "SERIALIZES" in hits[0].message
    assert "COLL-SERIALIZED" in hits[0].suggested_fix
    assert rep.metrics["roofline-drift"]["n_serialized"] == 1

    # measured far outside even the serial sum: a real mispricing
    mispriced = ledger(1e-2, 1.1e-3)
    assert mispriced[0]["verdict"] == "mispriced"
    rep2 = pm.run(program, AnalysisContext(
        name="s", extra={"roofline_drift": mispriced}))
    hits2 = rep2.by_rule("ROOFLINE-DRIFT")
    assert hits2 and "underprices" in hits2[0].message
    assert rep2.metrics["roofline-drift"]["n_serialized"] == 0

    # no serial band on the ticks: legacy message, no verdict claim
    rec = FlightRecorder()
    for _ in range(4):
        rec.tick("serve", ("decode", 4, 1), measured_s=1e-3,
                 predicted_s=1e-4)
    legacy = rec.drift_report()
    assert legacy[0]["verdict"] == "mispriced"
    assert "predicted_serial_s" not in legacy[0]
    rep3 = pm.run(program, AnalysisContext(
        name="s", extra={"roofline_drift": legacy}))
    assert rep3.by_rule("ROOFLINE-DRIFT")
    assert "underprices" in rep3.by_rule("ROOFLINE-DRIFT")[0].message


def test_schedule_prices_cond_at_its_most_expensive_branch():
    """Mutually exclusive cond branches must not SUM (exactly one
    executes — the eqn_flops rule): a cond over two dot branches
    prices like one dot, not two, and an untaken branch's compute
    never counts as COLL-SERIALIZED-hideable work next to a
    serialized collective."""
    from paddle_tpu.analysis import estimate_schedule

    w = jnp.zeros((256, 256), jnp.float32)
    x = jnp.zeros((256, 256), jnp.float32)

    def one_dot(p, x, w):
        return x @ w

    def cond_dots(p, x, w):
        return jax.lax.cond(p, lambda a: a @ w, lambda a: a @ w + 1.0,
                            x)

    e1 = estimate_schedule(_sched_program(one_dot, True, x, w))
    e2 = estimate_schedule(_sched_program(cond_dots, True, x, w))
    # exactly ONE branch's dot is priced (flops ~= one dot + the add's
    # elementwise tail; the pre-fix sum counted both dots, ~2.7x the
    # single-dot compute — now the heavier branch alone, < 2x)
    assert e2.flops < 1.1 * e1.flops, (e2.flops, e1.flops)
    assert e2.compute_s < 2.0 * e1.compute_s, (e2.compute_s,
                                               e1.compute_s)

    def serialized_with_cond(p, x, w, wc):
        y = jax.lax.psum(x @ w, "tp")
        z = jax.lax.cond(p, lambda a: (a @ wc).sum(),
                         lambda a: ((a @ wc) * 2.0).sum(), x)
        return y, z

    wc = jnp.zeros((256, 2048), jnp.float32)
    pm = PassManager(["schedule"])
    rep = pm.run(_sched_program(serialized_with_cond, True, x, w, wc),
                 AnalysisContext(name="c", mesh_axes={"tp": 8}))
    # the taken branch's dot IS hideable (independent of the psum): no
    # flag — but only ONE branch's worth of compute was credited
    assert rep.by_rule("COLL-SERIALIZED") == []
    m = rep.metrics["schedule"]
    assert m["n_collectives"] == 1
