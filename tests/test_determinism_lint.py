"""The lint-determinism CI gate: the Determinism Doctor must prove
the byte-identical-stream invariant on every committed serving config
(determinism_manifests/<config>.json — write-site taint canonicality,
RNG key provenance, scatter-overlap disjointness proofs, donation
audit, and the host-side thread-discipline counters), and each of the
six rules must have a planted-defect RED twin and a fixed GREEN twin.

Runs inside the standard tier-1 sweep; select alone with
`-m lint_determinism`. Reports ride the per-process lowering cache in
paddle_tpu.analysis.baseline (one trace per config)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.analysis import (PassManager, build_determinism_manifest,
                                 load_determinism_manifest, manifest_drift)
from paddle_tpu.analysis.baseline import (DETERMINISM_CONFIGS,
                                          lowered_program)
from paddle_tpu.analysis.determinism import analyze_determinism
from paddle_tpu.analysis.lowering import ArgInfo, lower_callable
from paddle_tpu.analysis.threads import lint_module_source

pytestmark = pytest.mark.lint_determinism


@pytest.fixture(scope="module")
def pass_manager():
    return PassManager(["determinism", "threads"])


def _det_report(name, pm):
    program, ctx, fwd = lowered_program(name)
    report = pm.run_source(fwd, ctx)
    report.extend(pm.run(program, ctx))
    return report


def _infos(*specs):
    return [ArgInfo(name=n, role=r, donated=d) for n, r, d in specs]


# ------------------------------------------------------- manifest gate


@pytest.mark.parametrize("name", sorted(DETERMINISM_CONFIGS))
def test_determinism_manifest_is_committed_and_current(name,
                                                       pass_manager):
    committed = load_determinism_manifest(name)
    assert committed is not None, (
        f"determinism_manifests/{name}.json is not committed — run "
        "python -m paddle_tpu.analysis --write-manifests")
    fresh = build_determinism_manifest(name,
                                       _det_report(name, pass_manager))
    drift = manifest_drift(fresh, committed)
    assert drift == [], "\n".join(drift)


@pytest.mark.parametrize("name", sorted(DETERMINISM_CONFIGS))
def test_serving_config_is_proven_deterministic(name, pass_manager):
    """Structural pins that outlive re-baselining: every committed
    serving capture must PROVE the invariant — all pool writes
    canonical (keyed by table row + position, never slot/batch
    order), a greedy decode with zero RNG sites, no unproven scatter
    overlaps, no donated buffer escaping unwritten, and a host
    runtime with zero unlocked shared write-write paths."""
    report = _det_report(name, pass_manager)
    det = report.metrics["determinism"]
    assert det["available"] and det["n_eqns"] > 0
    assert det["n_pool_writes"] >= 2          # k_pages + v_pages
    assert det["n_canonical_writes"] == det["n_pool_writes"]
    assert det["n_rng_sites"] == 0            # greedy decode
    assert det["n_overlap_pairs"] == det["n_proven_disjoint"] == 0
    assert det["n_donated_args"] >= 2 and det["n_alias_outputs"] == 0
    th = report.metrics["threads"]
    assert th["available"] and th["n_classes"] > 0
    # the io prefetch worker + the fleet router's replica threads
    assert th["n_threaded_classes"] >= 2
    # serving.fleet.FleetRouter shares churn/output/error paths across
    # replica threads BY DESIGN (_pending, _outputs, _errors) — the
    # invariant is that every one is lock-disciplined (zero findings),
    # not that none exist
    assert th["n_shared_paths"] == 3
    assert report.findings == []


# ------------------------------------ rule twins: KV-WRITE-NONCANONICAL


_POOL = np.zeros((16, 8, 2, 4), np.float32)
_TABLE = np.zeros((4, 4), np.int32)
_LENS = np.zeros((4,), np.int32)
_VAL = np.zeros((4, 2, 4), np.float32)
_POOL_INFOS = (("k_pages", "cache", True), ("table", "input", False),
               ("lens", "input", False), ("val", "input", False))


def test_kv_write_slot_keyed_is_red():
    """Planted defect: page id = jnp.arange(S) (the SLOT index — batch
    admission order), not a page-table row. The write lands wherever
    the scheduler packed the request: layout-dependent bytes."""
    def bad(pool, table, lens, val):
        pids = jnp.arange(4)
        return pool.at[pids, lens % 8].set(val)
    p = lower_callable(bad, _POOL, _TABLE, _LENS, _VAL, name="bad_slot",
                       arg_infos=_infos(*_POOL_INFOS))
    r = analyze_determinism(p)
    assert [f.rule_id for f in r.findings] == ["KV-WRITE-NONCANONICAL"]
    assert r.metrics["n_canonical_writes"] == 0
    assert "page table" in r.findings[0].message


def test_kv_write_table_keyed_twin_is_green():
    """The fix: route the write through the page table
    (table[slot, len//page]) — the canonical (row, position) key the
    committed decoder uses."""
    def good(pool, table, lens, val):
        pids = jnp.take_along_axis(table, (lens // 8)[:, None],
                                   axis=1)[:, 0]
        return pool.at[pids, lens % 8].set(val)
    p = lower_callable(good, _POOL, _TABLE, _LENS, _VAL,
                       name="good_table",
                       arg_infos=_infos(*_POOL_INFOS))
    r = analyze_determinism(p)
    assert r.findings == []
    assert r.metrics["n_canonical_writes"] == \
        r.metrics["n_pool_writes"] == 1


_WHOLE = np.zeros((3, 16, 8, 2, 4), np.float32)     # [L, P, ps, H, D]


@pytest.mark.parametrize("layer_by", ["loop_counter", "request"])
def test_kv_write_on_the_whole_pool_reads_page_and_offset_last(layer_by):
    """The decoders' layer loops carry the WHOLE pool and write
    `pool.at[layer, page, offset]`: the page and the offset are the last
    two index columns and keep their rules; the layer column before them
    may come from the loop's own counter (green: the committed form) and
    never from a request (red: a layer picked by the position lands a
    token's bytes in a layer the replay would not pick)."""
    def write(pool, table, lens, val):
        pids = jnp.take_along_axis(table, (lens // 8)[:, None],
                                   axis=1)[:, 0]
        if layer_by == "request":
            return pool.at[lens % 3, pids, lens % 8].set(val)

        def layer(pool, li):
            return pool.at[li, pids, lens % 8].set(val), None
        return jax.lax.scan(layer, pool, jnp.arange(3))[0]
    p = lower_callable(write, _WHOLE, _TABLE, _LENS, _VAL,
                       name=f"whole_pool_{layer_by}",
                       arg_infos=_infos(*_POOL_INFOS))
    r = analyze_determinism(p)
    assert r.metrics["n_pool_writes"] == 1
    if layer_by == "request":
        assert [f.rule_id for f in r.findings] == ["KV-WRITE-NONCANONICAL"]
        assert "layer index" in r.findings[0].message
    else:
        assert r.findings == []
        assert r.metrics["n_canonical_writes"] == 1


# -------------------------------------------- rule twins: RNG-KEY-TAINT


_KIDS = np.arange(4, dtype=np.uint32)
_POS = np.arange(4, dtype=np.int32)
_LOGITS = np.zeros((4, 11), np.float32)
_KEY_INFOS = (("kids", "input", False), ("pos", "input", False),
              ("logits", "input", False))


def test_rng_key_salted_by_batch_order_is_red():
    """Planted defect: the sampling key folds in jnp.arange(S) — the
    slot index. Re-batching the same request re-rolls its dice."""
    def bad(kids, pos, logits):
        keys = jax.vmap(lambda k, s: jax.random.fold_in(
            jax.random.fold_in(jax.random.PRNGKey(0), k), s))(
                kids, jnp.arange(4))
        return jax.vmap(jax.random.categorical)(keys, logits)
    p = lower_callable(bad, _KIDS, _POS, _LOGITS, name="bad_key",
                       arg_infos=_infos(*_KEY_INFOS))
    r = analyze_determinism(p)
    assert {f.rule_id for f in r.findings} == {"RNG-KEY-TAINT"}
    assert r.metrics["n_rng_sites"] > 0


def test_rng_key_rid_position_twin_is_green():
    """The fix: key = f(seed, request id, position) — request-
    intrinsic only, so the stream is a pure function of the request."""
    def good(kids, pos, logits):
        keys = jax.vmap(lambda k, s: jax.random.fold_in(
            jax.random.fold_in(jax.random.PRNGKey(0), k), s))(kids, pos)
        return jax.vmap(jax.random.categorical)(keys, logits)
    p = lower_callable(good, _KIDS, _POS, _LOGITS, name="good_key",
                       arg_infos=_infos(*_KEY_INFOS))
    r = analyze_determinism(p)
    assert r.findings == []
    assert r.metrics["n_rng_sites"] > 0


# ------------------------------------- rule twins: SCATTER-WRITE-OVERLAP


_V8 = np.zeros((4, 8, 2, 4), np.float32)
_OVL_INFOS = (("k_pages", "cache", True), ("val", "input", False))


def test_scatter_overlapping_windows_is_red():
    """Planted defect: two unguarded scatters into rows [0,4) and
    [2,6) of one pool — rows 2..3 are written twice and the final
    bytes depend on scatter execution order."""
    def bad(pool, val):
        pool = pool.at[jnp.arange(0, 4)].set(val)
        return pool.at[jnp.arange(2, 6)].set(val)
    p = lower_callable(bad, _POOL, _V8, name="bad_overlap",
                       arg_infos=_infos(*_OVL_INFOS))
    r = analyze_determinism(p)
    assert "SCATTER-WRITE-OVERLAP" in {f.rule_id for f in r.findings}
    assert r.metrics["n_overlap_pairs"] == 1
    assert r.metrics["n_proven_disjoint"] == 0


def test_scatter_disjoint_windows_twin_is_green():
    """The fix: static windows [0,4) and [4,8) — the range analysis
    proves the index sets disjoint, so write order cannot matter."""
    def good(pool, val):
        pool = pool.at[jnp.arange(0, 4)].set(val)
        return pool.at[jnp.arange(4, 8)].set(val)
    p = lower_callable(good, _POOL, _V8, name="good_overlap",
                       arg_infos=_infos(*_OVL_INFOS))
    r = analyze_determinism(p)
    assert r.by_rule("SCATTER-WRITE-OVERLAP") == []
    assert r.metrics["n_overlap_pairs"] == 1
    assert r.metrics["n_proven_disjoint"] == 1


# ---------------------------------------- rule twins: DONATE-HOST-ALIAS


def test_donated_passthrough_is_red():
    """Planted defect: a donated pool returned untouched — XLA may
    alias the output onto the donated input buffer, so the caller's
    'old' pages read back as whatever the donor became."""
    def bad(pool, x):
        return pool, x * 2.0
    p = lower_callable(bad, _POOL, np.ones((3,), np.float32),
                       name="bad_alias",
                       arg_infos=_infos(("k_pages", "cache", True),
                                        ("x", "input", False)))
    r = analyze_determinism(p)
    assert [f.rule_id for f in r.findings] == ["DONATE-HOST-ALIAS"]
    assert r.metrics["n_alias_outputs"] == 1


def test_donated_written_twin_is_green():
    """The fix: the donated pool flows through a scatter before it is
    returned — a fresh value, not a byte-alias of the donor."""
    def good(pool, x):
        v = x[None, None, None, :4].repeat(8, 1).repeat(2, 2)
        return pool.at[jnp.zeros((1,), jnp.int32)].set(v), x * 2.0
    p = lower_callable(good, _POOL, np.ones((8,), np.float32),
                       name="good_alias",
                       arg_infos=_infos(("k_pages", "cache", True),
                                        ("x", "input", False)))
    r = analyze_determinism(p)
    assert r.by_rule("DONATE-HOST-ALIAS") == []
    assert r.metrics["n_alias_outputs"] == 0


# ------------------------------------ rule twins: SERVE-UNLOCKED-SHARED


_UNLOCKED_RED = '''
import threading
from queue import Queue

class Pump:
    def __init__(self):
        self.q = Queue(4)
        self.n_batches = 0
        self._t = threading.Thread(target=self._work, daemon=True)
        self._t.start()

    def _work(self):
        while True:
            self.q.put(1)
            self.n_batches += 1

    def drain(self):
        self.n_batches = 0
'''

_UNLOCKED_GREEN = _UNLOCKED_RED.replace(
    "        self.n_batches = 0\n        self._t",
    "        self.n_batches = 0\n"
    "        self._mu = threading.Lock()\n        self._t").replace(
    "            self.q.put(1)\n            self.n_batches += 1",
    "            self.q.put(1)\n            with self._mu:\n"
    "                self.n_batches += 1").replace(
    "    def drain(self):\n        self.n_batches = 0",
    "    def drain(self):\n        with self._mu:\n"
    "            self.n_batches = 0")


def test_unlocked_shared_write_is_red():
    findings, stats = lint_module_source(_UNLOCKED_RED, "pump.py")
    assert [f.rule_id for f in findings] == ["SERVE-UNLOCKED-SHARED"]
    assert "n_batches" in findings[0].message
    assert stats["n_threaded_classes"] == 1
    assert stats["n_shared_paths"] == 1


def test_locked_shared_write_twin_is_green():
    """The fix: one owning lock around every write on both sides.
    The shared path still exists (the counter IS shared) — it is just
    disciplined now."""
    findings, stats = lint_module_source(_UNLOCKED_GREEN, "pump.py")
    assert findings == []
    assert stats["n_threaded_classes"] == 1
    assert stats["n_shared_paths"] == 1
    assert stats["n_lock_attrs"] == 1


# ---------------------------------------- rule twins: SERVE-LOCK-ORDER


_ABBA_RED = '''
import threading

class Tier:
    def __init__(self):
        self._index_mu = threading.Lock()
        self._pool_mu = threading.Lock()

    def put(self, k, v):
        with self._index_mu:
            with self._pool_mu:
                pass

    def get(self, k):
        with self._pool_mu:
            with self._index_mu:
                pass
'''

_ABBA_GREEN = _ABBA_RED.replace(
    "        with self._pool_mu:\n            with self._index_mu:",
    "        with self._index_mu:\n            with self._pool_mu:")


def test_abba_lock_order_is_red():
    findings, _ = lint_module_source(_ABBA_RED, "tier.py")
    assert [f.rule_id for f in findings] == ["SERVE-LOCK-ORDER"]
    assert "_index_mu" in findings[0].message \
        and "_pool_mu" in findings[0].message


def test_consistent_lock_order_twin_is_green():
    findings, stats = lint_module_source(_ABBA_GREEN, "tier.py")
    assert findings == []
    assert stats["n_lock_attrs"] == 2


def test_single_threaded_class_never_fires_shared_rule():
    """A class that spawns no thread produces no SERVE-UNLOCKED-SHARED
    finding no matter how it writes its attributes — the r5 fuzz-
    corpus no-false-positive bar (the corpus itself runs in
    test_dy2static_fuzz.py::test_fuzz_corpus_thread_lint_silent)."""
    src = _UNLOCKED_RED.replace(
        "        self._t = threading.Thread("
        "target=self._work, daemon=True)\n        self._t.start()\n",
        "")
    findings, stats = lint_module_source(src, "pump.py")
    assert findings == []
    assert stats["n_threaded_classes"] == 0


# --------------------------------- the documented expected red: verify


def test_speculative_verify_window_is_the_expected_red(tiny_decoder):
    """The one finding the committed runtime OWNS: the speculative
    verify window writes draft-token KV into the shared pool before
    acceptance. The written bytes carry DRAFT provenance — a function
    of the proposer, not the request — so KV-WRITE-NONCANONICAL fires
    on both pools by design (docs/static_analysis.md documents it;
    commit-on-accept would turn it green)."""
    program = tiny_decoder.analysis_program(verify_w=4)
    r = analyze_determinism(program)
    rules = [f.rule_id for f in r.findings]
    assert rules == ["KV-WRITE-NONCANONICAL"] * 2    # k_pages, v_pages
    assert all("draft" in f.message.lower() for f in r.findings)
    # the index side is still canonical — it is the VALUE provenance
    # that breaks the invariant here
    assert r.metrics["n_pool_writes"] == 2


@pytest.fixture(scope="module")
def tiny_decoder():
    import paddle_tpu as paddle
    from paddle_tpu.distributed import build_mesh
    from paddle_tpu.models import GPT, gpt_tiny
    from paddle_tpu.serving import PagedGPTDecoder
    paddle.seed(11)
    build_mesh(dp=1)
    cfg = gpt_tiny(max_seq_len=64, dtype="float32", remat=False)
    model = GPT(cfg)
    model.eval()
    return PagedGPTDecoder(model, num_pages=16, page_size=16,
                           max_batch=2)


# ------------------------------- dynamic ledger vs static pass agreement


def test_audit_pages_and_static_pass_agree_on_cow_run(tiny_decoder):
    """The dynamic page ledger and the static determinism pass are two
    views of ONE invariant: a real shared-prefix copy-on-write run
    must audit clean at runtime AND the same decoder's lowered program
    must statically prove every pool write canonical. If either side
    drifts (a ledger leak the pass can't see, or a pass rule firing on
    a run the ledger blesses), this pins it."""
    import numpy as np
    from paddle_tpu.serving import ContinuousBatchingEngine, PrefixCache

    dec = tiny_decoder
    cache = PrefixCache(16, salt=dec.cache_fingerprint())
    eng = ContinuousBatchingEngine(dec, max_new_tokens=4,
                                   prefix_cache=cache)
    prompt = np.asarray(list(range(1, 33)), np.int32)  # two full pages
    r1 = eng.submit(prompt)
    o1 = eng.run()[r1]
    r2 = eng.submit(prompt)                 # full hit -> CoW
    o2 = eng.run()[r2]
    assert o1 == o2                         # byte-identical streams
    assert eng.stats.prefix_cow == 1
    assert eng.audit_pages() == []          # dynamic ledger clean
    res = analyze_determinism(dec.analysis_program(k=2))
    assert res.findings == []               # static pass agrees
    assert res.metrics["n_canonical_writes"] == \
        res.metrics["n_pool_writes"]


# ------------------------------------------------------ CLI + front door


def test_cli_check_covers_determinism_drift(monkeypatch, capsys):
    """--check exits 1 when ONLY the determinism manifest is stale
    (lint, memory, propagation current), proving the new family is
    inside the CI gate."""
    from paddle_tpu.analysis import __main__ as cli
    from paddle_tpu.analysis import manifest as mf

    assert cli.main(["gpt_decode", "--check"]) == 0
    capsys.readouterr()

    real = mf.load_determinism_manifest

    def stale(name):
        data = real(name)
        if data:
            data = dict(data, n_findings=99)
        return data
    monkeypatch.setattr(mf, "load_determinism_manifest", stale)
    # the package re-exports the symbol; patch the import site too
    import paddle_tpu.analysis as pkg
    monkeypatch.setattr(pkg, "load_determinism_manifest", stale)
    assert cli.main(["gpt_decode", "--check"]) == 1
    out = capsys.readouterr().out
    assert "STALE" in out and "determinism" in out


def test_cli_determinism_prints_summary(capsys):
    from paddle_tpu.analysis.__main__ import main
    assert main(["gpt_decode", "--determinism",
                 "--no-manifest-check"]) == 0
    out = capsys.readouterr().out
    assert "pool writes canonical" in out
    assert "classes threaded" in out


def test_debug_determinism_report_front_door(tiny_decoder, capsys):
    from paddle_tpu import debug

    r = debug.determinism_report(tiny_decoder, k=2)
    out = capsys.readouterr().out
    assert "pool writes 2/2 canonical" in out
    assert r["findings"] == []
    assert r["graph"]["n_pool_writes"] == 2
    # serving.fleet.FleetRouter shares churn/output/error paths across
    # replica threads BY DESIGN — all lock-disciplined (findings == []
    # above), so they count as shared paths without being findings
    assert r["threads"]["n_shared_paths"] == 3

    host_only = debug.determinism_report(print_report=False)
    assert host_only["graph"] == {}
    assert host_only["threads"]["n_classes"] > 0
