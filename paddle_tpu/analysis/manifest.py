"""Per-model lint & memory manifests — the committed, diffable face of
the Graph Doctor (regenerate, diff, review).

`lint_manifests/<config>.json` pins each BASELINE config's op counts,
collective accounting, and finding summary. The graph-shape analyzer
treats the committed manifest as the baseline: any drift is an ERROR
until the manifest is regenerated and the diff reviewed.

`memory_manifests/<config>.json` pins the static per-device HBM
estimate (liveness peak, breakdown, top-k attribution) and the analytic
collective wire budget. The memory/sharding passes gate fresh runs
against it; `manifest_drift` powers the CLI's `--check` mode (stale
manifests fail CI instead of silently re-baselining the lint)."""
import json
import os

__all__ = ["manifest_dir", "manifest_path", "load_manifest",
           "build_manifest", "write_manifest",
           "memory_manifest_dir", "memory_manifest_path",
           "load_memory_manifest", "build_memory_manifest",
           "write_memory_manifest", "manifest_drift",
           "tuning_manifest_dir", "tuning_manifest_path",
           "load_tuning_manifest", "build_tuning_manifest",
           "write_tuning_manifest",
           "schedule_manifest_dir", "schedule_manifest_path",
           "load_schedule_manifest", "build_schedule_manifest",
           "write_schedule_manifest",
           "propagation_manifest_dir", "propagation_manifest_path",
           "load_propagation_manifest", "build_propagation_manifest",
           "write_propagation_manifest",
           "determinism_manifest_dir", "determinism_manifest_path",
           "load_determinism_manifest", "build_determinism_manifest",
           "write_determinism_manifest"]

_SCHEMA = 1
_MEMORY_SCHEMA = 1
_TUNING_SCHEMA = 1
_SCHEDULE_SCHEMA = 1
_PROPAGATION_SCHEMA = 1
_DETERMINISM_SCHEMA = 1


def manifest_dir():
    """Repo-root lint_manifests/."""
    here = os.path.dirname(os.path.abspath(__file__))
    repo = os.path.dirname(os.path.dirname(here))
    return os.path.join(repo, "lint_manifests")


def manifest_path(name):
    return os.path.join(manifest_dir(), f"{name}.json")


def load_manifest(name):
    """The committed manifest dict, or None when not yet committed."""
    try:
        with open(manifest_path(name)) as f:
            return json.load(f)
    except FileNotFoundError:
        return None


def build_manifest(name, program, report):
    """Manifest dict from one pass-manager run (deterministic: sorted
    keys, no timestamps — a re-run on an unchanged graph must produce a
    byte-identical file)."""
    counts = report.metrics.get("graph-shape", {}).get("op_counts", {})
    coll = report.metrics.get("collective", {})
    by_rule = {}
    for f in report.findings:
        by_rule[f.rule_id] = by_rule.get(f.rule_id, 0) + 1
    return {
        "schema": _SCHEMA,
        "model": name,
        "op_counts": {k: counts[k] for k in sorted(counts)},
        "collectives": {
            "count": coll.get("n_collectives", 0),
            "total_payload_bytes": coll.get("total_payload_bytes", 0),
            "total_wire_bytes": coll.get("total_wire_bytes", 0),
        },
        "findings_by_rule": {k: by_rule[k] for k in sorted(by_rule)},
        "max_severity": (str(report.max_severity)
                         if report.findings else None),
        "note": "regenerate: python -m paddle_tpu.analysis "
                "--write-manifests",
    }


def write_manifest(name, program, report):
    os.makedirs(manifest_dir(), exist_ok=True)
    data = build_manifest(name, program, report)
    with open(manifest_path(name), "w") as f:
        json.dump(data, f, indent=1, sort_keys=True)
        f.write("\n")
    return data


# ---------------------------------------------------------------- memory


def memory_manifest_dir():
    """Repo-root memory_manifests/ (next to lint_manifests/)."""
    here = os.path.dirname(os.path.abspath(__file__))
    repo = os.path.dirname(os.path.dirname(here))
    return os.path.join(repo, "memory_manifests")


def memory_manifest_path(name):
    return os.path.join(memory_manifest_dir(), f"{name}.json")


def load_memory_manifest(name):
    """The committed memory manifest dict, or None when not committed."""
    try:
        with open(memory_manifest_path(name)) as f:
            return json.load(f)
    except FileNotFoundError:
        return None


def build_memory_manifest(name, report):
    """Memory manifest dict from one pass-manager run (deterministic:
    sorted keys, no timestamps, native dtype widths — platform
    independent, so a TPU and a CPU checkout agree byte-for-byte)."""
    mem = report.metrics.get("memory", {})
    sh = report.metrics.get("sharding", {})
    return {
        "schema": _MEMORY_SCHEMA,
        "model": name,
        "per_device_peak_bytes": mem.get("peak_bytes", 0),
        "args_bytes": mem.get("args_bytes", 0),
        "output_bytes": mem.get("out_bytes", 0),
        "temp_peak_bytes": mem.get("temp_peak_bytes", 0),
        "donated_bytes": mem.get("donated_bytes", 0),
        "top_live": [
            {"op": b.get("op"), "name": b.get("name"),
             "device_bytes": b.get("device_bytes")}
            for b in mem.get("top_live", [])],
        "replication": {
            "n_replicated_big": sh.get("n_replicated_big", 0),
            "replicated_big_bytes": sh.get("replicated_big_bytes", 0),
        },
        "collectives": {
            "total_wire_bytes": sh.get("total_wire_bytes", 0),
            "n_mid_program_reshards": sh.get("n_mid_program_reshards", 0),
        },
        "note": "regenerate: python -m paddle_tpu.analysis "
                "--write-manifests",
        # dp-over-hosts captures only: the distinct-bytes-per-host
        # block (absent keeps single-host manifests byte-stable)
        **({"per_host": mem["per_host"]} if mem.get("per_host") else {}),
    }


def write_memory_manifest(name, report):
    os.makedirs(memory_manifest_dir(), exist_ok=True)
    data = build_memory_manifest(name, report)
    with open(memory_manifest_path(name), "w") as f:
        json.dump(data, f, indent=1, sort_keys=True)
        f.write("\n")
    return data


# ---------------------------------------------------------------- tuning


def tuning_manifest_dir():
    """Repo-root tuning_manifests/ (next to memory_manifests/)."""
    here = os.path.dirname(os.path.abspath(__file__))
    repo = os.path.dirname(os.path.dirname(here))
    return os.path.join(repo, "tuning_manifests")


def tuning_manifest_path(name):
    return os.path.join(tuning_manifest_dir(), f"{name}.json")


def load_tuning_manifest(name):
    """The committed tuning manifest dict, or None when not committed."""
    try:
        with open(tuning_manifest_path(name)) as f:
            return json.load(f)
    except FileNotFoundError:
        return None


def build_tuning_manifest(name, report):
    """Tuning manifest dict from one `autotune_layer` report
    (analysis/autotune.py): per-policy what-if peaks, recompute %, and
    the advisor's ranking. Deterministic — the replay runs over one
    seeded CPU trace and the roofline prices against a FIXED chip spec
    (v5e), so a TPU and a CPU checkout agree byte-for-byte."""
    return {
        "schema": _TUNING_SCHEMA,
        "model": name,
        "chip": report.chip,
        "hbm_budget_bytes": report.hbm_budget,
        "policies": {
            c.policy: {
                "peak_bytes": c.peak_bytes,
                "recompute_pct": round(c.recompute_pct, 2),
                "predicted_step_us": round(c.step_s * 1e6, 3),
                "bound": c.bound,
                "feasible": c.feasible,
            } for c in report.candidates},
        "ranked": [c.policy for c in report.candidates],
        "best": report.best.policy if report.best else None,
        "note": "regenerate: python -m paddle_tpu.analysis "
                "--write-manifests",
    }


def write_tuning_manifest(name, report):
    os.makedirs(tuning_manifest_dir(), exist_ok=True)
    data = build_tuning_manifest(name, report)
    with open(tuning_manifest_path(name), "w") as f:
        json.dump(data, f, indent=1, sort_keys=True)
        f.write("\n")
    return data


# -------------------------------------------------------------- schedule


def schedule_manifest_dir():
    """Repo-root schedule_manifests/ (next to tuning_manifests/)."""
    here = os.path.dirname(os.path.abspath(__file__))
    repo = os.path.dirname(os.path.dirname(here))
    return os.path.join(repo, "schedule_manifests")


def schedule_manifest_path(name):
    return os.path.join(schedule_manifest_dir(), f"{name}.json")


def load_schedule_manifest(name):
    """The committed schedule manifest dict, or None when absent."""
    try:
        with open(schedule_manifest_path(name)) as f:
            return json.load(f)
    except FileNotFoundError:
        return None


def build_schedule_manifest(name, report):
    """Schedule manifest dict from one pass-manager run
    (analysis/schedule.py metrics): the overlap-aware/serial/roofline
    step-time bracket, the wire-hiding fraction, and the critical-path
    attribution. Deterministic — node pricing runs over the cached CPU
    trace against the FIXED v5e spec (the tuning-manifest discipline),
    so a TPU and a CPU checkout agree byte-for-byte."""
    sch = report.metrics.get("schedule", {})
    return {
        "schema": _SCHEDULE_SCHEMA,
        "model": name,
        "chip": "v5e",
        "n_nodes": sch.get("n_nodes", 0),
        "n_collectives": sch.get("n_collectives", 0),
        "n_serialized_collectives": sch.get(
            "n_serialized_collectives", 0),
        "wire": {"ici_bytes": sch.get("wire_ici_bytes", 0),
                 "dcn_bytes": sch.get("wire_dcn_bytes", 0)},
        "ideal_step_us": sch.get("ideal_step_us", 0),
        "overlap_step_us": sch.get("overlap_step_us", 0),
        "serial_step_us": sch.get("serial_step_us", 0),
        "overlap_frac": sch.get("overlap_frac", 1.0),
        "critical_path": sch.get("critical_path", []),
        "note": "regenerate: python -m paddle_tpu.analysis "
                "--write-manifests",
    }


def write_schedule_manifest(name, report):
    os.makedirs(schedule_manifest_dir(), exist_ok=True)
    data = build_schedule_manifest(name, report)
    with open(schedule_manifest_path(name), "w") as f:
        json.dump(data, f, indent=1, sort_keys=True)
        f.write("\n")
    return data


# ----------------------------------------------------------- propagation


def propagation_manifest_dir():
    """Repo-root propagation_manifests/ (next to schedule_manifests/)."""
    here = os.path.dirname(os.path.abspath(__file__))
    repo = os.path.dirname(os.path.dirname(here))
    return os.path.join(repo, "propagation_manifests")


def propagation_manifest_path(name):
    return os.path.join(propagation_manifest_dir(), f"{name}.json")


def load_propagation_manifest(name):
    """The committed propagation manifest dict, or None when absent."""
    try:
        with open(propagation_manifest_path(name)) as f:
            return json.load(f)
    except FileNotFoundError:
        return None


def build_propagation_manifest(name, report):
    """Propagation manifest dict from one pass-manager run
    (analysis/propagation.py metrics): fixed-point coverage (exact vs
    conservative-fallback vars), the XLA cross-check's agreement
    counters, and the two lint feeds' counts. Deterministic — the
    fixed point over one cached CPU trace converges to the same specs
    on every machine, so a TPU and a CPU checkout agree
    byte-for-byte."""
    prop = report.metrics.get("propagation", {})
    return {
        "schema": _PROPAGATION_SCHEMA,
        "model": name,
        "n_vars": prop.get("n_vars", 0),
        "n_exact": prop.get("n_exact", 0),
        "n_fallback": prop.get("n_fallback", 0),
        "n_constraints": prop.get("n_constraints", 0),
        "annotations": {
            "n_annotated": prop.get("n_annotated", 0),
            "n_agree": prop.get("n_agree", 0),
            "n_diverge": prop.get("n_diverge", 0),
            "n_unmapped": prop.get("n_unmapped", 0),
            "agreement_rate": prop.get("agreement_rate", 1.0),
        },
        "n_divergences": prop.get("n_divergences", 0),
        "n_loop_carry_reshards": prop.get("n_loop_carry_reshards", 0),
        "iterations": prop.get("iterations", 0),
        "converged": prop.get("converged", True),
        "note": "regenerate: python -m paddle_tpu.analysis "
                "--write-manifests",
    }


def write_propagation_manifest(name, report):
    os.makedirs(propagation_manifest_dir(), exist_ok=True)
    data = build_propagation_manifest(name, report)
    with open(propagation_manifest_path(name), "w") as f:
        json.dump(data, f, indent=1, sort_keys=True)
        f.write("\n")
    return data


# ----------------------------------------------------------- determinism


def determinism_manifest_dir():
    """Repo-root determinism_manifests/ (next to schedule_manifests/)."""
    here = os.path.dirname(os.path.abspath(__file__))
    repo = os.path.dirname(os.path.dirname(here))
    return os.path.join(repo, "determinism_manifests")


def determinism_manifest_path(name):
    return os.path.join(determinism_manifest_dir(), f"{name}.json")


def load_determinism_manifest(name):
    """The committed determinism manifest dict, or None when absent."""
    try:
        with open(determinism_manifest_path(name)) as f:
            return json.load(f)
    except FileNotFoundError:
        return None


def build_determinism_manifest(name, report):
    """Determinism manifest dict from one pass-manager run: the graph
    leg's taint/write/race coverage (analysis/determinism.py metrics)
    plus the host leg's thread-discipline counters
    (analysis/threads.py).  Committed GREEN for every serving PROGRAM
    config — the one expected red (the SpeculativeEngine verify
    window) is a separate, uncommitted program pinned red by
    tests/test_determinism_lint.py.  Deterministic: the taint fixed
    point walks one cached CPU trace and the thread lint walks the
    checked-in sources, so every machine agrees byte-for-byte."""
    det = report.metrics.get("determinism", {})
    thr = report.metrics.get("threads", {})
    fnd = [f for f in report.findings
           if f.analyzer in ("determinism", "threads")]
    rules = dict(det.get("rules", {}))
    for k, v in thr.get("rules", {}).items():
        rules[k] = rules.get(k, 0) + v
    return {
        "schema": _DETERMINISM_SCHEMA,
        "model": name,
        "graph": {
            "n_eqns": det.get("n_eqns", 0),
            "n_pool_buffers": det.get("n_pool_buffers", 0),
            "n_pool_writes": det.get("n_pool_writes", 0),
            "n_canonical_writes": det.get("n_canonical_writes", 0),
            "n_rng_sites": det.get("n_rng_sites", 0),
            "n_overlap_pairs": det.get("n_overlap_pairs", 0),
            "n_proven_disjoint": det.get("n_proven_disjoint", 0),
            "n_donated_args": det.get("n_donated_args", 0),
            "n_alias_outputs": det.get("n_alias_outputs", 0),
        },
        "threads": {
            "n_files": thr.get("n_files", 0),
            "n_classes": thr.get("n_classes", 0),
            "n_threaded_classes": thr.get("n_threaded_classes", 0),
            "n_shared_paths": thr.get("n_shared_paths", 0),
            "n_lock_attrs": thr.get("n_lock_attrs", 0),
        },
        "rules": rules,
        "n_findings": len(fnd),
        "max_severity": (str(max(f.severity for f in fnd))
                         if fnd else None),
        "note": "regenerate: python -m paddle_tpu.analysis "
                "--write-manifests",
    }


def write_determinism_manifest(name, report):
    os.makedirs(determinism_manifest_dir(), exist_ok=True)
    data = build_determinism_manifest(name, report)
    with open(determinism_manifest_path(name), "w") as f:
        json.dump(data, f, indent=1, sort_keys=True)
        f.write("\n")
    return data


def manifest_drift(fresh, committed, path=""):
    """Recursive diff of a regenerated manifest dict vs the committed
    one. Returns ["path: committed -> fresh", ...] — empty means the
    committed file is current. The CLI's --check mode fails CI on any
    entry, so stale manifests can't silently re-baseline the lint."""
    if committed is None and isinstance(fresh, dict):
        # a manifest is always a dict, so a None here is the missing
        # FILE — a None VALUE (e.g. max_severity on a clean model)
        # falls through to the scalar compare below
        return [f"{path or '<manifest>'}: missing committed file"]
    if isinstance(fresh, dict) and isinstance(committed, dict):
        out = []
        for k in sorted(set(fresh) | set(committed)):
            sub = f"{path}.{k}" if path else str(k)
            if k not in fresh:
                out.append(f"{sub}: {committed[k]!r} -> <gone>")
            elif k not in committed:
                out.append(f"{sub}: <absent> -> {fresh[k]!r}")
            else:
                out.extend(manifest_drift(fresh[k], committed[k], sub))
        return out
    if isinstance(fresh, list) and isinstance(committed, list):
        if fresh != committed:
            return [f"{path}: list changed ({len(committed)} -> "
                    f"{len(fresh)} entries)"]
        return []
    if fresh != committed:
        return [f"{path}: {committed!r} -> {fresh!r}"]
    return []
