"""Finds everything that belongs to a cell by the names in BENCHMARK.json:
the configuration's file, the traffic mix's file, the cell's own file, the
family's module and the per-layer metrics' readers. A later PR adds files
and entries; nothing here names a cell, a configuration or a metric.
"""
import importlib
import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class BenchmarkError(RuntimeError):
    """What makes a run impossible: exits non-zero with no result line."""


def _json(path):
    if not os.path.exists(path):
        raise BenchmarkError(f"missing file {path}")
    with open(path) as f:
        return json.load(f)


def load_benchmark(root=ROOT):
    return _json(os.path.join(root, "BENCHMARK.json"))


def load_peaks(device_kind, here=HERE):
    table = _json(os.path.join(here, "peaks.json"))
    if device_kind not in table or device_kind.startswith("_"):
        raise BenchmarkError(
            f"device kind {device_kind!r} is not in benchmark/peaks.json: "
            "a share of a peak cannot be computed for it")
    return table[device_kind]


def _place_env(env):
    """A cell's file may state environment variables (`env`) that its run
    is made under. JAX reads its own when it is first imported, which is
    why they are placed here, before the family's module brings JAX in.
    Once JAX is there nothing is placed: returns the names that came too
    late."""
    if "jax" in sys.modules:
        return [k for k, v in env.items() if os.environ.get(k) != v]
    os.environ.update(env)
    return []


class Cell:
    """One entry of `workloads`, with all that its names lead to."""

    def __init__(self, name, root=ROOT, here=HERE):
        bench = load_benchmark(root)
        entry = next((w for w in bench["workloads"] if w["name"] == name),
                     None)
        if entry is None:
            raise BenchmarkError(
                f"no workload {name!r} in BENCHMARK.json (it has "
                f"{[w['name'] for w in bench['workloads']]})")
        conf = next(c for c in bench["configs"]
                    if c["name"] == entry["config"])
        self.name, self.chips = name, entry["chips"]
        self.bench, self.here = bench, here
        self.config = _json(os.path.join(root, conf["file"]))
        self.traffic = _json(os.path.join(
            here, "traffic", entry["traffic"] + ".json"))
        self.job = _json(os.path.join(here, "workloads", name + ".json"))
        self.env_late = _place_env(self.job.get("env", {}))
        self.family = importlib.import_module(
            f"benchmark.families.{self.config['family']}")

    def _reports(self, metric):
        listed = metric.get("workloads")
        return listed is None or self.name in listed

    def end_to_end(self):
        return [m for m in self.bench["end_to_end"] if self._reports(m)]

    def per_layer(self):
        e2e = {m["name"] for m in self.end_to_end()}
        return [m for m in self.bench["per_layer"]
                if self._reports(m) and m["moves"] in e2e]

    def reader(self, metric_name):
        """The metric's own reader: benchmark/metrics/<name>.py with a
        `read(run)` that returns a number, or None when it finds nothing
        to read."""
        path = os.path.join(self.here, "metrics", metric_name + ".py")
        if not os.path.exists(path):
            raise BenchmarkError(f"metric {metric_name!r} has no reader "
                                 f"at {path}")
        spec = importlib.util.spec_from_file_location(
            "benchmark.metrics." + metric_name.replace(".", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read
