"""Helpers of the benchmark's tests: a temporary copy of the benchmark's
data with the tiny cells of `tiny/` laid over it."""
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
SEED = 3000000019            # over 2**31, as the driver's seeds are


def make_root(tmp):
    """(root, here) of a copy that holds the real benchmark's files and the
    tiny configurations, cells and mixes added as files, none edited."""
    root = str(tmp)
    here = os.path.join(root, "benchmark")
    shutil.copytree(os.path.join(REPO, "benchmark"), here,
                    ignore=shutil.ignore_patterns("__pycache__"))
    tiny = os.path.join(HERE, "tiny")
    for sub in ("configs", "traffic", "workloads", "metrics"):
        for f in os.listdir(os.path.join(tiny, sub)):
            dst = os.path.join(here, sub, f)
            assert not os.path.exists(dst), f"{f} would edit a file"
            shutil.copy(os.path.join(tiny, sub, f), dst)
    shutil.copy(os.path.join(tiny, "BENCHMARK.json"),
                os.path.join(root, "BENCHMARK.json"))
    return root, here


def run(root_here, name, trace=0, faults=None, seconds=0.5, seed=SEED):
    from benchmark import run as harness

    root, here = root_here
    return harness.run_cell(name, seed, seconds, trace, device_check=False,
                            faults=faults, root=root, here=here)
