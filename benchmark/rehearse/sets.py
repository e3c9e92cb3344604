"""One run after another of one cell, each a process of its own:

    python -m benchmark.rehearse.sets <cell> <tag> <seconds> <trace> <seed>...

Appends to chiprun_out/set.<cell>.<tag>.jsonl one line a run: the seed, the
exit code, the run's wall time and its result line; keeps each run's standard
error in chiprun_out/err.<cell>.<tag>.<seed>.txt. `spreads` reads two such
sets. This process never touches JAX: a chip belongs to one process.
"""
import json
import os
import subprocess
import sys
import time


def main():
    cell, tag, seconds, trace = sys.argv[1:5]
    os.makedirs("chiprun_out", exist_ok=True)
    for seed in sys.argv[5:]:
        err = os.path.join("chiprun_out", f"err.{cell}.{tag}.{seed}.txt")
        t0 = time.perf_counter()
        with open(err, "w") as f:
            p = subprocess.run(
                [sys.executable, "-m", "benchmark.run", "--workload", cell,
                 "--seed", seed, "--seconds", seconds, "--trace", trace],
                stdout=subprocess.PIPE, stderr=f, text=True)
        wall = time.perf_counter() - t0
        last = p.stdout.strip().splitlines()[-1:] or [""]
        try:
            line = json.loads(last[0])
        except ValueError:
            line = None
        row = {"seed": int(seed), "rc": p.returncode, "wall_s": wall,
               "line": line}
        with open(os.path.join("chiprun_out", f"set.{cell}.{tag}.jsonl"),
                  "a") as out:
            out.write(json.dumps(row) + "\n")
        shown = dict(row, line=line and {
            k: line[k] for k in ("correct", "attempted", "failed", "metrics")})
        print(json.dumps(shown)[:900], flush=True)
        with open(err) as f:
            print("".join(f.readlines()[-2:])[:300], flush=True)


if __name__ == "__main__":
    main()
