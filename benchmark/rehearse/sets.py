"""One run after another of one cell, each a process of its own:

    python -m benchmark.rehearse.sets <cell> <tag> <seconds> <trace> <seed>...

Appends to chiprun_out/set.<cell>.<tag>.jsonl one line a run: the seed, the
exit code, the run's wall time and its result line; keeps each run's standard
error in chiprun_out/err.<cell>.<tag>.<seed>.txt. `spreads` reads two such
sets. Beside the runs a `witness` notes the machine's own pauses
(chiprun_out/witness.<cell>.<tag>.jsonl): a run's line says how many of
50 ms or more fell into its measured window, and their seconds (`pauses`,
`paused_s`; the window is placed by the line's `setup_s` and `phases`,
counted from the start of the run's process). This process never touches
JAX: a chip belongs to one process.
"""
import json
import os
import subprocess
import sys
import time

PAUSE_S = 0.05


def window_of(line, t0):
    """(start, end) of a run's measured window on this machine's clock,
    from its result line; the whole run where the line does not say."""
    try:
        setup = line.get("setup_s") or line["metrics"]["setup_s"]["value"]
        return t0 + setup, t0 + dict(line["phases"])["window_closed"]
    except (AttributeError, KeyError, TypeError):
        return t0, float("inf")


def main():
    cell, tag, seconds, trace = sys.argv[1:5]
    os.makedirs("chiprun_out", exist_ok=True)
    seen = os.path.join("chiprun_out", f"witness.{cell}.{tag}.jsonl")
    stop = seen + ".stop"
    witness = subprocess.Popen([sys.executable, "-m",
                                "benchmark.rehearse.witness", seen, stop])
    try:
        for seed in sys.argv[5:]:
            run(cell, tag, seconds, trace, seed, seen)
    finally:
        open(stop, "w").close()
        witness.wait(timeout=30)
        os.remove(stop)


def run(cell, tag, seconds, trace, seed, seen):
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
    start, end = window_of(line, t0)
    with open(seen) as f:
        pauses = [g for t, g in map(json.loads, f)
                  if g >= PAUSE_S and start <= t < end]
    row = {"seed": int(seed), "rc": p.returncode, "wall_s": wall,
           "pauses": len(pauses), "paused_s": sum(pauses), "line": line}
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
