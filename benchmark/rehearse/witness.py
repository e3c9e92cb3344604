"""A witness outside the program, for telling a pause of the machine from a
stall of the program:

    python -m benchmark.rehearse.witness <out.jsonl> <stop file>

Sleeps 1 ms in a loop and notes every time it woke more than 5 ms late, as
(seconds on `time.perf_counter()`, which all processes of a machine share;
the gap's seconds), one line each as it happens, until the stop file is
there. It imports nothing of the program and never touches JAX. A gap it
shares with a run's stalled horizon, to a tenth of a millisecond, is the
machine's: every process stood still (PERF.md section 6, PR 36: pauses of
107-117 ms, up to two in 20 s on some machines, none for minutes on
others). `sets` keeps one beside its runs.
"""
import json
import os
import sys
import time

LATE_S = 0.005


def main():
    out, stop = sys.argv[1:3]
    prev, n = time.perf_counter(), 0
    with open(out, "w") as f:
        while True:
            time.sleep(0.001)
            now = time.perf_counter()
            if now - prev > LATE_S:
                f.write(json.dumps([prev, now - prev]) + "\n")
                f.flush()
            prev, n = now, n + 1
            if n % 200 == 0 and os.path.exists(stop):
                break


if __name__ == "__main__":
    main()
