"""Run one cell traced and write what its trace holds, to be read by hand
before a reader is written against it:

    python -m benchmark.rehearse.dump_trace <cell> <seed> [seconds]

writes chiprun_out/<cell>.ops.txt: the device operations by total time, each
with its calls (a custom call with its operands' types, by which a kernel
is told), and the result line.
"""
import json
import os
import sys

from .. import run as harness


def main():
    cell, seed = sys.argv[1], int(sys.argv[2])
    seconds = float(sys.argv[3]) if len(sys.argv) > 3 else 6.0
    kept = {}
    orig = harness.result_of

    def keep(run, dev):
        kept["traced"] = run.traced
        return orig(run, dev)

    harness.result_of = keep
    result = harness.run_cell(cell, seed, seconds, 1)
    os.makedirs("chiprun_out", exist_ok=True)
    total = {}
    for start, end, name, text in kept["traced"]["all_ops"]:
        key = name
        if "custom-call(" in text:
            key += " <- " + text.split("custom-call(", 1)[1].split(
                "), custom_call")[0]
        t = total.setdefault(key, [0.0, 0])
        t[0] += (end - start) / 1e9
        t[1] += 1
    with open(os.path.join("chiprun_out", cell + ".ops.txt"), "w") as f:
        f.write(json.dumps(result) + "\n")
        for name, (sec, n) in sorted(total.items(), key=lambda kv: -kv[1][0]):
            f.write(f"{sec:.6f}s x{n} {name[:600]}\n")
    print(json.dumps(result))


if __name__ == "__main__":
    main()
