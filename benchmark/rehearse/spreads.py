"""The spreads a bound is set from, out of two sets of runs of one cell:

    python -m benchmark.rehearse.spreads chiprun_out/set.<cell>.A.jsonl chiprun_out/set.<cell>.B.jsonl

For each metric: each set's median and spread (the distance between the first
and third quartile of `statistics.quantiles(values, n=4)` as a share of the
median), the wider of the two, five times that, and how far the second set's
median lies from the first's; and, first, the machine's pauses that `sets`
saw inside each run's window, run by run, so that a far-off run can be held
against them.
"""
import json
import statistics
import sys


def spread(values):
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def metric_values(path):
    out = {}
    with open(path) as f:
        for row in map(json.loads, f):
            line = row["line"]
            if row["rc"] != 0 or not line:
                print(f"{path}: seed {row['seed']} gave no result "
                      f"(rc {row['rc']})")
                continue
            if not line["correct"]:
                print(f"{path}: seed {row['seed']} is not correct")
            for name, m in line["metrics"].items():
                out.setdefault(name, []).append(m["value"])
            out.setdefault("wall_s", []).append(row["wall_s"])
    return out


def pauses(path):
    with open(path) as f:
        return [round(row.get("paused_s") or 0.0, 4)
                for row in map(json.loads, f)]


def main():
    a, b = (metric_values(p) for p in sys.argv[1:3])
    print(json.dumps({"paused_s": [pauses(p) for p in sys.argv[1:3]]}))
    for name in a:
        va, vb = a[name], b.get(name, [])
        if len(va) < 3 or len(vb) < 3:
            continue
        sa, sb = spread(va), spread(vb)
        ma, mb = statistics.median(va), statistics.median(vb)
        print(json.dumps({
            "metric": name, "runs": [len(va), len(vb)],
            "medians": [ma, mb], "spreads": [sa, sb],
            "widest": max(sa, sb), "five_times": 5 * max(sa, sb),
            "second_median_off_by": (mb - ma) / ma,
            "min": min(va + vb), "max": max(va + vb)}))


if __name__ == "__main__":
    main()
