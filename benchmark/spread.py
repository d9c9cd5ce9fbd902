"""Spreads of the runs `tools_sets.sh` made, as the contract reads them:
per set the distance between the first and third quartile
(``statistics.quantiles(values, n=4)``) over the median; per metric the
wider of the two sets; and how the second set's median sits against the
first's.

    python3 benchmark/spread.py chiprun_out/sets/<cell>.jsonl
"""

import json
import statistics
import sys


def spread(values):
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main(path):
    rows = [json.loads(line.replace('"wall_s": ,', '"wall_s": null,'))
            for line in open(path)]
    bad = [r for r in rows if r["rc"] != 0 or not r["result"]["correct"]]
    print("%d runs, %d not correct or failed" % (len(rows), len(bad)))
    names = sorted(rows[0]["result"]["metrics"])
    for name in names:
        sets = {}
        for r in rows:
            sets.setdefault(r["set"], []).append(
                r["result"]["metrics"][name]["value"])
        line = "%-22s" % name
        medians = {}
        for key, values in sorted(sets.items()):
            if name == "setup_s":
                values = values[1:] if key == min(sets) else values
            medians[key] = statistics.median(values)
            line += "  %s: median %.6g spread %.3f%% (n=%d)" % (
                key, medians[key], 100 * spread(values), len(values))
        keys = sorted(medians)
        if len(keys) == 2:
            line += "  B/A %+.3f%%" % (
                100 * (medians[keys[1]] / medians[keys[0]] - 1))
        print(line)
    peaks = [r["result"]["device"]["memory_peak_bytes"] for r in rows]
    walls = [r["wall_s"] for r in rows if r["wall_s"] is not None]
    print("memory_peak_bytes %d..%d; wall %s s" % (
        min(peaks), max(peaks),
        "%.0f..%.0f" % (min(walls), max(walls)) if walls else "not taken"))


if __name__ == "__main__":
    main(sys.argv[1])
