#!/usr/bin/env python3
"""The spread of a cell's runs, as the contract defines it: for each
metric of each set the distance between the first and third quartile
(``statistics.quantiles(values, n=4)``) as a share of the median.

    python3 benchmarks/tests/spread.py <dir> <tag>

reads the last line of ``<dir>/<tag>_s<set>_r<run>.txt`` (what
``run.py`` printed) and prints, for each metric, each set's median and
spread, the wider spread, five times it (where a bound belongs), how far
the second set's median lies from the first's, and the reading the driver's
check quotes when it cannot tell a change from none: largest less smallest
over the median, with the run farthest from the median left out, of each
set and of all runs together."""

import glob
import json
import re
import statistics
import sys


def last_line(path):
    with open(path) as f:
        lines = [line for line in f.read().splitlines() if line.strip()]
    return json.loads(lines[-1]) if lines else None


def range_without_farthest(values):
    """(largest - smallest) / median of ``values`` less the one farthest
    from their median."""
    median = statistics.median(values)
    kept = sorted(values, key=lambda v: abs(v - median))[:-1] or values
    return (max(kept) - min(kept)) / median


def main(folder, tag):
    sets = {}
    for path in sorted(glob.glob(f"{folder}/{tag}_s*_r*.txt")):
        found = re.search(r"_s(\d+)_r(\d+)\.txt$", path)
        line = last_line(path)
        if not line or "metrics" not in line:
            print("no result in", path)
            continue
        if not line["correct"] or line["failed"]:
            print("NOT CORRECT or failed:", path)
        sets.setdefault(int(found.group(1)), []).append(line)
    names = sorted({n for runs in sets.values() for r in runs for n in r["metrics"]})
    for name in names:
        row, medians, spreads, every = [], [], [], []
        for number, runs in sorted(sets.items()):
            values = [r["metrics"][name]["value"] for r in runs if name in r["metrics"]]
            if name == "setup_s":
                values = values[1:] if number == 1 else values  # the first run compiles
            q1, median, q3 = statistics.quantiles(values, n=4)
            medians.append(median)
            spreads.append((q3 - q1) / median)
            every += values
            row.append(f"set{number}: n={len(values)} median={median:.6g} "
                       f"spread={100 * spreads[-1]:.3f}% "
                       f"range less farthest="
                       f"{100 * range_without_farthest(values):.3f}% "
                       f"min={min(values):.6g} max={max(values):.6g}")
        wider = max(spreads)
        drift = (medians[-1] - medians[0]) / medians[0] if len(medians) > 1 else 0
        print(f"{name}: wider spread {100 * wider:.3f}%, x5 = {500 * wider:.2f}%, "
              f"second median {100 * drift:+.3f}% from the first, all runs' "
              f"range less farthest {100 * range_without_farthest(every):.3f}%")
        for text in row:
            print("    " + text)
    peaks = [r["device"]["memory_peak_bytes"] for runs in sets.values() for r in runs]
    print("memory_peak_bytes:", min(peaks), "-", max(peaks))


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2])
