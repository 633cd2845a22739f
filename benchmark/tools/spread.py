"""The spread of each metric over the runs of a set, as the bounds are
set from it: the distance between the first and the third quartile
(``statistics.quantiles(values, n=4)``) as a share of the median.

    python benchmark/tools/spread.py <set1 result lines> [<set2 ...>]

Each file holds the last stdout lines of the runs of one set, one JSON
object a line (lines that are no result are skipped). Prints, for every
metric, each set's median and spread, the wider spread, five times it,
and the second set's median against the first's.
"""

from __future__ import annotations

import json
import sys
from statistics import median, quantiles


def results(path: str) -> list:
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line.startswith("{"):
                continue
            try:
                row = json.loads(line)
            except ValueError:
                continue
            if "metrics" in row and "correct" in row:
                out.append(row)
    return out


def spread(values: list) -> float:
    q1, _, q3 = quantiles(values, n=4)
    return (q3 - q1) / median(values)


def main(argv) -> int:
    sets = [results(p) for p in argv]
    names = sorted({n for rows in sets for r in rows for n in r["metrics"]})
    for rows, path in zip(sets, argv):
        bad = sum(not r["correct"] for r in rows)
        print(f"{path}: {len(rows)} runs, {bad} not correct")
    for name in names:
        meds, spreads = [], []
        for rows in sets:
            vals = [r["metrics"][name]["value"] for r in rows
                    if name in r["metrics"]]
            if len(vals) < 2:
                continue
            meds.append(median(vals))
            spreads.append(spread(vals))
        if not spreads:
            continue
        line = (f"{name}: medians {[round(m, 4) for m in meds]} spreads "
                f"{[round(s, 4) for s in spreads]} widest "
                f"{max(spreads):.4f} x5 {5 * max(spreads):.4f}")
        if len(meds) > 1:
            line += f" second/first {meds[1] / meds[0] - 1:+.4f}"
        print(line)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
