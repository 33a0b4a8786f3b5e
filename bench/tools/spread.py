"""Spread of each metric over sets of runs, as the bounds are set from it.

    python3 bench/tools/spread.py RESULTS.jsonl [RESULTS.jsonl ...]

Each file holds one set: the result lines (the last stdout line of
``bench/run.py``) of its runs, one per line, with a ``"seed"`` key added.
For each metric it prints each set's median and its spread, the distance
between the first and third quartile (``statistics.quantiles(n=4)``) as
a share of the median, and five times the widest spread: the bound that
spread calls for.
"""
from __future__ import annotations

import json
import statistics
import sys


def spread(values: list[float]) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(paths: list[str]) -> int:
    sets = []
    for path in paths:
        with open(path) as f:
            sets.append([json.loads(line) for line in f if line.strip()])
    names = sorted({m for s in sets for r in s for m in r["metrics"]})
    for name in names:
        rows = []
        for s in sets:
            vals = [r["metrics"][name]["value"] for r in s if name in r["metrics"]]
            if len(vals) >= 2:
                rows.append((statistics.median(vals), spread(vals), vals))
        if not rows:
            continue
        widest = max(r[1] for r in rows)
        print(json.dumps({"metric": name, "medians": [r[0] for r in rows],
                          "spreads": [r[1] for r in rows],
                          "five_times_widest": 5 * widest,
                          "values": [r[2] for r in rows]}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
