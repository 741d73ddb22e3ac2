#!/usr/bin/env python3
"""Spreads of a cell's end-to-end metrics over two sets of runs, the way
the contract reads them: per set, the distance between the first and the
third quartile (`statistics.quantiles(values, n=4)`) as a share of the
median; per metric the wider of the two; a bound is about five times the
widest over the cells, never under 1%.

    python3 benchmarks/tools/spread.py chiprun_out/call3/m_A*.out -- chiprun_out/call3/m_B*.out
"""
from __future__ import annotations

import json
import statistics
import sys


def last_line(path: str) -> dict:
    with open(path) as f:
        return json.loads(f.read().rstrip("\n").split("\n")[-1])


def spread(values) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main() -> None:
    args = sys.argv[1:]
    cut = args.index("--")
    sets = [[last_line(p) for p in args[:cut]],
            [last_line(p) for p in args[cut + 1:]]]
    for s in sets:
        assert all(line["correct"] for line in s), "a run is not correct"
    for name in sets[0][0]["metrics"]:
        per_set = [[line["metrics"][name]["value"] for line in s]
                   for s in sets]
        meds = [statistics.median(v) for v in per_set]
        # set-up: each side's first run compiles and is read apart
        skip = 1 if name == "setup_s" else 0
        spreads = [spread(v[skip:]) for v in per_set]
        print(f"{name}: medians {meds[0]:.6g} {meds[1]:.6g} "
              f"(second/first {meds[1] / meds[0] - 1:+.3%}), spreads "
              f"{spreads[0]:.3%} {spreads[1]:.3%}, five times the wider "
              f"{5 * max(spreads):.2%}; values "
              f"{[round(x, 3) for v in per_set for x in v]}")
    print("memory_peak_bytes",
          sorted({line["device"]["memory_peak_bytes"]
                  for s in sets for line in s}))


if __name__ == "__main__":
    main()
