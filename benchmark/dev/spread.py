#!/usr/bin/env python3
"""Spreads of a cell's two sets of runs, as ``sets.sh`` left them under
``chiprun_out/``:

    python3 benchmark/dev/spread.py <cell>

For each end-to-end metric and each set: the median and the spread — the
distance between the first and the third quartile
(``statistics.quantiles(values, n=4)``) as a share of the median — and the
second set's median against the first's. ``tight`` is what a bound must be
at least twice of: the mean over the two sets of the spread with each
set's run farthest from its median left out. ``setup_s`` leaves out the
first run of the first set, which compiles.
"""

from __future__ import annotations

import glob
import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def spread(values: list[float]) -> float:
    if len(values) < 2:  # a set of three with its first or its farthest run left out
        return float("nan")
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def trimmed(values: list[float]) -> list[float]:
    median = statistics.median(values)
    far = max(values, key=lambda v: abs(v - median))
    out = list(values)
    out.remove(far)
    return out


def main(cell: str) -> None:
    sets = {}
    for name in "AB":
        paths = sorted(glob.glob(os.path.join(ROOT, "chiprun_out", f"set_{cell}_{name}*.json")))
        sets[name] = [json.load(open(p)) for p in paths]
    print(cell, {s: len(r) for s, r in sets.items()}, "correct:", [r["correct"] for s in "AB" for r in sets[s]])
    for metric in sets["A"][0]["metrics"]:
        rows = []
        for name in "AB":
            values = [r["metrics"][metric]["value"] for r in sets[name]]
            if metric == "setup_s" and name == "A":
                values = values[1:]
            rows.append(values)
        med_a, med_b = statistics.median(rows[0]), statistics.median(rows[1])
        tight = statistics.mean(spread(trimmed(v)) for v in rows)
        print(
            f"{metric}: A median {med_a:.4f} spread {spread(rows[0]):.2%} | B median {med_b:.4f} "
            f"spread {spread(rows[1]):.2%} | all {spread(rows[0] + rows[1]):.2%} | tight {tight:.2%} "
            f"| B/A {med_b / med_a - 1:+.2%}"
        )
        for name, values in zip("AB", rows):
            print("   ", name, [round(v, 2) for v in values])


if __name__ == "__main__":
    main(sys.argv[1])
