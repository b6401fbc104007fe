#!/usr/bin/env python3
"""What a stage costs: nanoseconds an enter-and-exit of
``pathway_tpu.internals.tracing.stage`` with no commit open on the run
thread, and with one open (its exit is then folded into the commit's record
on the time line, where the program has one).

    python3 tools/stage_cost.py [checkout ...]

Each checkout (this one where none is named) is timed in a process of its
own, in turn and twice round, so that two commits are read on one host in
one call. No JAX, no chip: the host's clock alone.
"""

from __future__ import annotations

import json
import os
import statistics
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PROGRAM = """
import json, time
from pathway_tpu.internals import tracing

def per_stage(n=200_000):
    stage, now = tracing.stage, time.perf_counter_ns
    best = None
    for _ in range(5):
        t0 = now()
        for _ in range(n):
            with stage("cost.probe", rows=1):
                pass
        took = (now() - t0) / n
        best = took if best is None else min(best, took)
    return best

root = tracing.STAGES.begin_run()
outside = per_stage()
timeline = getattr(tracing, "commit_timeline", None)
commit_stage = getattr(tracing, "commit_stage", None) or (lambda: tracing.stage("commit"))
with commit_stage():
    inside = per_stage()
tracing.STAGES.end_run(root)
print(json.dumps({"no_commit_open_ns": outside, "commit_open_ns": inside,
                  "has_time_line": timeline is not None}))
"""


def main(argv: list[str]) -> int:
    checkouts = [os.path.abspath(path) for path in argv] or [ROOT]
    readings: dict[str, list[dict]] = {path: [] for path in checkouts}
    for _ in range(2):
        for path in checkouts:
            out = subprocess.run(
                [sys.executable, "-c", PROGRAM], env=dict(os.environ, PYTHONPATH=path), cwd=path,
                capture_output=True, text=True, check=True,
            )
            readings[path].append(json.loads(out.stdout.strip().splitlines()[-1]))
    for path, rows in readings.items():
        print(json.dumps({
            "checkout": path,
            "has_time_line": rows[0]["has_time_line"],
            "no_commit_open_ns": [round(r["no_commit_open_ns"], 1) for r in rows],
            "commit_open_ns": [round(r["commit_open_ns"], 1) for r in rows],
            "fold_ns": round(statistics.mean(r["commit_open_ns"] - r["no_commit_open_ns"] for r in rows), 1),
        }))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
