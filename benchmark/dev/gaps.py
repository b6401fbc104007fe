#!/usr/bin/env python3
"""Whose idle time is it? One traced run of a cell, on a TPU only, with the
program's own stages (``pw:<stage>``, ``pathway_tpu.internals.tracing``)
read from the same trace as the device's operations:

    python3 benchmark/dev/gaps.py <cell> <seed> <seconds>

Every gap between operations on the fullest chip is laid over the stages of
the thread that commits; each instant of it belongs to the innermost stage
open then (``trace.idle_split``, which the result line's ``idle_gaps`` come
from too). ``(no stage)`` is idle time under no ``pw:`` stage. Also prints
the program's stage table of the whole run: the stages a metric reads cover
all of it, the rest (``op.*``, the pieces of an embed call, ``commit.after``,
``commit.device_stage``) only the traced seconds, since they are recorded
only while a profiler session runs. With it the chunks of the batcher: how
many rows a chunk held and what share of them held fewer than the cap.
Writes ``chiprun_out/gaps_<cell>.json`` and prints it.
"""

from __future__ import annotations

import time

T_START = time.time()

import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, ROOT]

import trace as trace_mod  # noqa: E402

NO_STAGE = "(no stage)"


def stage_gaps(events: list[trace_mod.Event]) -> dict:
    """``trace.idle_split``'s idle seconds of the fullest chip, by innermost
    stage alone (``by_stage``; the ledger's ``idle_gaps`` go on from
    ``(no stage)`` to the benchmark's spans), and the longest stretches with
    no stage open on the thread that commits."""
    split = trace_mod.idle_split(events)
    if not split:
        return {}
    ops, segments, idle = split["ops"], split["stages"], split["idle_ns"]
    by_stage = {
        name[len(trace_mod.STAGE_PREFIX):]: ns / 1e9
        for name, ns in split["by_name"].items()
        if name.startswith(trace_mod.STAGE_PREFIX)
    }
    by_stage[NO_STAGE] = split["no_stage_ns"] / 1e9
    # the longest stretches of the chip's traced span with no stage open on
    # the commit thread, each with the stages before and after it
    bare = []
    edges = [(ops[0][0], ops[0][0], "(trace start)")] + segments + [(ops[-1][1], ops[-1][1], "(trace end)")]
    for (_, end_a, name_a), (start_b, _, name_b) in zip(edges, edges[1:]):
        lo, hi = max(end_a, ops[0][0]), min(start_b, ops[-1][1])
        if hi - lo > 1e6:
            bare.append([(lo - ops[0][0]) / 1e9, (hi - lo) / 1e9, name_a, name_b])
    bare.sort(key=lambda row: -row[1])
    return {
        "chip": split["plane"],
        "thread": list(split["thread"]) if split["thread"] else None,
        "busy_s": split["busy_s"],
        "idle_s": idle / 1e9,
        "by_stage": [[n, s] for n, s in sorted(by_stage.items(), key=lambda kv: -kv[1]) if s > 0],
        "no_stage_share": split["no_stage_ns"] / idle if idle else None,
        "longest_without_a_stage": bare[:8],
    }


def stage_table(totals: dict) -> dict:
    """The run thread's stages by self time, and how much of the run's wall
    lies in a stage other than the run's own."""
    wall = totals["run_wall_ns"]
    rows = sorted(totals["stages"].items(), key=lambda kv: -kv[1]["self_ns"])
    own = totals["stages"].get("run", {}).get("self_ns", 0)
    chunks = totals["stages"].get("udf.batch")
    return {
        "run_wall_s": wall / 1e9,
        "rows_per_chunk": chunks["counts"]["rows"] / chunks["calls"] if chunks else None,
        "short_chunk_share": chunks["counts"]["narrowed"] / chunks["calls"] if chunks else None,
        "in_stages_share": 1.0 - own / wall if wall else None,
        "blocked_on_device_share": sum(r["self_ns"] for _, r in rows if r["wait"]) / wall if wall else None,
        "stages": [
            [name, r["calls"], r["total_ns"] / 1e9, r["self_ns"] / 1e9, r["wait"], r["counts"]] for name, r in rows
        ],
        "threads": {
            thread: [[name, r["calls"], r["total_ns"] / 1e9, r["counts"]] for name, r in table.items()]
            for thread, table in totals["threads"].items()
        },
    }


def main(argv: list[str]) -> int:
    import run

    run.configure_compile_cache()

    import harness
    import jax

    from pathway_tpu.internals import tracing

    workload, seed, seconds = argv[0], int(argv[1]), float(argv[2])
    cell = harness.load_cell(ROOT, workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        sys.exit(f"gaps: {workload} needs {cell.chips} TPU chip(s); JAX reports {devices[0].platform!r}")
    kept: dict = {}
    load_events = trace_mod.load_events

    def keeping(trace_dir: str):
        # the harness reads the trace once, right after pw.run() returns,
        # and then deletes it: take the events and the stage table here
        kept["table"] = tracing.stage_totals()
        kept["events"] = load_events(trace_dir)
        return kept["events"]

    trace_mod.load_events = keeping
    try:
        result = harness.run_cell(cell, seed, seconds, True, devices, T_START)
    finally:
        trace_mod.load_events = load_events
    out = {
        "cell": workload,
        "seed": seed,
        "seconds": seconds,
        "correct": result["correct"],
        "metrics": {name: m["value"] for name, m in result["metrics"].items()},
        "idle_gaps": result.get("breakdown", {}).get("idle_gaps"),
        "gaps": stage_gaps(kept["events"]) if kept else None,
        "table": stage_table(kept["table"]) if kept else None,
    }
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", f"gaps_{workload}.json"), "w") as fh:
        json.dump(out, fh, indent=1)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
