#!/usr/bin/env python3
"""Whose idle time is it? One traced run of a cell, on a TPU only, with the
program's own stages (``pw:<stage>``, ``pathway_tpu.internals.tracing``)
read from the same trace as the device's operations:

    python3 benchmark/dev/gaps.py <cell> <seed> <seconds>

Every gap between operations on the fullest chip is laid over the stages of
the thread that commits; each instant of it belongs to the innermost stage
open then. ``by_stage`` splits the idle seconds exactly; ``by_gap`` gives
each whole gap to the stage that holds most of it, as ``trace.idle_gaps``
does with the benchmark's own spans. ``(no stage)`` is idle time under no
``pw:`` stage. Also prints the program's stage table of the whole run: the
stages a metric reads cover all of it, the rest (``op.*``, the pieces of an
embed call, ``commit.after``, ``commit.device_stage``) only the traced
seconds, since they are recorded only while a profiler session runs. With
it the chunks of the batcher: how many rows a chunk held and on what share
of them ``sizer()`` cut the step.
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

STAGE_PREFIX = "pw:"
NO_STAGE = "(no stage)"


def load_stage_events(trace_dir: str) -> list[trace_mod.Event]:
    """The ``pw:`` events of the newest trace under ``trace_dir``, which
    ``trace.load_events`` leaves out."""
    import glob

    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    data = ProfileData.from_file(paths[-1])
    # a host thread is a line, and every Python thread's line has the same
    # name: its place among the plane's lines tells them apart
    return [
        trace_mod.Event(plane.name, f"{line.name}#{i}", ev.name, ev.start_ns, ev.duration_ns)
        for plane in data.planes
        for i, line in enumerate(plane.lines)
        for ev in line.events
        if ev.name.startswith(STAGE_PREFIX)
    ]


def commit_thread(stages: list[trace_mod.Event]) -> tuple[str, str] | None:
    """The (plane, line) that holds most ``pw:commit`` events."""
    counts: dict[tuple[str, str], int] = {}
    for e in stages:
        if e.name == STAGE_PREFIX + "commit":
            counts[(e.plane, e.line)] = counts.get((e.plane, e.line), 0) + 1
    return max(counts, key=counts.get) if counts else None


def innermost_segments(stages: list[trace_mod.Event]) -> list[tuple[float, float, str]]:
    """One thread's nested stages as segments that do not overlap, each
    under the name of the innermost stage open in it, in time order."""
    spans = sorted((e.start_ns, -(e.start_ns + e.dur_ns), e.name[len(STAGE_PREFIX):]) for e in stages)
    out: list[tuple[float, float, str]] = []
    stack: list[tuple[float, str]] = []  # (end, name) of the open stages
    cursor = 0.0

    def emit(until: float) -> None:
        nonlocal cursor
        if stack and until > cursor:
            out.append((cursor, until, stack[-1][1]))
        cursor = max(cursor, until)

    for start, neg_end, name in spans:
        while stack and stack[-1][0] <= start:
            emit(stack[-1][0])
            stack.pop()
        emit(start)
        cursor = max(cursor, start)
        stack.append((-neg_end, name))
    while stack:
        emit(stack[-1][0])
        stack.pop()
    return out


def stage_gaps(device_events: list[trace_mod.Event], stages: list[trace_mod.Event]) -> dict:
    """Idle seconds of the fullest chip by innermost stage: split exactly
    (``by_stage``) and gap by gap to the stage holding most (``by_gap``)."""
    busy = trace_mod.busy_seconds(device_events)
    if not busy:
        return {}
    plane = max(busy, key=busy.get)
    ops = trace_mod._union([(e.start_ns, e.start_ns + e.dur_ns) for e in trace_mod._ops(device_events, plane)])
    thread = commit_thread(stages)
    segments = innermost_segments([e for e in stages if (e.plane, e.line) == thread])
    by_stage: dict[str, float] = {}
    by_gap: dict[str, float] = {}
    first = 0
    for (_, gap_start), (gap_end, _) in zip(ops, ops[1:]):
        while first < len(segments) and segments[first][1] <= gap_start:
            first += 1
        cover: dict[str, float] = {}
        i = first
        while i < len(segments) and segments[i][0] < gap_end:
            lap = min(gap_end, segments[i][1]) - max(gap_start, segments[i][0])
            if lap > 0:
                cover[segments[i][2]] = cover.get(segments[i][2], 0.0) + lap
            i += 1
        gap = gap_end - gap_start
        cover[NO_STAGE] = gap - sum(cover.values())
        for name, lap in cover.items():
            by_stage[name] = by_stage.get(name, 0.0) + lap / 1e9
        winner = max(cover, key=cover.get)
        by_gap[winner] = by_gap.get(winner, 0.0) + gap / 1e9
    idle = sum(by_gap.values())
    # the longest stretches of the chip's traced span with no stage open on
    # the commit thread, each with the stages before and after it
    bare = []
    edges = [(ops[0][0], ops[0][0], "(trace start)")] + segments + [(ops[-1][1], ops[-1][1], "(trace end)")]
    for (_, end_a, name_a), (start_b, _, name_b) in zip(edges, edges[1:]):
        lo, hi = max(end_a, ops[0][0]), min(start_b, ops[-1][1])
        if hi - lo > 1e6:
            bare.append([(lo - ops[0][0]) / 1e9, (hi - lo) / 1e9, name_a, name_b])
    bare.sort(key=lambda row: -row[1])

    def ranked(totals: dict[str, float]) -> list[list]:
        return [[n, s] for n, s in sorted(totals.items(), key=lambda kv: -kv[1]) if s > 0]

    return {
        "chip": plane,
        "thread": list(thread) if thread else None,
        "busy_s": busy[plane],
        "idle_s": idle,
        "by_stage": ranked(by_stage),
        "by_gap": ranked(by_gap),
        "no_stage_share": by_stage.get(NO_STAGE, 0.0) / idle if idle else None,
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
        "sizer_narrowed_share": chunks["counts"]["narrowed"] / chunks["calls"] if chunks else None,
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
        # and then deletes it: take the stages and the stage table here
        kept["table"] = tracing.stage_totals()
        kept["stages"] = load_stage_events(trace_dir)
        kept["device"] = load_events(trace_dir)
        return kept["device"]

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
        "idle_gaps_by_bench_spans": result.get("breakdown", {}).get("idle_gaps"),
        "gaps": stage_gaps(kept["device"], kept["stages"]) if kept else None,
        "table": stage_table(kept["table"]) if kept else None,
    }
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", f"gaps_{workload}.json"), "w") as fh:
        json.dump(out, fh, indent=1)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
