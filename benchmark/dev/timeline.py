#!/usr/bin/env python3
"""Where did a query wait, commit by commit? One traced run of a cell, on a
TPU only, read through the program's own time line
(``pathway_tpu.internals.tracing.commit_timeline()``: one record a commit):

    python3 benchmark/dev/timeline.py <cell> <seed> <seconds>

Writes ``chiprun_out/timeline_<cell>_<seed>.txt``: a line a commit (its
time, when it began since the run's first commit and how long it took, and
the stages folded into it as ``name first_start+span/calls`` in ms from the
commit's start, full collections marked) and a line a query of the window
(its commit and the four segments of ``layer_metrics/query_path.py``, ms).
Prints, and writes to ``chiprun_out/timeline_<cell>_<seed>.json``, the
summary: commits; full collections, those inside a commit of the run thread
and all of every thread's table (and how many collections of each generation
the process has made, set-up included: the program's hook is called for
every one); the segments' means and medians over the window's queries and
the residual of the identity (the four means' sum less the mean of ``ack -
sent``: 0 to the microsecond, or the time line is wrong); for an answerer's
cell the commits matched to the trace and the ``chat.fetch`` span of their
calls, to hold beside the result line's prefill and decode ms a call; the
traced run's end-to-end numbers and the result line's metrics.
"""

from __future__ import annotations

import time

T_START = time.time()

import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, ROOT]

import numpy as np  # noqa: E402

import readers  # noqa: E402

LAYER_METRICS = os.path.join(BENCH, "layer_metrics")
GC = "gc.full"


def span_ms(folded: dict) -> float:
    """A folded stage's first start to its last end, ms."""
    return (folded["last_t1_ns"] - folded["first_t0_ns"]) / 1e6


def commit_lines(commits: list[dict]) -> list[str]:
    if not commits:
        return []
    origin = commits[0]["t0_ns"]
    lines = []
    for record in commits:
        t0 = record["t0_ns"]
        stages = sorted(record["stages"].items(), key=lambda kv: kv[1]["first_t0_ns"])
        folded = " ".join(
            f"{'*' if name == GC else ''}{name} {(s['first_t0_ns'] - t0) / 1e6:.2f}+{span_ms(s):.2f}/{s['calls']}"
            for name, s in stages
        )
        lines.append(
            f"commit {record['time']} at {(t0 - origin) / 1e6:.1f} ms took {(record['t1_ns'] - t0) / 1e6:.2f} ms; {folded}"
        )
    return lines


def summarize(ctx, commits: list[dict], serve: str) -> tuple[dict, list[str]]:
    """The summary and the lines of the text file."""
    from pathway_tpu.internals import tracing

    query_path = readers.load_module("query_path", os.path.join(LAYER_METRICS, "query_path.py"))
    in_commits = [r["stages"][GC] for r in commits if GC in r["stages"]]
    totals = tracing.stage_totals()
    tables = {"run thread": totals["stages"], **totals["threads"]}
    out: dict = {
        # every collection, young ones too, calls the program's gc hook twice
        "collections_since_the_process_began": [g["collections"] for g in gc.get_stats()],
        "commits": len(commits),
        "full_collections": {
            "in_commits": sum(s["calls"] for s in in_commits),
            "in_commits_span_ms": sum(span_ms(s) for s in in_commits),
            "by_thread": {
                thread: {"calls": table[GC]["calls"], "ms": table[GC]["total_ns"] / 1e6}
                for thread, table in tables.items() if GC in table
            },
        },
    }
    lines = commit_lines(commits)
    queries, cut, why = query_path.segments(ctx.obs, commits, serve)
    if cut is None:
        out["queries"] = why
    else:
        seen = ctx.obs.queries
        whole = (seen.ack[queries] - seen.sent[queries]) * 1e3
        out["queries"] = {
            "cut": len(queries),
            "mean_ms": dict(zip(query_path.SEGMENTS, cut.mean(axis=0).tolist())),
            "median_ms": dict(zip(query_path.SEGMENTS, np.median(cut, axis=0).tolist())),
            "mean_ack_less_sent_ms": float(whole.mean()),
            "identity_residual_ms": float(cut.mean(axis=0).sum() - whole.mean()),
            "worst_query_residual_ms": float(np.abs(cut.sum(axis=1) - whole).max()),
        }
        lines += [
            f"query {i} commit {int(seen.commit[i])} " + " ".join(f"{name} {v:.2f}" for name, v in zip(query_path.SEGMENTS, row))
            for i, row in zip(queries, cut)
        ]
    if serve == "chat.batch" and ctx.trace is not None:
        device = readers.load_module("chat_call_device", os.path.join(LAYER_METRICS, "chat_call_device.py"))
        pairs, offset, spread = device.match_commits(
            ctx.trace["events"], commits, ctx.trace["start"], ctx.trace["stop"]
        )
        calls = [record["stages"] for record, _event in pairs if device.CALL_STAGE in record["stages"]]
        made = sum(s[device.CALL_STAGE]["calls"] for s in calls)
        out["matched_calls"] = {
            "commits_matched": len(pairs),
            "clock_distance_spread_us": None if spread is None else spread / 1e3,
            "calls": made,
            "chat_fetch_span_ms_a_call": sum(span_ms(s["chat.fetch"]) for s in calls) / made if made else None,
            "chat_batch_span_ms_a_call": sum(span_ms(s[device.CALL_STAGE]) for s in calls) / made if made else None,
        }
    return out, lines


def _holds(value, limit) -> bool:
    return limit is not None and value <= limit


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
        sys.exit(f"timeline: {workload} needs {cell.chips} TPU chip(s); JAX reports {devices[0].platform!r}")
    serve = next(
        (m["params"]["serve"] for m in cell.per_layer if m.get("reader") == "query_path"), "chat.batch"
    )
    kept: dict = {}
    per_layer_metrics, end_to_end_metrics = harness.per_layer_metrics, harness.end_to_end_metrics

    def keeping(cell_, ctx):
        # the readers' own context, and the time line before the comparison
        # (which may run the program again) can begin another run
        kept["summary"], kept["lines"] = summarize(ctx, tracing.commit_timeline(), serve)
        return per_layer_metrics(cell_, ctx)

    def keeping_end_to_end(*args):
        # a traced line holds the per-layer metrics alone
        kept["end_to_end"] = end_to_end_metrics(*args)
        return kept["end_to_end"]

    harness.per_layer_metrics, harness.end_to_end_metrics = keeping, keeping_end_to_end
    try:
        result = harness.run_cell(cell, seed, seconds, True, devices, T_START)
    finally:
        harness.per_layer_metrics, harness.end_to_end_metrics = per_layer_metrics, end_to_end_metrics
    out = {
        "cell": workload, "seed": seed, "seconds": seconds, "correct": result["correct"],
        "failed": result["failed"], "compared_off": {k: v for k, v in result["compared"].items() if not _holds(*v)},
        "end_to_end_traced": kept.get("end_to_end"), **kept.get("summary", {}),
        "metrics": {name: m["value"] for name, m in result["metrics"].items()},
        "idle_gaps": result.get("breakdown", {}).get("idle_gaps"),
    }
    directory = os.path.join(ROOT, "chiprun_out")
    os.makedirs(directory, exist_ok=True)
    stem = os.path.join(directory, f"timeline_{workload}_{seed}")
    with open(stem + ".txt", "w") as fh:
        fh.write("\n".join(kept.get("lines", [])) + "\n")
    with open(stem + ".json", "w") as fh:
        json.dump(out, fh, indent=1)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
