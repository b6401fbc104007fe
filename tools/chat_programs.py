#!/usr/bin/env python3
"""What a chat program's execution is made of: one traced run of a benchmark
cell, on a TPU only, and for the programs ``jit_chat_prefill`` and
``jit_chat_decode`` the device time of an execution by operation family:

    python3 <this repo>/tools/chat_programs.py <cell> <seed> <seconds> [compare] [tag]

run from the root of the checkout to measure (the parent's, under
``.checkouts/``, or this one): the benchmark and the program are the
working directory's. An instant of an execution belongs to the innermost
operation open then, so ``while`` holds only what none of its body's
operations cover, and the families of an execution sum to its busy time.
``compare`` 1 also runs the cell's comparison with its reference (``correct``).
Writes ``chiprun_out/programs_<tag or cell>.json`` beside this file's
``tools/`` (the one directory a chip call brings back) and prints it.
"""

from __future__ import annotations

import time

T_START = time.time()

import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402


#: of an operation's name and HLO text, the characters kept; the operations kept a program
OP_TEXT, OPS_KEPT = 240, 40


def split_programs(events, patterns: list[str], trace_mod) -> dict:
    """Per pattern: the executions found in the trace on the chip that ran
    most of them (one cut by the trace's start is among them, short), their
    mean milliseconds, and the mean milliseconds an execution by operation
    family and by single operation (innermost operation)."""
    out = {}
    for pattern in patterns:
        best: tuple[str, list] = ("", [])
        for plane in trace_mod.device_planes(events):
            hits = [e for e in events if e.plane == plane and e.line == "XLA Modules" and pattern in e.name]
            if len(hits) > len(best[1]):
                best = (plane, hits)
        plane, hits = best
        if not hits:
            out[pattern] = None
            continue
        ops = sorted(
            (e.start_ns, e.start_ns + e.dur_ns, e.name) for e in events if e.plane == plane and e.line == "XLA Ops"
        )
        families: dict[str, float] = {}
        single: dict[str, float] = {}
        for hit in hits:
            lo, hi = hit.start_ns, hit.start_ns + hit.dur_ns
            inside = [(s, e, n) for s, e, n in ops if lo <= s < hi]
            for start, end, name in trace_mod.innermost_segments(inside):
                family = trace_mod.op_family(name)
                families[family] = families.get(family, 0.0) + (min(end, hi) - start)
                single[name[:OP_TEXT]] = single.get(name[:OP_TEXT], 0.0) + (min(end, hi) - start)
        n = len(hits)
        out[pattern] = {
            "executions": n,
            "ms_each": [e.dur_ns / 1e6 for e in hits],
            "mean_ms": sum(e.dur_ns for e in hits) / n / 1e6,
            "families_ms": [[k, v / n / 1e6] for k, v in sorted(families.items(), key=lambda kv: -kv[1])],
            "ops_ms": [[k, v / n / 1e6] for k, v in sorted(single.items(), key=lambda kv: -kv[1])[:OPS_KEPT]],
        }
    return out


def main(argv: list[str]) -> int:
    root = os.getcwd()
    sys.path[:0] = [os.path.join(root, "benchmark"), root]
    import run
    import trace as trace_mod

    run.configure_compile_cache()

    import harness
    import jax

    workload, seed, seconds = argv[0], int(argv[1]), float(argv[2])
    compare = len(argv) > 3 and argv[3] == "1"
    tag = argv[4] if len(argv) > 4 else workload
    patterns = ["jit_chat_prefill", "jit_chat_decode"]
    cell = harness.load_cell(root, workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        sys.exit(f"chat_programs: {workload} needs {cell.chips} TPU chip(s); JAX reports {devices[0].platform!r}")
    kept: dict = {}
    load_events = trace_mod.load_events

    def keeping(trace_dir: str):
        # the harness reads the trace once and then deletes it
        kept["events"] = load_events(trace_dir)
        return kept["events"]

    trace_mod.load_events = keeping
    try:
        if compare:
            result = harness.run_cell(cell, seed, seconds, True, devices, T_START)
        else:
            result, _ = harness.measure(cell, seed, seconds, True, devices, T_START)
            result["correct"] = None
    finally:
        trace_mod.load_events = load_events
    out = {
        "cell": workload,
        "seed": seed,
        "seconds": seconds,
        "correct": result["correct"],
        "failed": result["failed"],
        "device": result["device"],
        "metrics": {name: m["value"] for name, m in result["metrics"].items()},
        "device_ops": result.get("breakdown", {}).get("device_ops"),
        "programs": split_programs(kept["events"], patterns, trace_mod) if kept else None,
    }
    out_dir = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"programs_{tag}.json"), "w") as fh:
        json.dump(out, fh, indent=1)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
