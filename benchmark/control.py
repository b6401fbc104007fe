#!/usr/bin/env python3
"""Readings for the limits of ``correct`` in a live-index cell (the controls
of ``pipelines/live_index.py``'s comparison), on the chip, at a cell's own size:

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3 --seconds 4

For each seed, in one process: the cell's set-up and a short window at the
cell's own load; the program's reading of every number compared (the lower
readings); then each control — the reference put in the program's place, one
precision down: float8 (e4m3) operands for the bfloat16 encoder, three-pass
``Precision.HIGH`` products for the float32 ``HIGHEST`` search — through the
same ``check.compare()``, which has to say ``correct: false`` (the upper
readings). One JSON line a seed. The benchmark's own runs never run this;
``tests/test_harness.py::test_the_control_reads_over_the_limit`` keeps it at
a size a test can hold.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _verdict(numbers: list[dict]) -> dict:
    return {
        "correct": all(n["ok"] for n in numbers),
        "failed": [n["name"] for n in numbers if not n["ok"]],
        "numbers": {n["name"]: n["value"] for n in numbers},
    }


def readings(cell, seed: int, seconds: float, devices) -> dict:
    """The program's verdict and each control's, for one seed."""
    import check
    import harness
    import reference

    _, evidence = harness.measure(cell, seed, seconds, False, devices, time.time())
    schedule, obs, facts = (evidence[k] for k in ("schedule", "obs", "facts"))
    params = facts["params"]
    memo: dict = {}
    out = {"seed": seed, "program": _verdict(check.compare(cell, seed, **evidence, memo=memo))}
    enc, max_len = cell.config["encoder"], cell.config["embedder"]["max_len"]
    texts, seen, n_docs = check.sampled_texts(seed, schedule, obs, facts)
    low = reference.embed_texts(params, texts, enc, max_len, operand=reference.quantize_fp8)
    out["control_float8_encoder"] = _verdict(
        check.compare(cell, seed, **evidence, stand_in={"embeddings": low}, memo=memo)
    )
    widest = {"docs": slice(0, n_docs), "queries": slice(n_docs, None)}
    for name, rows in (("program", seen), ("control_float8_encoder", low)):
        gaps = check.embed_gaps(rows, memo["ref"])
        out[name]["embed_gap_widest"] = {g: float(gaps[s].max()) for g, s in widest.items() if len(gaps[s])}
    if schedule.queries is not None:
        sample, docs, live = check.answer_sample(seed, schedule, obs)
        k, prefilled, moments = cell.config["index"]["k"], facts["prefilled"], facts["prefill_moments"]
        queries, vectors = check.answer_vectors(obs, sample, docs)
        for precision in ("high", "default"):
            answers = reference.low_precision_top_k(
                seed, queries, vectors, live, prefilled, k, moments, precision
            )
            out[f"control_{precision}_search"] = _verdict(
                check.compare(cell, seed, **evidence, stand_in={"answers": answers}, memo=memo)
            )
    return out


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True, help="comma-separated")
    parser.add_argument("--seconds", type=float, default=4.0)
    args = parser.parse_args(argv)
    sys.path[:0] = [HERE, ROOT]
    import run

    run.configure_compile_cache()
    import harness
    import jax

    cell = harness.load_cell(ROOT, args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"control: {args.workload} needs {cell.chips} TPU chip(s)", file=sys.stderr)
        return 2
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(readings(cell, seed, args.seconds, devices)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
