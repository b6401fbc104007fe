#!/usr/bin/env python3
"""Run a cell with keys of its mix or configuration overridden, for the
sweeps that find a rate (PERF.md, "The two fixed rates"):

    python3 benchmark/dev/override.py <cell> <seed> <seconds> <trace 0|1> [path=json ...]

``documents.in_flight=512`` sets a key of the mix, ``config.embedder.max_batch_size=64``
one of the configuration. Prints the result line without its breakdown.
"""

import time

T_START = time.time()

import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, ROOT]

import run  # noqa: E402

run.configure_compile_cache()

import harness  # noqa: E402
import jax  # noqa: E402

workload, seed, seconds, trace = sys.argv[1], int(sys.argv[2]), float(sys.argv[3]), bool(int(sys.argv[4]))
cell = harness.load_cell(ROOT, workload)
for item in sys.argv[5:]:
    path, value = item.split("=", 1)
    target = cell.config if path.startswith("config.") else cell.mix
    *parents, last = path.removeprefix("config.").split(".")
    for key in parents:
        target = target[key]
    target[last] = json.loads(value)
devices = jax.devices()
if devices[0].platform != "tpu" or len(devices) < cell.chips:
    sys.exit(f"override: {workload} needs {cell.chips} TPU chip(s); JAX reports {devices[0].platform!r}")
result = harness.run_cell(cell, seed, seconds, trace, devices, T_START)
result.pop("breakdown", None)
print(json.dumps(result))
