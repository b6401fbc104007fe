#!/usr/bin/env python3
"""The benchmark's entry point:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process. It looks for the chips the cell asks for and exits with code 2,
printing no result, where JAX reports anything else. It prints the numbers
``correct`` was decided from, each beside its limit, as its last lines on
standard error, and one JSON object as the last line of standard output:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` ``breakdown``, and last ``compared``.
"""

from __future__ import annotations

import time

T_START = time.time()  # set-up is counted from here

import argparse  # noqa: E402
import faulthandler  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
#: a run may take 360 s, its first in a checkout 1200 s; a hang past that
#: dumps every thread and exits 1
DEADLINE_S = 1150


def configure_compile_cache() -> str:
    """JAX's persistent cache at ``JAX_COMPILATION_CACHE_DIR`` where that is
    set, else at ``<checkout>/.jax_cache`` — exported, so that the program's
    own ``configure_compile_cache()`` takes the same directory — and every
    program kept, however quickly it compiled."""
    path = os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".jax_cache"))
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
    return path


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    faulthandler.dump_traceback_later(DEADLINE_S, exit=True, file=sys.__stderr__)
    sys.path[:0] = [HERE, ROOT]
    configure_compile_cache()

    import harness

    cell = harness.load_cell(ROOT, args.workload)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(
            f"benchmark: {args.workload} needs {cell.chips} TPU chip(s); JAX reports "
            f"{len(devices)} {devices[0].platform!r} device(s) ({devices[0].device_kind})",
            file=sys.stderr,
        )
        return 2
    result = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace), devices, T_START)
    faulthandler.cancel_dump_traceback_later()
    sys.stdout.flush()
    for name, (value, limit) in result["compared"].items():
        print(f"compared {name} = {value!r} (limit {limit!r})", file=sys.stderr)
    print(f"correct = {result['correct']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
