#!/usr/bin/env python3
"""The sweep that fixes ``pathway_tpu.ops.moe.BLOCK_ROWS``: ``routed_experts``
alone, on a TPU only, at the shapes of a prefill's group of rows in the three
answerers' cells, for a group that holds no real row, some and only real
rows, under each block length (the last one, longer than the call, is the
form without a loop):

    python3 tools/moe_block_sweep.py [blocks, comma separated]

Prints a line a (shape, real tokens) with the milliseconds a call under
each block, and writes ``chiprun_out/moe_block_sweep.json``.
"""

from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

#: name, tokens of a group of rows, choices a token, hidden, an expert's width,
#: experts held, experts routed over, real tokens of the group to try
SHAPES = [
    ("command-a-plus 4x768", 3072, 8, 4096, 4096, 16, 128, (0, 640, 1600, 2560, 3072)),
    ("dsv2lite 2x1536", 3072, 6, 2048, 1408, 64, 64, (0, 1020, 2040, 3072)),
    ("dsv2lite 4x1024", 4096, 6, 2048, 1408, 64, 64, (0, 1020, 2040, 4096)),
    ("lfm2 4x768", 3072, 4, 2048, 1536, 64, 64, (0, 640, 1600, 2560, 3072)),
]


def main(argv: list[str]) -> int:
    import jax
    import jax.numpy as jnp

    from pathway_tpu.ops import moe

    if jax.devices()[0].platform != "tpu":
        sys.exit(f"moe_block_sweep: needs a TPU; JAX reports {jax.devices()[0].platform!r}")
    blocks = [int(b) for b in argv[0].split(",")] if argv else [512, 1024, 2048, 4096, 8192, 1 << 30]
    out = []
    for name, n, k, hidden, width, held, of, reals in SHAPES:
        keys = jax.random.split(jax.random.key(39), 5)
        h = jax.random.normal(keys[0], (n, hidden), jnp.bfloat16)
        gate_up = (jax.random.normal(keys[1], (held, hidden, 2 * width), jnp.bfloat16) * 0.02).astype(jnp.bfloat16)
        down = (jax.random.normal(keys[2], (held, width, hidden), jnp.bfloat16) * 0.02).astype(jnp.bfloat16)
        weights, experts = jax.lax.top_k(jax.random.uniform(keys[3], (n, of)), k)
        experts = experts.astype(jnp.int32)
        for real in reals:
            counted = jnp.arange(n) >= n - real  # left-padded, as a prompt is
            row = {"shape": name, "pairs": n * k, "real_tokens": real, "ms": {}}
            for block in blocks:
                moe.BLOCK_ROWS = block
                fn = jax.jit(lambda *a: moe.routed_experts(*a, held=(0, held)))
                args = (h, weights, experts, gate_up, down, counted)
                y, sizes = jax.block_until_ready(fn(*args))
                times = []
                for _ in range(5):
                    t0 = time.perf_counter()
                    for _ in range(4):
                        y, _ = fn(*args)
                    jax.block_until_ready(y)
                    times.append((time.perf_counter() - t0) / 4 * 1e3)
                row["in_groups"] = int(sizes.sum())
                row["ms"][str(block)] = sorted(times)[2]
            out.append(row)
            print(json.dumps(row), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "moe_block_sweep.json"), "w") as fh:
        json.dump(out, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
