#!/usr/bin/env python3
"""The sweep behind ``pathway_tpu.ops.moe.tiling`` at widths that are no
multiple of 512: one grouped product (``lax.ragged_dot``, bfloat16 operands,
float32 results) alone, on a TPU only, at the shapes a chat model's expert
layer hands it, under the compiler's own tiles and under each candidate
tiling (rows, contracted, output), given through the same metadata
``ops.moe.grouped_product`` sets:

    python3 tools/expert_tile_sweep.py [name ...]

with no name every shape of ``SHAPES``. A decode step is 8 tokens of which 3
or 8 are real, each choosing ``k`` experts (the padding's pairs sort behind
the last group, as ``routed_experts`` sorts them); a prefill block is 2,048
sorted rows, all in groups. Prints a line a (shape, tiling): the ms a
product (best of three loops of products, each fed the last one's result so
that none is hoisted), the touched experts' bytes over that time in GB/s,
whether it compiled (a tiling that does not fit the kernel's scoped vector
memory is refused by the compiler) and the largest gap of its rows in groups
from the compiler's tiles' result, over the largest of those; writes
``chiprun_out/expert_tile_sweep.json``.
"""

from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: name, sorted rows, contracted, output, experts, choices a token, real tokens
#: (0: a prefill block, every row in a group), products a timed loop, tilings
#: to try (None: the compiler's own; a width a tile does not divide is masked)
SHAPES = [
    ("dsv2lite gate_up decode 3 real", 48, 2048, 2816, 64, 6, 3, 400,
     [None, "16,1024,1408", "16,2048,1408", "16,1024,2816", "16,512,1408", "16,1024,512", "16,1024,768",
      "16,1024,1024", "16,2048,512", "32,1024,1408", "48,1024,1408"]),
    ("dsv2lite gate_up decode 8 real", 48, 2048, 2816, 64, 6, 8, 400,
     [None, "16,1024,1408", "16,2048,1408", "16,1024,2816", "16,512,1408", "16,1024,512", "16,1024,768",
      "16,1024,1024", "16,2048,512", "32,1024,1408", "48,1024,1408"]),
    ("dsv2lite down decode 3 real", 48, 1408, 2048, 64, 6, 3, 400,
     [None, "16,1408,1024", "16,1408,2048", "16,1408,512", "16,512,1024", "16,768,1024", "16,1408,768",
      "32,1408,1024", "48,1408,1024"]),
    ("dsv2lite down decode 8 real", 48, 1408, 2048, 64, 6, 8, 400,
     [None, "16,1408,1024", "16,1408,2048", "16,1408,512", "16,512,1024", "16,768,1024", "16,1408,768",
      "32,1408,1024", "48,1408,1024"]),
    ("dsv2lite gate_up prefill block", 2048, 2048, 2816, 64, 6, 0, 20,
     [None, "512,1024,1408", "256,1024,1408", "128,1024,1408", "512,512,1408", "256,2048,1408", "256,1024,2816",
      "512,1024,512", "512,1024,768"]),
    ("dsv2lite down prefill block", 2048, 1408, 2048, 64, 6, 0, 20,
     [None, "512,1408,1024", "256,1408,1024", "128,1408,1024", "512,1408,512", "256,1408,2048", "512,1408,768",
      "512,768,1024"]),
]


def groups_of(rng, rows: int, experts: int, k: int, real: int):
    """Group sizes ``[experts]`` int32 of a call's sorted rows: ``real``
    tokens' ``k`` distinct choices (the rest of the rows, padding, in no
    group), or for ``real`` 0 ``rows`` choices, every row in a group."""
    import numpy as np

    tokens = real if real else -(-rows // k)
    choice = np.stack([rng.choice(experts, k, replace=False) for _ in range(tokens)]).ravel()
    return np.bincount(choice[: min(rows, choice.size)], minlength=experts).astype(np.int32)


def main(argv: list[str]) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from jax.experimental.xla_metadata import set_xla_metadata

    if jax.devices()[0].platform != "tpu":
        sys.exit(f"expert_tile_sweep: needs a TPU; JAX reports {jax.devices()[0].platform!r}")
    print(jax.devices(), flush=True)
    out = []
    for name, rows, contracted, width, experts, k, real, reps, tilings in SHAPES:
        if argv and name not in argv:
            continue
        rng = np.random.default_rng(48)
        sizes = groups_of(rng, rows, experts, k, real)
        in_groups = int(sizes.sum())
        touched = int(np.count_nonzero(sizes))
        x = jnp.asarray(rng.standard_normal((rows, contracted)), jnp.bfloat16)
        w = jnp.asarray(rng.standard_normal((experts, contracted, width)) * 0.02, jnp.bfloat16)
        g = jnp.asarray(sizes)
        read = touched * contracted * width * 2
        want = None
        for tiles in tilings:

            def product(x, w, g, tiles=tiles):
                if tiles is None:
                    return lax.ragged_dot(x, w, g, preferred_element_type=jnp.float32)
                with set_xla_metadata(ragged_dot_tiling=tiles):
                    return lax.ragged_dot(x, w, g, preferred_element_type=jnp.float32)

            def loop(x, w, g, product=product):
                def body(_, x):
                    y = product(x, w, g)[:, : min(contracted, width)]
                    y = jnp.pad(y, ((0, 0), (0, contracted - y.shape[1])))
                    return (x + 1e-3 * y).astype(x.dtype)

                return lax.fori_loop(0, reps, body, x)

            row = {"shape": name, "rows": rows, "contracted": contracted, "out": width, "real_tokens": real,
                   "rows_in_groups": in_groups, "touched": touched, "tiles": tiles or "compiler"}
            try:
                once = jax.jit(product).lower(x, w, g).compile()
                timed = jax.jit(loop).lower(x, w, g).compile()
            except Exception as exc:  # the compiler refuses a tiling it cannot fit
                row.update(compiles=False, error=str(exc).splitlines()[0][:160])
                out.append(row)
                print(json.dumps(row), flush=True)
                continue
            y = np.asarray(once(x, w, g))[:in_groups]
            if want is None:
                want = y
            jax.block_until_ready(timed(x, w, g))
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                jax.block_until_ready(timed(x, w, g))
                best = min(best, time.perf_counter() - t0)
            ms = best / reps * 1e3
            row.update(compiles=True, ms=ms, gb_per_s=read / ms / 1e6,
                       gap=float(np.abs(y - want).max() / np.abs(want).max()), sum=float(np.abs(y).sum()))
            out.append(row)
            print(json.dumps(row), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "expert_tile_sweep.json"), "w") as fh:
        json.dump(out, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
