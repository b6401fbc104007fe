"""Time ``ops.moe.routed_experts`` at a chat model's decode and prefill
shapes, in the compiler's ragged-dot tiles and in ``ops.moe.tiling``'s, and
print the tiles each compiled with.

    python3 benchmark/dev/expert_tiles.py [hidden,width,experts,k ...]

Run it on the chip: on the CPU the tiles are not used and the times say
nothing of the device. "GB/s" is the published bytes of the experts the real
rows chose (gate, up and down) over the time of one product.
"""

from __future__ import annotations

import re
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, __file__.rsplit("/benchmark/", 1)[0])
from pathway_tpu.ops import moe  # noqa: E402


def one(hidden, width, experts, k, tokens, real, reps, tiles):
    rng = np.random.default_rng(0)
    gate_up = jnp.asarray(rng.standard_normal((experts, hidden, 2 * width)) * 0.02, jnp.bfloat16)
    down = jnp.asarray(rng.standard_normal((experts, width, hidden)) * 0.02, jnp.bfloat16)
    h = jnp.asarray(rng.standard_normal((tokens, hidden)), jnp.bfloat16)
    choice = np.stack([rng.choice(experts, k, replace=False) for _ in range(tokens)]).astype(np.int32)
    weights = jnp.full((tokens, k), 1.0 / k, jnp.float32)
    counted = jnp.arange(tokens) < real

    def run(h, gate_up, down):
        def body(_, x):
            y, _ = moe.routed_experts(x, weights, jnp.asarray(choice), gate_up, down, counted)
            return (x + 1e-3 * y).astype(x.dtype)

        return jax.lax.fori_loop(0, reps, body, h)

    own = moe.tiling
    moe.tiling = own if tiles == "rule" else (lambda *shape: None)
    try:
        compiled = jax.jit(run).lower(h, gate_up, down).compile()
    finally:
        moe.tiling = own
    used = sorted(set(re.findall(r'ragged_dot_tiling="([^"]*)"', compiled.as_text())))
    out = compiled(h, gate_up, down)
    best = float("inf")
    for _ in range(3):
        t = time.perf_counter()
        out = compiled(h, gate_up, down)
        out.block_until_ready()
        best = min(best, time.perf_counter() - t)
    touched = len(set(choice[:real].ravel().tolist()))
    ms = best / reps * 1e3
    read = touched * 3 * hidden * width * 2
    print(
        f"{tiles:8s} hidden {hidden} width {width} experts {experts} k {k} tokens {tokens} real {real}: "
        f"{ms:.4f} ms a product, touched {touched}, {read / ms / 1e6:.1f} GB/s, tiles {used}, "
        f"sum {float(jnp.abs(out.astype(jnp.float32)).sum()):.6e}",
        flush=True,
    )


def main(argv):
    print(jax.devices(), flush=True)
    for spec in argv or ["2304,896,64,8", "2048,1536,64,4"]:
        hidden, width, experts, k = map(int, spec.split(","))
        for tokens, real, reps in ((8, 3, 400), (8, 8, 400), (4096, 3 * 1024, 4)):
            for tiles in ("compiler", "rule"):
                one(hidden, width, experts, k, tokens, real, reps, tiles)


if __name__ == "__main__":
    main(sys.argv[1:])
