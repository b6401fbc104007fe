#!/usr/bin/env python3
"""The sweep that chose how a prefill's grouped-query attention walks its
chunk (``pathway_tpu.models.decoder._walked_attention`` and
``PREFILL_TILE``): on a TPU only, a prefill's attention layers alone at the
answerers' shapes, the masked product every (query, key) score goes through
(``decoder._grouped_query``) beside the two candidates that visit only the
key tiles a query tile needs (``decoder.prefill_attention_tiles``): ``xla``,
a ``lax.map`` over (row, query tile) with a ``fori_loop`` between the row's
key-tile bounds (``xla_walk`` below), and ``pallas``, the program's kernel
with the bounds scalar-prefetched (``ops.prefill_attention``), each at tiles
of 128, 256 and 512:

    python3 tools/prefill_attention_sweep.py [shapes, comma separated]

A call's shape is ``rows`` rows of ``bucket`` slots walked in groups of rows
as ``decoder.prefill`` walks them, of which ``real`` hold prompts drawn from
the seed (the rest are padding rows), and every attention layer of the
model's period (Mellum2: three sliding layers and a full one). Prints a line
a (shape, bucket, real rows): milliseconds a call and the scores walked a
second, each implementation beside the parent's; writes
``chiprun_out/prefill_attention_sweep.json``.
"""

from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

#: name: query heads, key heads, head width, the period's attention layers by
#: window (0: full), rows a call, buckets, real rows to try, prompt lengths
#: (least, most)
SHAPES = {
    "mellum2": (32, 4, 128, (1024, 1024, 1024, 0), 8, (1536, 2048, 2560), (1, 2, 4, 8), (900, 2560)),
    "command-a-plus": (128, 8, 128, (4096, 4096, 4096, 0), 16, (768, 1024), (2, 6, 16), (400, 1024)),
    "lfm2": (32, 8, 64, (0,), 8, (768, 1024), (1, 3, 8), (110, 1024)),
}
TILES = (128, 256, 512)
REPEATS, CALLS = 5, 4


def xla_walk(q, k, v, k_valid, window, scale, tile):
    """Candidate (a): the same tiles walked by XLA, a ``lax.map`` over (row,
    query tile) with a ``fori_loop`` between the row's key-tile bounds, the
    key head's query heads sharing a key tile, float32 online softmax; a
    query tile that visits nothing gives zeros. ``[b, t, heads * d]``."""
    import jax.numpy as jnp
    from jax import lax

    from pathway_tpu.models import decoder as dec

    b, t, heads, d = q.shape
    kv = k.shape[2]
    first = jnp.where(k_valid.any(1), jnp.argmax(k_valid, axis=1), t).astype(jnp.int32)
    lo, count = dec.prefill_attention_tiles(first, t, tile, window)
    tiles = lo.shape[1]
    pad = tiles * tile - t
    if pad:
        q, k, v = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0))) for a in (q, k, v))
        k_valid = jnp.pad(k_valid, ((0, 0), (0, pad)))
    slots = jnp.arange(tile, dtype=jnp.int32)
    rows = jnp.repeat(jnp.arange(b, dtype=jnp.int32), tiles)
    at = jnp.tile(jnp.arange(tiles, dtype=jnp.int32), b)

    def query_tile(args):
        qt, r, i, lo, count = args  # qt [tile, kv, g, d]

        def key_tile(jj, carry):
            acc, top, total = carry
            j = lo + jj
            kt, vt = (lax.dynamic_slice(a, (r, j * tile, 0, 0), (1, tile, kv, d))[0] for a in (k, v))
            real = lax.dynamic_slice(k_valid, (r, j * tile), (1, tile))[0]
            back = (i * tile + slots)[:, None] - (j * tile + slots)[None, :]
            seen = (back >= 0) & real[None, :]
            if window:
                seen = seen & (back < window)
            scores = jnp.einsum("tkgd,skd->kgts", qt, kt, preferred_element_type=jnp.float32) * scale
            scores = jnp.where(seen, scores, -1e30)
            new_top = jnp.maximum(top, scores.max(-1, keepdims=True))
            probs = jnp.exp(scores - new_top)
            fade = jnp.exp(top - new_top)
            total = total * fade + probs.sum(-1, keepdims=True)
            acc = acc * fade + jnp.einsum(
                "kgts,skd->kgtd", probs.astype(v.dtype), vt, preferred_element_type=jnp.float32
            )
            return acc, new_top, total

        g = heads // kv
        acc = jnp.zeros((kv, g, tile, d), jnp.float32)
        top = jnp.full((kv, g, tile, 1), -1e30, jnp.float32)
        acc, _, total = lax.fori_loop(0, count, key_tile, (acc, top, jnp.zeros_like(top)))
        out = acc / jnp.where(total > 0, total, 1.0)
        return out.transpose(2, 0, 1, 3).reshape(tile, heads * d).astype(v.dtype)

    q_tiles = q.reshape(b * tiles, tile, kv, heads // kv, d)
    out = lax.map(query_tile, (q_tiles, rows, at, lo.reshape(-1), count.reshape(-1)))
    return out.reshape(b, tiles * tile, heads * d)[:, :t]


def attention(impl: str, tile: int, windows, heads: int, d: int):
    """A call's attention layers, one per entry of ``windows``, as
    ``decoder.prefill`` walks them: the rows in groups of
    ``PREFILL_BLOCK_TOKENS`` tokens, each group's chunk over its own keys."""
    from types import SimpleNamespace

    import jax
    import jax.numpy as jnp

    from pathway_tpu.models import decoder as dec

    scale = d**-0.5

    def layer(q, k, v, valid, window):
        b, t = valid.shape
        if impl == "masked":
            slots = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None], (b, t))
            return dec._grouped_query(q, k, v, slots, valid, None, window, SimpleNamespace(softmax_scale=scale))
        if impl == "xla":
            return xla_walk(q, k, v, valid, window, scale, tile)
        return dec._walked_attention(q, k, v, valid, window, scale, tile)

    def call(q, k, v, valid):
        rows, t = valid.shape
        group = max(1, min(rows, dec.PREFILL_BLOCK_TOKENS // t))
        while rows % group:
            group -= 1
        split = lambda a: a.reshape((rows // group, group) + a.shape[1:])  # noqa: E731

        def one(args):  # each layer's output feeds a sum, so that none is dropped
            return sum(layer(*args, w).astype(jnp.float32).sum() for w in windows)

        return jax.lax.map(one, (split(q), split(k), split(v), split(valid))).sum()

    return jax.jit(call)


def main(argv: list[str]) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pathway_tpu.models import decoder as dec

    if jax.devices()[0].platform != "tpu":
        sys.exit(f"prefill_attention_sweep: needs a TPU; JAX reports {jax.devices()[0].platform!r}")
    names = argv[0].split(",") if argv else list(SHAPES)
    out = []
    for name in names:
        heads, kv, d, windows, rows, buckets, reals, (least, most) = SHAPES[name]
        for bucket in buckets:
            keys = jax.random.split(jax.random.key(44), 3)
            q = jax.random.normal(keys[0], (rows, bucket, heads, d), jnp.bfloat16)
            k = jax.random.normal(keys[1], (rows, bucket, kv, d), jnp.bfloat16)
            v = jax.random.normal(keys[2], (rows, bucket, kv, d), jnp.bfloat16)
            impls = [("masked", 0)] + [(impl, tile) for impl in ("xla", "pallas") for tile in TILES if tile <= bucket]
            programs = {f"{impl}/{tile}" if tile else impl: attention(impl, tile, windows, heads, d) for impl, tile in impls}
            rng = np.random.default_rng(bucket)
            for real in reals:
                lengths = rng.integers(least, min(most, bucket) + 1, real)
                lengths[0] = min(most, bucket)  # the longest prompt sets the bucket
                valid = np.zeros((rows, bucket), bool)
                for r, n in enumerate(lengths):
                    valid[r, bucket - n :] = True
                valid = jnp.asarray(valid)
                square = len(windows) * rows * heads * bucket * bucket
                row = {"shape": name, "bucket": bucket, "rows": rows, "real_rows": int(real),
                       "lengths": [int(n) for n in lengths], "ms": {}, "gscores": {}, "gscores_per_s": {}}
                for label, fn in programs.items():
                    jax.block_until_ready(fn(q, k, v, valid))
                    times = []
                    for _ in range(REPEATS):
                        t0 = time.perf_counter()
                        for _ in range(CALLS):
                            y = fn(q, k, v, valid)
                        jax.block_until_ready(y)
                        times.append((time.perf_counter() - t0) / CALLS * 1e3)
                    ms = sorted(times)[REPEATS // 2]
                    if label == "masked":
                        scores = square
                    else:
                        tile = int(label.split("/")[1])
                        first = [bucket - int(n) for n in lengths] + [bucket] * (rows - real)
                        scores = heads * sum(dec._walked_pairs(first, bucket, tile, w) for w in windows)
                    row["ms"][label] = ms
                    row["gscores"][label] = scores / 1e9
                    row["gscores_per_s"][label] = scores / 1e9 / (ms / 1e3)
                best = min((ms, label) for label, ms in row["ms"].items() if label != "masked")
                row["best"] = best[1]
                out.append(row)
                print(json.dumps(row), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "prefill_attention_sweep.json"), "w") as fh:
        json.dump(out, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
