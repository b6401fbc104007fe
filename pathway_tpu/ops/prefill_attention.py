"""A chunk's grouped-query attention over its own keys, walking only the key
tiles each query tile needs: one Pallas kernel.

``q`` ``[b, t, heads, d]``, ``k``, ``v`` ``[b, t, kv_heads, d]`` in slot
order, ``k_valid`` ``[b, t]``; ``lo``, ``count`` ``[b, tiles]`` say which key
tiles query tile ``i`` of row ``r`` visits: ``lo[r, i]`` to ``lo[r, i] +
count[r, i] - 1`` (``models.decoder.prefill_attention_tiles`` gives them).
The grid is (row, key head, query tile). A row's keys and values of one key
head ride whole in VMEM (fetched once for all its query tiles), the bounds
are scalar-prefetched, and the kernel loops over the visited key tiles under
a float32 online softmax, the ``heads / kv_heads`` query heads of the key
head stacked as the rows of one product a key tile; inside a tile a score is
masked as the masked product masks it (causal by slot, a real key, and
within ``window`` where one is given). A query tile that visits nothing
writes zeros, never NaN. Off a TPU the kernel runs in interpret mode.

Timed against the masked product and an XLA walk of the same tiles by
``tools/prefill_attention_sweep.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: a query tile's rows (every query head of a key head, stacked), its scores
#: and probabilities in float32 and the whole row's keys and values held at
#: once: some 16 MB at Command A+'s 16 query heads a key head and tiles of 256
_VMEM_BYTES = 64 * 1024 * 1024


def _kernel(lo_ref, count_ref, q_ref, k_ref, v_ref, valid_ref, o_ref, *, tile, group, window, scale):
    r, i = pl.program_id(0), pl.program_id(2)
    d = k_ref.shape[-1]
    q = q_ref[0]  # [tile, group * d]
    q = jnp.concatenate([q[:, h * d : (h + 1) * d] for h in range(group)], axis=0)  # [group * tile, d]
    query_slot = i * tile + jax.lax.broadcasted_iota(jnp.int32, (group, tile, tile), 1)
    key_in_tile = jax.lax.broadcasted_iota(jnp.int32, (group, tile, tile), 2)

    def key_tile(jj, carry):
        acc, top, total = carry
        j = lo_ref[r, i] + jj
        at = pl.multiple_of(j * tile, tile)
        kt, vt = k_ref[0, 0, pl.ds(at, tile), :], v_ref[0, 0, pl.ds(at, tile), :]
        real = valid_ref[0, :, pl.ds(at, tile)]  # [1, tile]
        s = jax.lax.dot_general(q, kt, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) * scale
        back = query_slot - (j * tile + key_in_tile)
        seen = (back >= 0) & (real[None] > 0)
        if window:
            seen = seen & (back < window)
        s = jnp.where(seen, s.reshape(group, tile, tile), -1e30).reshape(group * tile, tile)
        new_top = jnp.maximum(top, s.max(-1, keepdims=True))
        p = jnp.exp(s - new_top)
        fade = jnp.exp(top - new_top)
        total = total * fade + p.sum(-1, keepdims=True)
        acc = acc * fade + jax.lax.dot_general(
            p.astype(vt.dtype), vt, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        return acc, new_top, total

    rows = group * tile
    init = (
        jnp.zeros((rows, d), jnp.float32),
        jnp.full((rows, 1), -1e30, jnp.float32),
        jnp.zeros((rows, 1), jnp.float32),
    )
    acc, _, total = jax.lax.fori_loop(0, count_ref[r, i], key_tile, init)
    out = (acc / jnp.where(total > 0, total, 1.0)).astype(o_ref.dtype)
    o_ref[0] = jnp.concatenate([out[h * tile : (h + 1) * tile] for h in range(group)], axis=1)


@functools.partial(jax.jit, static_argnames=("window", "scale", "tile"))
def walked_attention(q, k, v, k_valid, lo, count, *, window: int, scale: float, tile: int) -> jax.Array:
    """``[b, t, heads * d]``; a chunk that is no multiple of ``tile`` is
    padded at its end (keys no query reaches, queries dropped)."""
    b, t, heads, d = q.shape
    kv = k.shape[2]
    tiles = lo.shape[1]
    pad = tiles * tile - t
    if pad:
        q, k, v = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0))) for a in (q, k, v))
        k_valid = jnp.pad(k_valid, ((0, 0), (0, pad)))
    t_pad = tiles * tile
    group = heads // kv
    kernel = functools.partial(_kernel, tile=tile, group=group, window=window, scale=scale)
    whole = pl.BlockSpec((1, 1, t_pad, d), lambda r, h, i, lo, n: (r, h, 0, 0))
    query_tile = pl.BlockSpec((1, tile, group * d), lambda r, h, i, lo, n: (r, i, h))
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, t_pad, heads * d), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, kv, tiles),
            in_specs=[query_tile, whole, whole, pl.BlockSpec((1, 1, t_pad), lambda r, h, i, lo, n: (r, 0, 0))],
            out_specs=query_tile,
        ),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_BYTES),
        interpret=jax.default_backend() != "tpu",
    )(
        lo.astype(jnp.int32),
        count.astype(jnp.int32),
        q.reshape(b, t_pad, heads * d),
        k.transpose(0, 2, 1, 3),  # [b, kv, t, d]: a key head's keys whole
        v.transpose(0, 2, 1, 3),
        k_valid.astype(jnp.int32)[:, None, :],
    )
    return out[:, :t]
