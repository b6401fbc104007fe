"""A chunk's grouped-query attention over its own keys, walked in tiles
(``decoder._walked_attention``), against the masked product that evaluates
every score (``decoder._grouped_query``) on the real rows' outputs; the
tile bounds (``decoder.prefill_attention_tiles``) against the tiles that hold
a score a real query needs; and the counts the chat reports from them."""

from __future__ import annotations

import os
import sys
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from pathway_tpu.models import decoder as dec_mod  # noqa: E402
from pathway_tpu.models.decoder import DecoderConfig  # noqa: E402

#: (query heads, key heads, head width) at toy widths, by the query heads a
#: key head carries: Mellum2's 8, Command A+'s 16, LFM2's 4
LAYOUTS = {"mellum2": (16, 2, 16), "command-a-plus": (32, 2, 16), "lfm2": (16, 4, 8)}


def _inputs(seed, rows, t, layout, dtype):
    heads, kv, d = LAYOUTS[layout]
    keys = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(keys[0], (rows, t, heads, d), jnp.float32).astype(dtype)
    k = jax.random.normal(keys[1], (rows, t, kv, d), jnp.float32).astype(dtype)
    v = jax.random.normal(keys[2], (rows, t, kv, d), jnp.float32).astype(dtype)
    return q, k, v, d**-0.5


def _left_padded(t, lengths):
    valid = np.zeros((len(lengths), t), bool)
    for r, n in enumerate(lengths):
        valid[r, t - n :] = True
    return valid


def _masked(q, k, v, valid, window, scale):
    """The masked product over every score, in float32 from the same values."""
    b, t = valid.shape
    slots = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None], (b, t))
    f32 = [a.astype(jnp.float32) for a in (q, k, v)]
    return dec_mod._grouped_query(*f32, slots, jnp.asarray(valid), None, window, SimpleNamespace(softmax_scale=scale))


@pytest.mark.parametrize("layout", list(LAYOUTS))
@pytest.mark.parametrize("t, tile, lengths, window", [
    (32, 8, [32, 32], 0),  # every row real, a full layer
    (32, 8, [32, 32], 12),  # every row real, prompts past the window
    (32, 8, [27, 9, 0], 12),  # partly padded rows beside a row of padding
    (32, 8, [20, 5, 0], 0),  # the same under a full layer
    (30, 8, [30, 17, 0], 7),  # a bucket that is no multiple of the tile
    (30, 8, [25, 6], 0),
    (32, 8, [11, 6], 16),  # prompts shorter than the window
    (24, 32, [24, 3, 0], 5),  # one tile longer than the chunk
    (40, 4, [40, 33, 1, 0], 9),  # a row of one real token
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_walk_gives_the_masked_products_real_rows_and_zeros_for_padding(layout, t, tile, lengths, window, dtype):
    q, k, v, scale = _inputs(t + tile + window, len(lengths), t, layout, dtype)
    valid = _left_padded(t, lengths)
    got = np.asarray(dec_mod._walked_attention(q, k, v, jnp.asarray(valid), window, scale, tile).astype(jnp.float32))
    want = np.asarray(_masked(q, k, v, valid, window, scale))
    assert got.shape == want.shape == (len(lengths), t, q.shape[2] * q.shape[3])
    # float32 scores on both sides; the walk's probabilities meet the values in the values' dtype
    atol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(got[valid], want[valid], atol=atol)
    assert np.isfinite(got).all()
    for r, n in enumerate(lengths):
        if n == 0:  # a row of padding visits nothing and gives zeros
            assert not got[r].any()


def test_a_query_tile_of_padding_gives_zeros_that_cannot_poison_a_later_product():
    q, k, v, scale = _inputs(3, 2, 16, "mellum2", jnp.bfloat16)
    valid = _left_padded(16, [5, 16])
    got = np.asarray(dec_mod._walked_attention(q, k, v, jnp.asarray(valid), 0, scale, 4).astype(jnp.float32))
    assert not got[0, :8].any()  # the row's first two query tiles are padding
    assert np.isfinite(got).all() and (0 * got == 0).all()


# -- the bounds and the counts ------------------------------------------------


def _needed_tiles(first, t, tile, window):
    """By brute force: the (query tile, key tile) pairs that hold a score a
    real query needs, a row."""
    tiles = -(-t // tile)
    out = np.zeros((len(first), tiles, tiles), bool)
    for r, f in enumerate(first):
        for query in range(f, t):
            for key in range(f, query + 1):
                if not window or query - key < window:
                    out[r, query // tile, key // tile] = True
    return out


@pytest.mark.parametrize("t, tile, window", [(32, 8, 12), (32, 8, 0), (30, 8, 7), (40, 4, 9), (24, 32, 5), (64, 16, 16)])
def test_the_bounds_visit_exactly_the_tiles_that_hold_a_needed_score(t, tile, window):
    first = np.asarray([0, 3, 8, 17, t - 1, t])
    lo, count = dec_mod.prefill_attention_tiles(first, t, tile, window)
    visited = np.zeros((len(first), lo.shape[1], lo.shape[1]), bool)
    for r in range(len(first)):
        for i in range(lo.shape[1]):
            visited[r, i, lo[r, i] : lo[r, i] + count[r, i]] = True
    np.testing.assert_array_equal(visited, _needed_tiles(first, t, tile, window))
    # on the device, the same bounds
    dev_lo, dev_count = jax.jit(dec_mod.prefill_attention_tiles, static_argnums=(1, 2, 3))(jnp.asarray(first), t, tile, window)
    np.testing.assert_array_equal(np.asarray(dev_count), count)
    np.testing.assert_array_equal(np.asarray(dev_lo)[count > 0], lo[count > 0])


def test_a_query_tile_reads_no_key_tile_outside_its_bounds():
    """Each key tile a query tile does not visit is filled with NaN, one
    query tile at a time: that tile's output is what it was, so the kernel
    read none of them; the tiles it does visit are what the masked product
    needs (the bounds test above), so it visits exactly its ``count``."""
    t, tile, window, lengths = 32, 8, 12, [27, 9, 32, 0]
    q, k, v, scale = _inputs(5, len(lengths), t, "mellum2", jnp.float32)
    valid = jnp.asarray(_left_padded(t, lengths))
    clean = np.asarray(dec_mod._walked_attention(q, k, v, valid, window, scale, tile))
    first = np.asarray([t - n for n in lengths])
    lo, count = dec_mod.prefill_attention_tiles(first, t, tile, window)
    for r in range(len(lengths)):
        for i in range(t // tile):
            keep = np.zeros(t, bool)
            keep[lo[r, i] * tile : (lo[r, i] + count[r, i]) * tile] = True
            poison = jnp.where(jnp.asarray(keep)[:, None, None], k[r], jnp.nan)
            got = np.asarray(dec_mod._walked_attention(
                q, k.at[r].set(poison), v.at[r].set(jnp.where(jnp.asarray(keep)[:, None, None], v[r], jnp.nan)),
                valid, window, scale, tile))
            np.testing.assert_array_equal(got[r, i * tile : (i + 1) * tile], clean[r, i * tile : (i + 1) * tile])


def test_the_counts_are_the_tiles_the_walk_visits(monkeypatch):
    from test_decoder_mellum import TINY

    cfg = DecoderConfig.from_hf(TINY, dtype=jnp.float32)  # window 8, 4 query heads over 2, three sliding layers
    t, tile, lengths, rows = 32, 4, [27, 9, 30], 4
    monkeypatch.setattr(dec_mod, "PREFILL_TILE", tile)
    handed = []
    kernel = dec_mod.walked_attention

    def counting(q, k, v, k_valid, lo, count, **kw):  # the bounds the kernel is handed
        handed.append(int(np.asarray(count).sum()))
        return kernel(q, k, v, k_valid, lo, count, **kw)

    monkeypatch.setattr(dec_mod, "walked_attention", counting)
    valid = jnp.asarray(_left_padded(t, lengths + [0] * (rows - len(lengths))))
    q, k, v, scale = _inputs(1, rows, t, "lfm2", jnp.float32)
    dec_mod._walked_attention(q, k, v, valid, 8, scale, dec_mod.prefill_tile(t))
    dec_mod._walked_attention(q, k, v, valid, 0, scale, dec_mod.prefill_tile(t))
    sliding, full = handed
    # the sliding layers' real rows, a layer by (query, key) pair
    walked, _, needed = dec_mod.prefill_window_scores(cfg, t, lengths)
    assert walked == 3 * sliding * tile * tile and walked > needed
    # every layer, every row (the padding row visits nothing) and head
    assert dec_mod.prefill_attention_scores(cfg, t, lengths, rows) == (
        cfg.heads * (3 * sliding + full) * tile * tile, 4 * rows * cfg.heads * t * t)
    # by hand: the row of 27 starts in tile 1 (slot 5), of 9 in tile 5 (slot 23), of 30 in tile 0 (slot 2)
    assert full == (7 * 8 // 2) + (3 * 4 // 2) + (8 * 9 // 2)
    # a sliding layer's query tile reaches back to the tile of its first real query less 7
    assert sliding == (1 + 2 + 3 + 3 + 3 + 3 + 3) + (1 + 2 + 3) + (1 + 2 + 3 + 3 + 3 + 3 + 3 + 3)


def test_latent_attention_and_the_conv_operator_count_no_walk():
    from test_decoder_lfm2 import TINY as LFM2

    lfm2 = DecoderConfig.from_hf(LFM2)
    gqa_layers = sum(kind == "gqa" for kind in lfm2.attention_pattern)
    walked, square = dec_mod.prefill_attention_scores(lfm2, 16, [16, 5], 4)
    assert square == gqa_layers * 4 * lfm2.heads * 16 * 16 and 0 < walked < square
    latent = dec_mod.tiny_latent_moe_decoder()
    assert dec_mod.prefill_attention_scores(latent, 16, [16, 5], 4) == (0, 0)


# -- through the cache --------------------------------------------------------


@pytest.mark.parametrize("tile", [3, 4, 8])
@pytest.mark.parametrize("lengths, new", [([19, 11, 14], 6), ([33, 4, 21], 12), ([26], 5)])
def test_prefill_in_tiles_then_decode_through_the_wrapped_rings_agree_with_the_reference(monkeypatch, tile, lengths, new):
    import reference_mellum as ref
    from test_decoder_mellum import ATOL, TINY, _reference_logits, _through_the_cache

    cfg = DecoderConfig.from_hf(TINY, dtype=jnp.float32)
    params = jax.tree.map(lambda a: a.astype(jnp.float32), ref.make_params(11, TINY))
    monkeypatch.setattr(dec_mod, "PREFILL_TILE", tile)
    rng = np.random.default_rng(sum(lengths) + tile)
    rows = [rng.integers(4, 512, n + new) for n in lengths]
    got, _ = _through_the_cache(cfg, params, rows, lengths, new)
    for r, (row, n) in enumerate(zip(rows, lengths)):
        want = _reference_logits(params, row, n - 1 + np.arange(new))
        for step in range(new):
            np.testing.assert_allclose(got[step][r], want[step], atol=ATOL)
