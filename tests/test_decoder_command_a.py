"""The windowed-beside-full, parallel-block decoder with a held share of its
experts against the plain reference (``benchmark/reference_command_a.py``) at
a small size on the CPU: seeded random weights, logits compared, never
sampled tokens."""

from __future__ import annotations

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import reference_command_a as ref  # noqa: E402

from pathway_tpu.models import decoder as dec_mod  # noqa: E402
from pathway_tpu.models.decoder import DecoderConfig  # noqa: E402
from pathway_tpu.ops import moe  # noqa: E402

#: the published keys at a size a test can hold: every expert held here
TINY = {
    "model_type": "cohere2_moe", "vocab_size": 512, "hidden_size": 64, "num_hidden_layers": 4,
    "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 32,
    "num_experts": 8, "num_experts_per_tok": 2, "num_shared_experts": 2, "sliding_window": 8,
    "layer_types": ["sliding_attention", "sliding_attention", "sliding_attention", "full_attention"] * 2,
    "layer_norm_eps": 1e-5, "rms_norm_eps": None, "rope_theta": 50000, "rotary_pct": 1, "logit_scale": 1,
    "norm_topk_prob": True, "expert_selection_fn": "sigmoid", "first_k_dense_replace": 0, "hidden_act": "silu",
    "attention_bias": False, "use_qk_norm": False, "use_parallel_block": True, "use_gated_activation": True,
    "shared_expert_combination_strategy": "average", "position_embedding_type": "rope_gptj",
    "order_of_interleaved_layers": "local_attn_first", "tie_word_embeddings": True, "max_position_embeddings": 4096,
}


def _share(first: int, count: int) -> dict:
    """The configuration of the chip that holds ``count`` experts from ``first``."""
    return {**TINY, "num_experts": count, "held_here": {"experts": [first, count], "of_experts": 8}}


def _share_params(params, first: int, count: int):
    """The whole tree with the experts' weights cut to one chip's."""
    cut = lambda lp: {**lp, "experts_gate_w": lp["experts_gate_w"][first : first + count],  # noqa: E731
                      "experts_down_w": lp["experts_down_w"][first : first + count]}
    return {**params, "layers": [cut(lp) for lp in params["layers"]]}


@pytest.fixture(scope="module")
def model():
    """(configuration in float32, parameters in float32 holding bfloat16
    values): both sides then compute exactly, and differ by rounding order."""
    cfg = DecoderConfig.from_hf(TINY, dtype=jnp.float32)
    params = jax.tree.map(lambda a: a.astype(jnp.float32), ref.make_params(11, TINY))
    return cfg, params


def _reference_logits(params, ids, positions, dec=TINY):
    held = ref.held_experts(dec)
    return np.asarray(ref.forward(params, jnp.asarray(ids, jnp.int32), jnp.asarray(positions, jnp.int32), dec, held))


def _through_the_cache(cfg, params, rows, lengths, new):
    """Each row's prompt left-padded to the longest, prefilled, then ``new -
    1`` decode steps fed the row's own next tokens: ``[new][rows, vocab]``."""
    width = max(lengths)
    ids, mask = np.zeros((len(rows), width), np.int32), np.zeros((len(rows), width), bool)
    for r, (row, n) in enumerate(zip(rows, lengths)):
        ids[r, width - n :], mask[r, width - n :] = row[:n], True
    logits, cache, offset, _ = dec_mod.prefill(params, jnp.asarray(ids), jnp.asarray(mask), cfg, width + new)
    got = [np.asarray(logits)]
    for step in range(new - 1):
        tok = jnp.asarray([row[n + step] for row, n in zip(rows, lengths)], jnp.int32)
        logits, cache, _ = dec_mod.decode_step(params, tok, cache, offset, cfg)
        got.append(np.asarray(logits))
    return got, cache


# -- the configuration and the tree -------------------------------------------


def test_from_hf_reads_the_published_keys_the_layer_kinds_and_the_share(model):
    cfg, params = model
    assert cfg.attention_pattern == ("sliding", "sliding", "sliding", "full")
    assert cfg.layer_pattern == ("experts",) * 4
    assert (cfg.heads, cfg.kv_heads, cfg.head_dim, cfg.sliding_window) == (8, 2, 16, 8)
    assert (cfg.norm, cfg.parallel_block, cfg.rope_interleaved, cfg.tie_embeddings) == ("layer", True, True, True)
    assert (cfg.router, cfg.shared_combine, cfg.norm_topk_prob, cfg.rms_eps) == ("sigmoid", "average", True, 1e-5)
    assert (cfg.n_routed_experts, cfg.experts_held, cfg.held_experts) == (8, 8, None)
    own = dec_mod.init_decoder_params(jax.random.key(0), cfg)
    assert jax.tree.map(jnp.shape, own) == jax.tree.map(jnp.shape, params)
    assert "lm_head" not in own and "mlp_norm" not in own["layers"][0]
    share = DecoderConfig.from_hf(_share(2, 2))
    assert (share.n_routed_experts, share.experts_held, share.held_experts) == (8, 2, (2, 2))
    shapes = jax.eval_shape(lambda: dec_mod.init_decoder_params(jax.random.key(0), share))
    assert shapes["layers"][0]["experts_gate_w"].shape == (2, 64, 64) and shapes["layers"][0]["router_w"].shape == (64, 8)


@pytest.mark.parametrize("key, other", [
    ("use_qk_norm", True), ("use_parallel_block", False), ("first_k_dense_replace", 1), ("use_gated_activation", False),
    ("shared_expert_combination_strategy", "sum"), ("position_embedding_type", "rope_neox"),
    ("order_of_interleaved_layers", "global_attn_first"), ("expert_selection_fn", "softmax"), ("attention_bias", True),
    ("tie_word_embeddings", False),
])
def test_from_hf_refuses_by_name_a_key_whose_other_value_it_does_not_implement(key, other):
    with pytest.raises(ValueError, match=key):
        DecoderConfig.from_hf({**TINY, key: other})


def test_from_hf_refuses_a_layer_kind_it_does_not_know_and_a_share_that_does_not_add_up():
    with pytest.raises(ValueError, match="layer_types"):
        DecoderConfig.from_hf({**TINY, "layer_types": ["sliding_attention", "linear_attention"] * 2})
    with pytest.raises(ValueError, match="held_here"):
        DecoderConfig.from_hf({**_share(0, 2), "num_experts": 4})


def test_a_windowed_layers_cache_is_a_ring_of_the_window_and_a_full_layers_holds_every_position(model):
    cfg, _ = model
    cache = dec_mod.init_cache(cfg, 3, 20)
    assert [state["k"].shape for state in cache.layers] == [(3, 8, 2, 16)] * 3 + [(3, 20, 2, 16)]
    assert cache.valid.shape == (3, 20)
    short = dec_mod.init_cache(cfg, 3, 6)  # fewer positions than the window: no layer keeps more than there are
    assert [state["v"].shape[1] for state in short.layers] == [6] * 4


@pytest.mark.parametrize("make, count", [(dec_mod.mistral_7b, 3 + 32 * 7), (dec_mod.tiny_latent_moe_decoder, 3 + 9 + 2 * 12)])
def test_the_other_decoders_trees_are_as_they_were(make, count):
    cfg = make()
    shapes = jax.eval_shape(lambda: dec_mod.init_decoder_params(jax.random.key(0), cfg))
    assert len(jax.tree.leaves(shapes)) == count
    assert "lm_head" in shapes and all("mlp_norm" in lp and "attn_norm" in lp for lp in shapes["layers"])
    assert cfg.attention_pattern == (cfg.attention,) * cfg.layers and cfg.experts_held == cfg.n_routed_experts
    if cfg.n_routed_experts:
        assert shapes["layers"][1]["experts_gate_w"].shape[0] == cfg.n_routed_experts


FROZEN_SUMS = {"gqa": 10958.0712890625, "mla": 22463.185546875}


def test_the_tiny_presets_parameters_are_the_numbers_they_were():
    """Frozen at the parent commit: the same keys draw the same tensors."""
    sums = {}
    for name, cfg in (("gqa", dec_mod.tiny_decoder()), ("mla", dec_mod.tiny_latent_moe_decoder())):
        params = dec_mod.init_decoder_params(jax.random.key(5), cfg)
        sums[name] = float(sum(jnp.abs(leaf).sum() for leaf in jax.tree.leaves(params)))
    assert sums == pytest.approx(FROZEN_SUMS, rel=1e-6)


# -- the pieces ---------------------------------------------------------------


def test_the_norm_subtracts_the_mean_and_is_the_references():
    x = jnp.asarray(np.random.default_rng(0).normal(3.0, 2.0, (5, 64)), jnp.float32)
    g = jnp.asarray(np.random.default_rng(1).normal(1.0, 0.1, 64), jnp.float32)
    got = dec_mod.layer_norm(x, g, 1e-5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref.layer_norm(x, g, 1e-5)), atol=1e-6)
    np.testing.assert_allclose(np.asarray((got / g).mean(-1)), 0.0, atol=1e-5)
    assert not np.allclose(np.asarray(got), np.asarray(dec_mod.rms_norm(x, g, 1e-5)), atol=0.1)


def test_interleaved_rope_turns_the_pairs_the_reference_turns():
    x = jnp.asarray(np.random.default_rng(2).normal(size=(1, 6, 3, 16)), jnp.float32)
    at = jnp.asarray([[0, 1, 2, 5, 9, 40]])
    got = dec_mod.rope(x, at, 50000.0, interleaved=True)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(ref.rotate_pairs(x[0], at[0], 50000.0)), atol=1e-5)
    # pair (0, 1) turns by the position itself; the split layout pairs 0 with 8
    want = x[0, 3, 0, 0] * np.cos(5.0) - x[0, 3, 0, 1] * np.sin(5.0)
    assert float(got[0, 3, 0, 0]) == pytest.approx(float(want), abs=1e-5)
    assert not np.allclose(np.asarray(got), np.asarray(dec_mod.rope(x, at, 50000.0)), atol=1e-3)


@pytest.mark.parametrize("held", [None, (0, 2), (6, 2)])
def test_sigmoid_weights_sum_to_one_over_the_chosen_whatever_is_held(model, held):
    _, params = model
    h = jnp.asarray(np.random.default_rng(3).normal(size=(20, 64)), jnp.float32)
    w = params["layers"][0]["router_w"]
    weights, experts = moe.route_top_k(h, w, 2, renormalize=True, scoring="sigmoid")
    np.testing.assert_allclose(np.asarray(weights.sum(-1)), 1.0, atol=1e-6)
    scores = 1.0 / (1.0 + np.exp(-np.asarray(h, np.float64) @ np.asarray(w, np.float64)))
    order = np.argsort(-scores, axis=-1, kind="stable")[:, :2]
    np.testing.assert_array_equal(np.asarray(experts), order)
    top = np.take_along_axis(scores, order, axis=-1)
    np.testing.assert_allclose(np.asarray(weights), top / top.sum(-1, keepdims=True), atol=1e-6)
    # the reference's weights are the same numbers, and a share sees its own columns of them
    dense = np.asarray(ref.router(h, w, TINY))
    first, count = held or (0, 8)
    np.testing.assert_allclose(dense.sum(-1), 1.0, atol=1e-6)
    here = (np.asarray(experts) >= first) & (np.asarray(experts) < first + count)
    np.testing.assert_allclose(dense[:, first : first + count].sum(-1), (np.asarray(weights) * here).sum(-1), atol=1e-6)


def test_a_tie_between_sigmoid_scores_goes_to_the_lower_id():
    weights, experts = moe.route_top_k(jnp.ones((1, 4)), jnp.zeros((4, 6)), 2, renormalize=True, scoring="sigmoid")
    assert list(np.asarray(experts[0])) == [0, 1] and list(np.asarray(weights[0])) == [0.5, 0.5]


def test_the_head_is_the_embedding_times_the_logit_scale(model):
    cfg, params = model
    x = jnp.asarray(np.random.default_rng(4).normal(size=(3, 64)), jnp.float32)
    want = np.asarray(ref.layer_norm(x, params["final_norm"], 1e-5)) @ np.asarray(params["tok_emb"]).T
    np.testing.assert_allclose(np.asarray(dec_mod._head(params, x, cfg)), want, atol=1e-4)
    scaled = dataclasses.replace(cfg, logit_scale=0.25)
    np.testing.assert_allclose(np.asarray(dec_mod._head(params, x, scaled)), 0.25 * want, atol=1e-4)


# -- the forward pass, with and without the cache -----------------------------


def test_the_full_forward_pass_agrees_with_the_reference_at_every_position_past_the_window(model):
    cfg, params = model
    ids = np.random.default_rng(5).integers(4, 512, 21)
    logits, _ = dec_mod.decoder_forward(params, jnp.asarray(ids[None], jnp.int32), cfg)
    np.testing.assert_allclose(np.asarray(logits[0]), _reference_logits(params, ids, np.arange(21)), atol=2e-4)


@pytest.mark.parametrize("lengths, new", [
    ([5, 7, 6], 3),  # every prompt and every answer inside the window: the ring never wraps
    ([6, 8, 3], 7),  # the answers cross the window: decode wraps the ring
    ([19, 11, 14], 6),  # prompts longer than the window: prefill keeps their last 8 positions
    ([23, 4, 9], 12),  # a row inside the window beside rows past it, padded to different lengths
])
def test_prefill_then_decode_through_the_rings_agree_with_the_references_full_pass(model, lengths, new):
    cfg, params = model
    rng = np.random.default_rng(sum(lengths))
    rows = [rng.integers(4, 512, n + new) for n in lengths]
    got, cache = _through_the_cache(cfg, params, rows, lengths, new)
    assert int(cache.length) == max(lengths) + new - 1
    assert [state["k"].shape[1] for state in cache.layers] == [8, 8, 8, max(lengths) + new]
    for r, (row, n) in enumerate(zip(rows, lengths)):
        want = _reference_logits(params, row, n - 1 + np.arange(new))
        for step in range(new):
            np.testing.assert_allclose(got[step][r], want[step], atol=3e-4)


def test_a_chunk_of_several_tokens_into_a_ring_that_wraps_is_refused(model):
    cfg, params = model
    ids = jnp.asarray(np.random.default_rng(6).integers(4, 512, (1, 12)), jnp.int32)
    _, cache, offset, _ = dec_mod.prefill(params, ids[:, :9], None, cfg, 12)
    with pytest.raises(NotImplementedError, match="wraps"):
        dec_mod.decoder_forward(params, ids[:, 9:], cfg, cache, pos_offset=offset)
    # a cache no longer than the window is written as any buffer is, several tokens at once too
    _, cache, offset, _ = dec_mod.prefill(params, ids[:, :5], None, cfg, 8)
    chunk, _ = dec_mod.decoder_forward(params, ids[:, 5:8], cfg, cache, pos_offset=offset)
    want = _reference_logits(params, np.asarray(ids[0, :8]), np.arange(5, 8))
    np.testing.assert_allclose(np.asarray(chunk[0]), want, atol=3e-4)


def test_attention_in_blocks_of_queries_equals_attention_at_once(model, monkeypatch):
    # a chunk into a cache attends over the cache's keys, the masked product's case (a chunk
    # over its own keys is walked in tiles); a cache of the window's length never wraps
    cfg, params = model
    ids = jnp.asarray(np.random.default_rng(7).integers(4, 512, (2, 8)), jnp.int32)
    whole, _ = dec_mod.decoder_forward(params, ids, cfg, dec_mod.init_cache(cfg, 2, 8))
    monkeypatch.setattr(dec_mod, "ATTENTION_BLOCK_SCORES", 2 * 8 * 4 * 8)  # four queries a block
    blocks, _ = dec_mod.decoder_forward(params, ids, cfg, dec_mod.init_cache(cfg, 2, 8))
    np.testing.assert_allclose(np.asarray(blocks), np.asarray(whole), atol=1e-5)
    walked, _ = dec_mod.decoder_forward(params, ids, cfg)
    np.testing.assert_allclose(np.asarray(blocks), np.asarray(walked), atol=1e-4)


def _one_layer(cfg, params, layer: int):
    """The model cut to one of its layers, and that layer alone over a
    chunk: hidden states ``[b, t, hidden]``."""
    one = dataclasses.replace(cfg, layers=1, layer_types=(cfg.layer_types[layer],))
    tree = {**params, "layers": [params["layers"][layer]]}
    return lambda ids, offset: dec_mod._stack(tree, ids, one, None, None, offset)[0]


def test_a_full_layer_does_not_turn_with_the_position_and_a_sliding_layer_does(model):
    cfg, params = model
    ids = jnp.asarray(np.random.default_rng(8).integers(4, 512, (1, 6)), jnp.int32)
    # an offset of 3 puts the first four tokens at position 0 and the rest at 1, 2: other distances
    here, offset = jnp.zeros((1,), jnp.int32), jnp.full((1,), 3, jnp.int32)
    full, sliding = _one_layer(cfg, params, 3), _one_layer(cfg, params, 0)
    np.testing.assert_array_equal(np.asarray(full(ids, here)), np.asarray(full(ids, offset)))
    assert np.abs(np.asarray(sliding(ids, here)) - np.asarray(sliding(ids, offset))).max() > 1e-3
    # the table a sliding layer turns by is rope_theta's, and a full layer has none
    other = dataclasses.replace(cfg, rope_theta=100.0)
    assert np.abs(np.asarray(_one_layer(other, params, 0)(ids, here)) - np.asarray(sliding(ids, here))).max() > 1e-3
    np.testing.assert_array_equal(np.asarray(_one_layer(other, params, 3)(ids, here)), np.asarray(full(ids, here)))


# -- the chip's share of the experts ------------------------------------------


def test_the_shares_add_up_to_the_uncut_layer(model):
    """Four chips of two experts each: the routed parts of the four shares,
    plus attention and the averaged shared experts counted once, are the
    reference's whole layer."""
    cfg, params = model
    ids = jnp.asarray(np.random.default_rng(9).integers(4, 512, (1, 11)), jnp.int32)
    x = np.asarray(params["tok_emb"])[np.asarray(ids[0])]
    for layer, kind in enumerate(TINY["layer_types"][:4]):
        want = np.asarray(ref.layer(jnp.asarray(x), params["layers"][layer], kind, TINY))
        outs = []
        for first in (0, 2, 4, 6):
            share = DecoderConfig.from_hf(_share(first, 2), dtype=jnp.float32)
            out = _one_layer(share, _share_params(params, first, 2), layer)(ids, None)
            outs.append(np.asarray(out[0], np.float64))
        # out_j = x + a + sbar + r_j: the first whole, of the others what their experts add to it
        nothing_routed = np.asarray(ref.layer(jnp.asarray(x), _zero_routed(params["layers"][layer]), kind, TINY), np.float64)
        total = nothing_routed + sum(out - nothing_routed for out in outs)
        np.testing.assert_allclose(total, want, atol=2e-4)
        assert max(np.abs(out - nothing_routed).max() for out in outs) > 1e-2  # every share adds something


def _zero_routed(lp):
    return {**lp, "experts_down_w": jnp.zeros_like(lp["experts_down_w"])}


def test_a_share_through_the_cache_agrees_with_the_reference_given_the_same_share():
    dec = _share(4, 2)
    cfg = DecoderConfig.from_hf(dec, dtype=jnp.float32)
    params = jax.tree.map(lambda a: a.astype(jnp.float32), ref.make_params(13, dec))
    assert params["layers"][0]["experts_gate_w"].shape[0] == 2 and params["layers"][0]["router_w"].shape[1] == 8
    rng = np.random.default_rng(10)
    lengths, new = [12, 5], 5
    rows = [rng.integers(4, 512, n + new) for n in lengths]
    got, _ = _through_the_cache(cfg, params, rows, lengths, new)
    for r, (row, n) in enumerate(zip(rows, lengths)):
        want = _reference_logits(params, row, n - 1 + np.arange(new), dec)
        for step in range(new):
            np.testing.assert_allclose(got[step][r], want[step], atol=3e-4)
    # the same weights read as another chip's share give another answer: the share is not ignored
    other = _reference_logits(params, rows[0], [lengths[0] - 1], _share(0, 2))
    assert np.abs(other[0] - got[0][0]).max() > 1e-3


def _grouped_inputs(n=10, hidden=16, width=8, held=2, k=2, seed=31):
    rng = np.random.default_rng(seed)
    h = jnp.asarray(rng.normal(size=(n, hidden)), jnp.float32)
    gate_up = jnp.asarray(rng.normal(size=(held, hidden, 2 * width)) / 4, jnp.float32)
    down = jnp.asarray(rng.normal(size=(held, width, hidden)) / 3, jnp.float32)
    weights = jnp.asarray(rng.uniform(0.1, 1.0, (n, k)), jnp.float32)
    return h, weights, gate_up, down


def _plain_experts(h, weights, experts, gate_up, down, first):
    """Every pair by hand: a choice outside the held experts adds nothing."""
    out = np.zeros(h.shape, np.float64)
    width = down.shape[1]
    for n in range(h.shape[0]):
        for wt, e in zip(np.asarray(weights[n]), np.asarray(experts[n])):
            if first <= e < first + gate_up.shape[0]:
                gu = np.asarray(h[n], np.float64) @ np.asarray(gate_up[e - first], np.float64)
                act = gu[:width] / (1 + np.exp(-gu[:width])) * gu[width:]
                out[n] += wt * (act @ np.asarray(down[e - first], np.float64))
    return out


def test_a_token_whose_choices_all_lie_elsewhere_and_a_padding_token_take_no_expert_and_the_counts_say_so():
    h, weights, gate_up, down = _grouped_inputs()
    # experts 4 and 5 are held; token 0 chose both, 1 one of them, 2 none, 3 is padding that chose both
    experts = jnp.asarray([[4, 5], [5, 1], [0, 7], [4, 5]] + [[2, 4], [6, 3], [5, 4], [7, 7], [3, 5], [4, 0]], jnp.int32)
    counted = jnp.asarray([True, True, True, False] + [True] * 6)
    y, sizes = moe.routed_experts(h, weights, experts, gate_up, down, counted, (4, 2))
    want = _plain_experts(h, weights, experts, gate_up, down, 4)
    want[3] = 0.0
    np.testing.assert_allclose(np.asarray(y), want, atol=1e-5)
    assert not np.asarray(y[2]).any() and not np.asarray(y[3]).any()
    assert list(np.asarray(sizes)) == [1 + 1 + 1 + 1, 1 + 1 + 1 + 1]  # the real tokens' pairs of experts 4 and 5
    # whatever the tokens that take nothing hold, the others' rows are the same to the bit
    poisoned = h.at[2].set(jnp.inf).at[3].set(jnp.nan)
    y2, sizes2 = moe.routed_experts(poisoned, weights, experts, gate_up, down, counted, (4, 2))
    keep = np.asarray([0, 1, 4, 5, 6, 7, 8, 9])
    np.testing.assert_array_equal(np.asarray(y2)[keep], np.asarray(y)[keep])
    assert not np.asarray(y2[2]).any() and not np.asarray(y2[3]).any()
    np.testing.assert_array_equal(np.asarray(sizes2), np.asarray(sizes))


def test_a_share_that_holds_every_expert_is_the_whole_product_to_the_bit():
    h, weights, gate_up, down = _grouped_inputs(held=8)
    experts = jnp.asarray(np.random.default_rng(32).integers(0, 8, (10, 2)), jnp.int32)
    whole, sizes = moe.routed_experts(h, weights, experts, gate_up, down)
    held, held_sizes = moe.routed_experts(h, weights, experts, gate_up, down, None, (0, 8))
    np.testing.assert_array_equal(np.asarray(held), np.asarray(whole))
    np.testing.assert_array_equal(np.asarray(held_sizes), np.asarray(sizes))
    assert int(sizes.sum()) == 20


def test_the_counts_of_a_pass_are_of_the_held_experts_and_real_tokens_alone():
    dec = _share(2, 2)
    cfg = DecoderConfig.from_hf(dec, dtype=jnp.float32)
    params = jax.tree.map(lambda a: a.astype(jnp.float32), ref.make_params(17, dec))
    rng = np.random.default_rng(12)
    ids = jnp.asarray(rng.integers(4, 512, (3, 10)), jnp.int32)
    mask = jnp.asarray(np.arange(10)[None, :] >= np.asarray([0, 4, 10])[:, None])  # 10, 6 and 0 real tokens
    _, _, _, stats = dec_mod.prefill(params, ids, mask, cfg, 12)
    assert stats.load.shape == (4, 2) and 0 < int(stats.load.sum()) < 4 * 2 * 16
    # layer 0's counts by hand: the reference's router over the real tokens' normed embeddings
    x = jnp.asarray(np.asarray(params["tok_emb"])[np.asarray(ids)][np.asarray(mask)])
    h = ref.layer_norm(x, params["layers"][0]["attn_norm"], 1e-5)
    chosen = np.asarray(ref.router(h, params["layers"][0]["router_w"], dec)) > 0
    assert list(np.asarray(stats.load[0])) == [int(chosen[:, 2].sum()), int(chosen[:, 3].sum())]
    assert int(stats.touched) == int(np.count_nonzero(np.asarray(stats.load)))
