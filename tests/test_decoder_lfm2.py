"""The gated-short-convolution decoder (``lfm2_moe``: conv layers with a
fixed-size state beside one grouped-query cache, a norm a head, a sigmoid
router whose bias picks and whose score weighs) against the plain reference
(``benchmark/reference_lfm2.py``) at a small size on the CPU: seeded random
weights, logits compared, never sampled tokens."""

from __future__ import annotations

import dataclasses
import json
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

import reference_lfm2 as ref  # noqa: E402

from pathway_tpu.models import decoder as dec_mod  # noqa: E402
from pathway_tpu.models.decoder import DecoderConfig  # noqa: E402
from pathway_tpu.ops import moe  # noqa: E402

#: ``tiny_hybrid_moe_decoder`` under the published keys' names
TINY = {
    "model_type": "lfm2_moe", "vocab_size": 512, "hidden_size": 64, "num_hidden_layers": 5,
    "num_attention_heads": 4, "num_key_value_heads": 2, "intermediate_size": 160, "moe_intermediate_size": 32,
    "num_experts": 8, "num_experts_per_tok": 2, "num_dense_layers": 1, "conv_L_cache": 3, "conv_bias": False,
    "layer_types": ["conv", "full_attention", "conv", "conv", "conv"], "norm_eps": 1e-5, "norm_topk_prob": True,
    "use_expert_bias": True, "routed_scaling_factor": 1, "max_position_embeddings": 4096,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
}
CONV_LAYERS = (0, 2, 3, 4)
#: float32 on both sides, the same bfloat16-valued weights: what is left is the
#: order of float32 sums (the program's grouped product against the
#: reference's loop over every expert, attention's blocks), some 1e-5 of
#: logits that spread by 0.5, through five layers
ATOL = 3e-4


@pytest.fixture(scope="module")
def model():
    """(configuration in float32, parameters in float32 holding bfloat16
    values, ``expert_bias`` wide enough to move choices at this size): both
    sides then compute exactly, and differ by rounding order."""
    cfg = DecoderConfig.from_hf(TINY, dtype=jnp.float32)
    params = jax.tree.map(lambda a: a.astype(jnp.float32), ref.make_params(11, TINY))
    rng = np.random.default_rng(12)
    for lp in params["layers"]:
        if "expert_bias" in lp:
            lp["expert_bias"] = jnp.asarray(rng.normal(0.0, 0.05, 8), jnp.float32)
    return cfg, params


def _reference_logits(params, ids, positions, operand=None):
    logits, _ = ref.forward(params, jnp.asarray(ids, jnp.int32), jnp.asarray(positions, jnp.int32), TINY, operand)
    return np.asarray(logits)


def _padded(rows, lengths, width):
    ids, mask = np.zeros((len(rows), width), np.int32), np.zeros((len(rows), width), bool)
    for r, (row, n) in enumerate(zip(rows, lengths)):
        ids[r, width - n :], mask[r, width - n :] = row[:n], True
    return jnp.asarray(ids), jnp.asarray(mask)


def _through_the_cache(cfg, params, rows, lengths, new, width=None):
    """Each row's prompt left-padded to ``width`` (the longest), prefilled,
    then ``new - 1`` decode steps fed the row's own next tokens:
    ``[new][rows, vocab]``, the cache after prefill and at the end."""
    width = width or max(lengths)
    ids, mask = _padded(rows, lengths, width)
    logits, cache, offset, _ = dec_mod.prefill(params, ids, mask, cfg, width + new)
    got, after_prefill = [np.asarray(logits)], cache
    for step in range(new - 1):
        tok = jnp.asarray([row[n + step] for row, n in zip(rows, lengths)], jnp.int32)
        logits, cache, _ = dec_mod.decode_step(params, tok, cache, offset, cfg)
        got.append(np.asarray(logits))
    return got, after_prefill, cache


# -- (e) the configuration and the tree ---------------------------------------


def test_from_hf_reads_the_published_keys_and_the_tiny_preset_is_the_same_model(model):
    cfg, params = model
    assert cfg.attention_pattern == ("conv", "gqa", "conv", "conv", "conv")
    assert cfg.layer_pattern == ("dense", "experts", "experts", "experts", "experts")
    assert (cfg.heads, cfg.kv_heads, cfg.head_dim, cfg.conv_taps, cfg.rope_theta) == (4, 2, 16, 3, 1e6)
    assert (cfg.router, cfg.router_bias, cfg.norm_topk_prob, cfg.n_shared_experts) == ("sigmoid", True, True, 0)
    assert (cfg.qk_norm, cfg.tie_embeddings, cfg.norm, cfg.parallel_block, cfg.rope_interleaved) == (True, True, "rms", False, False)
    assert dataclasses.replace(cfg, dtype=jnp.bfloat16) == dec_mod.tiny_hybrid_moe_decoder()
    own = dec_mod.init_decoder_params(jax.random.key(0), cfg)
    assert jax.tree.map(jnp.shape, own) == jax.tree.map(jnp.shape, params)
    assert "lm_head" not in own and "shared_gate_w" not in own["layers"][1]
    assert set(own["layers"][0]) == {"conv_in_w", "conv_w", "o_w", "gate_w", "down_w", "attn_norm", "mlp_norm"}
    assert {"q_norm", "k_norm", "expert_bias"} <= set(own["layers"][1]) and "q_norm" not in own["layers"][2]
    assert float(jnp.abs(own["layers"][1]["expert_bias"]).max()) > 0


def test_from_hf_on_the_catalogs_row_gives_the_published_widths():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as fh:
        row = next(r for r in map(json.loads, fh) if r["name"] == "LFM2-24B-A2B")
    cfg = DecoderConfig.from_hf(row["config"])
    assert (cfg.hidden, cfg.layers, cfg.heads, cfg.kv_heads, cfg.head_dim, cfg.vocab_size) == (2048, 40, 32, 8, 64, 65536)
    assert (cfg.intermediate, cfg.moe_intermediate, cfg.n_routed_experts, cfg.experts_per_token) == (11776, 1536, 64, 4)
    assert (cfg.first_dense_layers, cfg.conv_taps, cfg.rope_theta, cfg.rms_eps) == (2, 3, 1e6, 1e-5)
    assert cfg.attention_pattern[:6] == ("conv", "conv", "gqa", "conv", "conv", "conv")
    assert cfg.attention_pattern.count("gqa") == 10 and cfg.attention_pattern.count("conv") == 30
    shapes = jax.eval_shape(lambda: dec_mod.init_decoder_params(jax.random.key(0), dataclasses.replace(cfg, layers=3, layer_types=cfg.layer_types[:3])))
    conv, attn = shapes["layers"][0], shapes["layers"][2]
    assert (conv["conv_in_w"].shape, conv["conv_w"].shape, conv["o_w"].shape) == ((2048, 6144), (2048, 3), (2048, 2048))
    assert (conv["gate_w"].shape, attn["q_w"].shape, attn["kv_w"].shape) == ((2048, 2 * 11776), (2048, 2048), (2048, 1024))
    assert (attn["experts_gate_w"].shape, attn["router_w"].shape, attn["expert_bias"].shape) == ((64, 2048, 3072), (2048, 64), (64,))
    assert attn["q_norm"].shape == attn["k_norm"].shape == (64,)


def test_a_full_attention_layer_rotates_in_this_family_and_takes_no_positions_in_command_a():
    """The kind comes from ``model_type``, not from the string."""
    from test_decoder_command_a import TINY as COMMAND_A

    assert DecoderConfig.from_hf(TINY).attention_pattern[1] == "gqa"
    assert DecoderConfig.from_hf(COMMAND_A).attention_pattern[3] == "full"
    cfg = DecoderConfig.from_hf(TINY, dtype=jnp.float32)
    params = dec_mod.init_decoder_params(jax.random.key(3), cfg)
    one = dataclasses.replace(cfg, layers=1, layer_types=("gqa",), first_dense_layers=1)
    tree = {**params, "layers": [{**params["layers"][1], **{k: params["layers"][0][k] for k in ("gate_w", "down_w")}}]}
    ids = jnp.asarray(np.random.default_rng(8).integers(4, 512, (1, 6)), jnp.int32)
    here, offset = jnp.zeros((1,), jnp.int32), jnp.full((1,), 3, jnp.int32)
    turned = [np.asarray(dec_mod._stack(tree, ids, one, None, None, at)[0]) for at in (here, offset)]
    assert np.abs(turned[0] - turned[1]).max() > 1e-3


@pytest.mark.parametrize("change, match", [
    ({"model_type": "lfm3"}, "model_type"), ({"conv_bias": True}, "conv_bias"), ({"use_expert_bias": False}, "use_expert_bias"),
    ({"tie_embedding": False}, "tie_embedding"), ({"layer_types": ["conv", "linear_attention", "conv", "conv", "conv"]}, "layer_types"),
    ({"layer_types": ["conv", "full_attention"]}, "layer_types"),
    ({"rope_parameters": {"rope_theta": 1000000, "rope_type": "yarn"}}, "rope_parameters"),
])
def test_from_hf_still_refuses_what_it_does_not_implement(change, match):
    with pytest.raises(ValueError, match=match):
        DecoderConfig.from_hf({**TINY, **change})


# -- (d) the router -----------------------------------------------------------


def test_the_bias_picks_and_the_score_weighs(model):
    _, params = model
    lp = params["layers"][1]
    h = jnp.asarray(np.random.default_rng(3).normal(size=(200, 64)), jnp.float32)
    weights, experts = moe.route_top_k(h, lp["router_w"], 2, renormalize=True, scoring="sigmoid", select_bias=lp["expert_bias"])
    _, unbiased = moe.route_top_k(h, lp["router_w"], 2, renormalize=True, scoring="sigmoid")
    moved = (np.sort(np.asarray(experts), -1) != np.sort(np.asarray(unbiased), -1)).any(-1)
    assert 0.05 < moved.mean() < 0.95  # the bias changes some tokens' choice, and not every one's
    scores = 1.0 / (1.0 + np.exp(-np.asarray(h, np.float64) @ np.asarray(lp["router_w"], np.float64)))
    order = np.argsort(-(scores + np.asarray(lp["expert_bias"], np.float64)), axis=-1, kind="stable")[:, :2]
    np.testing.assert_array_equal(np.asarray(experts), order)
    top = np.take_along_axis(scores, order, axis=-1)  # without the bias
    np.testing.assert_allclose(np.asarray(weights), top / (top.sum(-1, keepdims=True) + 1e-6), atol=1e-6)
    assert np.all(np.asarray(weights.sum(-1)) < 1.0)  # the published + 1e-6
    dense, ref_moved = ref.router(h, lp, TINY)
    np.testing.assert_array_equal(np.asarray(ref_moved), moved)
    np.testing.assert_allclose(np.take_along_axis(np.asarray(dense), order, axis=-1), np.asarray(weights), atol=1e-6)


@pytest.mark.parametrize("scoring", ["softmax", "sigmoid"])
@pytest.mark.parametrize("renormalize", [False, True])
def test_without_a_bias_the_router_is_what_it_was_to_the_bit(scoring, renormalize):
    rng = np.random.default_rng(4)
    h, w = jnp.asarray(rng.normal(size=(50, 64)), jnp.float32), jnp.asarray(rng.normal(size=(64, 8)) / 8, jnp.float32)
    weights, experts = moe.route_top_k(h, w, 3, renormalize=renormalize, scale=2.5, scoring=scoring)
    # the parent commit's lines, written out
    logits = jnp.matmul(h, w, precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits) if scoring == "sigmoid" else jax.nn.softmax(logits, axis=-1)
    top_weights, old_experts = jax.lax.top_k(scores, 3)
    old_weights = top_weights / (top_weights.sum(-1, keepdims=True) + 1e-20) if renormalize else top_weights
    np.testing.assert_array_equal(np.asarray(weights), np.asarray(old_weights * 2.5))
    np.testing.assert_array_equal(np.asarray(experts), np.asarray(old_experts))
    # a bias of zeros picks the same experts and, before the norm, weighs them the same
    zero_weights, zero_experts = moe.route_top_k(h, w, 3, scale=2.5, scoring=scoring, select_bias=jnp.zeros(8))
    np.testing.assert_array_equal(np.asarray(zero_weights), np.asarray(top_weights * 2.5))
    np.testing.assert_array_equal(np.asarray(zero_experts), np.asarray(old_experts))


# -- (a), (g) the forward pass, with and without the cache --------------------


def test_the_full_forward_pass_agrees_with_the_reference_at_every_position(model):
    cfg, params = model
    ids = np.random.default_rng(5).integers(4, 512, 21)
    logits, _ = dec_mod.decoder_forward(params, jnp.asarray(ids[None], jnp.int32), cfg)
    np.testing.assert_allclose(np.asarray(logits[0]), _reference_logits(params, ids, np.arange(21)), atol=ATOL)


@pytest.mark.parametrize("lengths, new", [
    ([5, 7, 6], 3),
    ([19, 1, 14], 6),  # a prompt of one token beside long ones: its filter sees zeros, not its neighbours
    ([2, 23, 9], 12),  # a prompt shorter than the filter
    ([8], 5),  # no padding at all
])
def test_prefill_then_decode_through_both_kinds_of_state_agree_with_the_references_full_pass(model, lengths, new):
    cfg, params = model
    rng = np.random.default_rng(sum(lengths))
    rows = [rng.integers(4, 512, n + new) for n in lengths]
    got, _, cache = _through_the_cache(cfg, params, rows, lengths, new)
    assert int(cache.length) == max(lengths) + new - 1
    assert [sorted(state) for state in cache.layers] == [["conv"], ["k", "v"], ["conv"], ["conv"], ["conv"]]
    assert cache.layers[0]["conv"].shape == (len(rows), 64, 3) and cache.layers[1]["k"].shape[1] == max(lengths) + new
    for r, (row, n) in enumerate(zip(rows, lengths)):
        want = _reference_logits(params, row, n - 1 + np.arange(new))
        for step in range(new):
            np.testing.assert_allclose(got[step][r], want[step], atol=ATOL)


def test_a_chunk_into_a_filled_cache_and_the_decode_loop_agree_with_the_reference(model):
    cfg, params = model
    ids = np.random.default_rng(6).integers(4, 512, 14)
    _, cache, offset, _ = dec_mod.prefill(params, jnp.asarray(ids[None, :6], jnp.int32), None, cfg, 16)
    chunk, cache = dec_mod.decoder_forward(params, jnp.asarray(ids[None, 6:10], jnp.int32), cfg, cache, pos_offset=offset)
    np.testing.assert_allclose(np.asarray(chunk[0]), _reference_logits(params, ids, np.arange(6, 10)), atol=ATOL)
    # the loop the chat runs: greedy tokens, each step's logit of its own token
    first = jnp.asarray([ids[10]], jnp.int32)
    tokens, chosen, stats = dec_mod.decode_loop(params, cache, first, offset, cfg, 3, dec_mod.greedy)
    seq = np.concatenate([ids[:11], np.asarray(tokens[0])])
    want = _reference_logits(params, seq, np.arange(10, 13))
    np.testing.assert_allclose(np.asarray(chosen[0]), want[np.arange(3), np.asarray(tokens[0])], atol=ATOL)
    assert stats.load.shape == (4, 8) and int(stats.load.sum()) == 3 * 4 * 2


def test_float8_operands_in_the_experts_products_fail_the_tolerance(model):
    """(g) The tolerance is tight enough to refuse one precision down."""
    _, params = model
    ids = np.random.default_rng(5).integers(4, 512, 21)
    exact = _reference_logits(params, ids, np.arange(21))
    low = _reference_logits(params, ids, np.arange(21), ref.quantize_fp8)
    assert np.abs(low - exact).max() > 30 * ATOL


# -- (b), (c) the state, and the padding rule ---------------------------------


@pytest.mark.parametrize("pad", [0, 1, 2, 3, 40])
def test_a_rows_state_and_what_it_is_served_do_not_follow_its_padding_or_its_neighbours(model, pad):
    """(b) The filter adds nothing of the padding or of the other rows, to
    the bit: every conv layer, handed the same values at a row's real
    positions, leaves the same state and the same outputs there whatever
    lies before them (poisoned here) and beside them. In the whole model
    that is the state of the conv layer before attention as it stands;
    the conv layers after it read what attention gave, and attention sums
    its keys in another order when a row is padded further (the masked
    scores are exact zeros after the softmax, their place in the float32
    sum is not), so their states and the logits agree within that rounding."""
    cfg, params = model
    rng = np.random.default_rng(21)
    row, n, new = rng.integers(4, 512, 16), 11, 5
    h_row = jnp.asarray(rng.normal(size=(n, 64)), jnp.float32)
    for layer in CONV_LAYERS:
        lp = params["layers"][layer]
        alone_out, alone_state = dec_mod._short_conv(h_row[None], lp, cfg, dec_mod._conv_state(cfg, 1), None)
        batch = jnp.asarray(rng.normal(size=(3, n + pad, 64)), jnp.float32).at[1, pad:].set(h_row).at[1, :pad].set(jnp.nan)
        mask = jnp.asarray(np.arange(n + pad)[None, :] >= np.asarray([0, pad, n + pad - 1])[:, None])
        out, state = dec_mod._short_conv(batch, lp, cfg, dec_mod._conv_state(cfg, 3), mask)
        np.testing.assert_array_equal(np.asarray(state["conv"][1]), np.asarray(alone_state["conv"][0]))
        np.testing.assert_array_equal(np.asarray(out[1, pad:]), np.asarray(alone_out[0]))
    alone, alone_cache, _ = _through_the_cache(cfg, params, [row], [n], new)
    others = [rng.integers(4, 512, n + pad + new) for _ in range(2)]
    lengths = [n + pad, n, max(1, pad)]  # the row between a longer neighbour and a short one
    got, cache, _ = _through_the_cache(cfg, params, [others[0], row, others[1]], lengths, new, width=n + pad)
    np.testing.assert_array_equal(np.asarray(cache.layers[0]["conv"][1]), np.asarray(alone_cache.layers[0]["conv"][0]))
    for layer in CONV_LAYERS[1:]:  # float32 rounding of attention's sums, on values up to 3
        np.testing.assert_allclose(np.asarray(cache.layers[layer]["conv"][1]), np.asarray(alone_cache.layers[layer]["conv"][0]), atol=1e-5)
    for step in range(new):  # the same rounding, on logits that spread by 0.5
        np.testing.assert_allclose(got[step][1], alone[step][0], atol=2e-5)


@pytest.mark.parametrize("n", [1, 2, 3, 9])
def test_the_state_after_prefill_is_the_rows_last_three_filter_inputs(model, n):
    """(c) Layer 0's state by hand: ``z = B * u`` of the row's normed
    embeddings at its last three positions, zeros where the row is shorter."""
    cfg, params = model
    row = np.random.default_rng(22).integers(4, 512, n)
    ids, mask = _padded([row, np.arange(4, 13)], [n, 9], 9)
    _, cache, _, _ = dec_mod.prefill(params, ids, mask, cfg, 12)
    lp = params["layers"][0]
    x = jnp.asarray(np.asarray(params["tok_emb"])[row])
    z, _ = ref.filter_input(ref.rms(x, lp["attn_norm"], 1e-5), lp)
    want = np.zeros((64, 3), np.float32)
    want[:, 3 - min(n, 3) :] = np.asarray(z)[-3:].T
    np.testing.assert_allclose(np.asarray(cache.layers[0]["conv"][0]), want, atol=1e-6)
    if n < 3:
        assert not np.asarray(cache.layers[0]["conv"][0, :, : 3 - n]).any()
    # a decode step rolls it: the oldest input leaves, the new token's enters last
    tok = jnp.asarray([7, 8], jnp.int32)
    _, stepped, _ = dec_mod.decode_step(params, tok, cache, jnp.asarray([9 - n, 0], jnp.int32), cfg)
    np.testing.assert_array_equal(np.asarray(stepped.layers[0]["conv"][0, :, :2]), np.asarray(cache.layers[0]["conv"][0, :, 1:]))
    z_new, _ = ref.filter_input(ref.rms(jnp.asarray(np.asarray(params["tok_emb"])[[7]]), lp["attn_norm"], 1e-5), lp)
    np.testing.assert_allclose(np.asarray(stepped.layers[0]["conv"][0, :, 2]), np.asarray(z_new[0]), atol=1e-6)


def test_the_state_is_a_size_that_does_not_follow_the_cache_and_the_chat_counts_it():
    cfg = dec_mod.tiny_hybrid_moe_decoder()
    short, long = dec_mod.init_cache(cfg, 4, 16), dec_mod.init_cache(cfg, 4, 4096)
    for layer in CONV_LAYERS:
        assert short.layers[layer]["conv"].shape == long.layers[layer]["conv"].shape == (4, 64, 3)
    assert long.layers[1]["k"].shape == (4, 4096, 2, 16)
    from pathway_tpu.internals import tracing
    from pathway_tpu.xpacks.llm.llms import TpuPipelineChat

    def fetch_counts(model, prompts):
        chat = TpuPipelineChat(model, max_new_tokens=4, max_prompt_len=16, max_batch_size=4, prompt_buckets=[16], eos_id=None)
        root = tracing.STAGES.begin_run()
        try:
            chat._fn(prompts)
        finally:
            tracing.STAGES.end_run(root)
        return tracing.stage_totals()["stages"]["chat.fetch"]["counts"]

    counts = fetch_counts(cfg, ["one two three", "four five"])
    assert counts["state_bytes"] == 4 * 4 * 64 * 3 * 2  # four conv layers, four rows, bfloat16
    assert counts["cache_bytes"] == counts["state_bytes"] + 2 * 4 * 20 * 2 * 16 * 2
    # a decoder with no such layer counts none
    assert fetch_counts("tiny", ["one two three"])["state_bytes"] == 0


# -- (f) the pieces -----------------------------------------------------------


def test_the_norm_a_head_changes_the_result_and_is_the_references(model):
    cfg, params = model
    rng = np.random.default_rng(9)
    lp = {**params["layers"][1], "q_norm": jnp.asarray(rng.normal(1.0, 0.2, 16), jnp.float32),
          "k_norm": jnp.asarray(rng.normal(1.0, 0.2, 16), jnp.float32)}
    h = jnp.asarray(rng.normal(size=(1, 7, 64)), jnp.float32)
    slots = jnp.arange(7, dtype=jnp.int32)[None]

    def attend(config):
        out, _ = dec_mod._gqa_attention(h, lp, config, None, 0, slots, slots, jnp.ones((1, 7), bool), False)
        return np.asarray(out[0] @ lp["o_w"])

    np.testing.assert_allclose(attend(cfg), np.asarray(ref.attention(h[0], lp, TINY)), atol=2e-5)
    assert np.abs(attend(dataclasses.replace(cfg, qk_norm=False)) - attend(cfg)).max() > 1e-2


def test_the_filter_is_causal_three_taps_wide_and_the_references(model):
    cfg, params = model
    lp = params["layers"][2]
    h = jnp.asarray(np.random.default_rng(10).normal(size=(1, 9, 64)), jnp.float32)
    got, _ = dec_mod._short_conv(h, lp, cfg, None, None)
    np.testing.assert_allclose(np.asarray(got[0] @ lp["o_w"]), np.asarray(ref.short_conv(h[0], lp, TINY)), atol=2e-5)
    # position 5 reads positions 3, 4, 5 and nothing else
    for moved, changes in ((2, False), (3, True), (5, True), (6, False)):
        other, _ = dec_mod._short_conv(h.at[0, moved].add(1.0), lp, cfg, None, None)
        assert bool(np.abs(np.asarray(other[0, 5] - got[0, 5])).max() > 1e-4) is changes


# -- the normal path ----------------------------------------------------------


def test_the_hybrid_decoder_answers_through_the_question_answerer_under_the_runner():
    """``from_hf`` on the published keys, ``TpuPipelineChat`` under
    ``BaseRAGQuestionAnswerer`` over a ``DataIndex``'s reply, run by the
    graph runner: the answer is the chat's own greedy tokens, and each is
    the reference's choice or within bfloat16 of it."""
    import pathway_tpu as pw
    from pathway_tpu.internals.runner import GraphRunner
    from pathway_tpu.stdlib.indexing import DataIndex, TpuKnnFactory
    from pathway_tpu.xpacks.llm import BaseRAGQuestionAnswerer
    from pathway_tpu.xpacks.llm.llms import TpuPipelineChat
    from pathway_tpu.xpacks.llm.mocks import FakeEmbedder

    params = ref.make_params(23, {**TINY, "expert_bias_std": 0.05})
    chat = TpuPipelineChat(
        DecoderConfig.from_hf(TINY), max_new_tokens=4, max_prompt_len=96, max_batch_size=2, prompt_buckets=[96],
        eos_id=None, params=params,
    )
    plain, asked = chat._fn, []
    chat._fn = lambda texts: (asked.extend(texts), plain(texts))[1]  # the graph keeps the function it finds
    docs = pw.debug.table_from_rows(pw.schema_from_types(text=str), [("alpha beta",), ("gamma delta",), ("epsilon zeta",)])
    embedder = FakeEmbedder(dim=16)
    docs = docs.select(text=pw.this.text, emb=embedder(pw.this.text))
    index = DataIndex(docs, TpuKnnFactory(dimensions=16, metric="cos", capacity=32), docs.emb)
    queries = pw.debug.table_from_rows(pw.schema_from_types(prompt=str), [("which letter comes first",)])
    queries = queries.select(prompt=pw.this.prompt, qemb=embedder(pw.this.prompt))
    answers = BaseRAGQuestionAnswerer(chat, None, search_topk=2).answer_index_reply(queries, index, queries.qemb)
    rows = list(GraphRunner().capture(answers)[0].values())
    made = chat.last_generation
    assert len(rows) == 1 and len(asked) == 1 and made["rows"] == 1 and made["tokens"].shape == (2, 4)
    served = [int(t) for t in made["tokens"][0]]
    assert [int(part[1:-1]) for part in rows[0][0].split()] == [t for t in served if t > 3]
    ids = chat.tokenizer.encode(asked[0], 1 << 30)
    assert 40 < len(ids) == made["prompt_tokens"][0] <= 96  # the template, two chunks and the question
    # the reference over the same ids, then the tokens served: the chat's float32 logit of each served token
    # against the reference's, over the spread of the reference's logits (bfloat16 against float32; read 0.01-0.05)
    want = _reference_logits(params, ids + served[:-1], len(ids) - 1 + np.arange(4))
    gap = np.abs(made["logits"][0] - want[np.arange(4), served]) / want.std(-1)
    assert gap.max() < 0.25, gap
