"""The latent-cache, routed-experts decoder against the plain reference
(``benchmark/reference_decoder.py``) at a small size on the CPU: seeded
random weights, logits compared, never sampled tokens."""

from __future__ import annotations

import json
import math
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

import reference_decoder as ref  # noqa: E402

from pathway_tpu.models import decoder as dec_mod  # noqa: E402
from pathway_tpu.models.decoder import DecoderConfig  # noqa: E402
from pathway_tpu.ops import moe  # noqa: E402

YARN = {"type": "yarn", "factor": 40, "original_max_position_embeddings": 128, "beta_fast": 32,
        "beta_slow": 1, "mscale": 0.707, "mscale_all_dim": 0.707}
#: the published keys at a size a test can hold
TINY = {
    "model_type": "deepseek_v2", "vocab_size": 512, "hidden_size": 64, "num_hidden_layers": 3,
    "num_attention_heads": 4, "num_key_value_heads": 4, "intermediate_size": 160,
    "kv_lora_rank": 16, "q_lora_rank": None, "qk_nope_head_dim": 8, "qk_rope_head_dim": 4, "v_head_dim": 8,
    "n_routed_experts": 8, "num_experts_per_tok": 2, "n_shared_experts": 1, "moe_intermediate_size": 32,
    "first_k_dense_replace": 1, "norm_topk_prob": False, "routed_scaling_factor": 1,
    "rms_norm_eps": 1e-6, "rope_theta": 10000, "rope_scaling": YARN, "max_position_embeddings": 4096,
}


@pytest.fixture(scope="module")
def model():
    """(configuration in float32, parameters in float32 holding bfloat16
    values): both sides then compute exactly, and differ by rounding order."""
    cfg = DecoderConfig.from_hf(TINY, dtype=jnp.float32)
    params = jax.tree.map(lambda a: a.astype(jnp.float32), ref.make_params(7, TINY))
    return cfg, params


def _reference_logits(params, ids, positions):
    return np.asarray(ref.forward(params, jnp.asarray(ids, jnp.int32), jnp.asarray(positions, jnp.int32), TINY))


def test_from_hf_reads_the_published_keys_and_the_layer_pattern(model):
    cfg, params = model
    assert cfg.layer_pattern == ("dense", "experts", "experts")
    assert (cfg.attention, cfg.cache_width, cfg.experts_per_token, cfg.n_routed_experts) == ("mla", 20, 2, 8)
    assert DecoderConfig.from_hf(TINY) == dec_mod.tiny_latent_moe_decoder()  # the preset is these keys
    own = dec_mod.init_decoder_params(jax.random.key(0), cfg)
    assert jax.tree.map(jnp.shape, own) == jax.tree.map(jnp.shape, params)
    with pytest.raises(ValueError, match="q_lora_rank"):
        DecoderConfig.from_hf({**TINY, "q_lora_rank": 1536})
    with pytest.raises(ValueError, match="model_type"):
        DecoderConfig.from_hf({**TINY, "model_type": "mamba"})


def test_the_cache_of_a_latent_layer_holds_rank_plus_rope_values_a_token(model):
    cfg, _ = model
    cache = dec_mod.init_cache(cfg, 2, 10)
    assert [set(state) for state in cache.layers] == [{"latent"}] * 3
    assert cache.layers[0]["latent"].shape == (2, 10, 16 + 4)
    gqa = dec_mod.init_cache(dec_mod.tiny_decoder(), 2, 10)
    assert gqa.layers[0]["k"].shape == (2, 10, 2, 16)


def test_prefill_then_decode_through_the_latent_cache_agree_with_the_references_full_pass(model):
    cfg, params = model
    rng = np.random.default_rng(3)
    ids = rng.integers(4, 512, 14)
    want = _reference_logits(params, ids, np.arange(14))
    logits, cache, offset, _ = dec_mod.prefill(params, jnp.asarray(ids[None, :9], jnp.int32), None, cfg, 14)
    np.testing.assert_allclose(np.asarray(logits[0]), want[8], atol=2e-4)
    for i in range(9, 14):
        logits, cache, _ = dec_mod.decode_step(params, jnp.asarray(ids[i : i + 1], jnp.int32), cache, offset, cfg)
        np.testing.assert_allclose(np.asarray(logits[0]), want[i], atol=2e-4)
    assert int(cache.length) == 14


def test_the_full_forward_pass_agrees_with_the_reference_at_every_position(model):
    cfg, params = model
    ids = np.random.default_rng(4).integers(4, 512, 12)
    logits, _ = dec_mod.decoder_forward(params, jnp.asarray(ids[None], jnp.int32), cfg)
    np.testing.assert_allclose(np.asarray(logits[0]), _reference_logits(params, ids, np.arange(12)), atol=2e-4)


def test_absorbed_attention_equals_expanded_attention(model):
    """Two tokens through the cache as one chunk (expanded over the cached
    latents) and as two single steps (absorbed)."""
    cfg, params = model
    ids = jnp.asarray(np.random.default_rng(5).integers(4, 512, (2, 9)), jnp.int32)
    _, cache, offset, _ = dec_mod.prefill(params, ids[:, :7], None, cfg, 9)
    chunk, cache_chunk = dec_mod.decoder_forward(params, ids[:, 7:], cfg, cache, pos_offset=offset)
    one, cache_steps, _ = dec_mod.decode_step(params, ids[:, 7], cache, offset, cfg)
    two, cache_steps, _ = dec_mod.decode_step(params, ids[:, 8], cache_steps, offset, cfg)
    np.testing.assert_allclose(np.asarray(chunk[:, 0]), np.asarray(one), atol=1e-4)
    np.testing.assert_allclose(np.asarray(chunk[:, 1]), np.asarray(two), atol=1e-4)
    for a, b in zip(cache_chunk.layers, cache_steps.layers):
        np.testing.assert_allclose(np.asarray(a["latent"]), np.asarray(b["latent"]), atol=1e-5)


def test_left_padded_rows_of_unequal_length_each_agree_with_the_reference(model):
    cfg, params = model
    rng = np.random.default_rng(6)
    lengths, width, new = [5, 11, 8], 12, 3
    rows = [rng.integers(4, 512, n + new) for n in lengths]
    ids = np.zeros((3, width), np.int32)
    mask = np.zeros((3, width), bool)
    for r, (row, n) in enumerate(zip(rows, lengths)):
        ids[r, width - n :], mask[r, width - n :] = row[:n], True
    logits, cache, offset, _ = dec_mod.prefill(params, jnp.asarray(ids), jnp.asarray(mask), cfg, width + new)
    assert list(np.asarray(offset)) == [7, 1, 4]
    got = [np.asarray(logits)]
    for step in range(new - 1):
        tok = jnp.asarray([row[n + step] for row, n in zip(rows, lengths)], jnp.int32)
        logits, cache, _ = dec_mod.decode_step(params, tok, cache, offset, cfg)
        got.append(np.asarray(logits))
    for r, (row, n) in enumerate(zip(rows, lengths)):
        want = _reference_logits(params, row, n - 1 + np.arange(new))
        for step in range(new):
            np.testing.assert_allclose(got[step][r], want[step], atol=2e-4)


def test_the_last_position_head_equals_the_full_head_at_that_position(model):
    cfg, params = model
    ids = jnp.asarray(np.random.default_rng(8).integers(4, 512, (2, 10)), jnp.int32)
    full, _ = dec_mod.decoder_forward(params, ids, cfg)
    last, _, _, _ = dec_mod.prefill(params, ids, None, cfg, 12)
    assert last.shape == (2, 512)
    np.testing.assert_allclose(np.asarray(last), np.asarray(full[:, -1]), atol=1e-4)


def test_prefill_in_groups_of_rows_equals_prefill_in_one(model, monkeypatch):
    cfg, params = model
    ids = jnp.asarray(np.random.default_rng(9).integers(4, 512, (4, 8)), jnp.int32)
    whole = dec_mod.prefill(params, ids, None, cfg, 10)
    monkeypatch.setattr(dec_mod, "PREFILL_BLOCK_TOKENS", 16)  # two rows a group
    grouped = dec_mod.prefill(params, ids, None, cfg, 10)
    np.testing.assert_allclose(np.asarray(grouped[0]), np.asarray(whole[0]), atol=1e-5)
    np.testing.assert_array_equal(np.asarray(grouped[3].load), np.asarray(whole[3].load))
    np.testing.assert_allclose(
        np.asarray(grouped[1].layers[2]["latent"]), np.asarray(whole[1].layers[2]["latent"]), atol=1e-6
    )


def test_bfloat16_stays_close_to_the_float32_reference():
    cfg = DecoderConfig.from_hf(TINY)  # bfloat16 compute
    params = ref.make_params(11, TINY)  # bfloat16 storage
    ids = np.random.default_rng(10).integers(4, 512, 16)
    logits, _ = dec_mod.decoder_forward(params, jnp.asarray(ids[None], jnp.int32), cfg)
    want = _reference_logits(params, ids, np.arange(16))
    gap = np.abs(np.asarray(logits[0]) - want).mean() / want.std()
    assert gap < 0.05, gap


# -- the router and the experts ------------------------------------------------


def test_the_router_is_a_float32_softmax_over_all_experts_and_keeps_six_of_64_as_they_are():
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.normal(size=(32, 48)), jnp.bfloat16)
    gate = jnp.asarray(rng.normal(size=(48, 64)) / 7, jnp.bfloat16)
    weights, experts = moe.route_top_k(h, gate, 6)
    logits = np.asarray(h, np.float64) @ np.asarray(gate, np.float64)
    scores = np.exp(logits - logits.max(-1, keepdims=True))
    scores /= scores.sum(-1, keepdims=True)
    order = np.argsort(-scores, axis=-1, kind="stable")[:, :6]
    np.testing.assert_array_equal(np.asarray(experts), order)
    assert weights.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(weights), np.take_along_axis(scores, order, -1), rtol=1e-5)
    assert float(weights.sum(-1).max()) < 1.0  # not renormalised
    renorm, _ = moe.route_top_k(h, gate, 6, renormalize=True, scale=2.0)
    np.testing.assert_allclose(np.asarray(renorm.sum(-1)), 2.0, rtol=1e-5)


def test_a_tie_goes_to_the_expert_with_the_lower_id():
    h = jnp.ones((3, 4), jnp.float32)
    gate = jnp.zeros((4, 8), jnp.float32).at[:, 5].set(1.0)  # expert 5 ahead, the rest level
    weights, experts = moe.route_top_k(h, gate, 3)
    np.testing.assert_array_equal(np.asarray(experts), [[5, 0, 1]] * 3)
    assert float(weights[0, 1]) == float(weights[0, 2])


def _dense_experts(h, weights, experts, gate_up, down):
    h, gate_up, down = (np.asarray(a, np.float64) for a in (h, gate_up, down))
    out = np.zeros_like(h)
    for n in range(h.shape[0]):
        for w, e in zip(np.asarray(weights[n], np.float64), np.asarray(experts[n])):
            gate, up = np.split(h[n] @ gate_up[e], 2)
            out[n] += w * ((gate / (1 + np.exp(-gate)) * up) @ down[e])
    return out


@pytest.mark.parametrize("skew", ["as_routed", "all_on_one_expert"])
def test_the_grouped_product_drops_no_token_whatever_the_skew(skew):
    rng = np.random.default_rng(1)
    n, hidden, width, n_experts, k = 40, 16, 8, 8, 2
    h = jnp.asarray(rng.normal(size=(n, hidden)), jnp.float32)
    gate_up = jnp.asarray(rng.normal(size=(n_experts, hidden, 2 * width)) / 4, jnp.float32)
    down = jnp.asarray(rng.normal(size=(n_experts, width, hidden)) / 3, jnp.float32)
    if skew == "as_routed":
        experts = jnp.asarray(np.stack([rng.permutation(n_experts)[:k] for _ in range(n)]), jnp.int32)
    else:  # every token's every choice is expert 3: 80 rows on one expert, none elsewhere
        experts = jnp.full((n, k), 3, jnp.int32)
    weights = jnp.asarray(rng.uniform(0.05, 0.3, (n, k)), jnp.float32)
    y, sizes = moe.routed_experts(h, weights, experts, gate_up, down)
    assert int(sizes.sum()) == n * k
    if skew == "all_on_one_expert":
        assert list(np.asarray(sizes)) == [0, 0, 0, n * k, 0, 0, 0, 0]
    np.testing.assert_allclose(np.asarray(y), _dense_experts(h, weights, experts, gate_up, down), atol=1e-4)


def test_every_token_on_one_expert_through_the_layer_still_gives_the_references_answer(model):
    """A router of zeros ties every expert, so every token takes experts 0
    and 1: four times the mean load, and nothing is dropped."""
    cfg, params = model
    params = {**params, "layers": [dict(lp) for lp in params["layers"]]}
    for lp in params["layers"][1:]:
        lp["router_w"] = jnp.zeros_like(lp["router_w"])
    ids = np.random.default_rng(12).integers(4, 512, 12)
    logits, _ = dec_mod.decoder_forward(params, jnp.asarray(ids[None], jnp.int32), cfg)
    _, _, _, stats = dec_mod.prefill(params, jnp.asarray(ids[None], jnp.int32), None, cfg, 12)
    np.testing.assert_allclose(np.asarray(logits[0]), _reference_logits(params, ids, np.arange(12)), atol=2e-4)
    load = np.asarray(stats.load)
    assert load.shape == (2, 8) and load.sum() == 2 * 12 * 2
    assert [list(row) for row in load] == [[12, 12, 0, 0, 0, 0, 0, 0]] * 2


def _grouped_product_inputs(n=12, hidden=16, width=8, n_experts=8, k=2, seed=21):
    rng = np.random.default_rng(seed)
    h = rng.normal(size=(n, hidden)).astype(np.float32)
    gate_up = jnp.asarray(rng.normal(size=(n_experts, hidden, 2 * width)) / 4, jnp.float32)
    down = jnp.asarray(rng.normal(size=(n_experts, width, hidden)) / 3, jnp.float32)
    experts = np.stack([rng.permutation(n_experts)[:k] for _ in range(n)]).astype(np.int32)
    weights = rng.uniform(0.05, 0.3, (n, k)).astype(np.float32)
    return h, weights, experts, gate_up, down


@pytest.mark.parametrize("n_padding", [0, 1, 5, 11])
def test_a_real_tokens_row_is_the_same_to_the_bit_whatever_the_padding_tokens_hold(n_padding):
    h, weights, experts, gate_up, down = _grouped_product_inputs()
    real = np.ones(12, bool)
    real[np.random.default_rng(n_padding).permutation(12)[:n_padding]] = False
    alone, alone_sizes = moe.routed_experts(
        jnp.asarray(h[real]), jnp.asarray(weights[real]), jnp.asarray(experts[real]), gate_up, down
    )
    for filler in (0.0, 3e38, np.inf, -np.inf):
        padded = h.copy()
        padded[~real] = filler
        y, sizes = moe.routed_experts(
            jnp.asarray(padded), jnp.asarray(weights), jnp.asarray(experts), gate_up, down, jnp.asarray(real)
        )
        np.testing.assert_array_equal(np.asarray(y)[real], np.asarray(alone))
        np.testing.assert_array_equal(np.asarray(y)[~real], 0.0)
        np.testing.assert_array_equal(np.asarray(sizes), np.asarray(alone_sizes))
    assert int(alone_sizes.sum()) == (12 - n_padding) * 2


def test_a_batch_of_padding_alone_takes_no_expert_and_gives_zeros():
    h, weights, experts, gate_up, down = _grouped_product_inputs()
    h[::2] = np.inf
    y, sizes = moe.routed_experts(
        jnp.asarray(h), jnp.asarray(weights), jnp.asarray(experts), gate_up, down, jnp.zeros(12, bool)
    )
    np.testing.assert_array_equal(np.asarray(sizes), 0)
    np.testing.assert_array_equal(np.asarray(y), 0.0)  # no NaN either


def test_without_a_mask_the_grouped_product_is_what_it_was_to_the_bit():
    """Against the product as it stood before it took a mask, and against a
    mask that counts every token."""
    h, weights, experts, gate_up, down = (jnp.asarray(a) for a in _grouped_product_inputs(n=40))
    n, k = experts.shape
    order = jnp.argsort(experts.reshape(-1))
    sizes = moe.expert_sizes(experts, 8)
    gate, up = jnp.split(
        jax.lax.ragged_dot(h[order // k], gate_up, sizes, preferred_element_type=jnp.float32), 2, axis=-1
    )
    out = jax.lax.ragged_dot(jax.nn.silu(gate) * up, down, sizes, preferred_element_type=jnp.float32)
    was = (out[jnp.argsort(order)].reshape(n, k, -1) * weights[..., None]).sum(1)
    for counted in (None, jnp.ones(n, bool)):
        y, got_sizes = moe.routed_experts(h, weights, experts, gate_up, down, counted)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(was))
        np.testing.assert_array_equal(np.asarray(got_sizes), np.asarray(sizes))


def test_expert_counts_and_touched_take_real_tokens_only(model):
    cfg, params = model
    rng = np.random.default_rng(13)
    ids = np.zeros((8, 6), np.int32)
    mask = np.zeros((8, 6), bool)
    for r, n in enumerate([4, 6, 3]):  # rows 3-7 are all padding
        ids[r, 6 - n :], mask[r, 6 - n :] = rng.integers(4, 512, n), True
    _, cache, offset, stats = dec_mod.prefill(params, jnp.asarray(ids), jnp.asarray(mask), cfg, 8)
    load = np.asarray(stats.load)
    assert load.sum() == 2 * 13 * 2  # two expert layers, 13 real tokens, two choices
    assert int(stats.touched) == np.count_nonzero(load)  # the experts a real token chose, no other
    _, _, _, alone = dec_mod.prefill(params, jnp.asarray(ids[:3]), jnp.asarray(mask[:3]), cfg, 8)
    np.testing.assert_array_equal(load, np.asarray(alone.load))
    assert int(stats.touched) == int(alone.touched)
    real = jnp.arange(8) < 3
    tok = jnp.asarray([5, 6, 7, 0, 0, 0, 0, 0], jnp.int32)
    _, _, step = dec_mod.decode_step(params, tok, cache, offset, cfg, real)
    assert np.asarray(step.load).sum() == 2 * 3 * 2
    assert int(step.touched) == np.count_nonzero(np.asarray(step.load)) <= 2 * 3 * 2
    # 8 rows of 2 choices would touch up to all 8 experts of a layer: 3 real rows touch what they chose
    _, _, every = dec_mod.decode_step(params, tok, cache, offset, cfg)
    assert int(step.touched) <= int(every.touched)


def test_rows_of_padding_beside_a_row_change_neither_its_tokens_nor_its_logits(model):
    """Rows 0-2 of a call of 8 (what ``TpuPipelineChat`` sends: rows to the
    cap, ``real`` for the decode loop) through prefill and the decode loop,
    against the same prompts with real rows beside them, to the bit, and in a
    call of three rows of their own (whose products have another shape and
    round in another order: the tokens, and the logits to a few ulps)."""
    cfg, params = model
    rng = np.random.default_rng(14)
    lengths, width, new = [5, 11, 8, 12, 7, 12, 9, 4], 12, 6
    ids = np.zeros((8, width), np.int32)
    mask = np.zeros((8, width), bool)
    for r, n in enumerate(lengths):
        ids[r, width - n :], mask[r, width - n :] = rng.integers(4, 512, n), True

    def generate(ids, mask, real):
        logits, cache, offset, pre = dec_mod.prefill(params, jnp.asarray(ids), jnp.asarray(mask), cfg, width + new)
        first = dec_mod.greedy(logits, 0)
        rest, rest_logits, dec = dec_mod.decode_loop(
            params, cache, first, offset, cfg, new - 1, dec_mod.greedy, None, real
        )
        tokens = np.concatenate([np.asarray(first)[:, None], np.asarray(rest)], axis=1)
        chosen = np.concatenate([np.asarray(dec_mod.logit_of(logits, first))[:, None], np.asarray(rest_logits)], axis=1)
        return tokens, chosen, pre, dec

    full_tokens, full_chosen, _, _ = generate(ids, mask, None)  # every row real
    padded_ids, padded_mask = ids.copy(), mask.copy()
    padded_ids[3:], padded_mask[3:] = 0, False
    tokens, chosen, pre, dec = generate(padded_ids, padded_mask, jnp.arange(8) < 3)
    assert chosen.dtype == np.float32
    np.testing.assert_array_equal(tokens[:3], full_tokens[:3])
    np.testing.assert_array_equal(chosen[:3], full_chosen[:3])
    own_tokens, own_chosen, own_pre, own_dec = generate(ids[:3], mask[:3], None)
    np.testing.assert_array_equal(tokens[:3], own_tokens)
    np.testing.assert_allclose(chosen[:3], own_chosen, rtol=0, atol=1e-5)
    for padded, own in ((pre, own_pre), (dec, own_dec)):  # the padding took no expert
        np.testing.assert_array_equal(np.asarray(padded.load), np.asarray(own.load))
        assert int(padded.touched) == int(own.touched)


# -- rotary frequencies ----------------------------------------------------------


def test_yarn_frequencies_are_the_closed_form():
    yarn = dec_mod.YarnScaling(40.0, 4096, 32.0, 1.0, 0.707, 0.707)
    got = dec_mod.rope_frequencies(64, 10000.0, yarn)
    plain = np.array([10000.0 ** (-2 * i / 64) for i in range(32)])
    low = math.floor(64 * math.log(4096 / (32 * 2 * math.pi)) / (2 * math.log(10000)))
    high = math.ceil(64 * math.log(4096 / (1 * 2 * math.pi)) / (2 * math.log(10000)))
    assert (low, high) == (10, 23)
    for i in range(32):
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        np.testing.assert_allclose(got[i], plain[i] / 40 * ramp + plain[i] * (1 - ramp), rtol=1e-6)
    np.testing.assert_allclose(got[:11], plain[:11], rtol=1e-6)  # fast dimensions as they are
    np.testing.assert_allclose(got[23:], plain[23:] / 40, rtol=1e-6)  # slow ones over the factor
    published = {"qk_rope_head_dim": 64, "rope_theta": 10000, "rope_scaling": {**YARN, "original_max_position_embeddings": 4096}}
    np.testing.assert_allclose(got, ref.rotary_frequencies(published), rtol=1e-6)
    np.testing.assert_allclose(dec_mod.rope_frequencies(64, 10000.0), plain, rtol=1e-6)


def test_the_softmax_scale_carries_the_square_of_yarns_factor():
    cfg = DecoderConfig.from_hf(_published())
    m = 0.1 * 0.707 * math.log(40) + 1
    assert cfg.softmax_scale == pytest.approx(192**-0.5 * m * m)
    assert dec_mod._rope_table_scale(cfg) == pytest.approx(1.0)
    assert ref.softmax_scale(_published()) == pytest.approx(cfg.softmax_scale)
    assert dec_mod.tiny_decoder().softmax_scale == pytest.approx(0.25)


def _published() -> dict:
    with open(os.path.join(BENCH, "configs", "dsv2lite-rag-answerer.json")) as fh:
        return json.load(fh)  # the decoder's keys are the file's own, at the top level, as published


def test_the_benchmarks_configuration_builds_the_published_widths():
    cfg = DecoderConfig.from_hf(_published())
    assert (cfg.hidden, cfg.heads, cfg.vocab_size, cfg.intermediate) == (2048, 16, 102400, 10944)
    assert (cfg.n_routed_experts, cfg.experts_per_token, cfg.n_shared_experts, cfg.moe_intermediate) == (64, 6, 2, 1408)
    assert cfg.layer_pattern == ("dense",) + ("experts",) * 4
    assert cfg.cache_width == 576
    shapes = jax.eval_shape(lambda: dec_mod.init_cache(cfg, 8, 2112))
    assert shapes.layers[4]["latent"].shape == (8, 2112, 576)
    tree = jax.eval_shape(lambda: dec_mod.init_decoder_params(jax.random.key(0), cfg, jnp.bfloat16))
    count = sum(math.prod(leaf.shape) for leaf in jax.tree.leaves(tree))
    assert 2.83e9 < count < 2.85e9  # 0.42 + 0.081 + 4 x 0.585 billion: 5.68 GB in bfloat16
