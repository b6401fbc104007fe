"""The windowed decoder of ``mellum`` (sliding grouped-query layers over rings
that wrap, beside a full layer that turns by YaRN times its attention factor,
a softmax router over experts renormalised, an untied head) against the plain
reference (``benchmark/reference_mellum.py``) at a small size on the CPU: a
window of 8 under prompts past it, seeded random weights, logits compared,
never sampled tokens."""

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

import reference_mellum as ref  # noqa: E402

from pathway_tpu.models import decoder as dec_mod  # noqa: E402
from pathway_tpu.models.decoder import DecoderConfig, LayerRope, YarnScaling  # noqa: E402
from pathway_tpu.ops import moe  # noqa: E402

#: Mellum2-12B-A2.5B-Instruct's config.json, as published (the keys the
#: language model reads)
PUBLISHED = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu", "hidden_size": 2304, "intermediate_size": 7168,
    "layer_types": ["sliding_attention", "sliding_attention", "sliding_attention", "full_attention"] * 7,
    "mlp_layer_types": ["sparse"] * 28, "max_position_embeddings": 131072, "max_window_layers": 0,
    "model_type": "mellum", "moe_intermediate_size": 896, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts": 64, "num_experts_per_tok": 8, "num_hidden_layers": 28, "num_key_value_heads": 4,
    "rms_norm_eps": 1e-06,
    "rope_parameters": {
        "full_attention": {"rope_type": "yarn", "rope_theta": 500000, "factor": 16, "original_max_position_embeddings": 8192,
                           "beta_fast": 32, "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000},
    },
    "sliding_window": 1024, "tie_word_embeddings": False, "vocab_size": 98304, "use_sliding_window": True,
}
#: the layer pattern at a size for tests: a window of 8; YaRN over an
#: original 64 positions at theta 1,000, so that at a few dozen positions it
#: turns pairs 3-7 a quarter as fast as plain RoPE would
TINY = {
    **PUBLISHED, "vocab_size": 512, "hidden_size": 64, "num_hidden_layers": 4, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 160, "moe_intermediate_size": 32, "num_experts": 8,
    "num_experts_per_tok": 2, "layer_types": PUBLISHED["layer_types"][:4], "mlp_layer_types": ["sparse"] * 4,
    "sliding_window": 8, "max_position_embeddings": 256,
    "rope_parameters": {
        "full_attention": {"rope_type": "yarn", "rope_theta": 1000, "factor": 4, "original_max_position_embeddings": 64,
                           "beta_fast": 32, "beta_slow": 1, "attention_factor": 1.1386294361119891},
        "sliding_attention": {"rope_type": "default", "rope_theta": 1000},
    },
}
WINDOW = 8
#: float32 on both sides, the same bfloat16-valued weights: what is left is the
#: order of float32 sums (the program's grouped product against the
#: reference's loop over every expert, attention's blocks and rings), some
#: 1e-5 of logits that spread by 0.5, through four layers
ATOL = 3e-4


@pytest.fixture(scope="module")
def model():
    """(configuration in float32, parameters in float32 holding bfloat16
    values): both sides then compute exactly, and differ by rounding order."""
    cfg = DecoderConfig.from_hf(TINY, dtype=jnp.float32)
    params = jax.tree.map(lambda a: a.astype(jnp.float32), ref.make_params(11, TINY))
    return cfg, params


def _reference_logits(params, ids, positions, dec=TINY, **control):
    return np.asarray(ref.forward(params, jnp.asarray(ids, jnp.int32), jnp.asarray(positions, jnp.int32), dec, **control))


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


def test_from_hf_on_the_published_keys_gives_the_published_widths_and_kinds():
    cfg = DecoderConfig.from_hf(PUBLISHED)
    assert (cfg.hidden, cfg.layers, cfg.heads, cfg.kv_heads, cfg.head_dim, cfg.vocab_size) == (2304, 28, 32, 4, 128, 98304)
    assert (cfg.moe_intermediate, cfg.n_routed_experts, cfg.experts_per_token, cfg.n_shared_experts) == (896, 64, 8, 0)
    assert (cfg.router, cfg.norm_topk_prob, cfg.router_bias, cfg.first_dense_layers) == ("softmax", True, False, 0)
    assert (cfg.norm, cfg.parallel_block, cfg.tie_embeddings, cfg.qk_norm, cfg.rope_interleaved) == ("rms", False, False, False, False)
    assert (cfg.sliding_window, cfg.rms_eps, cfg.rope_theta) == (1024, 1e-6, 500000.0)
    assert cfg.attention_pattern == ("sliding", "sliding", "sliding", "full") * 7
    assert cfg.layer_pattern == ("experts",) * 28
    assert cfg.rope_of("sliding") == LayerRope(500000.0)
    assert cfg.rope_of("full") == LayerRope(500000.0, YarnScaling(16, 8192, 32, 1), 1.2772588722239782)
    shapes = jax.eval_shape(lambda: dec_mod.init_decoder_params(jax.random.key(0), dataclasses.replace(
        cfg, layers=4, layer_types=cfg.layer_types[:4])))
    layer = shapes["layers"][3]
    assert (layer["q_w"].shape, layer["kv_w"].shape, layer["o_w"].shape) == ((2304, 4096), (2304, 1024), (4096, 2304))
    assert (layer["router_w"].shape, layer["experts_gate_w"].shape, layer["experts_down_w"].shape) == (
        (2304, 64), (64, 2304, 1792), (64, 896, 2304))
    assert shapes["lm_head"].shape == (2304, 98304) and "q_norm" not in layer and "shared_gate_w" not in layer
    # YaRN at theta 500,000 over 128: pairs 0-18 plain, 35-63 a sixteenth, between them a ramp
    plain = dec_mod.rope_frequencies(128, 500000.0)
    yarn = dec_mod.rope_frequencies(128, 500000.0, cfg.rope_of("full").yarn)
    np.testing.assert_array_equal(yarn[:19], plain[:19])
    np.testing.assert_allclose(yarn[35:], plain[35:] / 16, rtol=1e-6)
    assert np.all((yarn[19:35] < plain[19:35]) & (yarn[19:35] > plain[19:35] / 16))
    np.testing.assert_allclose(yarn, ref.yarn_frequencies(128, PUBLISHED["rope_parameters"]["full_attention"]), rtol=1e-6)


def test_from_hf_reads_the_tiny_keys_and_its_tree_is_the_references(model):
    cfg, params = model
    assert cfg.attention_pattern == ("sliding", "sliding", "sliding", "full") and cfg.sliding_window == WINDOW
    own = dec_mod.init_decoder_params(jax.random.key(0), cfg)
    assert jax.tree.map(jnp.shape, own) == jax.tree.map(jnp.shape, params)
    cache = dec_mod.init_cache(cfg, 3, 20)
    assert [state["k"].shape for state in cache.layers] == [(3, WINDOW, 2, 16)] * 3 + [(3, 20, 2, 16)]


@pytest.mark.parametrize("change, match", [
    ({"model_type": "mellum3"}, "model_type"), ({"attention_bias": True}, "attention_bias"),
    ({"hidden_act": "gelu"}, "hidden_act"), ({"tie_word_embeddings": True}, "tie_word_embeddings"),
    ({"use_sliding_window": False}, "use_sliding_window"), ({"mlp_layer_types": ["sparse", "dense"] * 2}, "mlp_layer_types"),
    ({"layer_types": ["sliding_attention", "linear_attention"] * 2}, "layer_types"),
    ({"layer_types": ["sliding_attention", "full_attention"]}, "layer_types"),
    ({"rope_parameters": {**TINY["rope_parameters"], "full_attention": {"rope_type": "default", "rope_theta": 1000}}}, "rope_parameters"),
    ({"rope_parameters": {**TINY["rope_parameters"], "sliding_attention": {**TINY["rope_parameters"]["full_attention"]}}}, "rope_parameters"),
])
def test_from_hf_refuses_by_name_a_key_whose_other_value_it_does_not_implement(change, match):
    with pytest.raises(ValueError, match=match):
        DecoderConfig.from_hf({**TINY, **change})


# -- the rotation by layer kind -----------------------------------------------


def _one_layer(cfg, params, layer: int):
    """Layer ``layer``'s attention operator alone over ``h`` ``[1, t,
    hidden]``, through ``o_w``."""
    lp, kind = params["layers"][layer], cfg.attention_pattern[layer]

    def attend(h, config=cfg):
        slots = jnp.arange(h.shape[1], dtype=jnp.int32)[None]
        out, _ = dec_mod._gqa_attention(h, lp, config, None, 0, slots, slots, jnp.ones(h.shape[:2], bool), False, kind)
        return np.asarray(out[0] @ lp["o_w"])

    return attend


def test_a_full_layer_of_this_family_turns_by_yarn_times_its_factor_and_command_a_takes_no_positions(model):
    from test_decoder_command_a import TINY as COMMAND_A
    from test_decoder_lfm2 import TINY as LFM2

    cfg, params = model
    h = jnp.asarray(np.random.default_rng(9).normal(size=(1, 40, 64)), jnp.float32)
    full, sliding = _one_layer(cfg, params, 3), _one_layer(cfg, params, 0)
    np.testing.assert_allclose(full(h), np.asarray(ref.attention(h[0], params["layers"][3], TINY, "full")), atol=2e-5)
    np.testing.assert_allclose(sliding(h), np.asarray(ref.attention(h[0], params["layers"][0], TINY, "sliding")), atol=2e-5)
    # each part of the full layer's turn shows in what it gives
    turn = cfg.rope_of("full")
    for other in (LayerRope(turn.theta, None, turn.scale), LayerRope(turn.theta, turn.yarn), None):
        moved = dataclasses.replace(cfg, layer_rope=(("full", other), ("sliding", cfg.rope_of("sliding"))))
        assert np.abs(full(h, moved) - full(h)).max() > 1e-2
    # the other families' kinds, as they were: Command A's full layer takes no positions, LFM2's one kind turns plainly
    command_a, lfm2 = DecoderConfig.from_hf(COMMAND_A), DecoderConfig.from_hf(LFM2)
    assert command_a.rope_of("full") is None and command_a.rope_of("sliding") == LayerRope(50000.0)
    assert lfm2.rope_of("gqa") == LayerRope(1e6) and command_a.layer_rope == (("full", None),) and lfm2.layer_rope == ()


# -- the router ---------------------------------------------------------------


def test_the_softmax_top_8_of_64_weights_sum_to_one_and_are_the_references():
    rng = np.random.default_rng(3)
    h, w = jnp.asarray(rng.normal(size=(300, 64)), jnp.float32), jnp.asarray(rng.normal(size=(64, 64)) / 8, jnp.float32)
    weights, experts = moe.route_top_k(h, w, 8, renormalize=True, scoring="softmax")
    np.testing.assert_allclose(np.asarray(weights.sum(-1)), 1.0, atol=1e-6)
    dec = {"num_experts_per_tok": 8, "norm_topk_prob": True}
    with jax.default_matmul_precision("highest"):
        dense = np.asarray(ref.router(h, {"router_w": w}, dec))
    np.testing.assert_allclose(np.take_along_axis(dense, np.asarray(experts), axis=-1), np.asarray(weights), atol=1e-6)
    assert (np.count_nonzero(dense, axis=-1) == 8).all()


# -- the forward pass, with and without the cache -----------------------------


@pytest.mark.parametrize("rows, contracted, out, tiles", [
    (64, 2304, 1792, "64,1152,896"),  # Mellum2's gate | up in a decode step
    (64, 896, 2304, "64,896,1152"),  # and its down
    (2048, 2304, 1792, "512,1152,896"),  # a prefill's block of sorted rows
    (2048, 2048, 3072, None),  # LFM2's widths, multiples of 512: the compiler's tiles
    (64, 4096, 8192, None),  # Command A+'s
    (64, 2048, 2816, "64,1024,1408"),  # DeepSeek-V2-Lite's: 2,816 halves into 1,408 = 11 x 128
    (48, 2048, 2816, "16,1024,1408"),  # its gate | up in a decode step (8 tokens x 6 choices)
    (48, 1408, 2048, "16,1408,1024"),  # and its down: the expert's whole width in one tile
    (2048, 2048, 2816, "256,1024,1408"),  # a prefill's block: 512 rows a tile would not fit beside them
    (2048, 1408, 2048, "256,1408,1024"),
    (16, 64, 64, None),  # a test's
])
def test_the_grouped_product_takes_wide_tiles_where_a_width_is_no_multiple_of_512(rows, contracted, out, tiles):
    assert moe.tiling(rows, contracted, out) == tiles


def test_the_full_forward_pass_agrees_with_the_reference_at_every_position_past_the_window(model):
    cfg, params = model
    ids = np.random.default_rng(5).integers(4, 512, 37)
    logits, _ = dec_mod.decoder_forward(params, jnp.asarray(ids[None], jnp.int32), cfg)
    np.testing.assert_allclose(np.asarray(logits[0]), _reference_logits(params, ids, np.arange(37)), atol=ATOL)


@pytest.mark.parametrize("lengths, new", [
    ([5, 7, 6], 3),  # every prompt inside the window, the answers too
    ([8, 8, 8], 4),  # prompts at the window: decode wraps the rings from its first step
    ([19, 11, 14], 6),  # prompts past the window, left-padded: prefill keeps their last 8 positions
    ([33, 4, 21], 12),  # a row inside the window beside rows past it; decode wraps again
    ([26], 5),  # no padding at all
])
def test_prefill_then_decode_through_the_wrapped_rings_agree_with_the_references_full_pass(model, lengths, new):
    cfg, params = model
    rng = np.random.default_rng(sum(lengths))
    rows = [rng.integers(4, 512, n + new) for n in lengths]
    got, cache = _through_the_cache(cfg, params, rows, lengths, new)
    assert [state["k"].shape[1] for state in cache.layers] == [WINDOW] * 3 + [max(lengths) + new]
    for r, (row, n) in enumerate(zip(rows, lengths)):
        want = _reference_logits(params, row, n - 1 + np.arange(new))
        for step in range(new):
            np.testing.assert_allclose(got[step][r], want[step], atol=ATOL)


@pytest.mark.parametrize("control", [*ref.CUTS, "float8_experts"])
def test_each_control_misses_the_tolerance_by_far(model, control):
    """Every corner the comparison has to see (the window dropped, the full
    layer's YaRN or its factor left out, float8 operands in the experts)
    moves the logits past the window far over the tolerance."""
    _, params = model
    ids = np.random.default_rng(5).integers(4, 512, 37)
    exact = _reference_logits(params, ids, np.arange(37))
    kwargs = {"operand": ref.quantize_fp8} if control == "float8_experts" else {"cut": control}
    low = _reference_logits(params, ids, np.arange(37), **kwargs)
    assert np.abs(low[WINDOW:] - exact[WINDOW:]).max() > 30 * ATOL


# -- the counts ---------------------------------------------------------------


def test_the_window_counts_are_the_hand_counts_and_the_chat_reports_them(model):
    cfg, _ = model
    # rows of 5, 8 and 11 real tokens in a prefill of 16: three sliding layers
    needed = (15 + 36 + (36 + 3 * 8)) * 3  # 1 + ... + 5; 1 + ... + 8; 1 + ... + 8 and three more of 8
    causal = (15 + 36 + 66) * 3  # 1 + ... + 5; 1 + ... + 8; 1 + ... + 11
    # a chunk of 16 is one tile: a real row walks its whole square
    assert dec_mod.prefill_window_scores(cfg, 16, [5, 8, 11]) == (3 * 3 * 16 * 16, causal, needed)
    # a row of 1,700 in the 2,048 bucket starts in tile 1 of 8 (slot 348); its query tiles 1-7 visit
    # 1, 2, 3, 4, 5, 5, 5 tiles of 256 x 256: from the first real slot to themselves, no more than
    # 1,023 slots behind their first query
    published = DecoderConfig.from_hf(PUBLISHED)
    assert dec_mod.prefill_window_scores(published, 2048, [1700]) == (
        21 * 25 * 256 * 256, 21 * 1700 * 1701 // 2, 21 * (1024 * 1025 // 2 + 676 * 1024))
    # every layer (the full one visits 1 + ... + 7 tiles) and head, two rows of padding beside it
    assert dec_mod.prefill_attention_scores(published, 2048, [1700], 3) == (
        32 * (21 * 25 + 7 * 28) * 256 * 256, 28 * 3 * 32 * 2048 * 2048)
    assert dec_mod.prefill_window_scores(dec_mod.tiny_decoder(), 16, [5]) == (0, 0, 0)
    from pathway_tpu.internals import tracing
    from pathway_tpu.xpacks.llm.llms import TpuPipelineChat

    def fetch_counts(decoder, prompts):
        chat = TpuPipelineChat(decoder, max_new_tokens=3, max_prompt_len=32, max_batch_size=4, prompt_buckets=[16, 32], eos_id=None)
        root = tracing.STAGES.begin_run()
        try:
            chat._fn(prompts)
        finally:
            tracing.STAGES.end_run(root)
        return tracing.stage_totals()["stages"]["chat.fetch"]["counts"]

    prompts = [" ".join(["w"] * n) for n in (3, 6, 9)]  # and CLS, SEP: 5, 8 and 11 tokens
    counts = fetch_counts(DecoderConfig.from_hf(TINY), prompts)
    assert (counts["window_scores_walked"], counts["window_scores_causal"], counts["window_scores_needed"]) == (
        3 * 3 * 16 * 16, causal, needed)
    # four layers of four heads: three real rows walk their one tile, the padding row none
    assert (counts["attention_scores_walked"], counts["attention_scores_square"]) == (
        4 * 4 * 3 * 16 * 16, 4 * 4 * 4 * 16 * 16)
    # a cache of rings: three layers of 8 slots beside one of 35, four rows, bfloat16
    assert counts["cache_bytes"] == 2 * 4 * 2 * 16 * 2 * (3 * WINDOW + 35)
    assert fetch_counts("tiny", prompts)["window_scores_walked"] == 0


# -- the normal path ----------------------------------------------------------


def test_the_mellum_decoder_answers_through_the_question_answerer_under_the_runner():
    """``from_hf`` on the published keys, ``TpuPipelineChat`` under
    ``BaseRAGQuestionAnswerer`` over a ``DataIndex``'s reply, run by the
    graph runner: the prompt passes the window, and each served token's
    logit is the reference's within bfloat16."""
    import pathway_tpu as pw
    from pathway_tpu.internals.runner import GraphRunner
    from pathway_tpu.stdlib.indexing import DataIndex, TpuKnnFactory
    from pathway_tpu.xpacks.llm import BaseRAGQuestionAnswerer
    from pathway_tpu.xpacks.llm.llms import TpuPipelineChat
    from pathway_tpu.xpacks.llm.mocks import FakeEmbedder

    params = ref.make_params(23, TINY)
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
    assert 5 * WINDOW < len(ids) == made["prompt_tokens"][0] <= 96  # the template, two chunks and the question
    # the chat's float32 logit of each served token against the reference's, over the spread of the
    # reference's logits (bfloat16 against float32)
    want = _reference_logits(params, ids + served[:-1], len(ids) - 1 + np.arange(4))
    gap = np.abs(made["logits"][0] - want[np.arange(4), served]) / want.std(-1)
    assert gap.max() < 0.25, gap
