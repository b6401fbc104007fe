"""The ``lfm2_moe`` answerer's pipeline end to end at a toy size, the faults
its comparison has to see, its control, its costs against hand counts and
its configuration against the catalog.

CPU readings at the toy size are in ``toy_answerer_lfm2.LIMITS``' comment;
each planted fault reads over a limit."""

from __future__ import annotations

import dataclasses
import json
import os
import types

import pytest

import costs_lfm2 as cost
import harness
import toy_answerer_lfm2 as toy_lfm2
from conftest import BENCH, ROOT

GAPS = {"served_logit_gap.prefill", "served_logit_gap.decode", "served_logit_gap.median", "greedy_gap", "served_logit_steps_off"}


@pytest.fixture(scope="module")
def sound():
    return toy_lfm2.run()


def _failed(result: dict) -> set:
    return {name for name, (value, limit) in result["compared"].items() if limit is None or not value <= limit}


def test_a_sound_run_through_the_new_pipeline_is_correct_and_compares_the_index_and_the_answers(sound):
    assert sound["correct"] is True, sound["compared"]
    assert sound["failed"] == 0 and sound["attempted"] > 60
    names = list(sound["compared"])
    for name in ("docs_lost", "knn_gap", "answers_unsound", "answers_lost", "answers_repeated", "answer_tokens_off",
                 "context_unsound", *sorted(GAPS)):
        assert name in names
    assert names.index("knn_gap") < names.index("answers_lost")  # the live index's numbers first, as they are
    assert 0 < sound["compared"]["served_logit_gap.decode"][0] < toy_lfm2.LIMITS["served_logit_gap.decode"]
    json.dumps(sound)


@pytest.mark.parametrize("fault", ["bias_added_to_the_weights", "state_taken_at_the_padded_end",
                                   "norm_a_head_left_out", "one_tap_of_the_filter_dropped"])
def test_a_planted_fault_in_the_decoder_is_not_correct(fault, monkeypatch):
    """Drive a whole run with the decoder broken underneath."""
    import jax
    import jax.numpy as jnp

    from pathway_tpu.models import decoder as dec_mod

    if fault == "bias_added_to_the_weights":  # the chosen experts' weights are score + bias, normalised
        inner_route = dec_mod.route_top_k

        def route(h, w, k, *, select_bias=None, **kw):
            _, experts = inner_route(h, w, k, select_bias=select_bias, **kw)
            scores = jax.nn.sigmoid(h.astype(jnp.float32) @ w.astype(jnp.float32)) + select_bias
            top = jnp.take_along_axis(scores, experts, axis=-1)
            return top / (top.sum(-1, keepdims=True) + 1e-6), experts

        monkeypatch.setattr(dec_mod, "route_top_k", route)
    elif fault == "state_taken_at_the_padded_end":  # a prompt leaves the filter's inputs of its chunk's first positions
        inner_conv = dec_mod._short_conv

        def conv(h, lp, cfg, state, real):
            out, after = inner_conv(h, lp, cfg, state, real)
            if state is not None and h.shape[1] > cfg.conv_taps:
                _, after = inner_conv(h[:, : cfg.conv_taps], lp, cfg, state, None if real is None else real[:, : cfg.conv_taps])
            return out, after

        monkeypatch.setattr(dec_mod, "_short_conv", conv)
    elif fault == "norm_a_head_left_out":
        inner_attention = dec_mod._gqa_attention

        def attention(h, lp, cfg, *args, **kwargs):
            return inner_attention(h, lp, dataclasses.replace(cfg, qk_norm=False), *args, **kwargs)

        monkeypatch.setattr(dec_mod, "_gqa_attention", attention)
    elif fault == "one_tap_of_the_filter_dropped":  # the oldest of the three
        inner_conv = dec_mod._short_conv
        monkeypatch.setattr(
            dec_mod, "_short_conv",
            lambda h, lp, cfg, state, real: inner_conv(h, {**lp, "conv_w": lp["conv_w"].at[:, 0].set(0)}, cfg, state, real),
        )
    result = toy_lfm2.run(seed=2**31 + 9)
    assert result["correct"] is False
    wrong = _failed(result)
    assert wrong and wrong <= GAPS, result["compared"]
    if fault == "state_taken_at_the_padded_end":  # prefill's own logits never read the state it leaves
        assert "served_logit_gap.prefill" not in wrong


def test_the_control_reads_over_the_medians_limit():
    """The reference in the program's place with float8 operands in the
    experts' products is refused, by the gaps and by nothing else, and by
    the median above all: the means are carried by the steps at which
    bfloat16 moved a near tie of the router, in the sound program too."""
    import jax

    import control_lfm2

    out = control_lfm2.readings(toy_lfm2.cell(), 2**31 + 8, 1.5, jax.devices())
    assert out["program"]["correct"] is True, out["program"]
    control = out["control_float8_experts"]
    assert control["correct"] is False
    assert "served_logit_gap.median" in control["failed"]
    assert control["numbers"]["served_logit_gap.median"] > 2 * out["program"]["numbers"]["served_logit_gap.median"]
    assert set(control["failed"]) <= GAPS


def test_a_checkout_whose_decoder_has_no_conv_layer_ends_in_load_cell(monkeypatch, tmp_path):
    """What the parent commit does with this cell: the pipeline's file is
    there (the benchmark's files are laid over it), the program's layer is not."""
    import builtins

    inner = builtins.open

    def parents_decoder(path, *args, **kwargs):
        if str(path).endswith(os.path.join("models", "decoder.py")):
            old = tmp_path / "decoder.py"
            old.write_text(inner(path).read().replace("def _short_conv(", "def gone("))
            return inner(old, *args, **kwargs)
        return inner(path, *args, **kwargs)

    monkeypatch.setattr(builtins, "open", parents_decoder)
    with pytest.raises(SystemExit, match="no gated short-convolution layer"):
        harness.find_pipeline("rag_answerer_lfm2")


def test_the_cell_loads_with_its_files_and_every_metric_has_a_reader():
    import readers

    cell = harness.load_cell(ROOT, "lfm2-rag-answer")
    assert cell.config["pipeline"] == "rag_answerer_lfm2" and cell.chips == 1
    assert cell.pipeline.__file__ == os.path.join(BENCH, "pipelines", "rag_answerer_lfm2.py")
    assert {m["name"] for m in cell.end_to_end} == {"setup_s", "query_p50_ms"}
    assert len(cell.per_layer) == 18 and all(m["workloads"] == ["lfm2-rag-answer"] for m in cell.per_layer)
    assert all(m["name"].endswith(".lfm2") and m["moves"] == "query_p50_ms" for m in cell.per_layer)
    assert {"chat_step_mfu.lfm2", "chat_state_bytes_per_row.lfm2", "chat_cache_bytes_per_row.lfm2"} <= {m["name"] for m in cell.per_layer}
    for metric in cell.per_layer:
        assert callable(readers.find(metric["reader"], os.path.join(BENCH, "layer_metrics")))
    for name in ("served_logit_gap.prefill", "served_logit_gap.decode", "served_logit_gap.median", "greedy_gap",
                 "served_logit_step_limit", "knn_gap", "embed_gap_docs"):
        assert name in cell.limits
    assert cell.mix["queries"]["search_rows_max"] == 128 and cell.mix["queries"]["rate_per_s"] > 0
    # rag-answer-msmarco's file with the rate alone changed
    with open(os.path.join(BENCH, "traffic", "rag-answer-msmarco.json")) as fh:
        older = json.load(fh)
    assert {**cell.mix, "rate_from": None, "queries": {**cell.mix["queries"], "rate_per_s": None}} == \
        {**older, "rate_from": None, "queries": {**older["queries"], "rate_per_s": None}}
    # the older answerers' cells still find their own files
    assert harness.load_cell(ROOT, "dsv2lite-rag-answer").pipeline.__file__ == os.path.join(BENCH, "pipelines", "rag_answerer.py")
    assert harness.load_cell(ROOT, "command-a-plus-rag-answer").pipeline.__file__ == os.path.join(BENCH, "pipelines", "rag_answerer_command_a.py")


# -- the configuration and the costs -------------------------------------------


@pytest.fixture(scope="module")
def published():
    with open(os.path.join(BENCH, "configs", "lfm2-24b-a2b-rag-answerer.json")) as fh:
        return json.load(fh)


def test_the_configuration_holds_the_catalogs_keys_with_depth_alone_changed(published):
    catalog = os.path.join("/opt/skills/guides/model-configs", "architectures.jsonl")
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as fh:
        row = next(r for r in map(json.loads, fh) if r["name"] == "LFM2-24B-A2B")
    changed = {k: published.get(k) for k, v in row["config"].items() if published.get(k) != v}
    assert changed == {"num_hidden_layers": 5, "num_dense_layers": 1,
                       "layer_types": ["conv", "full_attention", "conv", "conv", "conv"]}
    assert {"depth", "num_hidden_layers", "num_dense_layers", "layer_types", "index"} == set(published["reduced"])
    # the widths, uncut; every expert, head and vocabulary row held
    assert (published["hidden_size"], published["num_attention_heads"], published["num_key_value_heads"]) == (2048, 32, 8)
    assert (published["intermediate_size"], published["moe_intermediate_size"], published["num_experts"], published["num_experts_per_tok"]) == (11776, 1536, 64, 4)
    assert (published["conv_L_cache"], published["vocab_size"], published["rope_parameters"]["rope_theta"]) == (3, 65536, 1000000)
    for key in ("head_dim", "tie_embedding", "expert_bias", "chat.max_batch_size", "k", "padding"):
        assert key in published["assumed"]
    with open(os.path.join(BENCH, "configs", "dsv2lite-rag-answerer.json")) as fh:
        dsv = json.load(fh)
    assert published["encoder"] == dsv["encoder"] and published["embedder"] == dsv["embedder"]
    assert published["index"] == {**dsv["index"], "k": 10}  # the same cut: the two cells differ in the decoder
    assert published["guarantees"][:5] == dsv["guarantees"][:5] and len(published["guarantees"]) == 10
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        entry = next(c for c in json.load(fh)["configs"] if c["name"] == published["name"])
    assert entry["source"].startswith(row["source_url"]) and set(entry["reduced"]) == set(published["reduced"])


def test_the_program_reads_the_configuration_as_the_published_widths_and_the_layer_kinds(published):
    import jax

    from pathway_tpu.models.decoder import DecoderConfig, init_cache, init_decoder_params

    cfg = DecoderConfig.from_hf(published)
    assert (cfg.hidden, cfg.heads, cfg.kv_heads, cfg.head_dim, cfg.moe_intermediate, cfg.intermediate) == (2048, 32, 8, 64, 1536, 11776)
    assert (cfg.n_routed_experts, cfg.held_experts, cfg.experts_per_token, cfg.n_shared_experts) == (64, None, 4, 0)
    assert cfg.attention_pattern == ("conv", "gqa", "conv", "conv", "conv")
    assert cfg.layer_pattern == ("dense", "experts", "experts", "experts", "experts")
    chat = published["chat"]
    slots = chat["max_prompt_len"] + chat["max_new_tokens"]
    shapes = jax.eval_shape(lambda: init_cache(cfg, chat["max_batch_size"], slots))
    assert [{k: v.shape for k, v in s.items()} for s in shapes.layers] == (
        [{"conv": (8, 2048, 3)}, {"k": (8, 1088, 8, 64), "v": (8, 1088, 8, 64)}] + [{"conv": (8, 2048, 3)}] * 3
    )
    held = sum(leaf.size * leaf.dtype.itemsize for leaf in jax.tree.leaves(shapes.layers))
    assert held == cost.cache_bytes(8, slots, published) == 17_825_792 + 393_216
    assert published["bytes"]["kv_cache_8x1088_one_layer"] == 17_825_792 and published["bytes"]["conv_state_8_rows_4_layers"] == 393_216
    tree = jax.eval_shape(lambda: init_decoder_params(jax.random.key(0), cfg, jax.numpy.bfloat16))
    matrices = sum(leaf.size for leaf in jax.tree.leaves(tree) if leaf.ndim >= 2)
    assert 2 * matrices == 2 * cost.decoder_params(published) == published["bytes"]["decoder_parameters_bf16"] == 5_401_264_128


def test_parameter_counts_are_the_hand_counts(published):
    dec = published
    assert cost.conv_params(dec) == 2048 * 6144 + 2048 * 2048 == dec["bytes"]["conv_operator_parameters_each"] - 2048 * 3
    assert cost.attention_params(dec) == 2 * 2048 * 2048 + 2 * 2048 * 512 == 10_485_760 == dec["bytes"]["attention_operator_parameters"]
    assert cost.dense_params(dec) == 3 * 2048 * 11776 == 72_351_744 == dec["bytes"]["dense_feed_forward_parameters"]
    assert cost.expert_params(dec) == 3 * 2048 * 1536 == 9_437_184 == dec["bytes"]["routed_expert_parameters_each"]
    assert cost.router_params(dec) == 2048 * 64 == dec["bytes"]["router_parameters_a_layer"]
    assert cost.head_params(dec) == 2048 * 65536 == dec["bytes"]["embedding_parameters"]
    assert cost.layer_counts(dec) == {"conv": 4, "attention": 1, "dense": 1, "experts": 4}
    assert cost.token_matmul_params(dec) == 4 * 16_777_216 + 10_485_760 + 72_351_744 + 4 * 131_072


def test_prefill_and_decode_costs_are_the_hand_counts(published):
    dec = published
    token, head, expert = cost.token_matmul_params(dec), 134_217_728, 9_437_184
    filt = 4 * 2 * 3 * 2048  # four conv layers, three multiply-adds a channel
    # two prompts of 3 and 5 tokens: 6 + 15 (query, key) pairs in the one attention layer, 32 heads of 64
    assert cost.attention_pairs(5, dec) == 15
    assert cost.prefill_flops((3, 5), dec, 9) == 8 * (2 * token + filt) + 4 * 32 * 64 * 21 + 2 * 2 * head + 2 * 9 * expert
    # one row of 10 tokens, two steps: against 11 and 12 positions in the attention layer
    assert cost.decode_flops((10,), 2, dec, 7) == 2 * (2 * token + filt + 2 * head) + 4 * 32 * 64 * 23 + 2 * 7 * expert
    assert cost.cache_token_bytes(dec) == 2 * 8 * 64 * 2 and cost.state_row_bytes(dec) == 2048 * 3 * 2
    resident = token + 4 * 2048 * 3 + head
    assert cost.decode_bytes((10,), 2, dec, 5) == (
        2 * (2 * resident + 5 * expert) + 2048 * (23 + 2) + 2 * 4 * 2 * 12288 + 2 * (2 * 2048 + 4 * 65536)
    )
    assert cost.prefill_bytes((3, 5), dec, 6) == (
        2 * (resident + 6 * expert) + 8 * (4 + 2 * 2048 + 2048) + 2 * 4 * 12288 + 4 * 2 * 65536
    )
    # the issue's arithmetic: some 0.6 GFLOP a prefilled token with its four experts in four layers
    assert 0.55e9 < 2 * token + filt + 2 * 16 * expert < 0.65e9
    # a served row's cache: 2.2 MB of keys and values and 49 KB of filter state, whatever the positions
    assert cost.cache_bytes(1, 1088, dec) == 1088 * 2048 + 4 * 12288
    assert cost.cache_bytes(1, 100_000, dec) - cost.cache_bytes(1, 1088, dec) == (100_000 - 1088) * 2048


def test_the_roofline_reader_holds_each_execution_against_its_own_call(published):
    import readers
    import trace as trace_mod

    read = readers.find("chat_roofline_lfm2", os.path.join(BENCH, "layer_metrics"))
    peak = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
    call = {"at": 10.5, "rows": 3, "bucket": 768, "prompt_tokens": (600, 640, 700), "prefill_pairs_held": 1940 * 16,
            "decode_pairs_held": 3 * 63 * 16, "prefill_touched": 4 * 64, "decode_touched": 63 * 4 * 11}
    full = {**call, "rows": 8, "prompt_tokens": (640,) * 8, "prefill_pairs_held": 5120 * 16,
            "decode_pairs_held": 8 * 63 * 16, "decode_touched": 63 * 4 * 24}
    calls = [call, full, call, full]
    device, host = ("/device:TPU:0", "XLA Modules"), ("/host:CPU", "python#3")
    events = [
        # call 0 began before the trace did: its decode is in the trace, its span is not
        trace_mod.Event(*device, "jit_chat_decode(2)", 0.1e9, 0.2e9),
        trace_mod.Event(*host, "bench:lfm2_call.1", 0.6e9, 0.5e9),
        trace_mod.Event(*device, "jit_chat_prefill(1)", 0.61e9, 0.12e9),
        trace_mod.Event(*device, "jit_chat_decode(2)", 0.74e9, 0.33e9),
        trace_mod.Event(*host, "bench:lfm2_call.2", 1.9e9, 0.4e9),
        trace_mod.Event(*device, "jit_chat_prefill(1)", 1.91e9, 0.1e9),
        trace_mod.Event(*device, "jit_chat_decode(2)", 2.02e9, 0.2e9),
        # call 3 was cut by the trace's end: a prefill and no span
        trace_mod.Event(*device, "jit_chat_prefill(1)", 2.8e9, 0.12e9),
    ]
    cell = types.SimpleNamespace(config=published)

    def ctx(calls, events=events):
        obs = types.SimpleNamespace(evidence={"chat_calls": calls})
        return types.SimpleNamespace(trace={"events": events, "start": 10.0, "stop": 14.0}, peak=peak, obs=obs, cell=cell)

    def least(c, program):  # the larger of compute's time and the bytes': a prefill of few rows is bound by its experts' bytes
        tokens = c["prompt_tokens"]
        if program == "prefill":
            flops, nbytes = cost.prefill_flops(tokens, published, c["prefill_pairs_held"]), cost.prefill_bytes(tokens, published, c["prefill_touched"])
        else:
            flops, nbytes = cost.decode_flops(tokens, 63, published, c["decode_pairs_held"]), cost.decode_bytes(tokens, 63, published, c["decode_touched"])
        return max(flops / 197e12, nbytes / 819e9)

    assert least(full, "prefill") == cost.prefill_flops(full["prompt_tokens"], published, full["prefill_pairs_held"]) / 197e12
    assert least(call, "decode") == cost.decode_bytes(call["prompt_tokens"], 63, published, call["decode_touched"]) / 819e9
    prefill = read(ctx(calls), program="prefill", patterns=["jit_chat_prefill"])
    assert prefill == pytest.approx(100 * (least(full, "prefill") + least(call, "prefill")) / (0.12 + 0.1))
    decode = read(ctx(calls), program="decode", patterns=["jit_chat_decode"])
    assert decode == pytest.approx(100 * (least(full, "decode") + least(call, "decode")) / (0.33 + 0.2))
    assert 5 < prefill < 40 and 40 < decode < 100
    assert read(ctx([]), program="decode", patterns=["jit_chat_decode"]) is None
    spanless = [e for e in events if not e.name.startswith("bench:")]
    assert read(ctx(calls, spanless), program="prefill", patterns=["jit_chat_prefill"]) is None
    # a cell of another pipeline (its spans are another name's), and a program without the evidence
    other = [trace_mod.Event(e.plane, e.line, e.name.replace("lfm2_call", "cmda_call"), e.start_ns, e.dur_ns) for e in events]
    assert read(ctx(calls, other), program="prefill", patterns=["jit_chat_prefill"]) is None
    other_pipeline = types.SimpleNamespace(trace=ctx([]).trace, peak=peak, obs=types.SimpleNamespace(evidence={}), cell=cell)
    assert read(other_pipeline, program="prefill", patterns=["jit_chat_prefill"]) is None


def test_a_prompt_asked_twice_and_generated_twice_is_compared_a_query_with_its_own_generation():
    """The primer asks the first question again; where the two calls served
    other tokens for the one prompt, each answer is its own call's."""
    import numpy as np

    import check_lfm2

    first, second = np.asarray([7, 9, 2, 11]), np.asarray([7, 9, 12, 11])
    logits = np.zeros(4)
    printed = lambda tokens: " ".join(f"<{t}>" for t in tokens if t > 3)  # noqa: E731
    prompts = {0: "same text", 5: "same text", 2: "another"}
    generations = {"same text": [(first, logits), (second, logits)], "another": [(first, logits)]}
    results = {0: printed(second), 5: printed(first), 2: printed(first)}
    paired_prompts, paired = check_lfm2.one_generation_a_query(prompts, generations, results)
    assert paired_prompts[0] != paired_prompts[5] and paired_prompts[2] == "another"
    assert paired[paired_prompts[0]][0][0] is second and paired[paired_prompts[5]][0][0] is first
    assert "same text" not in paired and len(paired) == 3
    # an answer that is neither call's tokens: nothing is paired, and the count the comparison keeps sees it
    wrong = {**results, 0: printed(np.asarray([7, 9, 13, 11]))}
    assert check_lfm2.one_generation_a_query(prompts, generations, wrong) == (prompts, generations)
    # a prompt generated more often than it was asked stays a repeat
    thrice = {**generations, "same text": generations["same text"] + [(first, logits)]}
    assert check_lfm2.one_generation_a_query(prompts, thrice, results) == (prompts, thrice)
