"""The ``mellum`` answerer's pipeline end to end at a toy size, the faults
its comparison has to see, its controls, its costs against hand counts and
its configuration against the published keys.

CPU readings at the toy size are in ``toy_answerer_mellum.LIMITS``' comment;
each planted fault reads over a limit."""

from __future__ import annotations

import dataclasses
import json
import os
import types

import pytest

import costs_mellum as cost
import harness
import readers
import toy_answerer_mellum as toy_mellum
from conftest import BENCH, ROOT

GAPS = {"served_logit_gap.prefill", "served_logit_gap.decode", "served_logit_gap.median", "greedy_gap", "served_logit_steps_off"}
CELL = "mellum2-rag-answer-long"


@pytest.fixture(scope="module")
def sound():
    return toy_mellum.run()


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
    assert 0 < sound["compared"]["served_logit_gap.median"][0] < toy_mellum.LIMITS["served_logit_gap.median"]
    json.dumps(sound)


@pytest.mark.parametrize("fault", ["window_dropped_in_prefill", "full_layer_without_yarn", "attention_factor_left_out",
                                   "ring_read_as_if_it_never_wrapped"])
def test_a_planted_fault_in_the_decoder_is_not_correct(fault, monkeypatch):
    """Drive a whole run with the decoder broken underneath."""
    import jax.numpy as jnp

    from pathway_tpu.models import decoder as dec_mod

    if fault == "window_dropped_in_prefill":  # a prompt's queries see every key of the chunk
        inner_mask = dec_mod._mask
        monkeypatch.setattr(dec_mod, "_mask", lambda q_slot, k_valid, k_slot=None, window=0: inner_mask(q_slot, k_valid, k_slot))
    elif fault in ("full_layer_without_yarn", "attention_factor_left_out"):
        inner_attention = dec_mod._gqa_attention

        def attention(h, lp, cfg, *args, **kwargs):
            turn = cfg.rope_of("full")
            wrong = dataclasses.replace(turn, yarn=None) if fault == "full_layer_without_yarn" else dataclasses.replace(turn, scale=1.0)
            return inner_attention(h, lp, dataclasses.replace(cfg, layer_rope=(*cfg.layer_rope, ("full", wrong))), *args, **kwargs)

        monkeypatch.setattr(dec_mod, "_gqa_attention", attention)
    elif fault == "ring_read_as_if_it_never_wrapped":  # decode takes slot s for position s
        monkeypatch.setattr(dec_mod, "_ring_positions", lambda slots, last: jnp.arange(slots, dtype=jnp.int32))
    result = toy_mellum.run(seed=2**31 + 9)
    assert result["correct"] is False
    wrong = _failed(result)
    assert wrong and wrong <= GAPS, result["compared"]
    if fault == "ring_read_as_if_it_never_wrapped":  # prefill's own logits never read the ring
        assert "served_logit_gap.prefill" not in wrong


def test_every_control_reads_over_a_limit_and_the_program_under_all():
    """The reference in the program's place with a corner cut — float8
    operands in the experts' products, the window dropped, the full layer
    without YaRN or without its factor — is refused, by the gaps and by
    nothing else, and by the median at least."""
    import jax

    import control_mellum

    out = control_mellum.readings(toy_mellum.cell(), 2**31 + 8, 1.5, jax.devices())
    assert out["program"]["correct"] is True, out["program"]
    controls = {name: v for name, v in out.items() if name.startswith("control_")}
    assert set(controls) == {"control_float8_experts", "control_sliding_as_full", "control_full_without_yarn",
                             "control_no_attention_factor"}
    for name, control in controls.items():
        assert control["correct"] is False, name
        assert "served_logit_gap.median" in control["failed"] and set(control["failed"]) <= GAPS, (name, control)


def test_a_checkout_whose_decoder_turns_every_kind_alike_ends_in_load_cell(monkeypatch, tmp_path):
    """What the parent commit does with this cell: the pipeline's file is
    there (the benchmark's files are laid over it), the program's rotation
    by kind is not."""
    import builtins

    inner = builtins.open

    def parents_decoder(path, *args, **kwargs):
        if str(path).endswith(os.path.join("models", "decoder.py")):
            old = tmp_path / "decoder.py"
            old.write_text(inner(path).read().replace("def rope_of(", "def gone("))
            return inner(old, *args, **kwargs)
        return inner(path, *args, **kwargs)

    monkeypatch.setattr(builtins, "open", parents_decoder)
    with pytest.raises(SystemExit, match="no DecoderConfig.rope_of"):
        harness.find_pipeline("rag_answerer_mellum")


def test_the_cell_loads_with_its_files_and_every_metric_has_a_reader():
    cell = harness.load_cell(ROOT, CELL)
    assert cell.config["pipeline"] == "rag_answerer_mellum" and cell.chips == 1
    assert cell.pipeline.__file__ == os.path.join(BENCH, "pipelines", "rag_answerer_mellum.py")
    assert {m["name"] for m in cell.end_to_end} == {"setup_s", "query_p50_ms"}
    assert len(cell.per_layer) == 19 and all(m["workloads"] == [CELL] for m in cell.per_layer)
    assert all(m["name"].endswith(".mellum") and m["moves"] == "query_p50_ms" for m in cell.per_layer)
    names = {m["name"] for m in cell.per_layer}
    assert {"pump_blocked_share.mellum", "chat_host_us_per_query.mellum", "chat_prefill_roofline.mellum",
            "chat_decode_roofline.mellum", "chat_window_scores_wasted_share.mellum",
            "chat_window_scores_past_window_share.mellum"} <= names
    for metric in cell.per_layer:
        assert callable(readers.find(metric["reader"], os.path.join(BENCH, "layer_metrics")))
    for name in ("served_logit_gap.prefill", "served_logit_gap.decode", "served_logit_gap.median", "greedy_gap",
                 "served_logit_step_limit", "knn_gap", "embed_gap_docs"):
        assert name in cell.limits
    # rag-answer's mix (TREC-COVID lengths) with the rate and the search's cap alone changed
    with open(os.path.join(BENCH, "traffic", "rag-answer.json")) as fh:
        older = json.load(fh)
    queries = {k: v for k, v in cell.mix["queries"].items() if not k.startswith("search_rows_max")}
    assert {**cell.mix, "rate_from": None, "queries": {**queries, "rate_per_s": None}} == \
        {**older, "rate_from": None, "queries": {**older["queries"], "rate_per_s": None}}
    assert cell.mix["queries"]["search_rows_max"] == 128 and cell.mix["queries"]["rate_per_s"] > 0
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    assert len(bench["per_layer"]) <= 128
    assert CELL in next(m for m in bench["end_to_end"] if m["name"] == "query_p50_ms")["workloads"]


def test_the_window_wasted_share_reads_the_chats_counts():
    """The readers over the program's counts: walked less needed over walked,
    and of it the real tokens' causal pairs past their window."""
    from pathway_tpu.internals import tracing
    from pathway_tpu.models.decoder import DecoderConfig
    from pathway_tpu.xpacks.llm.llms import TpuPipelineChat

    metric, past = (
        json.load(open(os.path.join(BENCH, "layer_metrics", f"chat_window_scores_{name}_share.mellum.json")))
        for name in ("wasted", "past_window")
    )
    read = readers.find(metric["reader"], os.path.join(BENCH, "layer_metrics"))
    chat = TpuPipelineChat(DecoderConfig.from_hf(toy_mellum.DECODER), max_new_tokens=3, max_prompt_len=32,
                           max_batch_size=2, prompt_buckets=[32], eos_id=None)
    root = tracing.STAGES.begin_run()
    try:
        chat._fn([" ".join(["w"] * 18)])  # 20 tokens in a bucket of 32, a window of 8
    finally:
        tracing.STAGES.end_run(root)
    needed = 36 + 12 * 8  # the first 8 tokens' 1 + ... + 8, then 8 each
    assert read(None, **metric["params"]) == pytest.approx(100 * (1 - needed / (32 * 32)))
    assert read(None, **past["params"]) == pytest.approx(100 * (20 * 21 // 2 - needed) / (32 * 32))


# -- the configuration and the costs -------------------------------------------


@pytest.fixture(scope="module")
def published():
    with open(os.path.join(BENCH, "configs", "mellum2-12b-a2.5b-rag-answerer.json")) as fh:
        return json.load(fh)


def test_the_configuration_holds_the_published_keys_with_depth_alone_changed(published):
    published_keys = readers.load_module("decoder_mellum_tests", os.path.join(ROOT, "tests", "test_decoder_mellum.py")).PUBLISHED
    changed = {k: published.get(k) for k, v in published_keys.items() if published.get(k) != v}
    assert changed == {"num_hidden_layers": 4, "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
                       "mlp_layer_types": ["sparse"] * 4}
    assert {"depth", "num_hidden_layers", "layer_types", "mlp_layer_types", "index"} == set(published["reduced"])
    for key in ("qk_norm", "mtp_head", "attention_factor", "rotary_layout", "window", "chat.max_batch_size",
                "chat.max_new_tokens", "chat.prompt_buckets", "k", "decoder_tokenizer", "answer_length"):
        assert key in published["assumed"]
    with open(os.path.join(BENCH, "configs", "dsv2lite-rag-answerer.json")) as fh:
        dsv = json.load(fh)
    assert published["encoder"] == dsv["encoder"] and published["embedder"] == dsv["embedder"]
    assert published["index"] == {**dsv["index"], "k": 10}  # the same cut: the two cells differ in the decoder
    assert published["guarantees"][:6] == dsv["guarantees"][:6] and len(published["guarantees"]) == 10
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        entry = next(c for c in json.load(fh)["configs"] if c["name"] == published["name"])
    assert entry["source"].startswith("https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/blob/main/config.json")
    assert set(entry["reduced"]) == set(published["reduced"])


def test_the_program_reads_the_configuration_as_the_published_widths_kinds_and_rotations(published):
    import jax

    from pathway_tpu.models.decoder import DecoderConfig, init_cache, init_decoder_params

    cfg = DecoderConfig.from_hf(published)
    assert (cfg.hidden, cfg.heads, cfg.kv_heads, cfg.head_dim, cfg.moe_intermediate) == (2304, 32, 4, 128, 896)
    assert (cfg.n_routed_experts, cfg.held_experts, cfg.experts_per_token, cfg.n_shared_experts) == (64, None, 8, 0)
    assert cfg.attention_pattern == ("sliding", "sliding", "sliding", "full") and cfg.layer_pattern == ("experts",) * 4
    chat = published["chat"]
    slots = chat["max_prompt_len"] + chat["max_new_tokens"]
    shapes = jax.eval_shape(lambda: init_cache(cfg, chat["max_batch_size"], slots))
    assert [s["k"].shape for s in shapes.layers] == [(8, 1024, 4, 128)] * 3 + [(8, 2624, 4, 128)]
    held = sum(leaf.size * leaf.dtype.itemsize for leaf in jax.tree.leaves(shapes.layers))
    assert held == cost.cache_bytes(8, slots, published) == 3 * 16_777_216 + 42_991_616
    assert published["bytes"]["ring_8x1024_one_sliding_layer"] == 16_777_216
    assert published["bytes"]["cache_8x2624_full_layer"] == 42_991_616
    tree = jax.eval_shape(lambda: init_decoder_params(jax.random.key(0), cfg, jax.numpy.bfloat16))
    every = sum(leaf.size for leaf in jax.tree.leaves(tree))
    assert every == cost.decoder_params(published) == published["bytes"]["decoder_parameters"] == 2_123_976_960
    assert 2 * every == published["bytes"]["decoder_bytes_bf16"] == 4_247_953_920


def test_parameter_counts_are_the_hand_counts(published):
    dec = published
    assert cost.attention_params(dec) == 2304 * 4096 * 2 + 2304 * 512 * 2 == 21_233_664 == dec["bytes"]["attention_parameters_a_layer"]
    assert cost.router_params(dec) == 2304 * 64 == 147_456 == dec["bytes"]["router_parameters_a_layer"]
    assert cost.expert_params(dec) == 3 * 2304 * 896 == 6_193_152 == dec["bytes"]["routed_expert_parameters_each"]
    assert 2 * cost.head_params(dec) == 2 * 98_304 * 2304 == 452_984_832 == dec["bytes"]["embedding_and_head_parameters"]
    layer = 21_233_664 + 147_456 + 64 * 6_193_152 + 2 * 2304
    assert layer == 417_747_456 == dec["bytes"]["layer_parameters_with_norms"]
    assert cost.decoder_params(dec) == 4 * layer + 452_984_832 + 2304
    assert cost.layer_counts(dec) == {"sliding": 3, "full": 1, "layers": 4}
    assert cost.token_matmul_params(dec) == 4 * (21_233_664 + 147_456)


def test_prefill_and_decode_costs_are_the_hand_counts(published):
    dec = published
    token, head, expert, pair = cost.token_matmul_params(dec), 2304 * 98_304, 6_193_152, 4 * 32 * 128
    # a row of 1,500 tokens: three sliding layers of 1 + ... + 1,024 then 476 of 1,024; the full layer's triangle
    sliding = 1024 * 1025 // 2 + 476 * 1024
    assert cost.window_pairs(1500, 1024) == sliding and cost.window_pairs(600, 1024) == 600 * 601 // 2
    assert cost.attention_pairs(1500, dec) == 3 * sliding + 1500 * 1501 // 2
    assert cost.prefill_flops((1500, 600), dec, 9) == (
        2 * 2100 * token + pair * (3 * sliding + 1500 * 1501 // 2 + 4 * 600 * 601 // 2) + 2 * 2 * head + 2 * 9 * expert
    )
    # a row of 1,023 tokens, two steps: the rings see 1,024 then 1,024 positions, the full layer 1,024 then 1,025
    assert cost.decode_keys_seen((1023,), 2, dec) == 3 * 2048 + 2049
    assert cost.decode_flops((1023,), 2, dec, 7) == 2 * 2 * (token + head) + pair * (3 * 2048 + 2049) + 2 * 7 * expert
    kv = 2 * 4 * 128 * 2
    assert cost.cache_token_bytes(dec) == kv == 2048
    resident = token + head
    assert cost.decode_bytes((1023,), 2, dec, 5) == (
        2 * (2 * resident + 5 * expert) + kv * (3 * 2048 + 2049 + 2 * 4) + 2 * (2 * 2304 + 4 * 98_304)
    )
    # a prompt keeps its last 1,024 positions in a ring and every one in the full layer
    assert cost.prefill_bytes((1500, 600), dec, 6) == (
        2 * (resident + 6 * expert) + 2100 * (4 + 2 * 2304) + kv * (3 * 1024 + 1500 + 4 * 600) + 4 * 2 * 98_304
    )
    # a served row's cache: 6 MB of rings and 5.4 MB of full layer, whatever the prompt under 2,560
    assert cost.cache_bytes(1, 2624, dec) == 2048 * (3 * 1024 + 2624)


def test_the_roofline_reader_holds_each_execution_against_its_own_call(published):
    import trace as trace_mod

    read = readers.find("chat_roofline_mellum", os.path.join(BENCH, "layer_metrics"))
    peak = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
    call = {"at": 10.5, "rows": 3, "bucket": 2048, "prompt_tokens": (1600, 1700, 1900), "prefill_pairs_held": 5200 * 32,
            "decode_pairs_held": 3 * 63 * 32, "prefill_touched": 4 * 64, "decode_touched": 63 * 4 * 20}
    full = {**call, "rows": 8, "prompt_tokens": (1700,) * 8, "prefill_pairs_held": 13600 * 32,
            "decode_pairs_held": 8 * 63 * 32, "decode_touched": 63 * 4 * 40}
    calls = [call, full, call, full]
    device, host = ("/device:TPU:0", "XLA Modules"), ("/host:CPU", "python#3")
    events = [
        # call 0 began before the trace did: its decode is in the trace, its span is not
        trace_mod.Event(*device, "jit_chat_decode(2)", 0.1e9, 0.2e9),
        trace_mod.Event(*host, "bench:mellum_call.1", 0.6e9, 0.6e9),
        trace_mod.Event(*device, "jit_chat_prefill(1)", 0.61e9, 0.2e9),
        trace_mod.Event(*device, "jit_chat_decode(2)", 0.82e9, 0.33e9),
        trace_mod.Event(*host, "bench:mellum_call.2", 1.9e9, 0.5e9),
        trace_mod.Event(*device, "jit_chat_prefill(1)", 1.91e9, 0.12e9),
        trace_mod.Event(*device, "jit_chat_decode(2)", 2.04e9, 0.25e9),
        # call 3 was cut by the trace's end: a prefill and no span
        trace_mod.Event(*device, "jit_chat_prefill(1)", 2.8e9, 0.12e9),
    ]
    cell = types.SimpleNamespace(config=published)

    def ctx(calls, events=events):
        obs = types.SimpleNamespace(evidence={"chat_calls": calls})
        return types.SimpleNamespace(trace={"events": events, "start": 10.0, "stop": 14.0}, peak=peak, obs=obs, cell=cell)

    def least(c, program):
        tokens = c["prompt_tokens"]
        if program == "prefill":
            flops, nbytes = cost.prefill_flops(tokens, published, c["prefill_pairs_held"]), cost.prefill_bytes(tokens, published, c["prefill_touched"])
        else:
            flops, nbytes = cost.decode_flops(tokens, 63, published, c["decode_pairs_held"]), cost.decode_bytes(tokens, 63, published, c["decode_touched"])
        return max(flops / 197e12, nbytes / 819e9)

    assert least(full, "prefill") == cost.prefill_flops(full["prompt_tokens"], published, full["prefill_pairs_held"]) / 197e12
    assert least(call, "decode") == cost.decode_bytes(call["prompt_tokens"], 63, published, call["decode_touched"]) / 819e9
    prefill = read(ctx(calls), program="prefill", patterns=["jit_chat_prefill"])
    assert prefill == pytest.approx(100 * (least(full, "prefill") + least(call, "prefill")) / (0.2 + 0.12))
    decode = read(ctx(calls), program="decode", patterns=["jit_chat_decode"])
    assert decode == pytest.approx(100 * (least(full, "decode") + least(call, "decode")) / (0.33 + 0.25))
    assert 0 < prefill < 100 and 0 < decode < 100
    assert read(ctx([]), program="decode", patterns=["jit_chat_decode"]) is None
    spanless = [e for e in events if not e.name.startswith("bench:")]
    assert read(ctx(calls, spanless), program="prefill", patterns=["jit_chat_prefill"]) is None
    # a cell of another pipeline (its spans are another name's), and a program without the evidence
    other = [trace_mod.Event(e.plane, e.line, e.name.replace("mellum_call", "lfm2_call"), e.start_ns, e.dur_ns) for e in events]
    assert read(ctx(calls, other), program="prefill", patterns=["jit_chat_prefill"]) is None
    other_pipeline = types.SimpleNamespace(trace=ctx([]).trace, peak=peak, obs=types.SimpleNamespace(evidence={}), cell=cell)
    assert read(other_pipeline, program="prefill", patterns=["jit_chat_prefill"]) is None
