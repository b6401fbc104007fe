"""The ``cohere2_moe`` answerer's pipeline end to end at a toy size, the
faults its comparison has to see, its control, its costs against hand counts
and its configuration against the catalog.

CPU readings at the toy size (seeds 2**31 + 7 .. + 9): the program's means
0.006-0.016 (its widest step 0.39, where bfloat16 moved a near tie of the
router to another expert); each planted fault and the float8 control read
over a limit."""

from __future__ import annotations

import dataclasses
import json
import os
import types

import pytest

import costs_command_a as cost
import harness
import toy_answerer_command_a as toy_cmda
from conftest import BENCH, ROOT

GAPS = {"served_logit_gap.prefill", "served_logit_gap.decode", "greedy_gap", "served_logit_steps_off"}


@pytest.fixture(scope="module")
def sound():
    return toy_cmda.run()


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
    assert 0 < sound["compared"]["served_logit_gap.decode"][0] < toy_cmda.toy_answerer.LIMITS["served_logit_gap.decode"]
    json.dumps(sound)


@pytest.mark.parametrize("fault", ["window_ignored", "rope_on_the_full_layer", "softmax_for_sigmoid",
                                   "shared_experts_summed", "held_share_ignored", "stale_ring_slot_after_a_wrap"])
def test_a_planted_fault_in_the_decoder_is_not_correct(fault, monkeypatch):
    """Drive a whole run with the decoder broken underneath."""
    import jax.numpy as jnp

    from pathway_tpu.models import decoder as dec_mod
    from pathway_tpu.ops import moe

    if fault == "window_ignored":  # a sliding layer sees every key before it
        inner_mask = dec_mod._mask
        monkeypatch.setattr(dec_mod, "_mask", lambda q_slot, k_valid, k_slot=None, window=0: inner_mask(q_slot, k_valid, k_slot))
    elif fault == "rope_on_the_full_layer":
        inner_attention = dec_mod._gqa_attention

        def attention(h, lp, cfg, state, start, q_slot, q_pos, k_valid, chunk_only, kind="gqa", wraps=False):
            kind = "gqa" if kind == "full" else kind  # turned by position, no window
            return inner_attention(h, lp, cfg, state, start, q_slot, q_pos, k_valid, chunk_only, kind, wraps)

        monkeypatch.setattr(dec_mod, "_gqa_attention", attention)
    elif fault == "softmax_for_sigmoid":
        inner_route = moe.route_top_k
        monkeypatch.setattr(dec_mod, "route_top_k", lambda h, w, k, **kw: inner_route(h, w, k, **{**kw, "scoring": "softmax"}))
    elif fault == "shared_experts_summed":
        inner_from_hf = dec_mod.DecoderConfig.from_hf.__func__

        def from_hf(cls, hf, **overrides):
            return dataclasses.replace(inner_from_hf(cls, hf, **overrides), shared_combine="sum")

        monkeypatch.setattr(dec_mod.DecoderConfig, "from_hf", classmethod(from_hf))
    elif fault == "held_share_ignored":  # every chosen pair is computed, by whichever held expert its id falls on
        inner_routed = moe.routed_experts

        def routed(h, weights, experts, gate_up_w, down_w, counted=None, held=None):
            return inner_routed(h, weights, experts % gate_up_w.shape[0], gate_up_w, down_w, counted)

        monkeypatch.setattr(dec_mod, "routed_experts", routed)
    elif fault == "stale_ring_slot_after_a_wrap":  # once the ring has wrapped a step's key and value are not written
        inner_write = dec_mod._ring_write

        def ring_write(buffer, chunk, start, wraps):
            written = inner_write(buffer, chunk, start, wraps)
            if wraps and chunk.shape[1] == 1:
                return jnp.where(start >= buffer.shape[1], buffer, written)
            return written

        monkeypatch.setattr(dec_mod, "_ring_write", ring_write)
    result = toy_cmda.run(seed=2**31 + 9)
    assert result["correct"] is False
    wrong = _failed(result)
    assert wrong and wrong <= GAPS, result["compared"]
    if fault == "stale_ring_slot_after_a_wrap":  # prefill wrote its rows where they belong
        assert "served_logit_gap.prefill" not in wrong and "served_logit_gap.decode" in wrong


def test_the_control_reads_over_the_limit():
    """The reference in the program's place with float8 operands in the
    experts' products is refused, by the gaps and by nothing else."""
    import jax

    import control_command_a

    out = control_command_a.readings(toy_cmda.cell(), 5, 1.5, jax.devices())
    assert out["program"]["correct"] is True, out["program"]
    control = out["control_float8_experts"]
    assert control["correct"] is False
    assert "served_logit_gap.decode" in control["failed"]
    assert control["numbers"]["served_logit_gap.decode"] > 2 * out["program"]["numbers"]["served_logit_gap.decode"]
    assert set(control["failed"]) <= GAPS


def test_a_checkout_whose_grouped_product_takes_no_held_share_ends_in_load_cell(monkeypatch, tmp_path):
    """What the parent commit does with this cell: the pipeline's file is
    there (the benchmark's files are laid over it), the program's ``held`` is not."""
    import builtins

    inner = builtins.open

    def parents_moe(path, *args, **kwargs):
        if str(path).endswith(os.path.join("ops", "moe.py")):
            old = tmp_path / "moe.py"
            old.write_text(inner(path).read().replace("held: tuple[int, int]", "gone"))
            return inner(old, *args, **kwargs)
        return inner(path, *args, **kwargs)

    monkeypatch.setattr(builtins, "open", parents_moe)
    with pytest.raises(SystemExit, match="takes no held share"):
        harness.find_pipeline("rag_answerer_command_a")


def test_the_cell_loads_with_its_files_and_every_metric_has_a_reader():
    import readers

    cell = harness.load_cell(ROOT, "command-a-plus-rag-answer")
    assert cell.config["pipeline"] == "rag_answerer_command_a" and cell.chips == 1
    assert cell.pipeline.__file__ == os.path.join(BENCH, "pipelines", "rag_answerer_command_a.py")
    assert {m["name"] for m in cell.end_to_end} == {"setup_s", "query_p50_ms"}
    assert len(cell.per_layer) == 18 and all(m["workloads"] == ["command-a-plus-rag-answer"] for m in cell.per_layer)
    assert all(m["name"].endswith(".cmda") and m["moves"] == "query_p50_ms" for m in cell.per_layer)
    for metric in cell.per_layer:
        assert callable(readers.find(metric["reader"], os.path.join(BENCH, "layer_metrics")))
    for name in ("served_logit_gap.prefill", "served_logit_gap.decode", "greedy_gap", "served_logit_step_limit",
                 "knn_gap", "embed_gap_docs"):
        assert name in cell.limits
    assert cell.mix["queries"]["search_rows_max"] == 128 and cell.mix["queries"]["rate_per_s"] > 0
    # the older answerer's cell still finds its own files
    assert harness.load_cell(ROOT, "dsv2lite-rag-answer").pipeline.__file__ == os.path.join(BENCH, "pipelines", "rag_answerer.py")


# -- the configuration and the costs -------------------------------------------


@pytest.fixture(scope="module")
def published():
    with open(os.path.join(BENCH, "configs", "command-a-plus-rag-answerer.json")) as fh:
        return json.load(fh)


def test_the_configuration_holds_the_catalogs_keys_with_depth_experts_held_and_vocabulary_alone_changed(published):
    catalog = os.path.join("/opt/skills/guides/model-configs", "architectures.jsonl")
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as fh:
        row = next(r for r in map(json.loads, fh) if r["name"] == "command-a-plus-05-2026")
    changed = {k: published.get(k) for k, v in row["config"].items() if published.get(k) != v}
    assert changed == {"num_hidden_layers": 4, "num_experts": 16, "vocab_size": 32768}
    assert {"depth", "num_hidden_layers", "num_experts", "vocab_size", "index"} == set(published["reduced"])
    share = published["held_here"]
    assert (share["experts"], share["of_experts"], share["published_vocab_size"], share["published_num_hidden_layers"]) == ([0, 16], 128, 262144, 32)
    # the widths, uncut
    assert (published["hidden_size"], published["num_attention_heads"], published["num_key_value_heads"], published["head_dim"]) == (4096, 128, 8, 128)
    assert (published["intermediate_size"], published["num_experts_per_tok"], published["num_shared_experts"]) == (4096, 8, 4)
    assert (published["sliding_window"], published["rope_theta"]) == (4096, 50000)
    with open(os.path.join(BENCH, "configs", "bge-base-live-index.json")) as fh:
        bge = json.load(fh)
    assert published["encoder"] == bge["encoder"] and published["embedder"] == bge["embedder"]
    assert published["index"] == {**bge["index"], "capacity": 1048576, "prefilled": 750000}
    assert published["guarantees"][:5] == bge["guarantees"] and len(published["guarantees"]) == 9


def test_the_program_reads_the_configuration_as_the_published_widths_and_the_share(published):
    import jax

    from pathway_tpu.models.decoder import DecoderConfig, init_cache, init_decoder_params

    cfg = DecoderConfig.from_hf(published)
    assert (cfg.hidden, cfg.heads, cfg.kv_heads, cfg.head_dim, cfg.moe_intermediate) == (4096, 128, 8, 128, 4096)
    assert (cfg.n_routed_experts, cfg.held_experts, cfg.experts_per_token, cfg.n_shared_experts) == (128, (0, 16), 8, 4)
    assert cfg.attention_pattern == ("sliding", "sliding", "sliding", "full") and cfg.sliding_window == 4096
    chat = published["chat"]
    slots = chat["max_prompt_len"] + chat["max_new_tokens"]
    shapes = jax.eval_shape(lambda: init_cache(cfg, chat["max_batch_size"], slots))
    assert [s["k"].shape for s in shapes.layers] == [(16, 1088, 8, 128)] * 4  # under the window every layer keeps every position
    assert published["bytes"]["kv_cache_16x1088x4_layers"] == 2 * 16 * 1088 * 8 * 128 * 2 * 4 == cost.cache_bytes(16, slots, published)
    long = jax.eval_shape(lambda: init_cache(cfg, 2, 6208))  # the hand run's: three rings of the window, one full layer
    assert [s["k"].shape[1] for s in long.layers] == [4096, 4096, 4096, 6208]
    tree = jax.eval_shape(lambda: init_decoder_params(jax.random.key(0), cfg, jax.numpy.bfloat16))
    matrices = sum(leaf.size for leaf in jax.tree.leaves(tree) if leaf.ndim >= 2)
    assert 2 * matrices == published["bytes"]["decoder_parameters_bf16"] == 2 * cost.decoder_params(published)
    assert tree["layers"][0]["experts_gate_w"].shape == (16, 4096, 8192) and tree["layers"][0]["router_w"].shape == (4096, 128)


def test_parameter_counts_are_the_hand_counts(published):
    dec = published
    assert cost.attention_params(dec) == 2 * 4096 * 16384 + 2 * 4096 * 1024 == 142_606_336 == dec["bytes"]["attention_parameters_a_layer"]
    assert cost.expert_params(dec) == 3 * 4096 * 4096 == 50_331_648 == dec["bytes"]["routed_expert_parameters_each"]
    assert cost.shared_params(dec) == 201_326_592 == dec["bytes"]["shared_experts_parameters_a_layer"]
    assert cost.router_params(dec) == 4096 * 128 == dec["bytes"]["router_parameters_a_layer"]
    assert cost.head_params(dec) == 4096 * 32768
    assert cost.token_matmul_params(dec) == 4 * (142_606_336 + 524_288 + 201_326_592)
    assert cost.decoder_params(dec) == 4 * (142_606_336 + 524_288 + 201_326_592 + 16 * 50_331_648) + 134_217_728


def test_prefill_and_decode_costs_are_the_hand_counts(published):
    dec = published
    token, head, expert = cost.token_matmul_params(dec), 134_217_728, 50_331_648
    # two prompts of 3 and 5 tokens: 6 + 15 (query, key) pairs a layer, 128 heads of 128, scores and values
    assert cost.attention_pairs(5, dec) == 4 * 15
    assert cost.prefill_flops((3, 5), dec, 9) == 2 * 8 * token + 4 * 128 * 128 * 4 * 21 + 2 * 2 * head + 2 * 9 * expert
    # past the window a sliding layer's token sees 4,096 keys and the full layer's all of them
    assert cost.keys_seen("sliding_attention", 5000, dec) == 4096 and cost.keys_seen("full_attention", 5000, dec) == 5000
    assert cost.attention_pairs(4098, dec) == 3 * (4096 * 4097 // 2 + 2 * 4096) + 4098 * 4099 // 2
    # one row of 10 tokens, two steps: against 11 and 12 positions in each of 4 layers
    assert cost.decode_flops((10,), 2, dec, 7) == 2 * 2 * (token + head) + 4 * 128 * 128 * 4 * 23 + 2 * 7 * expert
    assert cost.cache_token_bytes(dec) == 2 * 8 * 128 * 2
    assert cost.decode_bytes((10,), 2, dec, 5) == 2 * (2 * (token + head) + 5 * expert) + 4096 * (4 * 23 + 2 * 4) + 2 * (2 * 4096 + 4 * 32768)
    assert cost.prefill_bytes((3, 5), dec, 6) == 2 * (token + head + 6 * expert) + 8 * (4 + 2 * 4096 + 4 * 4096) + 4 * 2 * 32768
    # the issue's arithmetic: 2.75 GFLOP a prefilled token before attention and the routed pair it keeps here
    assert 2.7e9 < 2 * token / 4 * 4 < 2.8e9
    assert cost.cache_bytes(2, 6208, dec) == 2 * 4096 * (3 * 4096 + 6208)


def test_the_roofline_reader_holds_each_execution_against_its_own_call(published):
    import readers
    import trace as trace_mod

    read = readers.find("chat_roofline_command_a", os.path.join(BENCH, "layer_metrics"))
    peak = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
    call = {"at": 10.5, "rows": 3, "bucket": 768, "prompt_tokens": (600, 640, 700), "prefill_pairs_held": 7800,
            "decode_pairs_held": 760, "prefill_touched": 64, "decode_touched": 63 * 4 * 3}
    full = {**call, "rows": 16, "prompt_tokens": (640,) * 16, "prefill_pairs_held": 10000, "decode_touched": 63 * 4 * 11}
    calls = [call, full, call, full]
    device, host = ("/device:TPU:0", "XLA Modules"), ("/host:CPU", "python#3")
    events = [
        # call 0 began before the trace did: its decode is in the trace, its span is not
        trace_mod.Event(*device, "jit_chat_decode(2)", 0.1e9, 0.36e9),
        trace_mod.Event(*host, "bench:cmda_call.1", 0.6e9, 1.2e9),
        trace_mod.Event(*device, "jit_chat_prefill(1)", 0.61e9, 0.5e9),
        trace_mod.Event(*device, "jit_chat_decode(2)", 1.12e9, 0.66e9),
        trace_mod.Event(*host, "bench:cmda_call.2", 1.9e9, 0.8e9),
        trace_mod.Event(*device, "jit_chat_prefill(1)", 1.91e9, 0.3e9),
        trace_mod.Event(*device, "jit_chat_decode(2)", 2.22e9, 0.45e9),
        # call 3 was cut by the trace's end: a prefill and no span
        trace_mod.Event(*device, "jit_chat_prefill(1)", 2.8e9, 0.5e9),
    ]
    cell = types.SimpleNamespace(config=published)

    def ctx(calls, events=events):
        obs = types.SimpleNamespace(evidence={"chat_calls": calls})
        return types.SimpleNamespace(trace={"events": events, "start": 10.0, "stop": 14.0}, peak=peak, obs=obs, cell=cell)

    def least(c, program):
        if program == "prefill":
            return cost.prefill_flops(c["prompt_tokens"], published, c["prefill_pairs_held"]) / 197e12
        return cost.decode_bytes(c["prompt_tokens"], 63, published, c["decode_touched"]) / 819e9

    prefill = read(ctx(calls), program="prefill", patterns=["jit_chat_prefill"])
    assert prefill == pytest.approx(100 * (least(full, "prefill") + least(call, "prefill")) / (0.5 + 0.3))
    decode = read(ctx(calls), program="decode", patterns=["jit_chat_decode"])
    assert decode == pytest.approx(100 * (least(full, "decode") + least(call, "decode")) / (0.66 + 0.45))
    assert 10 < prefill < 40 and 60 < decode < 100
    # means over the calls dispatched and the executions found, each in the trace, would have read over 100% here
    assert 100 * (least(full, "decode") + least(call, "decode") + least(full, "decode")) / 3 / ((0.36 + 0.66 + 0.45) / 3) > 100
    assert read(ctx([]), program="decode", patterns=["jit_chat_decode"]) is None
    spanless = [e for e in events if not e.name.startswith("bench:")]
    assert read(ctx(calls, spanless), program="prefill", patterns=["jit_chat_prefill"]) is None
    other_pipeline = types.SimpleNamespace(trace=ctx([]).trace, peak=peak, obs=types.SimpleNamespace(evidence={}), cell=cell)
    assert read(other_pipeline, program="prefill", patterns=["jit_chat_prefill"]) is None
