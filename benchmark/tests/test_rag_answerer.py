"""The answerer's pipeline end to end at a toy size, the faults its
comparison has to see, its control, and its costs against hand counts."""

from __future__ import annotations

import json
import math
import os

import pytest

import costs_decoder
import harness
import toy_answerer
from conftest import BENCH, ROOT


@pytest.fixture(scope="module")
def sound():
    return toy_answerer.run()


def _failed(result: dict) -> set:
    return {name for name, (value, limit) in result["compared"].items() if limit is None or not value <= limit}


def test_a_sound_run_is_correct_and_compares_the_index_and_the_answers(sound):
    assert sound["correct"] is True, sound["compared"]
    assert sound["failed"] == 0 and sound["attempted"] > 60
    names = list(sound["compared"])
    for name in ("docs_lost", "knn_gap", "answers_unsound", "answers_lost", "answers_repeated", "answer_tokens_off",
                 "context_unsound", "served_logit_gap.prefill", "served_logit_gap.decode", "greedy_gap",
                 "served_logit_steps_off"):
        assert name in names
    assert names.index("knn_gap") < names.index("answers_lost")  # the live index's numbers first, as they are
    assert 0 < sound["compared"]["served_logit_gap.decode"][0] < toy_answerer.LIMITS["served_logit_gap.decode"]
    assert set(sound["metrics"]) == {"setup_s", "index_lag_p95_ms", "query_p50_ms", "docs_per_s"}
    json.dumps(sound)


def _without_shared_experts(cell_) -> None:
    """The chat gets the weights with the shared experts' down projection
    zeroed: they add nothing."""
    import jax.numpy as jnp

    inner = cell_.pipeline.make_chat

    def make_chat(config, params):
        layers = [
            {**lp, "shared_down_w": jnp.zeros_like(lp["shared_down_w"])} if "shared_down_w" in lp else lp
            for lp in params["layers"]
        ]
        return inner(config, {**params, "layers": layers})

    cell_.pipeline.make_chat = make_chat


@pytest.mark.parametrize("fault", ["shared_experts_left_out", "chosen_weights_renormalised", "cache_slot_off_by_one",
                                   "softmax_scale_without_m_squared", "token_dropped_over_capacity"])
def test_a_planted_fault_in_the_decoder_is_not_correct(fault, monkeypatch):
    """Drive a whole run with the decoder broken underneath."""
    import jax.numpy as jnp
    from jax import lax

    from pathway_tpu.models import decoder as dec_mod
    from pathway_tpu.ops import moe

    cell = toy_answerer.cell()
    if fault == "shared_experts_left_out":
        _without_shared_experts(cell)
    elif fault == "chosen_weights_renormalised":
        inner = moe.route_top_k
        monkeypatch.setattr(dec_mod, "route_top_k", lambda h, w, k, **kw: inner(h, w, k, **{**kw, "renormalize": True}))
    elif fault == "cache_slot_off_by_one":  # a decode step's row lands one slot late
        inner_write = dec_mod._write

        def write(buffer, chunk, start):
            late = chunk.shape[1] == 1 and buffer.ndim == 3
            return inner_write(buffer, chunk, start + 1 if late else start)

        monkeypatch.setattr(dec_mod, "_write", write)
    elif fault == "softmax_scale_without_m_squared":
        plain = lambda self: (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5  # noqa: E731
        monkeypatch.setattr(dec_mod.DecoderConfig, "softmax_scale", property(plain))
    elif fault == "token_dropped_over_capacity":  # rows past 1.25 x the mean load of their expert add nothing
        inner_routed = moe.routed_experts

        def routed(h, weights, experts, gate_up_w, down_w):
            n, k = experts.shape
            capacity = math.ceil(1.25 * n * k / gate_up_w.shape[0])
            flat = experts.reshape(-1)
            one_hot = flat[:, None] == jnp.arange(gate_up_w.shape[0])
            place = (jnp.cumsum(one_hot, axis=0) - 1)[jnp.arange(n * k), flat]  # place among its expert's rows
            kept = jnp.where(place < capacity, weights.reshape(-1), 0.0).reshape(n, k)
            return inner_routed(h, kept, experts, gate_up_w, down_w)

        monkeypatch.setattr(dec_mod, "routed_experts", routed)
    result = toy_answerer.run(seed=2**31 + 8, cell_=cell)
    assert result["correct"] is False
    wrong = _failed(result)
    assert wrong and wrong <= {"served_logit_gap.prefill", "served_logit_gap.decode", "greedy_gap",
                               "served_logit_steps_off"}, result["compared"]
    if fault == "cache_slot_off_by_one":  # prefill wrote its rows where they belong
        assert "served_logit_gap.prefill" not in wrong and "served_logit_gap.decode" in wrong


def test_the_control_reads_over_the_limit():
    """The reference in the program's place with float8 operands in the
    experts' products is refused, by the gaps and by nothing else."""
    import jax

    import control_decoder

    out = control_decoder.readings(toy_answerer.cell(), 5, 1.5, jax.devices())
    assert out["program"]["correct"] is True, out["program"]
    control = out["control_float8_experts"]
    assert control["correct"] is False
    assert "served_logit_gap.decode" in control["failed"]
    assert control["numbers"]["served_logit_gap.decode"] > 2 * out["program"]["numbers"]["served_logit_gap.decode"]
    assert set(control["failed"]) <= {"served_logit_gap.prefill", "served_logit_gap.decode", "greedy_gap",
                                      "served_logit_steps_off"}


def test_a_checkout_without_the_routed_expert_product_ends_in_load_cell(monkeypatch):
    """What the parent commit does with this cell: the pipeline's file is
    there (the benchmark's files are laid over it), the program's is not."""
    inner = os.path.exists
    monkeypatch.setattr(os.path, "exists", lambda p: False if str(p).endswith(os.path.join("ops", "moe.py")) else inner(p))
    with pytest.raises(SystemExit, match="no routed-expert product"):
        harness.find_pipeline("rag_answerer")


def test_the_cell_loads_with_its_files_and_every_metric_has_a_reader():
    import readers

    cell = harness.load_cell(ROOT, "dsv2lite-rag-answer")
    assert cell.config["pipeline"] == "rag_answerer" and cell.chips == 1
    assert cell.pipeline.__file__ == os.path.join(BENCH, "pipelines", "rag_answerer.py")
    # a configuration without the key still runs the default
    assert harness.load_cell(ROOT, "bge-live-rag").pipeline.__file__ == os.path.join(BENCH, "pipelines", "live_index.py")
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s", "query_p50_ms"}
    assert len(cell.per_layer) >= 14 and all(m["workloads"] == ["dsv2lite-rag-answer"] for m in cell.per_layer)
    for metric in cell.per_layer:
        assert callable(readers.find(metric["reader"], os.path.join(BENCH, "layer_metrics")))
    for name in ("served_logit_gap.prefill", "served_logit_gap.decode", "greedy_gap", "served_logit_step_limit",
                 "knn_gap", "embed_gap_docs"):
        assert name in cell.limits


# -- the configuration and the costs -------------------------------------------


@pytest.fixture(scope="module")
def published():
    with open(os.path.join(BENCH, "configs", "dsv2lite-rag-answerer.json")) as fh:
        return json.load(fh)


def test_the_configuration_holds_the_catalogs_keys_with_depth_alone_changed(published):
    catalog = os.path.join("/opt/skills/guides/model-configs", "architectures.jsonl")
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as fh:
        row = next(r for r in map(json.loads, fh) if r["name"] == "DeepSeek-V2-Lite")
    changed = {k for k, v in row["config"].items() if published.get(k) != v}
    assert changed == {"num_hidden_layers"} and published["num_hidden_layers"] == 5
    assert {"depth", "num_hidden_layers", "index"} == set(published["reduced"])
    with open(os.path.join(BENCH, "configs", "bge-base-live-index.json")) as fh:
        bge = json.load(fh)
    assert published["encoder"] == bge["encoder"] and published["embedder"] == bge["embedder"]
    assert published["index"] == {**bge["index"], "k": 6}
    assert published["guarantees"][:5] == bge["guarantees"]


def test_the_cache_of_an_attention_layer_holds_576_values_a_token(published):
    import jax

    from pathway_tpu.models.decoder import DecoderConfig, init_cache

    cfg = DecoderConfig.from_hf(published)
    chat = published["chat"]
    shapes = jax.eval_shape(lambda: init_cache(cfg, chat["max_batch_size"], chat["max_prompt_len"] + chat["max_new_tokens"]))
    assert [s["latent"].shape for s in shapes.layers] == [(8, 2112, 576)] * 5
    assert published["bytes"]["latent_cache_8x2112"] == 8 * 2112 * 576 * 2 * 5


def test_parameter_counts_are_the_hand_counts(published):
    dec = published
    assert costs_decoder.attention_params(dec) == 2048 * 3072 + 2048 * 576 + 512 * 4096 + 2048 * 2048 == 13_762_560
    assert costs_decoder.dense_mlp_params(dec) == 3 * 2048 * 10944 == 67_239_936
    assert costs_decoder.expert_params(dec) == 3 * 2048 * 1408 == 8_650_752
    assert costs_decoder.shared_params(dec) == 17_301_504
    assert costs_decoder.router_params(dec) == 131_072 and costs_decoder.head_params(dec) == 209_715_200
    assert costs_decoder.layer_counts(dec) == (1, 4)
    # a token: 5 attentions, the dense MLP, and in 4 layers the router, the shared pair and 6 routed experts
    assert costs_decoder.token_matmul_params(dec) == 5 * 13_762_560 + 67_239_936 + 4 * (131_072 + 17_301_504 + 6 * 8_650_752)
    whole = 2 * 209_715_200 + 5 * 13_762_560 + 67_239_936 + 4 * (131_072 + 17_301_504 + 64 * 8_650_752)
    assert published["bytes"]["decoder_parameters_bf16"] == 2 * whole


def test_prefill_and_decode_costs_are_the_hand_counts(published):
    dec = published
    token = costs_decoder.token_matmul_params(dec)
    # two prompts of 4 tokens: 10 (query, key) pairs a prompt a layer, 16 heads, 192 + 128 wide
    attention = 5 * 2 * 16 * 10 * (128 + 64 + 128)
    assert costs_decoder.prefill_flops(2, 4, dec) == 2 * (2 * 4 * token + attention + 2 * 209_715_200)
    # a decode step of 3 rows against 100 slots, absorbed: 576 + 512 wide against the cache, W_kvb as absorptions
    kvb = 512 * 16 * 256
    absorbed = 5 * 2 * 16 * (100 * (2 * 512 + 64) + 512 * 256)
    assert costs_decoder.decode_step_flops(3, 100, dec) == 3 * (2 * (token - 5 * kvb) + absorbed + 2 * 209_715_200)
    resident = 5 * 13_762_560 + 67_239_936 + 4 * (131_072 + 17_301_504) + 209_715_200
    assert costs_decoder.resident_step_params(dec) == resident
    assert costs_decoder.cache_row_bytes(dec) == 5 * 576 * 2
    assert costs_decoder.decode_step_bytes(8, 1100, dec, 140) == (
        2 * (resident + 140 * 8_650_752) + 8 * 1101 * 5760 + 8 * 2 * 2048 + 4 * 8 * 102_400
    )
    assert costs_decoder.prefill_bytes(8, 2048, dec, 256) == (
        2 * (resident + 256 * 8_650_752) + 8 * 2048 * (4 + 2 * 2048 + 5760) + 4 * 8 * 102_400
    )
    # the issue's arithmetic: a full prefill batch some 14 TFLOP, a decode step some 4 ms of bytes
    assert 13e12 < costs_decoder.prefill_flops(8, 2048, dec) < 15e12
    assert 3.5e-3 < costs_decoder.decode_step_bytes(8, 1100, dec, 140) / 819e9 < 4.5e-3


def test_the_roofline_reader_takes_means_a_call_and_finds_nothing_without_chat_calls(published):
    import types

    import readers
    import trace as trace_mod

    read = readers.find("chat_roofline", os.path.join(BENCH, "layer_metrics"))
    peak = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
    events = [
        trace_mod.Event("/device:TPU:0", "XLA Modules", "jit_chat_prefill(1)", 0.0, 0.2e9),
        trace_mod.Event("/device:TPU:0", "XLA Modules", "jit_chat_prefill(1)", 1e9, 0.2e9),
        trace_mod.Event("/device:TPU:0", "XLA Modules", "jit_chat_decode(2)", 2e9, 0.4e9),
    ]
    call = (10.5, "chat", 2048, 3, 256, 63 * 140, (900, 1000, 1100))
    cell = types.SimpleNamespace(config=published)

    def ctx(calls):
        obs = types.SimpleNamespace(device_calls=calls)
        return types.SimpleNamespace(trace={"events": events, "start": 10.0, "stop": 14.0}, peak=peak, obs=obs, cell=cell)

    # three calls in the window against two executions in the trace: the means do not move
    prefill = read(ctx([call] * 3), program="prefill", patterns=["jit_chat_prefill"])
    assert prefill == pytest.approx(100 * costs_decoder.prefill_flops(8, 2048, published) / 197e12 / 0.2)
    decode = read(ctx([call]), program="decode", patterns=["jit_chat_decode"])
    assert 55 < decode < 75  # 63 steps of some 4.2 ms against 0.4 s
    assert read(ctx([]), program="decode", patterns=["jit_chat_decode"]) is None
    assert read(ctx([(10.5, "embed", 8, 32)]), program="prefill", patterns=["jit_chat_prefill"]) is None
