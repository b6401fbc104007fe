"""The RAG answerer's pipeline where the chat model is an ``lfm2_moe``
decoder (``configs/lfm2-24b-a2b-rag-answerer.json``): gated short
convolutions with a fixed-size state beside one grouped-query cache, a
sigmoid router whose bias picks and whose score weighs.
``pipelines/rag_answerer.py``'s graph, sinks and evidence as they are, built
on as ``rag_answerer_command_a.py`` builds on it. This file adds the
decoder's weights from the seed (``reference_lfm2.make_params``), the check
that the program reads the configuration's keys as the widths and the layer
kinds it states, what each timed call held (``obs.evidence["chat_calls"]``:
the rooflines and the step's work are reckoned from the real rows, the real
tokens and the pairs and experts the call counted), and the comparison: the
live index's numbers through ``check.compare``, then the answers' against
this model's reference (``check_lfm2.py``).

A checkout whose decoder has no conv layer cannot run this configuration:
loading this file there ends the run at once, in ``load_cell``, before JAX
is imported.
"""

from __future__ import annotations

import os
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_DECODER = os.path.join(ROOT, "pathway_tpu", "models", "decoder.py")
if not os.path.exists(_DECODER) or "def _short_conv(" not in open(_DECODER).read():
    raise SystemExit(
        "pipeline rag_answerer_lfm2: this checkout's decoder (pathway_tpu/models/decoder.py) has no gated "
        "short-convolution layer and no cache state of a fixed size; it cannot run a configuration whose "
        "chat model has four of them in five layers"
    )

import check  # noqa: E402
import check_lfm2  # noqa: E402
import costs_lfm2  # noqa: E402
import harness  # noqa: E402
import reference_lfm2  # noqa: E402

rag_answerer = harness.find_pipeline("rag_answerer")
live_index = rag_answerer.live_index

weights = rag_answerer.weights
facts = rag_answerer.facts


# -- set-up -------------------------------------------------------------------


def make_chat(config: dict, params):
    """The program's chat over the benchmark's weights, its decoder built
    from the configuration's own keys; refuses a program that reads them as
    other widths or other layer kinds."""
    from pathway_tpu.models.decoder import DecoderConfig
    from pathway_tpu.xpacks.llm.llms import TpuPipelineChat

    spec = config["chat"]
    cfg = DecoderConfig.from_hf(config)
    kinds = reference_lfm2.layer_kinds(config)
    want = (
        config["hidden_size"], config["num_hidden_layers"], config["num_attention_heads"],
        config["num_key_value_heads"], reference_lfm2.head_dim(config), config["intermediate_size"],
        config["moe_intermediate_size"], config["num_experts"], config["num_experts_per_tok"], 0,
        config["conv_L_cache"], tuple("conv" if op == "conv" else "gqa" for op, _ in kinds),
        tuple(ff for _, ff in kinds), config["vocab_size"], True, True, True,
    )
    got = (
        cfg.hidden, cfg.layers, cfg.heads, cfg.kv_heads, cfg.head_dim, cfg.intermediate, cfg.moe_intermediate,
        cfg.n_routed_experts, cfg.experts_per_token, cfg.n_shared_experts, cfg.conv_taps, cfg.attention_pattern,
        cfg.layer_pattern, cfg.vocab_size, cfg.router_bias, cfg.qk_norm, cfg.tie_embeddings,
    )
    if got != want or np.dtype(cfg.dtype).name != config["decoder_compute_dtype"]:
        raise RuntimeError(f"the program's decoder is {got}, the configuration says {want}")
    return TpuPipelineChat(
        cfg,
        max_new_tokens=spec["max_new_tokens"],
        max_prompt_len=spec["max_prompt_len"],
        max_batch_size=spec["max_batch_size"],
        prompt_buckets=spec["prompt_buckets"],
        keep_tail=spec["keep_tail"],
        params=params,
        eos_id=None,  # assumed: every answer runs its max_new_tokens
        cache_tag="benchmark",
    )


def set_up(cell, seed: int, schedule, state: dict, mesh, phase) -> None:
    """The front half first, as ``rag_answerer`` has it: the program that
    makes the prefilled rows holds the index twice over while it runs."""
    live_index.set_up(cell, seed, schedule, state, mesh, phase)
    state["decoder_params"] = reference_lfm2.make_params(seed, cell.config)
    state["chat"] = make_chat(cell.config, state["decoder_params"])
    phase("decoder_weights")
    rag_answerer.warm_up_chat(state["chat"])
    phase("warm_up_chat")


# -- the graph ----------------------------------------------------------------


def build(pw, cell, state: dict, feeds: dict, clock) -> None:
    """``rag_answerer``'s graph over a chat whose batch function also keeps
    what each call held, and in a traced run puts a host span with the call's
    place in ``chat_calls`` round it (``bench:lfm2_call.<n>``), by which
    ``layer_metrics/chat_roofline_lfm2.py`` knows a call's executions."""
    chat = state["chat"]
    plain = state["plain_chat_fn"] = chat._fn
    calls = clock.obs.evidence["chat_calls"] = []

    def chat_fn(prompt_texts):
        at = time.perf_counter()
        with clock.span(f"lfm2_call.{len(calls)}"):
            out = plain(prompt_texts)
        made = chat.last_generation
        calls.append({
            "at": at, "rows": made["rows"], "bucket": made["bucket"], "prompt_tokens": tuple(made["prompt_tokens"]),
            **{name: made[name] for name in ("prefill_pairs_held", "decode_pairs_held", "prefill_touched", "decode_touched")},
        })
        return out

    chat._fn = chat_fn
    rag_answerer.build(pw, cell, state, feeds, clock)


def restore(state: dict) -> None:
    rag_answerer.restore(state)
    plain = state.pop("plain_chat_fn", None)
    if plain is not None:
        state["chat"]._fn = plain


# -- the step's work, and the comparison --------------------------------------


def work_flops(cell, schedule, obs) -> float:
    """Model FLOPs of the real tokens embedded (documents at the sink,
    queries answered), prefilled and generated inside the window, the routed
    experts' for the pairs the calls counted."""
    dec, steps = cell.config, cell.config["chat"]["max_new_tokens"] - 1
    total = live_index.work_flops(cell, schedule, obs)
    for call in obs.evidence.get("chat_calls", ()):
        if obs.t0 <= call["at"] <= obs.t_end:
            total += costs_lfm2.prefill_flops(call["prompt_tokens"], dec, call["prefill_pairs_held"])
            total += costs_lfm2.decode_flops(call["prompt_tokens"], steps, dec, call["decode_pairs_held"])
    return total


def compare(cell, seed: int, *, schedule, obs, facts: dict) -> list[dict]:
    numbers = check.compare(cell, seed, schedule=schedule, obs=obs, facts=facts)
    return numbers + check_lfm2.compare(cell, seed, schedule=schedule, obs=obs, facts=facts)
