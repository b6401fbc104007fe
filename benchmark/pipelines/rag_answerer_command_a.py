"""The RAG answerer's pipeline where the chat model is a ``cohere2_moe``
decoder of which this chip holds a share (``configs/command-a-plus-rag-answerer.json``):
``pipelines/rag_answerer.py``'s graph, sinks and evidence as they are, built
on as that file is built on ``pipelines/live_index.py``. This file adds the
decoder's weights from the seed (``reference_command_a.make_params``: the
experts held here, the vocabulary rows held here), the check that the
program reads the configuration's keys as the widths and the share it
states, what each timed call held (``obs.evidence["chat_calls"]``: the
rooflines and the step's work are reckoned from the real rows, the real
tokens and the pairs an expert held here took), and the comparison: the live
index's numbers through ``check.compare``, then the answers' against this
model's reference (``check_command_a.py``).

A checkout whose routed-expert product cannot be told which experts it
holds cannot run this configuration: loading this file there ends the run
at once, in ``load_cell``, before JAX is imported.
"""

from __future__ import annotations

import os
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_MOE = os.path.join(ROOT, "pathway_tpu", "ops", "moe.py")
if not os.path.exists(_MOE) or "held: tuple[int, int]" not in open(_MOE).read():
    raise SystemExit(
        "pipeline rag_answerer_command_a: this checkout's routed-expert product (pathway_tpu/ops/moe.py) "
        "takes no held share of a layer's experts; it cannot run a configuration whose chat model holds "
        "16 of 128"
    )

import check  # noqa: E402
import check_command_a  # noqa: E402
import costs_command_a  # noqa: E402
import harness  # noqa: E402
import reference_command_a  # noqa: E402

rag_answerer = harness.find_pipeline("rag_answerer")
live_index = rag_answerer.live_index

weights = rag_answerer.weights
facts = rag_answerer.facts


# -- set-up -------------------------------------------------------------------


def make_chat(config: dict, params):
    """The program's chat over the benchmark's weights, its decoder built
    from the configuration's own keys; refuses a program that reads them as
    other widths or another share."""
    from pathway_tpu.models.decoder import DecoderConfig
    from pathway_tpu.xpacks.llm.llms import TpuPipelineChat

    spec, share = config["chat"], config["held_here"]
    cfg = DecoderConfig.from_hf(config)
    want = (
        config["hidden_size"], config["num_hidden_layers"], config["num_attention_heads"],
        config["num_key_value_heads"], config["head_dim"], config["intermediate_size"], share["of_experts"],
        tuple(share["experts"]), config["num_experts_per_tok"], config["num_shared_experts"],
        config["sliding_window"], tuple(k.removesuffix("_attention") for k in costs_command_a.layer_kinds(config)),
        config["vocab_size"],
    )
    got = (
        cfg.hidden, cfg.layers, cfg.heads, cfg.kv_heads, cfg.head_dim, cfg.moe_intermediate, cfg.n_routed_experts,
        cfg.held_experts, cfg.experts_per_token, cfg.n_shared_experts, cfg.sliding_window, cfg.attention_pattern,
        cfg.vocab_size,
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
    state["decoder_params"] = reference_command_a.make_params(seed, cell.config)
    state["chat"] = make_chat(cell.config, state["decoder_params"])
    phase("decoder_weights")
    rag_answerer.warm_up_chat(state["chat"])
    phase("warm_up_chat")


# -- the graph ----------------------------------------------------------------


def build(pw, cell, state: dict, feeds: dict, clock) -> None:
    """``rag_answerer``'s graph over a chat whose batch function also keeps
    what each call held, and in a traced run puts a host span with the call's
    place in ``chat_calls`` round it (``bench:cmda_call.<n>``), by which
    ``layer_metrics/chat_roofline_command_a.py`` knows a call's executions."""
    chat = state["chat"]
    plain = state["plain_chat_fn"] = chat._fn
    calls = clock.obs.evidence["chat_calls"] = []

    def chat_fn(prompt_texts):
        at = time.perf_counter()
        with clock.span(f"cmda_call.{len(calls)}"):
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
    experts' for the pairs an expert held here took."""
    dec, steps = cell.config, cell.config["chat"]["max_new_tokens"] - 1
    total = live_index.work_flops(cell, schedule, obs)
    for call in obs.evidence.get("chat_calls", ()):
        if obs.t0 <= call["at"] <= obs.t_end:
            total += costs_command_a.prefill_flops(call["prompt_tokens"], dec, call["prefill_pairs_held"])
            total += costs_command_a.decode_flops(call["prompt_tokens"], steps, dec, call["decode_pairs_held"])
    return total


def compare(cell, seed: int, *, schedule, obs, facts: dict) -> list[dict]:
    numbers = check.compare(cell, seed, schedule=schedule, obs=obs, facts=facts)
    return numbers + check_command_a.compare(cell, seed, schedule=schedule, obs=obs, facts=facts)
