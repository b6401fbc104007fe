"""The RAG answerer's pipeline where the chat model is a ``mellum`` decoder
(``configs/mellum2-12b-a2.5b-rag-answerer.json``): sliding grouped-query
layers whose rings wrap in every call, beside a full layer that turns by
YaRN times its attention factor, 64 experts chosen 8 at a time by softmax.
``pipelines/rag_answerer.py``'s graph, sinks and evidence as they are, built
on as ``rag_answerer_lfm2.py`` builds on it. This file adds the decoder's
weights from the seed (``reference_mellum.make_params``), the check that the
program reads the configuration's keys as the widths, the layer kinds and
the rotations it states, what each timed call held
(``obs.evidence["chat_calls"]``: the rooflines and the step's work are
reckoned from the real rows, the real tokens and the pairs and experts the
call counted), and the comparison: the live index's numbers through
``check.compare``, then the answers' against this model's reference
(``check_mellum.py``).

A checkout whose decoder turns every attention kind alike cannot run this
configuration: loading this file there ends the run at once, in
``load_cell``, before JAX is imported.
"""

from __future__ import annotations

import os
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_DECODER = os.path.join(ROOT, "pathway_tpu", "models", "decoder.py")
if not os.path.exists(_DECODER) or "def rope_of(" not in open(_DECODER).read():
    raise SystemExit(
        "pipeline rag_answerer_mellum: this checkout's decoder (pathway_tpu/models/decoder.py) turns no "
        "attention kind by a rotation of its own (no DecoderConfig.rope_of): it cannot run a configuration "
        "whose full layer turns by YaRN beside sliding layers that turn plainly"
    )

import check  # noqa: E402
import check_mellum  # noqa: E402
import costs_mellum  # noqa: E402
import harness  # noqa: E402
import reference_mellum  # noqa: E402

rag_answerer = harness.find_pipeline("rag_answerer")
live_index = rag_answerer.live_index

weights = rag_answerer.weights
facts = rag_answerer.facts


# -- set-up -------------------------------------------------------------------


def published_rotations(config: dict) -> tuple:
    """(theta, YaRN's keys, cos/sin scale) of each kind, as the sections give them."""
    sliding, full = config["rope_parameters"]["sliding_attention"], config["rope_parameters"]["full_attention"]
    yarn = (full["factor"], full["original_max_position_embeddings"], full["beta_fast"], full["beta_slow"])
    return (float(sliding["rope_theta"]), None, 1.0), (float(full["rope_theta"]), yarn, full["attention_factor"])


def make_chat(config: dict, params):
    """The program's chat over the benchmark's weights, its decoder built
    from the configuration's own keys; refuses a program that reads them as
    other widths, other layer kinds or other rotations."""
    from pathway_tpu.models.decoder import DecoderConfig
    from pathway_tpu.xpacks.llm.llms import TpuPipelineChat

    spec = config["chat"]
    cfg = DecoderConfig.from_hf(config)
    want = (
        config["hidden_size"], config["num_hidden_layers"], config["num_attention_heads"],
        config["num_key_value_heads"], config["head_dim"], config["moe_intermediate_size"], config["num_experts"],
        config["num_experts_per_tok"], 0, config["sliding_window"], tuple(reference_mellum.layer_kinds(config)),
        ("experts",) * config["num_hidden_layers"], config["vocab_size"], "softmax", True, False, False,
        published_rotations(config),
    )

    def rotation(kind: str) -> tuple:
        turn = cfg.rope_of(kind)
        yarn = turn.yarn and (turn.yarn.factor, turn.yarn.original_max_len, turn.yarn.beta_fast, turn.yarn.beta_slow)
        return turn.theta, yarn, turn.scale

    got = (
        cfg.hidden, cfg.layers, cfg.heads, cfg.kv_heads, cfg.head_dim, cfg.moe_intermediate, cfg.n_routed_experts,
        cfg.experts_per_token, cfg.n_shared_experts, cfg.sliding_window, cfg.attention_pattern, cfg.layer_pattern,
        cfg.vocab_size, cfg.router, cfg.norm_topk_prob, cfg.tie_embeddings, cfg.qk_norm,
        (rotation("sliding"), rotation("full")),
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
    state["decoder_params"] = reference_mellum.make_params(seed, cell.config)
    state["chat"] = make_chat(cell.config, state["decoder_params"])
    phase("decoder_weights")
    rag_answerer.warm_up_chat(state["chat"])
    phase("warm_up_chat")


# -- the graph ----------------------------------------------------------------


def build(pw, cell, state: dict, feeds: dict, clock) -> None:
    """``rag_answerer``'s graph over a chat whose batch function also keeps
    what each call held, and in a traced run puts a host span with the call's
    place in ``chat_calls`` round it (``bench:mellum_call.<n>``), by which
    ``layer_metrics/chat_roofline_mellum.py`` knows a call's executions."""
    chat = state["chat"]
    plain = state["plain_chat_fn"] = chat._fn
    calls = clock.obs.evidence["chat_calls"] = []

    def chat_fn(prompt_texts):
        at = time.perf_counter()
        with clock.span(f"mellum_call.{len(calls)}"):
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
    experts' for the pairs the calls counted, a sliding layer's scores over
    its window alone."""
    dec, steps = cell.config, cell.config["chat"]["max_new_tokens"] - 1
    total = live_index.work_flops(cell, schedule, obs)
    for call in obs.evidence.get("chat_calls", ()):
        if obs.t0 <= call["at"] <= obs.t_end:
            total += costs_mellum.prefill_flops(call["prompt_tokens"], dec, call["prefill_pairs_held"])
            total += costs_mellum.decode_flops(call["prompt_tokens"], steps, dec, call["decode_pairs_held"])
    return total


def compare(cell, seed: int, *, schedule, obs, facts: dict) -> list[dict]:
    numbers = check.compare(cell, seed, schedule=schedule, obs=obs, facts=facts)
    return numbers + check_mellum.compare(cell, seed, schedule=schedule, obs=obs, facts=facts)
