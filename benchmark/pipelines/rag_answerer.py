"""The RAG answerer's pipeline: the live index's graph, then upstream's
question answerer over a local chat model, through the public API:

    pw.io.python.read(documents) -> TpuEncoderEmbedder -> DataIndex(TpuKnnFactory)
    pw.io.python.read(queries)   -> TpuEncoderEmbedder
      -> BaseRAGQuestionAnswerer.answer_index_reply (query_docs_as_of_now, k hits,
         the prompt template) -> TpuPipelineChat (prefill, decode loop) -> pw.io.subscribe

The front half is ``pipelines/live_index.py``'s: its weights, its prefilled
index, its warm-up. This file adds the decoder's weights from the seed
(``reference_decoder.make_params``, handed to the program as its ``params=``),
the warm-up of the chat's programs, the graph to the answers' sink, and the
comparison: the live index's numbers through ``check.compare`` as they are,
then the answers' (``check_decoder.py``). The decoder's keys are the
configuration file's own top-level keys, as published.

A checkout whose program has no routed-expert product cannot run this
configuration: loading this file there ends the run at once, in
``load_cell``, before JAX is imported.
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if not os.path.exists(os.path.join(ROOT, "pathway_tpu", "ops", "moe.py")):
    raise SystemExit(
        "pipeline rag_answerer: this checkout's program has no routed-expert product "
        "(pathway_tpu/ops/moe.py) and no latent-cache decoder; it cannot run a configuration "
        "whose chat model has them"
    )

import check  # noqa: E402
import check_decoder  # noqa: E402
import costs_decoder  # noqa: E402
import harness  # noqa: E402
import reference  # noqa: E402
import reference_decoder  # noqa: E402

live_index = harness.find_pipeline("live_index")


# -- set-up -------------------------------------------------------------------


def make_chat(config: dict, params):
    """The program's chat over the benchmark's weights, its decoder built
    from the configuration's own keys; refuses a program that reads them as
    other widths."""
    from pathway_tpu.models.decoder import DecoderConfig
    from pathway_tpu.xpacks.llm.llms import TpuPipelineChat

    spec = config["chat"]
    cfg = DecoderConfig.from_hf(config)
    want = (
        config["hidden_size"], config["num_hidden_layers"], config["num_attention_heads"],
        config["kv_lora_rank"] + config["qk_rope_head_dim"], config["n_routed_experts"],
        config["num_experts_per_tok"], config["n_shared_experts"], config["moe_intermediate_size"],
        config["intermediate_size"], config["vocab_size"],
    )
    got = (
        cfg.hidden, cfg.layers, cfg.heads, cfg.cache_width, cfg.n_routed_experts, cfg.experts_per_token,
        cfg.n_shared_experts, cfg.moe_intermediate, cfg.intermediate, cfg.vocab_size,
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


def warm_up_chat(chat) -> None:
    """Every program the chat can run: prefill at each prompt bucket, the
    decode loop."""
    for bucket in chat.prompt_buckets:
        chat._fn([" ".join(["w0"] * (bucket - 2))])


def weights(cell, seed: int) -> dict:
    state = live_index.weights(cell, seed)
    state["seed"] = seed
    return state


def set_up(cell, seed: int, schedule, state: dict, mesh, phase) -> None:
    """The front half first: the program that makes the prefilled rows holds
    the index twice over while it runs (6.44 GB of scratch beside 6.45 GB of
    output), which fits one chip only before the decoder's weights are there."""
    live_index.set_up(cell, seed, schedule, state, mesh, phase)
    state["decoder_params"] = reference_decoder.make_params(seed, cell.config)
    state["chat"] = make_chat(cell.config, state["decoder_params"])
    phase("decoder_weights")
    warm_up_chat(state["chat"])
    phase("warm_up_chat")


# -- the graph ----------------------------------------------------------------


def build(pw, cell, state: dict, feeds: dict, clock) -> None:
    from pathway_tpu.stdlib.indexing import DataIndex, TpuKnnFactory
    from pathway_tpu.xpacks.llm import prompts
    from pathway_tpu.xpacks.llm.question_answering import BaseRAGQuestionAnswerer

    obs, span = clock.obs, clock.span
    embedder, index, chat = state["embedder"], state["index"], state["chat"]
    seed, mix, prefilled = state["seed"], cell.mix, state["prefilled"]
    n_docs, n_queries = len(obs.documents.ack), len(obs.queries.ack)
    doc_key = obs.evidence["doc_key"] = [None] * n_docs
    doc_emb = obs.evidence["doc_emb"] = [None] * n_docs
    query_ids = obs.evidence["query_ids"] = [None] * n_queries
    query_scores = obs.evidence["query_scores"] = [None] * n_queries
    query_emb = obs.evidence["query_emb"] = [None] * n_queries
    results = obs.evidence["results"] = [None] * n_queries
    #: prompt -> [(tokens [new], logits [new])]: what the timed calls produced
    generations = obs.evidence["generations"] = {}

    inner_fn = state["wrapped_chat_fn"] = chat._fn

    def chat_fn(prompt_texts):
        at = time.perf_counter()
        with span("chat_call"):
            out = inner_fn(prompt_texts)
        made = chat.last_generation
        obs.device_calls.append(
            (at, "chat", made["bucket"], made["rows"], made["prefill_touched"], made["decode_touched"],
             tuple(made["prompt_tokens"]))
        )
        for n, prompt in enumerate(prompt_texts):
            generations.setdefault(prompt, []).append((made["tokens"][n], made["logits"][n]))
        return out

    chat._fn = chat_fn

    class Factory(TpuKnnFactory):
        def build(self):
            return index

    def chunk_store(key):
        """A restarted deployment's chunk store: the text of a restored row, by key."""
        slot = int(key) - reference.PREFILL_KEY_BASE
        return reference_decoder.chunk_text(seed, slot, mix) if 0 <= slot < prefilled else None

    docs = pw.io.python.read(
        feeds["documents"],
        schema=pw.schema_from_types(doc_id=int, text=str),
        autocommit_duration_ms=cell.config["doc_autocommit_ms"],
    )
    docs = docs.select(doc_id=pw.this.doc_id, text=pw.this.text, emb=embedder(pw.this.text))
    data_index = DataIndex(
        docs,
        Factory(
            dimensions=embedder.get_embedding_dimension(),
            metric=cell.config["index"]["metric"],
            capacity=cell.config["index"]["capacity"],
            mesh=index.mesh,
        ),
        docs.emb,
    )

    def take_doc(i, key, row):
        doc_emb[i] = np.asarray(row["emb"], np.float32)
        doc_key[i] = key

    pw.io.subscribe(docs, on_change=clock.sink("documents", take_doc), on_time_end=clock.on_time_end)
    queries = pw.io.python.read(
        feeds["queries"],
        schema=pw.schema_from_types(query_id=int, text=str),
        autocommit_duration_ms=clock.schedule.queries.autocommit_ms,
    )
    queries = queries.select(query_id=pw.this.query_id, prompt=pw.this.text, qemb=embedder(pw.this.text))
    answerer = BaseRAGQuestionAnswerer(
        chat, None, search_topk=cell.config["index"]["k"], prompt_template=prompts.prompt_qa,
        chunk_store=chunk_store,
    )
    answers = answerer.answer_index_reply(queries, data_index, queries.qemb)
    asked = queries.restrict(answers)
    answers = answers.select(
        query_id=asked.query_id, qemb=asked.qemb, result=answers.result, context_docs=answers.context_docs
    )

    def take_answer(i, key, row):
        context = row["context_docs"]
        query_ids[i] = tuple(d["id"] for d in context)
        query_scores[i] = tuple(d["score"] for d in context)
        query_emb[i] = np.asarray(row["qemb"], np.float32)
        results[i] = row["result"]

    pw.io.subscribe(answers, on_change=clock.sink("queries", take_answer))


def restore(state: dict) -> None:
    inner = state.pop("wrapped_chat_fn", None)
    if inner is not None:
        state["chat"]._fn = inner


# -- the step's work, and the comparison --------------------------------------


def work_flops(cell, schedule, obs) -> float:
    """Model FLOPs of the real tokens embedded (documents at the sink,
    queries answered), prefilled and generated inside the window: a prompt
    of ``n`` tokens is one prefill of ``n``, then ``max_new_tokens - 1``
    decode steps against ``n + j`` filled slots."""
    dec, new = cell.config, cell.config["chat"]["max_new_tokens"]
    total = live_index.work_flops(cell, schedule, obs)
    lengths = [n for call in obs.device_calls if call[1] == "chat" and call[0] <= obs.t_end for n in call[6]]
    for n, count in zip(*np.unique(lengths, return_counts=True)):
        n = int(n)
        one = costs_decoder.prefill_flops(1, n, dec)
        one += sum(costs_decoder.decode_step_flops(1, n + j, dec) for j in range(1, new))
        total += int(count) * one
    return total


def facts(cell, state: dict, obs, seed: int, schedule) -> dict:
    from pathway_tpu.internals import tracing

    out = live_index.facts(cell, state, obs, seed, schedule)
    out["decoder_params"] = state["decoder_params"]
    stages = tracing.stage_totals()["stages"]
    out["prompts_truncated"] = stages.get("chat.batch", {}).get("counts", {}).get("truncated", 0)
    return out


def compare(cell, seed: int, *, schedule, obs, facts: dict) -> list[dict]:
    numbers = check.compare(cell, seed, schedule=schedule, obs=obs, facts=facts)
    return numbers + check_decoder.compare(cell, seed, schedule=schedule, obs=obs, facts=facts)
