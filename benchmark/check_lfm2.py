"""The answerer's part of the comparison that decides ``correct`` where the
chat model is ``lfm2_moe`` (``pipelines/rag_answerer_lfm2.py`` runs
``check.compare`` for the live index's numbers, as they are, and then this).

The numbers, their names and their arithmetic are ``check_decoder.py``'s, and
its code computes them, as ``check_command_a.py`` has it: the exact counts
(``answers_lost``, ``answers_repeated``, ``answer_tokens_off``,
``context_unsound``, ``served_logit_steps_off``) and, over
``check_decoder.SAMPLE_ANSWERS`` finished queries drawn from the seed,
``served_logit_gap.prefill``, ``.decode`` and ``greedy_gap`` of what the
timed calls served. What is this file's is the reference they are taken
against: ``reference_lfm2.served_logits`` over each sampled prompt followed
by the tokens served, float32 at ``highest``, no cache and no padding: a
row that the program served from a left-padded batch, through both kinds of
state, is held against its own sequence alone.

One number more than ``check_decoder``'s, ``served_logit_gap.median``: the
median, over every sampled step, of the ``served_logit_gap`` whose means the
other two are. This router takes four of 64 experts with weights that sum to
one and the layer has no shared expert, so one near tie that bfloat16 moves
to another expert changes a quarter of a token's feed-forward: on the chip
some 17% of the served tokens are not the float32 reference's best, those
steps read 0.1 to 0.8 and carry the means (0.06 in the sound program, 0.08
in the float8 control: no limit lies between with room). A precision lost
everywhere moves every step and so the median; a flipped choice moves its
own step and not the median. The limits (``limits/lfm2-rag-answer.json``,
each with its reason) lie between the program's widest sound reading and the
control's smallest where the two differ (``control_lfm2.py``: float8
operands in the experts' products); the median's is the one the control
fails.

The same near ties are why ``compare`` hands ``check_decoder`` **one
generation a query**. ``check_decoder`` keys the timed calls' generations by
prompt and holds every answer of a prompt against the first of them. The
harness's primer asks the window's first question again, and where the index
answers both alike the two prompts are one text, generated in two calls
that batched it with other rows, under another padding: bfloat16 then sums
in another order, and this model's choice of token follows (one run in
seventeen on the chip served another 64 tokens the second time, and read
``answer_tokens_off`` 1 for it). So where a prompt was asked by as many
queries as it has generations, and every answer is the printed tokens of a
generation of its own, each query is compared under a key of its own with
that generation. An answer that is no generation's tokens, a query without
a generation and a generation without a query are counted as they were.
"""

from __future__ import annotations

import types

import numpy as np

import check_decoder
import reference_lfm2 as reflfm


def reference_logits(cell, facts: dict, sample: list[dict], operand=None) -> list:
    chat = cell.config["chat"]
    return reflfm.served_logits(
        facts["decoder_params"], [(s["prompt"], s["tokens"]) for s in sample], cell.config,
        chat["max_prompt_len"] + chat["max_new_tokens"], operand=operand,
    )


def one_generation_a_query(prompts: dict[int, str], generations: dict, results: list) -> tuple[dict, dict]:
    """``prompts`` (query -> prompt) and ``generations`` (prompt -> the timed
    calls' ``(tokens, logits)``) with every prompt that several queries asked,
    and that was generated once for each, split under a key a query: the
    query's own generation, found by the tokens its answer prints. A prompt
    whose answers and generations do not pair off is left as it was."""
    askers: dict[str, list[int]] = {}
    for query, prompt in prompts.items():
        askers.setdefault(prompt, []).append(query)
    prompts, generations = dict(prompts), dict(generations)
    for prompt, queries in askers.items():
        made = generations.get(prompt) or []
        if len(queries) < 2 or len(made) != len(queries):
            continue
        printed = [[int(t) for t in tokens if t > 3] for tokens, _ in made]
        left, own = list(range(len(made))), {}
        for query in queries:
            answer = check_decoder.served_tokens(results[query])
            own[query] = next((n for n in left if printed[n] == answer), None)
            if own[query] is None:
                break
            left.remove(own[query])
        else:
            del generations[prompt]
            for query, n in own.items():
                prompts[query] = f"{prompt}\0{query}"
                generations[prompts[query]] = [made[n]]
    return prompts, generations


def compare(cell, seed: int, *, schedule, obs, facts: dict, stand_in=None, memo=None) -> list[dict]:
    """``check_decoder.compare`` with this model's reference in the memo it
    would otherwise fill from ``reference_decoder``."""
    memo = {} if memo is None else memo
    if "logits" not in memo:
        memo["prompts"] = check_decoder.program_prompts(cell, seed, schedule, obs, facts["prefilled"])
        memo["sample"] = check_decoder.sample_sequences(cell, seed, obs, memo["prompts"])
        memo["logits"] = reference_logits(cell, facts, memo["sample"])
    prompts, generations = one_generation_a_query(memo["prompts"], obs.evidence["generations"], obs.evidence["results"])
    paired = types.SimpleNamespace(evidence={**obs.evidence, "generations": generations})
    numbers = check_decoder.compare(
        cell, seed, schedule=schedule, obs=paired, facts=facts, stand_in=stand_in, memo={**memo, "prompts": prompts}
    )
    served = check_decoder.gaps(memo["logits"], memo["sample"], stand_in)["served"] if memo["sample"] else None
    value, limit = float("inf") if served is None else float(np.median(served)), cell.limits.get("served_logit_gap.median")
    numbers.append({"name": "served_logit_gap.median", "value": value, "limit": limit,
                    "ok": limit is not None and bool(np.isfinite(value)) and value <= limit})
    return numbers
