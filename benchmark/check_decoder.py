"""The answerer's part of the comparison that decides ``correct``
(``pipelines/rag_answerer.py`` runs ``check.compare`` for the live index's
numbers, as they are, and then this).

Counts, limit 0:

``answers_lost``       queries acknowledged at the sink whose answer is not the
    chat's string of tokens, or for whose prompt the chat never generated.
``answers_repeated``   generations beyond one a query: the chat ran twice for
    a prompt that one query sent.
``answer_tokens_off``  answers whose generation is not exactly
    ``max_new_tokens`` tokens, or that differ from the tokens the timed call
    itself produced as the program's tokenizer prints them.
``context_unsound``    queries whose prompt — the template over the texts of
    the ids the index returned, in rank order, a prefilled row's text read
    by key — is not a prompt the chat was given. That those ids are the exact
    top 6 as of the query's commit is the live index's ``answers_unsound``
    and ``knn_gap`` (``check.py``), over its own sample.

Gaps, for ``SAMPLE_ANSWERS`` finished queries drawn from the seed, the
reference (``reference_decoder.py``: float32 at ``HIGHEST``, no cache) run
over each one's prompt followed by the tokens served: at each of the served
tokens' positions the reference's logits ``z`` over the vocabulary, their
spread ``std(z)``, and

``served_logit_gap``  ``|c - z[t]| / std(z)``: the program's own logit ``c`` of
    the token ``t`` it served (float32, from the timed call) against the
    reference's logit of that token;
``greedy_gap``        ``(max(z) - z[t]) / std(z)``: how far under the reference's
    best the served token lies (with random weights the best and the next lie
    close, so a sound program may serve another token than the reference's:
    logits are compared, not tokens),

each as a mean against a limit between the program's largest sound reading
and the control's smallest (``control_decoder.py``, float8 operands in the
experts' products; ``PERF.md``, "How correct is decided"):
``served_logit_gap`` over prefill's first token (``.prefill``) and over the
decode steps (``.decode``) apart; ``greedy_gap`` over all the served tokens
together, because it is zero at most steps and a few hundredths at the rest,
and the mean of 16 first tokens alone swings from 0 to what the control
reads (0 to 0.0017 against 0.0095 to 0.0245 on the chip; PERF.md).
``served_logit_steps_off`` counts sampled steps whose ``served_logit_gap``
is over ``served_logit_step_limit``, a loose limit of a step's own: a mean
passes a fault in a few steps — one slot of the cache, one row of a batch.
"""

from __future__ import annotations

import sys

import numpy as np

import check
import reference
import reference_decoder as refdec

SAMPLE_ANSWERS = 16


def served_tokens(result) -> list[int] | None:
    """The ids in an answer as the program's tokenizer prints them
    (``<id> <id> ...``), or ``None`` where it is no such string."""
    if not isinstance(result, str):
        return None
    try:
        return [int(part[1:-1]) for part in result.split()]
    except ValueError:
        return None


def program_prompts(cell, seed: int, schedule, obs, prefilled: int) -> dict[int, str]:
    """For each answered query the prompt its reply implies: the template
    over the texts of the ids the index returned, in rank order."""
    texts = schedule.documents.texts
    doc_of_key = {key: i for i, key in enumerate(obs.evidence["doc_key"]) if key is not None}
    out = {}
    questions = schedule.queries.texts
    for i in np.flatnonzero(~np.isnan(obs.queries.ack)):  # the primer too: the chat answered it
        chunks = []
        for key in obs.evidence["query_ids"][i]:
            slot = int(key) - reference.PREFILL_KEY_BASE
            if key in doc_of_key:
                n = doc_of_key[key]
                chunks.append(texts[n] if n < len(texts) else texts[0])  # the primer resends the first
            elif 0 <= slot < prefilled:
                chunks.append(refdec.chunk_text(seed, slot, cell.mix))
        out[int(i)] = refdec.build_prompt(questions[i] if i < len(questions) else questions[0], chunks)
    return out


def sample_sequences(cell, seed: int, obs, prompts: dict[int, str]):
    """The sampled queries with, for each, the reference's own ids of its
    prompt (cut as the configuration says), the tokens served and the
    program's logits of them."""
    chat = cell.config["chat"]
    generations = obs.evidence["generations"]
    ids = np.array(sorted(i for i, p in prompts.items() if generations.get(p)), np.int64)
    lengths = np.array([len(prompts[i]) for i in ids])
    done = np.ones(len(ids), bool)
    picked = ids[check.draw_sample(seed, done, lengths, SAMPLE_ANSWERS)] if len(ids) else ids
    out = []
    for i in picked:
        tokens, logits = generations[prompts[i]][0]
        prompt_ids, cut = refdec.prompt_ids(
            prompts[i], cell.config["vocab_size"], chat["max_prompt_len"], chat["keep_tail"]
        )
        out.append({"query": int(i), "prompt": prompt_ids, "cut": cut, "tokens": [int(t) for t in tokens],
                    "logits": np.asarray(logits, np.float64)})
    return out


def gaps(reference_logits: list[np.ndarray], sample: list[dict], stand_in=None) -> dict:
    """Per sampled query and step the two gaps. ``stand_in`` — per query
    logits ``[new, vocab]`` of a control — takes the program's place: its
    logit of the served token for ``served_logit_gap``, its own best token
    for ``greedy_gap``."""
    served, greedy = [], []
    for n, (z, item) in enumerate(zip(reference_logits, sample)):
        z = z.astype(np.float64)
        steps = np.arange(len(item["tokens"]))
        spread = z.std(-1)
        tokens = np.asarray(item["tokens"])
        if stand_in is None:
            own, chosen = item["logits"], tokens
        else:
            low = stand_in[n].astype(np.float64)
            own, chosen = low[steps, tokens], low.argmax(-1)
        served.append(np.abs(own - z[steps, tokens]) / spread)
        greedy.append((z.max(-1) - z[steps, chosen]) / spread)
    return {"served": np.stack(served), "greedy": np.stack(greedy)}


def compare(cell, seed: int, *, schedule, obs, facts: dict, stand_in=None, memo=None) -> list[dict]:
    limits, chat = cell.limits, cell.config["chat"]
    memo = {} if memo is None else memo
    numbers: list[dict] = []

    def exact(name: str, value) -> None:
        numbers.append({"name": name, "value": int(value), "limit": 0, "ok": int(value) == 0})

    def within(name: str, value: float) -> None:
        limit = limits.get(name)
        ok = limit is not None and bool(np.isfinite(value)) and value <= limit
        numbers.append({"name": name, "value": float(value), "limit": limit, "ok": ok})

    generations = obs.evidence["generations"]
    if "prompts" not in memo:
        memo["prompts"] = program_prompts(cell, seed, schedule, obs, facts["prefilled"])
    prompts = memo["prompts"]
    lost = tokens_off = unsound = 0
    asked: dict[str, int] = {}
    for i, prompt in prompts.items():
        asked[prompt] = asked.get(prompt, 0) + 1
        answer = served_tokens(obs.evidence["results"][i])
        made = generations.get(prompt)
        if not made:
            unsound += 1
            lost += 1
            continue
        if answer is None:
            lost += 1
        elif len(made[0][0]) != chat["max_new_tokens"] or answer != [int(t) for t in made[0][0] if t > 3]:
            # the program's tokenizer prints no id under 4 (padding and the special ids)
            tokens_off += 1
    exact("answers_lost", lost)
    exact("answers_repeated", sum(max(0, len(made) - asked.get(p, 0)) for p, made in generations.items() if asked.get(p)))
    exact("answer_tokens_off", tokens_off)
    exact("context_unsound", unsound)
    if "sample" not in memo:
        memo["sample"] = sample_sequences(cell, seed, obs, prompts)
    sample = memo["sample"]
    if sample and "logits" not in memo:
        memo["logits"] = refdec.served_logits(
            facts["decoder_params"], [(s["prompt"], s["tokens"]) for s in sample], cell.config,
            chat["max_prompt_len"] + chat["max_new_tokens"],
        )
    if sample:
        got = gaps(memo["logits"], sample, stand_in)
        served, greedy = got["served"], got["greedy"]
        step_limit = limits.get("served_logit_step_limit", 0.0)
        print(
            f"answers compared: {len(sample)} ({sum(s['cut'] for s in sample)} cut), prompts of "
            f"{min(len(s['prompt']) for s in sample)}-{max(len(s['prompt']) for s in sample)} tokens; "
            f"prompts_truncated {facts.get('prompts_truncated')}; served_logit_gap mean and widest: prefill "
            f"{served[:, 0].mean():.5f} {served[:, 0].max():.5f}, decode {served[:, 1:].mean():.5f} "
            f"{served[:, 1:].max():.5f} (a step's limit {step_limit}); greedy_gap of the first tokens "
            f"{greedy[:, 0].mean():.5f}, of the rest {greedy[:, 1:].mean():.5f}, widest {greedy.max():.5f}; served tokens that are the reference's best: {np.mean(greedy == 0):.3f}",
            file=sys.stderr,
        )
        within("served_logit_gap.prefill", served[:, 0].mean())
        within("served_logit_gap.decode", served[:, 1:].mean())
        within("greedy_gap", greedy.mean())
        exact("served_logit_steps_off", np.count_nonzero(~(served <= step_limit)))
    else:
        for name in ("served_logit_gap.prefill", "served_logit_gap.decode", "greedy_gap"):
            within(name, float("inf"))
    return numbers
