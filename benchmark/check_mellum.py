"""The answerer's part of the comparison that decides ``correct`` where the
chat model is ``mellum`` (``pipelines/rag_answerer_mellum.py`` runs
``check.compare`` for the live index's numbers, as they are, and then this).

The numbers, their names and their arithmetic are ``check_lfm2.py``'s, and
its code and ``check_decoder.py``'s compute them: the exact counts
(``answers_lost``, ``answers_repeated``, ``answer_tokens_off``,
``context_unsound``, ``served_logit_steps_off``), over
``check_decoder.SAMPLE_ANSWERS`` finished queries drawn from the seed
``served_logit_gap.prefill``, ``.decode``, ``.median`` and ``greedy_gap`` of
what the timed calls served, one generation a query where a prompt was
asked and generated more than once (``check_lfm2.one_generation_a_query``:
this router, too, takes a share of a token's feed-forward from each of a few
experts with weights that sum to one and no shared expert, so a near tie
that bfloat16 moves changes a step, and a prompt generated twice under other
paddings may be served other tokens). What is this file's is the reference
they are taken against: ``reference_mellum.served_logits`` over each sampled
prompt followed by the tokens served, float32 at ``highest``, no cache, no
padding, the window an explicit mask over absolute positions: a row that the
program served from a left-padded batch, through rings that wrapped in
prefill and in decode, is held against its own sequence alone.

The limits (``limits/mellum2-rag-answer-long.json``, each with its reason)
lie between the program's widest sound reading and the controls' smallest:
``control_mellum.py`` puts the reference in the program's place with float8
operands in the experts' products, with the window's mask dropped, with the
full layer turned without YaRN, and without its attention factor.
"""

from __future__ import annotations

import check_decoder
import check_lfm2
import reference_mellum as refmel


def reference_logits(cell, facts: dict, sample: list[dict], operand=None, cut=None) -> list:
    chat = cell.config["chat"]
    return refmel.served_logits(
        facts["decoder_params"], [(s["prompt"], s["tokens"]) for s in sample], cell.config,
        chat["max_prompt_len"] + chat["max_new_tokens"], operand=operand, cut=cut,
    )


def compare(cell, seed: int, *, schedule, obs, facts: dict, stand_in=None, memo=None) -> list[dict]:
    """``check_lfm2.compare`` with this model's reference in the memo it
    would otherwise fill from ``reference_lfm2``."""
    memo = {} if memo is None else memo
    if "logits" not in memo:
        memo["prompts"] = check_decoder.program_prompts(cell, seed, schedule, obs, facts["prefilled"])
        memo["sample"] = check_decoder.sample_sequences(cell, seed, obs, memo["prompts"])
        memo["logits"] = reference_logits(cell, facts, memo["sample"])
    return check_lfm2.compare(cell, seed, schedule=schedule, obs=obs, facts=facts, stand_in=stand_in, memo=memo)
