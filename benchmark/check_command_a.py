"""The answerer's part of the comparison that decides ``correct`` where the
chat model is ``cohere2_moe`` (``pipelines/rag_answerer_command_a.py`` runs
``check.compare`` for the live index's numbers, as they are, and then this).

The numbers, their names and their arithmetic are ``check_decoder.py``'s, and
its code computes them: the exact counts (``answers_lost``,
``answers_repeated``, ``answer_tokens_off``, ``context_unsound``,
``served_logit_steps_off``) and, over ``check_decoder.SAMPLE_ANSWERS``
finished queries drawn from the seed, ``served_logit_gap.prefill``,
``.decode`` and ``greedy_gap`` of what the timed calls served. What is this
file's is the reference they are taken against:
``reference_command_a.served_logits`` over each sampled prompt followed by
the tokens served, under the configuration's own share of the experts
(``held_here``), float32 at ``highest``, no cache. The limits lie between
the program's widest sound reading and the control's smallest
(``control_command_a.py``: float8 operands in the experts' products;
``limits/command-a-plus-rag-answer.json`` has each with its reason).
"""

from __future__ import annotations

import check_decoder
import reference_command_a as refcmd


def reference_logits(cell, facts: dict, sample: list[dict], operand=None) -> list:
    chat = cell.config["chat"]
    return refcmd.served_logits(
        facts["decoder_params"], [(s["prompt"], s["tokens"]) for s in sample], cell.config,
        chat["max_prompt_len"] + chat["max_new_tokens"], operand=operand,
    )


def compare(cell, seed: int, *, schedule, obs, facts: dict, stand_in=None, memo=None) -> list[dict]:
    """``check_decoder.compare`` with this model's reference in the memo it
    would otherwise fill from ``reference_decoder``."""
    memo = {} if memo is None else memo
    if "logits" not in memo:
        memo["prompts"] = check_decoder.program_prompts(cell, seed, schedule, obs, facts["prefilled"])
        memo["sample"] = check_decoder.sample_sequences(cell, seed, obs, memo["prompts"])
        memo["logits"] = reference_logits(cell, facts, memo["sample"])
    return check_decoder.compare(cell, seed, schedule=schedule, obs=obs, facts=facts, stand_in=stand_in, memo=memo)
