"""A toy cell of the ``lfm2_moe`` answerer that lives only in the tests:
``toy_answerer.py``'s live index, traffic and chat sizes, the decoder's
layer pattern (conv over dense, then attention, conv, conv, conv over routed
experts) at widths a test can hold under the published keys' names, so the
same pipeline, reference and costs read them. ``expert_bias_std`` is wide
enough here to move one choice in three among 8 experts, and to show in the
weights if it were added to them. The gaps' limits are this toy's own: with
no shared expert and two of 8 experts a token, one near tie of the router
that bfloat16 moves to another expert changes half a token's feed-forward,
so the sound readings lie higher than ``toy_answerer``'s."""

from __future__ import annotations

import copy
import time

import harness
import toy_answerer

DECODER = {
    "model_type": "lfm2_moe", "vocab_size": 512, "hidden_size": 64, "num_hidden_layers": 5,
    "num_attention_heads": 4, "num_key_value_heads": 2, "intermediate_size": 160, "moe_intermediate_size": 32,
    "num_experts": 8, "num_experts_per_tok": 2, "num_dense_layers": 1, "conv_L_cache": 3, "conv_bias": False,
    "layer_types": ["conv", "full_attention", "conv", "conv", "conv"], "norm_eps": 1e-5, "norm_topk_prob": True,
    "use_expert_bias": True, "routed_scaling_factor": 1, "max_position_embeddings": 4096,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "expert_bias_std": 0.15, "decoder_compute_dtype": "bfloat16",
}
#: CPU readings at this size (seeds 2**31 + 7 .. + 10 and 5): the program's
#: means 0.016-0.058 (prefill), 0.024-0.073 (decode), 0.002-0.027 (greedy), its
#: widest step 0.63, its median step 0.014-0.026 (the float8 control's 0.046-0.062); the planted
#: faults read over one of these or more
LIMITS = {"served_logit_gap.prefill": 0.12, "served_logit_gap.decode": 0.12, "greedy_gap": 0.06,
          "served_logit_gap.median": 0.035, "served_logit_step_limit": 1.5}


def cell() -> harness.Cell:
    base = toy_answerer.cell()
    config = {k: v for k, v in base.config.items() if k not in toy_answerer.DECODER}
    config.update(copy.deepcopy(DECODER), pipeline="rag_answerer_lfm2")
    return harness.Cell(
        "toy-answer-lfm2", 1, config, base.mix, {**base.limits, **LIMITS}, base.end_to_end, [],
        harness.find_pipeline("rag_answerer_lfm2"),
    )


def run(seed: int = 2**31 + 7, seconds: float = 2.0, trace: bool = False, cell_=None):
    import jax

    return harness.run_cell(cell_ or cell(), seed, seconds, trace, jax.devices(), time.time())
