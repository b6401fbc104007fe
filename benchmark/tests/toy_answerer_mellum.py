"""A toy cell of the ``mellum`` answerer that lives only in the tests:
``toy_answerer.py``'s live index, traffic and chat sizes, the decoder's
layer pattern (three sliding layers, then a full layer that turns by YaRN,
each over routed experts) at widths a test can hold under the published
keys' names, so the same pipeline, reference and costs read them: a window
of 8 under prompts of some 70 tokens, so that every prefill wraps its rings
and every decode step reads them wrapped, and YaRN over an original 64
positions, so that it turns a prompt's later positions otherwise than plain
RoPE. The gaps' limits are this toy's own: with two of 8 experts a token and
no shared expert, one near tie of the router that bfloat16 moves to another
expert changes half a token's feed-forward, so the sound readings lie higher
than ``toy_answerer``'s."""

from __future__ import annotations

import copy
import time

import harness
import toy_answerer

DECODER = {
    "model_type": "mellum", "vocab_size": 512, "hidden_size": 64, "num_hidden_layers": 4,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 160,
    "moe_intermediate_size": 32, "num_experts": 8, "num_experts_per_tok": 2, "norm_topk_prob": True,
    "layer_types": ["sliding_attention", "sliding_attention", "sliding_attention", "full_attention"],
    "mlp_layer_types": ["sparse"] * 4, "sliding_window": 8, "use_sliding_window": True, "max_window_layers": 0,
    "rms_norm_eps": 1e-6, "attention_bias": False, "hidden_act": "silu", "tie_word_embeddings": False,
    "max_position_embeddings": 256,
    "rope_parameters": {
        "full_attention": {"rope_type": "yarn", "rope_theta": 1000, "factor": 4, "original_max_position_embeddings": 64,
                           "beta_fast": 32, "beta_slow": 1, "attention_factor": 1.1386294361119891},
        "sliding_attention": {"rope_type": "default", "rope_theta": 1000},
    },
    "decoder_compute_dtype": "bfloat16",
}
#: CPU readings at this size (seeds 2**31 + 5, + 7 .. + 10): the program's
#: means 0.009-0.039 (prefill), 0.020-0.031 (decode), 0.003-0.012 (greedy),
#: its median step 0.009-0.012, its widest step 1.30; the controls' medians
#: 0.031-0.037 (attention factor left out), 0.051-0.069 (float8 experts),
#: 0.13-0.17 (no YaRN), 2.3-2.8 (no window): the median's limit is the one
#: every control fails
LIMITS = {"served_logit_gap.prefill": 0.08, "served_logit_gap.decode": 0.06, "greedy_gap": 0.03,
          "served_logit_gap.median": 0.02, "served_logit_step_limit": 2.0}


def cell() -> harness.Cell:
    base = toy_answerer.cell()
    config = {k: v for k, v in base.config.items() if k not in toy_answerer.DECODER}
    config.update(copy.deepcopy(DECODER), pipeline="rag_answerer_mellum")
    return harness.Cell(
        "toy-answer-mellum", 1, config, base.mix, {**base.limits, **LIMITS}, base.end_to_end, [],
        harness.find_pipeline("rag_answerer_mellum"),
    )


def run(seed: int = 2**31 + 7, seconds: float = 2.0, trace: bool = False, cell_=None):
    import jax

    return harness.run_cell(cell_ or cell(), seed, seconds, trace, jax.devices(), time.time())
