"""A toy cell of the ``cohere2_moe`` answerer that lives only in the tests:
``toy_answerer.py``'s live index, traffic and chat sizes, the decoder's
layer pattern at widths a test can hold under the published keys' names (so
the same pipeline, reference and costs read them): a window of 8 under
prompts of some 70 tokens, so that prefill cuts to the ring and decode wraps
it, and a share of 2 of 8 experts. The limits are ``toy_answerer``'s: this
decoder's CPU readings (``test_rag_answerer_command_a.py``) lie under them as
that one's do."""

from __future__ import annotations

import copy
import time

import harness
import toy_answerer

DECODER = {
    "model_type": "cohere2_moe", "vocab_size": 512, "hidden_size": 64, "num_hidden_layers": 4,
    "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 32,
    "num_experts": 2, "num_experts_per_tok": 2, "num_shared_experts": 2, "sliding_window": 8,
    "layer_types": ["sliding_attention", "sliding_attention", "sliding_attention", "full_attention"],
    "layer_norm_eps": 1e-5, "rms_norm_eps": None, "rope_theta": 50000, "rotary_pct": 1, "logit_scale": 1,
    "norm_topk_prob": True, "expert_selection_fn": "sigmoid", "first_k_dense_replace": 0, "hidden_act": "silu",
    "attention_bias": False, "use_qk_norm": False, "use_parallel_block": True, "use_gated_activation": True,
    "shared_expert_combination_strategy": "average", "position_embedding_type": "rope_gptj",
    "order_of_interleaved_layers": "local_attn_first", "tie_word_embeddings": True, "max_position_embeddings": 4096,
    "held_here": {"experts": [2, 2], "of_experts": 8},
    "decoder_compute_dtype": "bfloat16",
}

def cell() -> harness.Cell:
    base = toy_answerer.cell()
    config = {k: v for k, v in base.config.items() if k not in toy_answerer.DECODER}
    config.update(copy.deepcopy(DECODER), pipeline="rag_answerer_command_a")
    return harness.Cell(
        "toy-answer-command-a", 1, config, base.mix, base.limits, base.end_to_end, [],
        harness.find_pipeline("rag_answerer_command_a"),
    )


def run(seed: int = 2**31 + 7, seconds: float = 2.0, trace: bool = False, cell_=None):
    import jax

    return harness.run_cell(cell_ or cell(), seed, seconds, trace, jax.devices(), time.time())
