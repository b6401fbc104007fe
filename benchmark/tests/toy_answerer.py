"""A toy answerer cell that lives only in the tests: ``toy.py``'s live index,
the decoder's layer pattern at widths a test can hold (the published keys'
names, so the same pipeline, reference and costs read them), prompts of
three chunks, the longest of them cut."""

from __future__ import annotations

import copy
import time

import harness
import toy

DECODER = {
    "model_type": "deepseek_v2", "vocab_size": 512, "hidden_size": 64, "num_hidden_layers": 3,
    "num_attention_heads": 4, "num_key_value_heads": 4, "intermediate_size": 160, "kv_lora_rank": 16,
    "q_lora_rank": None, "qk_nope_head_dim": 8, "qk_rope_head_dim": 4, "v_head_dim": 8, "n_routed_experts": 8,
    "num_experts_per_tok": 2, "n_shared_experts": 1, "moe_intermediate_size": 32, "first_k_dense_replace": 1,
    "norm_topk_prob": False, "routed_scaling_factor": 1, "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "max_position_embeddings": 4096,
    "rope_scaling": {"type": "yarn", "factor": 40, "original_max_position_embeddings": 128, "beta_fast": 32,
                     "beta_slow": 1, "mscale": 0.707, "mscale_all_dim": 0.707},
    "decoder_compute_dtype": "bfloat16",
}
CHAT = {"max_batch_size": 4, "max_new_tokens": 8, "max_prompt_len": 80, "prompt_buckets": [64, 80],
        "keep_tail": 24, "prompt_template": "prompts.prompt_qa"}
#: CPU readings at this size (seeds 2**31 + 7 .. + 12): the program's means
#: 0.010-0.022, its widest step 0.09; the float8 control's means 0.06-0.12
LIMITS = {"served_logit_gap.prefill": 0.04, "served_logit_gap.decode": 0.04, "greedy_gap": 0.04,
          "served_logit_step_limit": 0.6}


def cell() -> harness.Cell:
    base = toy.cell("rag")
    config = {**base.config, **copy.deepcopy(DECODER), "chat": dict(CHAT), "pipeline": "rag_answerer"}
    config["index"] = {**config["index"], "k": 3}
    mix = copy.deepcopy(toy.MIXES["rag"])
    mix["documents"]["tokens"] = {"dist": "lognormal", "median": 12.0, "sigma": 0.3, "min": 8, "max": 16}
    mix["queries"].update(rate_per_s=8, burst=None)
    mix["queries"].pop("burst")
    return harness.Cell(
        "toy-answer", 1, config, mix, {**toy.LIMITS, **LIMITS}, base.end_to_end, [],
        harness.find_pipeline("rag_answerer"),
    )


def run(seed: int = 2**31 + 7, seconds: float = 2.0, trace: bool = False, cell_=None):
    import jax

    return harness.run_cell(cell_ or cell(), seed, seconds, trace, jax.devices(), time.time())
