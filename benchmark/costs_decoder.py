"""Operations and bytes of the answerer's decoder programs, as functions of
their shapes and of the configuration's published keys. The yardstick's: a
PR that changes the program does not change what its work is counted as.

Matrix products only (2 operations a multiply-add): norms, softmax, SiLU,
the rotary turn, the router's top-k, the sort of the routed rows and the
embedding lookups are left out, so a share of a peak computed from these
reads a little low and never high. Attention is counted as a causal model
needs it: a token against the tokens before it and itself, not against a
padded square. Parameters are bfloat16 (2 bytes), as the configuration
states.
"""

from __future__ import annotations

PARAM_BYTES = 2


def attention_params(dec: dict) -> int:
    """``W_q``, ``W_kva``, ``W_kvb``, ``W_o`` of one latent-attention layer."""
    h, heads = dec["hidden_size"], dec["num_attention_heads"]
    nope, rot, vd, rank = dec["qk_nope_head_dim"], dec["qk_rope_head_dim"], dec["v_head_dim"], dec["kv_lora_rank"]
    return h * heads * (nope + rot) + h * (rank + rot) + rank * heads * (nope + vd) + heads * vd * h


def dense_mlp_params(dec: dict) -> int:
    return 3 * dec["hidden_size"] * dec["intermediate_size"]


def expert_params(dec: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * dec["hidden_size"] * dec["moe_intermediate_size"]


def shared_params(dec: dict) -> int:
    return dec["n_shared_experts"] * expert_params(dec)


def router_params(dec: dict) -> int:
    return dec["hidden_size"] * dec["n_routed_experts"]


def head_params(dec: dict) -> int:
    return dec["hidden_size"] * dec["vocab_size"]


def layer_counts(dec: dict) -> tuple[int, int]:
    """(dense layers, expert layers) of the configuration as it is run."""
    dense = min(dec["first_k_dense_replace"], dec["num_hidden_layers"])
    return dense, dec["num_hidden_layers"] - dense


def token_matmul_params(dec: dict) -> int:
    """Parameters one token is multiplied with outside attention's scores and
    the head: every layer's four attention matrices, the dense layers' MLP,
    and in an expert layer the router, the shared experts and the
    ``num_experts_per_tok`` routed experts it chose."""
    dense, experts = layer_counts(dec)
    routed = dec["num_experts_per_tok"] * expert_params(dec)
    return (
        (dense + experts) * attention_params(dec)
        + dense * dense_mlp_params(dec)
        + experts * (router_params(dec) + shared_params(dec) + routed)
    )


def attention_flops(queries: int, keys: int, dec: dict, absorbed: bool) -> int:
    """Scores and weighted values of ``queries`` x ``keys`` (query, key)
    pairs in one layer. Expanded: 192-wide scores and 128-wide values a
    head. Absorbed (a decode step): both against the latent row, ``rank +
    rope`` and ``rank`` wide, plus the two absorptions of ``W_kvb`` a query
    (``heads x nope x rank`` and ``heads x rank x v``)."""
    heads = dec["num_attention_heads"]
    nope, rot, vd, rank = dec["qk_nope_head_dim"], dec["qk_rope_head_dim"], dec["v_head_dim"], dec["kv_lora_rank"]
    pairs = queries * keys
    if absorbed:
        return 2 * heads * (pairs * (2 * rank + rot) + queries * rank * (nope + vd))
    return 2 * heads * pairs * (nope + rot + vd)


def prefill_flops(batch: int, tokens: int, dec: dict) -> int:
    """``batch`` prompts of ``tokens`` tokens each into an empty cache, the
    head at one position a prompt. A token attends to itself and what came
    before: ``tokens (tokens + 1) / 2`` pairs a prompt a layer."""
    layers = dec["num_hidden_layers"]
    pairs = tokens * (tokens + 1) // 2
    return batch * (
        2 * tokens * token_matmul_params(dec)
        + layers * attention_flops(1, pairs, dec, absorbed=False)
        + 2 * head_params(dec)
    )


def decode_step_flops(batch: int, context: int, dec: dict) -> int:
    """One token a row, each against a cache of ``context`` filled slots
    (its own included), absorbed; the head at every row. In the absorbed
    form ``W_kvb`` is not a product over the token (its two halves are
    absorbed into the query and the output), so it is taken out of the
    token's parameters and counted by :func:`attention_flops`."""
    layers = dec["num_hidden_layers"]
    heads, rank = dec["num_attention_heads"], dec["kv_lora_rank"]
    kvb = rank * heads * (dec["qk_nope_head_dim"] + dec["v_head_dim"])
    return batch * (
        2 * (token_matmul_params(dec) - layers * kvb)
        + layers * attention_flops(1, context, dec, absorbed=True)
        + 2 * head_params(dec)
    )


def resident_step_params(dec: dict) -> int:
    """Parameters every step reads whatever the routing: attention, the dense
    MLP, routers, shared experts, the head."""
    dense, experts = layer_counts(dec)
    return (
        (dense + experts) * attention_params(dec)
        + dense * dense_mlp_params(dec)
        + experts * (router_params(dec) + shared_params(dec))
        + head_params(dec)
    )


def cache_row_bytes(dec: dict) -> int:
    """One token's rows in every layer's latent cache."""
    return dec["num_hidden_layers"] * (dec["kv_lora_rank"] + dec["qk_rope_head_dim"]) * PARAM_BYTES


def prefill_bytes(batch: int, tokens: int, dec: dict, experts_touched: int) -> int:
    """The least a prefill must move: every resident parameter once, each
    touched routed expert's once (``experts_touched`` is summed over the
    expert layers), the ids in, the embedding rows looked up, the latent
    cache written, the last positions' logits out (float32)."""
    h = dec["hidden_size"]
    return (
        PARAM_BYTES * (resident_step_params(dec) + experts_touched * expert_params(dec))
        + batch * tokens * (4 + PARAM_BYTES * h + cache_row_bytes(dec))
        + 4 * batch * dec["vocab_size"]
    )


def decode_step_bytes(batch: int, context: int, dec: dict, experts_touched: int) -> int:
    """The least one decode step must move: every resident parameter, the
    routed experts that any row chose (summed over the expert layers: a
    padding row's too, its weights are read), the filled slots of the latent
    cache once, the new row written, the logits (float32) out."""
    return (
        PARAM_BYTES * (resident_step_params(dec) + experts_touched * expert_params(dec))
        + batch * (context + 1) * cache_row_bytes(dec)
        + batch * PARAM_BYTES * dec["hidden_size"]
        + 4 * batch * dec["vocab_size"]
    )
