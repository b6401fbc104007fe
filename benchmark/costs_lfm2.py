"""Operations and bytes of the chat programs under an ``lfm2_moe``
configuration (``configs/lfm2-24b-a2b-rag-answerer.json``), as functions of
what a call held and of the configuration's published keys. The yardstick's:
a PR that changes the program does not change what its work is counted as.

Counted is **what the call was for**, as ``costs_command_a.py`` counts: the
real rows and their real tokens, and their (token, choice) pairs as the call
itself counted them (every expert is held here, so that is every real
token's four in each expert layer). A padding row and a padding token count
nothing, whatever the program spends on them, so a share of a peak computed
from these reads low on a call that is mostly padding and never over what
the chip can do.

Matrix products (2 operations a multiply-add) and the conv layers' filter
(``conv_L_cache`` multiply-adds a channel a token: it is the layer's own
operator, counted as what it is); norms, softmax, SiLU, the gates' products,
the rotary turn, the router's top-k, the sort of the routed rows and the
embedding lookups are left out. Attention is counted as the model needs it,
a token against the tokens before it and itself, in the attention layers
alone; a conv layer keeps ``conv_L_cache`` filter inputs a channel a row,
read and written whole at every step, whatever the positions. Parameters and
both kinds of state are bfloat16 (2 bytes).
"""

from __future__ import annotations

from reference_lfm2 import head_dim, layer_kinds

PARAM_BYTES = 2


def conv_params(dec: dict) -> int:
    """``in_proj`` (``B | C | u``) and ``out_proj`` of one conv operator."""
    return 4 * dec["hidden_size"] ** 2


def filter_flops_a_token(dec: dict) -> int:
    """One conv layer's depthwise filter over one token."""
    return 2 * dec["conv_L_cache"] * dec["hidden_size"]


def attention_params(dec: dict) -> int:
    """``W_q``, ``W_k``, ``W_v``, ``W_o`` of one attention operator."""
    h, d = dec["hidden_size"], head_dim(dec)
    return 2 * h * dec["num_attention_heads"] * d + 2 * h * dec["num_key_value_heads"] * d


def dense_params(dec: dict) -> int:
    return 3 * dec["hidden_size"] * dec["intermediate_size"]


def expert_params(dec: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * dec["hidden_size"] * dec["moe_intermediate_size"]


def router_params(dec: dict) -> int:
    return dec["hidden_size"] * dec["num_experts"]


def head_params(dec: dict) -> int:
    """The tied embedding, read whole as the head."""
    return dec["hidden_size"] * dec["vocab_size"]


def layer_counts(dec: dict) -> dict:
    kinds = layer_kinds(dec)
    return {
        "conv": sum(op == "conv" for op, _ in kinds), "attention": sum(op != "conv" for op, _ in kinds),
        "dense": sum(ff == "dense" for _, ff in kinds), "experts": sum(ff == "experts" for _, ff in kinds),
    }


def token_matmul_params(dec: dict) -> int:
    """Parameters every token is multiplied with outside attention's scores,
    the routed experts and the head: each layer's operator, the dense
    feed-forwards, the routers."""
    n = layer_counts(dec)
    return (
        n["conv"] * conv_params(dec) + n["attention"] * attention_params(dec)
        + n["dense"] * dense_params(dec) + n["experts"] * router_params(dec)
    )


def resident_step_params(dec: dict) -> int:
    """Parameters every step reads whatever the routing (the filters' few among them)."""
    return token_matmul_params(dec) + layer_counts(dec)["conv"] * dec["hidden_size"] * dec["conv_L_cache"] + head_params(dec)


def decoder_params(dec: dict) -> int:
    """Every matrix the chip holds: the resident ones and the routed experts."""
    return resident_step_params(dec) + layer_counts(dec)["experts"] * dec["num_experts"] * expert_params(dec)


def attention_pairs(tokens: int, dec: dict) -> int:
    """(query, key) pairs of a prompt of ``tokens`` tokens over the attention layers."""
    return layer_counts(dec)["attention"] * tokens * (tokens + 1) // 2


def decode_keys_seen(prompt_tokens, steps: int, dec: dict) -> int:
    """Keys the generated tokens attend to over the attention layers: step
    ``j`` of a row of ``n`` prompt tokens against ``n + j`` filled positions."""
    return layer_counts(dec)["attention"] * sum(n + j for n in prompt_tokens for j in range(1, steps + 1))


def _pair_flops(dec: dict) -> int:
    """Scores and weighted values of one (query, key) pair over every head."""
    return 4 * dec["num_attention_heads"] * head_dim(dec)


def _token_flops(dec: dict) -> int:
    """One token through everything but attention's scores, the routed experts and the head."""
    return 2 * token_matmul_params(dec) + layer_counts(dec)["conv"] * filter_flops_a_token(dec)


def routed_flops(pairs: int, dec: dict) -> int:
    """The routed experts' products for the (token, choice) pairs counted."""
    return 2 * pairs * expert_params(dec)


def prefill_flops(prompt_tokens, dec: dict, pairs: int) -> int:
    """Prompts of ``prompt_tokens`` real tokens each into an empty cache, the
    head at one position a prompt."""
    return (
        sum(prompt_tokens) * _token_flops(dec)
        + _pair_flops(dec) * sum(attention_pairs(n, dec) for n in prompt_tokens)
        + 2 * len(prompt_tokens) * head_params(dec)
        + routed_flops(pairs, dec)
    )


def decode_flops(prompt_tokens, steps: int, dec: dict, pairs: int) -> int:
    """``steps`` further tokens a real row, step ``j`` against ``n + j`` filled
    positions; the head at every row and step."""
    rows = len(prompt_tokens)
    return (
        rows * steps * (_token_flops(dec) + 2 * head_params(dec))
        + _pair_flops(dec) * decode_keys_seen(prompt_tokens, steps, dec)
        + routed_flops(pairs, dec)
    )


def cache_token_bytes(dec: dict) -> int:
    """One token's key and value in one attention layer."""
    return 2 * dec["num_key_value_heads"] * head_dim(dec) * PARAM_BYTES


def state_row_bytes(dec: dict) -> int:
    """One row's filter inputs in one conv layer: a size no position changes."""
    return dec["hidden_size"] * dec["conv_L_cache"] * PARAM_BYTES


def cache_bytes(rows: int, max_len: int, dec: dict) -> int:
    """What a cache of ``max_len`` positions holds for ``rows`` rows: every
    position in an attention layer, the filter's inputs in a conv layer."""
    n = layer_counts(dec)
    return rows * (n["attention"] * max_len * cache_token_bytes(dec) + n["conv"] * state_row_bytes(dec))


def prefill_bytes(prompt_tokens, dec: dict, experts_touched: int) -> int:
    """The least a prefill must move: every resident parameter once, each
    touched routed expert's once (``experts_touched`` is summed over the
    layers), the real tokens' ids in, their embedding rows, their keys and
    values written, each real row's filter state written, the last
    positions' logits out (float32)."""
    tokens, rows, n = sum(prompt_tokens), len(prompt_tokens), layer_counts(dec)
    return (
        PARAM_BYTES * (resident_step_params(dec) + experts_touched * expert_params(dec))
        + tokens * (4 + PARAM_BYTES * dec["hidden_size"] + n["attention"] * cache_token_bytes(dec))
        + rows * n["conv"] * state_row_bytes(dec)
        + 4 * rows * dec["vocab_size"]
    )


def decode_bytes(prompt_tokens, steps: int, dec: dict, experts_touched: int) -> int:
    """The least a decode loop of ``steps`` steps must move: every resident
    parameter a step, the routed experts a real row chose
    (``experts_touched`` is summed over steps and layers), the keys and
    values each real row's token sees, its own written, each real row's
    filter state read and written a step a conv layer, the logits (float32)
    out."""
    rows, n = len(prompt_tokens), layer_counts(dec)
    return (
        PARAM_BYTES * (steps * resident_step_params(dec) + experts_touched * expert_params(dec))
        + cache_token_bytes(dec) * (decode_keys_seen(prompt_tokens, steps, dec) + rows * steps * n["attention"])
        + rows * steps * n["conv"] * 2 * state_row_bytes(dec)
        + rows * steps * (PARAM_BYTES * dec["hidden_size"] + 4 * dec["vocab_size"])
    )
