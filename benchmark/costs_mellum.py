"""Operations and bytes of the chat programs under a ``mellum``
configuration (``configs/mellum2-12b-a2.5b-rag-answerer.json``), as
functions of what a call held and of the configuration's published keys.
The yardstick's: a PR that changes the program does not change what its
work is counted as.

Counted is **what the call was for**, as ``costs_lfm2.py`` counts: the real
rows and their real tokens, and their (token, choice) pairs as the call
itself counted them (every expert is held here, so that is every real
token's eight in each layer). A padding row and a padding token count
nothing, whatever the program spends on them, so a share of a peak computed
from these reads low on a call that is mostly padding and never over what
the chip can do.

Matrix products (2 operations a multiply-add); norms, softmax, SiLU, the
gates' products, the rotary turn, the router's top-k, the sort of the routed
rows and the embedding lookups are left out. Attention is counted as the
model needs it: in a sliding layer a token against the ``min(i + 1,
sliding_window)`` positions its window holds (``window_pairs``), in the full
layer against every position before it and itself. A decode step reads a
ring at ``min(position, sliding_window)`` slots and the full layer's cache at
every filled position. Parameters and the cache are bfloat16 (2 bytes).
"""

from __future__ import annotations

from reference_mellum import head_dim, layer_kinds

PARAM_BYTES = 2


def attention_params(dec: dict) -> int:
    """``W_q``, ``W_k``, ``W_v``, ``W_o`` of one layer."""
    h, d = dec["hidden_size"], head_dim(dec)
    return 2 * h * dec["num_attention_heads"] * d + 2 * h * dec["num_key_value_heads"] * d


def expert_params(dec: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * dec["hidden_size"] * dec["moe_intermediate_size"]


def router_params(dec: dict) -> int:
    return dec["hidden_size"] * dec["num_experts"]


def head_params(dec: dict) -> int:
    """``lm_head`` (the embedding, untied, is as large)."""
    return dec["hidden_size"] * dec["vocab_size"]


def layer_counts(dec: dict) -> dict:
    kinds = layer_kinds(dec)
    return {"sliding": kinds.count("sliding"), "full": kinds.count("full"), "layers": len(kinds)}


def token_matmul_params(dec: dict) -> int:
    """Parameters every token is multiplied with outside attention's scores,
    the routed experts and the head: each layer's attention projections and
    router."""
    return layer_counts(dec)["layers"] * (attention_params(dec) + router_params(dec))


def resident_step_params(dec: dict) -> int:
    """Parameters every step reads whatever the routing."""
    return token_matmul_params(dec) + head_params(dec)


def decoder_params(dec: dict) -> int:
    """Every parameter the chip holds: the embedding, the head, each layer's
    projections, router, routed experts and two norms, the final norm."""
    n, h = layer_counts(dec)["layers"], dec["hidden_size"]
    return (
        2 * head_params(dec) + token_matmul_params(dec)
        + n * (dec["num_experts"] * expert_params(dec) + 2 * h) + h
    )


def window_pairs(tokens: int, window: int) -> int:
    """(query, key) pairs a sliding layer needs over a sequence of ``tokens``:
    ``sum over i < tokens of min(i + 1, window)``."""
    inside = min(tokens, window)
    return inside * (inside + 1) // 2 + (tokens - inside) * window


def attention_pairs(tokens: int, dec: dict) -> int:
    """(query, key) pairs of a prompt of ``tokens`` tokens over the layers:
    each sliding layer's window, the full layers' causal triangle."""
    n = layer_counts(dec)
    return n["sliding"] * window_pairs(tokens, dec["sliding_window"]) + n["full"] * tokens * (tokens + 1) // 2


def decode_keys_seen(prompt_tokens, steps: int, dec: dict) -> int:
    """Keys the generated tokens attend to over the layers: step ``j`` of a
    row of ``n`` prompt tokens sees ``n + j`` positions, of them a sliding
    layer the last ``sliding_window``."""
    n, window = layer_counts(dec), dec["sliding_window"]
    return sum(
        n["sliding"] * min(p + j, window) + n["full"] * (p + j)
        for p in prompt_tokens for j in range(1, steps + 1)
    )


def _pair_flops(dec: dict) -> int:
    """Scores and weighted values of one (query, key) pair over every head."""
    return 4 * dec["num_attention_heads"] * head_dim(dec)


def routed_flops(pairs: int, dec: dict) -> int:
    """The routed experts' products for the (token, choice) pairs counted."""
    return 2 * pairs * expert_params(dec)


def prefill_flops(prompt_tokens, dec: dict, pairs: int) -> int:
    """Prompts of ``prompt_tokens`` real tokens each into an empty cache, the
    head at one position a prompt."""
    return (
        2 * sum(prompt_tokens) * token_matmul_params(dec)
        + _pair_flops(dec) * sum(attention_pairs(n, dec) for n in prompt_tokens)
        + 2 * len(prompt_tokens) * head_params(dec)
        + routed_flops(pairs, dec)
    )


def decode_flops(prompt_tokens, steps: int, dec: dict, pairs: int) -> int:
    """``steps`` further tokens a real row, the head at every row and step."""
    rows = len(prompt_tokens)
    return (
        2 * rows * steps * (token_matmul_params(dec) + head_params(dec))
        + _pair_flops(dec) * decode_keys_seen(prompt_tokens, steps, dec)
        + routed_flops(pairs, dec)
    )


def cache_token_bytes(dec: dict) -> int:
    """One position's key and value in one layer."""
    return 2 * dec["num_key_value_heads"] * head_dim(dec) * PARAM_BYTES


def cache_bytes(rows: int, max_len: int, dec: dict) -> int:
    """What a cache of ``max_len`` positions holds for ``rows`` rows: a ring
    of ``min(sliding_window, max_len)`` slots in each sliding layer, every
    position in the full layers."""
    n = layer_counts(dec)
    return rows * cache_token_bytes(dec) * (n["sliding"] * min(dec["sliding_window"], max_len) + n["full"] * max_len)


def prefill_bytes(prompt_tokens, dec: dict, experts_touched: int) -> int:
    """The least a prefill must move: every resident parameter once, each
    touched routed expert's once (``experts_touched`` is summed over the
    layers), the real tokens' ids in and their embedding rows, the keys and
    values the cache keeps of them (a ring the last ``sliding_window``), the
    last positions' logits out (float32)."""
    n, window = layer_counts(dec), dec["sliding_window"]
    kept = sum(n["sliding"] * min(p, window) + n["full"] * p for p in prompt_tokens)
    return (
        PARAM_BYTES * (resident_step_params(dec) + experts_touched * expert_params(dec))
        + sum(prompt_tokens) * (4 + PARAM_BYTES * dec["hidden_size"])
        + kept * cache_token_bytes(dec)
        + 4 * len(prompt_tokens) * dec["vocab_size"]
    )


def decode_bytes(prompt_tokens, steps: int, dec: dict, experts_touched: int) -> int:
    """The least a decode loop of ``steps`` steps must move: every resident
    parameter a step, the routed experts a real row chose
    (``experts_touched`` is summed over steps and layers), the keys and
    values each real row's token sees and its own written in every layer, its
    embedding row and the logits (float32) out."""
    rows, n = len(prompt_tokens), layer_counts(dec)
    return (
        PARAM_BYTES * (steps * resident_step_params(dec) + experts_touched * expert_params(dec))
        + cache_token_bytes(dec) * (decode_keys_seen(prompt_tokens, steps, dec) + rows * steps * n["layers"])
        + rows * steps * (PARAM_BYTES * dec["hidden_size"] + 4 * dec["vocab_size"])
    )
