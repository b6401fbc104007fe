"""Operations and bytes of the chat programs under a ``cohere2_moe``
configuration (``configs/command-a-plus-rag-answerer.json``), as functions
of what a call held and of the configuration's published keys. The
yardstick's: a PR that changes the program does not change what its work is
counted as.

Counted is **what the call was for**: the real rows and their real tokens,
and of their (token, choice) pairs those an expert held here took, as the
call itself counted them. A padding row, a padding token and a pair whose
expert lies on another chip count nothing, whatever the program spends on
them, so a share of a peak computed from these reads low on a call that is
mostly padding and never over what the chip can do.

Matrix products only (2 operations a multiply-add): norms, softmax, SiLU,
the rotary turn, the router's top-k, the sort of the routed rows and the
embedding lookups are left out. Attention is counted as the model needs it:
a token against the tokens before it and itself, in a sliding layer at most
``sliding_window`` of them. Parameters are bfloat16 (2 bytes).
"""

from __future__ import annotations

from reference_command_a import router_width

PARAM_BYTES = 2


def attention_params(dec: dict) -> int:
    """``W_q``, ``W_k``, ``W_v``, ``W_o`` of one layer."""
    h, d = dec["hidden_size"], dec["head_dim"]
    return 2 * h * dec["num_attention_heads"] * d + 2 * h * dec["num_key_value_heads"] * d


def expert_params(dec: dict) -> int:
    """One expert, routed or shared: gate, up and down."""
    return 3 * dec["hidden_size"] * dec["intermediate_size"]


def shared_params(dec: dict) -> int:
    return dec["num_shared_experts"] * expert_params(dec)


def router_params(dec: dict) -> int:
    """The router is as wide as all the experts of the layer, held or not."""
    return dec["hidden_size"] * router_width(dec)


def head_params(dec: dict) -> int:
    """The tied embedding's rows held here, read whole as the head."""
    return dec["hidden_size"] * dec["vocab_size"]


def layer_kinds(dec: dict) -> list[str]:
    return list(dec["layer_types"][: dec["num_hidden_layers"]])


def token_matmul_params(dec: dict) -> int:
    """Parameters every token is multiplied with outside attention's scores,
    the routed experts and the head: each layer's attention matrices, its
    router and its shared experts."""
    return dec["num_hidden_layers"] * (attention_params(dec) + router_params(dec) + shared_params(dec))


def resident_step_params(dec: dict) -> int:
    """Parameters every step reads whatever the routing."""
    return token_matmul_params(dec) + head_params(dec)


def decoder_params(dec: dict) -> int:
    """Every matrix the chip holds: the resident ones and its routed experts."""
    return resident_step_params(dec) + dec["num_hidden_layers"] * dec["num_experts"] * expert_params(dec)


def keys_seen(kind: str, context: int, dec: dict) -> int:
    """Keys the token at the end of ``context`` tokens attends to, itself included."""
    return min(context, dec["sliding_window"]) if kind == "sliding_attention" else context


def attention_pairs(tokens: int, dec: dict) -> int:
    """(query, key) pairs of a prompt of ``tokens`` tokens over all layers:
    ``1 + 2 + ... + tokens`` in a layer, a sliding layer's terms capped at the
    window."""
    pairs = 0
    for kind in layer_kinds(dec):
        full = min(tokens, keys_seen(kind, tokens, dec))  # the tokens that see everything before them
        pairs += full * (full + 1) // 2 + (tokens - full) * full
    return pairs


def decode_keys_seen(prompt_tokens, steps: int, dec: dict) -> int:
    """Keys the generated tokens attend to over all layers: step ``j`` of a row
    of ``n`` prompt tokens against ``n + j`` filled positions."""
    return sum(keys_seen(kind, n + j, dec) for kind in layer_kinds(dec) for n in prompt_tokens for j in range(1, steps + 1))


def _pair_flops(dec: dict) -> int:
    """Scores and weighted values of one (query, key) pair over every head."""
    return 4 * dec["num_attention_heads"] * dec["head_dim"]


def routed_flops(pairs_held: int, dec: dict) -> int:
    """The routed experts' products for the pairs an expert held here took."""
    return 2 * pairs_held * expert_params(dec)


def prefill_flops(prompt_tokens, dec: dict, pairs_held: int) -> int:
    """Prompts of ``prompt_tokens`` real tokens each into an empty cache, the
    head at one position a prompt."""
    rows = len(prompt_tokens)
    return (
        2 * sum(prompt_tokens) * token_matmul_params(dec)
        + _pair_flops(dec) * sum(attention_pairs(n, dec) for n in prompt_tokens)
        + 2 * rows * head_params(dec)
        + routed_flops(pairs_held, dec)
    )


def decode_flops(prompt_tokens, steps: int, dec: dict, pairs_held: int) -> int:
    """``steps`` further tokens a real row, step ``j`` against ``n + j`` filled
    positions; the head at every row and step."""
    rows = len(prompt_tokens)
    return (
        2 * rows * steps * (token_matmul_params(dec) + head_params(dec))
        + _pair_flops(dec) * decode_keys_seen(prompt_tokens, steps, dec)
        + routed_flops(pairs_held, dec)
    )


def cache_token_bytes(dec: dict) -> int:
    """One token's key and value in one layer."""
    return 2 * dec["num_key_value_heads"] * dec["head_dim"] * PARAM_BYTES


def cache_bytes(rows: int, max_len: int, dec: dict) -> int:
    """What a cache of ``max_len`` positions holds for ``rows`` rows: a full
    layer every position, a sliding layer at most its window."""
    return rows * cache_token_bytes(dec) * sum(keys_seen(kind, max_len, dec) for kind in layer_kinds(dec))


def prefill_bytes(prompt_tokens, dec: dict, experts_touched: int) -> int:
    """The least a prefill must move: every resident parameter once, each
    touched routed expert's once (``experts_touched`` is summed over the
    layers), the real tokens' ids in, their embedding rows, their keys and
    values written, the last positions' logits out (float32)."""
    tokens, rows = sum(prompt_tokens), len(prompt_tokens)
    return (
        PARAM_BYTES * (resident_step_params(dec) + experts_touched * expert_params(dec))
        + tokens * (4 + PARAM_BYTES * dec["hidden_size"] + dec["num_hidden_layers"] * cache_token_bytes(dec))
        + 4 * rows * dec["vocab_size"]
    )


def decode_bytes(prompt_tokens, steps: int, dec: dict, experts_touched: int) -> int:
    """The least a decode loop of ``steps`` steps must move: every resident
    parameter a step, the routed experts a real row chose (``experts_touched``
    is summed over steps and layers), the keys and values each real row's
    token sees, its own written, the logits (float32) out."""
    rows = len(prompt_tokens)
    return (
        PARAM_BYTES * (steps * resident_step_params(dec) + experts_touched * expert_params(dec))
        + cache_token_bytes(dec) * (decode_keys_seen(prompt_tokens, steps, dec) + rows * steps * dec["num_hidden_layers"])
        + rows * steps * (PARAM_BYTES * dec["hidden_size"] + 4 * dec["vocab_size"])
    )
