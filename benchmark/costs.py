"""Operations and bytes of the programs the benchmark times, as functions of
their shapes. They are the yardstick's: a PR that changes the program does
not change what its work is counted as.

Matrix products only (2 operations a multiply-add); layer norms, softmax,
GELU, pooling and the embedding lookups are left out, as is usual for a
model's FLOP count, so every share of the peak computed from these reads a
little low and never high.
"""

from __future__ import annotations


def encoder_flops(tokens: int, enc: dict) -> int:
    """Forward FLOPs of one sequence of ``tokens`` tokens (real or padded)
    through the encoder ``enc`` (a configuration file's ``encoder`` group).

    Per layer: QKV ``2*t*h*3h``, scores and weighted values ``2*t*t*h``
    each, the output projection ``2*t*h*h``, the two FFN products
    ``2*t*h*f`` each."""
    h = enc["hidden_size"]
    f = enc["intermediate_size"]
    per_layer = tokens * (8 * h * h + 4 * h * f) + 4 * tokens * tokens * h
    return enc["num_hidden_layers"] * per_layer


def encoder_layer_param_count(enc: dict) -> int:
    """Matrix and bias parameters of the transformer layers (no embedding
    tables: a step reads only the rows it looks up)."""
    h = enc["hidden_size"]
    f = enc["intermediate_size"]
    per_layer = 4 * h * h + 2 * h * f + 9 * h + f
    return enc["num_hidden_layers"] * per_layer


def encoder_step_bytes(batch: int, seq: int, enc: dict) -> int:
    """The least a ``[batch, seq]`` embed step must move: the layers'
    float32 parameters once, the ids in, the looked-up embedding rows, and
    the pooled vectors out. Activations between layers are not counted (a
    fused step could keep them on the chip)."""
    h = enc["hidden_size"]
    return (
        4 * encoder_layer_param_count(enc)
        + 4 * batch * seq
        + 4 * batch * seq * h
        + 4 * batch * h
    )


def knn_search_flops(queries: int, capacity: int, dim: int) -> int:
    """One multiply-add per query, row and dimension (the six bf16 passes
    that make a float32 product at ``Precision.HIGHEST`` count once)."""
    return 2 * queries * capacity * dim


def knn_search_bytes(queries: int, capacity: int, dim: int) -> int:
    """What a brute-force scan must move: every float32 vector, the validity
    mask (1 byte) and the norm (4 bytes) of every row, and the
    ``[queries, capacity]`` float32 scores once (a ``top_k`` fused behind
    the product would read them where they are made)."""
    return 4 * capacity * dim + 5 * capacity + 4 * queries * capacity


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The least time the chip could take and which peak sets it."""
    t_flops = flops / peak["flops_per_s"]
    t_bytes = nbytes / peak["bytes_per_s"]
    if t_flops >= t_bytes:
        return t_flops, "compute"
    return t_bytes, "bandwidth"
