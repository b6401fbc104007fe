"""Routed experts: a router that scores every expert and a grouped product
over the experts tokens chose.

``route_top_k`` scores in float32 (the product at ``Precision.HIGHEST``: on
the chip a float32 product otherwise runs in one bfloat16 pass, and a near
tie would go to another expert than the one the published model takes) and
keeps the ``k`` largest, greedily, with their softmax weights as they are or
renormalised.

``routed_experts`` sorts the ``tokens * k`` (token, expert) pairs by expert
and runs each expert's gated MLP over its own rows with
``jax.lax.ragged_dot``: the work is that of the rows chosen, not of every
expert over every token, and an expert's weights are read only where it has
rows. There is no capacity: every pair is computed whatever the skew, all
tokens on one expert included. Padding takes no expert: given the mask of
real tokens, a padding token's pairs are sorted behind the last expert's
rows and belong to no group, so they are not computed, an expert that only
padding chose has no row and its weights are not read, and a padding
token's result is zero. The per-expert row counts of the real tokens come
back for the callers' counters (``expert_sizes``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def route_top_k(
    h: jax.Array,  # [n, hidden]
    gate_w: jax.Array,  # [hidden, experts]
    k: int,
    *,
    renormalize: bool = False,
    scale: float = 1.0,
) -> tuple[jax.Array, jax.Array]:
    """Softmax over all experts in float32, the ``k`` largest of it:
    weights ``[n, k]`` float32 and expert ids ``[n, k]`` int32. A tie goes to
    the expert with the lower id (``lax.top_k``)."""
    logits = jnp.matmul(
        h.astype(jnp.float32),
        gate_w.astype(jnp.float32),
        precision=lax.Precision.HIGHEST,
    )
    scores = jax.nn.softmax(logits, axis=-1)
    weights, experts = lax.top_k(scores, k)
    if renormalize:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    return weights * scale, experts.astype(jnp.int32)


def expert_sizes(experts: jax.Array, n_experts: int, counted: jax.Array | None = None) -> jax.Array:
    """How many of the (token, choice) pairs ``experts`` ``[n, k]`` name each
    expert, ``[n_experts]`` int32; of the ``counted`` ``[n]`` tokens alone
    where given. A compare-and-sum: a scatter-add into so few bins collides."""
    hit = experts[:, :, None] == jnp.arange(n_experts, dtype=experts.dtype)
    if counted is not None:
        hit = hit & counted[:, None, None]
    return hit.sum((0, 1), dtype=jnp.int32)


def routed_experts(
    h: jax.Array,  # [n, hidden]
    weights: jax.Array,  # [n, k] float32
    experts: jax.Array,  # [n, k] int32
    gate_up_w: jax.Array,  # [experts, hidden, 2 * width]: gate | up
    down_w: jax.Array,  # [experts, width, hidden]
    counted: jax.Array | None = None,  # [n] bool: False = a padding token
) -> tuple[jax.Array, jax.Array]:
    """``sum_i weights[:, i] * E_{experts[:, i]}(h)`` with ``E`` a gated SiLU
    MLP, ``[n, hidden]`` in ``h``'s dtype (float32 accumulation), and how
    many (token, choice) pairs each expert took, ``[experts]`` int32. Of the
    ``counted`` tokens alone where given: the others' pairs are in no
    expert's group and their rows of the result are zero."""
    n, k = experts.shape
    n_experts = gate_up_w.shape[0]
    # a padding token's pairs sort behind the last expert's rows, into no group
    keys = experts if counted is None else jnp.where(counted[:, None], experts, n_experts)
    order = jnp.argsort(keys.reshape(-1))  # stable: an expert's rows stay in token order
    token = order // k
    sizes = expert_sizes(experts, n_experts, counted)
    x = h[token]
    gate_up = lax.ragged_dot(x, gate_up_w.astype(h.dtype), sizes, preferred_element_type=jnp.float32)
    gate, up = jnp.split(gate_up, 2, axis=-1)
    act = (jax.nn.silu(gate) * up).astype(h.dtype)
    out = lax.ragged_dot(act, down_w.astype(h.dtype), sizes, preferred_element_type=jnp.float32)
    # back to (token, choice) order with a gather (the inverse permutation):
    # a scatter-add over the sorted rows costs the chip far more
    out = out[jnp.argsort(order)].reshape(n, k, -1)
    y = (out * weights[..., None]).sum(1)
    if counted is not None:
        # the rows past the groups hold whatever the device left there: the
        # mask decides a padding token's result, not those rows
        y = jnp.where(counted[:, None], y, 0.0)
    return y.astype(h.dtype), sizes
