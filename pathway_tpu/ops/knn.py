"""Fixed-capacity brute-force KNN resident in TPU HBM.

TPU-native replacement for the reference's CPU brute-force index
(reference: src/external_integration/brute_force_knn_integration.rs:70-113 —
dense matrix + norm loops) and the role usearch HNSW plays for as-of-now
retrieval. Design:

- The index is a *fixed-capacity slot array* ``[capacity, dim]`` with a
  validity mask — adds/removes are scatter updates into donated buffers, so
  mutation never reallocates or recompiles (static shapes; the host keeps the
  slot <-> key mapping).
- Search is one big masked matmul on the MXU followed by ``lax.top_k`` —
  exactly the shape XLA tiles best, and at ~1M x 384 it saturates HBM
  bandwidth rather than compute, which is the right regime for streaming
  ingest+query.
- Sharding: the capacity axis is laid out over the ``data`` mesh axis
  (see ``shard_state``); queries are replicated, local top-k per shard is
  merged with a second tiny top-k — the collective is an all-gather of
  ``[q, k]`` candidates over ICI, not the full score matrix.

Metrics match the reference's MetricKind subset: cosine, l2sq, dot
(usearch_integration.rs:20).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from pathway_tpu.parallel.mesh import DATA_AXIS, axis_size as mesh_axis_size

METRICS = ("cos", "l2sq", "dot")


class DeviceKnnState(NamedTuple):
    """Device-resident index state (a pytree; donate on update)."""

    vectors: jax.Array  # [capacity, dim]
    valid: jax.Array  # [capacity] bool
    norms: jax.Array  # [capacity] float32 — squared L2 norms, for l2sq


def knn_init(
    capacity: int,
    dim: int,
    dtype: jnp.dtype = jnp.float32,
    *,
    mesh: Mesh | None = None,
) -> DeviceKnnState:
    """Allocate an empty index; optionally sharded over the data axis."""
    state = DeviceKnnState(
        vectors=jnp.zeros((capacity, dim), dtype),
        valid=jnp.zeros((capacity,), jnp.bool_),
        norms=jnp.zeros((capacity,), jnp.float32),
    )
    if mesh is not None:
        state = shard_state(state, mesh)
    return state


def shard_state(state: DeviceKnnState, mesh: Mesh) -> DeviceKnnState:
    """Lay the capacity axis over the data mesh axis (HBM-sharded index)."""
    vec_sh = NamedSharding(mesh, P(DATA_AXIS, None))
    row_sh = NamedSharding(mesh, P(DATA_AXIS))
    return DeviceKnnState(
        vectors=jax.device_put(state.vectors, vec_sh),
        valid=jax.device_put(state.valid, row_sh),
        norms=jax.device_put(state.norms, row_sh),
    )


@functools.partial(jax.jit, donate_argnums=0)
def knn_update(
    state: DeviceKnnState,
    slots: jax.Array,  # [b] int32 — slot per row
    vectors: jax.Array,  # [b, dim]
    set_valid: jax.Array,  # [b] bool — True = insert, False = delete
    enabled: jax.Array,  # [b] bool — padding rows are disabled
) -> DeviceKnnState:
    """Scatter a batch of adds/removes into the slot array.

    The host allocator picks slots (free list) and pads batches to bucketed
    sizes; disabled rows scatter to slot ``capacity`` (dropped).

    Precondition: enabled slots must be unique within a batch — XLA scatter
    leaves the winner unspecified on duplicates. The host side (stdlib
    indexing) consolidates updates per key per commit, so a delete+reinsert
    of one key arrives as a single insert to a fresh slot.
    """
    capacity = state.vectors.shape[0]
    slots = jnp.where(enabled, slots, capacity)
    vecs = vectors.astype(state.vectors.dtype)
    new_vectors = state.vectors.at[slots].set(vecs, mode="drop")
    new_valid = state.valid.at[slots].set(set_valid, mode="drop")
    sq = jnp.sum(vectors.astype(jnp.float32) ** 2, axis=-1)
    new_norms = state.norms.at[slots].set(sq, mode="drop")
    return DeviceKnnState(new_vectors, new_valid, new_norms)


def _scores(
    state: DeviceKnnState, queries: jax.Array, metric: str
) -> jax.Array:
    """Higher-is-better scores ``[q, capacity]`` with invalid slots masked."""
    q = queries.astype(jnp.float32)
    db = state.vectors.astype(jnp.float32)
    # HIGHEST: TPU's default f32 matmul runs bf16 multiply passes, which
    # alone costs ~4% top-10 overlap vs exact host search; the score
    # matmul is tiny relative to embedding, so full precision is free
    dots = jnp.einsum(
        "qd,cd->qc", q, db, precision=lax.Precision.HIGHEST
    )
    if metric == "dot":
        scores = dots
    elif metric == "cos":
        qn = jnp.sqrt(jnp.sum(q * q, axis=-1, keepdims=True))
        dbn = jnp.sqrt(state.norms)[None, :]
        scores = dots / jnp.maximum(qn * dbn, 1e-30)
    elif metric == "l2sq":
        qn = jnp.sum(q * q, axis=-1, keepdims=True)
        scores = -(qn + state.norms[None, :] - 2.0 * dots)
    else:
        raise ValueError(f"unknown metric {metric!r}")
    return jnp.where(state.valid[None, :], scores, -jnp.inf)


@functools.partial(jax.jit, static_argnames=("k", "metric"))
def knn_search(
    state: DeviceKnnState,
    queries: jax.Array,  # [q, dim]
    k: int,
    metric: str = "cos",
) -> tuple[jax.Array, jax.Array]:
    """Top-k search. Returns (scores [q,k], slots [q,k]); empty hits have
    score ``-inf`` and slot ``capacity`` (host filters them)."""
    scores = _scores(state, queries, metric)
    top_scores, top_idx = lax.top_k(scores, k)
    capacity = state.vectors.shape[0]
    top_idx = jnp.where(jnp.isfinite(top_scores), top_idx, capacity)
    return top_scores, top_idx


@functools.partial(jax.jit, static_argnames=("k", "mesh", "metric"))
def knn_search_sharded(
    state: DeviceKnnState,
    queries: jax.Array,
    k: int,
    mesh: Mesh,
    metric: str = "cos",
) -> tuple[jax.Array, jax.Array]:
    """Sharded search: local top-k per capacity shard, then a merge top-k.

    Avoids materialising the global ``[q, capacity]`` score matrix across
    devices — only ``[q, k]`` candidates ride the ICI all-gather.
    """
    n = mesh_axis_size(mesh, DATA_AXIS)
    if n <= 1:
        return knn_search(state, queries, k, metric)
    cap_local = state.vectors.shape[0] // n
    # Per-shard candidate count can't exceed the shard's capacity.
    k_local = min(k, cap_local)

    def local(state_l: DeviceKnnState, q: jax.Array):
        scores = _scores(state_l, q, metric)
        s, i = lax.top_k(scores, k_local)
        shard = lax.axis_index(DATA_AXIS)
        i = i + shard * cap_local  # globalize slot ids
        s_all = lax.all_gather(s, DATA_AXIS, axis=1, tiled=True)
        i_all = lax.all_gather(i, DATA_AXIS, axis=1, tiled=True)
        ms, mi = lax.top_k(s_all, k)
        sel = jnp.take_along_axis(i_all, mi, axis=1)
        sel = jnp.where(jnp.isfinite(ms), sel, cap_local * n)
        return ms, sel

    spec_state = DeviceKnnState(
        vectors=P(DATA_AXIS, None), valid=P(DATA_AXIS), norms=P(DATA_AXIS)
    )
    from pathway_tpu.parallel.sharding import shard_map_norep

    fn = shard_map_norep(
        local,
        mesh=mesh,
        in_specs=(spec_state, P()),
        out_specs=(P(), P()),
    )
    return fn(state, queries)
