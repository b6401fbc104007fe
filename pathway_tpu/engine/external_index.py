"""External index operator: as-of-now retrieval against device-resident state.

Engine-side equivalent of the reference's `UseExternalIndexAsOfNow` timely
operator (reference: src/engine/dataflow/operators/external_index.rs:38 and
the `ExternalIndex` trait src/external_integration/mod.rs:40): the index is
mutable operator state *outside* the incremental collections; queries are
answered against the index state at arrival time and answers are never
revised when the index later changes — only query-row deletions retract
their answers (Appendix B of SURVEY.md).

The TPU implementation keeps the index in HBM (ops/knn.py): adds/removes are
bucket-padded scatter batches, searches are bucket-padded masked matmul +
top-k. Host state is only the key<->slot mapping.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Protocol, Sequence

import numpy as np

from pathway_tpu.engine.batch import DeltaBatch
from pathway_tpu.engine.graph import Node, Scope
from pathway_tpu.engine.value import Pointer, is_error
from pathway_tpu.internals import metrics as _metrics
from pathway_tpu.internals import tracing as _tracing

#: device dispatch volume on the KNN path — how many index mutations and
#: query probes each commit pushes through the pipeline
_KNN_UPDATES = _metrics.REGISTRY.counter(
    "pathway_device_knn_updates_total",
    "key add/remove mutations dispatched to the device KNN index",
)
_KNN_QUERIES = _metrics.REGISTRY.counter(
    "pathway_device_knn_queries_total",
    "query vectors dispatched to the device KNN search",
)


class ExternalIndex(Protocol):
    """Host-facing index contract (add/remove by key, batched search)."""

    def add(self, keys: Sequence[Pointer], vectors: Sequence[Any]) -> None: ...

    def remove(self, keys: Sequence[Pointer]) -> None: ...

    def search(
        self, queries: Sequence[Any], k: int
    ) -> list[list[tuple[Pointer, float]]]: ...


def _bucket(n: int, minimum: int = 8) -> int:
    b = minimum
    while b < n:
        b *= 2
    return b


_gather_pad_jit = None
_pack_results_jit = None


def _pack_results(scores, slots):
    """Stack (scores f32, slots i32) into ONE int32 array [2, q, k] (scores
    bitcast) so the host pays a single blocking device→host fetch per
    search instead of two."""
    global _pack_results_jit
    if _pack_results_jit is None:
        import jax

        @jax.jit
        def pack(s, i):
            import jax.numpy as jnp
            from jax import lax

            return jnp.stack(
                [
                    lax.bitcast_convert_type(
                        s.astype(jnp.float32), jnp.int32
                    ),
                    i.astype(jnp.int32),
                ]
            )

        _pack_results_jit = pack
    return _pack_results_jit(scores, slots)


def _gather_pad(dev, idx_pad, enabled):
    """Bucketed device gather: [B, dim] batch + padded indices -> [b, dim]
    float32 rows, zeroed where disabled. One module-level jit — jax caches
    the compilation per input shape, and all shapes here are bucketed."""
    global _gather_pad_jit
    if _gather_pad_jit is None:
        import jax
        import jax.numpy as jnp

        @jax.jit
        def gather(d, i, e):
            rows = jnp.take(d, i, axis=0).astype(jnp.float32)
            return jnp.where(e[:, None], rows, 0.0)

        _gather_pad_jit = gather
    return _gather_pad_jit(dev, idx_pad, enabled)


class DeviceKnnIndex:
    """HBM-resident brute-force KNN with a host slot allocator.

    Replaces the reference's CPU brute-force/usearch indexes with the
    fixed-capacity masked slot array of ops/knn.py. Capacity doubles by
    device-side copy when the free list runs dry; update and query batches
    are padded to power-of-two buckets so jit caches stay small.
    """

    def __init__(
        self,
        dim: int,
        metric: str = "cos",
        capacity: int = 1024,
        dtype: Any = None,
        mesh: Any = None,
    ) -> None:
        import jax.numpy as jnp

        from pathway_tpu.ops import knn_init

        self.dim = dim
        self.metric = metric
        self.capacity = capacity
        self.dtype = dtype if dtype is not None else jnp.float32
        self.mesh = mesh
        self.state = knn_init(capacity, dim, self.dtype, mesh=mesh)
        self.key_to_slot: dict[Pointer, int] = {}
        self.slot_to_key: dict[int, Pointer] = {}
        self._free: list[int] = list(range(capacity - 1, -1, -1))

    def __len__(self) -> int:
        return len(self.key_to_slot)

    # -- mutation ------------------------------------------------------------

    def _grow(self) -> None:
        from pathway_tpu.ops import knn_init
        from pathway_tpu.ops.knn import DeviceKnnState, shard_state

        old = self.state
        new_capacity = self.capacity * 2
        fresh = knn_init(new_capacity, self.dim, self.dtype, mesh=self.mesh)
        self.state = DeviceKnnState(
            vectors=fresh.vectors.at[: self.capacity].set(old.vectors),
            valid=fresh.valid.at[: self.capacity].set(old.valid),
            norms=fresh.norms.at[: self.capacity].set(old.norms),
        )
        if self.mesh is not None:
            # the copies above leave the layout to the compiler; the index
            # is sharded by contract (knn_search_sharded assumes it)
            self.state = shard_state(self.state, self.mesh)
        self._free = list(range(new_capacity - 1, self.capacity - 1, -1)) + self._free
        self.capacity = new_capacity

    def _apply(
        self, slots: list[int], vecs: np.ndarray, set_valid: list[bool]
    ) -> None:
        import jax.numpy as jnp

        from pathway_tpu.engine import device_residency as _dres
        from pathway_tpu.ops import knn_update

        n = len(slots)
        if n == 0:
            return
        b = _bucket(n)
        slots_arr = np.full((b,), 0, np.int32)
        slots_arr[:n] = slots
        vec_arr = np.zeros((b, self.dim), np.float32)
        vec_arr[:n] = vecs
        valid_arr = np.zeros((b,), bool)
        valid_arr[:n] = set_valid
        enabled = np.zeros((b,), bool)
        enabled[:n] = True
        h2d = (
            slots_arr.nbytes + vec_arr.nbytes + valid_arr.nbytes
            + enabled.nbytes
        )
        with _tracing.stage("knn.add.dispatch", rows=n, h2d_bytes=h2d):
            _dres.record_h2d(h2d)
            self.state = knn_update(
                self.state,
                jnp.asarray(slots_arr),
                jnp.asarray(vec_arr),
                jnp.asarray(valid_arr),
                jnp.asarray(enabled),
            )

    def add(self, keys: Sequence[Pointer], vectors: Sequence[Any]) -> None:
        # the host's share of an add (key and slot maps, stacking, padding)
        # is this stage's self time: the uploads and enqueues lie inside it
        # as ``knn.add.dispatch``
        with _tracing.stage("knn.add.host", rows=len(keys)):
            self._add(keys, vectors)

    def _add(self, keys: Sequence[Pointer], vectors: Sequence[Any]) -> None:
        from pathway_tpu.engine.device import LazyDeviceVector

        # Group lazy rows by their parent device batch — NOT by contiguous
        # runs: upstream operators iterate key sets and scramble row order,
        # which would fragment a 1000-row commit into ~1000 one-row device
        # updates (measured: 732 updates/commit, whose device-queue depth
        # then stalled the next query's search by ~6 s). One gather+scatter
        # per parent keeps the device queue a few ops deep.
        groups: dict[int, tuple[Any, list[int], list[Pointer]]] = {}
        host_keys: list[Pointer] = []
        host_vecs: list[Any] = []
        for key, vec in zip(keys, vectors):
            if (
                isinstance(vec, LazyDeviceVector)
                and vec.batch.dev is not None
                and tuple(vec.batch.dev.shape[1:]) == (self.dim,)
            ):
                handle, indices, gkeys = groups.setdefault(
                    id(vec.batch), (vec.batch, [], [])
                )
                indices.append(vec.index)
                gkeys.append(key)
            else:
                host_keys.append(key)
                host_vecs.append(vec)
        for handle, indices, gkeys in groups.values():
            if not self._add_device_run(gkeys, handle.dev, indices):
                # replacements take the general path; the lazy rows
                # materialise through their (prefetched) host twin
                self._add_host(
                    gkeys,
                    [LazyDeviceVector(handle, i) for i in indices],
                )
        if host_keys:
            self._add_host(host_keys, host_vecs)

    def _add_host(
        self, keys: Sequence[Pointer], vectors: Sequence[Any]
    ) -> None:
        slots, vecs, valid = [], [], []
        deferred_free: list[int] = []  # freed only after the batch lands, so
        # a replaced key's old slot can't be reused (= written twice) in it
        for key, vec in zip(keys, vectors):
            if key in self.key_to_slot:
                old_slot = self.key_to_slot.pop(key)
                self.slot_to_key.pop(old_slot, None)
                slots.append(old_slot)
                vecs.append(np.zeros((self.dim,), np.float32))
                valid.append(False)
                deferred_free.append(old_slot)
            if not self._free:
                self._apply(slots, np.asarray(vecs, np.float32), valid)
                self._free.extend(deferred_free)
                slots, vecs, valid, deferred_free = [], [], [], []
                if not self._free:
                    self._grow()
            slot = self._free.pop()
            self.key_to_slot[key] = slot
            self.slot_to_key[slot] = key
            slots.append(slot)
            vecs.append(np.asarray(vec, np.float32).reshape(self.dim))
            valid.append(True)
        self._apply(slots, np.asarray(vecs, np.float32), valid)
        self._free.extend(deferred_free)

    def _add_device_run(
        self, keys: Sequence[Pointer], dev: Any, indices: Sequence[int]
    ) -> bool:
        """Transfer-free ingest of one run of lazy rows sharing a live
        device batch (the embedder's jit output): gather on device and
        scatter straight into HBM — no device→host→device round trip
        (the bench pipeline's hot path)."""
        if tuple(dev.shape[1:]) != (self.dim,):
            return False  # rejection must precede any capacity growth
        if any(key in self.key_to_slot for key in keys):
            return False  # replacements take the general path
        while len(self._free) < len(keys):
            self._grow()  # device-side copy; cheaper than a host detour

        import jax.numpy as jnp

        from pathway_tpu.engine import device_residency as _dres
        from pathway_tpu.ops import knn_update

        n = len(keys)
        slots = []
        for key in keys:
            slot = self._free.pop()
            self.key_to_slot[key] = slot
            self.slot_to_key[slot] = key
            slots.append(slot)
        # every device-side shape is bucketed — otherwise each distinct
        # batch length would trigger a fresh compile
        b = _bucket(n)
        slots_arr = np.zeros((b,), np.int32)
        slots_arr[:n] = slots
        enabled = np.zeros((b,), bool)
        enabled[:n] = True
        idx_pad = np.zeros((b,), np.int32)
        idx_pad[:n] = indices
        # only the control arrays go up — the vectors are already resident
        h2d = slots_arr.nbytes + enabled.nbytes + idx_pad.nbytes
        with _tracing.stage("knn.add.dispatch", rows=n, h2d_bytes=h2d):
            _dres.record_h2d(h2d)
            enabled_dev = jnp.asarray(enabled)
            gathered = _gather_pad(
                dev, jnp.asarray(idx_pad), enabled_dev
            )
            self.state = knn_update(
                self.state,
                jnp.asarray(slots_arr),
                gathered,
                enabled_dev,
                enabled_dev,
            )
        return True

    def remove(self, keys: Sequence[Pointer]) -> None:
        slots, vecs, valid = [], [], []
        for key in keys:
            slot = self.key_to_slot.pop(key, None)
            if slot is None:
                continue
            self.slot_to_key.pop(slot, None)
            self._free.append(slot)
            slots.append(slot)
            vecs.append(np.zeros((self.dim,), np.float32))
            valid.append(False)
        self._apply(slots, np.asarray(vecs, np.float32), valid)

    # -- operator persistence -------------------------------------------------

    def op_state(self) -> dict:
        """Device arrays come back as numpy so snapshots pickle (the HBM
        copy is rebuilt on restore)."""
        return {
            "vectors": np.asarray(self.state.vectors),
            "valid": np.asarray(self.state.valid),
            "norms": np.asarray(self.state.norms),
            "key_to_slot": dict(self.key_to_slot),
            "free": list(self._free),
            "capacity": self.capacity,
        }

    def restore_op_state(self, state: dict) -> None:
        import jax.numpy as jnp

        from pathway_tpu.engine import device_residency as _dres
        from pathway_tpu.ops.knn import DeviceKnnState

        self.capacity = state["capacity"]
        self.state = DeviceKnnState(
            vectors=jnp.asarray(state["vectors"]),
            valid=jnp.asarray(state["valid"]),
            norms=jnp.asarray(state["norms"]),
        )
        _dres.record_h2d(
            int(self.state.vectors.nbytes)
            + int(self.state.valid.nbytes)
            + int(self.state.norms.nbytes)
        )
        self.key_to_slot = dict(state["key_to_slot"])
        self.slot_to_key = {s: k for k, s in self.key_to_slot.items()}
        self._free = list(state["free"])

    # -- read snapshots ------------------------------------------------------

    def read_view(self) -> "DeviceKnnIndex":
        """Immutable search-only twin at the current state, for the
        serving plane's per-commit snapshots.

        ``knn_update`` DONATES its input buffers (the scatter reuses
        them), so the view cannot alias ``self.state`` — it takes a
        device-side copy (HBM->HBM, no host transfer).  The slot maps
        are host dicts and copy shallowly.  The view's ``search`` is the
        exact production path, so snapshot reads are bit-identical to a
        synchronous read at the same commit."""
        import jax.numpy as jnp

        view = object.__new__(type(self))
        view.dim = self.dim
        view.metric = self.metric
        view.capacity = self.capacity
        view.dtype = self.dtype
        view.mesh = self.mesh
        view.state = type(self.state)(
            jnp.copy(self.state.vectors),
            jnp.copy(self.state.valid),
            jnp.copy(self.state.norms),
        )
        view.key_to_slot = dict(self.key_to_slot)
        view.slot_to_key = dict(self.slot_to_key)
        view._free = []
        return view

    # -- search --------------------------------------------------------------

    def search(
        self, queries: Sequence[Any], k: int
    ) -> list[list[tuple[Pointer, float]]]:
        import jax.numpy as jnp

        from pathway_tpu.engine import device_residency as _dres
        from pathway_tpu.ops import knn_search
        from pathway_tpu.ops.knn import knn_search_sharded

        n = len(queries)
        if n == 0:
            return []
        k_eff = min(k, self.capacity)
        b = _bucket(n)
        from pathway_tpu.engine.device import device_runs

        with _tracing.stage(
            "knn.search.dispatch", queries=n, padded_queries=b
        ) as dispatch:
            q_dev = None
            runs = device_runs(list(queries))
            if (
                len(runs) == 1
                and runs[0][2] is not None
                and tuple(runs[0][2].shape[1:]) == (self.dim,)
            ):
                # query vectors still live on device (embedder output):
                # gather there and fetch only the top-k — one small round
                # trip total
                dev, indices = runs[0][2], runs[0][3]
                idx_pad = np.zeros((b,), np.int32)
                idx_pad[:n] = indices
                enabled = np.zeros((b,), bool)
                enabled[:n] = True
                h2d = idx_pad.nbytes + enabled.nbytes
                q_dev = _gather_pad(
                    dev, jnp.asarray(idx_pad), jnp.asarray(enabled)
                )
            if q_dev is None:
                q = np.zeros((b, self.dim), np.float32)
                for i, vec in enumerate(queries):
                    q[i] = np.asarray(vec, np.float32).reshape(self.dim)
                h2d = q.nbytes
                q_dev = jnp.asarray(q)
            _dres.record_h2d(h2d)
            dispatch.add(h2d_bytes=h2d)
            if self.mesh is not None:
                scores, slots = knn_search_sharded(
                    self.state, q_dev, k_eff, self.mesh, self.metric
                )
            else:
                scores, slots = knn_search(
                    self.state, q_dev, k_eff, self.metric
                )
            packed_dev = _pack_results(scores, slots)
        # the one blocking read of a search: the scan queues behind
        # whatever the device was given before it
        with _tracing.stage("knn.search.fetch", wait=True, queries=n) as fetch:
            packed = np.asarray(packed_dev)
            fetch.add(d2h_bytes=packed.nbytes)
        _dres.record_d2h(packed.nbytes)
        scores = packed[0].view(np.float32)[:n]
        slots = packed[1][:n]
        out: list[list[tuple[Pointer, float]]] = []
        for i in range(n):
            hits = []
            for score, slot in zip(scores[i], slots[i]):
                key = self.slot_to_key.get(int(slot))
                if key is not None and np.isfinite(score):
                    hits.append((key, float(score)))
            out.append(hits)
        return out


class _HostKnnState(NamedTuple):
    """NumPy twin of ops.knn.DeviceKnnState (same field contract)."""

    vectors: np.ndarray  # [capacity, dim]
    valid: np.ndarray  # [capacity] bool
    norms: np.ndarray  # [capacity] float32 — squared L2 norms


class HostKnnIndex(DeviceKnnIndex):
    """CPU/NumPy twin of :class:`DeviceKnnIndex` — the bit-exact host spec
    for the device KNN kernels (PR-2 parity discipline): the oracle the
    device index is compared with, not something a pipeline falls back to.

    It *inherits* the slot allocator, bucket padding, replacement and
    growth logic (the behaviors that decide slot ids and therefore tie
    order), overriding only the device seams: state lives in NumPy
    arrays, the scatter update and the masked matmul + top-k run on
    host.  Tie-breaking matches ``lax.top_k`` (lowest slot first) via a
    stable descending argsort.  Float reduction order is the one seam a
    host spec cannot pin per-platform; the parity corpus uses exactly
    representable values so any order sums identically, and the
    check.py parity gate validates the real device per platform.
    """

    def __init__(
        self,
        dim: int,
        metric: str = "cos",
        capacity: int = 1024,
        dtype: Any = None,
        mesh: Any = None,
    ) -> None:
        self.dim = dim
        self.metric = metric
        self.capacity = capacity
        self.dtype = np.float32
        self.mesh = None  # host search never shards
        self.state = _HostKnnState(
            vectors=np.zeros((capacity, dim), np.float32),
            valid=np.zeros((capacity,), bool),
            norms=np.zeros((capacity,), np.float32),
        )
        self.key_to_slot = {}
        self.slot_to_key = {}
        self._free = list(range(capacity - 1, -1, -1))
        self._cow_shared = False

    def _grow(self) -> None:
        old = self.state
        new_capacity = self.capacity * 2
        vectors = np.zeros((new_capacity, self.dim), np.float32)
        valid = np.zeros((new_capacity,), bool)
        norms = np.zeros((new_capacity,), np.float32)
        vectors[: self.capacity] = old.vectors
        valid[: self.capacity] = old.valid
        norms[: self.capacity] = old.norms
        self.state = _HostKnnState(vectors, valid, norms)
        self._cow_shared = False  # growth allocated fresh arrays
        self._free = (
            list(range(new_capacity - 1, self.capacity - 1, -1)) + self._free
        )
        self.capacity = new_capacity

    def _add_device_run(
        self, keys: Sequence[Pointer], dev: Any, indices: Sequence[int]
    ) -> bool:
        # lazy device rows materialise through their (prefetched) host
        # twin on the general path — a host index never touches HBM
        return False

    def _apply(
        self, slots: list[int], vecs: np.ndarray, set_valid: list[bool]
    ) -> None:
        n = len(slots)
        if n == 0:
            return
        if self._cow_shared:
            # a read view shares these arrays: clone before the in-place
            # scatter so the published snapshot stays frozen (the device
            # twin gets this for free — knn_update is functional)
            self.state = _HostKnnState(
                self.state.vectors.copy(),
                self.state.valid.copy(),
                self.state.norms.copy(),
            )
            self._cow_shared = False
        vecs = np.asarray(vecs, np.float32).reshape(n, self.dim)
        idx = np.asarray(slots, np.int64)
        self.state.vectors[idx] = vecs
        self.state.valid[idx] = np.asarray(set_valid, bool)
        # same formula as ops.knn.knn_update: f32 square-sum of the row
        self.state.norms[idx] = np.sum(vecs * vecs, axis=-1)

    def op_state(self) -> dict:
        # explicit copies: the host arrays mutate in place, and a snapshot
        # must not alias live state (the device version copies via jax→np)
        return {
            "vectors": self.state.vectors.copy(),
            "valid": self.state.valid.copy(),
            "norms": self.state.norms.copy(),
            "key_to_slot": dict(self.key_to_slot),
            "free": list(self._free),
            "capacity": self.capacity,
        }

    def restore_op_state(self, state: dict) -> None:
        self.capacity = state["capacity"]
        self.state = _HostKnnState(
            vectors=np.asarray(state["vectors"], np.float32),
            valid=np.asarray(state["valid"], bool),
            norms=np.asarray(state["norms"], np.float32),
        )
        self.key_to_slot = dict(state["key_to_slot"])
        self.slot_to_key = {s: k for k, s in self.key_to_slot.items()}
        self._free = list(state["free"])
        self._cow_shared = False

    def read_view(self) -> "HostKnnIndex":
        """Copy-on-write read view: the view SHARES the live arrays and
        both sides are flagged, so the next in-place scatter on either
        clones first (``_apply``) — publishing an idle index costs two
        dict copies, not an array copy."""
        view = object.__new__(type(self))
        view.dim = self.dim
        view.metric = self.metric
        view.capacity = self.capacity
        view.dtype = self.dtype
        view.mesh = self.mesh
        view.state = self.state
        view.key_to_slot = dict(self.key_to_slot)
        view.slot_to_key = dict(self.slot_to_key)
        view._free = []
        view._cow_shared = True
        self._cow_shared = True
        return view

    def search(
        self, queries: Sequence[Any], k: int
    ) -> list[list[tuple[Pointer, float]]]:
        n = len(queries)
        if n == 0:
            return []
        k_eff = min(k, self.capacity)
        q = np.zeros((n, self.dim), np.float32)
        for i, vec in enumerate(queries):
            q[i] = np.asarray(vec, np.float32).reshape(self.dim)
        db = self.state.vectors
        dots = q @ db.T  # f32 matmul — ops.knn uses Precision.HIGHEST
        if self.metric == "dot":
            scores = dots
        elif self.metric == "cos":
            qn = np.sqrt(np.sum(q * q, axis=-1, keepdims=True))
            dbn = np.sqrt(self.state.norms)[None, :]
            scores = dots / np.maximum(qn * dbn, np.float32(1e-30))
        elif self.metric == "l2sq":
            qn = np.sum(q * q, axis=-1, keepdims=True)
            scores = -(qn + self.state.norms[None, :] - 2.0 * dots)
        else:
            raise ValueError(f"unknown metric {self.metric!r}")
        scores = np.where(self.state.valid[None, :], scores, -np.inf)
        # lax.top_k tie contract: highest score first, lowest slot among
        # equals — a stable argsort on the negated scores reproduces it
        order = np.argsort(-scores, axis=1, kind="stable")[:, :k_eff]
        top = np.take_along_axis(scores, order, axis=1)
        out: list[list[tuple[Pointer, float]]] = []
        for i in range(n):
            hits = []
            for score, slot in zip(top[i], order[i]):
                key = self.slot_to_key.get(int(slot))
                if key is not None and np.isfinite(score):
                    hits.append((key, float(score)))
            out.append(hits)
        return out


class ExternalIndexNode(Node):
    """As-of-now index operator: port 0 = indexed data, port 1 = queries.

    Output: keyed by query id, row = (result_ids: tuple[Pointer],
    result_scores: tuple[float]). Index-side updates within a commit are
    applied before queries of the same commit are answered. Answers stick
    until their query row is deleted.
    """

    def __init__(
        self,
        scope: Scope,
        index_table: Node,
        query_table: Node,
        index: ExternalIndex,
        index_col: int,
        query_col: int,
        k: int,
        limit_col: int | None = None,
    ) -> None:
        super().__init__(scope, [index_table, query_table], 2)
        # NOT ``self.index`` — that is the node's scope position
        # (Node.index), which every scheduler uses to address replicas;
        # shadowing it breaks sharded delivery for index pipelines
        self.ext_index = index
        self.index_col = index_col
        self.query_col = query_col
        self.k = k
        self.limit_col = limit_col

    def op_state(self) -> dict:
        state = super().op_state()
        index_state = getattr(self.ext_index, "op_state", None)
        if index_state is None:
            # silently skipping would resume with an empty index while the
            # reader has already seeked past the rows that populated it
            raise TypeError(
                f"{type(self.ext_index).__name__} does not implement "
                "op_state/restore_op_state, so it cannot be used with "
                "PersistenceMode.OPERATOR_PERSISTING"
            )
        state["index"] = index_state()
        return state

    def restore_op_state(self, state: dict) -> None:
        super().restore_op_state(state)
        if "index" in state and hasattr(self.ext_index, "restore_op_state"):
            self.ext_index.restore_op_state(state["index"])

    def process(self, time: int) -> DeltaBatch:
        index_batch = self.take(0)
        query_batch = self.take(1)

        # 1. fold index-side deltas into device state
        add_keys: list[Pointer] = []
        add_vecs: list[Any] = []
        rm_keys: list[Pointer] = []
        for key, row, diff in index_batch:
            vec = row[self.index_col]
            if diff > 0:
                if is_error(vec) or vec is None:
                    self.report(key, "error/None vector in index input")
                    continue
                add_keys.append(key)
                add_vecs.append(vec)
            else:
                rm_keys.append(key)
        # removes first so a same-commit delete+insert of a key nets to add
        if rm_keys or add_keys:
            with _tracing.stage(
                "knn.update",
                cat="pipeline",
                adds=len(add_keys),
                removes=len(rm_keys),
            ):
                if rm_keys:
                    add_set = set(add_keys)
                    self.ext_index.remove(
                        [k_ for k_ in rm_keys if k_ not in add_set]
                    )
                if add_keys:
                    self.ext_index.add(add_keys, add_vecs)
            _KNN_UPDATES.inc(len(rm_keys) + len(add_keys))

        # 2. answer new queries as-of-now; retract answers of deleted queries
        out = DeltaBatch()
        pending: list[tuple[Pointer, Any, int]] = []
        retracted: set[Pointer] = set()
        for key, row, diff in query_batch:
            if diff < 0:
                prev = self.current.get(key)
                if prev is not None and key not in retracted:
                    out.append(key, prev, -1)
                    retracted.add(key)
                continue
            vec = row[self.query_col]
            if is_error(vec) or vec is None:
                self.report(key, "error/None vector in query input")
                continue
            limit = self.k
            if self.limit_col is not None:
                lv = row[self.limit_col]
                if lv is not None and not is_error(lv):
                    limit = int(lv)
            pending.append((key, vec, limit))
        if pending:
            max_k = max(limit for _k, _v, limit in pending)
            with _tracing.stage(
                "knn.search", cat="pipeline", queries=len(pending)
            ):
                results = self.ext_index.search(
                    [v for _k, v, _l in pending], max_k
                )
            _KNN_QUERIES.inc(len(pending))
            for (key, _vec, limit), hits in zip(pending, results):
                hits = hits[:limit]
                # re-query of a live key replaces its previous answer (unless
                # the deletion pass of this commit already retracted it)
                prev = self.current.get(key)
                if prev is not None and key not in retracted:
                    out.append(key, prev, -1)
                out.append(
                    key,
                    (
                        tuple(hk for hk, _s in hits),
                        tuple(s for _hk, s in hits),
                    ),
                    1,
                )
        return out.consolidate()
