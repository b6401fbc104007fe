"""Engine graph: operator nodes, the Scope API, and the commit scheduler.

This is the TPU-native replacement for the reference's Rust engine
(reference: `Graph` trait src/engine/graph.rs:643-990 implemented by
`DataflowGraphInner` src/engine/dataflow.rs:820 over timely/differential).
Instead of translating timely, we keep the *contract* — tables are keyed
update streams processed per commit timestamp — and execute with a host-side
topological scheduler: every operator consumes consolidated delta batches at
time ``t`` and emits output deltas at ``t``. Heavy math (UDF microbatches,
vector search) is dispatched to JAX/XLA on TPU by the device-side operators;
everything here is control plane.

Key design points vs the reference:
- Differential's bilinear join update is realized per affected join-key group
  (recompute local old/new output, emit the difference) — same output stream,
  simpler state machine.
- Retraction of nondeterministic expression outputs reuses the operator's own
  current-state map, so deletions always cancel prior insertions (the
  reference needs a dedicated MapWithConsistentDeletions wrapper,
  src/engine/dataflow/operators.rs:308).
"""

from __future__ import annotations

import functools
import itertools
import logging
import threading
import time as _time
from typing import Any, Callable, Iterable, Sequence

import numpy as np

from pathway_tpu.engine import device as _device
from pathway_tpu.engine import device_pipeline as _device_pipeline
from pathway_tpu.engine.batch import DeltaBatch, apply_batch_to_state
from pathway_tpu.engine.device import VECTOR_THRESHOLD
from pathway_tpu.engine.expression import EngineExpression, EvalContext
from pathway_tpu.engine.reducers import Reducer
from pathway_tpu.engine.value import ERROR, Error, Pointer, hash_values, is_error, ref_scalar, rows_differ
from pathway_tpu.internals import metrics as _metrics
from pathway_tpu.internals import tracing as _tracing

_LOG = logging.getLogger("pathway_tpu.engine")


class EngineError(RuntimeError):
    """A row-level error that ended the run (``terminate_on_error``)."""


#: sink-side row counter; one shared series — the per-commit delta is what
#: stamps the ingest->sink latency histogram (internals/runner.py)
_OUTPUT_ROWS = _metrics.REGISTRY.counter(
    "pathway_output_rows_total",
    "rows delivered to subscribe sinks (insertions and retractions)",
)


class Node:
    """An operator in the engine graph."""

    def __init__(self, scope: "Scope", inputs: Sequence["Node"], arity: int) -> None:
        self.scope = scope
        self.inputs = list(inputs)
        self.arity = arity
        self.index = len(scope.nodes)
        scope.nodes.append(self)
        self.consumers: list[tuple[Node, int]] = []
        self.pending: dict[int, list[DeltaBatch]] = {}
        self._state: dict[Pointer, tuple] = {}
        self._state_lag: list[DeltaBatch] = []
        self._state_lag_rows = 0
        self.name: str = type(self).__name__
        self.trace: Any = None
        for port, inp in enumerate(self.inputs):
            inp.consumers.append((self, port))

    # -- lazy state ---------------------------------------------------------
    #
    # A node's ``current`` (key -> row) is only needed when somebody
    # actually observes it: a retraction arriving at this operator, a
    # state-peeking consumer (zip/ix/update/restrict), a snapshot, a test.
    # Differential dataflow pays for arrangements only where they exist;
    # here output batches are deferred and applied on first read, so a
    # bulk pipeline whose state is never inspected materialises no
    # per-row dict entries at all. Deferred columnar batches are cheap
    # (arrays); deferred row batches hold live tuples either way. The
    # rows cap bounds memory for long streams whose state nobody reads.

    _STATE_LAG_MAX_ROWS = 1 << 21

    @property
    def current(self) -> dict[Pointer, tuple]:
        if self._state_lag:
            lag, self._state_lag = self._state_lag, []
            self._state_lag_rows = 0
            for batch in lag:
                # deferred batches may be raw (the scheduler no longer
                # pre-consolidates); state application needs merged diffs
                apply_batch_to_state(self._state, batch.consolidate())
        return self._state

    @current.setter
    def current(self, value: dict[Pointer, tuple]) -> None:
        self._state = value
        self._state_lag = []
        self._state_lag_rows = 0

    def _defer_state(self, batch: DeltaBatch) -> None:
        """Queue an output batch for lazy application to ``current``."""
        self._state_lag.append(batch)
        self._state_lag_rows += len(batch)
        if self._state_lag_rows > self._STATE_LAG_MAX_ROWS:
            self.current  # noqa: B018 — drain via the property

    # -- scheduler interface ------------------------------------------------

    def has_pending(self) -> bool:
        return bool(self.pending)

    def take(self, port: int) -> DeltaBatch:
        return self.take_raw(port).consolidate()

    def take_raw(self, port: int) -> DeltaBatch:
        """Like :meth:`take` but without consolidation — for diff-linear
        consumers (segment-sum groupby) that tolerate duplicate and
        net-zero (key, row) entries."""
        batches = self.pending.pop(port, None)
        if not batches:
            return DeltaBatch()
        if len(batches) == 1:
            return batches[0]
        if all(b._entries is None for b in batches):
            # stay columnar: concatenating arrays keeps the zero-PyObject
            # path intact for the downstream segment consumer
            from pathway_tpu.engine.batch import Columns

            stacked = Columns.concat([b.columns for b in batches])
            if stacked is not None:
                out = DeltaBatch.from_columns(stacked, consolidated=False)
                # all-+1 inputs stay all-+1 stacked (keys may repeat
                # across parts, so the consolidated insert_only flag —
                # which asserts uniqueness — must NOT propagate)
                out._raw_insert_only = all(
                    b._raw_insert_only for b in batches
                )
                return out
        merged = DeltaBatch()
        for b in batches:
            merged.extend(b)
        return merged

    def push(self, port: int, batch: DeltaBatch) -> None:
        if batch:
            self.pending.setdefault(port, []).append(batch)

    def process(self, time: int) -> DeltaBatch:
        raise NotImplementedError

    def on_time_end(self, time: int) -> None:
        pass

    def on_end(self) -> None:
        pass

    def close(self) -> None:
        """Final resource teardown, after the post-``on_end`` settlement
        commit — ``on_end`` may inject final batches (temporal buffer
        flush) that still have to reach sinks, so sinks must not close
        inside ``on_end`` itself."""

    def report(
        self,
        key: Pointer | None,
        message: str,
        exc: BaseException | None = None,
    ) -> None:
        self.scope.report_error(self, key, message, exc)

    def snapshot(self) -> dict[Pointer, tuple]:
        return dict(self.current)

    # -- operator persistence (reference: operator_snapshot.rs) --------------

    #: mutable attributes beyond ``current`` that define operator state;
    #: captured at commit boundaries by OperatorSnapshotManager
    STATE_ATTRS: tuple = ()

    def op_state(self) -> dict:
        state: dict = {"current": dict(self.current)}
        for name in self.STATE_ATTRS:
            state[name] = getattr(self, name)
        return state

    def restore_op_state(self, state: dict) -> None:
        self.current = dict(state["current"])
        for name in self.STATE_ATTRS:
            if name in state:
                setattr(self, name, state[name])


class StaticSource(Node):
    """A table fully known at graph build time."""

    #: restored snapshots already contain these rows — a resumed run must
    #: not re-emit them (operator persistence)
    STATE_ATTRS = ("_emitted",)

    def __init__(self, scope: "Scope", rows: Iterable[tuple[Pointer, tuple]], arity: int):
        super().__init__(scope, [], arity)
        self._rows = list(rows)
        self._emitted = False

    def initial_batch(self) -> DeltaBatch | None:
        if self._emitted:
            return None
        self._emitted = True
        out = DeltaBatch((k, r, 1) for k, r in self._rows)
        out._raw_insert_only = True  # diffs literally +1 by construction
        return out

    def process(self, time: int) -> DeltaBatch:
        return self.take_raw(0)  # pass-through; consumers consolidate


class InputSession(Node):
    """Mutable input: connectors push inserts/removes/upserts, then commit.

    Mirrors the reference's InputSession / UpsertSession pair
    (src/connectors/adaptors.rs:23-60): in upsert mode an insert for an
    existing key retracts the previous row first.
    """

    def __init__(self, scope: "Scope", arity: int, upsert: bool = False):
        super().__init__(scope, [], arity)
        self.upsert = upsert
        self._buffer: list[tuple[Pointer, tuple | None, int]] = []
        self._has_removals = False
        self._has_rowless_removals = False

    def insert(self, key: Pointer, row: tuple) -> None:
        self._buffer.append((key, row, 1))

    def remove(self, key: Pointer, row: tuple | None = None) -> None:
        self._buffer.append((key, row, -1))
        self._has_removals = True
        if row is None:
            self._has_rowless_removals = True

    def flush(self) -> DeltaBatch | None:
        if not self._buffer:
            return None
        if not self.upsert and not self._has_rowless_removals:
            # dominant connector shapes: plain inserts, or removals that
            # carry their row — neither needs the per-row overlay (the
            # overlay exists solely to resolve row-less removals against
            # this commit's earlier updates and prior state)
            out = DeltaBatch(self._buffer)
            self._buffer = []
            if not self._has_removals:
                # every diff is +1 by construction of insert(); multiset-
                # correct consumers (columnar join) key off this hint and
                # dict-state consumers still consolidate in take()
                out._raw_insert_only = True
            self._has_removals = False
            return out
        state = self.current  # hoisted: property drains lazily-applied state
        from pathway_tpu.native import kernels as _native

        if (
            _native is not None
            and hasattr(_native, "session_overlay")
            and type(state) is dict
        ):
            # the whole overlay resolution (upsert retractions, row-less
            # removals against this commit's earlier updates) in one call
            entries = _native.session_overlay(
                self._buffer, state, self.upsert
            )
            if entries is not None:
                self._buffer.clear()
                self._has_removals = False
                self._has_rowless_removals = False
                return DeltaBatch(entries).consolidate()
        out = DeltaBatch()
        # overlay of keys touched this commit: key -> row | None (absent row)
        overlay: dict[Pointer, tuple | None] = {}

        def effective(key: Pointer) -> tuple | None:
            if key in overlay:
                return overlay[key]
            return state.get(key)

        if self.upsert:
            for key, row, diff in self._buffer:
                prev = effective(key)
                if diff > 0:
                    if prev is not None:
                        out.append(key, prev, -1)
                    assert row is not None
                    out.append(key, row, 1)
                    overlay[key] = row
                else:
                    if prev is not None:
                        out.append(key, prev, -1)
                        overlay[key] = None
        else:
            for key, row, diff in self._buffer:
                if diff < 0 and row is None:
                    row = effective(key)
                    if row is None:
                        continue
                if diff > 0:
                    overlay[key] = row
                elif effective(key) == row:
                    overlay[key] = None
                out.append(key, row, diff)  # type: ignore[arg-type]
        self._buffer.clear()
        self._has_removals = False
        self._has_rowless_removals = False
        return out.consolidate()

    def process(self, time: int) -> DeltaBatch:
        # pure pass-through: keep the batch raw so diff-linear consumers
        # (columnar groupby) can skip consolidation entirely
        return self.take_raw(0)


class ExpressionNode(Node):
    """Per-row expression evaluation (select/with_columns/apply).

    Deletions are retracted from ``current`` rather than re-evaluated, which
    keeps nondeterministic UDF outputs consistent between insert and delete.
    """

    def __init__(
        self,
        scope: "Scope",
        source: Node,
        expressions: Sequence[EngineExpression],
    ) -> None:
        super().__init__(scope, [source], len(expressions))
        self.expressions = list(expressions)

    def _columnar_inserts(self, batch: DeltaBatch) -> DeltaBatch | None:
        """Pure-insert batch → columnar output sharing the input's keys;
        None falls back to the row/entry paths."""
        from pathway_tpu.engine import device
        from pathway_tpu.engine.batch import Columns
        from pathway_tpu.native import kernels as _native

        payload = batch.columns
        if payload is not None:
            view: Any = device.PayloadView(payload)
        else:
            view = device.ColumnarView(batch.entries, from_entries=True)
        arrays = []
        for expr in self.expressions:
            try:
                arrays.append(device.eval_columnar(expr, view))
            except device.NotVectorizable:
                return None
        if payload is not None:
            out_payload = Columns.with_keys_of(payload, arrays)
        else:
            entries = batch.entries
            if _native is not None:
                kb = _native.entry_keys_bytes(entries, Pointer)
            else:
                kb = _entry_keys_bytes_py(entries)
            if kb is None:
                return None  # non-Pointer keys: row path
            out_payload = Columns(len(entries), arrays, kbytes=kb)
        out = DeltaBatch.from_columns(
            out_payload,
            consolidated=batch._insert_only,
            insert_only=batch._insert_only,
        )
        # keys are the input's: its all-+1 hint carries over verbatim
        out._raw_insert_only = batch._raw_insert_only or out._insert_only
        return out

    def process(self, time: int) -> DeltaBatch:
        batch = self.take_raw(0)
        if not (batch._insert_only or batch._raw_insert_only):
            batch = batch.consolidate()
        insert_only = batch._insert_only or batch._raw_insert_only
        if insert_only and len(batch) >= VECTOR_THRESHOLD:
            fast = self._columnar_inserts(batch)
            if fast is not None:
                return fast
        out = DeltaBatch()
        ctx = EvalContext()
        if not insert_only:
            state = self.current  # hoisted: drains lazy state once
            for key, row, diff in batch:
                if diff < 0:
                    prev = state.get(key)
                    if prev is not None:
                        out.append(key, prev, diff)
        inserts = (
            batch.entries
            if insert_only
            else [e for e in batch if e[2] > 0]
        )
        if len(inserts) >= VECTOR_THRESHOLD:
            # columnar eval with row-materialised output (retraction case
            # or non-Pointer keys); falls back row-wise on mixed columns
            from pathway_tpu.engine.device import (
                eval_expressions_columnar_cols,
            )
            from pathway_tpu.native import kernels as _native

            cols = eval_expressions_columnar_cols(
                self.expressions, inserts, from_entries=True
            )
            if cols is not None:
                fresh = not out.entries
                if _native is not None:
                    out.entries.extend(_native.build_entries(inserts, cols))
                elif not cols:  # arity-0 select: one () row per key
                    out.entries.extend(
                        (key, (), diff) for key, _row, diff in inserts
                    )
                else:
                    out.entries.extend(
                        (key, new_row, diff)
                        for (key, _row, diff), new_row in zip(
                            inserts, zip(*cols)
                        )
                    )
                if fresh and batch._insert_only:
                    out._consolidated = True
                    out._insert_only = True
                return out
        for key, row, diff in inserts:
            new_row = tuple(expr.evaluate(key, row, ctx) for expr in self.expressions)
            out.append(key, new_row, diff)
        for key, message in ctx.errors:
            self.report(key, message)
        return out


class BatchApplyNode(Node):
    """Batched UDF execution over the arg-prep table (arity 1 output).

    The engine-side analog of the reference's async row map
    (map_named_async / MapWithConsistentDeletions,
    src/engine/dataflow/operators.rs:182,308): all rows inserted in a commit
    are handed to ``rows_fn`` at once — the executor decides concurrency
    (async) or fusion into one jit call (device microbatch). Deletions
    retract the memoized current value, so nondeterministic UDF outputs
    always cancel correctly.
    """

    def __init__(
        self,
        scope: "Scope",
        source: Node,
        rows_fn: Callable[[list], list],
        arg_cols: Sequence[int],
        propagate_none: bool = False,
    ) -> None:
        super().__init__(scope, [source], 1)
        self.rows_fn = rows_fn
        self.arg_cols = list(arg_cols)
        self.propagate_none = propagate_none

    def process(self, time: int) -> DeltaBatch:
        batch = self.take(0)
        out = DeltaBatch()
        state = self.current  # hoisted: drains lazy state once
        for key, row, diff in batch:
            if diff < 0:
                prev = state.get(key)
                if prev is not None:
                    out.append(key, prev, diff)
        pending: list[tuple[Pointer, tuple, int]] = []
        for key, row, diff in batch:
            if diff <= 0:
                continue
            args = tuple(row[c] for c in self.arg_cols)
            if any(is_error(a) for a in args):
                self.report(key, "error value in UDF argument")
                out.append(key, (ERROR,), diff)
                continue
            if self.propagate_none and any(a is None for a in args):
                out.append(key, (None,), diff)
                continue
            pending.append((key, args, diff))
        if pending:
            try:
                results = self.rows_fn([args for _k, args, _d in pending])
            except Exception as e:  # noqa: BLE001 — whole-batch failure
                results = [(False, e)] * len(pending)
            for (key, _args, diff), (ok, value) in zip(pending, results):
                if ok:
                    out.append(key, (value,), diff)
                else:
                    self.report(key, f"UDF error: {value!r}", value)
                    out.append(key, (ERROR,), diff)
        return out


class FilterNode(Node):
    def __init__(self, scope: "Scope", source: Node, condition_col: int) -> None:
        super().__init__(scope, [source], source.arity)
        self.condition_col = condition_col

    def process(self, time: int) -> DeltaBatch:
        batch = self.take_raw(0)
        if not (batch._insert_only or batch._raw_insert_only):
            batch = batch.consolidate()
        c = self.condition_col
        if batch._insert_only or batch._raw_insert_only:
            payload = batch.columns
            if payload is not None:
                cond = payload.cols[c]
                if cond.dtype.kind == "b":
                    # columnar mask-compress: keys/cols stay arrays
                    out = DeltaBatch.from_columns(
                        payload.compress(cond),
                        consolidated=batch._insert_only,
                        insert_only=batch._insert_only,
                    )
                    out._raw_insert_only = (
                        batch._raw_insert_only or out._insert_only
                    )
                    return out
            from pathway_tpu.native import kernels as _native

            if _native is not None:
                kept = _native.filter_truthy(batch.entries, c)
                if kept is not None:  # all-bool conditions, no errors
                    out = DeltaBatch()
                    out.entries = kept
                    out._consolidated = batch._insert_only
                    out._insert_only = batch._insert_only
                    out._raw_insert_only = True
                    return out
            if not any(is_error(e[1][c]) for e in batch.entries):
                # C-speed comprehension: no retractions, no error conditions
                out = DeltaBatch()
                out.entries = [e for e in batch.entries if e[1][c]]
                out._consolidated = batch._insert_only
                out._insert_only = batch._insert_only
                out._raw_insert_only = True
                return out
            batch = batch.consolidate()  # ERROR rows: exact row semantics
        out = DeltaBatch()
        state = self.current  # hoisted: drains lazy state once
        for key, row, diff in batch:
            if diff < 0:
                if key in state:
                    out.append(key, state[key], diff)
                continue
            cond = row[self.condition_col]
            if is_error(cond):
                self.report(key, "error value in filter condition")
                continue
            if cond:
                out.append(key, row, diff)
        return out


class ConcatNode(Node):
    """Disjoint union of universes (reference: concat_tables)."""

    def __init__(self, scope: "Scope", sources: Sequence[Node]) -> None:
        arity = sources[0].arity
        assert all(s.arity == arity for s in sources)
        super().__init__(scope, list(sources), arity)

    def _columnar_bulk(self, batches: list[DeltaBatch]) -> DeltaBatch | None:
        """Cold-state pure-insert concat: stack the columnar payloads and
        screen cross-input key uniqueness vectorized — the bulk-load path
        with zero per-row objects. None falls back to the row loop."""
        from pathway_tpu.engine.batch import Columns

        if self._state or self._state_lag:
            return None  # membership checks against prior keys: row path
        payloads = []
        for b in batches:
            if not b:
                continue
            if b.columns is None or not (
                b._insert_only or b._raw_insert_only
            ):
                return None
            payloads.append(b.columns)
        if not payloads:
            return DeltaBatch()
        stacked = (
            payloads[0] if len(payloads) == 1 else Columns.concat(payloads)
        )
        if stacked is None or stacked.diffs is not None:
            return None
        try:
            kb = stacked.kbytes()
        except (OverflowError, TypeError):
            return None
        if kb is None or not _keys_unique(
            np.ascontiguousarray(kb), stacked.n
        ):
            return None  # duplicate keys need the reporting row path
        out = DeltaBatch.from_columns(
            stacked, consolidated=True, insert_only=True
        )
        return out

    def process(self, time: int) -> DeltaBatch:
        batches = [
            self.take_raw(port) for port in range(len(self.inputs))
        ]
        fast = self._columnar_bulk(batches)
        if fast is not None:
            return fast
        out = DeltaBatch()
        seen = set(self.current)
        for batch in batches:
            for key, row, diff in batch.consolidate():
                if diff > 0:
                    if key in seen:
                        self.report(key, "duplicate key in concat")
                        continue
                    seen.add(key)
                else:
                    seen.discard(key)
                out.append(key, row, diff)
        return out.consolidate()


class ReindexNode(Node):
    """Re-key a table by a pointer column (reindex / with_id / with_id_from)."""

    def __init__(self, scope: "Scope", source: Node, key_col: int) -> None:
        super().__init__(scope, [source], source.arity)
        self.key_col = key_col

    def process(self, time: int) -> DeltaBatch:
        batch = self.take(0)
        out = DeltaBatch()
        for key, row, diff in batch:
            new_key = row[self.key_col]
            if is_error(new_key) or not isinstance(new_key, Pointer):
                self.report(key, f"reindex id must be a pointer, got {new_key!r}")
                continue
            out.append(new_key, row, diff)
        return out.consolidate()


class KeyFilterNode(Node):
    """intersect / subtract / restrict — filter rows by other tables' key sets."""

    def __init__(
        self, scope: "Scope", source: Node, others: Sequence[Node], mode: str
    ) -> None:
        super().__init__(scope, [source, *others], source.arity)
        assert mode in ("intersect", "subtract", "restrict")
        self.mode = mode

    def _member_in(self, key: Pointer, other_states: list[dict]) -> bool:
        if self.mode == "subtract":
            return not any(key in s for s in other_states)
        return all(key in s for s in other_states)

    def process(self, time: int) -> DeltaBatch:
        source = self.inputs[0]
        src_batch = self.take(0)
        # membership deltas from the other sides
        affected: set[Pointer] = set()
        for port in range(1, len(self.inputs)):
            for key, _row, _diff in self.take(port):
                affected.add(key)
        out = DeltaBatch()
        handled: set[Pointer] = set()
        for key, row, diff in src_batch:
            handled.add(key)
        # hoisted property reads: drain each lazy state once, not per row
        state = self.current
        others = [o.current for o in self.inputs[1:]]
        src_state = source.current if affected else None
        # keys whose membership may flip (and are not already being updated)
        for key in affected - handled:
            row = src_state.get(key)
            was = key in state
            now = row is not None and self._member_in(key, others)
            if was and not now:
                out.append(key, state[key], -1)
            elif not was and now and row is not None:
                out.append(key, row, 1)
        for key, row, diff in src_batch:
            if diff < 0:
                if key in state:
                    out.append(key, state[key], -1)
            else:
                if self._member_in(key, others):
                    out.append(key, row, 1)
        return out.consolidate()


class OverrideUniverseNode(Node):
    """Pass-through after a universe promise (override_table_universe)."""

    def __init__(self, scope: "Scope", source: Node) -> None:
        super().__init__(scope, [source], source.arity)

    def process(self, time: int) -> DeltaBatch:
        return self.take(0)


class InputMirrors:
    """Own per-port input-state mirrors for state-peeking operators.

    Under sharded execution a local input REPLICA's ``current`` holds the
    shard of the keys IT processed, which diverges from the consumer's
    shard whenever an upstream reindex changed keys — so sharded scopes
    read OWN mirrors built from the batches routed here by row key.
    Single-worker scopes read the input's complete ``current`` directly
    (no memory duplication)."""

    def _init_mirrors(self) -> None:
        self._mirrors: list[dict] = [{} for _ in self.inputs]

    def _input_state(self, port: int) -> dict:
        if self.scope.sharded:
            return self._mirrors[port]
        return self.inputs[port].current

    def _absorb(self, port: int, batch: DeltaBatch) -> None:
        if self.scope.sharded:
            apply_batch_to_state(self._mirrors[port], batch)


class ZipNode(InputMirrors, Node):
    """Zip same-universe tables into one storage (column concatenation).

    The reference reaches the same goal by flattening same-universe columns
    into shared tuple storage (graph_runner/path_evaluator.py); here it is an
    explicit operator: a row is emitted once every input holds the key, so a
    base table zipped with tables over a superset universe restricts
    naturally.
    """

    STATE_ATTRS = ("_mirrors",)

    def __init__(self, scope: "Scope", sources: Sequence[Node]) -> None:
        super().__init__(scope, list(sources), sum(s.arity for s in sources))
        self._init_mirrors()

    def _combined(self, key: Pointer) -> tuple | None:
        parts = []
        for port in range(len(self.inputs)):
            row = self._input_state(port).get(key)
            if row is None:
                return None
            parts.append(row)
        return tuple(v for part in parts for v in part)

    def process(self, time: int) -> DeltaBatch:
        affected: set[Pointer] = set()
        for port in range(len(self.inputs)):
            batch = self.take(port)
            self._absorb(port, batch)
            for key, _row, _diff in batch:
                affected.add(key)
        out = DeltaBatch()
        state = self.current  # hoisted: drains lazy state once
        for key in affected:
            old = state.get(key)
            new = self._combined(key)
            if old is not None and rows_differ(old, new):
                out.append(key, old, -1)
            if new is not None and rows_differ(old, new):
                out.append(key, new, 1)
        return out


class JoinKind:
    INNER = "inner"
    LEFT = "left"
    RIGHT = "right"
    OUTER = "outer"


_JOIN_SALT = b"join"
_JOIN_LEFT_SALT = b"join-left"
_JOIN_RIGHT_SALT = b"join-right"


def join_result_key(lkey: Pointer | None, rkey: Pointer | None) -> Pointer:
    if lkey is not None and rkey is not None:
        return hash_values((lkey, rkey), salt=_JOIN_SALT)
    if lkey is not None:
        return hash_values((lkey,), salt=_JOIN_LEFT_SALT)
    assert rkey is not None
    return hash_values((rkey,), salt=_JOIN_RIGHT_SALT)


def _keys_unique(kb: np.ndarray, n: int) -> bool:
    """Vectorized uniqueness screen over (n,16) key bytes. Keys are
    uniform 128-bit content hashes, so low-64-bit uniqueness implies full
    uniqueness; only the ~n²/2⁶⁵ collision case pays the full check."""
    if n < 2:
        return True
    lo = np.sort(np.ascontiguousarray(kb[:, :8]).view(np.uint64).ravel())
    if not (lo[1:] == lo[:-1]).any():
        return True
    v = np.ascontiguousarray(kb).view(np.dtype((np.void, 16))).ravel()
    return len(np.unique(v)) == n


class _JoinSide:
    """One side's rows in columnar form: join-key arrays (one per key
    column), key bytes, and the full column set (object arrays where a
    column isn't clean). Unified-dtype key casts and the NaN screen are
    cached per side AND per key column, so probing a long-lived block
    costs the cast/scan once, not once per commit."""

    __slots__ = (
        "n", "jks", "kb", "cols", "dev_jks", "_jk_int", "_jk_f64", "_nan"
    )

    def __init__(self, n, jks, kb, cols, dev_jks=None) -> None:
        self.n = n
        self.jks = jks
        self.kb = kb
        self.cols = cols
        #: device twins of the join-key arrays (one per key column, or
        #: None) — set only when the batch arrived device-resident with
        #: int64 keys, so the device matcher can skip the H2D re-upload
        self.dev_jks = dev_jks
        self._jk_int: dict[int, np.ndarray] = {}
        self._jk_f64: dict[int, Any] = {}  # False = not representable
        self._nan: dict[int, bool] = {}

    def jk_has_nan(self, i: int = 0) -> bool:
        got = self._nan.get(i)
        if got is None:
            jk = self.jks[i]
            got = self._nan[i] = (
                jk.dtype.kind == "f" and bool(np.isnan(jk).any())
            )
        return got

    def jk_int(self, i: int = 0) -> np.ndarray:
        got = self._jk_int.get(i)
        if got is None:
            jk = self.jks[i]
            got = self._jk_int[i] = (
                jk if jk.dtype == np.int64 else jk.astype(np.int64)
            )
        return got

    def jk_f64(self, i: int = 0) -> np.ndarray | None:
        got = self._jk_f64.get(i)
        if got is None:
            jk = self.jks[i]
            if jk.dtype.kind == "i" and jk.size:
                amax = int(np.abs(jk).max())
                if amax < 0 or amax > _JOIN_FLOAT_EXACT:
                    self._jk_f64[i] = False  # would round in float64
                    return None
            cast = jk if jk.dtype == np.float64 else jk.astype(np.float64)
            got = self._jk_f64[i] = (
                False if bool(np.isnan(cast).any()) else cast
            )
        return None if got is False else got


_JOIN_FLOAT_EXACT = 1 << 53


def _device_ops_active():
    """The device_ops module when the JAX operator kernels may engage,
    else None.  The disabled case is one cached env check — the PR-2
    zero-overhead discipline for escape-hatched machinery."""
    from pathway_tpu.engine import device_ops as _dops

    return _dops if _dops.enabled() else None


def _unify_join_col(a: "_JoinSide", b: "_JoinSide", i: int):
    """Key column ``i`` of two sides cast to one comparison dtype matching
    Python dict-key equality (True == 1 == 1.0), or None when vectorized
    equality would diverge (NaN identity, huge ints in float64, or
    cross-kind pairs like str vs int — route those to the dict path)."""
    ajk, bjk = a.jks[i], b.jks[i]
    ka, kb_ = ajk.dtype.kind, bjk.dtype.kind
    if ka == kb_:
        if ka == "f" and (a.jk_has_nan(i) or b.jk_has_nan(i)):
            return None
        return ajk, bjk
    kinds = {ka, kb_}
    if kinds <= {"b", "i"}:
        return a.jk_int(i), b.jk_int(i)
    if kinds <= {"b", "i", "f"}:
        a2, b2 = a.jk_f64(i), b.jk_f64(i)
        if a2 is None or b2 is None:
            return None
        return a2, b2
    return None


def _unify_join_keys(a: "_JoinSide", b: "_JoinSide"):
    """Per-key-column unification: (left arrays, right arrays) or None."""
    left: list[np.ndarray] = []
    right: list[np.ndarray] = []
    for i in range(len(a.jks)):
        uni = _unify_join_col(a, b, i)
        if uni is None:
            return None
        left.append(uni[0])
        right.append(uni[1])
    return left, right


def _match_join_pairs(la: np.ndarray, ra: np.ndarray):
    """Index pairs (l_idx, r_idx) of all equal-key matches — a sort-based
    hash-join core; the smaller side becomes the sorted haystack."""
    empty = np.empty(0, np.int64)
    if len(la) == 0 or len(ra) == 0:
        return empty, empty
    if len(ra) > len(la):
        r_idx, l_idx = _match_join_pairs(ra, la)
        return l_idx, r_idx
    order = np.argsort(ra, kind="stable")
    rs = ra[order]
    lo = np.searchsorted(rs, la, "left")
    hi = np.searchsorted(rs, la, "right")
    counts = hi - lo
    total = int(counts.sum())
    if total == 0:
        return empty, empty
    l_idx = np.repeat(np.arange(len(la)), counts)
    starts = np.repeat(lo, counts)
    csum = np.cumsum(counts) - counts
    offs = np.arange(total) - np.repeat(csum, counts)
    return l_idx, order[starts + offs]


def _as_match_codes(arr: np.ndarray) -> np.ndarray | None:
    """Reinterpret a join-key column as int64 codes whose equality is
    exactly the column's value equality, or ``None`` when no such view
    exists. Integers widen losslessly; uint64 reinterprets bitwise (a
    bijection, so equality is preserved); floats widen to float64 (exact
    for every narrower float), normalise -0.0 to +0.0 via ``+ 0.0``, and
    reinterpret bits — sound only when NaN-free, since bit equality would
    call equal-bit NaNs a match."""
    k = arr.dtype.kind
    if k in "bi":
        return np.ascontiguousarray(arr, np.int64)
    if k == "u":
        if arr.dtype.itemsize == 8:
            return np.ascontiguousarray(arr).view(np.int64)
        return np.ascontiguousarray(arr, np.int64)
    if k == "f":
        f = np.ascontiguousarray(arr, np.float64) + 0.0
        if np.isnan(f).any():
            return None
        return f.view(np.int64)
    return None


def _match_join_pairs_multi(
    l_arrays: "list[np.ndarray]", r_arrays: "list[np.ndarray]"
):
    """Multi-column join matching: reduce key TUPLES to joint integer
    codes (factorized over the concatenation of both sides, so equal
    tuples get equal codes across sides), then run the single-array
    sort-based matcher. Columns arrive already dtype-unified.

    With the native kernels loaded and every key column int64-codeable,
    one hash-table kernel replaces the factorize + argsort + searchsorted
    pipeline; its output ordering (probe index ascending, build index
    ascending within a probe row) is the sort-based matcher's ordering,
    so the paths are interchangeable pair for pair."""
    from pathway_tpu.native import kernels as _native

    if _native is not None and hasattr(_native, "match_pairs_i64"):
        lc = [_as_match_codes(a) for a in l_arrays]
        if all(c is not None for c in lc):
            rc = [_as_match_codes(a) for a in r_arrays]
            if all(c is not None for c in rc):
                return _native.match_pairs_i64(lc, rc)
    from pathway_tpu.engine.device import factorize_multi

    if len(l_arrays) == 1:
        return _match_join_pairs(l_arrays[0], r_arrays[0])
    nl = len(l_arrays[0])
    both = [
        np.concatenate([la, ra]) for la, ra in zip(l_arrays, r_arrays)
    ]
    _first, inverse = factorize_multi(both)
    return _match_join_pairs(inverse[:nl], inverse[nl:])


def _hash_join_pairs_py(lkb: np.ndarray, rkb: np.ndarray) -> np.ndarray:
    """Python fallback for the vectorized join_result_key derivation."""
    import hashlib

    n = len(lkb)
    out = np.empty((n, 16), np.uint8)
    lmem, rmem = lkb.tobytes(), rkb.tobytes()
    for i in range(n):
        h = hashlib.blake2b(digest_size=16, person=b"pw-tpu-key")
        h.update(
            b"join\x04"
            + lmem[i * 16 : i * 16 + 16]
            + b"\x04"
            + rmem[i * 16 : i * 16 + 16]
        )
        out[i] = np.frombuffer(h.digest(), np.uint8)
    return out


def _entry_keys_bytes_py(entries: list) -> np.ndarray | None:
    if any(type(e[0]) is not Pointer for e in entries):
        return None
    buf = b"".join(int(e[0]).to_bytes(16, "little") for e in entries)
    return np.frombuffer(buf, np.uint8).reshape(len(entries), 16)


class JoinNode(Node):
    """Equality join with incremental per-group recomputation.

    Output rows are ``left_row + right_row`` with ``None`` padding on the
    unmatched side for outer kinds; result ids derive from the source ids
    (reference: join_tables python_api.rs:2986, dataflow join at
    dataflow.rs:2320+). ``id_from_left`` keeps the left row id (used by
    id-preserving joins such as ``ix``-style lookups and asof_now joins).

    Single-key inner joins run fully columnar while their input stays
    insert-only: arrangements are kept as columnar blocks, each commit is
    one sort-based NumPy hash join plus a vectorized BLAKE2b pass for the
    result keys, and the output is a columnar batch (no per-row Python
    objects). The first batch that needs exact row semantics (retraction,
    outer kind, exotic key) materialises the blocks into the dict
    arrangements once and the incremental row path takes over.
    """

    STATE_ATTRS = ("left_arr", "right_arr")

    def __init__(
        self,
        scope: "Scope",
        left: Node,
        right: Node,
        left_on: Sequence[int],
        right_on: Sequence[int],
        kind: str = JoinKind.INNER,
        id_from_left: bool = False,
        left_keys_repeat: bool = True,
        id_spec: tuple | None = None,
    ) -> None:
        super().__init__(scope, [left, right], left.arity + right.arity)
        self.left_on = list(left_on)
        self.right_on = list(right_on)
        self.kind = kind
        #: result-id source: None -> pair hash; ("left"/"right", None) ->
        #: that side's row key; ("left"/"right", col) -> that side's
        #: pointer column (reference join id= assignment)
        if id_spec is None and id_from_left:
            id_spec = ("left", None)
        self.id_spec = id_spec
        self.id_from_left = id_spec == ("left", None)
        # join-key → {row_key: row}
        self.left_arr: dict[Any, dict[Pointer, tuple]] = {}
        self.right_arr: dict[Any, dict[Pointer, tuple]] = {}
        # columnar arrangements (lists of _JoinSide blocks), active until
        # a batch forces the dict path
        self._blocks_left: list[_JoinSide] = []
        self._blocks_right: list[_JoinSide] = []
        #: custom-id joins: result id -> owning join-key group, so
        #: duplicate ids are caught ACROSS groups, not only within one;
        #: suppressed contenders wait in _id_waiters and are re-examined
        #: when the owner releases the id
        self._id_owners: dict[Pointer, Any] = {}
        self._id_waiters: dict[Pointer, set] = {}
        self._columnar_ok = (
            kind == JoinKind.INNER
            and id_spec is None
            and len(self.left_on) >= 1
            and len(self.left_on) == len(self.right_on)
        )

    def _okey(
        self,
        lk: Pointer | None,
        rk: Pointer | None,
        lrow: tuple | None,
        rrow: tuple | None,
        report: bool = True,
    ) -> Pointer:
        """Result row id per id_spec; an id_spec pointing at a side that
        is absent (outer padding) falls back to the pair hash.
        ``report=False`` on snapshot passes (old-state recomputation) so
        one bad row is reported once per batch, not once per pass."""
        spec = self.id_spec
        if spec is not None:
            side, col = spec
            v: Any = None
            if side == "left" and lk is not None:
                v = lk if col is None else lrow[col]
            elif side == "right" and rk is not None:
                v = rk if col is None else rrow[col]
            if isinstance(v, Pointer):
                return v
            if v is not None or (
                side == "left" and lk is not None
            ) or (side == "right" and rk is not None):
                # None / non-pointer id value: poison, don't emit a
                # non-Pointer row key into the dataflow
                if report:
                    self.report(
                        lk if lk is not None else rk,
                        f"join id= value is not a pointer: {v!r}",
                    )
                return None  # caller drops the row
        return join_result_key(lk, rk)

    # -- columnar fast path -------------------------------------------------

    def _side_from_batch(
        self, batch: DeltaBatch, on_cols: Sequence[int], arity: int
    ) -> _JoinSide | None:
        from pathway_tpu.engine import device
        from pathway_tpu.native import kernels as _native

        n = len(batch)
        if n == 0:
            return _JoinSide(0, None, None, [])
        payload = batch.columns
        if payload is not None:
            if payload.diffs is not None and not (payload.diffs == 1).all():
                return None
            jks = [payload.cols[c] for c in on_cols]
            if any(jk.dtype.kind not in "bifU" for jk in jks):
                return None
            try:
                kb = payload.kbytes()
            except (OverflowError, TypeError):
                return None
            if kb is None:
                return None
            if not batch._insert_only and not _keys_unique(kb, n):
                return None
            # a device-resident delivery with a single int64 key column
            # carries a device twin of the join keys: the matcher can
            # consume it in place of re-uploading (int64 only — float
            # code derivation normalises bits, so twins there are unsafe
            # and match_pairs re-validates by object identity anyway)
            dev_jks = None
            if (
                len(on_cols) == 1
                and jks[0].dtype == np.int64
                and getattr(payload, "resident", None) is not None
                and payload.resident()
            ):
                try:
                    twin = payload.device_column(on_cols[0])
                except Exception:  # noqa: BLE001 — host keys are the spec
                    from pathway_tpu.engine import device_ops as _dops

                    _dops.record_error("device_column")
                    twin = None
                if twin is not None:
                    dev_jks = [twin]
            return _JoinSide(
                n, jks, kb, list(payload.cols), dev_jks=dev_jks
            )
        entries = batch.entries
        if _native is not None and hasattr(_native, "entries_to_side"):
            # one pass over the rows screens diffs/keys and fills every
            # column typed (int64/float64/bool) or exact-object — no
            # ColumnarView scan, no per-column list comprehension
            got = _native.entries_to_side(
                entries, list(on_cols), arity, Pointer
            )
            if got is not None:
                kb, cols = got
                if not batch._insert_only and not _keys_unique(kb, n):
                    return None
                return _JoinSide(n, [cols[c] for c in on_cols], kb, cols)
        view = device.ColumnarView(entries, from_entries=True)
        jks = []
        for c in on_cols:
            jk = view.column(c)
            if jk is None or jk.dtype.kind not in "bifU":
                return None
            jks.append(jk)
        if _native is not None:
            diffs = _native.entry_diffs(entries)
            if not (diffs == 1).all():
                return None
            kb = _native.entry_keys_bytes(entries, Pointer)
        else:
            if any(e[2] != 1 for e in entries):
                return None
            kb = _entry_keys_bytes_py(entries)
        if kb is None:
            return None
        if not batch._insert_only and not _keys_unique(kb, n):
            # _raw_insert_only skipped the consolidate uniqueness scan;
            # duplicate (key,row) pairs would collapse lossily at the
            # dict-arrangement handover, so screen keys here
            return None
        cols = []
        for c in range(arity):
            col = view.column(c)
            if col is None:
                arr = np.empty(n, object)
                arr[:] = [e[1][c] for e in entries]
                col = arr
            cols.append(col)
        return _JoinSide(n, jks, kb, cols)

    def _emit_part(
        self,
        lside: _JoinSide,
        rside: _JoinSide,
        l_idx: np.ndarray,
        r_idx: np.ndarray,
    ):
        from pathway_tpu.engine.batch import Columns
        from pathway_tpu.native import kernels as _native

        lkb = np.ascontiguousarray(lside.kb[l_idx])
        rkb = np.ascontiguousarray(rside.kb[r_idx])

        def pair_keys() -> np.ndarray:
            # the vectorized BLAKE2b pass over the pair keys is the join's
            # single biggest fixed cost — run it only when the output keys
            # are actually observed (sink, state read, downstream keying)
            if _native is not None:
                return _native.hash_join_pairs(lkb, rkb)
            return _hash_join_pairs_py(lkb, rkb)

        cols = [c[l_idx] for c in lside.cols] + [
            c[r_idx] for c in rside.cols
        ]
        return Columns(len(l_idx), cols, kb_thunk=pair_keys)

    def _process_columnar_inner(
        self, left_batch: DeltaBatch, right_batch: DeltaBatch
    ) -> DeltaBatch | None:
        """Bilinear delta join over columnar blocks:
        ``ΔL⋈ΔR + ΔL⋈R + L⋈ΔR``. None → caller falls back to the dict
        path (state untouched: all screens run before any block append)."""
        from pathway_tpu.engine.batch import Columns

        ls = self._side_from_batch(
            left_batch, self.left_on, self.inputs[0].arity
        )
        rs = self._side_from_batch(
            right_batch, self.right_on, self.inputs[1].arity
        )
        if ls is None or rs is None:
            return None
        plan: list[tuple[_JoinSide, _JoinSide]] = []
        if rs.n:
            plan.extend((blk, rs) for blk in self._blocks_left)
        if ls.n:
            plan.extend((ls, blk) for blk in self._blocks_right)
        if ls.n and rs.n:
            plan.append((ls, rs))
        matches = []
        # measurement-driven placement of the pair matcher: the device
        # matcher is pair-for-pair identical to the host one, so the
        # choice is pure economics (observed ns/row each side)
        _dops = _device_ops_active() if plan else None
        use_device = False
        t0_ns = 0
        if _dops is not None:
            from pathway_tpu.optimize.placement import POLICY

            match_rows = sum(l.n + r.n for l, r in plan)
            t0_ns = _time.perf_counter_ns()
            use_device = POLICY.choose("join", self.index, match_rows)
        for l, r in plan:
            uni = _unify_join_keys(l, r)
            if uni is None:
                return None
            got = None
            if use_device:
                # hand the matcher any device key twins whose host array
                # IS the unified array (identity — unification that cast
                # or copied invalidates the twin)
                l_dev = r_dev = None
                if (
                    l.dev_jks is not None
                    and len(l.jks) == 1
                    and uni[0][0] is l.jks[0]
                ):
                    l_dev = l.dev_jks[0]
                if (
                    r.dev_jks is not None
                    and len(r.jks) == 1
                    and uni[1][0] is r.jks[0]
                ):
                    r_dev = r.dev_jks[0]
                try:
                    got = _dops.match_pairs(
                        uni[0], uni[1], l_dev=l_dev, r_dev=r_dev
                    )
                except Exception:  # noqa: BLE001 — host matcher is the spec
                    _dops.record_error("match_pairs")
                    got = None
            if got is None:
                l_idx, r_idx = _match_join_pairs_multi(*uni)
            else:
                l_idx, r_idx = got
            if len(l_idx):
                matches.append((l, r, l_idx, r_idx))
        if _dops is not None:
            POLICY.record(
                "join",
                self.index,
                use_device,
                match_rows,
                _time.perf_counter_ns() - t0_ns,
            )
        # all screens passed: commit the block appends, then emit
        if ls.n:
            self._blocks_left.append(ls)
        if rs.n:
            self._blocks_right.append(rs)
        parts = [
            self._emit_part(l, r, l_idx, r_idx)
            for l, r, l_idx, r_idx in matches
        ]
        if not parts:
            return DeltaBatch()
        payload = parts[0] if len(parts) == 1 else Columns.concat(parts)
        if payload is not None:
            return DeltaBatch.from_columns(
                payload, consolidated=True, insert_only=True
            )
        # cross-part dtype drift: materialise rows (correct, slower)
        out = DeltaBatch()
        for p in parts:
            out.entries.extend(
                DeltaBatch.from_columns(p, consolidated=True).entries
            )
        out._consolidated = True
        out._insert_only = True
        return out

    def _ensure_dict_arrangements(self) -> None:
        """Materialise columnar blocks into the dict arrangements (once),
        handing over to the incremental row path."""
        if not self._columnar_ok:
            return
        self._columnar_ok = False
        self._materialize_blocks_into(self.left_arr, self.right_arr)
        self._blocks_left.clear()
        self._blocks_right.clear()

    def _materialize_blocks_into(self, left_arr: dict, right_arr: dict) -> None:
        from pathway_tpu.engine.batch import Columns

        for blocks, arr in (
            (self._blocks_left, left_arr),
            (self._blocks_right, right_arr),
        ):
            for side in blocks:
                entries = Columns(
                    side.n, side.cols, kbytes=side.kb
                ).to_entries()
                jk_lists = zip(*(a.tolist() for a in side.jks))
                for (key, row, _d), jkv in zip(entries, jk_lists):
                    arr.setdefault(jkv, {})[key] = row

    def op_state(self) -> dict:
        # snapshot a dict VIEW of the arrangements without degrading the
        # live columnar blocks (mirrors GroupbyNode.op_state)
        state = {"current": dict(self.current)}
        if self._columnar_ok and (self._blocks_left or self._blocks_right):
            left: dict = {k: dict(v) for k, v in self.left_arr.items()}
            right: dict = {k: dict(v) for k, v in self.right_arr.items()}
            self._materialize_blocks_into(left, right)
            state["left_arr"] = left
            state["right_arr"] = right
        else:
            state["left_arr"] = self.left_arr
            state["right_arr"] = self.right_arr
        return state

    def restore_op_state(self, state: dict) -> None:
        super().restore_op_state(state)
        self._blocks_left.clear()
        self._blocks_right.clear()
        if self.left_arr or self.right_arr:
            self._columnar_ok = False

    def _jk(self, row: tuple, cols: Sequence[int], key: Pointer) -> Any:
        vals = tuple(row[c] for c in cols)
        if any(is_error(v) for v in vals):
            self.report(key, "error value in join key")
            return ERROR
        try:
            hash(vals)
        except TypeError:
            vals = tuple(repr(v) for v in vals)
        return vals

    def _local_output(
        self, jk: Any, report: bool = True
    ) -> dict[Pointer, tuple]:
        lrows = self.left_arr.get(jk, {})
        rrows = self.right_arr.get(jk, {})
        out: dict[Pointer, tuple] = {}
        l_pad = (None,) * self.inputs[0].arity
        r_pad = (None,) * self.inputs[1].arity
        custom = self.id_spec is not None

        def put(okey: Pointer | None, row: tuple) -> None:
            if okey is None:
                return  # poisoned id value, reported in _okey
            if custom:
                owner = self._id_owners.get(okey, jk)
                if okey in out or owner != jk:
                    # the reference errors on duplicate result ids; here
                    # the row poisons via the error log (within AND
                    # across join-key groups) and the first row wins
                    if report:
                        self.report(okey, "duplicate join result id")
                        if owner != jk:
                            # remember the contender: if the owner ever
                            # releases the id, this group re-emits
                            self._id_waiters.setdefault(
                                okey, set()
                            ).add(jk)
                    return
            out[okey] = row

        if lrows and rrows:
            for lk, lrow in lrows.items():
                for rk, rrow in rrows.items():
                    put(
                        self._okey(lk, rk, lrow, rrow, report),
                        lrow + rrow,
                    )
        if self.kind in (JoinKind.LEFT, JoinKind.OUTER) or (
            self.id_from_left and self.kind != JoinKind.INNER
        ):
            if not rrows:
                for lk, lrow in lrows.items():
                    put(
                        self._okey(lk, None, lrow, None, report),
                        lrow + r_pad,
                    )
        if self.kind in (JoinKind.RIGHT, JoinKind.OUTER) and not self.id_from_left:
            if not lrows:
                for rk, rrow in rrows.items():
                    put(
                        self._okey(None, rk, None, rrow, report),
                        l_pad + rrow,
                    )
        return out

    def _process_insert_only_inner(
        self, left_batch: DeltaBatch, right_batch: DeltaBatch
    ) -> DeltaBatch | None:
        """Incremental inner-join fast path for insert-only deltas:
        ``ΔL⋈R + L⋈(R+ΔR)`` — no per-group recompute, no old/new diffing,
        no consolidation pass (result keys are unique pair hashes). This
        is the bulk-load hot path; the general path below handles
        retractions and outer kinds. Returns None (state untouched) for
        multiplicities > 1, which the pair-emitting loops and the dict
        arrangements cannot represent."""
        from pathway_tpu.native import kernels as _native

        if _native is not None:
            entries = _native.join_insert_inner(
                left_batch.entries,
                right_batch.entries,
                self.left_on,
                self.right_on,
                self.left_arr,
                self.right_arr,
                ERROR,
                Pointer,
                None,  # lazy node state: scheduler defers the application
                join_result_key,
            )
            if entries is not None:
                out = DeltaBatch()
                out.entries = entries
                out._consolidated = True
                out._insert_only = True
                return out
            # non-scalar / ERROR join keys: Python keeps exact semantics
        if any(e[2] != 1 for e in left_batch.entries) or any(
            e[2] != 1 for e in right_batch.entries
        ):
            return None
        out = DeltaBatch()
        append = out.entries.append
        # ΔR pairs with the PRE-delta left arrangement...
        for rkey, rrow, _diff in right_batch:
            jk = self._jk(rrow, self.right_on, rkey)
            if jk is ERROR:
                continue
            lrows = self.left_arr.get(jk)
            if lrows:
                for lk, lrow in lrows.items():
                    append((join_result_key(lk, rkey), lrow + rrow, 1))
            self.right_arr.setdefault(jk, {})[rkey] = rrow
        # ...then ΔL pairs with the post-delta right arrangement, so
        # ΔL×ΔR pairs appear exactly once
        for lkey, lrow, _diff in left_batch:
            jk = self._jk(lrow, self.left_on, lkey)
            if jk is ERROR:
                continue
            rrows = self.right_arr.get(jk)
            if rrows:
                for rk, rrow in rrows.items():
                    append((join_result_key(lkey, rk), lrow + rrow, 1))
            self.left_arr.setdefault(jk, {})[lkey] = lrow
        out._consolidated = True
        out._insert_only = True
        return out

    def process(self, time: int) -> DeltaBatch:
        # raw takes: the columnar path is multiset-correct, so the
        # consolidation scan is skipped entirely while it holds
        left_batch = self.take_raw(0)
        right_batch = self.take_raw(1)
        if self._columnar_ok:

            def insertish(b: DeltaBatch) -> bool:
                return b._raw_insert_only or b._insert_only or not b

            if not (insertish(left_batch) and insertish(right_batch)):
                # hint absent ≠ retractions present (e.g. a row-path
                # expression output): consolidation may prove the batch
                # insert-only and keep the columnar join alive
                left_batch = left_batch.consolidate()
                right_batch = right_batch.consolidate()
            if insertish(left_batch) and insertish(right_batch):
                out = self._process_columnar_inner(left_batch, right_batch)
                if out is not None:
                    return out
            # this batch needs exact row semantics: hand the columnar
            # blocks to the dict arrangements (once) and fall through
            self._ensure_dict_arrangements()
        left_batch = left_batch.consolidate()
        right_batch = right_batch.consolidate()
        fast = (
            self.kind == JoinKind.INNER
            and self.id_spec is None
            and (left_batch._insert_only or not left_batch)
            and (right_batch._insert_only or not right_batch)
        )
        if fast:
            out = self._process_insert_only_inner(left_batch, right_batch)
            if out is not None:
                return out
        affected: set[Any] = set()
        old_local: dict[Any, dict[Pointer, tuple]] = {}

        def note(jk: Any) -> None:
            if jk is not ERROR and jk not in old_local:
                # snapshot pass: suppress reports (the new-state pass
                # reports each problem exactly once per batch)
                old_local[jk] = self._local_output(jk, report=False)
                affected.add(jk)

        staged: list[tuple[int, Any, Pointer, tuple, int]] = []
        for key, row, diff in left_batch:
            jk = self._jk(row, self.left_on, key)
            note(jk)
            staged.append((0, jk, key, row, diff))
        for key, row, diff in right_batch:
            jk = self._jk(row, self.right_on, key)
            note(jk)
            staged.append((1, jk, key, row, diff))

        for side, jk, key, row, diff in staged:
            if jk is ERROR:
                continue
            arr = self.left_arr if side == 0 else self.right_arr
            group = arr.setdefault(jk, {})
            if diff > 0:
                group[key] = row
            else:
                group.pop(key, None)
                if not group:
                    arr.pop(jk, None)

        out = DeltaBatch()
        freed: list[Pointer] = []
        # custom-id joins must visit groups deterministically: with
        # duplicate result ids the winner is the first group PROCESSED,
        # and set order is per-process hash order (str hashes are salted)
        # — sorting pins the winner across runs, processes and insertion
        # orders
        if self.id_spec is not None:
            affected = sorted(affected, key=repr)
        for jk in affected:
            old = old_local[jk]
            new = self._local_output(jk)
            if self.id_spec is not None:
                for okey in old:
                    if okey not in new and self._id_owners.get(okey) == jk:
                        del self._id_owners[okey]
                        if okey in self._id_waiters:
                            freed.append(okey)
                for okey in new:
                    self._id_owners[okey] = jk
            for okey, orow in old.items():
                if okey not in new or rows_differ(new[okey], orow):
                    out.append(okey, orow, -1)
            for okey, orow in new.items():
                if okey not in old or rows_differ(old[okey], orow):
                    out.append(okey, orow, 1)
        # a released custom id hands over to a suppressed contender:
        # without this, the contender's row would stay missing until an
        # unrelated update happened to touch its join-key group
        for okey in freed:
            if self._id_owners.get(okey) is not None:
                continue  # re-claimed within this batch
            for jk in sorted(
                self._id_waiters.pop(okey, ()), key=repr
            ):
                if jk in affected:
                    continue  # its recompute already saw the free id
                candidate = self._local_output(jk, report=False)
                row = candidate.get(okey)
                if row is not None:
                    self._id_owners[okey] = jk
                    out.append(okey, row, 1)
                    break
        return out.consolidate()


def _groupby_batch_arrays(
    batch: DeltaBatch, by_cols: Sequence[int], sum_cols: Sequence[int]
):
    """Extract ``(by arrays, diffs, sum value arrays)`` for a vectorized
    groupby pass — shared by the columnar state machine and the
    degraded-mode vectorized path so their cleanliness screens can never
    diverge. Returns None whenever the batch is not cleanly columnar:
    mixed/object dtypes, NaN group values (np.unique collapses NaNs while
    the row path groups them by bit pattern), non-numeric sum columns."""
    from pathway_tpu.engine import device
    from pathway_tpu.native import kernels as _native

    cols = batch.columns
    if cols is not None:
        bys = [cols.cols[c] for c in by_cols]
        if any(by.dtype.kind not in "bifU" for by in bys):
            return None
        diffs = cols.diffs
        getcol = lambda c: cols.cols[c]  # noqa: E731
    else:
        entries = batch.entries
        view = device.ColumnarView(entries, from_entries=True)
        bys = []
        for c in by_cols:
            by = view.column(c)
            if by is None or by.dtype.kind not in "bifU":
                return None
            bys.append(by)
        if _native is not None:
            diffs = _native.entry_diffs(entries)
        else:
            diffs = np.fromiter(
                (d for _k, _r, d in entries), np.int64, len(entries)
            )
        getcol = view.column
    if any(
        by.dtype.kind == "f" and np.isnan(by).any() for by in bys
    ):
        return None
    vals = []
    for c in sum_cols:
        if c < 0:
            vals.append(None)
            continue
        col = getcol(c)
        if col is None or col.dtype.kind not in "bif":
            return None
        vals.append(col)
    if diffs is None:
        diffs = np.ones(len(bys[0]), np.int64)
    return bys, diffs, vals


def _factorize_bys(bys: "list[np.ndarray]"):
    """``(raw tuples, inverse)`` of the distinct by-value tuples in a
    batch — single-column keeps the cheap ``np.unique`` path."""
    from pathway_tpu.engine.device import factorize, factorize_multi

    if len(bys) == 1:
        uniq, inverse = factorize(bys[0])
        return [(v,) for v in uniq], inverse.reshape(-1)
    first, inverse = factorize_multi(bys)
    return list(zip(*(by[first].tolist() for by in bys))), inverse


class _ColumnarGroups:
    """Fully columnar group state for count/sum groupbys over clean by
    columns (one or several).

    Replaces the per-group Python objects (dict entry + reducer states +
    tuple rebuilds) with flat arrays: ``member`` (signed multiplicity) and
    one accumulator array per sum reducer, indexed by a dense group id.
    A streaming delta commit then costs one factorization (``np.unique``,
    composite codes for multi-by) + segment reductions + O(touched
    groups) array math — the reference's semigroup reducer update
    (src/engine/reduce.rs:78) at NumPy speed.

    Any batch the arrays cannot represent exactly (mixed/object dtypes,
    NaN group values, ERROR cells, int64 overflow risk) makes the owner
    degrade to the dict-of-states row path BEFORE any mutation, via
    :meth:`materialize`.
    """

    __slots__ = (
        "by_cols",
        "_single",
        "gkey_salt",
        "kinds",
        "sum_cols",
        "index",
        "by_raw",
        "gkeys",
        "member",
        "accs",
        "size",
    )

    _CAP0 = 1024

    def __init__(
        self,
        by_cols: Sequence[int],
        reducers: Sequence[tuple[Reducer, Sequence[int]]],
        gkey_salt: bytes = b"",
    ) -> None:
        from pathway_tpu.engine.reducers import ReducerKind

        self.by_cols = list(by_cols)
        self.gkey_salt = gkey_salt
        # single-by state stores bare scalars in index/by_raw (tuple
        # wrapping + tuple hashing per touched group measurably drags
        # the incremental hot path); multi-by stores value tuples
        self._single = len(self.by_cols) == 1
        self.kinds = [r.kind for r, _c in reducers]
        self.sum_cols = [
            cols[0] if r.kind == ReducerKind.SUM else -1
            for r, cols in reducers
        ]
        self.index: dict[Any, int] = {}  # normalised by-value(s) -> group id
        self.by_raw: list[Any] = []  # first-seen raw by-value(s) per group
        self.gkeys: list[Pointer] = []
        self.member = np.zeros(self._CAP0, np.int64)
        self.accs: list[np.ndarray | None] = [
            np.zeros(self._CAP0, np.int64) if c >= 0 else None
            for c in self.sum_cols
        ]
        self.size = 0

    @staticmethod
    def _norm_one(v: Any) -> Any:
        """Group-identity key matching hash_values equivalence: bools are
        tagged apart from ints, int-valued floats collapse onto ints."""
        if isinstance(v, bool):
            return ("\x01b", v)
        if isinstance(v, float) and -(2**63) < v < 2**63 and v == int(v):
            return int(v)
        return v

    def _norm(self, raw: Any) -> Any:
        """Raw by-value (scalar for single-by, tuple for multi-by) -> the
        index key under hash_values-equivalent identity."""
        if self._single:
            return self._norm_one(raw)
        return tuple(map(self._norm_one, raw))

    def _grow(self, need: int) -> None:
        cap = len(self.member)
        if need <= cap:
            return
        while cap < need:
            cap *= 2
        member = np.zeros(cap, np.int64)
        member[: self.size] = self.member[: self.size]
        self.member = member
        for i, acc in enumerate(self.accs):
            if acc is not None:
                grown = np.zeros(cap, acc.dtype)
                grown[: self.size] = acc[: self.size]
                self.accs[i] = grown

    def _batch_arrays(self, batch: DeltaBatch):
        """(by arrays, diffs, sum value arrays) or None when not cleanly
        columnar."""
        return _groupby_batch_arrays(batch, self.by_cols, self.sum_cols)

    def process_batch(self, batch: DeltaBatch, node: "GroupbyNode"):
        """Apply one delta batch; returns the output DeltaBatch, or None to
        signal degradation (state untouched)."""
        from pathway_tpu.engine import device
        from pathway_tpu.engine.batch import Columns
        from pathway_tpu.engine.reducers import ReducerKind

        got = self._batch_arrays(batch)
        if got is None:
            return None
        bys, diffs, vals = got
        n = len(bys[0])
        if n == 0:
            return DeltaBatch()
        dmax = int(np.abs(diffs).max()) if n else 0
        if dmax < 0:  # abs(INT64_MIN) wraps
            return None
        for col in vals:
            if col is not None and device.int_sum_overflow_risk(col, n, dmax):
                return None
        if self._single:
            raws, inverse = device.factorize(bys[0])
            inverse = inverse.reshape(-1)
        else:
            raws, inverse = _factorize_bys(bys)
        nu = len(raws)
        # device placement: launch the segment reductions as one batch of
        # device scatter-adds and fetch AFTER the group-id resolution loop
        # below, so the kernels overlap the host dict walk; any device
        # trouble falls back to the host kernels (the bit-exact spec)
        job = None
        gdiffs = None
        deltas: list[np.ndarray | None] = []
        gb_idx = node.index if isinstance(node.index, int) else -1
        t0_ns = 0
        _dops = _device_ops_active()
        if _dops is not None:
            from pathway_tpu.optimize.placement import POLICY

            t0_ns = _time.perf_counter_ns()
            if POLICY.choose("groupby", gb_idx, n):
                try:
                    job = _dops.segment_reduce_dispatch(
                        inverse, diffs, vals, nu
                    )
                except Exception:  # noqa: BLE001 — host kernels are the spec
                    _dops.record_error("segment_reduce")
                    job = None
        if job is None:
            gdiffs = device.segment_count(inverse, diffs, nu)
            for col in vals:
                deltas.append(
                    None
                    if col is None
                    else device.segment_sum(inverse, col, diffs, nu)
                )
            if _dops is not None:
                POLICY.record(
                    "groupby", gb_idx, False, n,
                    _time.perf_counter_ns() - t0_ns,
                )
        # resolve group ids (creating new groups), all before mutation
        index = self.index
        gis = np.empty(nu, np.int64)
        created: list[int] = []
        for i, raw in enumerate(raws):
            k = self._norm(raw)
            gi = index.get(k)
            if gi is None:
                gi = self.size
                self._grow(gi + 1)
                index[k] = gi
                self.by_raw.append(raw)
                # group id = ref_scalar(*by values) — addressable from
                # pointer_from / ix_ref like the reference (ref_scalar,
                # python_api.rs:3373; group_by_table :2922)
                self.gkeys.append(
                    hash_values(
                        (raw,) if self._single else raw,
                        salt=self.gkey_salt,
                    )
                )
                self.size = gi + 1
                created.append(i)
            gis[i] = gi
        if job is not None:
            # the scatter-adds ran while the dict walk above resolved
            # group ids; materialise their results now
            gdiffs, deltas = job.fetch()
            POLICY.record(
                "groupby", gb_idx, True, n,
                _time.perf_counter_ns() - t0_ns,
            )
        # int64 accumulator headroom: degrade before any mutation
        for ri, delta in enumerate(deltas):
            if delta is None:
                continue
            acc = self.accs[ri]
            if acc.dtype.kind == "i" and delta.dtype.kind != "f":
                amax_acc = int(np.abs(acc[gis]).max(initial=0))
                amax_d = int(np.abs(delta).max(initial=0))
                if amax_acc < 0 or amax_acc + amax_d > (1 << 62):
                    for i in created:  # roll back group creation
                        del index[self._norm(raws[i])]
                    del self.by_raw[self.size - len(created) :]
                    del self.gkeys[self.size - len(created) :]
                    self.size -= len(created)
                    return None
        for ri, delta in enumerate(deltas):
            if delta is None:
                continue
            if delta.dtype.kind == "f" and self.accs[ri].dtype.kind == "i":
                # float contributions arrive: upcast like Python int+float
                self.accs[ri] = self.accs[ri].astype(np.float64)
        old_member = self.member[gis].copy()
        old_accs = [
            self.accs[ri][gis].copy() if d is not None else None
            for ri, d in enumerate(deltas)
        ]
        self.member[gis] = old_member + gdiffs
        for ri, delta in enumerate(deltas):
            if delta is None:
                continue
            acc = self.accs[ri]
            acc[gis] = acc[gis] + delta.astype(acc.dtype, copy=False)
        new_member = self.member[gis]
        for i in np.flatnonzero(new_member <= 0).tolist():
            index.pop(self._norm(raws[i]), None)
        # a group emits only when its VISIBLE row changes (matching the row
        # path's old_row != new_row guard): membership flips always count;
        # count columns change with member, sum columns with the stored acc
        # (post-rounding — a float delta swallowed by rounding emits nothing)
        changed = (old_member > 0) != (new_member > 0)
        for ri, kind in enumerate(self.kinds):
            if kind == ReducerKind.COUNT:
                changed |= old_member != new_member
            else:
                changed |= old_accs[ri] != self.accs[ri][gis]
        m_old = (old_member > 0) & changed
        m_new = (new_member > 0) & changed
        n_out = int(m_old.sum()) + int(m_new.sum())
        if n_out == 0:
            self._maybe_compact()
            return DeltaBatch()
        gkeys = self.gkeys
        by_raw = self.by_raw

        n_by = len(self.by_cols)
        single = self._single

        def block(mask, member_vals, acc_vals):
            sel = np.flatnonzero(mask)
            sel_g = gis[sel].tolist()
            kobjs = list(map(gkeys.__getitem__, sel_g))
            by_vals = list(map(by_raw.__getitem__, sel_g))
            # densify when the by values are cleanly typed, so downstream
            # columnar consumers (hash join, expressions) stay columnar;
            # mixed/exotic values keep the exact object representation
            cols = []
            for j in range(n_by):
                col_vals = (
                    by_vals if single else [t[j] for t in by_vals]
                )
                byv = device._extract(col_vals)
                if byv is None:
                    byv = np.empty(len(col_vals), object)
                    byv[:] = col_vals
                cols.append(byv)
            for ri, kind in enumerate(self.kinds):
                if kind == ReducerKind.COUNT:
                    cols.append(member_vals[sel])
                else:
                    cols.append(acc_vals[ri][sel])
            return kobjs, cols

        ko_old, cols_old = block(m_old, old_member, old_accs)
        new_accs = [
            self.accs[ri][gis] if d is not None else None
            for ri, d in enumerate(deltas)
        ]
        ko_new, cols_new = block(m_new, new_member, new_accs)
        kobjs = ko_old + ko_new

        def cat(a, b):
            # empty placeholders must not promote the other side's dtype,
            # and MISMATCHED dense dtypes (int by-values one commit, str
            # the next) must not silently promote values (int64+<U would
            # stringify the retraction side) — exact objects instead
            if len(a) == 0:
                return b
            if len(b) == 0:
                return a
            if a.dtype == b.dtype:
                return np.concatenate([a, b])
            arr = np.empty(len(a) + len(b), object)
            arr[: len(a)] = a.tolist()
            arr[len(a) :] = b.tolist()
            return arr

        out_cols = [cat(a, b) for a, b in zip(cols_old, cols_new)]
        if ko_old:
            out_diffs = np.concatenate(
                [
                    np.full(len(ko_old), -1, np.int64),
                    np.ones(len(ko_new), np.int64),
                ]
            )
        else:
            # pure-insert commit (bulk load, fresh groups): diffs=None
            # marks the batch insert-only so downstream columnar
            # consumers (the hash join) take it without consolidation
            out_diffs = None
        payload = Columns(
            len(kobjs), out_cols, kobjs=kobjs, diffs=out_diffs
        )
        self._maybe_compact()
        return DeltaBatch.from_columns(
            payload, consolidated=True, insert_only=out_diffs is None
        )

    def _maybe_compact(self) -> None:
        """Reclaim array slots of dead groups (index entry popped, slot
        orphaned). Group-key churn otherwise grows state without bound;
        the row path's dict ``del`` frees dead groups eagerly."""
        live = len(self.index)
        if self.size <= 4096 or self.size <= 2 * live:
            return
        order = sorted(self.index.items(), key=lambda kv: kv[1])
        old_gis = np.fromiter((gi for _k, gi in order), np.int64, live)
        self.by_raw = [self.by_raw[gi] for gi in old_gis]
        self.gkeys = [self.gkeys[gi] for gi in old_gis]
        member = np.zeros(max(self._CAP0, len(self.member) // 2), np.int64)
        while len(member) < live:
            member = np.zeros(len(member) * 2, np.int64)
        member[:live] = self.member[old_gis]
        self.member = member
        for ri, acc in enumerate(self.accs):
            if acc is None:
                continue
            grown = np.zeros(len(member), acc.dtype)
            grown[:live] = acc[old_gis]
            self.accs[ri] = grown
        self.index = {k: i for i, (k, _gi) in enumerate(order)}
        self.size = live

    def materialize(self, node: "GroupbyNode") -> dict[Pointer, list[Any]]:
        """Convert to the row path's dict-of-states form (degradation)."""
        from pathway_tpu.engine.reducers import ReducerKind

        groups: dict[Pointer, list[Any]] = {}
        for k, gi in self.index.items():
            raw = self.by_raw[gi]
            by_vals = (raw,) if self._single else raw
            states = []
            for ri, (reducer, _cols) in enumerate(node.reducers):
                state = reducer.make_state()
                state.count = int(self.member[gi])
                if reducer.kind == ReducerKind.SUM:
                    acc = self.accs[ri][gi]
                    state.acc = (
                        int(acc) if acc.dtype.kind == "i" else float(acc)
                    )
                states.append(state)
            gkey = self.gkeys[gi]
            groups[gkey] = [by_vals, states, int(self.member[gi])]
            node._gkey_cache[(tuple(map(type, by_vals)), by_vals)] = gkey
        return groups


class GroupbyNode(Node):
    """Group-by with engine reducers.

    Output row layout: grouping values, then one value per reducer; the group
    id is ``ref_scalar(*grouping values)`` unless ``set_id`` names a pointer
    column to use directly (reference: group_by_table python_api.rs:2922).

    Single-by-column count/sum groupbys hold their state in
    :class:`_ColumnarGroups` arrays until a batch requires exact row-wise
    semantics; then the state degrades (once) to the dict-of-states form.
    """

    STATE_ATTRS = ("groups",)

    def __init__(
        self,
        scope: "Scope",
        source: Node,
        by_cols: Sequence[int],
        reducers: Sequence[tuple[Reducer, Sequence[int]]],
        set_id: bool = False,
        instance_last: bool = False,
    ) -> None:
        from pathway_tpu.engine.reducers import ReducerKind

        super().__init__(scope, [source], len(by_cols) + len(reducers))
        self.by_cols = list(by_cols)
        self.reducers = list(reducers)
        self.set_id = set_id
        # instance groupbys derive ids like ref_scalar(*vals, instance=i)
        # (salt=b"inst", engine/value.py:377-381) so pointer_from with
        # instance= addresses the groups.
        # COMPAT: earlier builds salted every group id with b"groupby";
        # those keys are unreachable under the current derivation, so an
        # operator snapshot written by such a build must be REJECTED at
        # restore, never loaded — persistence.py guards this with
        # STATE_FORMAT (restoring would strand every persisted group
        # under a key no new row can ever touch).
        self._gkey_salt = b"inst" if instance_last else b""
        # gkey -> [by_vals, [reducer states], membership count]
        self._groups: dict[Pointer, list[Any]] = {}
        self._cg: _ColumnarGroups | None = None
        if (
            not set_id
            and len(by_cols) >= 1
            and all(
                r.kind in (ReducerKind.COUNT, ReducerKind.SUM)
                for r, _c in reducers
            )
        ):
            self._cg = _ColumnarGroups(
                by_cols, reducers, gkey_salt=self._gkey_salt
            )
        # (types, by_vals) -> gkey: a streaming workload touches the same
        # groups commit after commit — the blake2b derivation dominated
        # the incremental-update bench at ~1024 touched groups x 100
        # commits. The cache key carries the value TYPES because dict
        # equality is coarser than the type-tagged digest (True == 1 but
        # hash_values distinguishes them).
        self._gkey_cache: dict[tuple, Pointer] = {}

    @property
    def groups(self) -> dict[Pointer, list[Any]]:
        if self._cg is not None:
            self._groups = self._cg.materialize(self)
            self._cg = None
        return self._groups

    @groups.setter
    def groups(self, value: dict[Pointer, list[Any]]) -> None:
        self._groups = value
        self._cg = None

    def op_state(self) -> dict:
        # snapshots (operator persistence) must not degrade the columnar
        # state: materialise a dict VIEW for the snapshot, keep _cg live
        state = {"current": dict(self.current)}
        state["groups"] = (
            self._cg.materialize(self) if self._cg is not None else self._groups
        )
        return state

    def _group_key(self, by_vals: tuple) -> Pointer:
        if self.set_id:
            assert len(by_vals) == 1 and isinstance(by_vals[0], Pointer)
            return by_vals[0]
        ck = (tuple(map(type, by_vals)), by_vals)
        try:
            gkey = self._gkey_cache.get(ck)
        except TypeError:  # unhashable by-values: derive directly
            return hash_values(by_vals, salt=self._gkey_salt)
        if gkey is None:
            gkey = hash_values(by_vals, salt=self._gkey_salt)
            self._gkey_cache[ck] = gkey
        return gkey

    def _group_row(self, entry: list[Any]) -> tuple:
        by_vals, states, _count = entry
        vals = []
        for (reducer, _cols), state in zip(self.reducers, states):
            vals.append(reducer.compute(state))
        return tuple(by_vals) + tuple(vals)

    def _process_columnar(self, batch: DeltaBatch) -> DeltaBatch | None:
        """Vectorized path for count/sum groupbys over clean by columns:
        per-row work collapses to factorization + segment reductions
        (engine/device.py), leaving only per-group Python. Falls back (None)
        whenever semantics would differ from the row-wise loop."""
        from pathway_tpu.engine import device
        from pathway_tpu.engine.reducers import ReducerKind

        if self.set_id or len(self.by_cols) < 1:
            return None
        for reducer, cols in self.reducers:
            if reducer.kind not in (ReducerKind.COUNT, ReducerKind.SUM):
                return None
        sum_col_idx = [
            cols[0] if r.kind == ReducerKind.SUM else -1
            for r, cols in self.reducers
        ]
        got = _groupby_batch_arrays(batch, self.by_cols, sum_col_idx)
        if got is None:
            return None
        bys, diffs, vals = got
        n = len(bys[0])
        dmax = int(np.abs(diffs).max()) if n else 0
        if dmax < 0:  # abs(INT64_MIN) wraps
            return None
        sum_arrays: dict[int, Any] = {}
        for ri, col in enumerate(vals):
            if col is None:
                continue
            if device.int_sum_overflow_risk(col, n, dmax):
                return None
            sum_arrays[ri] = col
        uniques, inverse = _factorize_bys(bys)
        n_groups = len(uniques)
        gdiffs = device.segment_count(inverse, diffs, n_groups)
        aggs: list[Any] = []
        for ri, (reducer, cols) in enumerate(self.reducers):
            if reducer.kind == ReducerKind.COUNT:
                aggs.append(None)
            else:
                aggs.append(
                    device.segment_sum(
                        inverse, sum_arrays[ri], diffs, n_groups
                    )
                )
        out = DeltaBatch()
        for gi, by_vals in enumerate(uniques):
            gkey = self._group_key(by_vals)
            entry = self.groups.get(gkey)
            old_row = self._group_row(entry) if entry is not None else None
            if entry is None:
                entry = [
                    by_vals,
                    [reducer.make_state() for reducer, _c in self.reducers],
                    0,
                ]
                self.groups[gkey] = entry
            gdiff = int(gdiffs[gi])
            entry[2] += gdiff
            for ri, ((reducer, _cols), state) in enumerate(
                zip(self.reducers, entry[1])
            ):
                state.count += gdiff
                if reducer.kind == ReducerKind.SUM:
                    delta = aggs[ri][gi].item()
                    state.acc = delta if state.acc is None else state.acc + delta
            new_row: tuple | None = None
            if entry[2] <= 0:
                del self.groups[gkey]
                self._gkey_cache.pop(
                    (tuple(map(type, by_vals)), by_vals), None
                )
            else:
                new_row = self._group_row(entry)
            if old_row is not None and old_row != new_row:
                out.append(gkey, old_row, -1)
            if new_row is not None and old_row != new_row:
                out.append(gkey, new_row, 1)
        return out.consolidate()

    def process(self, time: int) -> DeltaBatch:
        if self._cg is not None:
            # segment sums are diff-linear: duplicate / net-zero entries
            # contribute exactly their diff, so skip consolidation
            batch = self.take_raw(0)
            out = self._cg.process_batch(batch, self)
            if out is not None:
                return out
            # this batch needs exact row semantics: degrade the columnar
            # state to dict-of-states (once) and fall through
            self.groups  # noqa: B018 — property materialises + clears _cg
            batch = batch.consolidate()
        else:
            batch = self.take(0)
        if len(batch) >= VECTOR_THRESHOLD:
            fast = self._process_columnar(batch)
            if fast is not None:
                return fast
        touched: dict[Pointer, tuple | None] = {}
        for key, row, diff in batch:
            by_vals = tuple(row[c] for c in self.by_cols)
            if any(is_error(v) for v in by_vals):
                self.report(key, "error value in groupby key")
                continue
            gkey = self._group_key(by_vals)
            entry = self.groups.get(gkey)
            if gkey not in touched:
                touched[gkey] = self._group_row(entry) if entry is not None else None
            if entry is None:
                entry = [
                    by_vals,
                    [reducer.make_state() for reducer, _c in self.reducers],
                    0,
                ]
                self.groups[gkey] = entry
            entry[2] += diff
            for (reducer, cols), state in zip(self.reducers, entry[1]):
                args = tuple(row[c] for c in cols)
                reducer.update(state, args, diff, time)
        out = DeltaBatch()
        for gkey, old_row in touched.items():
            entry = self.groups.get(gkey)
            new_row: tuple | None = None
            if entry is not None:
                if entry[2] <= 0:
                    del self.groups[gkey]
                    bv = tuple(entry[0])
                    self._gkey_cache.pop((tuple(map(type, bv)), bv), None)
                else:
                    new_row = self._group_row(entry)
            if old_row is not None and old_row != new_row:
                out.append(gkey, old_row, -1)
            if new_row is not None and old_row != new_row:
                out.append(gkey, new_row, 1)
        return out.consolidate()


class DeduplicateNode(Node):
    """Keep one accepted row per instance (reference: deduplicate :2943).

    ``acceptor(new_value, old_value) -> bool`` decides whether a newly
    arriving row replaces the current one.
    """

    STATE_ATTRS = ("accepted",)

    def __init__(
        self,
        scope: "Scope",
        source: Node,
        value_col: int,
        instance_cols: Sequence[int],
        acceptor: Callable[[Any, Any], bool],
    ) -> None:
        super().__init__(scope, [source], source.arity)
        self.value_col = value_col
        self.instance_cols = list(instance_cols)
        self.acceptor = acceptor
        self.accepted: dict[Pointer, tuple] = {}  # gkey -> row

    def process(self, time: int) -> DeltaBatch:
        batch = self.take(0)
        out = DeltaBatch()
        for key, row, diff in batch:
            inst = tuple(row[c] for c in self.instance_cols)
            gkey = hash_values(inst, salt=b"dedup")
            prev = self.accepted.get(gkey)
            if diff > 0:
                new_val = row[self.value_col]
                if is_error(new_val):
                    self.report(key, "error value in deduplicate")
                    continue
                if prev is None:
                    accept = True
                else:
                    try:
                        accept = bool(self.acceptor(new_val, prev[self.value_col]))
                    except Exception as e:  # noqa: BLE001
                        self.report(key, f"error in deduplicate acceptor: {e}")
                        continue
                if accept:
                    if prev is not None:
                        out.append(gkey, prev, -1)
                    self.accepted[gkey] = row
                    out.append(gkey, row, 1)
            else:
                if prev is not None and not rows_differ(prev, row):
                    out.append(gkey, prev, -1)
                    del self.accepted[gkey]
        return out.consolidate()


class FlattenNode(Node):
    """Explode a sequence column into one row per element; with
    ``with_origin`` the source row id is appended as a final column
    (reference flatten origin_id)."""

    def __init__(
        self,
        scope: "Scope",
        source: Node,
        flat_col: int,
        with_origin: bool = False,
    ) -> None:
        super().__init__(scope, [source], source.arity + (1 if with_origin else 0))
        self.flat_col = flat_col
        self.with_origin = with_origin

    def _explode(self, key: Pointer, row: tuple) -> list[tuple[Pointer, tuple]]:
        value = row[self.flat_col]
        if is_error(value):
            self.report(key, "error value in flatten column")
            return []
        if value is None:
            return []
        try:
            elements = list(value)
        except TypeError:
            self.report(key, f"cannot flatten non-sequence {value!r}")
            return []
        out = []
        for i, element in enumerate(elements):
            new_key = hash_values((key, i), salt=b"flatten")
            new_row = row[: self.flat_col] + (element,) + row[self.flat_col + 1 :]
            if self.with_origin:
                new_row = new_row + (key,)
            out.append((new_key, new_row))
        return out

    def process(self, time: int) -> DeltaBatch:
        batch = self.take(0)
        out = DeltaBatch()
        for key, row, diff in batch:
            for new_key, new_row in self._explode(key, row):
                out.append(new_key, new_row, diff)
        return out.consolidate()


class SortNode(Node):
    """Maintains prev/next pointers per instance, sorted by a key column.

    Output row: ``(prev: Pointer|None, next: Pointer|None)`` keyed by the
    source row id (reference: add_prev_next_pointers,
    src/engine/dataflow/operators/prev_next.rs:770 — here recomputed per
    affected instance group, which preserves the output contract).
    """

    STATE_ATTRS = ("members",)

    def __init__(
        self, scope: "Scope", source: Node, key_col: int, instance_col: int | None
    ) -> None:
        super().__init__(scope, [source], 2)
        self.key_col = key_col
        self.instance_col = instance_col
        self.members: dict[Any, dict[Pointer, Any]] = {}  # instance -> {key: sortval}

    def _instance(self, row: tuple) -> Any:
        if self.instance_col is None:
            return None
        v = row[self.instance_col]
        try:
            hash(v)
        except TypeError:
            v = repr(v)
        return v

    def _ordered(self, inst: Any) -> list[Pointer]:
        rows = self.members.get(inst, {})
        items = list(rows.items())
        try:
            # None sorts first; natural order within non-None values
            items.sort(key=lambda kv: (kv[1] is not None, kv[1], int(kv[0]))
                       if kv[1] is not None else (False, 0, int(kv[0])))
        except TypeError:
            # incomparable mix: deterministic fallback by type name + repr
            items.sort(
                key=lambda kv: (
                    kv[1] is not None,
                    type(kv[1]).__name__,
                    repr(kv[1]),
                    int(kv[0]),
                )
            )
        return [k for k, _v in items]

    def _local(self, inst: Any) -> dict[Pointer, tuple]:
        ordered = self._ordered(inst)
        out: dict[Pointer, tuple] = {}
        for i, k in enumerate(ordered):
            prev = ordered[i - 1] if i > 0 else None
            nxt = ordered[i + 1] if i < len(ordered) - 1 else None
            out[k] = (prev, nxt)
        return out

    def process(self, time: int) -> DeltaBatch:
        batch = self.take(0)
        old: dict[Any, dict[Pointer, tuple]] = {}
        for key, row, diff in batch:
            inst = self._instance(row)
            if inst not in old:
                old[inst] = self._local(inst)
        for key, row, diff in batch:
            inst = self._instance(row)
            group = self.members.setdefault(inst, {})
            if diff > 0:
                group[key] = row[self.key_col]
            else:
                group.pop(key, None)
                if not group:
                    self.members.pop(inst, None)
        out = DeltaBatch()
        for inst, old_rows in old.items():
            new_rows = self._local(inst)
            for k, r in old_rows.items():
                if rows_differ(new_rows.get(k), r):
                    out.append(k, r, -1)
            for k, r in new_rows.items():
                if rows_differ(old_rows.get(k), r):
                    out.append(k, r, 1)
        return out.consolidate()


class IxNode(InputMirrors, Node):
    """Pointer-lookup join: for each input row, fetch the source row its
    key column points to (reference: ix_table python_api.rs:2963).
    """

    STATE_ATTRS = ("forward", "reverse", "_mirrors")

    def __init__(
        self,
        scope: "Scope",
        keys_table: Node,
        source_table: Node,
        key_col: int,
        optional: bool = False,
        strict: bool = True,
    ) -> None:
        super().__init__(scope, [keys_table, source_table], source_table.arity)
        self.key_col = key_col
        self.optional = optional
        self.strict = strict
        self.forward: dict[Pointer, Pointer] = {}  # input key -> source key
        self.reverse: dict[Pointer, set[Pointer]] = {}  # source key -> input keys
        self._init_mirrors()

    def _lookup(self, key: Pointer, skey: Pointer | None) -> tuple | None:
        if skey is None:
            if self.optional:
                return (None,) * self.arity
            self.report(key, "ix: key is None and optional=False")
            return None
        src = self._input_state(1).get(skey)
        if src is None:
            if self.strict:
                self.report(key, f"ix: missing key {skey!r}")
                return None
            return (None,) * self.arity
        return src

    def process(self, time: int) -> DeltaBatch:
        keys_batch = self.take(0)
        source_batch = self.take(1)
        self._absorb(1, source_batch)
        out = DeltaBatch()
        # Source-side changes: re-emit rows for affected input keys
        affected_src: set[Pointer] = {key for key, _r, _d in source_batch}
        handled: set[Pointer] = set()
        for key, row, diff in keys_batch:
            handled.add(key)
        state = self.current  # hoisted: drains lazy state once
        for skey in affected_src:
            for ikey in self.reverse.get(skey, set()) - handled:
                old = state.get(ikey)
                new = self._lookup(ikey, self.forward.get(ikey))
                if old is not None and rows_differ(old, new):
                    out.append(ikey, old, -1)
                if new is not None and rows_differ(old, new):
                    out.append(ikey, new, 1)
        # Input-side changes
        for key, row, diff in keys_batch:
            if diff < 0:
                if key in state:
                    out.append(key, state[key], -1)
                skey = self.forward.pop(key, None)
                if skey is not None:
                    self.reverse.get(skey, set()).discard(key)
                continue
            skey = row[self.key_col]
            if is_error(skey):
                self.report(key, "error value in ix key")
                continue
            if skey is not None and not isinstance(skey, Pointer):
                self.report(key, f"ix key must be a pointer, got {skey!r}")
                continue
            if key in state:
                out.append(key, state[key], -1)
            if skey is not None:
                self.forward[key] = skey
                self.reverse.setdefault(skey, set()).add(key)
            new = self._lookup(key, skey)
            if new is not None:
                out.append(key, new, 1)
        return out.consolidate()


class UpdateRowsNode(InputMirrors, Node):
    """``orig.update_rows(updates)`` — updates win per key; union of universes."""

    STATE_ATTRS = ("_mirrors",)

    def __init__(self, scope: "Scope", orig: Node, updates: Node) -> None:
        assert orig.arity == updates.arity
        super().__init__(scope, [orig, updates], orig.arity)
        self._init_mirrors()

    def _effective(self, key: Pointer) -> tuple | None:
        upd = self._input_state(1).get(key)
        if upd is not None:
            return upd
        return self._input_state(0).get(key)

    def process(self, time: int) -> DeltaBatch:
        affected: set[Pointer] = set()
        for port in (0, 1):
            batch = self.take(port)
            self._absorb(port, batch)
            for key, _row, _diff in batch:
                affected.add(key)
        out = DeltaBatch()
        state = self.current  # hoisted: drains lazy state once
        for key in affected:
            old = state.get(key)
            new = self._effective(key)
            if old is not None and rows_differ(old, new):
                out.append(key, old, -1)
            if new is not None and rows_differ(old, new):
                out.append(key, new, 1)
        return out


class UpdateCellsNode(InputMirrors, Node):
    """``orig.update_cells(updates)`` — override selected columns per key.

    ``update_cols[i]`` gives, for each output column, the column index in the
    updates table or -1 to keep the original value.
    """

    STATE_ATTRS = ("_mirrors",)

    def __init__(
        self, scope: "Scope", orig: Node, updates: Node, update_cols: Sequence[int]
    ) -> None:
        super().__init__(scope, [orig, updates], orig.arity)
        self.update_cols = list(update_cols)
        self._init_mirrors()

    def _effective(self, key: Pointer) -> tuple | None:
        orig = self._input_state(0).get(key)
        if orig is None:
            return None
        upd = self._input_state(1).get(key)
        if upd is None:
            return orig
        return tuple(
            upd[uc] if uc >= 0 else orig[i] for i, uc in enumerate(self.update_cols)
        )

    def process(self, time: int) -> DeltaBatch:
        affected: set[Pointer] = set()
        for port in (0, 1):
            batch = self.take(port)
            self._absorb(port, batch)
            for key, _row, _diff in batch:
                affected.add(key)
        out = DeltaBatch()
        state = self.current  # hoisted: drains lazy state once
        for key in affected:
            old = state.get(key)
            new = self._effective(key)
            if old is not None and rows_differ(old, new):
                out.append(key, old, -1)
            if new is not None and rows_differ(old, new):
                out.append(key, new, 1)
        return out


def _reads_unready(row: tuple, unready: set) -> bool:
    """Whether a host read of ``row`` would wait for one of the ``unready``
    device batches; one the chip has finished meanwhile leaves the set."""
    for value in row:
        if type(value) is _device.LazyDeviceVector and value.batch in unready:
            if not value.batch.ready():
                return True
            unready.discard(value.batch)
    return False


class SubscribeNode(Node):
    """Sink: per-row callbacks + time/end notifications (subscribe_table).

    One sink's callbacks keep their order whichever thread makes them: the
    rows of a time, its ``on_time_end``, the rows of the next. Across sinks
    nothing is promised. ``close`` follows the pipeline's ``drain``."""

    def __init__(
        self,
        scope: "Scope",
        source: Node,
        on_change: Callable[[Pointer, tuple, int, int], None] | None = None,
        on_time_end: Callable[[int], None] | None = None,
        on_end: Callable[[], None] | None = None,
        skip_errors: bool = True,
    ) -> None:
        super().__init__(scope, [source], source.arity)
        self._on_change = on_change
        self._on_time_end = on_time_end
        self._on_end = on_end
        self.skip_errors = skip_errors
        self._saw_data = False

    def process(self, time: int) -> DeltaBatch:
        """Call ``on_change`` for the batch's rows, on this thread as long
        as that holds it for no device batch: from the first row that
        reads a batch of this commit the chip has not finished, the rest
        (in order) is left to the completion worker, which waits for the
        download in the run thread's place; so is every row while an
        earlier emission of this sink is still with it. Decided from what
        the sink sees and no option: ``PATHWAY_TPU_ASYNC_DEVICE=0``, a
        commit with no device batch and rows that are ready emit inline."""
        batch = self.take(0)
        on_change = self._on_change
        rows = 0
        retractions = 0
        unready: set = set()
        #: the rows the worker is to deliver (None: none yet)
        deferred: list | None = None
        if on_change is not None and _device_pipeline.async_enabled():
            if _device_pipeline.holds(self):
                deferred = []
            else:
                unready = _device.unready_device_batches()
        with _tracing.stage("sink.emit", cat="sink") as emit:
            for key, row, diff in batch:
                if self.skip_errors and any(is_error(v) for v in row):
                    self.report(key, "error value in output row")
                    continue
                self._saw_data = True
                rows += 1
                if diff < 0:
                    retractions += 1
                if on_change is None:
                    continue
                if deferred is None:
                    if not (unready and _reads_unready(row, unready)):
                        on_change(key, row, time, diff)
                        continue
                    deferred = []
                deferred.append((key, row, diff))
            if deferred:
                _device_pipeline.hand_over(
                    time,
                    self,
                    functools.partial(self._deliver, deferred, time),
                    unready,
                )
                emit.add(rows=rows - len(deferred), deferred_rows=len(deferred))
            else:
                emit.add(rows=rows)
        if rows:
            _OUTPUT_ROWS.inc(rows)
            tr = _tracing.current()
            if tr is not None:
                tr.note_sink(rows)
        if retractions:
            _metrics.FLIGHT.record(
                "retractions", time=time, count=retractions, sink=self.index
            )
        return batch

    def _deliver(self, rows: list, time: int) -> None:
        """The completion worker's half of ``process``: a ``sink.emit``
        stage of that thread's table."""
        on_change = self._on_change
        with _tracing.stage("sink.emit", cat="sink", rows=len(rows)):
            for key, row, diff in rows:
                on_change(key, row, time, diff)

    def on_time_end(self, time: int) -> None:
        if self._on_time_end is None:
            return
        if _device_pipeline.holds(self):
            # behind the rows of ``time`` that are with the worker
            _device_pipeline.hand_over(
                time, self, functools.partial(self._on_time_end, time)
            )
        else:
            self._on_time_end(time)

    def close(self) -> None:
        # the user's on_end ("stream finished") fires here — after the
        # settlement commit — so buffer-flush rows injected by upstream
        # on_end hooks were already delivered through on_change
        if self._on_end is not None:
            self._on_end()


class ErrorLogNode(Node):
    """Error log as an engine table of (message,) rows
    (reference: error_log dataflow.rs:3980, pw.global_error_log()).
    """

    def __init__(self, scope: "Scope") -> None:
        super().__init__(scope, [], 1)
        self._counter = itertools.count()
        self.buffered: list[tuple[Pointer, tuple, int]] = []

    def log(self, message: str) -> None:
        key = hash_values((next(self._counter), message), salt=b"errlog")
        self.buffered.append((key, (message,), 1))
        _metrics.FLIGHT.record("error", message=message)

    def flush_buffer(self) -> DeltaBatch | None:
        if not self.buffered:
            return None
        out = DeltaBatch(self.buffered)
        self.buffered = []
        return out

    def process(self, time: int) -> DeltaBatch:
        return self.take(0)


def emit_local_group_diffs(
    out: DeltaBatch,
    old_groups: dict,
    local_fn: Callable[[Any], dict],
) -> None:
    """Shared incremental-recompute tail: for each touched group, diff the
    snapshot taken before the batch against the recomputed local output and
    emit retract/insert pairs. Used by the group-local operators (joins,
    sort, sessions, temporal joins)."""
    for inst, old_rows in old_groups.items():
        new_rows = local_fn(inst)
        for k, r in old_rows.items():
            if rows_differ(new_rows.get(k), r):
                out.append(k, r, -1)
        for k, r in new_rows.items():
            if rows_differ(old_rows.get(k), r):
                out.append(k, r, 1)


class Scope:
    """The engine graph builder + owner of all nodes.

    The Python framework lowers its ParseGraph onto this API; it mirrors the
    reference's `Scope` pyclass (src/python_api.rs:2248) with tables as
    node handles and columns as tuple positions.
    """

    def __init__(self) -> None:
        self.nodes: list[Node] = []
        self.error_log_default = ErrorLogNode(self)
        self._error_log_stack: list[ErrorLogNode] = [self.error_log_default]
        #: ``pw.run(terminate_on_error=True)``: the first reported error
        #: raises out of the run instead of poisoning its row
        self.terminate_on_error = False
        self._error_logged: set[int] = set()  # node indexes already logged
        self.worker_index = 0
        self.worker_count = 1
        #: set by the sharded/distributed schedulers: replica node state
        #: (`current`) then holds only a key shard, so state-peeking
        #: operators (zip/ix/update/iterate) switch to own input mirrors
        self.sharded = False

    # -- error plumbing -----------------------------------------------------

    def report_error(
        self,
        node: Node,
        key: Pointer | None,
        message: str,
        exc: BaseException | None = None,
    ) -> None:
        trace = f" at {node.trace}" if node.trace else ""
        text = f"{node.name}{trace}: {message}"
        # nodes built inside `with pw.local_error_log()` carry their own log
        log = getattr(node, "error_log", None) or self._error_log_stack[-1]
        log.log(text)
        if self.terminate_on_error:
            raise EngineError(text) from exc
        if node.index not in self._error_logged:
            # a run whose every row fails must not look like success to a
            # user who reads no error-log table: each operator's first
            # error goes to the process log, with the traceback if any
            self._error_logged.add(node.index)
            _LOG.error(
                "%s (further errors of this operator go to the error log "
                "table only)",
                text,
                exc_info=exc,
            )

    def error_log(self) -> ErrorLogNode:
        return ErrorLogNode(self)

    def push_error_log(self, log: ErrorLogNode) -> None:
        self._error_log_stack.append(log)

    def pop_error_log(self) -> None:
        self._error_log_stack.pop()

    # -- table constructors -------------------------------------------------

    def empty_table(self, arity: int) -> Node:
        return StaticSource(self, [], arity)

    def static_table(self, rows: Iterable[tuple[Pointer, tuple]], arity: int) -> Node:
        return StaticSource(self, rows, arity)

    def input_session(self, arity: int, upsert: bool = False) -> InputSession:
        return InputSession(self, arity, upsert=upsert)

    # -- operators ----------------------------------------------------------

    def expression_table(
        self, table: Node, expressions: Sequence[EngineExpression]
    ) -> Node:
        return ExpressionNode(self, table, expressions)

    def zip_tables(self, tables: Sequence[Node]) -> Node:
        if len(tables) == 1:
            return tables[0]
        return ZipNode(self, tables)

    def filter_table(self, table: Node, condition_col: int) -> Node:
        return FilterNode(self, table, condition_col)

    def batch_apply_table(
        self,
        table: Node,
        rows_fn: Callable[[list], list],
        arg_cols: Sequence[int],
        propagate_none: bool = False,
    ) -> Node:
        return BatchApplyNode(self, table, rows_fn, arg_cols, propagate_none)

    def concat_tables(self, tables: Sequence[Node]) -> Node:
        return ConcatNode(self, tables)

    def reindex_table(self, table: Node, key_col: int) -> Node:
        return ReindexNode(self, table, key_col)

    def intersect_tables(self, table: Node, others: Sequence[Node]) -> Node:
        return KeyFilterNode(self, table, others, "intersect")

    def subtract_table(self, table: Node, other: Node) -> Node:
        return KeyFilterNode(self, table, [other], "subtract")

    def restrict_table(self, table: Node, universe: Node) -> Node:
        return KeyFilterNode(self, table, [universe], "restrict")

    def override_table_universe(self, table: Node, universe: Node) -> Node:
        return OverrideUniverseNode(self, table)

    def join_tables(
        self,
        left: Node,
        right: Node,
        left_on: Sequence[int],
        right_on: Sequence[int],
        kind: str = JoinKind.INNER,
        id_from_left: bool = False,
        id_spec: tuple | None = None,
    ) -> Node:
        return JoinNode(
            self,
            left,
            right,
            left_on,
            right_on,
            kind=kind,
            id_from_left=id_from_left,
            id_spec=id_spec,
        )

    def group_by_table(
        self,
        table: Node,
        by_cols: Sequence[int],
        reducers: Sequence[tuple[Reducer, Sequence[int]]],
        set_id: bool = False,
        instance_last: bool = False,
    ) -> Node:
        return GroupbyNode(
            self,
            table,
            by_cols,
            reducers,
            set_id=set_id,
            instance_last=instance_last,
        )

    def deduplicate(
        self,
        table: Node,
        value_col: int,
        instance_cols: Sequence[int],
        acceptor: Callable[[Any, Any], bool],
    ) -> Node:
        return DeduplicateNode(self, table, value_col, instance_cols, acceptor)

    def recompute_table(
        self, sources: Sequence[Node], compute: Callable[[list], dict], arity: int
    ) -> Node:
        return RecomputeNode(self, sources, compute, arity)

    def export_table(
        self, table: Node, handle: "ExportedTable | None" = None
    ) -> "ExportedTable":
        """Reference graph.rs:609 export_table: subscribe the node into a
        cross-graph handle (pass ``handle`` to fill a pre-created one)."""
        exported = handle if handle is not None else ExportedTable(table.arity)
        self.subscribe_table(
            table,
            on_change=exported._on_change,
            on_end=exported._on_end,
        )
        return exported

    def flatten_table(
        self, table: Node, flat_col: int, with_origin: bool = False
    ) -> Node:
        return FlattenNode(self, table, flat_col, with_origin=with_origin)

    def sort_table(self, table: Node, key_col: int, instance_col: int | None) -> Node:
        return SortNode(self, table, key_col, instance_col)

    def ix_table(
        self,
        keys_table: Node,
        source_table: Node,
        key_col: int,
        optional: bool = False,
        strict: bool = True,
    ) -> Node:
        return IxNode(self, keys_table, source_table, key_col, optional, strict)

    def update_rows_table(self, orig: Node, updates: Node) -> Node:
        return UpdateRowsNode(self, orig, updates)

    def update_cells_table(
        self, orig: Node, updates: Node, update_cols: Sequence[int]
    ) -> Node:
        return UpdateCellsNode(self, orig, updates, update_cols)

    def subscribe_table(
        self,
        table: Node,
        on_change: Callable[[Pointer, tuple, int, int], None] | None = None,
        on_time_end: Callable[[int], None] | None = None,
        on_end: Callable[[], None] | None = None,
        skip_errors: bool = True,
    ) -> SubscribeNode:
        return SubscribeNode(
            self, table, on_change, on_time_end, on_end, skip_errors=skip_errors
        )

    def remove_errors_from_table(self, table: Node) -> Node:
        return _RemoveErrorsNode(self, table)

    # -- execution ----------------------------------------------------------

    def run(
        self,
        strict: bool = False,
        probe: bool = False,
        optimize: bool = True,
    ) -> "Scheduler":
        """Build-and-go convenience: pump every static source through one
        commit and finish.  ``strict=True`` first runs the pre-execution
        static analyzer (pathway_tpu.analysis) and raises
        ``AnalysisError`` on any error-severity finding — the graph is
        rejected before any state is created.  ``optimize=True`` (default)
        runs the pre-execution graph rewriter (pathway_tpu.optimize);
        ``PATHWAY_TPU_OPTIMIZE=0`` is the environment escape hatch."""
        if strict:
            from pathway_tpu.analysis import check_strict

            check_strict(self)
        scheduler = Scheduler(self, probe=probe, optimize=optimize)
        scheduler.run_static()
        return scheduler


class _RemoveErrorsNode(Node):
    def __init__(self, scope: Scope, source: Node) -> None:
        super().__init__(scope, [source], source.arity)

    def process(self, time: int) -> DeltaBatch:
        batch = self.take(0)
        out = DeltaBatch()
        state = self.current  # hoisted: drains lazy state once
        for key, row, diff in batch:
            if diff < 0:
                if key in state:
                    out.append(key, state[key], -1)
                continue
            if any(is_error(v) for v in row):
                continue
            out.append(key, row, diff)
        return out


class OperatorStats:
    """Per-operator probe counters (reference: OperatorStats
    graph.rs:500-542 + Prober dataflow.rs:671-798)."""

    __slots__ = ("insertions", "deletions", "batches", "time_spent", "last_time")

    def __init__(self) -> None:
        self.insertions = 0
        self.deletions = 0
        self.batches = 0
        self.time_spent = 0.0  # seconds inside process()
        self.last_time: int | None = None  # last commit that touched this op

    def snapshot(self) -> dict:
        return {
            "insertions": self.insertions,
            "deletions": self.deletions,
            "batches": self.batches,
            "time_spent": self.time_spent,
            "last_time": self.last_time,
        }


class Scheduler:
    """Topological commit-batch pump (replaces timely's worker loop,
    reference: dataflow.rs:5769-5822). All deltas at one logical time are
    processed as a unit; the sweep loops until quiescent so same-time
    feedback (error logs) settles within the commit.

    This class is the single worker's pump and the base of the sharded and
    mesh ones: it owns the sweep over ``self.scopes`` (a single worker has
    one), the operator statistics, and ``commit()`` / ``finish()``. A
    subclass says how an output reaches its consumers (``_deliver``), how
    sources are flushed (``_flush_sources``) and what ``propagate`` does
    round the sweep.

    ``probe=True`` collects per-operator stats into ``self.stats``
    (node index → OperatorStats), feeding the monitoring dashboard and the
    Prometheus endpoint.
    """

    def __init__(
        self,
        scope: "Scope | Sequence[Scope]",
        probe: bool = False,
        optimize: bool = True,
    ) -> None:
        #: one scope a worker: a single worker has one
        self.scopes = [scope] if isinstance(scope, Scope) else list(scope)
        if optimize:
            from pathway_tpu.optimize import optimize_scopes

            # single-worker: no exchanges to elide, but fusion/pushdown
            # still apply (skips itself under PATHWAY_TPU_OPTIMIZE=0 and
            # in analyze mode; idempotent per scope)
            optimize_scopes(self.scopes)
        self.time = 0
        self.probe = probe
        #: the pump thread inserts per-operator entries while the live
        #: monitoring thread snapshots the dict — serialize the inserts
        self._stats_lock = threading.Lock()
        #: node index -> OperatorStats, aggregated across ``self.scopes``
        self.stats: dict[int, OperatorStats] = {}  # guarded-by: self._stats_lock
        if probe:
            self._queue_gauge = _metrics.REGISTRY.gauge(
                "pathway_queue_depth",
                "operators with pending delta batches (backpressure)",
            )

    @property
    def scope(self) -> Scope:
        """Canonical scope for monitoring and analysis (with replicas,
        worker 0's: it carries the superset, sinks attach there only)."""
        return self.scopes[0]

    def _nodes(self) -> Iterable[Node]:
        return itertools.chain.from_iterable(s.nodes for s in self.scopes)

    def _stats_of(self, node: Node) -> OperatorStats:
        st = self.stats.get(node.index)
        if st is None:
            with self._stats_lock:
                st = self.stats.setdefault(node.index, OperatorStats())
        return st

    def _note_stats(
        self, node: Node, out: DeltaBatch, time: int, t0: float
    ) -> None:
        st = self._stats_of(node)
        st.time_spent += _time.perf_counter() - t0
        st.batches += 1
        st.last_time = time
        cols = out.columns
        if cols is not None:
            # count from the diff vector — don't materialise rows just
            # for monitoring
            if cols.diffs is None:
                st.insertions += cols.n
            else:
                pos = int((cols.diffs > 0).sum())
                st.insertions += pos
                st.deletions += cols.n - pos
        else:
            # consolidate for counting: raw batches may carry net-zero
            # churn that monitoring should not report
            for _k, _r, d in out.consolidate():
                if d > 0:
                    st.insertions += 1
                else:
                    st.deletions += 1

    def _deliver(self, worker: int, node: Node, out: DeltaBatch) -> None:
        """How ``node``'s output on ``worker`` reaches its consumers."""
        for consumer, port in node.consumers:
            consumer.push(port, out)

    def _sweep(self, time: int) -> bool:
        """Run every node that has pending batches until none has,
        same-time error-log feedback included. True if anything ran."""
        probe = self.probe
        # an operator's sweep is a stage only while a sampled commit or a
        # profiler session is there to show it: no metric reads it
        detail = _tracing.detail_on()
        dops = None
        if detail:
            # the device operator kernels' time goes to the stage that
            # launched them (critical-path analysis needs the per-node
            # split, not just the global kernel_ns bucket)
            from pathway_tpu.engine import device_ops

            if device_ops.enabled():
                dops = device_ops
        worked = False
        t0 = 0.0
        st = _tracing.NO_STAGE
        while True:
            ran = 0
            for worker, scope in enumerate(self.scopes):
                for node in scope.nodes:
                    if not node.has_pending():
                        continue
                    ran += 1
                    if probe:
                        t0 = _time.perf_counter()
                    if detail:
                        st = _tracing.stage(
                            "op." + type(node).__name__,
                            cat="sink" if isinstance(node, SubscribeNode) else "op",
                            label=getattr(node, "name", None),
                            batches=1,
                            node=node.index,
                            shard=worker,
                        )
                        dns0 = dops.total_ns() if dops is not None else 0
                    with st:
                        out = node.process(time)
                        if out is None:
                            out = DeltaBatch()
                        # no eager consolidation or apply: consumers
                        # consolidate in take() (cached), lazy state drain
                        # consolidates before applying, and a columnar
                        # batch stays arrays for the vectorized exchange
                        node._defer_state(out)
                        if dops is not None:
                            dns = dops.total_ns() - dns0
                            if dns:
                                st.add(device_ns=dns)
                    if probe:
                        self._note_stats(node, out, time, t0)
                    if out:
                        self._deliver(worker, node, out)
            if probe:
                self._queue_gauge.value = float(ran)
            if ran:
                worked = True
                continue
            # flush error-log buffers; may create new pending work
            flushed = False
            for node in self._nodes():
                if isinstance(node, ErrorLogNode):
                    batch = node.flush_buffer()
                    if batch:
                        node.push(0, batch)
                        flushed = True
            if not flushed:
                return worked
            worked = True

    def propagate(self, time: int) -> None:
        self._sweep(time)
        for node in self._nodes():
            node.on_time_end(time)
        _device_pipeline.commit_boundary(time)

    def _settle(self) -> None:
        """``on_end`` hooks may inject final batches (buffer flush):
        propagate those as one more commit."""
        if any(n.has_pending() for n in self._nodes()):
            self.propagate(self.time)
            self.time += 1

    def _end_nodes(self) -> None:
        """Run on_end hooks, settle what they injected, then tear sinks
        down."""
        for node in self._nodes():
            node.on_end()
        self._settle()
        # every row a sink left to the completion worker is delivered
        # before its ``close`` (the user's ``on_end``)
        _device_pipeline.drain()
        for node in self._nodes():
            node.close()

    def _analysis_intercept(self) -> bool:
        """Under ``cli analyze`` (PATHWAY_TPU_ANALYZE=1) the scheduler
        records the built graph for static analysis and skips execution
        (replicas are identical: worker 0's scope is analyzed once)."""
        from pathway_tpu.analysis import runtime as _analysis_runtime

        return _analysis_runtime.intercept(self.scope)

    def _flush_sources(self) -> None:
        for node in self.scope.nodes:
            if isinstance(node, StaticSource):
                batch = node.initial_batch()
            elif isinstance(node, InputSession):
                batch = node.flush()
            else:
                continue
            if batch:
                node.push(0, batch)

    def run_static(self) -> None:
        """Batch mode: all static sources at time 0, one commit, then end."""
        if self._analysis_intercept():
            self.time = 1
            return
        self._flush_sources()
        self.propagate(0)
        self.time = 1
        self._end_nodes()

    def commit(self) -> int:
        """Streaming mode: flush all input sessions as one commit."""
        time = self.time
        if not self._analysis_intercept():
            self._flush_sources()
            self.propagate(time)
        self.time += 1
        return time

    def finish(self) -> None:
        if self._analysis_intercept():
            return
        self.commit()
        self._end_nodes()


class RecomputeNode(Node):
    """Whole-recompute operator: ``compute(input_states) -> {key: row}``,
    diffed against the previous output. Backs row transformers
    (reference complex_columns.rs — demand-driven there, local recompute
    here, same results)."""

    STATE_ATTRS = ("_input_states",)

    def __init__(
        self,
        scope: "Scope",
        sources: Sequence[Node],
        compute: Callable[[list], dict],
        arity: int,
    ) -> None:
        super().__init__(scope, list(sources), arity)
        self.compute = compute
        # own mirror of each input built from received batches — under
        # sharded execution the local replicas' `current` only holds one
        # shard, while this node (pinned to worker 0) sees every batch
        self._input_states: list[dict[Pointer, tuple]] = [
            {} for _ in sources
        ]

    def process(self, time: int) -> DeltaBatch:
        for port in range(len(self.inputs)):
            apply_batch_to_state(self._input_states[port], self.take(port))
        try:
            new = self.compute(self._input_states)
        except Exception as e:  # noqa: BLE001
            self.report(None, f"row transformer error: {e!r}")
            return DeltaBatch()
        out = DeltaBatch()
        state = self.current  # hoisted: drains lazy state once
        for key, row in state.items():
            if rows_differ(new.get(key), row):
                out.append(key, row, -1)
        for key, row in new.items():
            if rows_differ(state.get(key), row):
                out.append(key, row, 1)
        return out.consolidate()


class ExportedTable:
    """Cross-graph table handle (reference: ExportedTable graph.rs:609,
    export.rs): a live snapshot plus update callbacks, consumable by
    ``import_table`` in another graph."""

    def __init__(self, arity: int) -> None:
        import threading

        self.arity = arity
        self.current: dict[Pointer, tuple] = {}
        self._callbacks: list = []
        self.finished = False
        self._lock = threading.Lock()

    # producer side --------------------------------------------------------
    def _on_change(self, key: Pointer, row: tuple, time: int, diff: int) -> None:
        with self._lock:
            if diff > 0:
                self.current[key] = row
            else:
                self.current.pop(key, None)
            callbacks = list(self._callbacks)
        for cb in callbacks:
            cb(key, row, time, diff)

    def _on_end(self) -> None:
        with self._lock:
            self.finished = True
            callbacks = list(self._callbacks)
        for cb in callbacks:
            cb(None, None, None, 0)

    # consumer side --------------------------------------------------------
    def snapshot(self) -> dict[Pointer, tuple]:
        with self._lock:
            return dict(self.current)

    def subscribe(self, callback) -> None:
        with self._lock:
            self._callbacks.append(callback)

    def subscribe_with_snapshot(self, callback) -> tuple[dict, bool]:
        """Atomically: register the callback and return (snapshot,
        finished). No update committed after the snapshot can be missed,
        and none in the snapshot is re-delivered."""
        with self._lock:
            self._callbacks.append(callback)
            return dict(self.current), self.finished

    def unsubscribe(self, callback) -> None:
        with self._lock:
            if callback in self._callbacks:
                self._callbacks.remove(callback)
