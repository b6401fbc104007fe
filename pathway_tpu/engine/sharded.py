"""Multi-worker execution: key-sharded scopes with inter-operator exchange.

Reference worker model (src/engine/dataflow/config.rs:63-120,
value.rs:94-130 Key::shard, docs worker-architecture.md:36-47): every
worker runs the IDENTICAL dataflow over a hash-partition of the key space;
records cross workers at exchange points before stateful operators, and
single-threaded sinks run on worker 0.

Here each logical worker owns a full engine Scope built from the same
graph logic (the reference re-executes the Python logic per worker,
python_api.rs:3329). The sharded scheduler propagates all scopes in
lockstep; when operator A on worker w emits a batch for consumer B, the
batch is partitioned by B's co-location key and delivered to B's replica
on the owning worker:

- groupby/deduplicate: by grouping/instance values
- join: per side, by the join-key columns
- ix: lookups route to the owner of the pointed-at row
- temporal/iterate/external-index/subscribe/output: worker 0 (their state
  is global — watermarks, fixed-points, as-of-now indexes; the reference
  similarly pins non-partitionable sinks to one worker)
- everything else: by row key

In-process today; the exchange seam is where ICI/DCN collectives slot in
for multi-host (SURVEY §2.10 mapping).
"""

from __future__ import annotations

import os
from typing import Any, Callable, Sequence

from pathway_tpu.engine.batch import (
    DeltaBatch,
    apply_batch_to_state,
    columnarize_entries,
)
from pathway_tpu.engine.device import VECTOR_THRESHOLD
from pathway_tpu.engine.graph import (
    DeduplicateNode,
    ErrorLogNode,
    GroupbyNode,
    InputSession,
    IxNode,
    JoinNode,
    Node,
    Scheduler,
    Scope,
    SortNode,
    StaticSource,
    SubscribeNode,
)
# the vectorized routing math lives in engine/routing.py; `_shard_of` and
# `_object_codes` are re-exported here because the partitioner closures
# below and older call sites (engine/distributed.py, tests) address them
# through this module
from pathway_tpu.engine.routing import (  # noqa: F401 — re-exports
    EXCHANGE_STATS,
    _object_codes,
    _shard_of,
    batch_shards,
    columnar_shards,
    entry_shards,
    shards_of_values,
)
from pathway_tpu.engine.value import Pointer

Entry = tuple

#: debug cross-check: recompute routing for every elided delivery and
#: assert the whole batch is already co-located (optimizer soundness net)
_VERIFY_ELISION = os.environ.get("PATHWAY_TPU_VERIFY_ELISION") == "1"


def _assert_colocated(
    consumer: Node, port: int, out: DeltaBatch, worker: int, n: int
) -> None:
    shards = batch_shards(partition_rule(consumer, port), out, n)
    if shards is not None and len(shards) and not (shards == worker).all():
        raise AssertionError(
            f"elided exchange into {consumer.name}#{consumer.index} "
            f"(port {port}) moved rows off worker {worker}"
        )


def partition_rule(consumer: Node, port: int) -> tuple:
    """ONE classification of how entries entering ``consumer`` on ``port``
    pick their worker — consumed by BOTH the per-row closure builder and
    the vectorized columnar exchange, so the two can never drift:

    - ``("pin",)``        everything to worker 0 (globally-stateful op)
    - ``("key",)``        by row key (full 128-bit pointer mod n)
    - ``("cols", cols)``  by ``hash(tuple(row[c] for c in cols))``
    - ``("col", c)``      by the bare value ``row[c]`` (c None = constant)
    """
    from pathway_tpu.engine import temporal as _temporal
    from pathway_tpu.engine.external_index import ExternalIndexNode
    from pathway_tpu.engine.graph import RecomputeNode
    from pathway_tpu.engine.iterate import IterateNode

    if isinstance(consumer, GroupbyNode):
        return ("cols", list(consumer.by_cols))
    if isinstance(consumer, DeduplicateNode):
        return ("cols", list(consumer.instance_cols))
    if isinstance(consumer, JoinNode):
        return (
            "cols",
            list(consumer.left_on if port == 0 else consumer.right_on),
        )
    if isinstance(consumer, SortNode):
        return ("col", consumer.instance_col)
    if isinstance(consumer, IxNode):
        return ("col", consumer.key_col) if port == 0 else ("key",)
    if isinstance(
        consumer,
        (
            SubscribeNode,
            ErrorLogNode,
            ExternalIndexNode,
            IterateNode,
            RecomputeNode,  # row transformers consume whole input states
            _temporal.GradualBroadcastNode,  # needs the threshold triplet
            _temporal.BufferNode,
            _temporal.ForgetNode,
            _temporal.FreezeNode,
            _temporal.SessionAssignNode,
            _temporal.IntervalJoinNode,
            _temporal.AsofJoinNode,
            _temporal.AsofNowJoinNode,
        ),
    ):
        return ("pin",)  # global state: pin to worker 0
    return ("key",)


def partitioner(
    consumer: Node, port: int, n_workers: int
) -> Callable[[Pointer, tuple], int] | None:
    """Per-row closure for :func:`partition_rule`; None = worker 0."""
    rule = partition_rule(consumer, port)
    kind = rule[0]
    if kind == "pin":
        return None
    if kind == "cols":
        cols = rule[1]

        def by_cols(key: Pointer, row: tuple) -> int:
            return _shard_of(tuple(row[c] for c in cols), n_workers)

        return by_cols
    if kind == "col":
        col = rule[1]

        def by_col(key: Pointer, row: tuple) -> int:
            return _shard_of(
                row[col] if col is not None else None, n_workers
            )

        return by_col

    def by_key(key: Pointer, row: tuple) -> int:
        return _shard_of(key, n_workers)

    return by_key


class ShardedScheduler(Scheduler):
    """Lockstep commit pump over N identically-built scopes: the base's
    sweep and commit, with the exchange as ``_deliver``."""

    def __init__(
        self,
        scopes: Sequence[Scope],
        probe: bool = False,
        optimize: bool = True,
    ) -> None:
        # its own rewrite below: the optimizer's elisions are its to keep
        super().__init__(scopes, probe, optimize=False)
        self.n = len(self.scopes)
        for scope in self.scopes:
            # replica `current` holds key shards: state-peeking operators
            # (zip/ix/update/iterate) must use their own input mirrors
            scope.sharded = True
        sigs = [
            [type(node).__name__ for node in scope.nodes]
            for scope in self.scopes
        ]
        # worker 0 may carry extra TRAILING nodes: sinks attach there only
        # (single-threaded sinks, reference data_storage.rs:611)
        for w, sig in enumerate(sigs[1:], start=1):
            if sigs[0][: len(sig)] != sig:
                raise ValueError(
                    f"worker {w} scope diverged: the graph logic must build "
                    "the identical operator sequence on every worker"
                )
        #: (producer, consumer, port) edges the optimizer proved exchange-
        #: redundant — _deliver pushes those straight to the co-located
        #: replica (rewrites every replica scope in place, identically)
        self._elided: set = set()
        if optimize:
            from pathway_tpu.optimize import optimize_scopes

            self._elided = optimize_scopes(self.scopes)
        # partition function cache per (consumer index, port)
        self._parts: dict[tuple[int, int], Any] = {}

    def _partition_fn(self, consumer: Node, port: int):
        key = (consumer.index, port)
        fn = self._parts.get(key, False)
        if fn is False:
            fn = partitioner(consumer, port, self.n)
            self._parts[key] = fn
        return fn

    def _columnar_shards(
        self, consumer: Node, port: int, out: DeltaBatch
    ):
        return columnar_shards(
            partition_rule(consumer, port), out.columns, self.n
        )

    def _deliver(
        self, worker: int, producer: Node, out: DeltaBatch
    ) -> None:
        """Exchange step: split ``out`` per consumer and push each part to
        the consumer's replica on the owning worker. The consumer topology
        comes from worker 0's scope — the superset, since sinks attach
        there only.

        Delivery planes, in decision order (every branch counts exactly
        one of elided/host/collective plus ``repartitions``):

        1. optimizer-elided edges skip all routing (PR 4) — checked
           BEFORE the collective is even considered;
        2. pinned consumers take the whole batch on worker 0 (host);
        3. columnar batches on a device-colocated mesh may repartition
           through engine/collective_exchange (one all-to-all instead of
           n gather+push hops) — a decline falls through to
        4. the host columnar gather split, then
        5. the row-entry fallback.
        """
        import time as _walltime

        import numpy as np

        from pathway_tpu.engine import collective_exchange as _collective

        elided = self._elided
        for consumer, port in self.scopes[0].nodes[producer.index].consumers:
            if (producer.index, consumer.index, port) in elided:
                # optimizer-proven redundant exchange: every row already
                # lives on `worker` — skip the routing digests entirely
                if _VERIFY_ELISION:
                    _assert_colocated(consumer, port, out, worker, self.n)
                EXCHANGE_STATS["elided"] += 1
                EXCHANGE_STATS["repartitions"] += 1
                self.scopes[worker].nodes[consumer.index].push(port, out)
                continue
            fn = self._partition_fn(consumer, port)
            if fn is None:
                EXCHANGE_STATS["host_deliveries"] += 1
                EXCHANGE_STATS["repartitions"] += 1
                target = self.scopes[0].nodes[consumer.index]
                target.push(port, out)
                continue
            if out._entries is None and out.columns is not None:
                shards = self._columnar_shards(consumer, port, out)
                if shards is not None:
                    cparts = _collective.exchange(
                        consumer.index,
                        out.columns,
                        shards,
                        self.n,
                        consumer=consumer,
                    )
                    if cparts is not None:
                        EXCHANGE_STATS["collective_deliveries"] += 1
                        EXCHANGE_STATS["repartitions"] += 1
                        for w, cols in enumerate(cparts):
                            if cols is None:
                                continue
                            part = DeltaBatch.from_columns(
                                cols,
                                consolidated=out._consolidated,
                                insert_only=out._insert_only,
                            )
                            part._raw_insert_only = out._raw_insert_only
                            self.scopes[w].nodes[consumer.index].push(
                                port, part
                            )
                        continue
                    # host gather split — timed only while the per-edge
                    # exchange policy is comparing sides (one cached env
                    # check otherwise)
                    track = _collective.tracking(self.n)
                    t0 = _walltime.perf_counter_ns() if track else 0
                    EXCHANGE_STATS["host_deliveries"] += 1
                    EXCHANGE_STATS["repartitions"] += 1
                    for w in range(self.n):
                        idx = np.flatnonzero(shards == w)
                        if not len(idx):
                            continue
                        part = DeltaBatch.from_columns(
                            out.columns.gather(idx),
                            consolidated=out._consolidated,
                            insert_only=out._insert_only,
                        )
                        part._raw_insert_only = out._raw_insert_only
                        self.scopes[w].nodes[consumer.index].push(
                            port, part
                        )
                    if track:
                        _collective.record_host(
                            consumer.index,
                            out.columns.n,
                            _walltime.perf_counter_ns() - t0,
                        )
                    continue
            EXCHANGE_STATS["host_deliveries"] += 1
            EXCHANGE_STATS["repartitions"] += 1
            parts: list[list[Entry]] = [[] for _ in range(self.n)]
            shards = entry_shards(
                partition_rule(consumer, port), out.entries, self.n
            )
            if shards is not None:
                for e, w in zip(out.entries, shards):
                    parts[w].append(e)
            else:
                for key, row, diff in out:
                    parts[fn(key, row)].append((key, row, diff))
            for w, entries in enumerate(parts):
                if entries:
                    batch = DeltaBatch(entries)
                    batch._consolidated = out._consolidated
                    self.scopes[w].nodes[consumer.index].push(port, batch)

    def _flush_sources(self) -> None:
        for w, scope in enumerate(self.scopes):
            for node in scope.nodes:
                if isinstance(node, StaticSource):
                    if w:
                        # the same static rows exist on every worker
                        # replica; only worker 0 emits, the exchange
                        # spreads them
                        node._emitted = True
                        continue
                    batch = node.initial_batch()
                elif isinstance(node, InputSession):
                    batch = node.flush()
                    if batch:
                        # flush may return raw diffs; routing applies state
                        batch = batch.consolidate()
                else:
                    continue
                if batch:
                    self._route_source(node, batch)

    def _route_source(self, node: Node, batch: DeltaBatch) -> None:
        """Sources read whole on worker 0 and reshard at the exchange
        (reference dataflow.rs:3492).

        State bookkeeping serves two invariants at once:
        - the worker-0 replica keeps the FULL source state, so
          upsert/remove flushes resolve against complete history and emit
          retractions for rows whose shard lives elsewhere;
        - replicas w>0 keep their row-key shard, so consumers that peek at
          an input's ``current`` (zip/update/ix source side) find exactly
          the rows whose downstream parts they receive."""
        if batch._entries is not None and len(batch) >= VECTOR_THRESHOLD:
            # bulk source commits enter the exchange as arrays so the
            # replica sharding and consumer routes below run the
            # vectorized kernel, not a per-row hash loop (static sources
            # arrive raw — consolidate first, since the columnar twin
            # asserts unique-key +1 invariants)
            cbatch = columnarize_entries(batch.consolidate())
            if cbatch is not None:
                batch = cbatch
        replica0 = self.scopes[0].nodes[node.index]
        replica0._defer_state(batch)
        if self.n > 1:
            shards = None
            if batch._entries is None and batch.columns is not None:
                shards = columnar_shards(("key",), batch.columns, self.n)
            if shards is not None:
                import numpy as np

                for w in range(1, self.n):
                    idx = np.flatnonzero(shards == w)
                    if len(idx):
                        self.scopes[w].nodes[node.index]._defer_state(
                            DeltaBatch.from_columns(
                                batch.columns.gather(idx),
                                consolidated=batch._consolidated,
                            )
                        )
            else:
                parts: list[list[Entry]] = [[] for _ in range(self.n)]
                key_shards = shards_of_values(
                    [e[0] for e in batch.entries], self.n
                )
                for e, w in zip(batch.entries, key_shards):
                    parts[w].append(e)
                for w in range(1, self.n):
                    if parts[w]:
                        replica = self.scopes[w].nodes[node.index]
                        replica._defer_state(DeltaBatch(parts[w]))
        self._deliver(0, replica0, batch)

    # -- results --------------------------------------------------------------

    def merged_state(self, index: int) -> dict[Pointer, tuple]:
        """Union of one operator's state across workers (for captures)."""
        out: dict[Pointer, tuple] = {}
        for scope in self.scopes:
            out.update(scope.nodes[index].current)
        return out
