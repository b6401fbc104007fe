"""GraphRunner — lowers the lazy Table graph onto the engine scope.

New implementation of the reference's graph_runner
(reference: python/pathway/internals/graph_runner/__init__.py:36 +
expression_evaluator.py + path_evaluator.py): tree-shakes reachable specs,
flattens columns into engine tuple positions, compiles the expression DSL to
engine expressions, and pumps the scheduler.
"""

from __future__ import annotations

import os
import time as _time
from typing import TYPE_CHECKING, Any, Callable, Sequence

from pathway_tpu.engine import expression as eex
from pathway_tpu.engine.graph import Node, Scheduler, Scope
from pathway_tpu.engine.reducers import make_reducer
from pathway_tpu.engine.value import Pointer
from pathway_tpu.internals import dtype as dt
from pathway_tpu.internals import expression as pex
from pathway_tpu.internals.desugaring import substitute
from pathway_tpu.internals.expression import ColumnExpression, ColumnReference
from pathway_tpu.internals.universe import solver

if TYPE_CHECKING:
    from pathway_tpu.internals.table import Table


class Layout:
    """Maps (table_id, column_name) → tuple position in a storage node."""

    def __init__(self) -> None:
        self.columns: dict[tuple[int, str], int] = {}
        self.key_tables: set[int] = set()  # tables whose id == storage key
        self.id_columns: dict[int, int] = {}  # table_id -> position of its id col

    def position(self, ref: ColumnReference) -> int | None:
        if ref.name == "id":
            return self.id_columns.get(ref.table._id)
        return self.columns.get((ref.table._id, ref.name))


_CAST_NAMES = {
    dt.INT: "Int",
    dt.FLOAT: "Float",
    dt.BOOL: "Bool",
    dt.STR: "String",
}


from pathway_tpu.engine import device_pipeline as _device_pipeline
from pathway_tpu import serving as _serving
from pathway_tpu.internals.udfs.executors import make_kw_fn as _make_kw_fn
from pathway_tpu.internals import metrics as _metrics
from pathway_tpu.internals import profiling as _profiling
from pathway_tpu.internals import timeseries as _timeseries
from pathway_tpu.internals import tracing as _tracing

#: ingest->sink latency, observed once per delta batch weighted by the
#: rows the commit delivered to subscribe sinks
_INGEST_LATENCY = _metrics.REGISTRY.histogram(
    "pathway_ingest_to_sink_latency_seconds",
    "end-to-end ingest->sink latency stamped per delta batch",
)
#: same series the sink nodes bump (engine/graph.py SubscribeNode)
_OUT_ROWS = _metrics.REGISTRY.counter("pathway_output_rows_total")


class _Arrivals:
    """What one commit took from its connector drivers: the one place a
    commit's arrival stamps are computed. ``oldest`` is the arrival of the
    commit's oldest row (``InputDriver.poll`` sets it when rows enter a
    session: the row's arrival at its reader, or that poll's time where
    the reader cannot say) and ``polled`` when the poll that took that row
    ran, both by ``time.monotonic`` and None for a commit with no row;
    ``sources`` names the drivers that gave a row. The stage's
    ``commit_wait_ns`` and ``arrival_to_poll_ns``, the sampled trace's
    ``origin_mono`` and the latency histogram's origin all come from here."""

    __slots__ = ("oldest", "polled", "sources")

    def __init__(self, drivers: list) -> None:
        self.oldest = self.polled = None
        self.sources: list[str] = []
        for d in drivers:
            inner = getattr(d, "driver", d)
            take = getattr(inner, "take_pending", None)
            oldest, polled = take() if take is not None else (None, None)
            if oldest is None:
                continue
            name = getattr(inner, "source_name", None)
            if name:
                self.sources.append(str(name))
            if self.oldest is None or oldest < self.oldest:
                self.oldest, self.polled = oldest, polled


def _elapsed_ns(since: float | None, until: float | None) -> int:
    """Nanoseconds between two ``time.monotonic`` stamps; 0 where either
    is missing (a commit with no row) or they run backwards."""
    if since is None or until is None:
        return 0
    return max(0, int((until - since) * 1e9))


def _observe_commit_latency(
    stamp: float | None, commit_started: float, rows_before: float
) -> None:
    """Stamp the latency histogram with this commit's sink-row delta.
    Rows without an ingest stamp (static data, replays) fall back to the
    commit start so the histogram ``_count`` always equals the rows the
    sinks produced."""
    rows = int(_OUT_ROWS.value - rows_before)
    if rows <= 0:
        return
    origin = stamp if stamp is not None else commit_started
    _INGEST_LATENCY.observe_n(max(0.0, _time.monotonic() - origin), rows)


def _entries_taken(drivers: list) -> int:
    """Rows the connector drivers have fed to their sessions so far."""
    return sum(
        getattr(getattr(d, "driver", d), "entries_total", 0) for d in drivers
    )


def _adopt_scheduler(runner: Any, sched: Scheduler) -> Scheduler:
    runner.scheduler = sched  # telemetry sampler reads stats here
    if runner.monitor is not None:
        runner.monitor.scheduler = sched
    return sched


def _probe_wanted(runner: Any) -> bool:
    return (
        runner.monitor is not None
        and getattr(runner.monitor, "wants_operator_stats", True)
    ) or getattr(runner, "probe_stats", False)


def _replay(drivers: list) -> list:
    """Journaled events go back into their sessions before the first
    poll; returns the drivers that keep a journal."""
    persistent = [d for d in drivers if hasattr(d, "replay")]
    for d in persistent:
        d.replay()
    return persistent


def _resume_and_commit(sched, scopes: list, drivers: list, snapshot_mgr) -> None:
    """A run's first commit: operator persistence restores state directly
    (no event replay) and the clock resumes after the snapshotted commit,
    so sink timestamps / part names stay monotonic across restarts; then
    the static sources (and what was replayed) are one commit. It is a
    ``commit`` stage like the pump's: operators run nowhere else."""
    if snapshot_mgr is not None:
        restored_time = snapshot_mgr.restore(scopes, drivers)
        if restored_time is not None:
            sched.time = max(sched.time, restored_time + 1)
    with _tracing.commit_stage() as commit:
        commit.time = sched.commit()


def _commit_step(
    commit, sched, drivers: list, announce=None, peer_spans=None
) -> tuple[int, float]:
    """One data commit of any runner, inside the ``commit`` stage the pump
    hands over. The four recorders every commit pays stand side by side
    here: the stage's counts and its record on the time line, the sampled
    trace, the latency histogram and the flight ring; what they know of
    the rows' arrivals is one :class:`_Arrivals`. The counts are of the
    commit's oldest row, from its arrival: ``commit_wait_ns`` until the
    commit began, and ``arrival_to_poll_ns`` until the poll that took it,
    which is the part of its autocommit window the pump did not wait
    again (0 for a row polled as it arrived). The mesh leader passes what
    only it has: ``announce`` runs between the trace's begin and the commit
    (the context tuple rides the first exchange round's frames, so
    followers adopt it at commit start) and ``peer_spans`` is where the
    followers' spans arrive. Returns the commit's time and when it
    started."""
    started = _time.monotonic()
    arrivals = _Arrivals(drivers)
    commit.add(
        commit_wait_ns=_elapsed_ns(arrivals.oldest, started),
        arrival_to_poll_ns=_elapsed_ns(arrivals.oldest, arrivals.polled),
    )
    rows_before = _OUT_ROWS.value
    ctx = _tracing.TRACER.begin(
        sched.time, origin_mono=arrivals.oldest, sources=arrivals.sources
    )
    if announce is not None:
        announce()
    commit.time = time = sched.commit()
    _observe_commit_latency(arrivals.oldest, started, rows_before)
    _metrics.FLIGHT.record("commit", time=time)
    if ctx is not None:
        _tracing.TRACER.end(
            time, peer_spans=dict(peer_spans) if peer_spans else None
        )
        if peer_spans:
            peer_spans.clear()
    return time, started


def _after_commit(
    time: int,
    scopes: list,
    drivers: list,
    started: float | None = None,
    *,
    w0: "GraphRunner | None" = None,
    persistent: Sequence = (),
    snapshot_mgr=None,
    fault_plan=None,
    process_id: int = 0,
) -> None:
    """What follows a commit, in the one order every runner keeps: the
    journal's offsets, the operator snapshot, the read tier's view, the
    fault plan, the monitor (``w0`` carries it and the connectors it
    counts). A caller passes what it has."""
    with _tracing.detail("commit.after"):
        serving = _serving.enabled()
        if persistent or snapshot_mgr is not None or serving:
            # exactly-once seam: a checkpoint/offset for commit N may only
            # be cut once N's staged device work has completed (read
            # snapshots sit on the same seam: a published view must
            # contain all of commit N, none of N+1)
            _device_pipeline.drain_until(time)
        for d in persistent:
            d.on_commit(time)
        if snapshot_mgr is not None:
            snapshot_mgr.on_commit(scopes, drivers, time)
        if serving:
            # one snapshot spanning every local replica: reads merge the
            # key-sharded views back into the synchronous answer (in a
            # mesh every process publishes its own shard; rollback
            # republication truncates stale views)
            _serving.publish_on_commit(scopes, time)
        if fault_plan is not None:
            fault_plan.on_commit(process_id, time)
        if w0 is not None and w0.monitor is not None:
            w0._sync_monitor_connectors()
            w0.monitor.on_commit(time, started)


def _end_run(
    sched, scopes: list, drivers: list, persistent: list, snapshot_mgr
) -> None:
    """A run's last commit (a ``commit`` stage too), then the traces, the
    journal's last offsets and the final snapshot."""
    with _tracing.commit_stage() as commit:
        commit.time = sched.time
        sched.finish()
    _tracing.TRACER.export()
    for d in persistent:
        d.on_commit(sched.time)
    if snapshot_mgr is not None:
        snapshot_mgr.snapshot(scopes, drivers, sched.time)


def _pump_drivers(w0: "GraphRunner", drivers: list, on_data, on_idle=None) -> None:
    """The one streaming poll loop (GraphRunner / ShardedGraphRunner /
    DistributedGraphRunner all drive it): poll every connector driver,
    accumulate rows into input sessions, and call ``on_data(commit)``
    (which commits, inside the ``commit`` stage it is handed) when a
    driver's autocommit deadline expires or a driver finishes. Also drains
    passive loopback sources (AsyncTransformer) once no live driver can
    still feed them, and backs off exponentially when idle (``on_idle``
    hooks extra idle work, e.g. coordinator pings).

    The autocommit window (``autocommit_duration_ms`` on each connector,
    reference python/pathway/io/python/__init__.py read kwarg) is what
    keeps commit granularity healthy: committing on every poll turns a
    fast feed into thousands of tiny commits whose per-commit overhead
    (scheduler sweep + device dispatch + decay barrier) dwarfs the row
    work. Data waits at most the window **from its arrival**: a
    connector's deadline is ``first_pending_wall`` (the arrival of its
    oldest uncommitted row, as its reader saw it; the poll's own time
    where the reader cannot say) plus its window, so the time a row
    queued while this loop was inside a commit counts against its window
    and is not waited a second time. A deadline already past at the poll
    starts the commit after that same sweep, which has drained the feed:
    never before the rows are in their sessions. A 0-window connector
    (queries) pulls the commit forward immediately."""
    live = list(drivers)
    idle_spins = 0
    pending = False  # rows sit in input sessions awaiting a commit
    deadline = 0.0
    # consecutive sweeps share one ``pump.poll`` stage: it closes when the
    # loop commits or sleeps, so a busy feed makes one stage, not thousands
    # (a raise leaves it open and uncounted; the run's own stage unwinds it)
    poll = None
    rows_before = 0

    def end_poll() -> None:
        nonlocal poll
        _tracing.end(poll, rows=_entries_taken(drivers) - rows_before)
        poll = None

    while live:
        if poll is None:
            poll = _tracing.begin("pump.poll")
            rows_before = _entries_taken(drivers)
        produced = False
        flush_now = False
        for d in list(live):
            status = d.poll()
            if status == "done":
                live.remove(d)
                produced = True
                flush_now = True  # stream end surfaces immediately
                # a driver's last poll can drain rows AND report EOF in
                # one call — those rows are in the session now, so a
                # commit must follow even if nothing else was pending
                pending = True
            elif status == "data":
                produced = True
                eff = getattr(d, "effective_autocommit_s", None)
                # the window runs from the arrival of the driver's oldest
                # uncommitted row; a driver that keeps no stamp: from now
                arrived = getattr(
                    getattr(d, "driver", d), "first_pending_wall", None
                )
                ac_deadline = (
                    _time.monotonic() if arrived is None else arrived
                ) + (eff() if eff is not None else getattr(d, "autocommit_s", 0.0))
                deadline = min(deadline, ac_deadline) if pending else ac_deadline
                pending = True
        if pending and (flush_now or _time.monotonic() >= deadline):
            end_poll()
            with _tracing.commit_stage() as commit:
                on_data(commit)
            pending = False
            idle_spins = 0
            continue
        if produced:
            idle_spins = 0
            continue  # keep draining the feed until the window closes
        if pending:
            # nothing new this sweep: sleep out (a slice of) the window
            end_poll()
            with _tracing.stage("pump.sleep"):
                _time.sleep(
                    min(max(deadline - _time.monotonic(), 0.0), 0.001)
                )
            continue
        notified = False
        if live and all(
            getattr(d, "upstream_done", None) is not None for d in live
        ):
            for d in live:
                if getattr(d, "_upstream_notified", False):
                    continue
                if w0._loopback_upstream_live(d, live):
                    continue
                d._upstream_notified = True
                d.upstream_done()
                notified = True
                break
        if not notified:
            end_poll()
            idle_spins += 1
            with _tracing.stage("pump.sleep"):
                _time.sleep(min(0.001 * idle_spins, 0.05))
            if on_idle is not None:
                on_idle()


class GraphRunner:
    def __init__(
        self,
        scope: Scope | None = None,
        persistence_config: Any = None,
        attach_drivers: bool = True,
    ) -> None:
        self.scope = scope if scope is not None else Scope()
        self.nodes: dict[int, Node] = {}
        self.attach_drivers = attach_drivers  # False on sharded replicas >0
        self.drivers: list[Any] = []  # connector drivers (streaming mode)
        self.monitors: list[Any] = []
        self.monitor: Any = None  # StatsMonitor (internals/monitoring.py)
        self._local_logs: dict[int, Node] = {}  # local error logs by id
        self.persistence = persistence_config
        if persistence_config is not None:
            self._wire_udf_cache(persistence_config)

    def _sync_monitor_connectors(self) -> None:
        if self.monitor is None:
            return
        seen: dict[str, int] = {}
        for d in self.drivers:
            inner = getattr(d, "driver", d)
            name = getattr(inner, "source_name", None)
            if name is None:
                continue
            # two drivers may share a source_name (e.g. default
            # 'python-connector'); suffix duplicates so counters don't fight
            n = seen.get(name, 0)
            seen[name] = n + 1
            if n:
                name = f"{name}#{n}"
            st = self.monitor.connector(name)
            st.entries = getattr(inner, "entries_total", 0)
            st.batches = getattr(inner, "batches_total", 0)
            wall = getattr(inner, "last_entry_wall", None)
            if wall is not None:
                st.last_entry_at = wall
            st.finished = getattr(inner, "done", False)

    @staticmethod
    def _wire_udf_cache(config: Any) -> None:
        """Route default DiskCaches at the persistence backend (reference:
        PersistenceMode::UdfCaching, servers.py:62-81 with_cache)."""
        import os as _os

        from pathway_tpu.engine.persistence import FileBackend
        from pathway_tpu.internals.udfs.caches import set_udf_cache_root

        backend = getattr(config, "backend", None)
        if isinstance(backend, FileBackend):
            set_udf_cache_root(_os.path.join(backend.root, "udf-cache"))

    # -- expression compilation --------------------------------------------

    def compile(self, expression: ColumnExpression, layout: Layout) -> eex.EngineExpression:
        override = getattr(expression, "_engine_override", None)
        if override is not None:
            return override
        c = lambda e: self.compile(e, layout)  # noqa: E731
        if isinstance(expression, ColumnReference):
            if expression.name == "id":
                pos = layout.id_columns.get(expression.table._id)
                if pos is not None:
                    return eex.ColumnRef(pos)
                if expression.table._id in layout.key_tables:
                    return eex.KeyRef()
                raise ValueError(
                    f"cannot reference {expression!r} in this context"
                )
            pos = layout.position(expression)
            if pos is None:
                raise ValueError(
                    f"column {expression!r} is not available in this context"
                )
            return eex.ColumnRef(pos)
        if isinstance(expression, pex.ColumnConstExpression):
            return eex.Const(expression._value)
        if isinstance(expression, pex.BinaryOpExpression):
            return eex.Binary(expression._op, c(expression._left), c(expression._right))
        if isinstance(expression, pex.UnaryOpExpression):
            return eex.Unary(expression._op, c(expression._arg))
        if isinstance(expression, pex.BooleanExpression):
            return eex.BooleanChain(expression._op, [c(a) for a in expression._args])
        if isinstance(expression, pex.IsNoneExpression):
            return eex.IsNone(c(expression._arg), expression._negated)
        if isinstance(expression, pex.IfElseExpression):
            return eex.IfElse(
                c(expression._cond), c(expression._then), c(expression._otherwise)
            )
        if isinstance(expression, pex.CoalesceExpression):
            return eex.Coalesce([c(a) for a in expression._args])
        if isinstance(expression, pex.RequireExpression):
            return eex.Require(c(expression._value), [c(d) for d in expression._deps])
        if isinstance(expression, pex.ApplyExpression):
            args = [c(a) for a in expression._args]
            kw_names = list(expression._kwargs.keys())
            args += [c(expression._kwargs[k]) for k in kw_names]
            fn = _make_kw_fn(expression._fn, len(expression._args), kw_names)
            return eex.Apply(
                fn,
                args,
                propagate_none=expression._propagate_none,
                deterministic=expression._deterministic,
            )
        if isinstance(expression, pex.CastExpression):
            target = _CAST_NAMES.get(expression._dtype.strip_optional())
            if target is None:
                return c(expression._arg)
            return eex.Cast(c(expression._arg), target)
        if isinstance(expression, pex.DeclareTypeExpression):
            return c(expression._arg)
        if isinstance(expression, pex.ConvertExpression):
            return eex.Convert(c(expression._arg), expression._target, expression._unwrap)
        if isinstance(expression, pex.UnwrapExpression):
            return eex.Unwrap(c(expression._arg))
        if isinstance(expression, pex.FillErrorExpression):
            return eex.FillError(c(expression._arg), c(expression._fallback))
        if isinstance(expression, pex.MakeTupleExpression):
            return eex.MakeTuple([c(a) for a in expression._args])
        if isinstance(expression, pex.GetExpression):
            return eex.SequenceGet(
                c(expression._arg),
                c(expression._index),
                c(expression._default) if expression._default is not None else None,
                expression._checked,
            )
        if isinstance(expression, pex.PointerExpression):
            return eex.PointerFrom(
                [c(a) for a in expression._args],
                c(expression._instance) if expression._instance is not None else None,
            )
        if isinstance(expression, pex.BatchApplyExpression):
            raise NotImplementedError(
                "async/batched UDF calls are only supported as top-level "
                "select columns"
            )
        if isinstance(expression, pex.ReducerExpression):
            raise ValueError("reducers are only allowed inside .reduce(...)")
        raise NotImplementedError(f"cannot compile expression {expression!r}")

    # -- storage ------------------------------------------------------------

    def storage_for(
        self, base: "Table", expressions: Sequence[ColumnExpression]
    ) -> tuple[Node, Layout]:
        """Build a storage node exposing ``base``'s columns plus any columns
        of other (universe-related) tables referenced by ``expressions``."""
        tables: dict[int, "Table"] = {base._id: base}
        for e in expressions:
            for ref in e._dependencies():
                t = ref.table
                if t._id not in tables:
                    if not solver.query_related(base._universe, t._universe):
                        raise ValueError(
                            f"column {ref!r} belongs to a table with an unrelated "
                            f"universe; join or use with_universe_of first"
                        )
                    tables[t._id] = t
        ordered = [base] + [t for tid, t in sorted(tables.items()) if tid != base._id]
        nodes = [self.build(t) for t in ordered]
        storage = self.scope.zip_tables(nodes)
        layout = Layout()
        offset = 0
        for t in ordered:
            for i, name in enumerate(t._column_names):
                layout.columns[(t._id, name)] = offset + i
            layout.key_tables.add(t._id)
            offset += len(t._column_names)
        return storage, layout

    def base_layout(self, table: "Table") -> Layout:
        layout = Layout()
        for i, name in enumerate(table._column_names):
            layout.columns[(table._id, name)] = i
        layout.key_tables.add(table._id)
        return layout

    # -- lowering -----------------------------------------------------------

    def _error_log_node(self, log_id):
        if log_id is None:
            return self.scope.error_log_default
        node = self._local_logs.get(log_id)
        if node is None:
            node = self._local_logs[log_id] = self.scope.error_log()
        return node

    def build(self, table: "Table") -> Node:
        if table._id in self.nodes:
            return self.nodes[table._id]
        node = self._build(table)
        log_id = getattr(table, "_error_log_id", None)
        if log_id is not None:
            node.error_log = self._error_log_node(log_id)
        node.name = f"{table._spec.kind}<{table._name}>"
        node.trace = table._trace
        self._annotate_schema(node, table)
        self.nodes[table._id] = node
        return node

    @staticmethod
    def _annotate_schema(node: Node, table: "Table") -> None:
        """Attach the framework-level dtypes as engine-type hints for the
        static analyzer (pathway_tpu/analysis): ``node.schema_types`` is a
        list of per-column ``frozenset[engine Type]`` possible-type sets.
        Only attached when the built node's tuple layout matches the table
        columns 1:1 (the base_layout invariant); the analyzer uses the
        hint for source-like and opaque nodes and infers the rest."""
        if node.arity != len(table._column_names):
            return
        hints = []
        for name in table._column_names:
            d = table._dtypes.get(name)
            if d is None:
                hints.append(frozenset({dt.EngineType.ANY}))
                continue
            try:
                members = {d.strip_optional().to_engine()}
                if d.is_optional():
                    members.add(dt.EngineType.NONE)
            except Exception:  # noqa: BLE001 — exotic dtype: stay opaque
                members = {dt.EngineType.ANY}
            hints.append(frozenset(members))
        node.schema_types = hints

    def _project(self, node: Node, positions: Sequence[int]) -> Node:
        return self.scope.expression_table(node, [eex.ColumnRef(i) for i in positions])

    def _build(self, table: "Table") -> Node:
        spec = table._spec
        kind = spec.kind
        scope = self.scope

        if kind == "error_log":
            return self._error_log_node(spec.params.get("log_id"))

        if kind == "static":
            return scope.static_table(spec.params["rows"], len(table._column_names))

        if kind == "input":
            # connector-backed table: the io layer supplies an attach function
            attach = spec.params["attach"]
            import inspect

            if "make_driver" in inspect.signature(attach).parameters:
                node, driver = attach(scope, make_driver=self.attach_drivers)
            else:  # custom attach without the kwarg: discard after the fact
                node, driver = attach(scope)
            if driver is not None and not self.attach_drivers:
                driver = None  # replica scopes never poll; worker 0 reads
            if driver is not None:
                sync_group = spec.params.get("sync_group")
                if sync_group is not None:
                    sync_group.ensure_run(id(self))
                    driver.sync_group = sync_group
                    driver.sync_col = table._column_names.index(
                        spec.params["sync_column"]
                    )
                    sync_group.register(driver)
                persistent_id = spec.params.get("persistent_id")
                if persistent_id is not None and self.persistence is not None:
                    from pathway_tpu.engine.persistence import PersistentDriver
                    from pathway_tpu.persistence import PersistenceMode

                    if (
                        self.persistence.persistence_mode
                        == PersistenceMode.PERSISTING
                    ):
                        driver = PersistentDriver(
                            driver, self.persistence.backend, persistent_id
                        )
                self.drivers.append(driver)
            return node

        if kind == "select":
            exprs = spec.params["exprs"]
            expr_list = list(exprs.values())
            storage, layout = self.storage_for(spec.inputs[0], expr_list)
            if not any(isinstance(e, pex.BatchApplyExpression) for e in expr_list):
                return scope.expression_table(
                    storage, [self.compile(e, layout) for e in expr_list]
                )
            return self._build_select_with_udfs(expr_list, storage, layout)

        if kind == "filter":
            base = spec.inputs[0]
            cond = spec.params["condition"]
            storage, layout = self.storage_for(base, [cond])
            n = len(base._column_names)
            pre = scope.expression_table(
                storage,
                [
                    self.compile(ColumnReference(base, name), layout)
                    for name in base._column_names
                ]
                + [self.compile(cond, layout)],
            )
            filtered = scope.filter_table(pre, n)
            return self._project(filtered, range(n))

        if kind == "remove_errors":
            return scope.remove_errors_from_table(self.build(spec.inputs[0]))

        if kind == "groupby_reduce":
            return self._build_groupby(table)

        if kind == "join_select":
            return self._build_join(table)

        if kind == "concat":
            aligned = []
            for t in spec.inputs:
                node = self.build(t)
                layout = self.base_layout(t)
                aligned.append(
                    scope.expression_table(
                        node,
                        [
                            self.compile(ColumnReference(t, name), layout)
                            for name in table._column_names
                        ],
                    )
                )
            return scope.concat_tables(aligned)

        if kind == "update_rows":
            orig, updates = spec.inputs
            orig_node = self.build(orig)
            upd_node = self.build(updates)
            upd_layout = self.base_layout(updates)
            upd_aligned = scope.expression_table(
                upd_node,
                [
                    self.compile(ColumnReference(updates, name), upd_layout)
                    for name in table._column_names
                ],
            )
            return scope.update_rows_table(orig_node, upd_aligned)

        if kind == "update_cells":
            orig, updates = spec.inputs
            orig_node = self.build(orig)
            upd_node = self.build(updates)
            update_cols = [
                updates._column_names.index(name) if name in updates._column_names else -1
                for name in table._column_names
            ]
            return scope.update_cells_table(orig_node, upd_node, update_cols)

        if kind == "reindex":
            base = spec.inputs[0]
            new_id = spec.params["new_id"]
            storage, layout = self.storage_for(base, [new_id])
            n = len(base._column_names)
            pre = scope.expression_table(
                storage,
                [
                    self.compile(ColumnReference(base, name), layout)
                    for name in base._column_names
                ]
                + [self.compile(new_id, layout)],
            )
            reindexed = scope.reindex_table(pre, n)
            return self._project(reindexed, range(n))

        if kind == "intersect":
            base, *others = spec.inputs
            return scope.intersect_tables(
                self.build(base), [self.build(o) for o in others]
            )

        if kind == "subtract":
            base, other = spec.inputs
            return scope.subtract_table(self.build(base), self.build(other))

        if kind == "restrict":
            base, other = spec.inputs
            return scope.restrict_table(self.build(base), self.build(other))

        if kind == "override_universe":
            base, other = spec.inputs
            return scope.override_table_universe(self.build(base), self.build(other))

        if kind == "flatten":
            base = spec.inputs[0]
            col_idx = base._column_names.index(spec.params["column"])
            return scope.flatten_table(
                self.build(base),
                col_idx,
                with_origin=spec.params.get("origin_id") is not None,
            )

        if kind == "sort":
            base = spec.inputs[0]
            key_expr = spec.params["key"]
            inst_expr = spec.params["instance"]
            exprs = [key_expr] + ([inst_expr] if inst_expr is not None else [])
            storage, layout = self.storage_for(base, exprs)
            pre = scope.expression_table(storage, [self.compile(e, layout) for e in exprs])
            return scope.sort_table(pre, 0, 1 if inst_expr is not None else None)

        if kind == "ix":
            keys_table, source = spec.inputs
            keys_node = self.build(keys_table)
            source_node = self.build(source)
            key_col = keys_table._column_names.index("_pw_ix_key")
            return scope.ix_table(
                keys_node,
                source_node,
                key_col,
                optional=spec.params.get("optional", False),
                strict=not spec.params.get("allow_misses", False),
            )

        if kind == "deduplicate":
            base = spec.inputs[0]
            value = spec.params["value"]
            instance = spec.params["instance"]
            storage, layout = self.storage_for(base, [value, *instance])
            n = len(base._column_names)
            pre_exprs = [
                self.compile(ColumnReference(base, name), layout)
                for name in base._column_names
            ]
            pre_exprs.append(self.compile(value, layout))
            for inst in instance:
                pre_exprs.append(self.compile(inst, layout))
            pre = scope.expression_table(storage, pre_exprs)
            dedup = scope.deduplicate(
                pre,
                value_col=n,
                instance_cols=list(range(n + 1, n + 1 + len(instance))),
                acceptor=spec.params["acceptor"],
            )
            return self._project(dedup, range(n))

        if kind == "external_index":
            from pathway_tpu.engine.external_index import ExternalIndexNode

            data_t, query_t = spec.inputs
            data_node = self.build(data_t)
            query_node = self.build(query_t)
            data_prep = scope.expression_table(
                data_node,
                [self.compile(spec.params["index_expr"], self.base_layout(data_t))],
            )
            query_layout = self.base_layout(query_t)
            q_exprs = [self.compile(spec.params["query_expr"], query_layout)]
            limit_col = None
            if spec.params["limit_expr"] is not None:
                q_exprs.append(self.compile(spec.params["limit_expr"], query_layout))
                limit_col = 1
            query_prep = scope.expression_table(query_node, q_exprs)
            return ExternalIndexNode(
                scope,
                data_prep,
                query_prep,
                spec.params["factory"](),
                index_col=0,
                query_col=0,
                k=spec.params["k"],
                limit_col=limit_col,
            )

        if kind in ("buffer", "forget", "freeze"):
            from pathway_tpu.engine import temporal as tmp

            base_node = self.build(spec.inputs[0])
            cls = {
                "buffer": tmp.BufferNode,
                "forget": tmp.ForgetNode,
                "freeze": tmp.FreezeNode,
            }[kind]
            return cls(
                scope,
                base_node,
                spec.params["threshold_col"],
                spec.params["time_col"],
            )

        if kind == "row_transformer":
            sources = [self.build(t) for t in spec.inputs]
            return scope.recompute_table(
                sources, spec.params["compute"], spec.params["arity"]
            )

        if kind == "gradual_broadcast":
            from pathway_tpu.engine.temporal import GradualBroadcastNode

            base_node = self.build(spec.inputs[0])
            # threshold table lowered to a 3-column (lower, value, upper)
            # storage by Table._gradual_broadcast
            thr_node = self.build(spec.inputs[1])
            return GradualBroadcastNode(scope, base_node, thr_node)

        if kind == "session_assign":
            from pathway_tpu.engine.temporal import SessionAssignNode

            return SessionAssignNode(
                scope,
                self.build(spec.inputs[0]),
                spec.params["time_col"],
                spec.params["instance_col"],
                spec.params["max_gap"],
            )

        if kind in ("interval_join", "asof_join", "asof_now_join"):
            return self._build_temporal_join(table)

        if kind == "iterate_param":
            rows = getattr(self, "iterate_params", None)
            if rows is None:
                raise ValueError(
                    "iterate parameter table used outside pw.iterate"
                )
            return scope.static_table(
                rows[spec.params["slot"]], len(table._column_names)
            )

        if kind == "table_transform":
            from pathway_tpu.engine.iterate import IterateNode

            fn = spec.params["fn"]
            node = self.build(spec.inputs[0])
            return IterateNode(
                scope,
                [node],
                len(table._column_names),
                lambda states, _fn=fn: _fn(states[0]),
            )

        if kind == "iterate_result":
            from pathway_tpu.engine.iterate import IterateNode

            engine = spec.params["engine"]
            name = spec.params["name"]
            input_nodes = [self.build(t) for t in spec.inputs]

            def compute(states: list[dict], _engine=engine, _name=name) -> dict:
                return _engine.compute_all(states)[_name]

            return IterateNode(
                scope, input_nodes, len(table._column_names), compute
            )

        raise NotImplementedError(f"unknown table spec kind {kind!r}")

    def _build_temporal_join(self, table: "Table") -> Node:
        from pathway_tpu.engine import temporal as tmp

        spec = table._spec
        kind = spec.kind
        left, right = spec.inputs
        on = spec.params["on"]
        how = spec.params["how"]
        exprs: dict[str, ColumnExpression] = spec.params["exprs"]
        scope = self.scope

        left_node = self.build(left)
        right_node = self.build(right)
        llayout = self.base_layout(left)
        rlayout = self.base_layout(right)
        nl = len(left._column_names)
        nr = len(right._column_names)
        k = len(on)

        has_time = kind in ("interval_join", "asof_join")
        # interval/asof nodes key on ONE instance value: several equality
        # conditions fold into a single tuple-valued column (exactly the
        # reference's `*on` -> join key tuple, _interval_join.py:583)
        fold = has_time and k > 1

        def prep(node, side, layout, n, time_expr):
            extras: list[eex.EngineExpression] = [eex.KeyRef()]
            if time_expr is not None:
                extras.append(self.compile(time_expr, layout))
            # explicit side index: `base is left` would misfire on
            # self-joins where left and right are the same table
            compiled = [self.compile(pair[side], layout) for pair in on]
            if fold:
                extras.append(eex.MakeTuple(compiled))
            else:
                extras.extend(compiled)
            return scope.expression_table(
                node, [eex.ColumnRef(i) for i in range(n)] + extras
            )

        lt_expr = spec.params.get("left_time")
        rt_expr = spec.params.get("right_time")
        left_prep = prep(left_node, 0, llayout, nl, lt_expr if has_time else None)
        right_prep = prep(right_node, 1, rlayout, nr, rt_expr if has_time else None)

        t_off = 1 if has_time else 0
        k_extras = 1 if fold else k
        l_inst = list(range(nl + 1 + t_off, nl + 1 + t_off + k_extras))
        r_inst = list(range(nr + 1 + t_off, nr + 1 + t_off + k_extras))

        if kind == "interval_join":
            node = tmp.IntervalJoinNode(
                scope,
                left_prep,
                right_prep,
                left_time_col=nl + 1,
                right_time_col=nr + 1,
                lower_bound=spec.params["lower_bound"],
                upper_bound=spec.params["upper_bound"],
                left_instance_col=l_inst[0] if k >= 1 else None,
                right_instance_col=r_inst[0] if k >= 1 else None,
                kind=how,
            )
        elif kind == "asof_join":
            node = tmp.AsofJoinNode(
                scope,
                left_prep,
                right_prep,
                left_time_col=nl + 1,
                right_time_col=nr + 1,
                left_instance_col=l_inst[0] if k >= 1 else None,
                right_instance_col=r_inst[0] if k >= 1 else None,
                direction=spec.params["direction"],
                kind=how,
            )
        else:
            node = tmp.AsofNowJoinNode(
                scope, left_prep, right_prep, l_inst, r_inst, kind=how
            )
        combined = Layout()
        for i, name in enumerate(left._column_names):
            combined.columns[(left._id, name)] = i
        combined.id_columns[left._id] = nl
        off = nl + 1 + t_off + k_extras
        for i, name in enumerate(right._column_names):
            combined.columns[(right._id, name)] = off + i
        combined.id_columns[right._id] = off + nr
        return scope.expression_table(
            node, [self.compile(e, combined) for e in exprs.values()]
        )

    def _build_select_with_udfs(
        self,
        expr_list: list[ColumnExpression],
        storage: Node,
        layout: Layout,
    ) -> Node:
        """Select with UDF (BatchApply) columns: plain columns evaluate in one
        expression node; each UDF column becomes a BatchApplyNode over the
        same prep node; results zip back together in output order.

        UDF calls nested inside other expressions are rejected — the engine
        batches them per commit, so they must be whole select columns
        (matching the reference's async_apply_table contract,
        src/engine/dataflow.rs:1757)."""
        scope = self.scope

        def check_no_nested(e: ColumnExpression) -> None:
            for child in e._children():
                if isinstance(child, pex.BatchApplyExpression):
                    raise NotImplementedError(
                        "async/batched UDF calls must be top-level select "
                        "columns, not nested inside other expressions"
                    )
                check_no_nested(child)

        pre_exprs: list[eex.EngineExpression] = []
        plan: list[tuple[str, Any]] = []
        for e in expr_list:
            check_no_nested(e)
            if isinstance(e, pex.BatchApplyExpression):
                arg_positions = []
                for a in (*e._args, *e._kwargs.values()):
                    pre_exprs.append(self.compile(a, layout))
                    arg_positions.append(len(pre_exprs) - 1)
                plan.append(("batch", (e, arg_positions)))
            else:
                pre_exprs.append(self.compile(e, layout))
                plan.append(("plain", len(pre_exprs) - 1))
        pre = scope.expression_table(storage, pre_exprs)
        parts: list[Node] = [pre]
        col_map: list[int] = []
        offset = len(pre_exprs)
        for tag, payload in plan:
            if tag == "plain":
                col_map.append(payload)
            else:
                e, arg_positions = payload
                node = scope.batch_apply_table(
                    pre, e._rows_fn, arg_positions, e._propagate_none
                )
                node.name = f"udf<{e._name}>"
                parts.append(node)
                col_map.append(offset)
                offset += 1
        zipped = scope.zip_tables(parts)
        return self._project(zipped, col_map)

    def _build_groupby(self, table: "Table") -> Node:
        from pathway_tpu.internals.table import Table as TableCls

        spec = table._spec
        base = spec.inputs[0]
        by_refs: list[ColumnReference] = spec.params["by"]
        exprs: dict[str, ColumnExpression] = spec.params["exprs"]
        set_id: bool = spec.params["set_id"]
        scope = self.scope

        # collect distinct reducer nodes over all output expressions
        reducer_nodes: list[pex.ReducerExpression] = []

        def collect(e: ColumnExpression) -> None:
            if isinstance(e, pex.ReducerExpression):
                if not any(e is r for r in reducer_nodes):
                    reducer_nodes.append(e)
                return
            for child in e._children():
                collect(child)

        for e in exprs.values():
            collect(e)

        arg_exprs: list[ColumnExpression] = []
        for r in reducer_nodes:
            arg_exprs.extend(r._args)

        storage, layout = self.storage_for(base, [*by_refs, *arg_exprs])
        pre_exprs: list[eex.EngineExpression] = [
            self.compile(b, layout) for b in by_refs
        ]
        nb = len(by_refs)
        reducer_descr = []
        pos = nb
        for r in reducer_nodes:
            arg_cols = list(range(pos, pos + len(r._args)))
            pre_exprs.extend(self.compile(a, layout) for a in r._args)
            pos += len(r._args)
            # ARG_MIN/ARG_MAX take (value, row-id) pairs
            from pathway_tpu.engine.reducers import ReducerKind

            if r._kind in (ReducerKind.ARG_MIN, ReducerKind.ARG_MAX):
                pre_exprs.append(eex.KeyRef())
                arg_cols = [arg_cols[0], pos]
                pos += 1
            reducer_descr.append((make_reducer(r._kind, **r._options), arg_cols))

        pre = scope.expression_table(storage, pre_exprs)
        grouped = scope.group_by_table(
            pre,
            by_cols=list(range(nb)),
            reducers=reducer_descr,
            set_id=set_id,
            instance_last=spec.params.get("instance_last", False),
        )

        # post-projection: reducer nodes -> group-row positions; by refs too
        by_positions = {(b.table._id, b.name): i for i, b in enumerate(by_refs)}

        post_layout = Layout()
        post_layout.columns.update(by_positions)

        def replace(e: ColumnExpression) -> ColumnExpression | None:
            for i, r in enumerate(reducer_nodes):
                if e is r:
                    marker = pex.ColumnConstExpression(None)
                    marker._engine_override = eex.ColumnRef(nb + i)  # type: ignore[attr-defined]
                    return marker
            return None

        post_exprs = []
        for e in exprs.values():
            substituted = substitute(e, replace)
            post_exprs.append(self.compile(substituted, post_layout))
        return scope.expression_table(grouped, post_exprs)

    def _build_join(self, table: "Table") -> Node:
        spec = table._spec
        left, right = spec.inputs
        on = spec.params["on"]
        how = spec.params["how"]
        exprs: dict[str, ColumnExpression] = spec.params["exprs"]
        scope = self.scope

        left_node = self.build(left)
        right_node = self.build(right)
        llayout = self.base_layout(left)
        rlayout = self.base_layout(right)

        nl = len(left._column_names)
        nr = len(right._column_names)
        k = len(on)

        left_prep = scope.expression_table(
            left_node,
            [eex.ColumnRef(i) for i in range(nl)]
            + [eex.KeyRef()]
            + [self.compile(le, llayout) for le, _re in on],
        )
        right_prep = scope.expression_table(
            right_node,
            [eex.ColumnRef(i) for i in range(nr)]
            + [eex.KeyRef()]
            + [self.compile(re_, rlayout) for _le, re_ in on],
        )
        id_spec = spec.params.get("id_spec")
        if id_spec is not None and id_spec[1] is not None:
            # name -> column index in the side's prep row
            side, name = id_spec
            names = (left if side == "left" else right)._column_names
            id_spec = (side, names.index(name))
        joined = scope.join_tables(
            left_prep,
            right_prep,
            left_on=list(range(nl + 1, nl + 1 + k)),
            right_on=list(range(nr + 1, nr + 1 + k)),
            kind=how,
            id_spec=id_spec,
        )
        combined = Layout()
        for i, name in enumerate(left._column_names):
            combined.columns[(left._id, name)] = i
        combined.id_columns[left._id] = nl
        off = nl + 1 + k
        for i, name in enumerate(right._column_names):
            combined.columns[(right._id, name)] = off + i
        combined.id_columns[right._id] = off + nr
        return scope.expression_table(
            joined, [self.compile(e, combined) for e in exprs.values()]
        )

    # -- execution ----------------------------------------------------------

    def _make_scheduler(self) -> Scheduler:
        return _adopt_scheduler(
            self, Scheduler(self.scope, probe=_probe_wanted(self))
        )

    def run_static(self) -> Scheduler:
        sched = self._make_scheduler()
        t0 = _time.monotonic()
        with _tracing.commit_stage() as commit:
            commit.time = 0  # every static source's rows are of time 0
            sched.run_static()
        _after_commit(sched.time, [self.scope], self.drivers, t0, w0=self)
        return sched

    @_tracing.traced_run
    def run(self) -> Scheduler:
        """Run to completion: static commit if no drivers, else the streaming
        loop (poll drivers, commit, until all report done)."""
        if not self.drivers:
            return self.run_static()
        sched = self._make_scheduler()
        scopes, drivers = [self.scope], self.drivers
        persistent = _replay(drivers)
        if persistent:
            # flush replayed events as the first commit so downstream state
            # is rebuilt even if no new input arrives
            with _tracing.commit_stage() as commit:
                commit.time = sched.commit()
        snapshot_mgr = self._operator_snapshot_manager()
        _resume_and_commit(sched, scopes, drivers, snapshot_mgr)

        def on_data(commit) -> None:
            time, started = _commit_step(commit, sched, drivers)
            _after_commit(
                time, scopes, drivers, started, w0=self,
                persistent=persistent, snapshot_mgr=snapshot_mgr,
            )

        _pump_drivers(self, drivers, on_data)
        _end_run(sched, scopes, drivers, persistent, snapshot_mgr)
        return sched

    def _loopback_upstream_live(self, driver, remaining) -> bool:
        """True when another still-running driver's input session can reach
        this loopback's subscribed table — its results may yet produce new
        rows for the subscription, so the loopback must stay open."""
        upstream = getattr(driver, "upstream_table", None)
        if upstream is None:
            return False
        node = self.build(upstream)
        ancestors: set[int] = set()
        stack = [node]
        while stack:
            n = stack.pop()
            if id(n) in ancestors:
                continue
            ancestors.add(id(n))
            stack.extend(n.inputs)
        for other in remaining:
            if other is driver:
                continue
            session = getattr(other, "session", None)
            inner = getattr(session, "_session", session)
            if inner is not None and id(inner) in ancestors:
                return True
        return False

    def _operator_snapshot_manager(self):
        if self.persistence is None:
            return None
        from pathway_tpu.engine.persistence import OperatorSnapshotManager
        from pathway_tpu.persistence import PersistenceMode

        if (
            getattr(self.persistence, "persistence_mode", None)
            != PersistenceMode.OPERATOR_PERSISTING
        ):
            return None
        return OperatorSnapshotManager(
            self.persistence.backend,
            getattr(self.persistence, "snapshot_interval_ms", 0),
        )

    def capture(self, *tables: "Table") -> list[dict[Pointer, tuple]]:
        from pathway_tpu.internals import parse_graph

        nodes = [self.build(t) for t in tables]
        for node in nodes:
            # capture reads node state directly, without a SubscribeNode —
            # the graph optimizer must treat these as observed sinks (no
            # fusion-inerting, no arity narrowing)
            node._pw_observed = True
        # attach + consume INTERNAL sinks only (AsyncTransformer loopback
        # subscriptions — a capture without them would deadlock); user
        # output sinks stay registered for the eventual pw.run()
        remaining = []
        for sink in parse_graph.G.sinks:
            if not sink.internal:
                remaining.append(sink)
                continue
            node = self.build(sink.table)
            driver = sink.attach(self.scope, node)
            if driver is not None:
                self.drivers.append(driver)
        parse_graph.G.sinks = remaining
        self.run()
        return [node.snapshot() for node in nodes]


class ShardedGraphRunner:
    """N logical workers, each owning a replica of the graph; batches
    exchange between operator replicas by co-location key
    (engine/sharded.py; reference worker model config.rs:63-120).

    Input connectors poll on worker 0 and reshard (reference
    dataflow.rs:3492 `scope.index() < parallel_readers`); subscribe/output
    sinks attach on worker 0 only (single-threaded sinks,
    data_storage.rs:611).
    """

    def __init__(self, n_workers: int, persistence_config: Any = None) -> None:
        if n_workers < 1:
            raise ValueError("n_workers must be >= 1")
        from pathway_tpu.internals.license import check_worker_count

        check_worker_count(n_workers)
        self.workers = [
            GraphRunner(
                persistence_config=persistence_config,
                attach_drivers=(i == 0),
            )
            for i in range(n_workers)
        ]
        self.n = n_workers
        self.monitor: Any = None

    def build(self, table: "Table") -> list[Node]:
        return [w.build(table) for w in self.workers]

    def _make_scheduler(self):
        from pathway_tpu.engine.sharded import ShardedScheduler

        return _adopt_scheduler(
            self,
            ShardedScheduler(
                [w.scope for w in self.workers], probe=_probe_wanted(self)
            ),
        )

    @_tracing.traced_run
    def run(self):
        sched = self._make_scheduler()
        w0 = self.workers[0]
        w0.monitor = self.monitor  # worker 0 counts the connectors for it
        drivers = list(w0.drivers)  # inputs read on worker 0
        scopes = [w.scope for w in self.workers]
        persistent = _replay(drivers)
        snapshot_mgr = w0._operator_snapshot_manager()
        _resume_and_commit(sched, scopes, drivers, snapshot_mgr)

        def on_data(commit) -> None:
            time, started = _commit_step(commit, sched, drivers)
            _after_commit(
                time, scopes, drivers, started, w0=w0,
                persistent=persistent, snapshot_mgr=snapshot_mgr,
            )

        _pump_drivers(w0, drivers, on_data)
        _end_run(sched, scopes, drivers, persistent, snapshot_mgr)
        if not drivers:
            # static run: the single up-front commit bypassed on_data
            _after_commit(sched.time, scopes, drivers)
        return sched

    def capture(self, *tables: "Table") -> list[dict[Pointer, tuple]]:
        from pathway_tpu.internals import parse_graph

        replicas = [self.build(t) for t in tables]
        for reps in replicas:
            for node in reps:
                # capture reads replica state without a SubscribeNode; the
                # optimizer must leave these nodes intact on every worker
                node._pw_observed = True
        # internal sinks: worker 0 only; build every sink table first so
        # SubscribeNodes land after all shared nodes (index alignment)
        remaining = [s for s in parse_graph.G.sinks if not s.internal]
        internal = [s for s in parse_graph.G.sinks if s.internal]
        nodes = [self.workers[0].build(s.table) for s in internal]
        for w in self.workers[1:]:
            for s in internal:
                w.build(s.table)
        for sink, node in zip(internal, nodes):
            driver = sink.attach(self.workers[0].scope, node)
            if driver is not None:
                self.workers[0].drivers.append(driver)
        parse_graph.G.sinks = remaining
        sched = self.run()
        return [
            sched.merged_state(reps[0].index) for reps in replicas
        ]

    def attach_sinks(self) -> None:
        """Attach ALL registered sinks on worker 0 (pw.run path). All sink
        tables build FIRST so SubscribeNodes land after every shared node
        and worker replicas stay index-aligned."""
        _attach_sinks_on_primary(self.workers, attach=True)


def _attach_sinks_on_primary(workers: list, attach: bool) -> int:
    """Build every registered sink table on every worker replica (index
    alignment), then attach the actual sink drivers on worker 0's scope
    (single-threaded sinks, reference data_storage.rs:611) — or skip the
    attachment entirely (follower processes). Returns the shared graph
    length: nodes past it exist only on the attaching scope."""
    from pathway_tpu.internals import parse_graph

    sinks = list(parse_graph.G.sinks)
    nodes = [workers[0].build(s.table) for s in sinks]
    for w in workers[1:]:
        for s in sinks:
            w.build(s.table)
    n_shared = len(workers[0].scope.nodes)
    if attach:
        for sink, node in zip(sinks, nodes):
            driver = sink.attach(workers[0].scope, node)
            if driver is not None:
                workers[0].drivers.append(driver)
    parse_graph.G.sinks = []
    return n_shared


class DistributedGraphRunner:
    """Multi-process execution: the same program running in PATHWAY_PROCESSES
    processes, exchanging key-sharded batches over the TCP mesh
    (engine/distributed.py; reference CommunicationConfig::Cluster,
    config.rs:72-86, launched by `pathway spawn`, cli.py:93-107).

    Every process hosts ``threads`` local worker replicas; total workers =
    threads x processes. Process 0 is the coordinator: connector drivers
    poll there, sinks attach there, and it broadcasts commit/finish
    commands to the followers.
    """

    def __init__(
        self,
        threads: int,
        processes: int,
        process_id: int,
        first_port: int = 10000,
        persistence_config: Any = None,
    ) -> None:
        if processes < 2:
            raise ValueError("DistributedGraphRunner needs processes >= 2")
        if not 0 <= process_id < processes:
            raise ValueError(
                f"PATHWAY_PROCESS_ID={process_id} out of range for "
                f"{processes} processes"
            )
        from pathway_tpu.internals.license import check_worker_count

        check_worker_count(threads * processes)
        self.threads = threads
        self.processes = processes
        self.process_id = process_id
        self.first_port = first_port
        #: the full persistence config, kept on EVERY process: operator-
        #: persisting meshes give each process its own snapshot manager
        #: (journal/UDF-cache wiring below stays primary-only)
        self.persistence = persistence_config
        primary = process_id == 0
        self.workers = [
            GraphRunner(
                persistence_config=persistence_config if primary else None,
                attach_drivers=primary and i == 0,
            )
            for i in range(threads)
        ]
        self.monitor: Any = None
        self._epoch = 0

    def build(self, table: "Table") -> list[Node]:
        return [w.build(table) for w in self.workers]

    def attach_sinks(self) -> None:
        """Build every sink table on every local replica (index alignment
        across processes); attach actual sink drivers on process 0 only."""
        self.n_shared = _attach_sinks_on_primary(
            self.workers, attach=self.process_id == 0
        )

    @_tracing.traced_run
    def run(self):
        from pathway_tpu.engine.distributed import (
            DistributedScheduler,
            MeshTransport,
        )

        if os.environ.get("PATHWAY_TPU_RESHARD"):
            # one-shot re-shard helper (MeshSupervisor rescale): the same
            # program, launched with the NEW process count, rewrites the
            # per-process operator snapshots instead of joining a mesh
            return self._reshard_snapshots(
                int(os.environ["PATHWAY_TPU_RESHARD"])
            )
        transport = MeshTransport(
            self.process_id, self.processes, self.first_port
        )
        try:
            sched = DistributedScheduler(
                [w.scope for w in self.workers],
                self.process_id,
                self.processes,
                transport,
                # attach_sinks records the pre-attachment length; without
                # sinks, every node is shared on every replica
                n_shared=getattr(
                    self, "n_shared", len(self.workers[0].scope.nodes)
                ),
                # followers always probe: their piggybacked mesh snapshots
                # must carry per-operator series for the leader's /metrics
                # even though their own monitoring level is forced NONE
                probe=_probe_wanted(self) or self.process_id != 0,
            )
            _adopt_scheduler(self, sched)
            if self.monitor is not None:
                # live reference: the leader's endpoint renders follower
                # snapshots as they arrive on round frames
                self.monitor.mesh_snapshots = sched.mesh_metrics
            if self.process_id == 0:
                sched.announce_topology()
                self._coordinate(sched, transport)
            else:
                sched.receive_topology()
                self._follow(sched, transport)
            return sched
        finally:
            transport.close()

    # -- rescale -------------------------------------------------------------

    def _reshard_snapshots(self, old_processes: int):
        """Re-shard the mesh's per-process operator snapshots from
        ``old_processes`` to ``self.processes`` worker processes.

        Runs in a dedicated helper child between the quiesced old mesh and
        the relaunched new one: the graph is already built (the program ran
        normally up to ``pw.run``), so the live routing partitioners are
        available.  The helper applies the same graph-optimizer plan the
        mesh would (announce_topology + _ensure_optimized inputs), so node
        classes match the snapshot signatures."""
        import json as _json

        if self.persistence is None:
            raise RuntimeError(
                "PATHWAY_TPU_RESHARD requires persistence "
                "(PersistenceMode.OPERATOR_PERSISTING)"
            )
        scopes = [w.scope for w in self.workers]
        n_shared = getattr(self, "n_shared", len(scopes[0].nodes))
        protected = set()
        for node in scopes[0].nodes[:n_shared]:
            for consumer, _port in node.consumers:
                if consumer.index >= n_shared:
                    protected.add(node.index)
        from pathway_tpu.optimize import optimize_scopes

        optimize_scopes(scopes, n_shared=n_shared, protected=protected)
        from pathway_tpu.engine.persistence import (
            reshard_process_snapshots,
        )

        report = reshard_process_snapshots(
            self.persistence.backend,
            old_processes,
            self.processes,
            self.threads,
            scopes,
            n_shared=n_shared,
        )
        _metrics.FLIGHT.record("reshard", **report)
        print("PATHWAY_RESHARD_JSON " + _json.dumps(report), flush=True)
        return None

    # -- fault tolerance ----------------------------------------------------

    def _note_epoch(self) -> None:
        _metrics.REGISTRY.gauge(
            "pathway_mesh_epoch",
            "current mesh recovery epoch (bumped by every recovery or "
            "leader election; frames from older epochs are fenced)",
        ).set(self._epoch)

    def _report_rescale_metrics(self) -> None:
        """A leader relaunched after ``MeshSupervisor.rescale`` carries
        the supervisor's rescale stamps in its environment: surface them
        as metric families on this (fresh) process's registry so the
        leader ``/metrics`` reports the cumulative rescale history."""
        try:
            rescales = int(os.environ.get("PATHWAY_TPU_RESCALED", "0"))
        except ValueError:
            rescales = 0
        if rescales <= 0:
            return
        _metrics.REGISTRY.counter(
            "pathway_mesh_rescales_total",
            "completed N->M mesh rescales (quiesce + re-shard + relaunch)",
        ).inc(rescales)
        try:
            wall = float(os.environ.get("PATHWAY_TPU_RESCALE_WALL_S", ""))
        except ValueError:
            wall = None
        if wall is not None:
            _metrics.REGISTRY.histogram(
                "pathway_mesh_rescale_seconds",
                "wall time of the most recent rescale, quiesce request "
                "to relaunch",
                buckets=(0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0),
            ).observe(wall)

    def _snapshot_manager(self):
        """Per-process operator snapshot manager, or None when persistence
        is absent / not OPERATOR_PERSISTING.  Every process snapshots its
        OWN replica states under a process-qualified name, keeping a small
        ring of recent commits so the mesh can roll back to a COMMON one."""
        if self.persistence is None:
            return None
        from pathway_tpu.engine.persistence import OperatorSnapshotManager
        from pathway_tpu.persistence import PersistenceMode

        if (
            getattr(self.persistence, "persistence_mode", None)
            != PersistenceMode.OPERATOR_PERSISTING
        ):
            return None
        return OperatorSnapshotManager(
            self.persistence.backend,
            getattr(self.persistence, "snapshot_interval_ms", 0),
            name=f"operator-snapshot-p{self.process_id}",
            retain=3,
        )

    @staticmethod
    def _recovery_enabled(snapshot_mgr) -> bool:
        """Worker recovery is OPT-IN: it needs both the env switch and an
        operator-snapshot backend.  Everything else fail-stops, exactly as
        before this layer existed."""
        return snapshot_mgr is not None and os.environ.get(
            "PATHWAY_TPU_RECOVER", ""
        ).lower() in ("1", "true", "yes")

    @staticmethod
    def _recover_deadline() -> float:
        try:
            return max(
                1.0,
                float(os.environ.get("PATHWAY_TPU_RECOVER_DEADLINE", "60")),
            )
        except ValueError:
            return 60.0

    @staticmethod
    def _fault_plan():
        if not os.environ.get("PATHWAY_TPU_FAULT_PLAN"):
            return None
        from pathway_tpu.engine.faults import active_plan

        return active_plan()

    @staticmethod
    def _request_kill(peer: int) -> None:
        """Ask the MeshSupervisor (if one launched this mesh) to SIGKILL a
        suspected-hung worker so the death→restart path takes over; a
        no-op without a supervisor (the caller then fail-stops on the
        reestablish deadline)."""
        sup_dir = os.environ.get("PATHWAY_TPU_SUPERVISOR_DIR")
        if not sup_dir:
            return
        try:
            with open(
                os.path.join(sup_dir, f"kill-{peer}"), "w"
            ) as fh:
                fh.write(str(os.getpid()))
        except OSError:
            pass

    def _rewind_sinks(self, to_time: int) -> None:
        """Truncate file sinks past the rollback point so re-driven
        commits land exactly once.  Callback sinks (pw.io.subscribe) have
        no rewind seam: re-driven commits reach them at-least-once — a
        documented recovery limit."""
        from pathway_tpu.engine.connectors import FILE_WRITERS

        for writer in list(FILE_WRITERS):
            writer.rewind_to(to_time)

    def _recover_mesh(
        self, sched, transport, snapshot_mgr, dead_peer: int, drivers: list
    ) -> None:
        """Leader-side recovery: park survivors, get the dead worker
        restarted (supervisor), re-mesh, re-handshake, roll every process
        back to the restarted worker's snapshot, and resync the links."""
        t0 = _time.monotonic()
        self._epoch += 1
        epoch = self._epoch
        self._note_epoch()
        _metrics.FLIGHT.record(
            "peer_dead", peer=dead_peer, time=sched.time, epoch=epoch
        )
        _metrics.FLIGHT.dump(f"peer {dead_peer} lost (leader view)")
        # abandon the in-flight sampled trace AFTER the dump, so the dump
        # references its trace id; drop the dead incarnation's piggybacked
        # metrics snapshot and spans so the aggregated /metrics stops
        # rendering stale worker label sets
        _tracing.TRACER.drop()
        sched.mesh_metrics.pop(dead_peer, None)
        sched.trace_peer_spans.pop(dead_peer, None)
        _profiling.PROFILER.prune(dead=(dead_peer,))
        _timeseries.STORE.prune_workers(dead={str(dead_peer)})
        _metrics.FLIGHT.record(
            "recovery_start", peer=dead_peer, epoch=epoch
        )
        deadline = self._recover_deadline()
        # survivors park in `recovering` (their own PeerLostError or this
        # command gets them there) and re-mesh toward the restarted worker
        for peer in sorted(sched._outbox):
            if peer == dead_peer or peer in transport.dead_peers:
                continue
            transport.send(peer, ("cmd", "recover", dead_peer, epoch))
        # a hung (not dead) worker must actually die before its restart
        # can bind the exchange port again
        self._request_kill(dead_peer)
        detect_s = _time.monotonic() - t0
        transport.reestablish(dead_peer, deadline=deadline)
        sched.reannounce_to(dead_peer)
        frame = transport.recv(dead_peer, timeout=deadline)
        if not (
            isinstance(frame, tuple) and frame and frame[0] == "rejoin"
        ):
            raise RuntimeError(
                f"process 0: expected the restarted worker {dead_peer}'s "
                f"rejoin frame, got {frame!r}"
            )
        rejoin_time = int(frame[1])
        if rejoin_time < 0:
            raise RuntimeError(
                f"process 0: restarted worker {dead_peer} has no operator "
                "snapshot to resume from (it died before its first commit "
                "boundary); cold-starting one worker of a warm mesh would "
                "diverge state — fail-stop"
            )
        transport.broadcast(("cmd", "rollback", rejoin_time, epoch))
        sched.rollback(rejoin_time, snapshot_mgr, drivers)
        self._rewind_sinks(rejoin_time)
        sched.resync(epoch)
        _metrics.REGISTRY.counter(
            "pathway_mesh_recoveries_total",
            "mesh-wide recoveries completed after a worker loss",
        ).inc(1)
        _metrics.FLIGHT.record(
            "recovery_done",
            peer=dead_peer,
            epoch=epoch,
            to_time=rejoin_time,
            detect_s=round(detect_s, 6),
            wall_s=round(_time.monotonic() - t0, 6),
        )
        _metrics.FLIGHT.dump(f"peer {dead_peer} recovered (leader view)")

    # -- the two run loops --------------------------------------------------

    def _coordinate(self, sched, transport) -> None:
        from pathway_tpu.engine.distributed import (
            RECV_TIMEOUT,
            PeerLostError,
        )

        w0 = self.workers[0]
        w0.monitor = self.monitor  # worker 0 counts the connectors for it
        drivers = list(w0.drivers)
        persistent = _replay(drivers)
        snapshot_mgr = self._snapshot_manager()
        recovery = self._recovery_enabled(snapshot_mgr)
        fault_plan = self._fault_plan()
        self._report_rescale_metrics()
        common = -1
        if snapshot_mgr is not None:
            # startup rejoin protocol: collect every follower's latest
            # snapshot time, roll the whole mesh back to the oldest
            # common commit, then barrier — a plain cold start runs the
            # same path with T = -1.  Rejoin frames carry each survivor's
            # mesh epoch: a leader restarted after failover must resume
            # ABOVE the epochs the survivors advanced to, or its rollback
            # command would be rejected by their fences as a zombie's.
            times = [snapshot_mgr.latest_time()]
            peer_epochs = [0]
            for peer in sorted(sched._outbox):
                frame = transport.recv(peer)
                if not (
                    isinstance(frame, tuple)
                    and frame
                    and frame[0] == "rejoin"
                ):
                    raise RuntimeError(
                        f"process 0: expected peer {peer}'s rejoin frame, "
                        f"got {frame!r}"
                    )
                times.append(frame[1])
                peer_epochs.append(
                    int(frame[2]) if len(frame) >= 3 else 0
                )
            common = min(
                (t if t is not None else -1) for t in times
            )
            self._epoch = max([self._epoch] + peer_epochs) + 1
            self._note_epoch()
            transport.broadcast(("cmd", "rollback", common, self._epoch))
            sched.fence.admit("rollback", self._epoch)
            sched.rollback(common, snapshot_mgr, drivers)
            # the resumed sink files may carry commits newer than the
            # mesh's last COMMON snapshot (a cold restart lost them):
            # truncate so re-driven commits land exactly once
            self._rewind_sinks(common)
            sched.resync(self._epoch)
        quiesce_path = None
        sup_dir = os.environ.get("PATHWAY_TPU_SUPERVISOR_DIR")
        if sup_dir and snapshot_mgr is not None:
            quiesce_path = os.path.join(sup_dir, "quiesce")

        def maybe_quiesce(committed_time: int | None) -> None:
            """Service a supervisor rescale request: stop at a commit
            boundary, force a durable snapshot of it on every process,
            and exit with the quiesce code so the supervisor can re-shard
            and relaunch."""
            if quiesce_path is None or not os.path.exists(quiesce_path):
                return
            from pathway_tpu.engine.supervisor import EXIT_QUIESCED

            try:
                if committed_time is None:
                    # idle stream: every polled row has been committed
                    # (on_data commits per poll batch), so the current
                    # state IS the state at the last commit — quiesce
                    # there rather than cutting an empty commit, which
                    # would shift later commit timestamps off the
                    # uninterrupted run's and break sink bit-identity.
                    # sched.time is the NEXT commit's stamp; the last
                    # committed boundary is one behind it.
                    committed_time = sched.time - 1
                transport.broadcast(("cmd", "quiesce", committed_time))
            except PeerLostError:
                # a peer died mid-quiesce: skip this attempt and let the
                # ordinary recovery paths run — the marker file stays, so
                # quiesce retries at the next boundary after recovery
                return
            snapshot_mgr.snapshot(sched.scopes, drivers, committed_time)
            _metrics.FLIGHT.record(
                "quiesce", time=committed_time, process=self.process_id
            )
            _metrics.FLIGHT.dump("quiesced for rescale")
            raise SystemExit(EXIT_QUIESCED)

        if common < 0:
            # fresh start: the initial barrier commit establishes time 1
            # and flushes static sources.  A mesh RESUMED from a common
            # snapshot must skip it — the restored state is already at
            # the rollback boundary, and an extra (empty) commit here
            # would shift every later commit timestamp off the
            # uninterrupted run's numbering, breaking sink bit-identity.
            transport.broadcast(("cmd", "commit"))
            with _tracing.commit_stage() as commit:
                commit.time = barrier_time = sched.commit()
            # followers snapshot (and publish) EVERY commit, including this
            # one; the leader must too, or a worker that dies before the
            # first data commit forces a rollback to a boundary the leader
            # cannot restore.  Same exactly-once seam as the data path: the
            # barrier commit flushes static sources, which can stage device
            # work this snapshot must contain
            _after_commit(
                barrier_time, sched.scopes, drivers, snapshot_mgr=snapshot_mgr
            )
        last_sign_of_life = _time.monotonic()

        def announce() -> None:
            transport.broadcast(("cmd", "commit"))

        def on_data(commit) -> None:
            nonlocal last_sign_of_life
            try:
                transport.raise_if_peer_dead()
                time, started = _commit_step(
                    commit, sched, drivers,
                    announce=announce, peer_spans=sched.trace_peer_spans,
                )
            except PeerLostError as exc:
                if not recovery or exc.peer is None or exc.peer == 0:
                    raise
                self._recover_mesh(
                    sched, transport, snapshot_mgr, exc.peer, drivers
                )
                return  # the rolled-back commit re-drives on the next poll
            _after_commit(
                time, sched.scopes, drivers, started, w0=w0,
                persistent=persistent, snapshot_mgr=snapshot_mgr,
                fault_plan=fault_plan, process_id=self.process_id,
            )
            last_sign_of_life = started
            maybe_quiesce(time)

        # pings must always undercut the followers' recv timeout, or a
        # quiet stream trips spurious peer-crash errors
        ping_every = min(30.0, RECV_TIMEOUT / 2.0)

        def on_idle() -> None:
            # fail-stop promptly when a peer's socket closed — the
            # send path alone needs TWO sends after the RST to notice
            nonlocal last_sign_of_life
            try:
                transport.raise_if_peer_dead()
            except PeerLostError as exc:
                if not recovery or exc.peer is None or exc.peer == 0:
                    raise
                self._recover_mesh(
                    sched, transport, snapshot_mgr, exc.peer, drivers
                )
                last_sign_of_life = _time.monotonic()
                return
            maybe_quiesce(None)
            # keep follower recv timeouts from tripping during long quiet
            # stretches of a streaming run
            if _time.monotonic() - last_sign_of_life > ping_every:
                transport.broadcast(("cmd", "ping"))
                last_sign_of_life = _time.monotonic()

        _pump_drivers(w0, drivers, on_data, on_idle)
        transport.broadcast(("cmd", "finish"))
        # the leader holds the assembled mesh traces: the export is its
        _end_run(sched, sched.scopes, drivers, persistent, snapshot_mgr)

    def _follow(self, sched, transport) -> None:
        from pathway_tpu.engine.distributed import PeerLostError

        snapshot_mgr = self._snapshot_manager()
        recovery = self._recovery_enabled(snapshot_mgr)
        fault_plan = self._fault_plan()
        deadline = self._recover_deadline()
        if snapshot_mgr is not None:
            latest = snapshot_mgr.latest_time()
            transport.send(
                0,
                ("rejoin", latest if latest is not None else -1,
                 self._epoch),
            )
        while True:
            try:
                frame = transport.recv(0)
            except PeerLostError:
                # the leader itself died or hung: dump forensics and —
                # with recovery on — elect an interim leader, take over
                # its duties, and rejoin its restarted successor
                self._leader_failover(sched, transport, snapshot_mgr)
                continue
            kind = frame[0]
            if kind != "cmd":
                raise RuntimeError(
                    f"process {self.process_id}: expected a coordinator "
                    f"command, got {kind!r}"
                )
            cmd = frame[1]
            if cmd == "ping":
                # answer so the leader's suspicion clock sees an idle-but-
                # alive follower (absorbed by its receiver thread)
                transport.heartbeat(0)
                continue
            if cmd == "commit":
                try:
                    time = sched.commit()
                except PeerLostError as exc:
                    if exc.peer == 0 or 0 in transport.dead_peers:
                        self._leader_failover(
                            sched, transport, snapshot_mgr
                        )
                        continue
                    if not recovery or exc.peer is None:
                        raise
                    try:
                        self._park_for_recovery(sched, transport, exc.peer)
                    except PeerLostError as parked:
                        # the leader died while this survivor was parked
                        # waiting for its recovery command
                        if parked.peer == 0 or 0 in transport.dead_peers:
                            self._leader_failover(
                                sched, transport, snapshot_mgr
                            )
                        else:
                            raise
                    continue
                _metrics.FLIGHT.record(
                    "commit", time=time, process=self.process_id
                )
                _after_commit(
                    time, sched.scopes, [], snapshot_mgr=snapshot_mgr,
                    fault_plan=fault_plan, process_id=self.process_id,
                )
            elif cmd == "recover":
                # a peer died; this follower survived without noticing
                # (or already parked — _park_for_recovery consumed the
                # command and re-meshed; this branch is the idle path).
                # Fencing makes fault-injected duplicates no-ops.
                if not sched.fence.admit("recover", frame[3]):
                    continue
                _dead = frame[2]
                _metrics.FLIGHT.record(
                    "peer_dead",
                    peer=_dead,
                    time=sched.time,
                    epoch=frame[3],
                )
                _metrics.FLIGHT.dump(
                    f"peer {_dead} lost (survivor view)"
                )
                transport.reestablish(_dead, deadline=deadline)
                _metrics.FLIGHT.record(
                    "recovery_remesh", peer=_dead, epoch=frame[3]
                )
            elif cmd == "rollback":
                # a re-processed rollback would deadlock in resync, so a
                # zombie ex-leader's (or a duplicated) command is fenced
                if not sched.fence.admit("rollback", frame[3]):
                    continue
                self._epoch = max(self._epoch, int(frame[3]))
                self._note_epoch()
                sched.rollback(frame[2], snapshot_mgr, [])
                sched.resync(frame[3])
            elif cmd == "quiesce":
                from pathway_tpu.engine.supervisor import EXIT_QUIESCED

                if snapshot_mgr is not None:
                    snapshot_mgr.snapshot(sched.scopes, [], frame[2])
                _metrics.FLIGHT.record(
                    "quiesce", time=frame[2], process=self.process_id
                )
                _metrics.FLIGHT.dump("quiesced for rescale")
                raise SystemExit(EXIT_QUIESCED)
            elif cmd == "finish":
                sched.finish()
                if snapshot_mgr is not None:
                    snapshot_mgr.snapshot(sched.scopes, [], sched.time)
                return
            else:
                raise RuntimeError(f"unknown coordinator command {cmd!r}")

    def _leader_failover(self, sched, transport, snapshot_mgr) -> None:
        """Follower-side response to losing the leader (process 0).

        Every survivor dumps its flight ring first — leader loss must
        leave forensics whether or not failover is possible.  With
        recovery off that is the whole story: fail-stop, and the
        supervisor reports EXIT_LEADER_LOST.

        With recovery on, survivors run a deterministic epoch-stamped
        election: the lowest live rank becomes the *interim leader* and
        takes over the leader-only duties that cannot wait for the
        restart — the supervisor kill request (a HUNG ex-leader must
        actually die before its successor can bind the exchange port)
        and the aggregation of survivor metrics snapshots.  Everyone
        then re-meshes toward the supervisor-restarted process 0,
        re-runs the topology handshake against it, and sends an
        epoch-stamped rejoin; the restarted leader resumes coordination
        (rollback to the last common commit) above the survivors'
        epoch, so any frame a zombie ex-leader manages to flush is
        rejected by the epoch fence (and its replaced socket).  A
        cascading survivor death during the window fail-stops on the
        election deadline."""
        from pathway_tpu.engine.distributed import (
            PeerLostError,
            elect_leader,
        )

        recovery = self._recovery_enabled(snapshot_mgr)
        last_seen = getattr(transport, "last_seen", {}).get(0)
        _metrics.FLIGHT.record(
            "leader_dead",
            process=self.process_id,
            time=sched.time,
            epoch=self._epoch,
            recovery=recovery,
            # silence on the leader link before it was declared dead —
            # the detection latency (suspicion timeout or socket close)
            detect_s=(
                None
                if last_seen is None
                else round(_time.monotonic() - last_seen, 6)
            ),
        )
        _metrics.FLIGHT.dump("leader (process 0) lost")
        _tracing.TRACER.drop()  # after the dump — it references the id
        if not recovery:
            raise PeerLostError(
                f"process {self.process_id}: leader (process 0) lost "
                "and recovery is disabled — fail-stop (flight ring "
                "dumped)",
                peer=0,
            )
        t0 = _time.monotonic()
        deadline = self._recover_deadline()
        end = t0 + deadline
        survivors = sorted(
            p
            for p in range(self.processes)
            if p != 0 and p not in transport.dead_peers
        )
        epoch = self._epoch + 1
        interim = elect_leader(survivors)
        others = [p for p in survivors if p != self.process_id]
        latest = snapshot_mgr.latest_time()
        latest = -1 if latest is None else latest
        if self.process_id == interim:
            # interim leader inherits /metrics aggregation: start from a
            # clean slate so the dead leader's (and any other dead
            # incarnation's) worker label sets don't linger in the
            # rendered exposition
            sched.prune_mesh_metrics(dead=(0,))
            for peer in others:
                transport.send(peer, ("elect", epoch, interim))
            rejoin_times = [latest]
            for peer in others:
                # collect the survivor's ack, absorbing round/abort
                # debris its broken commit may have left on the link
                while True:
                    remaining = max(0.1, end - _time.monotonic())
                    frame = transport.recv(peer, timeout=remaining)
                    if (
                        isinstance(frame, tuple)
                        and len(frame) >= 4
                        and frame[0] == "elect-ack"
                        and frame[1] == epoch
                    ):
                        break
                rejoin_times.append(frame[2])
                if frame[3] is not None:
                    # the ack carries the survivor's metrics snapshot with
                    # an optional piggybacked profiler payload — route the
                    # sidecar to the new leader's profile aggregation so
                    # `cli profile` keeps covering the mesh across failover
                    peer_profile = frame[3].pop("__profile__", None)
                    if peer_profile is not None:
                        _profiling.PROFILER.absorb(peer, peer_profile)
                    sched.mesh_metrics[peer] = frame[3]
            self._request_kill(0)
            _metrics.REGISTRY.counter(
                "pathway_mesh_elections_total",
                "leader elections completed after losing process 0",
            ).inc(1)
            _metrics.REGISTRY.histogram(
                "pathway_mesh_election_seconds",
                "leader-loss detection to election-complete wall time",
                buckets=(0.01, 0.05, 0.1, 0.5, 1.0, 5.0, 15.0, 60.0),
            ).observe(_time.monotonic() - t0)
            _metrics.FLIGHT.record(
                "election_done",
                interim=interim,
                epoch=epoch,
                survivors=survivors,
                rollback_target=min(rejoin_times),
                wall_s=round(_time.monotonic() - t0, 6),
            )
        else:
            while True:
                remaining = end - _time.monotonic()
                if remaining <= 0:
                    raise PeerLostError(
                        f"process {self.process_id}: no election from "
                        f"interim leader {interim} within {deadline:g}s "
                        "of losing the leader — fail-stop",
                        peer=interim,
                    )
                try:
                    frame = transport.recv(
                        interim, timeout=min(remaining, 1.0)
                    )
                except PeerLostError:
                    if interim in transport.dead_peers:
                        raise  # cascade: the interim died too
                    continue  # poll timeout: keep waiting
                if (
                    isinstance(frame, tuple)
                    and len(frame) >= 3
                    and frame[0] == "elect"
                    and frame[1] > self._epoch
                ):
                    epoch = int(frame[1])
                    break
            transport.send(
                interim,
                ("elect-ack", epoch, latest,
                 sched._metrics_snapshot()),
            )
        self._epoch = epoch
        self._note_epoch()
        sched.fence.admit("elect", epoch)
        # re-mesh toward the restarted process 0 and re-run the startup
        # handshake; the normal follow loop takes the rollback from there
        transport.reestablish(
            0, deadline=max(1.0, end - _time.monotonic())
        )
        sched.receive_topology()
        transport.send(0, ("rejoin", latest, self._epoch))
        _metrics.FLIGHT.record(
            "leader_failover_done",
            process=self.process_id,
            epoch=self._epoch,
            wall_s=round(_time.monotonic() - t0, 6),
        )
        # second dump so the on-disk forensics cover the whole failover
        # lifecycle (the first dump happened at leader_dead, before the
        # election outcome existed)
        _metrics.FLIGHT.dump("leader failover complete")

    def _park_for_recovery(self, sched, transport, dead_peer: int) -> None:
        """Survivor path when a peer dies MID-COMMIT: dump forensics, then
        park in `recovering` — drain the leader link (with backoff, under
        a bounded deadline) until its recover command arrives, and re-mesh
        toward the restarted worker.  The subsequent rollback command is
        handled by the normal follow loop."""
        import random as _random

        from pathway_tpu.engine.distributed import PeerLostError

        _metrics.FLIGHT.record(
            "peer_dead", peer=dead_peer, time=sched.time
        )
        _metrics.FLIGHT.dump(f"peer {dead_peer} lost (survivor view)")
        _tracing.TRACER.drop()  # after the dump — it references the id
        _metrics.FLIGHT.record("recovery_parked", peer=dead_peer)
        deadline = self._recover_deadline()
        end = _time.monotonic() + deadline
        wait = 0.05
        frame = sched._pending_recover
        sched._pending_recover = None
        while True:
            if frame is not None:
                if (
                    isinstance(frame, tuple)
                    and len(frame) >= 4
                    and frame[0] == "cmd"
                    and frame[1] == "recover"
                ):
                    # a duplicated (fault-injected or zombie-leader)
                    # recover from an already-handled epoch is fenced;
                    # a fresh one advances the fence so the idle-path
                    # handler won't re-run it
                    if sched.fence.admit("recover", frame[3]):
                        break
                    frame = None
                    continue
                # stale commit/round debris from the aborted exchange
                frame = None
            remaining = end - _time.monotonic()
            if remaining <= 0:
                raise PeerLostError(
                    f"process {self.process_id}: no recovery command "
                    f"within {deadline:g}s of losing peer {dead_peer} — "
                    "fail-stop",
                    peer=dead_peer,
                )
            try:
                frame = transport.recv(
                    0, timeout=min(remaining, wait)
                )
            except PeerLostError:
                if 0 in transport.dead_peers:
                    raise  # the leader itself is gone: fatal
                frame = None  # just a poll timeout: keep waiting
            wait = min(wait * 2, 1.0) * (0.75 + 0.5 * _random.random())
        transport.reestablish(frame[2], deadline=deadline)
        _metrics.FLIGHT.record(
            "recovery_remesh", peer=frame[2], epoch=frame[3]
        )
