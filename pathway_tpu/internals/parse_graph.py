"""Global capture graph ``G`` and ``pw.run``.

(reference: python/pathway/internals/parse_graph.py:244 + run.py:12).
Sinks (io.write / subscribe / debug captures) register here; ``pw.run``
lowers everything reachable and pumps the scheduler — static sources run in
one commit; connector-backed sources run the streaming loop.
"""

from __future__ import annotations

import time as _time
from dataclasses import dataclass, field
from typing import TYPE_CHECKING, Any, Callable

from pathway_tpu.engine.graph import Node, Scheduler, Scope

if TYPE_CHECKING:
    from pathway_tpu.internals.table import Table


@dataclass
class SinkSpec:
    table: "Table"
    attach: Callable[[Scope, Node], Any]  # returns optional driver
    #: internal sinks (AsyncTransformer loopback subscriptions) are part of
    #: the dataflow itself: debug captures must attach them to make their
    #: loopback sources progress, while user output sinks stay registered
    #: for the eventual pw.run()
    internal: bool = False


class ParseGraph:
    def __init__(self) -> None:
        self.sinks: list[SinkSpec] = []
        self.error_log_tables: list[Table] = []

    def add_sink(
        self,
        table: "Table",
        attach: Callable[[Scope, Node], Any],
        internal: bool = False,
    ) -> None:
        self.sinks.append(SinkSpec(table, attach, internal))

    def clear(self) -> None:
        self.sinks = []
        self.error_log_tables = []


G = ParseGraph()


def run(
    *,
    monitoring_level: Any = None,
    with_http_server: bool = False,
    monitoring_server_port: int | None = None,
    debug: bool = False,
    persistence_config: Any = None,
    strict: bool = False,
    terminate_on_error: bool | None = None,
    **kwargs: Any,
) -> None:
    """Execute the captured graph (reference: pw.run, internals/run.py:12).

    ``monitoring_level``: pw.MonitoringLevel (NONE/IN_OUT/ALL) — IN_OUT and
    ALL render a live rich dashboard; ``with_http_server`` additionally
    serves Prometheus metrics on port 20000 + PATHWAY_PROCESS_ID
    (reference monitoring.py:56-228, http_server.rs:22).

    ``strict=True`` runs the pre-execution static analyzer over the built
    graph and raises ``pathway_tpu.analysis.AnalysisError`` on any
    error-severity finding before any data flows.

    ``terminate_on_error=True`` (default: ``PATHWAY_TERMINATE_ON_ERROR``,
    off) raises ``EngineError`` at the first row-level error — a failing
    UDF, an error value reaching an operator — instead of poisoning the
    row to ``ERROR``, logging it and carrying on."""
    from pathway_tpu.analysis import runtime as _analysis_runtime
    from pathway_tpu.internals.accelerator import configure_compile_cache
    from pathway_tpu.internals.config import get_pathway_config
    from pathway_tpu.internals.runner import (
        DistributedGraphRunner,
        GraphRunner,
        ShardedGraphRunner,
    )

    configure_compile_cache()
    config = get_pathway_config()
    if persistence_config is None:
        # env-driven persistence (PATHWAY_PERSISTENT_STORAGE etc.,
        # reference PathwayConfig.replay_config)
        persistence_config = config.replay_config
    threads = kwargs.get("threads") or config.threads
    processes = kwargs.get("processes") or config.processes
    if _analysis_runtime.enabled():
        # graph-only mode (cli analyze): one local worker, no connector
        # drivers, no exchange sockets, no dashboards — the scheduler
        # intercepts before any data flows, whatever the topology asks for
        runner = GraphRunner(persistence_config=None, attach_drivers=False)
        processes = threads = 1
        monitoring_level = None
        with_http_server = False
    elif processes > 1:
        # multi-process: identical program per process, key-sharded TCP
        # exchange (engine/distributed.py; reference `pathway spawn`
        # cluster topology, config.rs:72-86)
        runner: Any = DistributedGraphRunner(
            threads,
            processes,
            int(config.process_id),
            first_port=config.first_port,
            persistence_config=persistence_config,
        )
        if int(config.process_id) != 0:
            # live dashboards belong to process 0 only (the Prometheus
            # endpoint stays per-process: port 20000 + process_id, as in
            # the reference http_server.rs:22)
            from pathway_tpu.internals.monitoring import MonitoringLevel

            monitoring_level = MonitoringLevel.NONE
    elif threads > 1:
        # multi-worker: identical graph per worker, key-sharded exchange
        # (engine/sharded.py; reference PATHWAY_THREADS)
        runner: Any = ShardedGraphRunner(
            threads, persistence_config=persistence_config
        )
    else:
        runner = GraphRunner(persistence_config=persistence_config)

    if terminate_on_error is None:
        terminate_on_error = config.terminate_on_error
    for worker in getattr(runner, "workers", [runner]):
        worker.scope.terminate_on_error = terminate_on_error

    monitor = None
    http_server = None
    level = monitoring_level
    if level is not None or with_http_server:
        import sys

        from pathway_tpu.internals.monitoring import (
            MonitoringHttpServer,
            MonitoringLevel,
            StatsMonitor,
        )

        if level is None or level == MonitoringLevel.AUTO:
            level = (
                MonitoringLevel.IN_OUT
                if sys.stderr.isatty()
                else MonitoringLevel.NONE
            )
        if level != MonitoringLevel.NONE or with_http_server:
            monitor = StatsMonitor(
                level if level != MonitoringLevel.NONE else MonitoringLevel.IN_OUT
            )
            runner.monitor = monitor
            if level != MonitoringLevel.NONE:
                monitor.start_live()
            if with_http_server:
                http_server = MonitoringHttpServer(
                    monitor, port=monitoring_server_port
                )

    from pathway_tpu import serving as _serving
    from pathway_tpu.internals import profiling as _profiling
    from pathway_tpu.internals import timeseries as _timeseries
    from pathway_tpu.internals.metrics import FLIGHT
    from pathway_tpu.internals.telemetry import run_span, telemetry_enabled

    query_server = None
    if _serving.enabled() and not _analysis_runtime.enabled():
        # the serving plane is per-process: every mesh member answers
        # queries from its own shard's snapshots on 21000 + process_id
        query_server = _serving.start_server()

    profiler_started = False
    telemetry_loop_started = False
    if not _analysis_runtime.enabled():
        # sampling profiler: strictly opt-in (PATHWAY_TPU_PROFILE=1) —
        # when unset this is a boolean test, no thread, no cost
        profiler_started = _profiling.PROFILER.maybe_start()
        # metrics history ring: feed it whenever something can read it
        # (an HTTP endpoint serving /timeseries) or the user asked for
        # it explicitly (PATHWAY_TPU_TIMESERIES=1 / PATHWAY_TPU_SLO)
        if with_http_server or _timeseries.loop_enabled():
            if monitor is None and _timeseries.loop_enabled():
                # SLO evaluation without a dashboard: a quiet monitor
                # gives the loop its scheduler/mesh_snapshots views
                from pathway_tpu.internals.monitoring import (
                    MonitoringLevel,
                    StatsMonitor,
                )

                monitor = StatsMonitor(MonitoringLevel.IN_OUT)
                runner.monitor = monitor
            if monitor is not None:
                _timeseries.start_loop(monitor)
                telemetry_loop_started = True

    if telemetry_enabled():
        # per-operator stats feed the metrics sampler + operator spans
        runner.probe_stats = True
    FLIGHT.record(
        "run_start", threads=threads, processes=processes,
        process_id=int(config.process_id),
    )
    try:
        with run_span(lambda: getattr(runner, "scheduler", None)):
            if isinstance(runner, (ShardedGraphRunner, DistributedGraphRunner)):
                runner.attach_sinks()
                if strict:
                    from pathway_tpu.analysis import check_strict

                    # workers are identical replicas; worker 0 carries the
                    # superset (sinks attach there only)
                    check_strict(runner.workers[0].scope)
                runner.run()
            else:
                for sink in G.sinks:
                    node = runner.build(sink.table)
                    driver = sink.attach(runner.scope, node)
                    if driver is not None:
                        runner.drivers.append(driver)
                if strict:
                    from pathway_tpu.analysis import check_strict

                    check_strict(runner.scope)
                runner.run()
        FLIGHT.record("run_end")
    except BaseException as exc:
        # crash forensics from ANY worker: the last commits/exchanges/
        # errors of this process land on disk before the raise surfaces
        # (PATHWAY_TPU_FLIGHT_DIR picks where)
        FLIGHT.record("run_error", error=repr(exc))
        FLIGHT.dump(f"pw.run raised: {exc!r}")
        raise
    finally:
        if telemetry_loop_started:
            # final tick inside stop_loop captures the run's last state
            _timeseries.stop_loop()
        if profiler_started:
            _profiling.PROFILER.stop()
            # best-effort forensics: export() swallows write failures
            _profiling.PROFILER.export()
        if monitor is not None:
            monitor.stop()
        if http_server is not None and not kwargs.get("_keep_http_server"):
            http_server.stop()
        if query_server is not None and not kwargs.get("_keep_http_server"):
            _serving.stop_server()
        # reap the device completion worker: a raising run must not
        # leave the daemon behind (it respawns on next use)
        from pathway_tpu.engine import device_pipeline as _device_pipeline

        _device_pipeline.stop_worker()
        G.clear()


def run_all(**kwargs: Any) -> None:
    run(**kwargs)
