"""Declarative registry of ``PATHWAY_*`` environment flags.

The runtime's env flags fall into two liveness classes, and the split is
a documented API contract, not an implementation detail:

- ``live`` — re-read on **every** call/delivery/commit so operators can
  flip planes mid-run (``PATHWAY_TPU_COLLECTIVE_EXCHANGE=0`` must take
  effect on the next exchange, not the next process).  Caching one of
  these at import time silently freezes the plane and breaks the
  documented contract (PR 16/17 prose: "live per call", "live per
  delivery").
- ``startup`` — read once when the process (or subsystem) starts;
  changing them mid-run is documented to have no effect (ports, fault
  plans, trace ring sizes, ...).

``analysis.deviceplane`` consumes this registry for **PWD606**: a flag
registered here as ``live`` that is read and cached at module or class
scope is a flag-liveness violation.  Flags not registered here are left
alone by the analyzer, but keeping the registry complete is the point —
it is the single place the liveness contract is written down as data.
"""

from __future__ import annotations

from dataclasses import dataclass

LIVE = "live"
STARTUP = "startup"


@dataclass(frozen=True)
class FlagSpec:
    name: str
    liveness: str  # LIVE | STARTUP
    owner: str  # module that reads it
    help: str


def _spec(name: str, liveness: str, owner: str, help: str) -> FlagSpec:
    return FlagSpec(name=name, liveness=liveness, owner=owner, help=help)


#: name -> FlagSpec.  ``live`` entries are the per-call planes; everything
#: else is startup-scoped configuration.
REGISTRY: dict[str, FlagSpec] = {
    spec.name: spec
    for spec in (
        # -- live-per-call planes (PR 9/12/16/17 contracts) -------------
        _spec(
            "PATHWAY_TPU_COLLECTIVE_EXCHANGE",
            LIVE,
            "engine.collective_exchange",
            "0/1/auto — collective exchange plane, re-read per exchange",
        ),
        _spec(
            "PATHWAY_TPU_DEVICE_RESIDENCY",
            LIVE,
            "engine.device_residency",
            "0/1/auto — device-resident seam, re-read per delivery",
        ),
        _spec(
            "PATHWAY_TPU_DEVICE_OPS",
            LIVE,
            "engine.device_ops",
            "0/1/auto — device operator kernels, re-read per dispatch",
        ),
        _spec(
            "PATHWAY_TPU_ASYNC_DEVICE",
            LIVE,
            "engine.device_pipeline",
            "0/1 — async device pipeline, re-read per commit boundary",
        ),
        _spec(
            "PATHWAY_TPU_OPTIMIZE",
            LIVE,
            "optimize",
            "0/1 — graph rewriter escape hatch, re-read per run() start",
        ),
        _spec(
            "PATHWAY_TPU_RESULT_CACHE",
            LIVE,
            "serving.result_cache",
            "0/1 — serving result cache, re-read per lookup and insert",
        ),
        _spec(
            "PATHWAY_TPU_RESULT_CACHE_BYTES",
            LIVE,
            "serving.result_cache",
            "result-cache byte budget (64 MiB), re-read per insert",
        ),
        _spec(
            "PATHWAY_TPU_REPLICA_MAX_STALENESS_S",
            LIVE,
            "serving.replica",
            "replica staleness bound in seconds (5), re-read per query",
        ),
        # -- startup-scoped configuration -------------------------------
        _spec(
            "PATHWAY_TPU_VERIFY_ELISION",
            STARTUP,
            "engine.sharded",
            "1 — debug cross-check of elided exchange co-location",
        ),
        _spec(
            "PATHWAY_TPU_COLLECTIVE_MIN_ROWS",
            STARTUP,
            "engine.collective_exchange",
            "row floor below which collective exchange declines",
        ),
        _spec(
            "PATHWAY_TPU_DEVICE_OPS_MIN_ROWS",
            STARTUP,
            "engine.device_ops",
            "row floor below which device kernels decline",
        ),
        _spec(
            "PATHWAY_TPU_DEVICE_INFLIGHT",
            STARTUP,
            "engine.device_pipeline",
            "staged-batch depth bound for the async pipeline",
        ),
        _spec(
            "PATHWAY_TPU_SERVING",
            STARTUP,
            "serving.server",
            "1 — start the per-process HTTP query front",
        ),
        _spec(
            "PATHWAY_TPU_SERVING_QUEUE",
            STARTUP,
            "serving.server",
            "admission-control queue bound",
        ),
        _spec(
            "PATHWAY_TPU_SERVING_THREADS",
            STARTUP,
            "serving.server",
            "query worker thread count",
        ),
        _spec(
            "PATHWAY_TPU_SERVING_BATCH_WINDOW_MS",
            STARTUP,
            "serving.server",
            "KNN micro-batch window",
        ),
        _spec(
            "PATHWAY_TPU_SERVING_PORT_BASE",
            STARTUP,
            "serving.server",
            "query-server port base (21000 + process id)",
        ),
        _spec(
            "PATHWAY_TPU_SERVING_STREAM_PORT_BASE",
            STARTUP,
            "serving.stream",
            "snapshot-stream port base (22000 + process id)",
        ),
        _spec(
            "PATHWAY_TPU_SERVING_FEDERATION",
            STARTUP,
            "serving.federation",
            "1 — leader-side federation front over the whole mesh",
        ),
        _spec(
            "PATHWAY_TPU_FEDERATION_PORT",
            STARTUP,
            "serving.federation",
            "federation front port (23000)",
        ),
        _spec(
            "PATHWAY_TPU_REPLICAS",
            STARTUP,
            "serving.federation",
            "replica pool: a count (port scheme) or host:port list",
        ),
        _spec(
            "PATHWAY_TPU_REPLICA_PORT_BASE",
            STARTUP,
            "serving.replica",
            "replica query port base (24000 + replica id)",
        ),
        _spec(
            "PATHWAY_TPU_LOCKWATCH",
            STARTUP,
            "internals.lockwatch",
            "1 — runtime lock-order-cycle recorder",
        ),
        _spec(
            "PATHWAY_TPU_PROFILE",
            STARTUP,
            "internals.profiling",
            "1 — sampling profiler",
        ),
        _spec(
            "PATHWAY_TPU_PROFILE_HZ",
            STARTUP,
            "internals.profiling",
            "profiler sample rate",
        ),
        _spec(
            "PATHWAY_TPU_PROFILE_DIR",
            STARTUP,
            "internals.profiling",
            "profiler export directory",
        ),
        _spec(
            "PATHWAY_TPU_TRACE",
            STARTUP,
            "internals.tracing",
            "1 — structured tracing",
        ),
        _spec(
            "PATHWAY_TPU_TRACE_DIR",
            STARTUP,
            "internals.tracing",
            "trace export directory",
        ),
        _spec(
            "PATHWAY_TPU_TRACE_RING",
            STARTUP,
            "internals.tracing",
            "trace ring capacity",
        ),
        _spec(
            "PATHWAY_TPU_TRACE_SAMPLE",
            STARTUP,
            "internals.tracing",
            "trace sampling ratio",
        ),
        _spec(
            "PATHWAY_TPU_REQUEST_TRACE",
            STARTUP,
            "internals.tracing",
            "1 — read-tier request tracing (X-Pathway-Trace)",
        ),
        _spec(
            "PATHWAY_TPU_REQUEST_TRACE_SAMPLE",
            STARTUP,
            "internals.tracing",
            "request-trace sampling interval",
        ),
        _spec(
            "PATHWAY_TPU_REQUEST_TRACE_RING",
            STARTUP,
            "internals.metrics",
            "wide-event request ring capacity",
        ),
        _spec(
            "PATHWAY_TPU_SLO",
            STARTUP,
            "internals.timeseries",
            "SLO sentinel policy document path / inline JSON",
        ),
        _spec(
            "PATHWAY_TPU_TIMESERIES",
            STARTUP,
            "internals.timeseries",
            "metrics history ring config",
        ),
        _spec(
            "PATHWAY_TPU_FLIGHT_DIR",
            STARTUP,
            "internals.metrics",
            "flight-event spool directory",
        ),
        _spec(
            "PATHWAY_TPU_FLIGHT_EVENTS",
            STARTUP,
            "internals.metrics",
            "flight-event ring capacity",
        ),
        _spec(
            "PATHWAY_TPU_ANALYZE",
            STARTUP,
            "analysis",
            "off/warn/strict — pre-execution graph analyzer mode",
        ),
        _spec(
            "PATHWAY_TPU_UDF_CACHE",
            STARTUP,
            "internals.udfs.caches",
            "UDF result-cache directory",
        ),
        _spec(
            "PATHWAY_TPU_DISABLE_NATIVE",
            STARTUP,
            "native",
            "1 — force the pure-python engine",
        ),
        _spec(
            "PATHWAY_TPU_FAULT_PLAN",
            STARTUP,
            "engine.faults",
            "chaos fault-plan JSON for seeded failure tests",
        ),
        _spec(
            "PATHWAY_TPU_RESTART_COUNT",
            STARTUP,
            "engine.faults",
            "supervisor restart generation counter",
        ),
        _spec(
            "PATHWAY_TPU_RECOVER",
            STARTUP,
            "internals.runner",
            "checkpoint directory to recover from",
        ),
        _spec(
            "PATHWAY_TPU_RECOVER_DEADLINE",
            STARTUP,
            "internals.runner",
            "recovery wall-clock deadline",
        ),
        _spec(
            "PATHWAY_TPU_RESHARD",
            STARTUP,
            "internals.runner",
            "checkpoint resharding target width",
        ),
        _spec(
            "PATHWAY_TPU_RESCALED",
            STARTUP,
            "internals.runner",
            "set by the supervisor on post-rescale restarts",
        ),
        _spec(
            "PATHWAY_TPU_RESCALE_WALL_S",
            STARTUP,
            "internals.runner",
            "rescale wall-clock budget",
        ),
        _spec(
            "PATHWAY_TPU_RESCALE_TIMEOUT",
            STARTUP,
            "engine.supervisor",
            "rescale barrier timeout",
        ),
        _spec(
            "PATHWAY_TPU_SUPERVISOR_DIR",
            STARTUP,
            "internals.runner",
            "supervisor scratch directory",
        ),
        _spec(
            "PATHWAY_TPU_MESH_TIMEOUT",
            STARTUP,
            "engine.distributed",
            "mesh handshake timeout",
        ),
        _spec(
            "PATHWAY_TPU_CONNECTOR_RETRIES",
            STARTUP,
            "engine.connectors",
            "external connector retry budget",
        ),
        _spec(
            "PATHWAY_EXCHANGE_COLUMNAR",
            STARTUP,
            "engine.distributed",
            "0/1 — columnar wire encoding for exchange frames",
        ),
        _spec(
            "PATHWAY_EXCHANGE_MAX_FRAME",
            STARTUP,
            "engine.distributed",
            "wire frame size bound",
        ),
        _spec(
            "PATHWAY_EXCHANGE_BIND",
            STARTUP,
            "engine.distributed",
            "exchange listener bind address",
        ),
        _spec(
            "PATHWAY_EXCHANGE_SECRET",
            STARTUP,
            "engine.distributed",
            "mesh frame HMAC secret",
        ),
        _spec(
            "PATHWAY_THREADS",
            STARTUP,
            "internals.runner",
            "worker thread count per process",
        ),
        _spec(
            "PATHWAY_PROCESSES",
            STARTUP,
            "internals.runner",
            "mesh process count",
        ),
        _spec(
            "PATHWAY_PROCESS_ID",
            STARTUP,
            "internals.runner",
            "this process's mesh rank",
        ),
        _spec(
            "PATHWAY_FIRST_PORT",
            STARTUP,
            "engine.distributed",
            "base port for mesh listeners",
        ),
        _spec(
            "PATHWAY_RUN_ID",
            STARTUP,
            "engine.connectors",
            "run identity for persistence namespacing",
        ),
        _spec(
            "PATHWAY_TELEMETRY_SERVER",
            STARTUP,
            "internals.telemetry",
            "telemetry export endpoint",
        ),
    )
}

#: flag names whose documented contract is re-read per call.
LIVE_FLAGS: frozenset[str] = frozenset(
    name for name, spec in REGISTRY.items() if spec.liveness == LIVE
)


def liveness_of(name: str) -> str | None:
    """Liveness class for ``name``, or ``None`` if unregistered."""
    spec = REGISTRY.get(name)
    return spec.liveness if spec else None
