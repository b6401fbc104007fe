"""Sampled per-commit distributed tracing: spans, critical path, export.

The metrics plane (internals/metrics.py) answers *how much*; this module
answers *why*: for a sampled delta-batch commit it records a tree of
spans — connector ingest wait, every operator ``process()`` (including
FusedChainNode sweeps), exchange encode/apply, mesh recv waits, sink
emit — across every worker of a TCP mesh, and assembles them on the
leader into one trace with per-worker tracks.

Design constraints, matching the metrics plane:

- **lock-cheap, allocation-free when idle** — tracing is off unless
  ``PATHWAY_TPU_TRACE=1``; when on, only every Nth commit is sampled
  (``PATHWAY_TPU_TRACE_SAMPLE``, default 16) and the hot-path guard for
  an unsampled commit is one attribute load (:func:`current` returning
  ``None``).  Assembled traces live in a bounded ring like the
  :class:`~pathway_tpu.internals.metrics.FlightRecorder`.
- **mesh-transparent** — the leader decides sampling at commit start
  and piggybacks the trace context on the round frames it already
  sends (the 8th element, next to the metrics snapshot slot); quiet
  followers piggyback their span lists back on frames bound for the
  leader.  No extra frames, no extra round trips.
- **epoch-fenced** — the context tuple carries the mesh recovery
  epoch; a context stamped by a fenced-out zombie leader is ignored
  (:meth:`TraceRecorder.adopt`), and recovery/failover paths drop the
  in-flight context after the flight-recorder dump (which references
  its trace id — see ``metrics.set_trace_id_provider``).
- **self-limiting** — the recorder measures its own per-sampled-commit
  bookkeeping cost and doubles the sampling interval when the
  amortized overhead approaches the 5%% observability gate, decaying
  back toward the configured base when it is comfortably under
  (:meth:`TraceRecorder._adapt`).

Span timestamps are microseconds since the epoch, derived from one
per-process wall anchor plus ``perf_counter`` deltas — monotonic per
worker track by construction, which is exactly the invariant the
Chrome trace-event export (:func:`chrome_trace`) needs and
:func:`validate_chrome_trace` enforces.

Critical-path attribution (:func:`critical_path`) buckets each traced
commit's wall time into ``queue_wait`` (connector ingest wait plus any
``cat="wait"`` spans), ``exchange`` (PWCF encode + decode/apply, mesh
recv blocking during commit exchange rounds, and the collective
exchange's pack/unpack marshalling), ``device`` (native ``kernel_ns``
deltas), and ``host_compute`` (the residual) — the four sum to the
commit wall exactly, so downstream consumers (bench JSON, the
async-device-pipeline work) can trust the decomposition.

Stages (:func:`stage`, :class:`StageTable`) are the always-on layer
under the sampled spans: one pair of ``perf_counter_ns`` reads per stage
feeds (1) a per-run table of calls, total and self time and counts
(:func:`stage_totals`), zeroed when a ``pw.run()`` begins, (2) a
``jax.profiler.TraceAnnotation("pw:<name>")`` while a profiler session
runs, so the program's stages lie on the device trace's clock, and (3)
the sampled commit's span list, when there is one. A stage that no
metric reads from the table is opened with :func:`detail` instead: it
exists only while reader (2) or (3) is there to see it.

The table is a sum over the run; its time line (:func:`commit_timeline`)
is one record a commit stage of the run thread (:func:`commit_stage`): the
commit's time, its interval, and every stage that exited inside it, folded
by name. A full garbage collection is a stage too (``gc.full``), on
whichever thread the collector ran.
"""

from __future__ import annotations

import functools
import gc
import json
import os
import sys
import tempfile
import threading
import time as _time
from array import array
from collections import deque
from typing import Any

from pathway_tpu.internals import metrics as _metrics

__all__ = [
    "TraceContext",
    "RequestTrace",
    "TraceRecorder",
    "TRACER",
    "TRACE_HEADER",
    "SPANS_HEADER",
    "current",
    "parse_trace_header",
    "encode_spans",
    "decode_spans",
    "critical_path",
    "chrome_trace",
    "validate_chrome_trace",
    "StageTable",
    "STAGES",
    "stage",
    "detail",
    "detail_on",
    "NO_STAGE",
    "begin",
    "end",
    "stage_totals",
    "commit_stage",
    "commit_timeline",
    "traced_run",
]

#: spans kept per commit per worker before dropping (bounds frame size)
MAX_SPANS = 2048

#: amortized (overhead / interval) share of commit wall that triggers an
#: interval doubling — half the 5% observability gate, for headroom
OVERHEAD_TARGET = 0.02

#: request header carrying the read-tier trace context across HTTP hops:
#: ``"<trace_id>;<parent_span_id>;<0|1 sampling bit>"``
TRACE_HEADER = "X-Pathway-Trace"

#: response header piggybacking a remote hop's span list back to its
#: caller (compact JSON; dropped rather than split when oversized)
SPANS_HEADER = "X-Pathway-Trace-Spans"

#: span-piggyback budget — one HTTP header line; an oversized payload is
#: dropped (the caller keeps its own leg span, so the trace stays valid)
MAX_SPANS_HEADER_BYTES = 16384


def parse_trace_header(value: str | None) -> tuple[str, str, bool] | None:
    """Decode an ``X-Pathway-Trace`` value into
    ``(trace_id, parent_span_id, sampled)``; ``None`` when absent or
    garbled — a skewed peer must never break the request path."""
    if not value:
        return None
    parts = str(value).split(";")
    if len(parts) != 3 or not parts[0] or not parts[1]:
        return None
    return parts[0], parts[1], parts[2] == "1"


def encode_spans(spans: list[dict]) -> str | None:
    """Compact JSON for the response-header span piggyback, or ``None``
    when there is nothing to send or the payload would blow the header
    budget."""
    if not spans:
        return None
    try:
        payload = json.dumps(spans, separators=(",", ":"), default=repr)
    except (TypeError, ValueError):
        return None
    if len(payload) > MAX_SPANS_HEADER_BYTES:
        return None
    return payload


def decode_spans(value: str | None) -> list[dict]:
    """Parse a piggybacked span list defensively: malformed input yields
    ``[]``, and only dict entries with a string name and numeric ``ts``
    survive (the shape :func:`chrome_trace` depends on)."""
    if not value:
        return []
    try:
        spans = json.loads(value)
    except (TypeError, ValueError):
        return []
    if not isinstance(spans, list):
        return []
    out: list[dict] = []
    for s in spans:
        if (
            isinstance(s, dict)
            and isinstance(s.get("name"), str)
            and isinstance(s.get("ts"), (int, float))
        ):
            out.append(s)
    return out

# one per-process clock anchor: wall time is captured once, every span
# timestamp is the anchor plus a perf_counter/monotonic delta — so per-
# worker timestamps are strictly monotonic even if the system clock steps
_ANCHOR_WALL = _time.time()
_ANCHOR_PERF = _time.perf_counter()
_ANCHOR_MONO = _time.monotonic()


def perf_to_wall(t: float) -> float:
    return _ANCHOR_WALL + (t - _ANCHOR_PERF)


def mono_to_wall(t: float) -> float:
    return _ANCHOR_WALL + (t - _ANCHOR_MONO)


def _us(wall: float) -> int:
    return int(wall * 1e6)


def _kernel_ns_snapshot() -> dict | None:
    """Per-kernel cumulative ns across every kernel plane: the C++ host
    kernels (native.kernel_ns) and the JAX device operator kernels
    (engine.device_ops), the latter prefixed ``device_ops.`` — span
    deltas over this snapshot feed the critical-path ``kernel_ns``
    bucket, so device-resident operators show up as device time."""
    out: dict | None = None
    try:
        from pathway_tpu import native

        kernel_ns = getattr(native, "kernel_ns", None)
        if kernel_ns is not None:
            out = dict(kernel_ns())
    except Exception:
        out = None
    try:
        from pathway_tpu.engine import device_ops

        dns = device_ops.kernel_ns()
        if dns:
            out = dict(out) if out else {}
            for name, ns in dns.items():
                out["device_ops." + name] = ns
    except Exception:
        pass
    return out


class TraceContext:
    """The in-flight sampled commit: identity plus the span accumulator.

    Created by the leader (:meth:`TraceRecorder.begin`) or adopted from
    the leader's round-frame context tuple on a follower
    (:meth:`TraceRecorder.adopt`, ``remote=True``)."""

    __slots__ = (
        "trace_id",
        "commit_time",
        "origin_wall",
        "epoch",
        "pid",
        "remote",
        "begin_wall",
        "spans",
        "dropped",
        "sink_rows",
        "native_ns0",
        "overhead_s",
    )

    def __init__(
        self,
        trace_id: str,
        commit_time: int,
        origin_wall: float,
        epoch: int,
        pid: int,
        remote: bool = False,
    ) -> None:
        self.trace_id = trace_id
        self.commit_time = int(commit_time)
        self.origin_wall = float(origin_wall)
        self.epoch = int(epoch)
        self.pid = int(pid)
        self.remote = remote
        self.begin_wall = perf_to_wall(_time.perf_counter())
        self.spans: list[dict] = []
        self.dropped = 0
        self.sink_rows = 0
        self.native_ns0: dict | None = None
        self.overhead_s = 0.0

    def span(
        self, name: str, cat: str, t0: float, t1: float, **args: Any
    ) -> None:
        """Record one completed span from perf_counter stamps ``t0``/``t1``
        (taken by the instrumented call site around the work)."""
        if len(self.spans) >= MAX_SPANS:
            self.dropped += 1
            return
        ev: dict = {
            "name": name,
            "cat": cat,
            "ts": _us(perf_to_wall(t0)),
            "dur": max(0, int((t1 - t0) * 1e6)),
            "pid": self.pid,
        }
        if args:
            ev["args"] = args
        self.spans.append(ev)

    def note_sink(self, rows: int) -> None:
        self.sink_rows += int(rows)


class RequestTrace:
    """One in-flight read-tier request: identity plus span accumulator.

    Unlike :class:`TraceContext` (single-slot, pump-thread-private), a
    request trace is born on an HTTP handler thread and accumulates
    spans from the federation scatter pool concurrently, so its span
    list and span-id counter are lock-guarded.  ``track`` is the OS
    pid: every process a request crosses renders on its own Chrome
    track, so per-track timestamps stay monotonic even though each
    process stamps spans off its own clock anchor."""

    __slots__ = (
        "trace_id",
        "parent_span",
        "endpoint",
        "remote",
        "track",
        "origin_wall",
        "begin_wall",
        "spans",
        "dropped",
        "overhead_s",
        "_lock",
        "_sid",
    )

    def __init__(
        self,
        trace_id: str,
        endpoint: str,
        parent_span: str | None = None,
        remote: bool = False,
    ) -> None:
        self.trace_id = trace_id
        self.parent_span = parent_span
        self.endpoint = endpoint
        self.remote = remote
        self.track = os.getpid()
        self.begin_wall = perf_to_wall(_time.perf_counter())
        self.origin_wall = self.begin_wall
        self._lock = threading.Lock()
        self.spans: list[dict] = []  # guarded-by: self._lock
        self._sid = 0  # guarded-by: self._lock
        self.dropped = 0  # guarded-by: self._lock
        self.overhead_s = 0.0

    def alloc_sid(self) -> str:
        """Reserve a span id BEFORE the RPC it will name, so the
        outbound trace header can carry it as the callee's parent."""
        with self._lock:
            self._sid += 1
            return f"{self.track:x}.{self._sid}"

    def span(
        self,
        name: str,
        cat: str,
        t0: float,
        t1: float,
        sid: str | None = None,
        **args: Any,
    ) -> None:
        """Record one completed span from perf_counter stamps; safe to
        call from any thread holding a reference to this context."""
        ev: dict = {
            "name": name,
            "cat": cat,
            "ts": _us(perf_to_wall(t0)),
            "dur": max(0, int((t1 - t0) * 1e6)),
            "pid": self.track,
        }
        if sid is not None:
            args["sid"] = sid
        if self.parent_span is not None:
            args.setdefault("parent", self.parent_span)
        if args:
            ev["args"] = args
        with self._lock:
            if len(self.spans) >= MAX_SPANS:
                self.dropped += 1
                return
            self.spans.append(ev)

    def add_remote_spans(
        self, spans: list[dict], parent_sid: str
    ) -> None:
        """Merge a callee's piggybacked spans.  Each span keeps the
        ``pid`` track its own process stamped; spans that did not carry
        a parent (older peers) are adopted under this leg's sid."""
        with self._lock:
            for s in spans:
                if len(self.spans) >= MAX_SPANS:
                    self.dropped += 1
                    continue
                args = dict(s.get("args") or {})
                args.setdefault("parent", parent_sid)
                self.spans.append(dict(s, args=args))

    def header(self, parent_sid: str) -> str:
        """The outbound ``X-Pathway-Trace`` value for one hop — only
        sampled requests ever propagate, so the bit is always 1."""
        return f"{self.trace_id};{parent_sid};1"

    def take_spans(self) -> list[dict]:
        with self._lock:
            return list(self.spans)


class TraceRecorder:
    """Process-wide sampling trace recorder (singleton: :data:`TRACER`).

    The engine's only hot-path contact points are :func:`current` (one
    attribute read, ``None`` when the running commit is unsampled) and
    :meth:`begin` (a counter bump + modulo when tracing is enabled, a
    single boolean test when it is not)."""

    def __init__(
        self,
        enabled: bool | None = None,
        sample: int | None = None,
        maxlen: int | None = None,
    ) -> None:
        if maxlen is None:
            try:
                maxlen = int(os.environ.get("PATHWAY_TPU_TRACE_RING", "64"))
            except ValueError:
                maxlen = 64
        self._lock = threading.Lock()
        #: the ring and the query counter are the cross-thread surface:
        #: serving workers record queries and exporters snapshot the ring
        #: while the pump appends.  _ctx/_count/_export_seq/_overhead_ema
        #: are pump-thread-private and deliberately unguarded.
        self._traces: deque = deque(maxlen=max(1, maxlen))  # guarded-by: self._lock
        self._ctx: TraceContext | None = None
        self._count = 0
        self._query_count = 0  # guarded-by: self._lock
        self._request_count = 0  # guarded-by: self._lock
        #: per-HTTP-handler-thread request context slot; thread-local so
        #: concurrent requests on the serving pool never share a trace
        self._req_local = threading.local()
        self._export_seq = 0
        self._overhead_ema: float | None = None
        self._req_overhead_ema: float | None = None
        self.epoch = 0
        self.configure(enabled=enabled, sample=sample)

    # -- configuration -------------------------------------------------------

    def configure(
        self,
        enabled: bool | None = None,
        sample: int | None = None,
        clear: bool = False,
        request_enabled: bool | None = None,
        request_sample: int | None = None,
    ) -> None:
        """(Re)read the knobs; tests and benches call this directly
        instead of mutating the environment."""
        if enabled is None:
            enabled = os.environ.get("PATHWAY_TPU_TRACE", "").lower() in (
                "1",
                "true",
                "yes",
            )
        if sample is None:
            try:
                sample = int(
                    os.environ.get("PATHWAY_TPU_TRACE_SAMPLE", "16")
                )
            except ValueError:
                sample = 16
        if request_enabled is None:
            request_enabled = os.environ.get(
                "PATHWAY_TPU_REQUEST_TRACE", ""
            ).lower() in ("1", "true", "yes")
        if request_sample is None:
            try:
                request_sample = int(
                    os.environ.get(
                        "PATHWAY_TPU_REQUEST_TRACE_SAMPLE", "16"
                    )
                )
            except ValueError:
                request_sample = 16
        self.enabled = bool(enabled)
        self.base_interval = max(1, int(sample))
        self.interval = self.base_interval
        self.request_enabled = bool(request_enabled)
        self.request_base_interval = max(1, int(request_sample))
        self.request_interval = self.request_base_interval
        try:
            self.worker_id = int(os.environ.get("PATHWAY_PROCESS_ID", "0"))
        except ValueError:
            self.worker_id = 0
        self._ctx = None
        self._req_local = threading.local()
        self._overhead_ema = None
        self._req_overhead_ema = None
        if clear:
            with self._lock:
                self._traces.clear()
                self._query_count = 0
                self._request_count = 0
            self._count = 0
            self._export_seq = 0

    # -- commit lifecycle ----------------------------------------------------

    def begin(
        self,
        commit_time: int,
        origin_mono: float | None = None,
        sources: list[str] | None = None,
    ) -> TraceContext | None:
        """Leader/local-side sampling decision at commit start.

        ``origin_mono`` is the connector ingest stamp
        (``InputDriver.first_pending_wall``, a ``time.monotonic`` value:
        the arrival of the commit's oldest row at its reader)
        popped by the runner — the trace's time zero.  Returns the
        active context when this commit is sampled, else ``None``."""
        if not self.enabled:
            return None
        self._count += 1
        if (self._count - 1) % self.interval:
            return None
        t0 = _time.perf_counter()
        now_wall = perf_to_wall(t0)
        origin_wall = (
            mono_to_wall(origin_mono) if origin_mono is not None else now_wall
        )
        origin_wall = min(origin_wall, now_wall)
        ctx = TraceContext(
            trace_id=(
                f"t{self.worker_id:02d}-{os.getpid():x}-{self._count:06x}"
            ),
            commit_time=commit_time,
            origin_wall=origin_wall,
            epoch=self.epoch,
            pid=self.worker_id,
        )
        ctx.native_ns0 = _kernel_ns_snapshot()
        if now_wall - origin_wall > 1e-6:
            # the connector-ingest wait, synthesized as the first span —
            # rendered on the track, but bucketed via the begin/origin
            # delta (not the "wait" category) to avoid double counting
            ev: dict = {
                "name": "ingest-wait",
                "cat": "queue",
                "ts": _us(origin_wall),
                "dur": max(0, int((now_wall - origin_wall) * 1e6)),
                "pid": self.worker_id,
            }
            if sources:
                ev["args"] = {"sources": sources}
            ctx.spans.append(ev)
        self._ctx = ctx
        ctx.overhead_s += _time.perf_counter() - t0
        return ctx

    def ctx_frame(self) -> tuple | None:
        """The context tuple the leader piggybacks on round frames —
        ``("ctx", trace_id, commit_time, origin_wall, epoch)``."""
        ctx = self._ctx
        if ctx is None or ctx.remote:
            return None
        return ("ctx", ctx.trace_id, ctx.commit_time, ctx.origin_wall,
                ctx.epoch)

    def adopt(self, payload: tuple) -> TraceContext | None:
        """Follower-side: activate the leader's trace context from a
        round-frame tuple.  A context stamped with an epoch below this
        process's fence floor is a zombie ex-leader's — ignored."""
        epoch = int(payload[4])
        if epoch < self.epoch:
            return None
        self.epoch = epoch
        ctx = self._ctx
        if ctx is not None and ctx.trace_id == payload[1]:
            return ctx
        ctx = TraceContext(
            trace_id=str(payload[1]),
            commit_time=int(payload[2]),
            origin_wall=float(payload[3]),
            epoch=epoch,
            pid=self.worker_id,
            remote=True,
        )
        self._ctx = ctx
        return ctx

    def take_spans(self) -> list[dict]:
        """Copy of the active context's spans so far — what a quiet
        follower piggybacks to the leader (the leader keeps the latest
        copy per peer, so the final quiescent round wins)."""
        ctx = self._ctx
        return list(ctx.spans) if ctx is not None else []

    def drop(self) -> None:
        """Abandon the in-flight context (followers at commit end;
        every process on recovery/failover — call AFTER the flight
        dump so forensics still reference the trace id)."""
        self._ctx = None

    def end(
        self, commit_time: int, peer_spans: dict | None = None
    ) -> dict | None:
        """Leader/local-side commit end: assemble the trace (local +
        piggybacked peer spans), attribute the critical path, ring it,
        and feed the adaptive sampler."""
        ctx = self._ctx
        self._ctx = None
        if ctx is None or ctx.remote:
            return None
        t_end = _time.perf_counter()
        end_wall = perf_to_wall(t_end)
        kernels: dict[str, int] = {}
        device_s = 0.0
        if ctx.native_ns0 is not None:
            now_ns = _kernel_ns_snapshot() or {}
            for k, ns in now_ns.items():
                d = int(ns) - int(ctx.native_ns0.get(k, 0))
                if d > 0:
                    kernels[k] = d
            device_s = sum(kernels.values()) / 1e9
        workers: dict[int, list] = {}
        if peer_spans:
            for peer, spans in sorted(peer_spans.items()):
                if spans:
                    workers[int(peer)] = list(spans)
        trace: dict = {
            "trace_id": ctx.trace_id,
            "commit_time": int(commit_time),
            "epoch": ctx.epoch,
            "worker": ctx.pid,
            "origin_wall": ctx.origin_wall,
            "begin_wall": ctx.begin_wall,
            "end_wall": end_wall,
            "spans": ctx.spans,
            "workers": workers,
            "sink_rows": ctx.sink_rows,
            "dropped_spans": ctx.dropped,
            "device_kernel_ns": kernels,
            "device_s": device_s,
        }
        trace["critical_path"] = critical_path(trace)
        with self._lock:
            self._traces.append(trace)
        overhead = ctx.overhead_s + (_time.perf_counter() - t_end)
        self._adapt(overhead, max(end_wall - ctx.begin_wall, 0.0))
        return trace

    def _adapt(self, overhead_s: float, commit_wall_s: float) -> None:
        """Keep the amortized tracing cost under the overhead target by
        doubling the sampling interval when a sampled commit's
        bookkeeping is too large a share of the (interval-amortized)
        commit wall, decaying back toward the configured base when the
        cost is comfortably below it."""
        amortized = overhead_s / max(1, self.interval)
        ratio = amortized / max(commit_wall_s, 1e-6)
        ema = self._overhead_ema
        self._overhead_ema = ratio if ema is None else 0.5 * ema + 0.5 * ratio
        if self._overhead_ema > OVERHEAD_TARGET:
            self.interval = min(self.interval * 2, 4096)
            self._overhead_ema /= 2.0  # doubling halves the amortized cost
        elif (
            self.interval > self.base_interval
            and self._overhead_ema < OVERHEAD_TARGET / 4.0
        ):
            self.interval = max(self.base_interval, self.interval // 2)
            self._overhead_ema *= 2.0

    # -- serving-plane query traces ------------------------------------------

    def record_query(
        self,
        name: str,
        t0: float,
        t1: float,
        commit_time: int = 0,
        **args: Any,
    ) -> dict | None:
        """Record one served query (or query micro-batch) as a standalone
        ``kind="serving"`` trace in the same ring.

        Queries run on serving threads CONCURRENTLY with commits, so
        they never touch the single-slot commit context (``_ctx``) —
        each call assembles its own one-span trace.  Sampling uses its
        own counter at the same interval, so query volume cannot starve
        commit traces (and vice versa).  ``commit_time`` is the served
        snapshot's commit time: ``cli trace`` correlates query spans
        with the commit that published their view."""
        if not self.enabled:
            return None
        with self._lock:
            self._query_count += 1
            if (self._query_count - 1) % self.interval:
                return None
        origin_wall = perf_to_wall(t0)
        end_wall = perf_to_wall(t1)
        span: dict = {
            "name": name,
            "cat": "serving",
            "ts": _us(origin_wall),
            "dur": max(0, int((t1 - t0) * 1e6)),
            "pid": self.worker_id,
        }
        if args:
            span["args"] = dict(args)
        trace: dict = {
            "kind": "serving",
            "trace_id": (
                f"q{self.worker_id:02d}-{os.getpid():x}"
                f"-{self._query_count:06x}"
            ),
            "commit_time": int(commit_time),
            "epoch": self.epoch,
            "worker": self.worker_id,
            "origin_wall": origin_wall,
            "begin_wall": origin_wall,
            "end_wall": end_wall,
            "spans": [span],
            "workers": {},
            "sink_rows": 0,
            "dropped_spans": 0,
            "device_kernel_ns": {},
            "device_s": 0.0,
        }
        trace["critical_path"] = critical_path(trace)
        with self._lock:
            self._traces.append(trace)
        return trace

    # -- read-tier request traces --------------------------------------------

    def begin_request(self, endpoint: str) -> RequestTrace | None:
        """Root-side sampling decision for one read-tier request.

        The first request is always sampled (a single smoke query must
        yield a trace), then every ``request_interval``-th; the counter
        is lock-guarded because requests land on concurrent handler
        threads.  The context lives in a thread-local slot for the
        handler's duration."""
        if not self.request_enabled:
            return None
        t0 = _time.perf_counter()
        with self._lock:
            self._request_count += 1
            count = self._request_count
        if (count - 1) % self.request_interval:
            return None
        ctx = RequestTrace(
            trace_id=f"r{self.worker_id:02d}-{os.getpid():x}-{count:06x}",
            endpoint=endpoint,
        )
        self._req_local.ctx = ctx
        ctx.overhead_s += _time.perf_counter() - t0
        return ctx

    def adopt_request(
        self, header_value: str | None, endpoint: str = ""
    ) -> RequestTrace | None:
        """Downstream-hop side: adopt the caller's trace context from an
        ``X-Pathway-Trace`` header.  The ROOT owns the sampling
        decision, so a sampled header is honored even when this
        process's own request tracing is off (a traced federation
        front can stitch through untraced workers)."""
        parsed = parse_trace_header(header_value)
        if parsed is None or not parsed[2]:
            return None
        ctx = RequestTrace(
            trace_id=parsed[0],
            endpoint=endpoint,
            parent_span=parsed[1],
            remote=True,
        )
        self._req_local.ctx = ctx
        return ctx

    def current_request(self) -> RequestTrace | None:
        """This thread's in-flight request trace, or None — the guard
        every read-tier instrumentation site checks first."""
        return getattr(self._req_local, "ctx", None)

    def take_request_spans(self) -> list[dict]:
        """A remote hop's accumulated spans, for the response-header
        piggyback back to the caller."""
        ctx = self.current_request()
        return ctx.take_spans() if ctx is not None else []

    def drop_request(self) -> None:
        """Clear this thread's request slot — called unconditionally in
        handler ``finally`` blocks so pooled serving threads never leak
        a context into the next request they pick up."""
        self._req_local.ctx = None

    def end_request(
        self, ctx: RequestTrace | None, status: int = 200, **fields: Any
    ) -> dict | None:
        """Root-side request end: assemble the trace (local + merged
        remote spans, each on its own per-process track), attribute the
        critical path, ring it, and feed the request sampler."""
        self._req_local.ctx = None
        if ctx is None or ctx.remote:
            return None
        t_end = _time.perf_counter()
        end_wall = perf_to_wall(t_end)
        with ctx._lock:
            spans = list(ctx.spans)
            dropped = ctx.dropped
        trace: dict = {
            "kind": "request",
            "trace_id": ctx.trace_id,
            "endpoint": ctx.endpoint,
            "status": int(status),
            "commit_time": int(fields.pop("commit_time", 0) or 0),
            "epoch": self.epoch,
            "worker": ctx.track,
            "origin_wall": ctx.origin_wall,
            "begin_wall": ctx.begin_wall,
            "end_wall": end_wall,
            "spans": spans,
            "workers": {},
            "sink_rows": 0,
            "dropped_spans": dropped,
            "device_kernel_ns": {},
            "device_s": 0.0,
        }
        if fields:
            trace["request"] = dict(fields)
        trace["critical_path"] = critical_path(trace)
        with self._lock:
            self._traces.append(trace)
        overhead = ctx.overhead_s + (_time.perf_counter() - t_end)
        self._adapt_request(
            overhead, max(end_wall - ctx.begin_wall, 0.0)
        )
        return trace

    def _adapt_request(self, overhead_s: float, wall_s: float) -> None:
        """Same EMA-doubling discipline as :meth:`_adapt`, on the
        request sampler's own interval so query floods cannot push the
        commit sampler around (and vice versa)."""
        amortized = overhead_s / max(1, self.request_interval)
        ratio = amortized / max(wall_s, 1e-6)
        ema = self._req_overhead_ema
        self._req_overhead_ema = (
            ratio if ema is None else 0.5 * ema + 0.5 * ratio
        )
        if self._req_overhead_ema > OVERHEAD_TARGET:
            self.request_interval = min(self.request_interval * 2, 4096)
            self._req_overhead_ema /= 2.0
        elif (
            self.request_interval > self.request_base_interval
            and self._req_overhead_ema < OVERHEAD_TARGET / 4.0
        ):
            self.request_interval = max(
                self.request_base_interval, self.request_interval // 2
            )
            self._req_overhead_ema *= 2.0

    # -- read side -----------------------------------------------------------

    def traces(self) -> list[dict]:
        with self._lock:
            return list(self._traces)

    def active_trace_id(self) -> str | None:
        ctx = self._ctx
        return ctx.trace_id if ctx is not None else None

    def summary(self) -> dict:
        """Structured roll-up for bench JSON: trace count, span volume,
        the mean critical-path buckets, and the last commit's full
        breakdown.  Serving-plane query traces are rolled up separately
        (``query_traces`` / ``query_ms_mean``) so query latency cannot
        skew the commit critical-path means."""
        all_traces = self.traces()
        queries = [t for t in all_traces if t.get("kind") == "serving"]
        requests = [t for t in all_traces if t.get("kind") == "request"]
        traces = [
            t
            for t in all_traces
            if t.get("kind") not in ("serving", "request")
        ]
        query_summary: dict = {}
        if queries:
            query_summary = {
                "query_traces": len(queries),
                "query_ms_mean": round(
                    sum(
                        (t["end_wall"] - t["origin_wall"]) for t in queries
                    )
                    / len(queries)
                    * 1000.0,
                    3,
                ),
            }
        if requests:
            query_summary["request_traces"] = len(requests)
            query_summary["request_ms_mean"] = round(
                sum((t["end_wall"] - t["origin_wall"]) for t in requests)
                / len(requests)
                * 1000.0,
                3,
            )
            query_summary["request_sample_interval"] = self.request_interval
        if not traces:
            return {
                "traces": 0,
                "sample_interval": self.interval,
                **query_summary,
            }
        n = len(traces)
        keys = (
            "wall_s",
            "host_compute_s",
            "exchange_s",
            "queue_wait_s",
            "device_s",
        )
        mean = {
            k: round(sum(t["critical_path"][k] for t in traces) / n, 6)
            for k in keys
        }
        # mean bucket shares as fractions of the mean wall — computed
        # from the means (not averaged per-trace) so older ring entries
        # without a "shares" field cannot skew the roll-up
        mean["shares"] = _bucket_shares(
            mean["wall_s"],
            mean["host_compute_s"],
            mean["exchange_s"],
            mean["queue_wait_s"],
            mean["device_s"],
        )
        spans = sum(
            len(t["spans"]) + sum(len(v) for v in t["workers"].values())
            for t in traces
        )
        return {
            "traces": n,
            "spans": spans,
            "sample_interval": self.interval,
            "critical_path_mean": mean,
            "last": traces[-1]["critical_path"],
            **query_summary,
        }

    def export(self, directory: str | None = None) -> str | None:
        """Dump the ring as one Chrome trace-event JSON file
        (``pathway_trace_p<worker>_pid<pid>_<n>.json``) into
        ``directory`` / ``PATHWAY_TPU_TRACE_DIR`` / the system temp
        dir.  Returns the path, or None when there is nothing to dump
        or the dump itself fails (export must never mask a run)."""
        traces = self.traces()
        if not traces:
            return None
        try:
            directory = (
                directory
                or os.environ.get("PATHWAY_TPU_TRACE_DIR")
                or tempfile.gettempdir()
            )
            os.makedirs(directory, exist_ok=True)
            self._export_seq += 1
            path = os.path.join(
                directory,
                f"pathway_trace_p{self.worker_id}"
                f"_pid{os.getpid()}_{self._export_seq:03d}.json",
            )
            payload = chrome_trace(traces)
            payload["otherData"] = {
                "worker": self.worker_id,
                "pid": os.getpid(),
                "traces": [
                    {
                        "trace_id": t["trace_id"],
                        "kind": t.get("kind", "commit"),
                        "commit_time": t["commit_time"],
                        "epoch": t["epoch"],
                        "sink_rows": t["sink_rows"],
                        "critical_path": t["critical_path"],
                        **(
                            {"spans": t["spans"]}
                            if t.get("kind") in ("serving", "request")
                            else {}
                        ),
                        **(
                            {
                                "endpoint": t.get("endpoint", ""),
                                "status": t.get("status", 0),
                                "request": t.get("request", {}),
                            }
                            if t.get("kind") == "request"
                            else {}
                        ),
                    }
                    for t in traces
                ],
            }
            with open(path, "w") as fh:
                json.dump(payload, fh, default=repr)
            return path
        except Exception:
            return None


# -- critical-path attribution ------------------------------------------------


def critical_path(trace: dict) -> dict:
    """Bucket a trace's wall time (origin -> commit end) into
    queue-wait / exchange / device / host-compute, plus the serialized
    chain of significant spans in timestamp order.

    The buckets sum to ``wall_s`` exactly by construction: queue-wait is
    the ingest wait (begin - origin) plus ``cat="wait"`` spans, exchange
    is measured encode/apply/marshalling time plus mesh recv blocking
    during commit exchange rounds (wire latency is exchange cost — the
    device collective has no wire, which is exactly what the
    collective_exchange bench leg compares), device is the native
    ``kernel_ns`` delta, and host-compute is the residual (clamped at
    zero, flagged via ``clamped``). A ``cat="device_wait"`` span (a
    ``wait`` stage: the host standing at a blocking read of the device)
    has no bucket of its own: it stays in the residual, where that time
    lay before the stages named it, so the four buckets and what
    ``device_pipeline.Controller.observe`` reads keep their meaning."""
    wall = max(1e-9, trace["end_wall"] - trace["origin_wall"])
    queue = max(0.0, trace["begin_wall"] - trace["origin_wall"])
    exchange = 0.0
    for s in trace["spans"]:
        cat = s.get("cat")
        dur = s.get("dur", 0) / 1e6
        if cat == "wait":
            queue += dur
        elif cat == "exchange":
            exchange += dur
    device = float(trace.get("device_s", 0.0))
    host = wall - queue - exchange - device
    clamped = host < 0.0
    host = max(0.0, host)
    chain: list[dict] = []
    for s in sorted(trace["spans"], key=lambda s: s["ts"]):
        if s.get("cat") == "commit":
            continue
        dur_ms = s.get("dur", 0) / 1000.0
        if dur_ms >= wall * 1000.0 * 0.01 or s.get("cat") in (
            "wait",
            "exchange",
            "queue",
        ):
            chain.append(
                {
                    "name": s["name"],
                    "cat": s.get("cat", ""),
                    "ms": round(dur_ms, 3),
                }
            )
            if len(chain) >= 64:
                break
    return {
        "wall_s": round(wall, 6),
        "host_compute_s": round(host, 6),
        "exchange_s": round(exchange, 6),
        "queue_wait_s": round(queue, 6),
        "device_s": round(device, 6),
        # per-bucket shares as fractions of commit wall: the docs/s
        # trajectory and the bucket trajectory stay comparable across
        # BENCH_r* files regardless of absolute commit duration
        "shares": _bucket_shares(wall, host, exchange, queue, device),
        "clamped": clamped,
        "chain": chain,
    }


def _bucket_shares(
    wall: float, host: float, exchange: float, queue: float, device: float
) -> dict:
    w = max(wall, 1e-9)
    return {
        "host_compute": round(host / w, 4),
        "exchange": round(exchange / w, 4),
        "queue_wait": round(queue / w, 4),
        "device": round(device / w, 4),
    }


# -- Chrome trace-event export ------------------------------------------------


def chrome_trace(traces: list[dict]) -> dict:
    """Render assembled traces as a Chrome trace-event JSON object
    (Perfetto/chrome://tracing loadable): complete ``"X"`` events on one
    track per worker (``pid``/``tid`` = worker id), a root ``commit``
    span per worker per trace for containment parentage, and ``"M"``
    metadata events naming the tracks.  Events are sorted by timestamp,
    so each track's sequence is monotonic — the invariant
    :func:`validate_chrome_trace` checks."""
    events: list[dict] = []
    pids: set[int] = set()
    for trace in traces:
        groups: dict[int, list[dict]] = {}
        for s in trace["spans"]:
            groups.setdefault(int(s.get("pid", trace["worker"])), []).append(s)
        for peer, spans in trace["workers"].items():
            for s in spans:
                groups.setdefault(int(s.get("pid", peer)), []).append(s)
        for wid, spans in sorted(groups.items()):
            if not spans:
                continue
            pids.add(wid)
            start = min(s["ts"] for s in spans)
            end = max(s["ts"] + s.get("dur", 0) for s in spans)
            root_args: dict = {
                "trace": trace["trace_id"],
                "commit_time": trace["commit_time"],
            }
            if wid == trace["worker"]:
                root_args["critical_path"] = {
                    k: v
                    for k, v in trace["critical_path"].items()
                    if k != "chain"
                }
                if trace["device_kernel_ns"]:
                    root_args["device_kernel_ns"] = trace["device_kernel_ns"]
            events.append(
                {
                    "name": (
                        f"query @{trace['commit_time']}"
                        if trace.get("kind") == "serving"
                        else f"request {trace.get('endpoint') or '?'}"
                        if trace.get("kind") == "request"
                        else f"commit {trace['commit_time']}"
                    ),
                    "cat": "commit",
                    "ph": "X",
                    "ts": start,
                    "dur": max(0, end - start),
                    "pid": wid,
                    "tid": wid,
                    "args": root_args,
                }
            )
            for s in spans:
                ev = {
                    "name": s["name"],
                    "cat": s.get("cat", ""),
                    "ph": "X",
                    "ts": s["ts"],
                    "dur": s.get("dur", 0),
                    "pid": wid,
                    "tid": wid,
                    "args": dict(
                        s.get("args") or {}, trace=trace["trace_id"]
                    ),
                }
                events.append(ev)
    # a root span shares its start ts with its first child: emit the
    # longer (enclosing) event first so viewers nest them correctly
    events.sort(key=lambda e: (e["ts"], -e.get("dur", 0)))
    meta = [
        {
            "name": "process_name",
            "ph": "M",
            "pid": wid,
            "tid": wid,
            "args": {"name": f"worker {wid}"},
        }
        for wid in sorted(pids)
    ]
    return {"traceEvents": meta + events, "displayTimeUnit": "ms"}


def validate_chrome_trace(obj: Any) -> list[dict]:
    """Strict Chrome trace-event conformance check (the trace-export
    gate in tools/check.py): the object is a ``{"traceEvents": [...]}``
    dict or a bare event list; every event is ``"X"`` (with a numeric
    non-negative ``dur``), a matched ``"B"``/``"E"`` pair, or ``"M"``
    metadata; and timestamps are monotonic non-decreasing per
    ``(pid, tid)`` track.  Returns the event list; raises
    ``ValueError`` on any violation."""
    if isinstance(obj, list):
        events = obj
    elif isinstance(obj, dict):
        events = obj.get("traceEvents")
        if not isinstance(events, list):
            raise ValueError("trace object has no traceEvents list")
    else:
        raise ValueError(f"not a trace object: {type(obj).__name__}")
    last_ts: dict[tuple, float] = {}
    open_begins: dict[tuple, list] = {}
    for i, ev in enumerate(events):
        if not isinstance(ev, dict):
            raise ValueError(f"event {i}: not an object")
        ph = ev.get("ph")
        if ph == "M":
            continue
        if ph not in ("X", "B", "E"):
            raise ValueError(f"event {i}: unsupported phase {ph!r}")
        ts = ev.get("ts")
        if not isinstance(ts, (int, float)):
            raise ValueError(f"event {i}: missing/non-numeric ts")
        track = (ev.get("pid"), ev.get("tid"))
        if ts < last_ts.get(track, float("-inf")):
            raise ValueError(
                f"event {i}: non-monotonic ts on track {track}"
            )
        last_ts[track] = ts
        if ph == "X":
            dur = ev.get("dur")
            if not isinstance(dur, (int, float)) or dur < 0:
                raise ValueError(
                    f"event {i}: X event needs a non-negative dur"
                )
        elif ph == "B":
            open_begins.setdefault(track, []).append(ev.get("name"))
        else:  # "E"
            stack = open_begins.get(track)
            if not stack:
                raise ValueError(
                    f"event {i}: E without a matching B on track {track}"
                )
            stack.pop()
    for track, stack in open_begins.items():
        if stack:
            raise ValueError(
                f"track {track}: unclosed B events {stack!r}"
            )
    return events


#: the process-wide recorder every instrumented hot path consults
TRACER = TraceRecorder()


def current() -> TraceContext | None:
    """The active sampled-commit context, or None — THE hot-path guard;
    call once per batch/sweep, not per row."""
    return TRACER._ctx


def _active_trace_id() -> str | None:
    rctx = TRACER.current_request()
    if rctx is not None:
        return rctx.trace_id
    ctx = TRACER._ctx
    return ctx.trace_id if ctx is not None else None


# -- stages: per-run totals, profiler annotations, sampled spans --------------

#: name of the stage that spans a whole ``run()`` of a runner; its self
#: time is what the run thread did outside every other stage
RUN_STAGE = "run"

#: name of the stage a commit runs under; the run thread's make the time line
COMMIT_STAGE = "commit"

#: name of the stage a full (generation 2) garbage collection runs under
GC_STAGE = "gc.full"

#: commits the time line keeps (``bge-live-rag``, the busiest cell, makes
#: some 580 a window)
TIMELINE_COMMITS = 1024

_now = _time.perf_counter_ns

#: ``jax.profiler.TraceAnnotation`` once jax has been imported by someone
#: else (None until then: the relational paths never import jax for this);
#: looked for when a run begins and by :func:`detail_on`, not per stage
_trace_annotation: Any = None


def _annotation() -> Any:
    global _trace_annotation
    if _trace_annotation is None and "jax" in sys.modules:
        from jax.profiler import TraceAnnotation

        _trace_annotation = TraceAnnotation
    return _trace_annotation


class _ThreadStages:
    """One thread's open stages and its table ``name -> [calls, total_ns,
    child_ns, wait, counts]``; written by that thread alone."""

    __slots__ = ("thread", "stack", "table")

    def __init__(self, thread: threading.Thread) -> None:
        self.thread = thread
        self.stack: list[_Stage] = []
        self.table: dict[str, list] = {}


class _Stage:
    """One open stage. ``with stage(...) as st`` or :func:`begin` /
    :func:`end`; ``st.add(rows=n)`` adds counts known only inside."""

    __slots__ = (
        "owner", "thread", "name", "cat", "wait", "label", "counts", "t0",
        "child_ns", "annotation",
    )

    def __init__(
        self,
        owner: "StageTable",
        thread: _ThreadStages,
        name: str,
        cat: str | None,
        wait: bool,
        label: str | None,
        counts: dict,
    ) -> None:
        self.owner = owner
        self.thread = thread
        self.name = name
        self.cat = cat
        self.wait = wait
        self.label = label
        self.counts = counts
        self.child_ns = 0
        self.annotation = None

    def add(self, **counts: int) -> None:
        have = self.counts
        if not have:
            self.counts = counts
            return
        for key, value in counts.items():
            have[key] = have.get(key, 0) + value

    def __enter__(self) -> "_Stage":
        annotation = _trace_annotation  # resolved when the run began
        if annotation is not None and annotation.is_enabled():
            # a jax.profiler session is running: the stage goes into it
            self.annotation = annotation("pw:" + self.name)
            self.annotation.__enter__()
        # on the stack last: a collection that falls in here is a child of
        # the stage that was open, not of this one before its clock runs
        self.thread.stack.append(self)
        self.t0 = _now()
        return self

    def __exit__(self, *exc: Any) -> bool:
        t1 = _now()
        if self.annotation is not None:
            self.annotation.__exit__(None, None, None)
        th = self.thread
        stack = th.stack
        while stack and stack.pop() is not self:
            pass  # stages an exception left open above this one
        dur = t1 - self.t0
        if stack:
            stack[-1].child_ns += dur
        name = self.name
        entry = th.table.get(name)
        if entry is None:
            entry = th.table[name] = [0, 0, 0, self.wait, {}]
        entry[0] += 1
        entry[1] += dur
        entry[2] += self.child_ns
        counts = self.counts
        if counts:
            have = entry[4]
            for key, value in counts.items():
                have[key] = have.get(key, 0) + value
        commit = self.owner._commit
        if commit is not None and th is commit.thread:
            commit.fold(self, t1)
        ctx = TRACER._ctx
        if ctx is not None and th is self.owner._run_thread:
            # the sampled commit's context belongs to the run thread
            args = dict(counts)
            if self.label is not None:
                args["label"] = self.label
            cat = self.cat or ("device_wait" if self.wait else "stage")
            ctx.span(name, cat, self.t0 / 1e9, t1 / 1e9, **args)
        return False


class _CommitStage(_Stage):
    """A ``commit`` stage (:func:`commit_stage`). The outermost one open on
    the run thread is the time line's open record
    (:meth:`StageTable.timeline`): whoever commits inside it leaves the
    commit's ``time`` here, and every stage that exits on its thread while
    it is open is folded into it by name."""

    __slots__ = ("time", "places", "folded")

    def __enter__(self) -> "_CommitStage":
        self.time: int | None = None
        #: a folded stage's place in ``folded``, by name
        self.places: dict[str, int] = {}
        #: ``first_t0_ns, last_t1_ns, calls`` of each folded stage, flat
        self.folded: list[int] = []
        super().__enter__()
        owner = self.owner
        if owner._commit is None and self.thread is owner._run_thread:
            owner._commit = self
        return self

    def fold(self, st: _Stage, t1: int) -> None:
        """A stage exited on this commit's thread: the commit's own closes
        the record, any other is folded into it by name."""
        if st is self:
            owner = self.owner
            owner._commit = None
            # two tuples and one array a record: nothing the collector
            # has to walk however long the ring
            owner._timeline.append((
                self.time, self.t0, t1,
                tuple(self.places), array("q", self.folded),
            ))
            return
        folded = self.folded
        place = self.places.get(st.name)
        if place is None:
            self.places[st.name] = len(folded)
            folded += (st.t0, t1, 1)
        else:
            folded[place + 1] = t1
            folded[place + 2] += 1


class StageTable:
    """Process-wide stage totals of the current (or last) run (singleton:
    :data:`STAGES`).

    Every thread writes a table of its own, so the hot path takes no lock
    and a name met on two threads (a device fetch on the run thread and
    on the pipeline's completion worker) is two rows. ``self_ns`` is a
    stage's duration less what the stages it caused on the same thread
    covered, so the self times of the run thread's stages, the run's own
    included, sum to ``run_wall_ns`` exactly."""

    def __init__(self) -> None:
        # re-entrant: a collection that falls while it is held opens a
        # stage (``_on_collection``), on a thread that may have no table yet
        self._lock = threading.RLock()
        self._threads: list[_ThreadStages] = []  # guarded-by: self._lock
        self._local = threading.local()
        self._run_thread: _ThreadStages | None = None
        self._root: _Stage | None = None
        self._run_wall_ns = 0
        #: the time line: the commit open on the run thread and the records
        #: of the closed ones
        self._commit: _CommitStage | None = None
        self._timeline: deque = deque(maxlen=TIMELINE_COMMITS)
        self._collection: _Stage | None = None  # the open ``gc.full``

    def _thread(self) -> _ThreadStages:
        try:
            return self._local.stages
        except AttributeError:
            th = self._local.stages = _ThreadStages(threading.current_thread())
            with self._lock:
                self._threads.append(th)
            return th

    def stage(
        self,
        name: str,
        cat: str | None = None,
        wait: bool = False,
        label: str | None = None,
        **counts: int,
    ) -> _Stage:
        """A stage to enter. ``wait`` flags one whose exit blocks on the
        device (its sampled span has the category ``device_wait``);
        ``cat`` and ``label`` go to the sampled commit's span."""
        try:
            thread = self._local.stages
        except AttributeError:
            thread = self._thread()
        return _Stage(self, thread, name, cat, wait, label, counts)

    def commit_stage(self) -> _CommitStage:
        """The stage a runner commits under: ``with commit_stage() as
        commit`` and ``commit.time = ...`` inside. The run thread's are the
        time line's records (:meth:`timeline`)."""
        return _CommitStage(
            self, self._thread(), COMMIT_STAGE, None, False, None, {}
        )

    def begin_run(self) -> _Stage | None:
        """Zero every thread's table and open the run's own stage on this
        thread. A run begun while another is open (an iterate body, a
        second runner on another thread) leaves the table alone."""
        if self._root is not None:
            return None
        _annotation()
        th = self._thread()
        with self._lock:
            self._threads = [
                t for t in self._threads if t is th or t.thread.is_alive()
            ]
            for t in self._threads:
                t.table.clear()
        th.stack.clear()
        self._run_thread = th
        self._run_wall_ns = 0
        self._commit = self._collection = None
        self._timeline.clear()
        gc.callbacks.append(self._on_collection)
        self._root = self.stage(RUN_STAGE).__enter__()
        return self._root

    def end_run(self, root: _Stage | None) -> None:
        if root is None or root is not self._root:
            return
        root.__exit__(None, None, None)
        self._run_wall_ns = root.thread.table[RUN_STAGE][1]
        self._root = None
        try:
            gc.callbacks.remove(self._on_collection)
        except ValueError:
            pass

    def _on_collection(self, phase: str, info: dict) -> None:
        """``gc.callbacks``' hook while a run is open: a full collection is
        a ``gc.full`` stage of the thread it ran on, so its time leaves the
        self time of the stage it interrupted; inside a commit of the run
        thread it is folded into the record like any stage."""
        if info["generation"] != 2:
            return
        if phase == "start":
            self._collection = self.stage(GC_STAGE).__enter__()
            return
        st, self._collection = self._collection, None
        if st is not None:
            st.__exit__(None, None, None)

    def timeline(self) -> list[dict]:
        """One record a commit stage of the run thread in the current (or
        last) run, oldest first, the newest :data:`TIMELINE_COMMITS`, every
        stamp in ``perf_counter_ns``: ``time`` (what ``Scheduler.commit()``
        returned: the identifier every sink callback of the commit is
        handed), ``t0_ns`` and ``t1_ns`` (the stage's own reads, so the
        interval is its ``pw:commit`` annotation's) and ``stages`` (every
        stage that exited on the run thread while the commit was open, by
        name: ``first_t0_ns``, ``last_t1_ns``, ``calls``)."""
        return [
            {
                "time": time,
                "t0_ns": t0,
                "t1_ns": t1,
                "stages": {
                    name: {
                        "first_t0_ns": folded[place],
                        "last_t1_ns": folded[place + 1],
                        "calls": folded[place + 2],
                    }
                    for place, name in zip(range(0, len(folded), 3), names)
                },
            }
            for time, t0, t1, names, folded in list(self._timeline)
        ]

    def totals(self) -> dict:
        """``{"run_wall_ns", "running", "stages", "threads"}``: ``stages``
        is the run thread's table (the calling thread's before any run),
        ``threads`` the other threads' by thread name; a row is
        ``{"calls", "total_ns", "self_ns", "wait", "counts"}``. While a
        run is open its own stage is not yet a row and ``run_wall_ns`` is
        the time since it began."""
        run_thread = self._run_thread or self._thread()
        root = self._root
        if root is not None:
            wall = _now() - root.t0
        else:
            wall = self._run_wall_ns
        with self._lock:
            threads = list(self._threads)
        out: dict = {
            "run_wall_ns": wall,
            "running": root is not None,
            "stages": {},
            "threads": {},
        }
        for th in threads:
            rows = {
                name: {
                    "calls": e[0],
                    "total_ns": e[1],
                    "self_ns": e[1] - e[2],
                    "wait": e[3],
                    "counts": dict(e[4]),
                }
                for name, e in th.table.copy().items()
            }
            if th is run_thread:
                out["stages"] = rows
            elif rows:
                name = th.thread.name
                if name in out["threads"]:
                    name = f"{name}#{th.thread.ident}"
                out["threads"][name] = rows
        return out


#: the process-wide stage table every instrumented layer writes
STAGES = StageTable()

stage = STAGES.stage
stage_totals = STAGES.totals
commit_stage = STAGES.commit_stage
commit_timeline = STAGES.timeline


class _NoStage:
    """What :func:`detail` hands out while nobody is looking: falsy, and
    a no-op to enter, to add to and to leave."""

    __slots__ = ()

    def __bool__(self) -> bool:
        return False

    def add(self, **counts: int) -> None:
        pass

    def __enter__(self) -> "_NoStage":
        return self

    def __exit__(self, *exc: Any) -> bool:
        return False


NO_STAGE = _NoStage()


def detail_on() -> bool:
    """Whether a sampled commit is current or a ``jax.profiler`` session
    is running: only then is a :func:`detail` stage recorded."""
    if TRACER._ctx is not None:
        return True
    annotation = _trace_annotation or _annotation()
    return annotation is not None and annotation.is_enabled()


def detail(name: str, **kwargs: Any) -> "_Stage | _NoStage":
    """A stage that no metric reads from the table (an operator's sweep,
    the pieces of an embed call): recorded, in all three readers, only
    while :func:`detail_on`; otherwise its time stays in its parent's
    self time and the hot path pays one test."""
    return STAGES.stage(name, **kwargs) if detail_on() else NO_STAGE


def begin(name: str, **kwargs: Any) -> _Stage:
    """Open a stage where a ``with`` does not fit; close it with
    :func:`end`."""
    return STAGES.stage(name, **kwargs).__enter__()


def end(st: _Stage, **counts: int) -> None:
    if counts:
        st.add(**counts)
    st.__exit__(None, None, None)


def traced_run(run: Any) -> Any:
    """Decorator for a runner's ``run()``: the stage table is zeroed when
    it begins and holds the run's wall when it returns or raises."""

    @functools.wraps(run)
    def wrapper(*args: Any, **kwargs: Any) -> Any:
        root = STAGES.begin_run()
        try:
            return run(*args, **kwargs)
        finally:
            STAGES.end_run(root)

    return wrapper


# flight-recorder integration: every event recorded (and every dump
# written) while a sampled commit is in flight references its trace id
_metrics.set_trace_id_provider(_active_trace_id)
