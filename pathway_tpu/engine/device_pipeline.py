"""Async device pipeline: double-buffered commit staging between the host
dataflow and device work.

The synchronous engine serializes host and device per commit: every
scheduler sweep ends in :func:`~pathway_tpu.engine.device.decay_device_batches`,
a blocking device->host download of every device batch the commit
produced, so connector ingest for commit N+1 cannot start until commit
N's device work (embed dispatch, index scatter, D2H DMA) has fully
retired.  On the streaming RAG bench that barrier is most of the ~20x
gap between `pw.run` throughput and the device embed ceiling.

This module turns the barrier into a pipeline stage:

- **staging queue (host->device)** — at each commit boundary the
  scheduler hands the commit's live :class:`DeviceBatchHandle` set to
  :meth:`DevicePipeline.commit_boundary` instead of decaying it inline.
  The handles' D2H DMA is *started* (``copy_to_host_async``) but not
  awaited; the host thread returns to the connector poll loop and
  ingests commit N+1 while the device crunches commit N.  jax dispatch
  stays async end to end — the only ``block_until_ready``-equivalent
  wait is the completion worker's ``decay()``.
- **completion queue (device->host)** — a single daemon worker pops
  what is staged strictly FIFO and completes it (awaits the DMA,
  releases HBM), so commit completion is **in order** by construction:
  commit N's device effects are fully host-resident before commit N+1's
  are.  Exactly-once/checkpoint semantics are preserved by the runner
  calling :meth:`drain_until` before persistence/snapshot ``on_commit``
  hooks — a checkpoint for commit N can only be cut after N completed.
- **a sink's emission (device->host, for a consumer that is not the
  pump)** — a subscribe sink whose rows point into a device batch the
  chip has not finished (``DeviceBatchHandle.ready()`` false) does not
  wait for it on the run thread: ``SubscribeNode.process`` emits the rows
  that are ready itself and hands the rest of its batch to
  :meth:`DevicePipeline.hand_over` **while the sink runs**, not at the
  boundary.  The worker waits for the download in the run thread's
  place and calls the user's ``on_change`` for those rows, then
  the sink's ``on_time_end`` if the scheduler left it one; so a row is
  delivered when its batch is down, not after its commit's other
  operators, and the run thread goes on to them (in a RAG graph: the
  queries' embedding, the index update and the search's enqueue lie
  under the documents' encoder instead of behind it).  **A user's
  ``on_change`` / ``on_time_end`` can therefore run on the thread
  ``pw-device-pipeline``**; one sink's callbacks keep their order (rows
  of ``t``, ``on_time_end(t)``, rows of ``t+1``: while anything of a sink
  is with the worker, everything of that sink goes behind it), across
  sinks nothing is promised, and ``on_end`` stays on the run thread,
  behind :meth:`drain`.  What was handed over is part of its commit:
  :meth:`drain_until` and :meth:`drain` wait for it (a checkpoint, a
  published read view and ``on_end`` see every row of their commit
  delivered), the in-flight bound counts it, and a callback that raises
  on the worker is raised on the run thread at the next seam, like a
  failed decay; nothing more is delivered behind it.  A sink of host
  rows, of rows that are ready, or in a commit with no device batch
  never leaves the run thread.
- **double buffering / backpressure** — at most ``depth`` commits
  (default 2, ``PATHWAY_TPU_DEVICE_INFLIGHT``) may be in flight;
  staging commit N+depth blocks until commit N retires, bounding HBM to
  ``depth`` commits' worth of batches (the sync path bounds it to 1).
- **ingest window feedback** — :class:`IngestWindowController` scales
  the connector autocommit window (:func:`ingest_window_scale`, consumed
  by ``InputDriver.effective_autocommit_s``) once per device commit: a
  commit that found the pipeline full, or had to block on the in-flight
  bound, widens the window x1.25 (up to x4) so a saturated device stage
  gets fewer, fatter commits; an idle completion stage (occupancy under
  0.25) relaxes it back toward 1.0.  How many rows one device call holds
  is not decided here: that is the UDF's ``max_batch_size``.

``PATHWAY_TPU_ASYNC_DEVICE=0`` is the escape hatch: the commit boundary
then decays inline and every sink emits on the run thread, waiting
where it has to, bit-identical to the pre-pipeline engine (PR-2
style: the synchronous path stays the spec; tests/test_device_pipeline.py
holds the two modes to bit-identical sinks on all three schedulers).

Occupancy is first-class: ``pathway_device_queue_depth`` (staged +
in-completion commits), ``pathway_device_occupancy_ratio`` (EMA share
of wall time the completion stage is busy), and the
``pathway_device_dispatch_complete_seconds`` histogram (commit-boundary
dispatch -> completion retire latency) all live on the PR-5 registry,
so they ride the mesh snapshot piggyback to the leader ``/metrics``
and render in ``cli stats``.
"""

from __future__ import annotations

import os
import threading
import time as _time
from collections import deque

from pathway_tpu.internals import metrics as _metrics
from pathway_tpu.internals import tracing as _tracing

__all__ = [
    "DevicePipeline",
    "IngestWindowController",
    "PIPELINE",
    "async_enabled",
    "commit_boundary",
    "drain",
    "drain_until",
    "hand_over",
    "holds",
    "reset",
    "ingest_window_scale",
]

#: dispatch->complete latency bucket bounds, seconds — device commits
#: retire in the 100us..1s band on live hardware, slower over remote links
DISPATCH_BUCKETS = (
    0.0001,
    0.00025,
    0.0005,
    0.001,
    0.0025,
    0.005,
    0.01,
    0.025,
    0.05,
    0.1,
    0.25,
    0.5,
    1.0,
    2.5,
    5.0,
)


def async_enabled() -> bool:
    """The escape hatch: ``PATHWAY_TPU_ASYNC_DEVICE=0`` restores the
    synchronous inline-decay commit boundary (the bit-exact spec)."""
    return os.environ.get("PATHWAY_TPU_ASYNC_DEVICE", "1").lower() not in (
        "0",
        "false",
        "no",
    )


def _env_int(name: str, default: int) -> int:
    try:
        return max(1, int(os.environ.get(name, str(default))))
    except ValueError:
        return default


class IngestWindowController:
    """The in-flight bound and the autocommit window's feedback loop.

    ``depth`` is the staged-commit bound (double buffering by default,
    ``PATHWAY_TPU_DEVICE_INFLIGHT``).  ``window_scale`` multiplies
    connector autocommit windows (1.0..4.0) and moves once per *device*
    commit (host-only commits never tick): a blocked or full pipeline
    widens it x1.25, an idle completion stage relaxes it /1.25, and in
    between it holds — monotone and clamped, so it cannot oscillate
    unboundedly.
    """

    #: occupancy below which the device stage counts as starved
    IDLE_OCCUPANCY = 0.25

    def __init__(self) -> None:
        self.depth = _env_int("PATHWAY_TPU_DEVICE_INFLIGHT", 2)
        self.window_scale = 1.0
        self.ticks = 0
        self.grows = 0

    def observe(
        self, *, staged_depth: int, blocked: bool, occupancy: float
    ) -> None:
        """One device-commit tick of the feedback loop."""
        self.ticks += 1
        if blocked or staged_depth >= self.depth:
            # the completion stage is the bottleneck: fewer, larger commits
            self.window_scale = min(4.0, self.window_scale * 1.25)
            self.grows += 1
        elif occupancy < self.IDLE_OCCUPANCY:
            # device starved: the window relaxes back to its configured value
            self.window_scale = max(1.0, self.window_scale / 1.25)

    def stats(self) -> dict:
        return {
            "depth": self.depth,
            "window_scale": round(self.window_scale, 3),
            "ticks": self.ticks,
            "grows": self.grows,
        }


class DevicePipeline:
    """Process-wide staging/completion pipe (singleton: :data:`PIPELINE`).

    Hot-path contract: a commit with no device batches costs one WeakSet
    truthiness test (identical to the sync path) — the lock, the worker
    thread, and the metrics handles are only touched by device commits.
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        #: FIFO of (commit_time, handles, dispatch_perf, sink, work)
        #: awaiting completion: a commit's handles to decay (no sink), or
        #: what a sink of that commit left to the worker (:meth:`hand_over`)
        self._staged: deque = deque()  # guarded-by: self._cv
        #: sink -> pieces of its work staged or running (:meth:`holds`)
        self._held: dict = {}  # guarded-by: self._cv
        self._active_time: int | None = None  # guarded-by: self._cv
        self._completed_time = -1  # guarded-by: self._cv
        self._worker: threading.Thread | None = None
        self._stop = False  # guarded-by: self._cv
        self._error: BaseException | None = None  # guarded-by: self._cv
        self._busy_s = 0.0  # guarded-by: self._cv
        self._occ_mark: float | None = None  # guarded-by: self._cv
        self._occupancy = 0.0  # guarded-by: self._cv
        self.controller = IngestWindowController()
        self._g_depth = _metrics.REGISTRY.gauge(
            "pathway_device_queue_depth",
            "device-pipeline commits staged or completing",
        )
        self._g_occ = _metrics.REGISTRY.gauge(
            "pathway_device_occupancy_ratio",
            "EMA share of wall time the device completion stage is busy",
        )
        self._h_latency = _metrics.REGISTRY.histogram(
            "pathway_device_dispatch_complete_seconds",
            "device commit dispatch -> in-order completion latency",
            buckets=DISPATCH_BUCKETS,
        )
        self._c_commits = _metrics.REGISTRY.counter(
            "pathway_device_pipeline_commits_total",
            "device commits retired through the async pipeline",
        )

    # -- lifecycle -----------------------------------------------------------

    def configure(self) -> None:
        """Drain outstanding work and re-read the env knobs — tests and
        benches call this between runs instead of mutating the singleton."""
        self.drain()
        with self._cv:
            self._error = None
            self._completed_time = -1
            self._busy_s = 0.0
            self._occ_mark = None
            self._occupancy = 0.0
            self._g_occ.value = 0.0
        self.controller = IngestWindowController()

    def _ensure_worker(self) -> None:
        w = self._worker
        if w is None or not w.is_alive():
            with self._cv:
                self._stop = False
            self._worker = threading.Thread(
                target=self._run_completions,
                name="pw-device-pipeline",
                daemon=True,
            )
            self._worker.start()

    def stop_worker(self, timeout: float = 5.0) -> None:
        """Reap the completion worker (run teardown).  The worker first
        retires anything still staged, so a clean run loses nothing; a
        raising run must not leave the daemon behind to accumulate
        across runs — ``_ensure_worker`` respawns it on next use."""
        w = self._worker
        if w is None:
            return
        with self._cv:
            self._stop = True
            self._cv.notify_all()
        if w.is_alive():
            w.join(timeout=timeout)
        if not w.is_alive():
            self._worker = None

    def _take_error_locked(self) -> BaseException | None:
        err = self._error
        self._error = None
        return err

    def _raise_pending(self) -> None:
        with self._cv:
            err = self._take_error_locked()
        if err is not None:
            raise err

    # -- staging side (scheduler thread) -------------------------------------

    def _in_flight_locked(self, but: int | None = None) -> int:
        """Commits with anything staged or completing, ``but`` aside: a
        commit's decay and what its sinks handed over count once."""
        times = self._staged_times_locked()
        if self._active_time is not None:
            times.add(self._active_time)
        times.discard(but)
        return len(times)

    def _staged_times_locked(self) -> set:
        return {entry[0] for entry in self._staged}

    def holds(self, sink) -> bool:
        """Whether anything ``sink`` handed over is still staged or
        running: its next callback then goes behind it, to keep the sink's
        order (rows of ``t``, ``on_time_end(t)``, rows of ``t + 1``)."""
        return sink in self._held  # one key's read: no lock

    def hand_over(self, time: int, sink, work, handles=()) -> None:
        """``sink`` (a node of commit ``time``) would have held the run
        thread at a device batch the chip has not finished: the worker
        takes ``work`` (the sink's callbacks for its rows, or its
        ``on_time_end``) behind what is staged already; the download of
        ``handles``, the batches it will wait for, is started here. Called
        while the sink runs, not at the boundary, so the rows are delivered
        when their batch is down and not after their commit's other
        operators. Only where :func:`async_enabled`; the commit's boundary
        applies the in-flight bound."""
        self._raise_pending()
        for handle in handles:
            handle.prefetch()  # start the DMA; the worker awaits it
        self._ensure_worker()
        with self._cv:
            self._held[sink] = self._held.get(sink, 0) + 1
            self._staged.append(
                (int(time), (), _time.perf_counter(), sink, work)
            )
            self._g_depth.value = float(self._in_flight_locked())
            self._cv.notify_all()

    def commit_boundary(self, time: int) -> None:
        """End-of-commit hook, replacing the inline decay barrier.

        Sync mode (``PATHWAY_TPU_ASYNC_DEVICE=0``): decay inline —
        bit-identical to the pre-pipeline engine.  Async mode: start the
        D2H DMA for every live handle, stage the commit on the FIFO
        (blocking only when ``depth`` earlier commits are still in
        flight), and return to the host sweep immediately."""
        from pathway_tpu.engine import device as _device
        from pathway_tpu.engine import device_residency as _dres

        # exchange outputs kept device-resident are consumed within the
        # commit that delivered them; materialize any survivor here so
        # HBM stays bounded by one commit and downstream persistence
        # only ever sees host-resident state (exactly-once discipline)
        _dres.decay_resident_batches()
        handles = _device.stage_device_batches()
        if not handles and not self._held:
            # nothing of this commit's on the device, and no sink's work
            # with the worker that the in-flight bound would have to count
            return
        if not async_enabled():
            for handle in handles:
                handle.decay()
            return
        self._raise_pending()
        with _tracing.detail(
            "commit.device_stage", cat="pipeline", batches=len(handles)
        ):
            t0 = _time.perf_counter()
            for handle in handles:
                handle.prefetch()  # start the DMA; never await it here
            self._ensure_worker()
            blocked = False
            with self._cv:
                # what this commit's own sinks handed over is part of it
                while self._in_flight_locked(but=time) >= self.controller.depth:
                    blocked = True
                    # genuine pipeline stall: host blocked on the device
                    # stage — attributed to the queue_wait bucket
                    with _tracing.stage(
                        "commit.device_wait", cat="wait", wait=True
                    ):
                        self._cv.wait(timeout=60.0)
                    err = self._take_error_locked()
                    if err is not None:
                        raise err
                if handles:
                    self._staged.append((int(time), handles, t0, None, None))
                self._g_depth.value = float(self._in_flight_locked())
                self._cv.notify_all()
                staged_depth = len(self._staged_times_locked())
                occupancy = self._occupancy
            self.controller.observe(
                staged_depth=staged_depth, blocked=blocked, occupancy=occupancy
            )

    # -- completion side (worker thread) -------------------------------------

    def _run_completions(self) -> None:
        while True:
            with self._cv:
                # bounded wait + stop flag: an untimed wait here would
                # strand the daemon at shutdown if the final notify races
                # the run teardown
                while not self._staged:
                    if self._stop:
                        return
                    self._cv.wait(timeout=0.5)
                time_, handles, t_dispatch, sink, work = self._staged.popleft()
                self._active_time = time_
                self._g_depth.value = float(self._in_flight_locked())
                failed = self._error is not None
                self._cv.notify_all()
            t0 = _time.perf_counter()
            err: BaseException | None = None
            try:
                if sink is None:
                    for handle in handles:
                        handle.decay()
                elif not failed:
                    # after a failure the run is coming down, and nothing
                    # more is delivered, as inline
                    work()
            except BaseException as e:  # noqa: BLE001 — surfaced on main thread
                err = e
            t1 = _time.perf_counter()
            with self._cv:
                self._busy_s += t1 - t0
                mark = self._occ_mark
                self._occ_mark = t1
                if mark is not None and t1 > mark:
                    ratio = min(1.0, (t1 - t0) / (t1 - mark))
                    self._occupancy = (
                        0.8 * self._occupancy + 0.2 * ratio
                    )
                    self._g_occ.value = round(self._occupancy, 4)
                self._completed_time = time_
                self._active_time = None
                self._g_depth.value = float(self._in_flight_locked())
                if sink is None:
                    self._h_latency.observe(max(0.0, t1 - t_dispatch))
                    self._c_commits.inc()
                elif self._held[sink] > 1:
                    self._held[sink] -= 1
                else:
                    del self._held[sink]
                if err is not None and self._error is None:
                    self._error = err
                self._cv.notify_all()

    # -- barriers (runner thread) --------------------------------------------

    def drain_until(self, time: int) -> None:
        """Block until every staged commit at or before ``time`` has
        completed — THE exactly-once seam: the runner calls this before
        persistence/snapshot ``on_commit`` hooks so a checkpoint for
        commit N is only cut once N's device effects are host-resident."""
        from pathway_tpu.engine import device_residency as _dres

        _dres.decay_resident_batches()
        if self._worker is None:
            return
        with _tracing.stage("commit.device_wait", wait=True), self._cv:
            while (self._staged and self._staged[0][0] <= time) or (
                self._active_time is not None and self._active_time <= time
            ):
                self._cv.wait(timeout=60.0)
        self._raise_pending()

    def drain(self) -> None:
        """Complete everything in flight (run end, pre-snapshot, tests)."""
        from pathway_tpu.engine import device_residency as _dres

        _dres.decay_resident_batches()
        if self._worker is None:
            return
        with _tracing.stage("commit.device_wait", wait=True), self._cv:
            while self._staged or self._active_time is not None:
                self._cv.wait(timeout=60.0)
        self._raise_pending()

    def reset(self) -> None:
        """Recovery path: the in-flight commits belong to a timeline a
        snapshot rollback un-happens.  Completing them is still correct
        (decay only frees HBM and fills host twins) — so drain, then
        drop any queued error: the rolled-back timeline re-derives."""
        try:
            self.drain()
        except BaseException:  # noqa: BLE001 — rolled-back work may not raise
            pass
        with self._cv:
            self._error = None
            self._completed_time = -1

    # -- read side -----------------------------------------------------------

    def inflight(self) -> int:
        with self._cv:
            return self._in_flight_locked()

    def completed_time(self) -> int:
        return self._completed_time

    def occupancy(self) -> float:
        return self._occupancy

    def stats(self) -> dict:
        """Structured roll-up for bench JSON."""
        from pathway_tpu.engine import collective_exchange as _collective
        from pathway_tpu.engine import device_ops as _dops
        from pathway_tpu.engine import device_residency as _dres

        return {
            "enabled": async_enabled(),
            "inflight": self.inflight(),
            "completed_commits": int(self._c_commits.value),
            "occupancy_ratio": round(self._occupancy, 4),
            "dispatch_complete_p50_ms": round(
                self._h_latency.quantile(0.5) * 1000.0, 3
            ),
            "dispatch_complete_p99_ms": round(
                self._h_latency.quantile(0.99) * 1000.0, 3
            ),
            "controller": self.controller.stats(),
            # the device-resident operator kernels share the pipe's
            # device: their launch volume belongs in the same roll-up
            "device_ops": {
                "enabled": _dops.enabled(),
                "hit_counts": _dops.hit_counts(),
            },
            # the collective exchange dispatches through the same device
            # (its all-to-all launches overlap host work the way staged
            # commits do) — surface its engagement next to the pipe's
            "collective_exchange": {
                "enabled": _collective.enabled(),
                "events": dict(_collective.COLLECTIVE_STATS),
            },
            # the residency plane keeps exchange outputs on that same
            # device between operators — its transfer ledger belongs
            # beside the planes that produce and consume the buffers
            "device_residency": _dres.stats(),
        }


#: the process-wide pipeline every scheduler's commit boundary feeds
PIPELINE = DevicePipeline()


def commit_boundary(time: int) -> None:
    PIPELINE.commit_boundary(time)


def holds(sink) -> bool:
    return PIPELINE.holds(sink)


def hand_over(time: int, sink, work, handles=()) -> None:
    PIPELINE.hand_over(time, sink, work, handles)


def drain() -> None:
    PIPELINE.drain()


def drain_until(time: int) -> None:
    PIPELINE.drain_until(time)


def stop_worker() -> None:
    PIPELINE.stop_worker()


def reset() -> None:
    PIPELINE.reset()


def ingest_window_scale() -> float:
    """Multiplier for connector autocommit windows (1.0 when the
    pipeline is off or idle).  Only a congested device stage widens the
    window — host-only programs never see a changed commit cadence."""
    if not async_enabled() or PIPELINE.inflight() == 0:
        return 1.0
    return PIPELINE.controller.window_scale
