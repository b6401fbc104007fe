"""Async device pipeline (engine/device_pipeline.py): the double-buffered
commit staging/completion queues, the ingest window controller, the
``PATHWAY_TPU_ASYNC_DEVICE`` escape hatch, and the batch executor's step.

The synchronous inline-decay boundary is the bit-exact spec: every parity
test here runs the same program with the pipeline on and off and asserts
bit-identical sink events on the single-worker, sharded in-process, and
TCP-mesh schedulers — plus one chaos run where a worker is SIGKILLed
mid-flight with commits staged, and recovery still converges to the
fault-free sink.  tools/check.py additionally reruns this whole file
under ``PATHWAY_TPU_ASYNC_DEVICE=0`` (the async-parity gate).
"""

from __future__ import annotations

import csv
import json
import os
import socket
import sys
import textwrap
import threading
import time

import numpy as np
import pytest

import pathway_tpu as pw
from pathway_tpu.engine import device as dev_mod
from pathway_tpu.engine import device_pipeline as dp
from pathway_tpu.engine import expression as ex
from pathway_tpu.engine.graph import Scheduler, Scope
from pathway_tpu.engine.sharded import ShardedScheduler
from pathway_tpu.engine.value import Pointer, ref_scalar
from pathway_tpu.internals import tracing
from pathway_tpu.internals.udfs import batch_executor

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_pipeline():
    """The pipeline is a process-wide singleton: drain and reset it around
    every test so staged work / queued errors never leak across tests."""
    dev_mod._LIVE_HANDLES.clear()
    dp.PIPELINE.configure()
    yield
    dev_mod._LIVE_HANDLES.clear()
    dp.PIPELINE.configure()


@pytest.fixture
def async_on(monkeypatch):
    """Tests asserting that deferral HAPPENS must see the pipeline enabled
    even when the ambient environment disables it (the tools/check.py
    async-parity leg reruns this file with PATHWAY_TPU_ASYNC_DEVICE=0;
    parity tests pass either way, but these would vacuously fail)."""
    monkeypatch.setenv("PATHWAY_TPU_ASYNC_DEVICE", "1")


class _GatedDev:
    """A fake device array: ``__array__`` (the D2H download) blocks on an
    event and logs its tag, so tests can hold a commit's completion open
    and observe ordering."""

    def __init__(self, arr, gate=None, log=None, tag=None, fail=None):
        self._arr = np.asarray(arr)
        self._gate = gate
        self._log = log
        self._tag = tag
        self._fail = fail
        self.shape = self._arr.shape
        self.dtype = self._arr.dtype

    def is_ready(self):
        """What ``jax.Array.is_ready`` says: the chip has produced it."""
        return self._gate is None or self._gate.is_set()

    def __array__(self, dtype=None, copy=None):
        if self._gate is not None and not self._gate.wait(timeout=30):
            raise TimeoutError("test gate never opened")
        if self._fail is not None:
            raise self._fail
        if self._log is not None:
            self._log.append(self._tag)
        out = self._arr if dtype is None else self._arr.astype(dtype)
        return np.array(out, copy=True) if copy else out


def _wait_for(pred, timeout=10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.005)
    return False


# -- unit: staging / completion ------------------------------------------------


class TestPipelineUnit:
    def test_sync_mode_decays_inline(self, monkeypatch):
        monkeypatch.setenv("PATHWAY_TPU_ASYNC_DEVICE", "0")
        handle = dev_mod.DeviceBatchHandle(np.ones((4, 2), np.float32))
        dp.commit_boundary(1)
        assert handle.dev is None  # decayed before the boundary returned
        assert handle.host().shape == (4, 2)
        assert dp.PIPELINE.inflight() == 0

    def test_async_defers_completion_until_drain(self, async_on):
        gate = threading.Event()
        handle = dev_mod.DeviceBatchHandle(
            _GatedDev(np.full((3, 2), 7.0, np.float32), gate=gate)
        )
        dp.commit_boundary(1)
        # boundary returned while the download is still gated open
        assert handle.dev is not None
        assert dp.PIPELINE.inflight() == 1
        gate.set()
        dp.drain()
        assert handle.dev is None
        assert handle.host()[0, 0] == 7.0
        assert dp.PIPELINE.inflight() == 0

    def test_completion_is_fifo_across_commits(self, async_on):
        log: list = []
        gate1 = threading.Event()
        open_gate = threading.Event()
        open_gate.set()
        h1 = dev_mod.DeviceBatchHandle(
            _GatedDev(np.zeros((1, 1)), gate=gate1, log=log, tag="a")
        )
        dp.commit_boundary(1)
        h2 = dev_mod.DeviceBatchHandle(
            _GatedDev(np.zeros((1, 1)), gate=open_gate, log=log, tag="b")
        )
        dp.commit_boundary(2)
        assert log == []  # commit 2 may not complete before commit 1
        gate1.set()
        dp.drain()
        assert log == ["a", "b"]
        assert dp.PIPELINE.completed_time() == 2
        assert h1.dev is None and h2.dev is None

    def test_backpressure_bounds_inflight_to_depth(self, async_on):
        gate = threading.Event()
        handles = []
        for t in (1, 2):
            handles.append(
                dev_mod.DeviceBatchHandle(
                    _GatedDev(np.zeros((1, 1)), gate=gate)
                )
            )
            dp.commit_boundary(t)
        assert dp.PIPELINE.inflight() == 2  # depth default: double buffer

        h3 = dev_mod.DeviceBatchHandle(_GatedDev(np.zeros((1, 1)), gate=gate))
        handles.append(h3)
        third = threading.Thread(target=dp.commit_boundary, args=(3,))
        third.start()
        time.sleep(0.25)
        assert third.is_alive()  # staging commit 3 blocked on the bound
        gate.set()
        third.join(timeout=30)
        assert not third.is_alive()
        dp.drain()
        assert all(h.dev is None for h in handles)
        # the blocked staging fed the controller's grow rule
        assert dp.PIPELINE.controller.grows >= 1

    def test_worker_error_surfaces_on_drain(self, async_on):
        boom = RuntimeError("DMA exploded")
        bad = dev_mod.DeviceBatchHandle(_GatedDev(np.zeros((1, 1)), fail=boom))
        dp.commit_boundary(1)
        with pytest.raises(RuntimeError, match="DMA exploded"):
            dp.drain()
        # the error is consumed: the pipeline is usable again
        ok = dev_mod.DeviceBatchHandle(np.zeros((2, 2), np.float32))
        dp.commit_boundary(2)
        dp.drain()
        assert bad.dev is not None and ok.dev is None

    def test_reset_clears_pending_error(self, async_on):
        doomed = dev_mod.DeviceBatchHandle(
            _GatedDev(np.zeros((1, 1)), fail=RuntimeError("rolled back"))
        )
        dp.commit_boundary(1)
        assert doomed.dev is not None  # strong ref held past the boundary
        assert _wait_for(lambda: dp.PIPELINE.inflight() == 0)
        dp.reset()  # recovery path: rolled-back timeline must not raise
        dp.drain()
        assert dp.PIPELINE.completed_time() == -1

    def test_drain_until_is_a_partial_barrier(self, async_on):
        gate = threading.Event()
        held = dev_mod.DeviceBatchHandle(
            _GatedDev(np.zeros((1, 1)), gate=gate)
        )
        dp.commit_boundary(5)
        t0 = time.monotonic()
        dp.drain_until(4)  # nothing at or before 4: returns immediately
        assert time.monotonic() - t0 < 5.0
        assert dp.PIPELINE.inflight() == 1
        gate.set()
        dp.drain_until(5)
        assert dp.PIPELINE.inflight() == 0
        assert held.dev is None

    def test_metrics_and_stats_populate(self, async_on):
        commits_before = dp.PIPELINE._c_commits.value
        hist_before = dp.PIPELINE._h_latency.count
        held = []
        for t in (1, 2):
            held.append(
                dev_mod.DeviceBatchHandle(np.zeros((8, 4), np.float32))
            )
            dp.commit_boundary(t)
        dp.drain()
        assert dp.PIPELINE._c_commits.value == commits_before + 2
        assert dp.PIPELINE._h_latency.count == hist_before + 2
        assert dp.PIPELINE._g_depth.value == 0.0
        stats = dp.PIPELINE.stats()
        assert stats["enabled"] and stats["inflight"] == 0
        assert stats["dispatch_complete_p99_ms"] >= 0.0
        assert set(stats["controller"]) == {
            "depth", "window_scale", "ticks", "grows"
        }

    def test_host_only_commit_is_free(self, async_on):
        commits_before = dp.PIPELINE._c_commits.value
        dp.commit_boundary(1)  # no live handles: no staging, no worker
        assert dp.PIPELINE.inflight() == 0
        assert dp.PIPELINE._c_commits.value == commits_before

    def test_window_scale_is_unity_when_idle(self, async_on):
        dp.PIPELINE.controller.window_scale = 3.0
        assert dp.ingest_window_scale() == 1.0  # nothing in flight


# -- unit: worker shutdown -----------------------------------------------------


class TestWorkerShutdown:
    def test_stop_worker_reaps_daemon(self):
        dp.PIPELINE._ensure_worker()
        w = dp.PIPELINE._worker
        assert w is not None and w.is_alive()
        dp.PIPELINE.stop_worker()
        assert not w.is_alive()
        assert dp.PIPELINE._worker is None
        # next use respawns a fresh worker
        dp.PIPELINE._ensure_worker()
        assert dp.PIPELINE._worker.is_alive()
        dp.PIPELINE.stop_worker()

    def test_raising_run_leaves_no_leaked_threads(self, monkeypatch):
        from pathway_tpu.internals.parse_graph import G

        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        monkeypatch.setenv("PATHWAY_TPU_SERVING", "1")
        monkeypatch.setenv("PATHWAY_TPU_SERVING_PORT_BASE", str(port))
        G.clear()
        t = pw.debug.table_from_rows(
            pw.schema_from_types(x=int), [(1,), (2,)]
        )

        def boom(*a, **k):
            raise RuntimeError("sink boom")

        pw.io.subscribe(t, on_change=boom)
        # a live completion worker going INTO the raising run: the
        # teardown in pw.run must reap it along with the serving pool
        dp.PIPELINE._ensure_worker()
        with pytest.raises(RuntimeError, match="sink boom"):
            pw.run(monitoring_level=None)

        def leaked():
            return [
                th.name
                for th in threading.enumerate()
                if th.is_alive()
                and th.name.startswith(("pw-device-pipeline", "pw-serving"))
            ]

        deadline = time.monotonic() + 5.0
        while leaked() and time.monotonic() < deadline:
            time.sleep(0.05)
        assert leaked() == [], f"daemons survived the run: {leaked()}"


# -- unit: ingest window controller ---------------------------------------------


class TestWindowController:
    def test_window_grows_and_clamps_on_saturation(self):
        c = dp.IngestWindowController()
        c.observe(staged_depth=0, blocked=True, occupancy=1.0)
        assert c.window_scale == pytest.approx(1.25) and c.grows == 1
        for _ in range(30):
            c.observe(staged_depth=c.depth, blocked=False, occupancy=1.0)
        assert c.window_scale == 4.0 and c.grows == 31

    def test_window_relaxes_to_unity_when_device_starved(self):
        c = dp.IngestWindowController()
        c.window_scale = 4.0
        c.observe(staged_depth=0, blocked=False, occupancy=0.0)
        assert c.window_scale == pytest.approx(3.2)
        for _ in range(30):
            c.observe(staged_depth=0, blocked=False, occupancy=0.0)
        assert c.window_scale == 1.0 and c.grows == 0 and c.ticks == 31

    def test_busy_midband_holds_steady(self):
        c = dp.IngestWindowController()
        c.window_scale = 2.0
        c.observe(staged_depth=0, blocked=False, occupancy=0.6)
        assert c.window_scale == 2.0 and c.grows == 0 and c.ticks == 1

    def test_inflight_bound_read_from_env(self, monkeypatch):
        monkeypatch.setenv("PATHWAY_TPU_DEVICE_INFLIGHT", "3")
        c = dp.IngestWindowController()
        assert c.depth == 3 and c.stats()["depth"] == 3
        c.observe(staged_depth=2, blocked=False, occupancy=1.0)
        assert c.grows == 0  # two staged is under the bound of three
        c.observe(staged_depth=3, blocked=False, occupancy=1.0)
        assert c.grows == 1 and c.window_scale == pytest.approx(1.25)


# -- unit: executor step --------------------------------------------------------


def _idle_ticks(n=10):
    """What a starved device looks like to the controller, ``n`` commits
    long (it once halved the embed step at each)."""
    for _ in range(n):
        dp.PIPELINE.controller.observe(
            staged_depth=0, blocked=False, occupancy=0.0
        )


def _embed_real_rows(n_rows, max_batch_size=256):
    """Real (unpadded) rows of each jitted step a ``TpuEncoderEmbedder``
    makes for one commit of ``n_rows`` texts (the encoder itself stubbed)."""
    from pathway_tpu.xpacks.llm.embedders import TpuEncoderEmbedder

    emb = TpuEncoderEmbedder(
        "minilm_l6", max_len=16, max_batch_size=max_batch_size,
        device_resident=False,
    )
    dim = emb.get_embedding_dimension()
    real = []

    def step(ids, mask=None):
        ids = np.asarray(ids)
        real.append(int((ids != 0).any(axis=1).sum()))
        return np.zeros((len(ids), dim), np.float32)

    emb._jit_embed_ids = emb._jit_embed = step
    out = emb._executor.run(emb._fn, [(f"w{i} w{i + 1}",) for i in range(n_rows)])
    assert len(out) == n_rows and all(ok for ok, _ in out)
    return real


class TestExecutorStep:
    @staticmethod
    def _chunks(executor, n_rows=8):
        sizes = []

        def fn(xs):
            sizes.append(len(xs))
            return xs

        out = executor.run(fn, [(i,) for i in range(n_rows)])
        assert [v for ok, v in out] == list(range(n_rows))
        return sizes

    @pytest.mark.parametrize(
        "cap,n_rows,expected",
        [
            (4, 8, [4, 4]),
            (4, 10, [4, 4, 2]),
            (256, 2030, [256] * 7 + [238]),
            (64, 3, [3]),  # a commit under the cap is one chunk
        ],
    )
    def test_chunks_hold_the_cap_and_a_shorter_tail(self, cap, n_rows, expected):
        sizes = self._chunks(batch_executor(max_batch_size=cap), n_rows)
        assert sizes == expected

    def test_no_cap_means_one_chunk(self):
        assert self._chunks(batch_executor(), n_rows=2030) == [2030]

    def test_idle_device_does_not_narrow_the_step(self, async_on):
        _idle_ticks()
        sizes = self._chunks(batch_executor(max_batch_size=8), n_rows=20)
        assert sizes == [8, 8, 4]

    @pytest.mark.parametrize(
        "cap,calls,narrowed",
        [(4, 3, 1), (None, 1, 0)],  # 2.5 caps: the tail is short; no cap: never
    )
    def test_stage_counts_rows_and_short_chunks(self, cap, calls, narrowed):
        root = tracing.STAGES.begin_run()
        try:
            self._chunks(batch_executor(max_batch_size=cap), n_rows=10)
        finally:
            tracing.STAGES.end_run(root)
        row = tracing.stage_totals()["stages"]["udf.batch"]
        assert row["calls"] == calls
        assert row["counts"] == {"rows": 10, "narrowed": narrowed}

    def test_embedder_steps_at_its_cap_after_idle_ticks(self, async_on):
        _idle_ticks()
        assert _embed_real_rows(600) == [256, 256, 88]

    def test_embedder_chunking_ignores_tracing(self, async_on):
        assert not tracing.TRACER.enabled
        _idle_ticks()
        off = _embed_real_rows(150, max_batch_size=64)
        tracing.TRACER.configure(enabled=True, sample=1, clear=True)
        try:
            _idle_ticks()
            on = _embed_real_rows(150, max_batch_size=64)
        finally:
            tracing.TRACER.drop()
            tracing.TRACER.configure(enabled=False, clear=True)
            tracing.TRACER.epoch = 0
        assert off == on == [64, 64, 22]


# -- critical-path shares (tracing satellite) ---------------------------------


def test_critical_path_reports_bucket_shares():
    origin = 1000.0
    trace = {
        "origin_wall": origin,
        "begin_wall": origin + 0.010,
        "end_wall": origin + 0.100,
        "device_s": 0.005,
        "spans": [
            {"name": "recv-wait:p1", "cat": "wait",
             "ts": int((origin + 0.02) * 1e6), "dur": 20_000, "pid": 0},
            {"name": "pwcf-encode", "cat": "exchange",
             "ts": int((origin + 0.05) * 1e6), "dur": 30_000, "pid": 0},
        ],
    }
    cp = tracing.critical_path(trace)
    shares = cp["shares"]
    assert set(shares) == {"host_compute", "exchange", "queue_wait", "device"}
    assert shares["exchange"] == pytest.approx(0.30, abs=0.01)
    assert shares["device"] == pytest.approx(0.05, abs=0.01)
    assert sum(shares.values()) == pytest.approx(1.0, abs=0.05)


# -- parity: single-worker scheduler ------------------------------------------


def _embed_rows(arg_rows):
    """Batch UDF body: fake device embed — stacks args into a [n, 2]
    'device' matrix and hands back lazy per-row cells, exactly the shape
    the real embedder produces (device.lazy_rows registers the batch in
    _LIVE_HANDLES for the commit boundary to stage)."""
    mat = np.asarray(
        [[float(a[0]), float(a[1]) * 2.0] for a in arg_rows], np.float32
    )
    return [(True, c) for c in dev_mod.lazy_rows(mat, len(arg_rows))]


def _host_row(row):
    """Materialise any lazy device cell — the canonical sink form."""
    return tuple(
        tuple(float(x) for x in np.asarray(c))
        if isinstance(c, dev_mod.LazyDeviceVector)
        else c
        for c in row
    )


def _run_device_chain(n_commits=3, per=80):
    events: list = []
    sc = Scope()
    sess = sc.input_session(2)
    ba = sc.batch_apply_table(sess, _embed_rows, [0, 1])
    sc.subscribe_table(
        ba,
        on_change=lambda k, row, t, d: events.append(
            (int(k), _host_row(row), t, d)
        ),
    )
    sched = Scheduler(sc)
    for commit in range(n_commits):
        for i in range(per):
            key = commit * per + i
            sess.insert(ref_scalar(key), (key, float(i) * 0.5))
        sched.commit()
    # retraction + replacement commit (exercises the memoized-deletion path)
    for i in range(10):
        sess.remove(ref_scalar(i), (i, float(i) * 0.5))
        sess.insert(ref_scalar(i), (i, float(i) * 0.5 + 9.0))
    sched.commit()
    dp.drain()
    state = {int(k): _host_row(row) for k, row in ba.current.items()}
    return sorted(events, key=repr), state


def test_scheduler_parity_async_on_off(monkeypatch):
    monkeypatch.setenv("PATHWAY_TPU_ASYNC_DEVICE", "0")
    dp.PIPELINE.configure()
    ev_off, state_off = _run_device_chain()
    monkeypatch.setenv("PATHWAY_TPU_ASYNC_DEVICE", "1")
    dp.PIPELINE.configure()
    before = dp.PIPELINE._c_commits.value
    ev_on, state_on = _run_device_chain()
    assert dp.PIPELINE._c_commits.value > before  # async path was exercised
    assert ev_off == ev_on
    assert state_off == state_on
    assert ev_on  # non-vacuous


def test_scheduler_boundary_decays_inline_in_sync_mode(monkeypatch):
    """The scheduler's commit boundary routes through the pipeline: under
    the escape hatch the handle is host-resident the moment commit()
    returns, bit-identical to the pre-pipeline engine."""
    monkeypatch.setenv("PATHWAY_TPU_ASYNC_DEVICE", "0")
    captured: list = []
    orig = dev_mod.lazy_rows

    def capture_lazy_rows(mat, n, prefetch=True):
        cells = orig(mat, n, prefetch)
        captured.append(cells[0].batch)
        return cells

    monkeypatch.setattr(dev_mod, "lazy_rows", capture_lazy_rows)
    sc = Scope()
    sess = sc.input_session(2)
    sc.batch_apply_table(sess, _embed_rows, [0, 1])
    sched = Scheduler(sc)
    sess.insert(ref_scalar(1), (1, 2.0))
    sched.commit()
    assert captured and all(h.dev is None for h in captured)


# -- a sink over device rows: all three schedulers, async against =0 -----------

SCHEDULERS = ("single", "sharded", "mesh")
WORKER = "pw-device-pipeline"


class _SinkRig:
    """session -> a fake device embed -> sinks, under one of the three
    schedulers (two replicas under ``sharded`` and ``mesh``, the sinks on
    worker 0 as the runners attach them). ``gate`` is what the embed's next
    device batches wait for (None: a host array stands in for the device's,
    ready at once). Every callback is logged with its thread."""

    def __init__(self, kind, on_change=None):
        from pathway_tpu.engine import distributed as dist

        self.gate = None
        self.log: list = []
        self._inner = on_change
        self._transport = None
        scopes, self.sessions, embedded = [], [], []
        for _w in range(1 if kind == "single" else 2):
            sc = Scope()
            sess = sc.input_session(2)
            embedded.append(sc.batch_apply_table(sess, self._embed, [0, 1]))
            scopes.append(sc)
            self.sessions.append(sess)
        n_shared = len(scopes[0].nodes)
        self.scope, self.session, self.embedded = (
            scopes[0], self.sessions[0], embedded[0]
        )
        self.sink = scopes[0].subscribe_table(
            embedded[0],
            on_change=self._on_change,
            on_time_end=lambda t: self._note("end", t),
            on_end=lambda: self._note("closed"),
        )
        #: a sink of host rows alone, in the same commits
        self.host_sink = scopes[0].subscribe_table(
            self.session,
            on_change=lambda k, row, t, d: self._note("host", int(k), t, d),
        )
        if kind == "single":
            self.sched = Scheduler(scopes[0])
        elif kind == "sharded":
            self.sched = ShardedScheduler(scopes)
        else:
            self._transport = dist.MeshTransport(
                0, 1, addresses=[("127.0.0.1", 0)]
            )
            self.sched = dist.DistributedScheduler(
                scopes, 0, 1, self._transport, n_shared=n_shared
            )
            self.sched.announce_topology()

    def _embed(self, arg_rows):
        mat = np.asarray(
            [[float(a[0]), float(a[1]) * 2.0] for a in arg_rows], np.float32
        )
        dev = mat if self.gate is None else _GatedDev(mat, gate=self.gate)
        return [(True, c) for c in dev_mod.lazy_rows(dev, len(arg_rows))]

    def _note(self, *event):
        self.log.append((*event, threading.current_thread().name))

    def _on_change(self, key, row, time, diff):
        if self._inner is not None:
            self._inner(key, row, time, diff)
        self._note("row", int(key), _host_row(row), time, diff)

    def commit(self, keys, gate=None):
        self.gate = gate
        for key in keys:
            self.session.insert(ref_scalar(key), (key, float(key) * 0.5))
        return self.sched.commit()

    def events(self, *kinds):
        """The log without the threads, of the kinds asked for."""
        return [e[:-1] for e in self.log if e[0] in kinds]

    def threads(self, kind):
        return {e[-1] for e in self.log if e[0] == kind}

    def close(self):
        if self._transport is not None:
            self._transport.close()


@pytest.fixture
def rig(request):
    made = []

    def make(kind, **kwargs):
        made.append(_SinkRig(kind, **kwargs))
        return made[-1]

    yield make
    for r in made:
        r.close()


def _opens_soon(delay=0.15):
    """A gate a timer opens: a device batch that takes ``delay`` seconds."""
    gate = threading.Event()
    timer = threading.Timer(delay, gate.set)
    timer.daemon = True
    timer.start()
    return gate


def _run_sink_program(rig, kind):
    """Three commits over slow device batches and one of retractions, then
    the end: everything the embedded table's sink was told, in order."""
    r = rig(kind)
    for commit in range(3):
        r.commit(range(commit * 40, commit * 40 + 40), gate=_opens_soon(0.05))
    r.gate = _opens_soon(0.05)
    for i in range(10):
        r.session.remove(ref_scalar(i), (i, float(i) * 0.5))
        r.session.insert(ref_scalar(i), (i, float(i) * 0.5 + 9.0))
    r.sched.commit()
    r.sched.finish()
    return r


@pytest.mark.parametrize("kind", SCHEDULERS)
def test_sink_over_device_rows_same_rows_same_order(kind, rig, monkeypatch):
    """The asynchronous sink hands its rows to the worker; what the
    callbacks see, and in which order, is the inline spec's."""
    monkeypatch.setenv("PATHWAY_TPU_ASYNC_DEVICE", "0")
    dp.PIPELINE.configure()
    off = _run_sink_program(rig, kind)
    assert off.threads("row") == {threading.current_thread().name}
    monkeypatch.setenv("PATHWAY_TPU_ASYNC_DEVICE", "1")
    dp.PIPELINE.configure()
    on = _run_sink_program(rig, kind)
    assert WORKER in on.threads("row")  # the hand-over was exercised
    kinds = ("row", "end", "closed")
    assert on.events(*kinds) == off.events(*kinds)
    assert on.events("host") == off.events("host")
    assert len(on.events("row")) == 140 and on.events("closed")


@pytest.mark.parametrize("kind", SCHEDULERS)
def test_sink_keeps_its_order_behind_a_slow_batch(kind, rig, async_on):
    """Rows of t, ``on_time_end(t)``, rows of t + 1: a later commit whose
    rows are ready waits its turn behind the emission still with the
    worker, and nothing is delivered before the slow batch is down."""
    r = rig(kind)
    gate = threading.Event()
    t0 = r.commit(range(8), gate=gate)
    t1 = r.commit(range(8, 12))  # ready rows, behind the pending emission
    assert r.events("row", "end") == []
    assert dp.PIPELINE.inflight() == 2
    gate.set()
    dp.drain()
    got = r.events("row", "end")
    assert [e[0] for e in got] == ["row"] * 8 + ["end"] + ["row"] * 4 + ["end"]
    assert [e[3] for e in got if e[0] == "row"] == [t0] * 8 + [t1] * 4
    assert [e[1] for e in got if e[0] == "end"] == [t0, t1]
    assert r.threads("row") == r.threads("end") == {WORKER}
    # the next commit finds nothing of this sink with the worker
    r.commit(range(12, 16))
    assert r.log[-1][0] == "end" and r.log[-1][-1] != WORKER
    assert {e[-1] for e in r.log if e[0] == "row" and e[3] > t1} == {
        threading.current_thread().name
    }


@pytest.mark.parametrize("kind", SCHEDULERS)
def test_ready_and_host_rows_stay_on_the_run_thread(kind, rig, async_on):
    """A sink leaves the run thread only for a device batch the chip has
    not finished: ready device rows are emitted inline, and so are the
    host rows of another sink in a commit that holds a slow batch."""
    me = threading.current_thread().name
    r = rig(kind)
    r.commit(range(20))  # every device batch ready
    assert r.threads("row") == r.threads("host") == r.threads("end") == {me}
    assert not dp.holds(r.sink)
    r.commit(range(20, 40), gate=_opens_soon())
    dp.drain()
    assert r.threads("host") == {me}
    assert WORKER in r.threads("row")
    assert len(r.events("row")) == len(r.events("host")) == 40


@pytest.mark.parametrize("kind", SCHEDULERS)
def test_failing_on_change_on_the_worker_fails_the_run_thread(
    kind, rig, async_on
):
    def boom(key, row, time, diff):
        if threading.current_thread().name == WORKER:
            raise RuntimeError("sink boom on the worker")

    r = rig(kind, on_change=boom)
    gate = threading.Event()
    r.commit(range(6), gate=gate)
    gate.set()
    with pytest.raises(RuntimeError, match="sink boom on the worker"):
        r.sched.finish()
    # nothing was delivered behind the failure, and the pipeline is usable
    assert r.events("row") == [] and r.events("closed") == []
    assert not dp.holds(r.sink)
    dp.PIPELINE.configure()
    ok = rig(kind)
    ok.commit(range(4), gate=_opens_soon(0.02))
    ok.sched.finish()
    assert len(ok.events("row")) == 4


@pytest.mark.parametrize("kind", SCHEDULERS)
def test_commit_seams_see_every_row_delivered(kind, rig, async_on):
    """The journal's ``on_commit(t)`` (behind ``drain_until(t)`` in
    ``_after_commit``) and the user's ``on_end`` come after every row of
    their commit was handed to its callback."""
    from pathway_tpu.internals import runner

    r = rig(kind)
    seen = {}

    class Journal:
        def on_commit(self, time):
            seen[time] = r.events("row", "end")

    t0 = r.commit(range(16), gate=_opens_soon())
    runner._after_commit(t0, r.sched.scopes, [], persistent=[Journal()])
    assert [e[0] for e in seen[t0]] == ["row"] * 16 + ["end"]
    assert all(e[3] == t0 for e in seen[t0] if e[0] == "row")
    r.commit(range(16, 24), gate=_opens_soon())
    r.sched.finish()
    assert r.log[-1][0] == "closed"
    assert len(r.events("row")) == 24


@pytest.mark.parametrize("kind", SCHEDULERS)
def test_deferred_rows_are_counted_where_they_were_handed_over(
    kind, rig, async_on
):
    """``sink.emit`` on the run thread: ``rows`` is what it emitted itself,
    ``deferred_rows`` what it left to the worker, whose own ``sink.emit``
    stage counts those as its ``rows``; the time line's record takes the
    stage like any other."""
    me = threading.current_thread().name
    root = tracing.STAGES.begin_run()
    try:
        r = rig(kind)
        with tracing.commit_stage() as commit:
            commit.time = r.commit(range(10))  # ready: emitted inline
        with tracing.commit_stage() as commit:
            commit.time = r.commit(range(10, 40), gate=_opens_soon())
        dp.drain()
    finally:
        tracing.STAGES.end_run(root)
    inline = sum(1 for e in r.log if e[0] == "row" and e[-1] == me)
    handed = sum(1 for e in r.log if e[0] == "row" and e[-1] == WORKER)
    assert inline + handed == 40 and handed >= 15
    totals = tracing.stage_totals()
    counts = totals["stages"]["sink.emit"]["counts"]
    assert counts["deferred_rows"] == handed
    # the embedded table's rows emitted here, and the host sink's 40
    assert counts["rows"] == inline + 40
    worker = totals["threads"][WORKER]["sink.emit"]
    assert worker["counts"] == {"rows": handed}
    assert all("sink.emit" in rec["stages"] for rec in tracing.commit_timeline())


def test_sync_mode_never_hands_over(rig, monkeypatch):
    """``PATHWAY_TPU_ASYNC_DEVICE=0``: a slow batch holds the run thread,
    as it always did, and no count of deferred rows appears."""
    monkeypatch.setenv("PATHWAY_TPU_ASYNC_DEVICE", "0")
    root = tracing.STAGES.begin_run()
    try:
        r = rig("single")
        r.commit(range(12), gate=_opens_soon(0.05))
    finally:
        tracing.STAGES.end_run(root)
    assert r.threads("row") == {threading.current_thread().name}
    assert dp.PIPELINE._worker is None or not dp.PIPELINE._staged
    counts = tracing.stage_totals()["stages"]["sink.emit"]["counts"]
    assert counts == {"rows": 24}


def test_a_batch_is_cut_at_the_first_row_that_would_wait(async_on):
    """Rows that are ready go out on the run thread; from the first one of
    an unfinished batch on, the rest of the sink's batch is the worker's,
    in the batch's order."""
    gate = threading.Event()
    log: list = []

    def embed(arg_rows):
        mat = np.asarray([[float(a[0]), 0.0] for a in arg_rows], np.float32)
        half = len(arg_rows) // 2
        cells = dev_mod.lazy_rows(mat[:half], half)
        cells += dev_mod.lazy_rows(
            _GatedDev(mat[half:], gate=gate), len(arg_rows) - half
        )
        return [(True, c) for c in cells]

    sc = Scope()
    sess = sc.input_session(2)
    ba = sc.batch_apply_table(sess, embed, [0, 1])
    sc.subscribe_table(
        ba,
        on_change=lambda k, row, t, d: log.append(
            (float(np.asarray(row[-1])[0]), threading.current_thread().name)
        ),
    )
    sched = Scheduler(sc)
    for key in range(10):
        sess.insert(ref_scalar(key), (key, 0.0))
    sched.commit()
    inline = [v for v, th in log]
    assert inline and all(th != WORKER for _v, th in log)
    gate.set()
    dp.drain()
    assert len(log) == 10
    assert [th for _v, th in log[len(inline):]] == [WORKER] * (10 - len(inline))
    # the cut is at the first gated row: every row behind it is the worker's
    assert all(v < 5 for v in inline) and sorted(v for v, _ in log) == [
        float(i) for i in range(10)
    ]


def test_sink_order_holds_under_stress(rig, async_on):
    """Many commits whose device batches finish at odd moments, the
    interpreter switching threads every 10 us: no row is lost or doubled,
    and one sink's callbacks stay in the inline order whichever thread made
    each (rows of t, ``on_time_end(t)``, rows of t + 1)."""
    import random

    rng = random.Random(41)
    pending: list = []
    stop = threading.Event()

    def opener():
        while not stop.is_set() or pending:
            if pending:
                pending.pop(0).set()
            time.sleep(rng.random() * 0.002)

    th = threading.Thread(target=opener, daemon=True)
    before = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    th.start()
    try:
        r = rig("single")
        want: list = []
        key = 0
        for _commit in range(150):
            n = rng.randrange(1, 6)
            gate = None
            if rng.random() < 0.6:
                gate = threading.Event()
                pending.append(gate)
            t = r.commit(range(key, key + n), gate=gate)
            want += [("row", k, t) for k in range(key, key + n)] + [("end", t)]
            key += n
        stop.set()
        r.sched.finish()
    finally:
        stop.set()
        sys.setswitchinterval(before)
        th.join(timeout=30)
    assert not th.is_alive()
    got = [
        (e[0], int(e[2][0][0]), e[3]) if e[0] == "row" else (e[0], e[1])
        for e in r.log
        if e[0] in ("row", "end")
    ]
    # the last commit (``finish``) holds no row: its ``on_time_end`` alone
    assert got[: len(want)] == want and got[len(want):] == [("end", t + 1)]
    assert {WORKER, threading.current_thread().name} <= r.threads("row")
    assert not dp.holds(r.sink) and dp.PIPELINE.inflight() == 0


def test_handed_over_work_counts_toward_the_inflight_bound(async_on):
    """A commit whose sinks handed work over is in flight until the worker
    has done it, whether or not it staged a device batch of its own; what
    a commit's own sinks handed over does not hold its boundary up."""
    gates = [threading.Event() for _ in range(2)]
    done: list = []
    for t, gate in enumerate(gates, start=1):
        handle = dev_mod.DeviceBatchHandle(_GatedDev(np.zeros((1, 1)), gate=gate))
        dp.hand_over(
            t, "sink", lambda t=t, h=handle: done.append((t, h.host().shape))
        )
        dp.commit_boundary(t)  # its own emission pending: no stall
    assert dp.PIPELINE.inflight() == 2 and done == []
    assert dp.holds("sink") and not dp.holds("another")
    dp.hand_over(3, "sink", lambda: done.append((3, None)))  # no batch of its own
    third = threading.Thread(target=dp.commit_boundary, args=(3,))
    third.start()
    time.sleep(0.25)
    assert third.is_alive()  # two earlier commits in flight: the bound holds
    gates[0].set()
    third.join(timeout=30)
    assert not third.is_alive()
    gates[1].set()
    dp.drain()
    assert [t for t, _ in done] == [1, 2, 3]
    assert dp.PIPELINE.inflight() == 0 and not dp.holds("sink")


# -- parity: sharded in-process scheduler -------------------------------------


def _sharded_device_scopes(n=3, events=None):
    """Replicated sharded graph with a device-batch stage feeding the
    worker-0 sink, alongside a groupby (exchange) branch."""
    from pathway_tpu.engine.reducers import SumReducer

    scopes = []
    for w in range(n):
        sc = Scope()
        rows = [(Pointer(i), (i % 7, float(i))) for i in range(200)]
        src = sc.static_table(rows, 2)
        e1 = sc.expression_table(
            src,
            [ex.ColumnRef(0), ex.Binary("*", ex.ColumnRef(1), ex.Const(2.0))],
        )
        ba = sc.batch_apply_table(e1, _embed_rows, [0, 1])
        gb = sc.group_by_table(
            e1, by_cols=[0], reducers=[(SumReducer(), [1])]
        )
        if w == 0 and events is not None:
            sc.subscribe_table(
                ba,
                on_change=lambda k, row, t, d: events.append(
                    ("ba", int(k), _host_row(row), d)
                ),
            )
            sc.subscribe_table(
                gb,
                on_change=lambda k, row, t, d: events.append(
                    ("gb", int(k), _host_row(row), d)
                ),
            )
        scopes.append(sc)
    return scopes


def test_sharded_parity_async_on_off(monkeypatch):
    def run():
        events: list = []
        sched = ShardedScheduler(_sharded_device_scopes(3, events))
        sched.finish()
        dp.drain()
        return sorted(events, key=repr)

    monkeypatch.setenv("PATHWAY_TPU_ASYNC_DEVICE", "0")
    dp.PIPELINE.configure()
    ev_off = run()
    monkeypatch.setenv("PATHWAY_TPU_ASYNC_DEVICE", "1")
    dp.PIPELINE.configure()
    ev_on = run()
    assert ev_off == ev_on
    assert ev_on


# -- parity: TCP mesh ----------------------------------------------------------


def _free_port_base(n: int) -> int:
    for _ in range(64):
        probe = socket.socket()
        probe.bind(("127.0.0.1", 0))
        base = probe.getsockname()[1]
        probe.close()
        if base + n >= 65535:
            continue
        if all(_bindable(base + i) for i in range(n)):
            return base
    raise RuntimeError("no free port range found")


def _bindable(port: int) -> bool:
    s = socket.socket()
    try:
        s.bind(("127.0.0.1", port))
        return True
    except OSError:
        return False
    finally:
        s.close()


# The UDF keeps each batch's handle alive past the commit boundary (the
# `_keep` list) so the pipeline genuinely stages and completes device
# work mesh-wide; sums stay fp-exact (n + 3n = 4n) so on/off runs are
# comparable bit for bit.
DEVICE_MESH_PROGRAM = """
    import numpy as np
    import pathway_tpu as pw
    from pathway_tpu.engine import device as _dev

    _keep = []

    @pw.udf(executor=pw.udfs.batch_executor(max_batch_size=32))
    def embed(ns: list) -> list:
        mat = np.asarray(
            [[float(n), float(n) * 3.0] for n in ns], np.float32
        )
        cells = _dev.lazy_rows(mat, len(ns))
        _keep.extend(c.batch for c in cells)
        return [float(np.asarray(c).sum()) for c in cells]

    words = pw.io.csv.read(
        {indir!r},
        schema=pw.schema_from_types(word=str, n=int),
        mode="static",
    )
    sel = words.select(word=pw.this.word, n=embed(pw.this.n))
    flt = sel.filter(sel.n > 10.0)
    counts = flt.groupby(flt.word).reduce(
        word=flt.word, total=pw.reducers.sum(flt.n)
    )
    pw.io.csv.write(counts, {out!r})
    pw.run()
"""


def _spawn_device_mesh(tmp_path, code, async_on_flag, out):
    from pathway_tpu.cli import spawn

    prog = tmp_path / f"prog_{int(async_on_flag)}.py"
    prog.write_text(textwrap.dedent(code))
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env["PATHWAY_TPU_ASYNC_DEVICE"] = "1" if async_on_flag else "0"
    env.pop("PATHWAY_PERSISTENT_STORAGE", None)
    rc = spawn(
        sys.executable,
        [str(prog)],
        threads=1,
        processes=3,
        first_port=_free_port_base(3),
        env=env,
    )
    assert rc == 0
    with open(out, newline="") as fh:
        rows = list(csv.DictReader(fh))
    return sorted(
        (r["word"], float(r["total"]))
        for r in rows
        if int(r["diff"]) > 0
    )


def test_mesh_parity_async_on_off(tmp_path):
    indir = tmp_path / "in"
    indir.mkdir()
    with open(indir / "words.csv", "w") as fh:
        fh.write("word,n\n")
        fh.writelines(f"w{i % 11},{i % 9}\n" for i in range(300))
    results = {}
    for flag in (False, True):
        out = tmp_path / f"out_{int(flag)}.csv"
        results[flag] = _spawn_device_mesh(
            tmp_path,
            DEVICE_MESH_PROGRAM.format(indir=str(indir), out=str(out)),
            flag,
            out,
        )
    assert results[True] == results[False]
    assert results[True]


# -- chaos: worker kill with commits staged -----------------------------------


# Streaming wordcount + fake device embed stage, operator persistence on:
# the kill lands at a commit boundary while the async pipeline has device
# work staged; recovery must roll back through the PR-6 snapshot protocol
# and reconverge to the fault-free sink bit for bit.
CHAOS_DEVICE_PROGRAM = """
    import os
    import numpy as np
    import pathway_tpu as pw
    import pathway_tpu.engine.connectors as _conn
    from pathway_tpu.engine import device as _dev
    from pathway_tpu.persistence import Backend, Config, PersistenceMode

    _orig_poll = _conn.FsReader.poll
    def _poll(self):
        entries, done = _orig_poll(self)
        if not entries and os.path.exists({stop!r}):
            done = True
        return entries, done
    _conn.FsReader.poll = _poll

    _keep = []

    @pw.udf(executor=pw.udfs.batch_executor(max_batch_size=16))
    def embed(ws: list) -> list:
        mat = np.asarray(
            [[float(len(w)), float(len(w)) * 3.0] for w in ws], np.float32
        )
        cells = _dev.lazy_rows(mat, len(ws))
        _keep.extend(c.batch for c in cells)
        return [float(np.asarray(c).sum()) for c in cells]

    words = pw.io.plaintext.read(
        {indir!r}, mode="streaming", persistent_id="w"
    )
    scored = words.select(data=words.data, score=embed(words.data))
    counts = scored.groupby(scored.data).reduce(
        word=scored.data,
        cnt=pw.reducers.count(),
        s=pw.reducers.sum(scored.score),
    )
    pw.io.csv.write(counts, {out!r})
    pw.run(persistence_config=Config(
        Backend.filesystem({store!r}),
        persistence_mode=PersistenceMode.OPERATOR_PERSISTING,
    ))
"""


def _run_device_chaos(tmp_path, tag, *, n_files=6, extra_env=None):
    from pathway_tpu.cli import spawn

    indir = tmp_path / f"in-{tag}"
    indir.mkdir()
    out = tmp_path / f"out-{tag}.csv"
    stop = tmp_path / f"stop-{tag}"
    prog = tmp_path / f"prog-{tag}.py"
    prog.write_text(
        textwrap.dedent(
            CHAOS_DEVICE_PROGRAM.format(
                indir=str(indir),
                out=str(out),
                store=str(tmp_path / f"store-{tag}"),
                stop=str(stop),
            )
        )
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env["PATHWAY_TPU_ASYNC_DEVICE"] = "1"
    env.pop("PATHWAY_PERSISTENT_STORAGE", None)
    env["PATHWAY_TPU_MESH_TIMEOUT"] = "30"
    env["PATHWAY_TPU_RECOVER_DEADLINE"] = "45"
    env.update(extra_env or {})
    result: dict = {}

    def run() -> None:
        result["rc"] = spawn(
            sys.executable,
            [str(prog)],
            threads=1,
            processes=3,
            first_port=_free_port_base(3),
            env=env,
        )

    th = threading.Thread(target=run)
    th.start()
    try:
        for k in range(n_files):
            lines = [f"w{k}_{i}" for i in range(3)] + ["common"]
            (indir / f"f{k}.txt").write_text("\n".join(lines) + "\n")
            marker = f"w{k}_0"
            deadline = time.monotonic() + 90
            while time.monotonic() < deadline:
                if out.exists() and marker in out.read_text():
                    break
                if not th.is_alive():
                    raise AssertionError(
                        f"mesh exited early (rc={result.get('rc')}) "
                        f"before file {k} committed"
                    )
                time.sleep(0.05)
            else:
                raise AssertionError(
                    f"file {k} never reached the sink "
                    f"(rc={result.get('rc')})"
                )
        stop.write_text("")
        th.join(timeout=90)
    finally:
        stop.write_text("")
        th.join(timeout=10)
    assert not th.is_alive(), "mesh did not shut down after STOP"
    assert result.get("rc") == 0, f"mesh exited rc={result.get('rc')}"
    return out.read_bytes()


def _canonical(sink_bytes: bytes) -> list[bytes]:
    return sorted(sink_bytes.splitlines())


def test_chaos_kill_with_staged_commits_recovers_bit_identical(tmp_path):
    """SIGKILL a non-leader worker at a commit boundary while the async
    pipeline is live: the supervisor restarts it, discard_inflight resets
    the pipeline, the mesh rolls back to the snapshot, and the recovered
    sink matches the fault-free run bit for bit."""
    baseline = _run_device_chaos(tmp_path, "baseline")
    plan = json.dumps(
        {"seed": 7, "faults": [
            {"type": "kill", "process": 1, "at_commit": 3},
        ]}
    )
    flight_dir = tmp_path / "flight"
    flight_dir.mkdir()
    faulted = _run_device_chaos(
        tmp_path,
        "faulted",
        extra_env={
            "PATHWAY_TPU_RECOVER": "1",
            "PATHWAY_TPU_FAULT_PLAN": plan,
            "PATHWAY_TPU_FLIGHT_DIR": str(flight_dir),
        },
    )
    assert _canonical(faulted) == _canonical(baseline), (
        "recovered run's sink differs from the fault-free run"
    )
