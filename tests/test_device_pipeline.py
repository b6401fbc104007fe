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
