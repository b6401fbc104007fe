"""Mesh-wide distributed tracing: sampling span recorder, critical-path
attribution, Chrome trace-event export, trace-context propagation over
the TCP mesh, and trace survival across worker kill -> recovery
(reference: PR "Mesh-wide distributed tracing")."""

from __future__ import annotations

import json
import os
import socket
import sys
import textwrap
import threading
import time
import urllib.request

import pytest

from pathway_tpu.internals import metrics as _metrics
from pathway_tpu.internals import tracing

pytestmark = pytest.mark.usefixtures("own_stage_table")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port_base(n: int) -> int:
    """A base port such that base..base+n-1 are currently bindable."""
    for _ in range(64):
        probe = socket.socket()
        probe.bind(("127.0.0.1", 0))
        base = probe.getsockname()[1]
        probe.close()
        if base + n >= 65535:
            continue
        ok = True
        for i in range(n):
            s = socket.socket()
            try:
                s.bind(("127.0.0.1", base + i))
            except OSError:
                ok = False
                break
            finally:
                s.close()
        if ok:
            return base
    raise RuntimeError("no free port range found")


def _recorder(sample: int = 1) -> tracing.TraceRecorder:
    r = tracing.TraceRecorder()
    r.configure(enabled=True, sample=sample, clear=True)
    return r


@pytest.fixture
def global_tracer():
    """The process-wide TRACER, enabled for the test and fully reset
    afterwards so the rest of the suite sees tracing off."""
    tracing.TRACER.configure(enabled=True, sample=1, clear=True)
    yield tracing.TRACER
    tracing.TRACER.drop()
    tracing.TRACER.configure(enabled=False, clear=True)
    tracing.TRACER.epoch = 0


class TestSampling:
    def test_first_commit_always_sampled(self):
        r = _recorder(sample=4)
        assert r.begin(1) is not None

    def test_interval_counts_commits_not_samples(self):
        r = _recorder(sample=4)
        # pin the interval: on zero-work commits the adaptive sampler
        # (rightly) backs off, which is not what this test measures
        r._adapt = lambda *a: None
        sampled = []
        for t in range(1, 10):
            ctx = r.begin(t)
            if ctx is not None:
                sampled.append(t)
                r.end(t)
        # (count - 1) % 4 == 0 -> commits 1, 5, 9
        assert sampled == [1, 5, 9]

    def test_disabled_recorder_samples_nothing(self):
        r = tracing.TraceRecorder()
        r.configure(enabled=False, sample=1, clear=True)
        assert r.begin(1) is None
        assert r.traces() == []

    def test_trace_ids_unique_and_worker_stamped(self):
        r = _recorder()
        r._adapt = lambda *a: None  # see above: pin the interval
        a = r.begin(1)
        r.end(1)
        b = r.begin(2)
        r.end(2)
        assert a.trace_id != b.trace_id
        assert a.trace_id.startswith(f"t{r.worker_id:02d}-")


class TestSpansAndOverflow:
    def test_span_overflow_increments_dropped(self):
        r = _recorder()
        ctx = r.begin(1)
        t0 = time.perf_counter()
        for _ in range(tracing.MAX_SPANS + 10):
            ctx.span("s", "op", t0, t0)
        assert len(ctx.spans) <= tracing.MAX_SPANS
        assert ctx.dropped >= 10

    def test_take_spans_is_a_copy(self):
        r = _recorder()
        ctx = r.begin(1)
        ctx.span("s", "op", time.perf_counter(), time.perf_counter())
        taken = r.take_spans()
        n = len(ctx.spans)
        taken.append({"name": "bogus"})
        assert len(ctx.spans) == n

    def test_drop_abandons_context(self):
        r = _recorder()
        assert r.begin(1) is not None
        r.drop()
        assert r.active_trace_id() is None
        assert r.end(1) is None
        assert r.traces() == []


class TestEpochFence:
    def test_adopt_rejects_lower_epoch(self):
        r = _recorder()
        r.epoch = 2
        assert r.adopt(("ctx", "tzz-1", 5, 123.0, 1)) is None

    def test_adopt_accepts_and_raises_epoch(self):
        r = _recorder()
        r.epoch = 1
        ctx = r.adopt(("ctx", "tzz-2", 5, 123.0, 3))
        assert ctx is not None and ctx.remote
        assert r.epoch == 3
        # remote contexts never re-broadcast and never ring locally
        assert r.ctx_frame() is None
        assert r.end(5) is None
        assert r.traces() == []

    def test_adopt_is_idempotent_per_trace_id(self):
        r = _recorder()
        a = r.adopt(("ctx", "tzz-3", 5, 123.0, 0))
        b = r.adopt(("ctx", "tzz-3", 5, 123.0, 0))
        assert a is b

    def test_resync_fences_the_global_tracer(self, global_tracer):
        from pathway_tpu.engine.distributed import DistributedScheduler

        sched = DistributedScheduler.__new__(DistributedScheduler)
        sched._outbox = {}  # no peers: the barrier is a no-op
        sched.resync(epoch=2)
        assert global_tracer.epoch >= 2


class TestCriticalPath:
    def test_buckets_sum_to_wall_by_construction(self):
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
        assert cp["wall_s"] == pytest.approx(0.100)
        assert cp["queue_wait_s"] == pytest.approx(0.030)  # ingest + wait
        assert cp["exchange_s"] == pytest.approx(0.030)
        assert cp["device_s"] == pytest.approx(0.005)
        assert cp["host_compute_s"] == pytest.approx(0.035)
        assert not cp["clamped"]
        total = (
            cp["queue_wait_s"] + cp["exchange_s"]
            + cp["device_s"] + cp["host_compute_s"]
        )
        assert total == pytest.approx(cp["wall_s"], rel=0.05)
        assert [c["name"] for c in cp["chain"]] == [
            "recv-wait:p1", "pwcf-encode"
        ]

    def test_host_residual_clamps_at_zero(self):
        trace = {
            "origin_wall": 0.0,
            "begin_wall": 0.0,
            "end_wall": 0.010,
            "device_s": 0.0,
            "spans": [
                {"name": "apply:p1", "cat": "exchange",
                 "ts": 0, "dur": 50_000, "pid": 0},
            ],
        }
        cp = tracing.critical_path(trace)
        assert cp["clamped"]
        assert cp["host_compute_s"] == 0.0

    def test_end_attributes_a_real_commit(self):
        r = _recorder()
        ctx = r.begin(7, origin_mono=time.monotonic() - 0.05)
        t0 = time.perf_counter()
        time.sleep(0.01)
        t1 = time.perf_counter()
        ctx.span("map<t>", "op", t0, t1)
        ctx.span("pwcf-encode", "exchange", t1, time.perf_counter())
        ctx.note_sink(12)
        trace = r.end(7)
        assert trace is not None
        assert trace["sink_rows"] == 12
        cp = trace["critical_path"]
        # the 50 ms connector wait dominates and lands in queue-wait
        assert cp["queue_wait_s"] >= 0.04
        if not cp["clamped"]:
            total = (
                cp["queue_wait_s"] + cp["exchange_s"]
                + cp["device_s"] + cp["host_compute_s"]
            )
            assert total == pytest.approx(cp["wall_s"], rel=0.05)
        # the synthesized ingest-wait span leads the chain
        assert cp["chain"][0]["name"] == "ingest-wait"


class TestAdaptiveSampling:
    def test_interval_doubles_under_overhead(self):
        r = _recorder(sample=2)
        r._adapt(overhead_s=1.0, commit_wall_s=0.001)
        assert r.interval == 4
        r._adapt(overhead_s=1.0, commit_wall_s=0.001)
        assert r.interval == 8

    def test_interval_capped(self):
        r = _recorder(sample=2)
        for _ in range(20):
            r._adapt(overhead_s=10.0, commit_wall_s=0.001)
        assert r.interval == 4096

    def test_interval_decays_toward_base(self):
        r = _recorder(sample=2)
        r.interval = 8
        r._overhead_ema = 0.0
        r._adapt(overhead_s=0.0, commit_wall_s=1.0)
        assert r.interval == 4
        for _ in range(10):
            r._overhead_ema = 0.0
            r._adapt(overhead_s=0.0, commit_wall_s=1.0)
        assert r.interval == r.base_interval == 2


class TestChromeExport:
    def _one_trace(self, r: tracing.TraceRecorder) -> dict:
        ctx = r.begin(3, origin_mono=time.monotonic() - 0.01)
        t0 = time.perf_counter()
        ctx.span("filter<t>", "op", t0, time.perf_counter())
        peer_spans = {
            1: [{"name": "apply:p0", "cat": "exchange",
                 "ts": ctx.spans[0]["ts"], "dur": 5, "pid": 1}],
        }
        return r.end(3, peer_spans=peer_spans)

    def test_chrome_trace_validates_and_covers_workers(self):
        r = _recorder()
        trace = self._one_trace(r)
        obj = tracing.chrome_trace([trace])
        events = tracing.validate_chrome_trace(obj)
        xs = [e for e in events if e.get("ph") == "X"]
        assert {e["pid"] for e in xs} == {0, 1}
        roots = [e for e in xs if e["name"].startswith("commit ")]
        assert roots and roots[0]["args"]["trace"] == trace["trace_id"]
        assert all(
            e.get("args", {}).get("trace") == trace["trace_id"] for e in xs
        )
        metas = [e for e in obj["traceEvents"] if e.get("ph") == "M"]
        assert {e["args"]["name"] for e in metas} >= {"worker 0", "worker 1"}

    def test_export_writes_valid_file(self, tmp_path):
        r = _recorder()
        self._one_trace(r)
        path = r.export(str(tmp_path))
        assert path is not None and os.path.exists(path)
        base = os.path.basename(path)
        assert base.startswith("pathway_trace_p") and base.endswith(
            "_001.json"
        )
        obj = json.loads(open(path).read())
        tracing.validate_chrome_trace(obj)
        other = obj["otherData"]
        assert other["traces"] and other["traces"][0]["critical_path"]

    def test_export_empty_ring_writes_nothing(self, tmp_path):
        r = _recorder()
        assert r.export(str(tmp_path)) is None
        assert list(tmp_path.iterdir()) == []

    def test_validate_rejects_x_without_dur(self):
        with pytest.raises(ValueError):
            tracing.validate_chrome_trace(
                [{"ph": "X", "name": "a", "ts": 1, "pid": 0, "tid": 0}]
            )

    def test_validate_rejects_nonmonotonic_track(self):
        with pytest.raises(ValueError):
            tracing.validate_chrome_trace([
                {"ph": "X", "name": "a", "ts": 10, "dur": 1,
                 "pid": 0, "tid": 0},
                {"ph": "X", "name": "b", "ts": 5, "dur": 1,
                 "pid": 0, "tid": 0},
            ])

    def test_validate_rejects_unmatched_begin(self):
        with pytest.raises(ValueError):
            tracing.validate_chrome_trace(
                [{"ph": "B", "name": "a", "ts": 1, "pid": 0, "tid": 0}]
            )

    def test_validate_rejects_unknown_phase(self):
        with pytest.raises(ValueError):
            tracing.validate_chrome_trace(
                [{"ph": "Q", "name": "a", "ts": 1, "pid": 0, "tid": 0}]
            )


class TestFlightIntegration:
    """Satellite: flight records/dumps reference the in-flight trace id,
    and repeated dumps from one process never clobber each other."""

    def test_flight_record_carries_trace_id(self, global_tracer):
        ctx = global_tracer.begin(1)
        fr = _metrics.FlightRecorder()
        fr.record("commit", time=1)
        (event,) = fr.snapshot()
        assert event["trace_id"] == ctx.trace_id

    def test_flight_dump_names_do_not_collide(
        self, tmp_path, monkeypatch, global_tracer
    ):
        monkeypatch.setenv("PATHWAY_TPU_FLIGHT_DIR", str(tmp_path))
        ctx = global_tracer.begin(1)
        fr = _metrics.FlightRecorder()
        fr.record("commit", time=1)
        p1 = fr.dump("first")
        p2 = fr.dump("second")
        assert p1 != p2 and os.path.exists(p1) and os.path.exists(p2)
        assert p1.endswith("_001.json") and p2.endswith("_002.json")
        assert os.path.basename(p1).startswith("pathway_flight_p")
        payload = json.loads(open(p1).read())
        assert payload["trace_id"] == ctx.trace_id
        assert payload["events"][0]["trace_id"] == ctx.trace_id

    def test_no_trace_id_when_tracing_off(self, tmp_path, monkeypatch):
        monkeypatch.setenv("PATHWAY_TPU_FLIGHT_DIR", str(tmp_path))
        fr = _metrics.FlightRecorder()
        fr.record("commit", time=1)
        payload = json.loads(open(fr.dump("quiet")).read())
        assert payload["trace_id"] is None
        assert "trace_id" not in payload["events"][0]


class TestPruneMeshMetrics:
    def test_prunes_dead_and_out_of_width_peers(self):
        from pathway_tpu.engine.distributed import DistributedScheduler

        class _Transport:
            dead_peers = {3}

        sched = DistributedScheduler.__new__(DistributedScheduler)
        sched.transport = _Transport()
        sched.n_processes = 4
        sched.mesh_metrics = {1: {}, 2: {}, 3: {}, 5: {}}
        sched.trace_peer_spans = {1: [], 3: [], 7: []}
        sched.prune_mesh_metrics(dead=(2,))
        assert set(sched.mesh_metrics) == {1}
        assert set(sched.trace_peer_spans) == {1}


class TestCli:
    def test_trace_subcommand_reads_export_dir(
        self, tmp_path, capsys, global_tracer
    ):
        from pathway_tpu import cli

        ctx = global_tracer.begin(1, origin_mono=time.monotonic() - 0.01)
        t0 = time.perf_counter()
        ctx.span("filter<t>", "op", t0, time.perf_counter())
        global_tracer.end(1)
        assert global_tracer.export(str(tmp_path)) is not None
        assert cli.main(["trace", str(tmp_path)]) == 0
        out = capsys.readouterr().out
        assert ctx.trace_id in out
        assert "wall=" in out

    def test_trace_subcommand_json_mode(
        self, tmp_path, capsys, global_tracer
    ):
        from pathway_tpu import cli

        global_tracer.begin(1)
        global_tracer.end(1)
        path = global_tracer.export(str(tmp_path))
        assert cli.main(["trace", "--json", path]) == 0
        reports = json.loads(capsys.readouterr().out)
        assert reports and reports[0]["file"] == path

    def test_trace_subcommand_rejects_invalid_file(self, tmp_path, capsys):
        from pathway_tpu import cli

        bad = tmp_path / "pathway_trace_bad.json"
        bad.write_text(json.dumps(
            {"traceEvents": [{"ph": "Q", "name": "a", "ts": 1}]}
        ))
        assert cli.main(["trace", str(bad)]) == 2

    def test_stats_renders_histogram_percentiles(self, capsys):
        from pathway_tpu import cli
        from pathway_tpu.internals.monitoring import (
            MonitoringHttpServer,
            MonitoringLevel,
            StatsMonitor,
        )

        h = _metrics.REGISTRY.histogram(
            "test_trace_cli_seconds", "cli percentile fixture",
            buckets=(0.1, 1.0, 10.0),
        )
        for v in (0.05, 0.05, 0.5, 0.5, 0.5, 5.0):
            h.observe(v)
        monitor = StatsMonitor(MonitoringLevel.IN_OUT)
        server = MonitoringHttpServer(monitor, port=0)
        try:
            assert cli.main(["stats", str(server.port)]) == 0
        finally:
            server.stop()
        out = capsys.readouterr().out
        header = next(
            line for line in out.splitlines() if "family" in line
        )
        assert "p50" in header and "p95" in header and "p99" in header
        row = next(
            line for line in out.splitlines()
            if "test_trace_cli_seconds" in line
        )
        # p50 falls in the (0.1, 1.0] bucket, p99 in (1.0, 10.0]
        assert "-" not in row.split()[-3:]


class TestMeshAssembledTrace:
    def test_three_process_trace_covers_ingest_to_sink(self, tmp_path):
        """3-process TCP mesh with tracing on: the leader's exported
        Chrome trace is valid, spans every worker, and covers the whole
        commit path (ingest wait -> operators -> exchange -> sink) under
        one consistent trace id."""
        from pathway_tpu.cli import spawn

        indir = tmp_path / "in"
        indir.mkdir()
        with open(indir / "words.csv", "w") as fh:
            fh.write("word\n")
            fh.writelines(f"w{i % 17}\n" for i in range(600))
        out = tmp_path / "out.csv"
        trace_dir = tmp_path / "traces"
        trace_dir.mkdir()
        prog = tmp_path / "prog.py"
        prog.write_text(
            textwrap.dedent(
                """
                import pathway_tpu as pw

                words = pw.io.csv.read(
                    {indir!r},
                    schema=pw.schema_from_types(word=str),
                    mode="static",
                )
                counts = words.groupby(pw.this.word).reduce(
                    word=pw.this.word, count=pw.reducers.count()
                )
                pw.io.csv.write(counts, {out!r})
                pw.run()
                """.format(indir=str(indir), out=str(out))
            )
        )
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        env["JAX_PLATFORMS"] = "cpu"
        env["PATHWAY_TPU_TRACE"] = "1"
        env["PATHWAY_TPU_TRACE_SAMPLE"] = "1"
        env["PATHWAY_TPU_TRACE_DIR"] = str(trace_dir)
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
        exports = sorted(trace_dir.glob("pathway_trace_p0_*.json"))
        assert exports, "leader exported no trace file"

        pids: set[int] = set()
        cats: set[str] = set()
        ids_per_trace: dict[str, set] = {}
        for path in exports:
            obj = json.loads(path.read_text())
            events = tracing.validate_chrome_trace(obj)
            for e in events:
                if e.get("ph") != "X":
                    continue
                pids.add(e["pid"])
                if e.get("cat"):
                    cats.add(e["cat"])
                tid = e.get("args", {}).get("trace")
                assert tid, f"X event without trace id: {e['name']}"
                ids_per_trace.setdefault(tid, set()).add(e["pid"])
            for t in obj["otherData"]["traces"]:
                cp = t["critical_path"]
                if not cp["clamped"]:
                    total = (
                        cp["queue_wait_s"] + cp["exchange_s"]
                        + cp["device_s"] + cp["host_compute_s"]
                    )
                    assert total == pytest.approx(
                        cp["wall_s"], rel=0.05, abs=1e-6
                    )
        # every worker contributed spans to the assembled trace set
        assert pids == {0, 1, 2}, pids
        assert "op" in cats and "sink" in cats
        assert cats & {"exchange", "wait"}, cats
        # the data commit's trace spans multiple workers
        assert any(len(p) >= 2 for p in ids_per_trace.values())


# -- trace survival across worker kill -> recovery ---------------------------

TRACED_CHAOS_PROGRAM = """
    import os
    import pathway_tpu as pw
    import pathway_tpu.engine.connectors as _conn
    from pathway_tpu.persistence import Backend, Config, PersistenceMode

    _orig_poll = _conn.FsReader.poll
    def _poll(self):
        entries, done = _orig_poll(self)
        if not entries and os.path.exists({stop!r}):
            done = True
        return entries, done
    _conn.FsReader.poll = _poll

    pid = int(os.environ.get("PATHWAY_PROCESS_ID", "0"))
    words = pw.io.plaintext.read(
        {indir!r}, mode="streaming", persistent_id="w"
    )
    counts = words.groupby(words.data).reduce(
        word=words.data, cnt=pw.reducers.count()
    )
    pw.io.csv.write(counts, {out!r})
    pw.run(
        with_http_server=(pid == 0),
        monitoring_server_port=int(os.environ["TEST_METRICS_PORT_BASE"]),
        persistence_config=Config(
            Backend.filesystem({store!r}),
            persistence_mode=PersistenceMode.OPERATOR_PERSISTING,
        ),
    )
"""


class TestTraceSurvivesRecovery:
    def test_kill_recover_keeps_traces_and_prunes_scrape(self, tmp_path):
        """SIGKILL worker 1 at a commit boundary mid-stream with tracing
        on (sample=1): flight forensics reference trace ids, the leader
        keeps exporting well-formed traces after the recovery epoch, and
        a LIVE leader scrape after recovery shows only live worker label
        sets (the stale-incarnation prune)."""
        from pathway_tpu.cli import spawn

        indir = tmp_path / "in"
        indir.mkdir()
        out = tmp_path / "out.csv"
        stop = tmp_path / "stop"
        flight_dir = tmp_path / "flight"
        flight_dir.mkdir()
        trace_dir = tmp_path / "traces"
        trace_dir.mkdir()
        prog = tmp_path / "prog.py"
        prog.write_text(
            textwrap.dedent(
                TRACED_CHAOS_PROGRAM.format(
                    indir=str(indir),
                    out=str(out),
                    store=str(tmp_path / "store"),
                    stop=str(stop),
                )
            )
        )
        metrics_port = _free_port_base(1)
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("PATHWAY_PERSISTENT_STORAGE", None)
        # more generous than the test_fault_tolerance defaults: this file
        # sorts last in the suite, where a restarted worker's cold
        # re-import of the full stack is at its slowest
        env["PATHWAY_TPU_MESH_TIMEOUT"] = "60"
        env["PATHWAY_TPU_RECOVER_DEADLINE"] = "90"
        env["PATHWAY_TPU_RECOVER"] = "1"
        env["PATHWAY_TPU_FAULT_PLAN"] = json.dumps(
            {"seed": 7, "faults": [
                {"type": "kill", "process": 1, "at_commit": 3},
            ]}
        )
        env["PATHWAY_TPU_FLIGHT_DIR"] = str(flight_dir)
        env["PATHWAY_TPU_TRACE"] = "1"
        env["PATHWAY_TPU_TRACE_SAMPLE"] = "1"
        env["PATHWAY_TPU_TRACE_DIR"] = str(trace_dir)
        env["TEST_METRICS_PORT_BASE"] = str(metrics_port)
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

        scraped: dict = {}
        th = threading.Thread(target=run)
        th.start()
        try:
            for k in range(7):
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
                if k == 5:
                    # well past the at_commit=3 kill: the mesh has
                    # recovered — scrape the live leader endpoint
                    scraped["body"] = (
                        urllib.request.urlopen(
                            f"http://127.0.0.1:{metrics_port}/metrics",
                            timeout=10,
                        ).read().decode()
                    )
            stop.write_text("")
            th.join(timeout=90)
        finally:
            stop.write_text("")
            th.join(timeout=10)
        assert not th.is_alive(), "mesh did not shut down after STOP"
        assert result.get("rc") == 0, f"mesh exited rc={result.get('rc')}"

        # (1) post-recovery scrape: conformant, and every worker label
        # names a live incarnation — no stale sets from the dead peer
        families = _metrics.validate_exposition(scraped["body"])
        workers: set[str] = set()
        for fam in families.values():
            for _n, labels, _v in fam["samples"]:
                if "worker" in labels:
                    workers.add(labels["worker"])
        assert workers == {"0", "1", "2"}, workers

        # (2) flight forensics reference trace ids (sample=1 means every
        # commit event carries one; the dump's own trace_id is the
        # in-flight commit when the peer died mid-commit)
        dumps = list(flight_dir.glob("pathway_flight_*.json"))
        assert dumps, "no flight-recorder dumps on peer death"
        ids: set[str] = set()
        for p in dumps:
            payload = json.loads(p.read_text())
            assert "trace_id" in payload
            if payload["trace_id"]:
                ids.add(payload["trace_id"])
            for event in payload["events"]:
                if event.get("trace_id"):
                    ids.add(event["trace_id"])
        assert ids, "no flight event references a trace id"

        # (3) the leader's export validates and contains post-recovery
        # traces stamped with the bumped epoch
        exports = sorted(trace_dir.glob("pathway_trace_p0_*.json"))
        assert exports, "leader exported no trace file"
        epochs: list[int] = []
        for path in exports:
            obj = json.loads(path.read_text())
            tracing.validate_chrome_trace(obj)
            epochs += [t["epoch"] for t in obj["otherData"]["traces"]]
        assert epochs and max(epochs) >= 1, epochs


# -- stages: per-run totals, profiler annotations, sampled spans --------------

#: every stage of ISSUE 25's table that a single-worker RAG run passes
#: through, with the counts each carries
RAG_STAGES = {
    "pump.poll": ("rows",),
    "pump.sleep": (),
    "commit": ("commit_wait_ns", "arrival_to_poll_ns"),
    "op.BatchApplyNode": ("batches",),
    "op.ExternalIndexNode": ("batches",),
    "op.SubscribeNode": ("batches",),
    "commit.device_stage": ("batches",),
    "commit.device_wait": (),
    "commit.after": (),
    "sink.emit": ("rows",),
    "udf.batch": ("rows", "narrowed"),
    "embed.tokenize": ("tokens",),
    "embed.pad": ("rows", "padded_rows", "padded_tokens", "pieces"),
    "embed.dispatch": ("h2d_bytes", "tokens", "padded_tokens"),
    "embed.rows_out": ("rows",),
    "knn.add.host": ("rows",),
    "knn.add.dispatch": ("rows", "h2d_bytes"),
    "knn.search.dispatch": ("queries", "padded_queries", "h2d_bytes"),
    "knn.search.fetch": ("queries", "d2h_bytes"),
}
N_DOCS, N_QUERIES = 24, 3


def _self_sum(totals: dict) -> int:
    return sum(row["self_ns"] for row in totals["stages"].values())


def _run_counting_stream(n: int = 20) -> list:
    """A small relational streaming ``pw.run()``: n rows in, their sum out."""
    import pathway_tpu as pw
    from pathway_tpu.internals.parse_graph import G

    G.clear()

    class Numbers(pw.io.python.ConnectorSubject):
        def run(self) -> None:
            for i in range(n):
                self.next(a=i)

    table = pw.io.python.read(
        Numbers(), schema=pw.schema_from_types(a=int),
        autocommit_duration_ms=5,
    )
    total = table.reduce(s=pw.reducers.sum(pw.this.a))
    seen: list = []
    pw.io.subscribe(
        total,
        on_change=lambda key, row, time, is_addition: seen.append(
            (row["s"], is_addition)
        ),
    )
    pw.run()
    return seen


class TestStageTable:
    def test_self_ns_is_duration_less_children(self):
        table = tracing.StageTable()
        with table.stage("outer"):
            with table.stage("inner"):
                with table.stage("leaf"):
                    pass
            with table.stage("inner"):
                pass
        rows = table.totals()["stages"]
        outer, inner, leaf = rows["outer"], rows["inner"], rows["leaf"]
        assert (outer["calls"], inner["calls"], leaf["calls"]) == (1, 2, 1)
        assert outer["self_ns"] == outer["total_ns"] - inner["total_ns"]
        assert inner["self_ns"] == inner["total_ns"] - leaf["total_ns"]
        assert leaf["self_ns"] == leaf["total_ns"]
        assert _self_sum(table.totals()) == outer["total_ns"]

    def test_counts_sum_over_calls(self):
        table = tracing.StageTable()
        for rows in (3, 4):
            with table.stage("udf.batch", rows=rows, narrowed=0) as st:
                st.add(tokens=10 * rows)
        st = table.stage("knn.search.fetch", wait=True).__enter__()
        st.add(queries=2)
        st.__exit__(None, None, None)
        rows = table.totals()["stages"]
        assert rows["udf.batch"]["counts"] == {
            "rows": 7, "narrowed": 0, "tokens": 70,
        }
        assert rows["udf.batch"]["wait"] is False
        assert rows["knn.search.fetch"]["wait"] is True
        assert rows["knn.search.fetch"]["counts"] == {"queries": 2}

    def test_a_raise_inside_a_stage_unwinds_the_stack(self):
        table = tracing.StageTable()
        with table.stage("outer"):
            with pytest.raises(ZeroDivisionError):
                with table.stage("raises"):
                    left_open = table.stage("left_open").__enter__()
                    assert left_open is not None
                    1 / 0
            with table.stage("after"):
                pass
        rows = table.totals()["stages"]
        assert "left_open" not in rows  # never closed: never counted
        assert rows["outer"]["self_ns"] == (
            rows["outer"]["total_ns"]
            - rows["raises"]["total_ns"]
            - rows["after"]["total_ns"]
        )

    def test_another_thread_writes_a_table_of_its_own(self):
        table = tracing.StageTable()
        root = table.begin_run()

        def worker() -> None:
            with table.stage("device.fetch_rows", wait=True, d2h_bytes=8):
                pass

        thread = threading.Thread(target=worker, name="stage-worker")
        with table.stage("device.fetch_rows", wait=True, d2h_bytes=4):
            thread.start()
            thread.join(timeout=30)
        assert not thread.is_alive()
        table.end_run(root)
        totals = table.totals()
        assert totals["stages"]["device.fetch_rows"]["counts"] == {
            "d2h_bytes": 4
        }
        assert totals["threads"]["stage-worker"]["device.fetch_rows"][
            "counts"
        ] == {"d2h_bytes": 8}
        # the other thread's stage is outside the run thread's sum
        assert _self_sum(totals) == totals["run_wall_ns"]

    def test_a_run_inside_a_run_leaves_the_table_alone(self):
        table = tracing.StageTable()
        root = table.begin_run()
        with table.stage("commit"):
            inner = table.begin_run()  # an iterate body's runner
            assert inner is None
            table.end_run(inner)
        assert table.totals()["running"] is True
        table.end_run(root)
        totals = table.totals()
        assert totals["running"] is False
        assert set(totals["stages"]) == {"commit", tracing.RUN_STAGE}
        assert totals["run_wall_ns"] == totals["stages"]["run"]["total_ns"]

    def test_a_detail_stage_is_nothing_while_nobody_looks(self):
        assert not tracing.TRACER.enabled and not tracing.detail_on()
        before = tracing.stage_totals()
        st = tracing.detail("test.detail_off", rows=1)
        assert st is tracing.NO_STAGE and not st
        with st as entered:
            entered.add(rows=2)
        after = tracing.stage_totals()
        assert "test.detail_off" not in after["stages"]
        assert set(after["stages"]) == set(before["stages"])

    def test_a_detail_stage_is_recorded_in_a_sampled_commit(self):
        tracing.TRACER.configure(enabled=True, sample=1, clear=True)
        try:
            ctx = tracing.TRACER.begin(1)
            assert ctx is not None and tracing.detail_on()
            with tracing.detail("test.detail_on", rows=3) as st:
                assert st
            tracing.TRACER.end(1)
            assert not tracing.detail_on()
        finally:
            tracing.TRACER.drop()
            tracing.TRACER.configure(enabled=False, clear=True)
            tracing.TRACER.epoch = 0
        totals = tracing.stage_totals()
        rows = {**totals["stages"], **{
            name: row
            for table in totals["threads"].values()
            for name, row in table.items()
        }}
        assert rows["test.detail_on"]["counts"]["rows"] >= 3

    @pytest.mark.parametrize(
        "cat,bucket",
        [("wait", "queue_wait_s"), ("device_wait", "host_compute_s")],
    )
    def test_a_device_wait_span_stays_in_the_residual(self, cat, bucket):
        """A ``wait`` stage's span (``device_wait``) is no queue wait: the
        buckets the device pipeline's controller reads keep their meaning."""
        trace = {
            "origin_wall": 10.0, "begin_wall": 10.0, "end_wall": 11.0,
            "device_s": 0.0,
            "spans": [
                {"name": "blocked", "cat": cat, "ts": 0, "dur": 400_000},
            ],
        }
        cp = tracing.critical_path(trace)
        other = ({"queue_wait_s", "host_compute_s"} - {bucket}).pop()
        assert cp[bucket] == pytest.approx(
            1.0 if bucket == "host_compute_s" else 0.4
        )
        assert cp[other] == pytest.approx(
            0.0 if other == "queue_wait_s" else 0.6
        )

    def test_begin_and_end_where_a_with_does_not_fit(self):
        st = tracing.begin("test.begin_end", rows=1)
        tracing.end(st, rows=2)
        local = tracing.stage_totals()
        rows = {**local["stages"], **{
            name: row
            for table in local["threads"].values()
            for name, row in table.items()
        }}
        assert rows["test.begin_end"]["counts"]["rows"] >= 3


class TestStagesOfARun:
    def test_run_thread_stages_sum_to_the_run_wall(self):
        seen = _run_counting_stream()
        assert seen[-1] == (sum(range(20)), True)
        totals = tracing.stage_totals()
        assert totals["running"] is False and totals["run_wall_ns"] > 0
        stages = totals["stages"]
        assert {"run", "pump.poll", "commit", "sink.emit"} <= set(stages)
        assert abs(_self_sum(totals) - totals["run_wall_ns"]) <= (
            0.01 * totals["run_wall_ns"]
        )
        assert stages["pump.poll"]["counts"]["rows"] == 20
        assert stages["sink.emit"]["counts"]["rows"] == len(seen)
        # every commit has its wait, sampled or not (tracing is off here)
        assert not tracing.TRACER.enabled
        assert "commit_wait_ns" in stages["commit"]["counts"]
        # with no sampled commit and no profiler session, the stages no
        # metric reads are not recorded: their time is their parent's own
        assert not {name for name in stages if name.startswith("op.")}
        assert "commit.after" not in stages

    def test_operators_run_under_a_commit_stage_and_nowhere_else(self):
        """The static sources' first commit and the last one in finish()
        are ``commit`` stages too, so ``commit`` less its named children
        is the scheduler's own time."""
        import pathway_tpu as pw
        from pathway_tpu.internals.parse_graph import G

        G.clear()

        class Numbers(pw.io.python.ConnectorSubject):
            def run(self) -> None:
                for i in range(5):
                    self.next(a=i)

        streamed = pw.io.python.read(
            Numbers(), schema=pw.schema_from_types(a=int),
            autocommit_duration_ms=5,
        )
        static = pw.debug.table_from_rows(
            pw.schema_from_types(a=int), [(100,), (101,)]
        )
        open_stages: list = []

        def on_change(key, row, time, is_addition) -> None:
            open_stages.append(
                [st.name for st in tracing.STAGES._thread().stack]
            )

        pw.io.subscribe(streamed, on_change=on_change)
        pw.io.subscribe(static, on_change=on_change)
        pw.run()
        assert len(open_stages) == 7
        for names in open_stages:
            assert names[:2] == ["run", "commit"], names
            assert names[-1] == "sink.emit", names
        stages = tracing.stage_totals()["stages"]
        assert stages["sink.emit"]["total_ns"] <= stages["commit"]["total_ns"]
        assert "run.finish" not in stages

    def test_the_next_run_zeroes_the_table(self):
        _run_counting_stream(n=30)
        first = tracing.stage_totals()["stages"]
        _run_counting_stream(n=4)
        second = tracing.stage_totals()["stages"]
        assert first["pump.poll"]["counts"]["rows"] == 30
        assert second["pump.poll"]["counts"]["rows"] == 4
        assert second["run"]["calls"] == 1

    def test_stages_import_no_jax(self):
        """A relational run must not import jax for its annotations."""
        import subprocess

        prog = textwrap.dedent(
            """
            import sys
            import pathway_tpu as pw
            from pathway_tpu.internals import tracing

            class S(pw.io.python.ConnectorSubject):
                def run(self):
                    for i in range(5):
                        self.next(a=i)

            t = pw.io.python.read(
                S(), schema=pw.schema_from_types(a=int),
                autocommit_duration_ms=5,
            )
            pw.io.subscribe(t, on_change=lambda **kw: None)
            pw.run()
            assert "commit" in tracing.stage_totals()["stages"]
            print("jax" in sys.modules)
            """
        )
        env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
        out = subprocess.run(
            [sys.executable, "-c", prog], env=env, capture_output=True,
            text=True, timeout=120,
        )
        assert out.returncode == 0, out.stderr[-2000:]
        assert out.stdout.strip().splitlines()[-1] == "False"


_perf_ns = time.perf_counter_ns  # the stages' clock


class _PushClock:
    """Stands in for the ``time`` module of ``engine/connectors.py``: the
    real clock, keeping every stamp a thread other than the pump's took,
    which are the stamps ``QueueReader.push`` put on the rows."""

    def __init__(self) -> None:
        self.pump = threading.get_ident()
        self.pushed: dict[int, list[float]] = {}  # feed's thread -> stamps
        self.sleep = time.sleep

    def monotonic(self) -> float:
        now = time.monotonic()
        thread = threading.get_ident()
        if thread != self.pump:
            self.pushed.setdefault(thread, []).append(now)
        return now


def _run_two_feeds(monkeypatch=None, rows=(12, 5)):
    """A streaming ``pw.run()`` with two Python connectors (a windowed one
    and one that commits at its poll) and a sink each. Returns what each
    sink saw, as ``(commit time, perf_counter_ns inside the callback, which
    of its feed's rows)``, and, with ``monkeypatch``, the clock that kept
    the pushes' stamps with each feed's thread."""
    import pathway_tpu as pw
    from pathway_tpu.engine import connectors
    from pathway_tpu.internals.parse_graph import G

    G.clear()
    clock = None
    if monkeypatch is not None:
        clock = _PushClock()
        monkeypatch.setattr(connectors, "_time", clock)
    threads: list = [None, None]

    def feed(place: int, n: int, pause_every: int):
        class Feed(pw.io.python.ConnectorSubject):
            def run(self) -> None:
                threads[place] = threading.get_ident()
                for i in range(n):
                    self.next(a=1000 * place + i)
                    if i % pause_every == pause_every - 1:
                        time.sleep(0.02)

        return Feed()

    seen: list[list] = [[], []]
    for place, (n, window, pause_every) in enumerate(
        ((rows[0], 10, 4), (rows[1], None, 1))
    ):
        table = pw.io.python.read(
            feed(place, n, pause_every), schema=pw.schema_from_types(a=int),
            autocommit_duration_ms=window,
        )
        pw.io.subscribe(
            table,
            on_change=lambda key, row, time, is_addition, place=place: seen[
                place
            ].append((time, _perf_ns(), row["a"] % 1000)),
        )
    pw.run()
    G.clear()
    return seen, clock, threads


def _arrivals_by_commit(seen, clock, threads) -> dict[int, list[float]]:
    """``commit time -> the arrivals of the rows a sink saw in it``, on the
    stages' clock: ``QueueReader.push`` stamped them with ``time.monotonic``
    (kept by the clock with each feed's thread), and the two clocks'
    distance is read here."""
    distance_ns = time.perf_counter_ns() - int(time.monotonic() * 1e9)
    arrived: dict[int, list[float]] = {}
    for place, rows in enumerate(seen):
        stamps = clock.pushed[threads[place]]
        assert len(stamps) == len(rows)
        for commit_time, _at, which in rows:
            arrived.setdefault(commit_time, []).append(
                stamps[which] * 1e9 + distance_ns
            )
    return arrived


class TestCommitTimeLine:
    """The stage table's time line (ISSUE 40): one record a commit of the
    run thread, and full collections as a stage of their own."""

    def test_one_record_for_every_commit_time_a_sink_saw(self):
        seen, _clock, _threads = _run_two_feeds()
        assert [len(rows) for rows in seen] == [12, 5]
        commits = tracing.commit_timeline()
        times = [record["time"] for record in commits]
        # the first commit, the data commits and the last one: each once
        assert len(times) == len(set(times))
        assert times == sorted(times)
        totals = tracing.stage_totals()["stages"]
        assert len(commits) == totals["commit"]["calls"]
        by_time = {record["time"]: record for record in commits}
        for rows in seen:
            for commit_time, at, _which in rows:
                record = by_time[commit_time]  # a KeyError: no record
                assert record["t0_ns"] <= at <= record["t1_ns"]
                assert record["stages"]["sink.emit"]["calls"] >= 1
        # records lie in time order and apart, inside the run
        for before, after in zip(commits, commits[1:]):
            assert before["t0_ns"] < before["t1_ns"] <= after["t0_ns"]
        assert sum(r["t1_ns"] - r["t0_ns"] for r in commits) == (
            totals["commit"]["total_ns"]
        )

    def test_a_row_arrived_before_the_commit_that_took_it_began(
        self, monkeypatch
    ):
        """What a reader cuts a row's wait by: ``record.t0_ns`` less the
        row's own send is never negative, for any row of either feed."""
        seen, clock, threads = _run_two_feeds(monkeypatch)
        by_time = {r["time"]: r for r in tracing.commit_timeline()}
        arrived = _arrivals_by_commit(seen, clock, threads)
        assert sum(len(stamps) for stamps in arrived.values()) == 12 + 5
        for commit_time, stamps in arrived.items():
            record = by_time[commit_time]
            assert max(stamps) <= record["t0_ns"] + 50e3
        # a feed that pauses every fourth row cannot land in one commit
        assert len({t for t, _at, _which in seen[0]}) >= 2

    def test_the_stages_counts_are_of_the_records_oldest_rows(
        self, monkeypatch
    ):
        """``commit_wait_ns`` is of each commit's oldest row over all its
        drivers, until ``_commit_step`` read the clock a little inside the
        stage: the records' own ``t0_ns`` less the oldest push they took
        sum to it less that little."""
        seen, clock, threads = _run_two_feeds(monkeypatch)
        by_time = {r["time"]: r for r in tracing.commit_timeline()}
        counts = tracing.stage_totals()["stages"]["commit"]["counts"]
        arrived = _arrivals_by_commit(seen, clock, threads)
        assert len(arrived) >= 2
        waited = sum(
            by_time[commit_time]["t0_ns"] - min(stamps)
            for commit_time, stamps in arrived.items()
        )
        slack = len(arrived) * 200e3
        assert -slack <= counts["commit_wait_ns"] - waited <= slack
        assert 0 <= counts["arrival_to_poll_ns"] <= counts["commit_wait_ns"]

    def test_a_stage_entered_twice_folds_to_two_calls(self):
        table = tracing.StageTable()
        root = table.begin_run()
        with table.stage("before.any.commit"):
            pass
        with table.commit_stage() as commit:
            commit.time = 7
            with table.stage("twice") as first:
                pass
            with table.stage("once"):
                with table.commit_stage():  # an iterate body's: a child
                    pass
            with table.stage("twice") as second:
                pass
            second_t0 = second.t0
        with table.stage("after.the.commit"):
            pass
        table.end_run(root)
        (record,) = table.timeline()
        assert record["time"] == 7
        assert list(record["stages"]) == ["twice", "commit", "once"]
        twice = record["stages"]["twice"]
        rows = table.totals()["stages"]
        assert twice["calls"] == 2 == rows["twice"]["calls"]
        assert twice["first_t0_ns"] == first.t0 < second_t0
        assert twice["last_t1_ns"] > second_t0
        assert rows["twice"]["total_ns"] < (
            twice["last_t1_ns"] - twice["first_t0_ns"]
        )
        assert record["t0_ns"] <= twice["first_t0_ns"]
        assert twice["last_t1_ns"] <= record["t1_ns"]
        assert record["stages"]["once"]["calls"] == 1
        # the table's row has both commits, the record's interval its own
        assert rows["commit"]["calls"] == 2
        inner = record["stages"]["commit"]
        assert record["t1_ns"] - record["t0_ns"] == (
            rows["commit"]["total_ns"]
            - (inner["last_t1_ns"] - inner["first_t0_ns"])
        )

    def test_only_the_run_threads_commits_are_records(self):
        table = tracing.StageTable()
        root = table.begin_run()

        def elsewhere() -> None:
            with table.commit_stage():
                with table.stage("device.fetch_rows"):
                    pass

        with table.commit_stage() as commit:
            commit.time = 1
            worker = threading.Thread(target=elsewhere, name="other-runner")
            worker.start()
            worker.join()
        # a stage that is only named like a commit's is a stage like any
        with table.stage("commit"):
            pass
        table.end_run(root)
        (record,) = table.timeline()
        assert record["time"] == 1 and record["stages"] == {}
        assert "commit" in table.totals()["threads"]["other-runner"]
        assert table.totals()["stages"]["commit"]["calls"] == 2
        # a table no run has begun on keeps no record at all
        bare = tracing.StageTable()
        with bare.commit_stage():
            pass
        assert bare.timeline() == []

    def test_the_ring_never_passes_its_bound(self, monkeypatch):
        monkeypatch.setattr(tracing, "TIMELINE_COMMITS", 8)
        table = tracing.StageTable()
        root = table.begin_run()
        for i in range(30):
            with table.commit_stage() as commit:
                commit.time = i
            assert len(table.timeline()) == min(i + 1, 8)
        table.end_run(root)
        assert [r["time"] for r in table.timeline()] == list(range(22, 30))
        # the next run begins with an empty line
        root = table.begin_run()
        assert table.timeline() == []
        table.end_run(root)
        assert tracing.STAGES._timeline.maxlen == 1024

    def test_a_full_collection_is_a_stage_of_its_own(self):
        import gc

        table = tracing.StageTable()
        root = table.begin_run()
        with table.stage("interrupted"):
            gc.collect(1)  # a younger generation: no stage
            gc.collect()
        table.end_run(root)
        rows = table.totals()["stages"]
        assert rows["gc.full"]["calls"] == 1
        assert rows["gc.full"]["counts"] == {}
        assert rows["gc.full"]["total_ns"] > 0
        # the collection's time is out of the interrupted stage's own
        assert rows["interrupted"]["self_ns"] == (
            rows["interrupted"]["total_ns"] - rows["gc.full"]["total_ns"]
        )
        assert _self_sum(table.totals()) == table.totals()["run_wall_ns"]

    def test_a_collection_inside_a_commit_is_folded_into_its_record(self):
        import gc

        table = tracing.StageTable()
        root = table.begin_run()
        gc.collect()  # between commits: in the table alone
        with table.commit_stage() as commit:
            commit.time = 3
            gc.collect()

        def elsewhere() -> None:
            gc.collect()

        worker = threading.Thread(target=elsewhere, name="feed")
        worker.start()
        worker.join()
        table.end_run(root)
        (record,) = table.timeline()
        folded = record["stages"]["gc.full"]
        assert folded["calls"] == 1
        assert record["t0_ns"] <= folded["first_t0_ns"] < folded["last_t1_ns"]
        assert folded["last_t1_ns"] <= record["t1_ns"]
        totals = table.totals()
        assert totals["stages"]["gc.full"]["calls"] == 2
        assert totals["threads"]["feed"]["gc.full"]["calls"] == 1

    def test_a_collection_while_the_tables_lock_is_held_does_not_wait(self):
        """``totals()`` allocates under the lock; a collection that falls
        there opens a stage, on a thread that may have no table yet."""
        table = tracing.StageTable()
        root = table.begin_run()
        done: list = []

        def no_table_yet() -> None:
            with table._lock:
                table._on_collection("start", {"generation": 2})
                table._on_collection("stop", {"generation": 2})
            done.append(table.totals()["threads"]["reader"]["gc.full"]["calls"])

        worker = threading.Thread(target=no_table_yet, name="reader", daemon=True)
        worker.start()
        worker.join(10)
        table.end_run(root)
        assert done == [1]

    def test_the_collectors_hook_is_gone_after_the_run(self):
        import gc

        def hooks() -> list:
            return [
                hook for hook in gc.callbacks
                if getattr(hook, "__self__", None) is tracing.STAGES
            ]

        assert hooks() == []
        during: list = []

        @tracing.traced_run
        def run(fail: bool) -> None:
            during.append(len(hooks()))
            with tracing.commit_stage():
                inner = tracing.STAGES.begin_run()  # a run inside a run
                tracing.STAGES.end_run(inner)
                during.append(len(hooks()))
                if fail:
                    raise RuntimeError("the run raised")

        run(False)
        assert hooks() == []
        with pytest.raises(RuntimeError):
            run(True)
        assert hooks() == []
        assert during == [1, 1, 1, 1]
        _run_counting_stream(n=3)
        assert hooks() == []

    def test_stage_totals_has_the_keys_it_had(self):
        _run_counting_stream(n=6)
        totals = tracing.stage_totals()
        assert set(totals) == {"run_wall_ns", "running", "stages", "threads"}
        for row in totals["stages"].values():
            assert set(row) == {"calls", "total_ns", "self_ns", "wait", "counts"}
        line = tracing.commit_timeline()
        assert len(line) == totals["stages"]["commit"]["calls"]
        for record in line:
            assert set(record) == {"time", "t0_ns", "t1_ns", "stages"}
            for folded in record["stages"].values():
                assert set(folded) == {"first_t0_ns", "last_t1_ns", "calls"}
        json.dumps(line)  # plain dicts, lists and numbers all the way down


@pytest.fixture(scope="class")
def rag_run(tmp_path_factory):
    """One toy RAG ``pw.run()`` (HashTokenizer, MiniLM at 16 tokens,
    ``DataIndex(TpuKnnFactory)`` on the CPU backend) with every commit
    sampled, inside a ``jax.profiler`` session."""
    import jax
    import numpy as np

    import pathway_tpu as pw
    from pathway_tpu.engine import device_ops
    from pathway_tpu.internals.parse_graph import G
    from pathway_tpu.stdlib.indexing import DataIndex, TpuKnnFactory
    from pathway_tpu.xpacks.llm.embedders import TpuEncoderEmbedder

    G.clear()
    embedder = TpuEncoderEmbedder("minilm_l6", max_len=16, max_batch_size=8)
    docs_acked = threading.Event()

    class Docs(pw.io.python.ConnectorSubject):
        def run(self) -> None:
            for i in range(N_DOCS):
                self.next(doc_id=i, text=f"w{i} w{i + 1} w{i % 5}")

    class Queries(pw.io.python.ConnectorSubject):
        def run(self) -> None:
            # the queries come once the documents are in and the pump,
            # finding nothing to poll, has slept at least once
            docs_acked.wait(timeout=120)
            pause = threading.Event()  # never set: a bounded wait
            for _ in range(12000):
                if "pump.sleep" in tracing.stage_totals()["stages"]:
                    break
                pause.wait(timeout=0.01)
            for i in range(N_QUERIES):
                self.next(query_id=i, text=f"w{i} w{i + 1}")

    docs = pw.io.python.read(
        Docs(), schema=pw.schema_from_types(doc_id=int, text=str),
        autocommit_duration_ms=20,
    )
    docs = docs.select(doc_id=pw.this.doc_id, emb=embedder(pw.this.text))
    index = DataIndex(
        docs,
        TpuKnnFactory(
            dimensions=embedder.get_embedding_dimension(), metric="cos",
            capacity=64,
        ),
        docs.emb,
    )
    queries = pw.io.python.read(
        Queries(), schema=pw.schema_from_types(query_id=int, text=str),
        autocommit_duration_ms=20,
    )
    queries = queries.select(
        query_id=pw.this.query_id, qemb=embedder(pw.this.text)
    )
    answers = index.query_as_of_now(queries, queries.qemb, number_of_matches=2)
    rows: list = []

    def on_doc(key, row, time, is_addition) -> None:
        rows.append(np.asarray(row["emb"]).shape)
        if len(rows) == N_DOCS:
            docs_acked.set()

    pw.io.subscribe(docs, on_change=on_doc)
    pw.io.subscribe(
        answers,
        on_change=lambda key, row, time, is_addition: rows.append(
            row["_pw_index_reply_ids"]
        ),
    )
    kernels_before = dict(device_ops.kernel_ns())
    trace_dir = tmp_path_factory.mktemp("xplane")
    tracing.TRACER.configure(enabled=True, sample=1, clear=True)
    try:
        with jax.profiler.trace(str(trace_dir)):
            pw.run()
        yield {
            "rows": rows,
            "totals": tracing.stage_totals(),
            "traces": tracing.TRACER.traces(),
            "trace_dir": trace_dir,
            "kernels_before": kernels_before,
            "kernels_after": dict(device_ops.kernel_ns()),
        }
    finally:
        tracing.TRACER.drop()
        tracing.TRACER.configure(enabled=False, clear=True)
        tracing.TRACER.epoch = 0
        G.clear()


class TestStagesOfARagRun:
    def test_every_stage_of_the_table_appears_with_its_counts(self, rag_run):
        assert len(rag_run["rows"]) == N_DOCS + N_QUERIES
        stages = rag_run["totals"]["stages"]
        assert set(RAG_STAGES) <= set(stages), set(RAG_STAGES) - set(stages)
        for name, counts in RAG_STAGES.items():
            assert stages[name]["calls"] >= 1
            assert set(counts) <= set(stages[name]["counts"]), name
        assert stages["udf.batch"]["counts"]["rows"] == N_DOCS + N_QUERIES
        assert stages["embed.pad"]["counts"]["rows"] == N_DOCS + N_QUERIES
        assert stages["knn.add.host"]["counts"]["rows"] == N_DOCS
        assert stages["knn.add.dispatch"]["counts"]["rows"] == N_DOCS
        assert stages["knn.search.fetch"]["counts"]["queries"] == N_QUERIES
        # a sink emits its rows itself or leaves them to the completion
        # worker (the documents', where the encoder has not finished), whose
        # own ``sink.emit`` counts what it was handed
        emitted = stages["sink.emit"]["counts"]
        deferred = emitted.get("deferred_rows", 0)
        assert emitted["rows"] + deferred == N_DOCS + N_QUERIES
        handed = sum(
            table["sink.emit"]["counts"]["rows"]
            for table in rag_run["totals"]["threads"].values()
            if "sink.emit" in table
        )
        assert handed == deferred
        # a dispatch is a piece of a chunk; texts of one length are one piece
        assert stages["embed.dispatch"]["calls"] == stages["embed.pad"]["counts"]["pieces"]
        assert stages["embed.dispatch"]["calls"] == stages["udf.batch"]["calls"]
        assert stages["embed.dispatch"]["counts"]["tokens"] == (
            stages["embed.tokenize"]["counts"]["tokens"]
        )
        # int32 ids, and nothing else, go up for a text: 4 bytes a padded token
        assert stages["embed.dispatch"]["counts"]["h2d_bytes"] == (
            4 * stages["embed.pad"]["counts"]["padded_tokens"]
        )

    def test_the_partition_holds_with_device_stages(self, rag_run):
        totals = rag_run["totals"]
        assert _self_sum(totals) == totals["run_wall_ns"]
        # children lie inside their parents
        stages = totals["stages"]
        inside = sum(
            stages[name]["total_ns"]
            for name in ("embed.tokenize", "embed.pad", "embed.dispatch",
                         "embed.rows_out")
        )
        assert inside <= stages["udf.batch"]["total_ns"]
        assert stages["udf.batch"]["total_ns"] <= stages["commit"]["total_ns"]

    def test_stages_that_block_on_the_device_are_flagged_wait(self, rag_run):
        stages = rag_run["totals"]["stages"]
        waits = {name for name, row in stages.items() if row["wait"]}
        assert {"knn.search.fetch", "commit.device_wait"} <= waits
        assert waits <= {
            "knn.search.fetch", "commit.device_wait", "device.fetch_rows"
        }
        # the completion worker's fetches, and the emissions that wait for
        # them in the run thread's place, are rows of its own thread
        for table in rag_run["totals"]["threads"].values():
            assert set(table) <= {"device.fetch_rows", "sink.emit"}, table

    def test_a_knn_enqueue_is_not_device_kernel_time(self, rag_run):
        new = {
            name
            for name, ns in rag_run["kernels_after"].items()
            if ns != rag_run["kernels_before"].get(name, 0)
        }
        assert not {"knn_update", "knn_search"} & new
        for trace in rag_run["traces"]:
            assert not any(
                "knn" in name for name in trace["device_kernel_ns"]
            )

    def test_a_profiler_session_holds_the_stages_as_annotations(self, rag_run):
        from jax.profiler import ProfileData

        paths = sorted(rag_run["trace_dir"].glob(
            "plugins/profile/*/*.xplane.pb"
        ))
        assert paths, "the profiler session wrote no .xplane.pb"
        data = ProfileData.from_file(str(paths[-1]))
        names = {
            ev.name
            for plane in data.planes
            for line in plane.lines
            for ev in line.events
            if ev.name.startswith("pw:")
        }
        assert {"pw:commit", "pw:embed.dispatch", "pw:udf.batch",
                "pw:knn.add.dispatch", "pw:pump.poll"} <= names

    def test_a_sampled_commit_exports_the_new_stages(self, rag_run):
        traces = [t for t in rag_run["traces"] if t.get("kind") is None]
        assert traces
        events = tracing.validate_chrome_trace(tracing.chrome_trace(traces))
        names = {e["name"] for e in events if e.get("ph") == "X"}
        assert {
            "udf.batch", "embed.tokenize", "embed.dispatch", "knn.update",
            "knn.add.dispatch", "knn.search.fetch", "sink.emit",
            "op.BatchApplyNode", "commit.device_stage",
        } <= names
        by_name = {e["name"]: e for e in events if e.get("ph") == "X"}
        assert by_name["knn.search.fetch"]["cat"] == "device_wait"
        assert by_name["op.BatchApplyNode"]["cat"] == "op"
        assert by_name["sink.emit"]["cat"] == "sink"
        assert by_name["udf.batch"]["args"]["rows"] >= 1
        for trace in traces:
            cp = trace["critical_path"]
            if not cp["clamped"]:
                assert cp["queue_wait_s"] + cp["exchange_s"] + cp[
                    "device_s"
                ] + cp["host_compute_s"] == pytest.approx(
                    cp["wall_s"], rel=0.01, abs=1e-5
                )
