"""Observability: operator probes, console dashboard, Prometheus endpoint
(reference: internals/monitoring.py:56-228, src/engine/http_server.rs:22-194,
graph.rs:500-542 probes)."""

import urllib.request

import pathway_tpu as pw
from pathway_tpu.internals.monitoring import (
    MonitoringHttpServer,
    MonitoringLevel,
    StatsMonitor,
)
from pathway_tpu.internals.runner import GraphRunner


def _pipeline():
    t = pw.debug.table_from_rows(
        pw.schema_from_types(word=str), [("a",), ("b",), ("a",)]
    )
    return t.groupby(t.word).reduce(word=t.word, cnt=pw.reducers.count())


class TestOperatorProbes:
    def test_scheduler_collects_stats(self):
        counts = _pipeline()
        runner = GraphRunner()
        runner.monitor = StatsMonitor(MonitoringLevel.ALL)
        node = runner.build(counts)
        runner.run()
        sched = runner.monitor.scheduler
        assert sched is not None and sched.stats
        st = sched.stats[node.index]
        assert st.insertions >= 2  # two groups emitted
        assert st.time_spent > 0
        assert runner.monitor.commits >= 1

    def test_connector_stats_flow(self, tmp_path):
        src = tmp_path / "in.jsonl"
        src.write_text('{"w": "x"}\n{"w": "y"}\n')

        class S(pw.Schema):
            w: str

        t = pw.io.jsonlines.read(src, schema=S, mode="static")
        runner = GraphRunner()
        runner.monitor = StatsMonitor(MonitoringLevel.IN_OUT)
        runner.build(t)
        runner.run()
        (stats,) = runner.monitor.connectors.values()
        assert stats.entries == 1  # one file payload
        assert stats.finished


class TestPrometheusEndpoint:
    def test_scrapeable_metrics(self):
        counts = _pipeline()
        runner = GraphRunner()
        monitor = StatsMonitor(MonitoringLevel.ALL)
        runner.monitor = monitor
        runner.build(counts)
        runner.run()
        server = MonitoringHttpServer(monitor, port=0)
        try:
            body = (
                urllib.request.urlopen(
                    f"http://127.0.0.1:{server.port}/metrics", timeout=5
                )
                .read()
                .decode()
            )
        finally:
            server.stop()
        assert "pathway_commits_total" in body
        assert "pathway_operator_rows" in body
        assert "pathway_uptime_seconds" in body

    def test_unknown_path_404(self):
        monitor = StatsMonitor()
        server = MonitoringHttpServer(monitor, port=0)
        try:
            import urllib.error

            try:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{server.port}/nope", timeout=5
                )
                raise AssertionError("expected 404")
            except urllib.error.HTTPError as e:
                assert e.code == 404
        finally:
            server.stop()


class TestTimeseriesAndProfileRoutes:
    def _get(self, port, path):
        return urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=5
        ).read().decode()

    def test_timeseries_index_and_family_query(self):
        import json

        from pathway_tpu.internals import timeseries

        timeseries.STORE.clear()
        now = __import__("time").time()
        timeseries.STORE.observe(
            "route_fam", {"worker": "0"}, 5.0, t=now - 1
        )
        timeseries.STORE.observe(
            "route_fam", {"worker": "1"}, 6.0, t=now - 1
        )
        server = MonitoringHttpServer(StatsMonitor(), port=0)
        try:
            index = json.loads(self._get(server.port, "/timeseries"))
            assert {"families", "stats", "slos"} <= set(index)
            assert any(
                f["family"] == "route_fam" for f in index["families"]
            )
            result = json.loads(
                self._get(
                    server.port,
                    "/timeseries?family=route_fam&window=30&worker=1",
                )
            )
            assert result["family"] == "route_fam"
            assert result["window_s"] == 30.0
            # the extra query param filtered on the worker label
            assert len(result["series"]) == 1
            assert result["series"][0]["labels"]["worker"] == "1"
            assert result["series"][0]["points"][0][1] == 6.0
        finally:
            server.stop()
            timeseries.STORE.clear()

    def test_timeseries_bad_window_is_400(self):
        import json
        import urllib.error

        server = MonitoringHttpServer(StatsMonitor(), port=0)
        try:
            try:
                self._get(
                    server.port, "/timeseries?family=x&window=soon"
                )
                raise AssertionError("expected 400")
            except urllib.error.HTTPError as e:
                assert e.code == 400
                assert "window" in json.loads(e.read().decode())["error"]
        finally:
            server.stop()

    def test_profile_404_when_profiler_idle(self):
        import urllib.error

        from pathway_tpu.internals.profiling import PROFILER

        PROFILER.configure(enabled=False, clear=True)
        server = MonitoringHttpServer(StatsMonitor(), port=0)
        try:
            try:
                self._get(server.port, "/profile")
                raise AssertionError("expected 404")
            except urllib.error.HTTPError as e:
                assert e.code == 404
                assert b"PATHWAY_TPU_PROFILE" in e.read()
        finally:
            server.stop()

    def test_profile_serves_merged_document(self):
        import json

        from pathway_tpu.internals import profiling

        profiling.PROFILER.configure(enabled=False, clear=True)
        # a failover test that ran earlier in this process leaves the fence raised
        profiling.PROFILER.epoch = 0
        assert profiling.PROFILER.absorb(
            1,
            {
                "v": profiling.VERSION,
                "worker": 1,
                "pid": 999,
                "seq": 1,
                "epoch": 0,
                "wall_s": 1.0,
                "rate_hz": 50.0,
                "samples": [["operator", "graph:process", 0.5, 5]],
                "sample_count": 5,
                "dropped_stacks": 0,
                "device": {},
            },
        )
        server = MonitoringHttpServer(StatsMonitor(), port=0)
        try:
            doc = json.loads(self._get(server.port, "/profile"))
        finally:
            server.stop()
            profiling.PROFILER.configure(enabled=False, clear=True)
        profiling.validate_profile(doc)
        assert doc["workers"]["1"]["sample_count"] == 5
        assert doc["phases"]["operator"] == 0.5


class TestDashboard:
    def test_live_table_renders(self):
        import io

        from rich.console import Console

        buf = io.StringIO()
        monitor = StatsMonitor(
            MonitoringLevel.IN_OUT, console=Console(file=buf, width=80)
        )
        monitor.connector("fs:/data").entries = 5
        monitor.start_live()
        monitor.on_commit(1, 0.0)
        monitor.stop()
        out = buf.getvalue()
        assert "fs:/data" in out and "5" in out

    def test_pw_run_with_monitoring(self, tmp_path):
        out = tmp_path / "o.jsonl"
        t = _pipeline()
        pw.io.jsonlines.write(t, out)
        pw.run(monitoring_level=MonitoringLevel.NONE, with_http_server=False)
        assert out.exists()


class TestViz:
    def test_table_viz_live_render(self):
        import io

        from rich.console import Console

        buf = io.StringIO()
        t = pw.debug.table_from_rows(
            pw.schema_from_types(word=str, n=int), [("alpha", 1), ("beta", 2)]
        )
        from pathway_tpu.stdlib.viz import table_viz

        table_viz(t, title="demo", console=Console(file=buf, width=80))
        pw.run()
        out = buf.getvalue()
        assert "alpha" in out and "beta" in out and "demo" in out

    def test_table_show_method(self):
        import io

        from rich.console import Console

        buf = io.StringIO()
        t = pw.debug.table_from_rows(pw.schema_from_types(x=int), [(5,)])
        t.show(console=Console(file=buf, width=60))
        pw.run()
        assert "5" in buf.getvalue()


class TestTelemetryPipeline:
    """Periodic process metrics + per-operator counters (reference
    telemetry.rs:195-407 — the sampler runs whenever telemetry is on,
    OTLP export only when an endpoint is reachable)."""

    def test_sampler_collects_process_and_operator_metrics(
        self, monkeypatch
    ):
        import time

        import pathway_tpu as pw
        from pathway_tpu.internals import telemetry
        from pathway_tpu.internals.parse_graph import G

        monkeypatch.setenv("PATHWAY_PROCESS_METRICS", "1")
        monkeypatch.setenv("PATHWAY_TELEMETRY_INTERVAL_S", "0.05")
        G.clear()

        class Feed(pw.io.python.ConnectorSubject):
            def run(self):
                for i in range(50):
                    self.next(k=i % 5, v=i)
                time.sleep(0.3)  # keep the run alive past one interval

        t = pw.io.python.read(
            Feed(),
            schema=pw.schema_from_types(k=int, v=int),
            autocommit_duration_ms=None,
        )
        agg = t.groupby(pw.this.k).reduce(
            k=pw.this.k, s=pw.reducers.sum(pw.this.v)
        )
        pw.io.null.write(agg)
        pw.run()
        sample = telemetry.latest_process_metrics()
        assert sample.get("memory_rss_bytes", 0) > 0
        ops = sample.get("operators", {})
        assert ops, f"no operator counters in {sample}"
        assert any(
            st.get("insertions", 0) > 0 for st in ops.values()
        ), ops
        assert any("Groupby" in name for name in ops)

    def test_disabled_by_default(self, monkeypatch):
        from pathway_tpu.internals import telemetry

        monkeypatch.delenv("PATHWAY_TELEMETRY_SERVER", raising=False)
        monkeypatch.delenv("PATHWAY_PROCESS_METRICS", raising=False)
        telemetry.set_monitoring_config(server_endpoint=None)
        assert not telemetry.telemetry_enabled()


class TestInteractiveLayer:
    """Notebook interactive surface (reference internals/interactive.py):
    LiveTable display updates per commit, background interactive mode."""

    def test_live_table_updates_through_injected_handle(self):
        import time

        import pathway_tpu as pw
        from pathway_tpu.internals.parse_graph import G

        G.clear()

        class Handle:
            def __init__(self):
                self.updates = []

            def update(self, obj):
                self.updates.append(
                    obj.data if hasattr(obj, "data") else str(obj)
                )

        class Feed(pw.io.python.ConnectorSubject):
            def run(self):
                self.next(k=1, v=10)
                self.commit()
                time.sleep(0.2)
                self.next(k=2, v=20)
                self.commit()

        t = pw.io.python.read(
            Feed(),
            schema=pw.schema_from_types(k=int, v=int),
            autocommit_duration_ms=None,
        )
        handle = Handle()
        live = pw.LiveTable(t, display_handle=handle)
        pw.run()
        assert live.n_commits >= 2
        assert handle.updates, "display handle never updated"
        final = handle.updates[-1]
        assert "10" in final and "20" in final and "<table>" in final

    def test_enable_interactive_mode_runs_in_background(self):
        import time

        import pathway_tpu as pw
        from pathway_tpu.internals.parse_graph import G

        G.clear()
        seen = []

        class Feed(pw.io.python.ConnectorSubject):
            def run(self):
                for i in range(3):
                    self.next(v=i)
                    self.commit()
                    time.sleep(0.05)

        t = pw.io.python.read(
            Feed(),
            schema=pw.schema_from_types(v=int),
            autocommit_duration_ms=None,
        )
        pw.io.subscribe(
            t,
            on_change=lambda key, row, time, is_addition: seen.append(
                row["v"]
            ),
        )
        thread = pw.enable_interactive_mode()
        assert thread.is_alive() or seen  # cell returned immediately
        pw.stop_interactive_mode()
        assert sorted(seen) == [0, 1, 2]

    def test_table_repr_html_shows_schema(self):
        import pathway_tpu as pw
        from pathway_tpu.internals.parse_graph import G

        G.clear()
        t = pw.debug.table_from_rows(
            pw.schema_from_types(a=int, b=str), [(1, "x")]
        )
        h = t._repr_html_()
        assert "pw.Table" in h and ">a<" in h and ">b<" in h
