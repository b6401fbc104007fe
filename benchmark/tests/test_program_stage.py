"""The per-layer metrics read from the program's stage table
(``layer_metrics/program_stage.py``), on toy runs, and ``dev/gaps.py`` on a
made-up trace (the arithmetic it shares with the result line's
``idle_gaps`` is ``trace.idle_split``'s: ``test_yardstick.py``)."""

from __future__ import annotations

import importlib.util
import json
import math
import os

import pytest

import readers
import toy
import trace as trace_mod
from conftest import BENCH, ROOT

LAYER_METRICS = os.path.join(BENCH, "layer_metrics")
INGEST = [
    "pump_poll_share.ingest", "commit_overhead_share.ingest", "embed_host_us_per_doc.ingest",
    "embed_dispatch_us_per_doc.ingest", "index_add_host_us_per_doc.ingest", "h2d_bytes_per_doc.ingest",
]
RAG = [
    "commit_wait_ms.rag", "embed_host_us_per_doc.rag", "commit_overhead_share.query",
    "search_fetch_ms.query", "pump_blocked_share.query",
]


def _metric(name: str) -> dict:
    with open(os.path.join(LAYER_METRICS, name + ".json")) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def read():
    return readers.find("program_stage", LAYER_METRICS)


@pytest.fixture(scope="module")
def readings(read):
    """Each new metric read off the stage table its own kind of toy run
    left: ``.ingest`` after a backfill, ``.rag`` and ``.query`` after a
    live-rag run."""
    out = {}
    for mix, names in (("backfill", INGEST), ("rag", RAG)):
        assert toy.run(mix)["correct"] is True
        for name in names:
            out[name] = read(None, **_metric(name)["params"])
    return out


@pytest.mark.parametrize("name", INGEST + RAG)
def test_each_new_metric_reads_a_finite_number_off_a_toy_run(name, readings):
    assert _metric(name)["reader"] == "program_stage"
    value = readings[name]
    assert value is not None and math.isfinite(value) and value >= 0, (name, value)
    if name.endswith(("_share.ingest", "_share.query")):
        assert value <= 100.0


def test_new_metrics_are_the_ones_benchmark_json_lists():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        listed = {m["name"]: m for m in json.load(fh)["per_layer"]}
    for name in INGEST + RAG:
        cell = "minilm-backfill" if name in INGEST else "bge-live-rag"
        assert listed[name]["workloads"] == [cell] and listed[name]["source"] == "program_counter"
        assert listed[name]["better"] == "lower"


def test_a_stage_that_does_not_exist_reads_none(read, readings):
    assert read(None, numerator=["no.such.stage"], denominator=["@run_wall_ns"]) is None
    assert read(None, numerator=["commit"], denominator=["no.such.stage:calls"]) is None
    assert read(None, numerator=["no.such.*"], denominator=["@run_wall_ns"]) is None
    # optional terms and sums of nothing
    assert read(None, numerator=["commit", "-no.such.stage?"], denominator=["commit"]) == 1.0
    assert read(None, numerator=["commit"], denominator=["commit:no_such_count"]) is None


def test_a_program_without_a_stage_table_reads_none(read, monkeypatch):
    from pathway_tpu.internals import tracing

    monkeypatch.delattr(tracing, "stage_totals")
    assert read(None, **_metric("pump_poll_share.ingest")["params"]) is None


def test_the_reading_divides_sums_of_the_table(read, monkeypatch):
    from pathway_tpu.internals import tracing

    def row(total, self_, wait=False, **counts):
        return {"calls": 2, "total_ns": total, "self_ns": self_, "wait": wait, "counts": counts}

    table = {
        "run_wall_ns": 1000,
        "stages": {
            "commit": row(600, 100, commit_wait_ns=50),
            "udf.batch": row(300, 20, rows=10),
            "embed.dispatch": row(200, 200, h2d_bytes=640),
            "knn.search.fetch": row(80, 80, wait=True),
            "commit.device_wait": row(20, 20, wait=True),
            "sink.emit": row(100, 100, rows=10),
        },
        "threads": {},
    }
    monkeypatch.setattr(tracing, "stage_totals", lambda: table)
    assert read(None, **_metric("pump_blocked_share.query")["params"]) == 10.0
    assert read(None, **_metric("embed_host_us_per_doc.rag")["params"]) == pytest.approx(0.01)
    assert read(None, **_metric("commit_wait_ms.rag")["params"]) == pytest.approx(25e-6)
    assert read(None, **_metric("h2d_bytes_per_doc.ingest")["params"]) == 64.0
    # 200 ns in embed.dispatch over the 10 rows of udf.batch, whatever the calls: what PR 26's
    # 976.8 us a call hid (3.8 us a document, where the parent's 800.9 a call was 24)
    assert read(None, **_metric("embed_dispatch_us_per_doc.ingest")["params"]) == pytest.approx(0.02)
    # 600 - 300 (udf.batch) - 100 (sink.emit) - 20 (commit.device_wait); no knn.update, no knn.search
    assert read(None, **_metric("commit_overhead_share.query")["params"]) == pytest.approx(18.0)


def _gaps_module():
    spec = importlib.util.spec_from_file_location("gaps", os.path.join(BENCH, "dev", "gaps.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_gaps_py_reads_the_stages_share_of_the_idle_time():
    gaps = _gaps_module()
    E = trace_mod.Event
    host = ("/host:CPU", "python3#4")
    events = [
        E(*host, "pw:commit", 0, 100),
        E(*host, "pw:udf.batch", 10, 50),
        E(*host, "pw:embed.tokenize", 20, 30),
        E(*host, "pw:commit", 120, 30),
        E("/host:CPU", "python3#7", "pw:pipeline.complete", 0, 200),
        E("/device:TPU:0", "XLA Ops", "%fusion.1 = f32[8]", 0, 15),
        E("/device:TPU:0", "XLA Ops", "%fusion.2 = f32[8]", 55, 10),
        E("/device:TPU:0", "XLA Ops", "%fusion.3 = f32[8]", 130, 5),
    ]
    out = gaps.stage_gaps(events)
    # gap 1: 15..55 (udf.batch 5+5, embed.tokenize 30); gap 2: 65..130 (commit 35+10, no stage 20)
    assert dict(out["by_stage"]) == pytest.approx(
        {"embed.tokenize": 30e-9, "udf.batch": 10e-9, "commit": 45e-9, gaps.NO_STAGE: 20e-9}
    )
    assert out["thread"] == list(host) and out["chip"] == "/device:TPU:0"
    assert out["idle_s"] == pytest.approx(105e-9) and out["no_stage_share"] == pytest.approx(20 / 105)
