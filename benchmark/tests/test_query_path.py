"""The readers of the program's time line (``layer_metrics/query_path.py``,
``chat_call_device.py``, ``gc_full_share.py``; ISSUE 40) on a hand-made
``Observed``, time line and trace: the four segments sum to a query's whole
wait; a commit cut by either end of the trace is left out; the distance
between the host's clock and the trace's is recovered; each reader gives
``None``, and raises nothing, on a program that keeps no time line."""

from __future__ import annotations

import json
import os
import types

import numpy as np
import pytest

import readers
import trace as trace_mod
from conftest import BENCH, ROOT
from pathway_tpu.internals import tracing

LAYER_METRICS = os.path.join(BENCH, "layer_metrics")
MS = 1_000_000  # ns
#: the trace's clock runs this far ahead of the host's perf_counter_ns
CLOCK_DISTANCE = 7_000_000_123
HOST, DEVICE = "/host:CPU", "/device:TPU:0"
RUN_THREAD, FEED_THREAD = "python3#3", "python3#5"


def _record(time, t0_ms, t1_ms, stages):
    return {
        "time": time, "t0_ns": t0_ms * MS, "t1_ns": t1_ms * MS,
        "stages": {
            name: {"first_t0_ns": a * MS, "last_t1_ns": b * MS, "calls": calls}
            for name, (a, b, calls) in stages.items()
        },
    }


#: commits 10..14, host ms. 10 begins before the traced part (1,000 ms to
#: 3,000 ms) and 14 ends after it; 12 holds no query and makes no call
RECORDS = [
    _record(10, 900, 1_150, {"chat.batch": (950, 1_140, 1), "knn.search": (910, 920, 1)}),
    _record(11, 1_200, 1_600, {"chat.batch": (1_230, 1_590, 2), "knn.search": (1_205, 1_215, 1)}),
    _record(12, 1_700, 1_740, {"knn.update": (1_701, 1_735, 1)}),
    _record(13, 1_800, 2_300, {"chat.batch": (1_850, 2_290, 1), "knn.search": (1_810, 1_822, 1)}),
    _record(14, 2_800, 3_100, {"chat.batch": (2_830, 3_090, 1), "knn.search": (2_805, 2_815, 1)}),
]
TIMELINE = RECORDS
#: (query, sent ms, its commit, ack ms); the last slot of every array is the primer's
QUERIES = [(0, 1_000, 11, 1_595), (1, 1_100, 11, 1_596), (2, 1_300, 13, 2_295), (3, 1_790, 13, 2_296), (4, 2_500, 14, 3_095)]


def _obs(lost: int | None = None):
    n = len(QUERIES) + 2  # one query never acknowledged, and the primer
    seen = types.SimpleNamespace(
        sent=np.full(n, np.nan), ack=np.full(n, np.nan), commit=np.full(n, -1, np.int64),
    )
    for i, sent, commit, ack in QUERIES:
        seen.sent[i], seen.commit[i], seen.ack[i] = sent / 1e3, commit, ack / 1e3
    seen.sent[5] = 2.9  # sent, never acknowledged
    seen.sent[-1], seen.commit[-1], seen.ack[-1] = 0.5, 10, 1.145  # the primer
    if lost is not None:
        seen.commit[lost] = 99
    return types.SimpleNamespace(queries=seen)


def _events(distance: int = CLOCK_DISTANCE):
    """The trace of host seconds 1.0 to 3.0: commit 10 began before the
    session and is not in it; commit 14's annotation closed after ``stop``
    was read and is. An annotation opens 3 us before its stage's clock is
    read and closes 2 us after."""
    def at(ms: float) -> float:
        return ms * MS + distance

    events = [
        trace_mod.Event(HOST, RUN_THREAD, "pw:commit", at(t0) - 3_000, (t1 - t0) * MS + 5_000)
        for t0, t1 in ((1_200, 1_600), (1_700, 1_740), (1_800, 2_300), (2_800, 3_100))
    ]
    events += [
        trace_mod.Event(HOST, RUN_THREAD, "pw:chat.batch", at(1_230), 360 * MS),
        trace_mod.Event(HOST, FEED_THREAD, "pw:commit", at(1_250), 10 * MS),  # another runner's
        trace_mod.Event(HOST, FEED_THREAD, "bench:generator_send", at(1_300), MS),
    ]
    for name, start, dur in (
        # commit 11's two calls: 40 + 100 and 50 + 120 ms on the device
        ("jit_chat_prefill(1)", 1_235, 40), ("jit_chat_decode(2)", 1_275, 100),
        ("jit_chat_prefill(1)", 1_400, 50), ("jit_chat_decode(2)", 1_450, 120),
        ("jit__lambda(3)", 1_702, 20),  # commit 12: the encoder
        ("jit_chat_prefill(1)", 1_860, 150), ("jit_chat_decode(2)", 2_010, 270),  # commit 13
        ("jit_chat_prefill(1)", 2_835, 60), ("jit_chat_decode(2)", 2_895, 190),  # commit 14: cut
    ):
        events.append(trace_mod.Event(DEVICE, "XLA Modules", name, at(start), dur * MS))
        events.append(trace_mod.Event(DEVICE, "XLA Ops", "%fusion.1 = f32[8]", at(start), dur * MS))
    return events


def _ctx(events=None, lost=None):
    trace = None if events is None else {"events": events, "start": 1.0, "stop": 3.0, "window_s": 2.0}
    return types.SimpleNamespace(obs=_obs(lost), trace=trace)


@pytest.fixture
def with_timeline(monkeypatch):
    monkeypatch.setattr(tracing, "commit_timeline", lambda: TIMELINE)


@pytest.fixture(scope="module")
def query_path():
    return readers.load_module("query_path_under_test", os.path.join(LAYER_METRICS, "query_path.py"))


@pytest.fixture(scope="module")
def chat_call_device():
    return readers.load_module("chat_call_device_under_test", os.path.join(LAYER_METRICS, "chat_call_device.py"))


def test_the_four_segments_sum_to_every_querys_whole_wait(query_path):
    queries, cut, why = query_path.segments(_obs(), TIMELINE, "chat.batch")
    assert why is None and list(queries) == [0, 1, 2, 3, 4]  # not the lost one, not the primer
    whole = np.array([ack - sent for _i, sent, _commit, ack in QUERIES], dtype=float)
    assert np.allclose(cut.sum(axis=1), whole, atol=1e-6)
    assert abs(cut.mean(axis=0).sum() - whole.mean()) < 1e-6
    # query 0: sent at 1,000, its commit 1,200 to 1,600, the call 1,230 to 1,590, the sink at 1,595
    assert np.allclose(cut[0], [200, 30, 360, 5], atol=1e-6)
    # query 3 was sent 10 ms before its commit began
    assert np.allclose(cut[3], [10, 50, 440, 6], atol=1e-6)


@pytest.mark.parametrize(
    "segment, serve, expected",
    [
        ("queued", "chat.batch", 200.0), ("to_serve", "chat.batch", 30.0), ("serve", "chat.batch", 360.0),
        ("to_sink", "chat.batch", 5.0), ("serve", "knn.search", 10.0), ("to_serve", "knn.search", 5.0),
    ],
)
def test_a_reading_is_the_median_of_its_segment(query_path, with_timeline, segment, serve, expected):
    assert query_path.read(_ctx(), segment=segment, serve=serve) == pytest.approx(expected)


def test_a_query_that_cannot_be_cut_reads_none(query_path, with_timeline, monkeypatch, capsys):
    # its commit has left the ring
    assert query_path.read(_ctx(lost=2), segment="queued", serve="chat.batch") is None
    assert "not in the ring" in capsys.readouterr().err
    # its record lacks the stage that serves it
    assert query_path.read(_ctx(), segment="queued", serve="no.such.stage") is None
    assert "no stage" in capsys.readouterr().err
    # a cell that sends no queries
    none = types.SimpleNamespace(queries=types.SimpleNamespace(
        sent=np.full(0, np.nan), ack=np.full(0, np.nan), commit=np.full(0, -1, np.int64)))
    assert query_path.read(types.SimpleNamespace(obs=none), segment="queued", serve="chat.batch") is None
    # a program from before the time line
    monkeypatch.delattr(tracing, "commit_timeline")
    assert query_path.read(_ctx(), segment="queued", serve="chat.batch") is None
    assert "no time line" in capsys.readouterr().err


def test_a_commit_cut_by_either_end_of_the_trace_is_left_out(chat_call_device):
    pairs, offset, spread = chat_call_device.match_commits(_events(), RECORDS, 1.0, 3.0)
    # 10 is a record with no event, 14 an event whose record ends after the stop
    assert [record["time"] for record, _event in pairs] == [11, 12, 13]
    assert offset == pytest.approx(CLOCK_DISTANCE - 3_000, abs=1)
    assert spread < 100_000
    calls = chat_call_device.calls_on_device(_events(), pairs, offset, ["jit_chat_prefill"])
    assert [(record["time"], ns / MS) for record, ns, _end in calls] == [(11, 90.0), (13, 150.0)]


@pytest.mark.parametrize("distance", [0, CLOCK_DISTANCE, -3_600_000_000_000])
def test_the_clocks_distance_is_recovered(chat_call_device, with_timeline, distance, capsys):
    ctx = _ctx(_events(distance))
    # three calls in the two matched commits that made one: (40 + 50 + 150) / 3, (100 + 120 + 270) / 3
    assert chat_call_device.read(ctx, what="prefill_ms", patterns=["jit_chat_prefill"]) == pytest.approx(80.0)
    assert chat_call_device.read(ctx, what="decode_ms", patterns=["jit_chat_decode"]) == pytest.approx(490 / 3)
    assert "3 commits matched" in capsys.readouterr().err
    # the last prefill of commit 11 ends at 1,450 (less the annotation's 3 us), of 13 at 2,010:
    # queries 0 to 3 waited 450, 350, 710, 220 ms for it
    first = chat_call_device.read(ctx, what="first_token_ms", patterns=["jit_chat_prefill"])
    assert first == pytest.approx((350 + 450) / 2 + 0.003, abs=1e-3)


def test_no_trace_no_time_line_or_no_match_reads_none(chat_call_device, with_timeline, monkeypatch, capsys):
    assert chat_call_device.read(_ctx(), what="prefill_ms", patterns=["jit_chat_prefill"]) is None
    # a trace whose commits are none of the time line's
    strangers = [e._replace(dur_ns=e.dur_ns * 3) if e.name == "pw:commit" else e for e in _events()]
    assert chat_call_device.read(_ctx(strangers), what="prefill_ms", patterns=["jit_chat_prefill"]) is None
    # commits match, no execution of that name
    assert chat_call_device.read(_ctx(_events()), what="prefill_ms", patterns=["no_such_program"]) is None
    monkeypatch.delattr(tracing, "commit_timeline")
    assert chat_call_device.read(_ctx(_events()), what="decode_ms", patterns=["jit_chat_decode"]) is None
    assert "no time line" in capsys.readouterr().err


def test_full_collections_of_every_thread_over_the_runs_wall(monkeypatch):
    read = readers.find("gc_full_share", LAYER_METRICS)
    row = {"calls": 1, "total_ns": 0, "self_ns": 0, "wait": False, "counts": {}}
    totals = {
        "run_wall_ns": 50_000 * MS, "running": False,
        "stages": {"gc.full": dict(row, calls=4, total_ns=1_600 * MS), "commit": dict(row, total_ns=9_000 * MS)},
        "threads": {"Thread-1 (runner)": {"gc.full": dict(row, total_ns=400 * MS)}, "pw-device-pipeline": {}},
    }
    monkeypatch.setattr(tracing, "stage_totals", lambda: totals)
    assert read(None) == pytest.approx(4.0)
    totals["stages"].pop("gc.full")
    totals["threads"].clear()
    assert read(None) == 0.0  # the stage exists and no full collection ran
    monkeypatch.delattr(tracing, "GC_STAGE")
    assert read(None) is None  # a program that does not name the collector


def test_a_real_run_leaves_a_time_line_the_readers_can_cut(query_path):
    """Through the program itself, at a toy size: every acknowledged
    query's commit is in the ring and holds the search, the four segments
    sum to its wait, and ``dev/timeline.py`` prints the same."""
    import time

    import jax

    import harness
    import toy

    _result, evidence = harness.measure(toy.cell("rag"), 2**31 + 7, 2.0, False, jax.devices(), time.time())
    obs, line = evidence["obs"], tracing.commit_timeline()
    assert line and all(r["time"] is not None for r in line)
    queries, cut, why = query_path.segments(obs, line, "knn.search")
    assert why is None and len(queries) == obs.queries.acked - 1  # less the primer
    whole = (obs.queries.ack[queries] - obs.queries.sent[queries]) * 1e3
    assert np.allclose(cut.sum(axis=1), whole, atol=1e-3)
    assert (cut[:, 1:] >= 0).all()  # a commit's stages lie in order; a send may end inside its commit
    assert cut[:, 0].min() > -5.0
    assert readers.find("gc_full_share", LAYER_METRICS)(None) >= 0.0
    timeline = readers.load_module("timeline_under_test", os.path.join(BENCH, "dev", "timeline.py"))
    summary, lines = timeline.summarize(types.SimpleNamespace(obs=obs, trace=None), line, "knn.search")
    assert summary["commits"] == len(line)
    assert summary["queries"]["cut"] == len(queries)
    assert abs(summary["queries"]["identity_residual_ms"]) < 0.01
    assert sum(text.startswith("commit ") for text in lines) == len(line)
    assert sum(text.startswith("query ") for text in lines) == len(queries)


def test_the_new_metrics_are_the_ones_benchmark_json_lists():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    listed = {m["name"]: m for m in bench["per_layer"]}
    cells = {"query": "bge-live-rag", "answer": "dsv2lite-rag-answer",
             "cmda": "command-a-plus-rag-answer", "lfm2": "lfm2-rag-answer", "ingest": "minilm-backfill"}
    new = [
        name for name in listed
        if name.startswith(("query_queued_", "query_to_serve_", "query_serve_", "query_to_sink_",
                            "chat_prefill_device_ms", "chat_decode_device_ms", "query_first_token_",
                            "gc_full_share"))
    ]
    assert len(new) == 26
    for name in new:
        metric = listed[name]
        with open(os.path.join(LAYER_METRICS, name + ".json")) as fh:
            body = json.load(fh)
        assert metric["workloads"] == [cells[name.rsplit(".", 1)[1]]]
        assert metric["better"] == "lower"
        assert metric["moves"] == ("docs_per_s" if name.endswith(".ingest") else "query_p50_ms")
        device = body["reader"] == "chat_call_device"
        assert metric["source"] == ("device_trace" if device else "program_counter")
        assert callable(readers.find(body["reader"], LAYER_METRICS))
        if body["reader"] == "query_path":
            assert set(body["params"]) == {"segment", "serve"}
            assert body["params"]["serve"] == ("knn.search" if name.endswith(".query") else "chat.batch")
