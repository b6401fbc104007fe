"""The yardstick's arithmetic: costs, schedules, the trace reduction, and
the names in BENCHMARK.json."""

from __future__ import annotations

import json
import os
import re

import numpy as np
import pytest

import costs
import readers
import trace as trace_mod
import traffic
from conftest import BENCH, HERE, ROOT

MINILM = {"hidden_size": 384, "intermediate_size": 1536, "num_hidden_layers": 6}
BGE = {"hidden_size": 768, "intermediate_size": 3072, "num_hidden_layers": 12}


def test_flops_and_bytes_match_hand_counts():
    # MiniLM, one 128-token sequence: 6 layers of 128*(8*384^2 + 4*384*1536) + 4*128^2*384
    assert costs.encoder_flops(128, MINILM) == 6 * (128 * 3_538_944 + 25_165_824) == 2_868_903_936
    assert 256 * costs.encoder_flops(128, MINILM) == 734_439_407_616  # a 256 x 128 step
    assert round(costs.encoder_flops(512, BGE) / 1e9, 1) == 96.6  # ISSUE 24's document
    # a 4M x 384 scan for 8 queries: vectors + 5 bytes a row + the scores once
    assert costs.knn_search_bytes(8, 4_194_304, 384) == 6_442_450_944 + 20_971_520 + 134_217_728
    assert costs.knn_search_flops(8, 4_194_304, 384) == 2 * 8 * 4_194_304 * 384
    peak = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
    seconds, bound = costs.roofline_seconds(
        costs.knn_search_flops(8, 4_194_304, 384), costs.knn_search_bytes(8, 4_194_304, 384), peak)
    assert bound == "bandwidth" and abs(seconds - 8.056e-3) < 1e-5
    seconds, bound = costs.roofline_seconds(
        256 * costs.encoder_flops(128, MINILM), costs.encoder_step_bytes(256, 128, MINILM), peak)
    assert bound == "compute" and abs(seconds - 3.728e-3) < 1e-5


def test_a_schedule_is_a_function_of_the_seed_alone():
    with open(os.path.join(BENCH, "traffic", "live-rag.json")) as fh:
        mix = json.load(fh)
    mix["queries"]["burst"] = {"on_s": 0.5, "off_s": 0.5}  # no committed mix bursts yet; the generator can
    a, b, c = (traffic.build(mix, seed, 4.0) for seed in (2**31 + 1, 2**31 + 1, 7))
    assert a.documents.texts == b.documents.texts and a.queries.texts == b.queries.texts
    assert np.array_equal(a.queries.due_s, b.queries.due_s)
    assert a.documents.texts != c.documents.texts
    # every seed: the same lengths and the same gaps, in another order
    assert sorted(a.documents.tokens) == sorted(c.documents.tokens)
    gaps_a, gaps_c = (np.sort(np.diff(s.documents.due_s)) for s in (a, c))  # all but each one's last
    assert np.allclose(np.percentile(gaps_a, [10, 50, 90, 99]), np.percentile(gaps_c, [10, 50, 90, 99]), rtol=0.02)
    assert len(a.queries.due_s) == round(mix["queries"]["rate_per_s"] * 4.0)
    # bursts: nothing is due in an "off" half-second
    assert np.all(np.mod(a.queries.due_s, 1.0) < 0.5)
    words = [len(t.split()) for t in a.documents.texts]
    assert words == list(a.documents.tokens - traffic.SPECIAL_TOKENS)
    assert 32 <= a.documents.tokens.min() and a.documents.tokens.max() <= 512
    # the mean is the named corpus's (BEIR Table 1, TREC-COVID: 160.77 words, and CLS and SEP)
    assert abs(traffic.token_counts(mix["documents"]["tokens"], 20000).mean() - 162.77) < 0.5


def test_trace_reduction_on_a_recorded_trace():
    """16 ms of a traced minilm-backfill window on a TPU v5 lite (PR 24):
    device programs, their operations (names cut to 48 characters) and the
    benchmark's host spans."""
    with open(os.path.join(HERE, "trace_small.json")) as fh:
        events = [trace_mod.Event(*e) for e in json.load(fh)]
    assert trace_mod.device_planes(events) == ["/device:TPU:0"]
    busy = trace_mod.busy_seconds(events)["/device:TPU:0"]
    ops = [e for e in events if e.line == "XLA Ops"]
    span = (max(e.start_ns + e.dur_ns for e in ops) - min(e.start_ns for e in ops)) / 1e9
    assert 0 < busy <= span
    assert busy <= sum(e.dur_ns for e in ops) / 1e9 + 1e-9  # a union, not a sum
    embed_s, embed_n = trace_mod.module_seconds(events, ["jit__lambda"])
    assert embed_n > 0 and 0 < embed_s <= 1.01 * busy  # a program spans its operations' gaps
    assert trace_mod.module_seconds(events, ["no_such_program"]) == (0.0, 0)
    top = trace_mod.top_device_ops(events)
    assert 0 < len(top) <= 10 and all(" = " not in name and len(name) < 64 for name, _ in top)
    assert top == sorted(top, key=lambda kv: -kv[1])
    gaps = trace_mod.idle_gaps(events)
    assert gaps and abs(sum(s for _, s in gaps) - (span - busy)) < 1e-6
    summary = trace_mod.summarize(events, span, chips=1)
    assert summary["busy_s"] == busy and summary["window_s"] == span


E = trace_mod.Event
RUN, FEED = ("/host:CPU", "python3#4"), ("/host:CPU", "python3#9")
#: three operations on the chip leave two gaps, 15..55 and 65..130
OPS = [E("/device:TPU:0", "XLA Ops", f"%fusion.{n} = f32[8]", start, dur) for n, (start, dur) in enumerate([(0, 15), (55, 10), (130, 5)])]
NESTED = [E(*RUN, "pw:commit", 0, 100), E(*RUN, "pw:udf.batch", 10, 50), E(*RUN, "pw:embed.tokenize", 20, 30),
          E(*RUN, "pw:commit", 120, 30)]


@pytest.mark.parametrize("host, want", [
    # the innermost stage open on the run thread takes each idle instant; 100..120 has none
    (NESTED, {"pw:embed.tokenize": 30, "pw:udf.batch": 10, "pw:commit": 45, trace_mod.OTHER: 20}),
    # a stage that covers everything on another thread (the device pipeline's, a feed's) takes nothing
    (NESTED + [E(*FEED, "pw:pipeline.complete", 0, 200), E(*FEED, "pw:commit", 0, 1)],
     {"pw:embed.tokenize": 30, "pw:udf.batch": 10, "pw:commit": 45, trace_mod.OTHER: 20}),
    # nor does a stage that covers most of a gap on the run thread take the whole of it
    ([E(*RUN, "pw:commit", 0, 200), E(*RUN, "pw:pump.sleep", 70, 50)], {"pw:commit": 55, "pw:pump.sleep": 50}),
    # under no stage, the benchmark's span open then (on any thread), and under neither, engine: other
    (NESTED + [E(*FEED, "bench:generator_send", 95, 15), E(*RUN, "bench:embed_call", 10, 50)],
     {"pw:embed.tokenize": 30, "pw:udf.batch": 10, "pw:commit": 45, "generator_send": 10, trace_mod.OTHER: 10}),
    # a trace of a program without stages: the benchmark's spans alone, innermost first
    ([E(*RUN, "bench:embed_call", 10, 50), E(*RUN, "bench:sink_documents", 60, 60), E(*FEED, "bench:generator_send", 100, 5)],
     {"embed_call": 40, "sink_documents": 50, "generator_send": 5, trace_mod.OTHER: 10}),
    ([], {trace_mod.OTHER: 105}),
])
def test_idle_time_goes_to_the_innermost_stage_open_on_the_run_thread(host, want):
    gaps = dict(trace_mod.idle_gaps(OPS + host))
    assert gaps == pytest.approx({name: ns / 1e9 for name, ns in want.items()})
    assert sum(gaps.values()) == pytest.approx(105e-9)  # every idle instant, once
    split = trace_mod.idle_split(OPS + host)
    assert split["thread"] == (RUN if any(e.name == "pw:commit" for e in host) else None)
    assert trace_mod.idle_gaps(OPS + host, limit=1) == [list(max(gaps.items(), key=lambda kv: kv[1]))]


def test_innermost_segments_of_nested_and_of_lapping_spans():
    assert trace_mod.innermost_segments((e.start_ns, e.start_ns + e.dur_ns, e.name) for e in NESTED) == [
        (0, 10, "pw:commit"), (10, 20, "pw:udf.batch"), (20, 50, "pw:embed.tokenize"), (50, 60, "pw:udf.batch"),
        (60, 100, "pw:commit"), (120, 150, "pw:commit"),
    ]
    # two threads' spans lap without nesting: no instant is counted twice
    assert trace_mod.innermost_segments([(0, 10, "a"), (5, 15, "b")]) == [(0, 5, "a"), (5, 15, "b")]


def test_op_family_drops_hlo_text_and_serial():
    assert trace_mod.op_family("%convert_reduce_fusion.9 = (f32[256,128]{1,0}) fusion(...)") == "convert_reduce_fusion"
    assert trace_mod.op_family("%copy-start = (f32[2,3]) copy-start(...)") == "copy-start"


def test_benchmark_json_names_units_and_files():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    name_re = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    unit_re = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
    names = [c["name"] for c in bench["configs"]] + [w["name"] for w in bench["workloads"]]
    names += [w["traffic"] for w in bench["workloads"]]
    metrics = bench["end_to_end"] + bench["per_layer"]
    assert all(name_re.match(n) for n in names + [m["name"] for m in metrics])
    assert all(unit_re.match(m["unit"]) and m["better"] in ("lower", "higher") for m in metrics)
    assert len({m["name"] for m in metrics}) == len(metrics)
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for w in bench["workloads"]:
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
        assert os.path.exists(os.path.join(BENCH, "traffic", w["traffic"] + ".json"))
        assert os.path.exists(os.path.join(BENCH, "limits", w["name"] + ".json"))
    for m in bench["per_layer"]:
        assert os.path.exists(os.path.join(BENCH, "layer_metrics", m["name"] + ".json"))
        assert set(m["workloads"]) <= cells and m["moves"] in e2e
        moved = e2e[m["moves"]]
        assert set(m["workloads"]) <= set(moved.get("workloads", cells))
    for c in bench["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"]))
    assert all(0.01 <= m["bound"] <= 0.1 for m in bench["end_to_end"])
    # the contract's keys and no others
    assert set(bench) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert all(set(c) == {"name", "source", "file", "reduced", "why"} for c in bench["configs"])
    assert all(set(w) == {"name", "config", "traffic", "chips", "why"} for w in bench["workloads"])
    assert all(set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"} for m in bench["end_to_end"])
    assert all(set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"} for m in bench["per_layer"])
    assert all(m["source"] in ("host_clock", "device_trace") for m in bench["end_to_end"])
    assert all(1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200 for c in bench["configs"])
    assert {w["config"] for w in bench["workloads"]} == {c["name"] for c in bench["configs"]}
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 4)
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    assert 1 <= bench["run_seconds"] <= 51 and os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 65536


def test_a_reader_is_found_in_readers_py_or_in_a_file_of_its_own(tmp_path):
    assert readers.find("counter_ratio", str(tmp_path)) is readers.counter_ratio
    (tmp_path / "twice.py").write_text("def read(ctx, factor):\n    return ctx * factor\n")
    assert readers.find("twice", str(tmp_path))(21, factor=2) == 42
    try:
        readers.find("nowhere", str(tmp_path))
    except KeyError as exc:
        assert "nowhere" in str(exc)
    else:
        raise AssertionError("a reader that is nowhere was found")


def test_a_tail_read_per_layer_is_the_end_to_end_arithmetic():
    """``wait_percentile`` reads what ``end_to_end_metrics`` reads: due time to
    the sink's callback over all events, one never acknowledged at the grace."""
    import harness

    mix = {"vocabulary_words": 20, "documents": {"loop": "closed", "in_flight": 4, "pool_per_s": 10, "tokens": {"dist": "uniform", "min": 4, "max": 8}},
           "queries": {"loop": "open", "arrivals": "poisson", "rate_per_s": 10, "tokens": {"dist": "uniform", "min": 4, "max": 8}}}
    schedule = traffic.build(mix, 2**31 + 11, 2.0)
    obs = harness.Observed(schedule)
    obs.t0, obs.t_end = 100.0, 102.0
    obs.queries.ack[:-1] = obs.t0 + schedule.queries.due_s + np.linspace(0.010, 0.200, 20)
    obs.queries.ack[7] = np.nan  # never acknowledged: it waited the whole grace
    ctx = readers.Context(cell=None, obs=obs, schedule=schedule, seconds=2.0, chips=1, peak=None, trace=None, flops=0.0)
    waits = obs.waits_ms("queries", schedule.queries)
    assert waits.max() == pytest.approx((102.0 + harness.GRACE_S - 100.0 - schedule.queries.due_s[7]) * 1e3)
    cell = harness.Cell("t", 1, {}, mix, {}, [{"name": "query_p50_ms"}], [], None)
    e2e = harness.end_to_end_metrics(cell, schedule, obs, 2.0, 1.0)
    assert readers.wait_percentile(ctx, "queries", 50) == pytest.approx(e2e["query_p50_ms"])
    assert readers.wait_percentile(ctx, "queries", 95) == pytest.approx(float(np.percentile(waits, 95)))
    assert readers.wait_percentile(ctx, "documents", 95) is None  # a closed loop has no due times
