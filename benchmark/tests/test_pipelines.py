"""The seam between the harness and a cell's pipeline: a pipeline that lives
only in the tests runs through ``harness.run_cell`` from a configuration that
names it; a name with no file ends the run before set-up; the live index's
warm-up takes its search shapes from the mix."""

from __future__ import annotations

import json
import os
import time

import pytest

import harness
import toy
import traffic
from conftest import BENCH, HERE, ROOT

ECHO_CONFIG = {"name": "echo-toy", "pipeline": "echo", "autocommit_ms": 20, "guarantees": ["every event comes back as sent"]}
E2E = [{"name": n, "unit": "x"} for n in ("setup_s", "docs_per_s", "index_lag_p95_ms", "query_p50_ms")]


def echo_cell(mix: str = "rag") -> harness.Cell:
    pipeline = harness.find_pipeline(ECHO_CONFIG["pipeline"], os.path.join(HERE, "pipelines"))
    return harness.Cell("toy-echo", 1, dict(ECHO_CONFIG), dict(toy.MIXES[mix]), {}, E2E, [], pipeline)


def run(cell: harness.Cell, seed: int = 2**31 + 11) -> dict:
    import jax

    return harness.run_cell(cell, seed, 1.5, False, jax.devices(), time.time())


@pytest.mark.parametrize("mix", ["rag", "backfill"])
def test_a_pipeline_of_the_tests_runs_end_to_end_through_run_cell(mix):
    result = run(echo_cell(mix))
    assert result["correct"] is True, result["compared"]
    assert result["failed"] == 0 and result["attempted"] > 50
    assert list(result)[:5] == ["correct", "attempted", "failed", "metrics", "device"] and list(result)[-1] == "compared"
    wanted = {"documents_lost", "documents_repeated", "documents_altered", "error_log", "compiles_in_window"}
    assert wanted <= set(result["compared"])
    assert ("queries_altered" in result["compared"]) == (mix == "rag")
    assert result["metrics"]["docs_per_s"]["value"] > 0 and result["metrics"]["setup_s"]["value"] > 0


def test_its_own_comparison_sees_its_own_fault():
    cell = echo_cell()
    inner = cell.pipeline.weights
    cell.pipeline.weights = lambda cell_, seed: {**inner(cell_, seed), "echo": lambda text: text.rsplit(" ", 1)[0]}
    result = run(cell)
    assert result["correct"] is False
    assert result["compared"]["documents_altered"][0] > 0 and result["compared"]["queries_altered"][0] > 0
    assert result["compared"]["documents_lost"] == [0, 0]


def test_a_traced_run_asks_the_pipeline_for_the_work_its_step_counts():
    import jax

    cell = echo_cell()
    cell.per_layer = [{"name": "late", "unit": "ms", "reader": "generator_late_p95", "params": {"stream": "queries"}},
                      {"name": "mfu", "unit": "%", "reader": "step_mfu"}]
    result = harness.run_cell(cell, 5, 2.0, True, jax.devices(), time.time())
    assert result["correct"] is True, result["compared"]
    assert set(result["metrics"]) == {"late"}  # a pipeline with no work counted reports no share of the peak


def _tmp_root(tmp_path, pipeline: str | None) -> str:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    for entry in bench["configs"]:
        with open(os.path.join(ROOT, entry["file"])) as fh:
            config = json.load(fh)
        assert "pipeline" not in config  # both committed configurations run the default
        if pipeline is not None:
            config["pipeline"] = pipeline
        entry["file"] = entry["name"] + ".json"
        (tmp_path / entry["file"]).write_text(json.dumps(config))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(tmp_path)


def test_a_configuration_without_the_key_runs_live_index(tmp_path):
    cell = harness.load_cell(_tmp_root(tmp_path, None), "bge-live-rag")
    assert cell.pipeline.__file__ == os.path.join(BENCH, "pipelines", "live_index.py")
    assert all(callable(getattr(cell.pipeline, part)) for part in harness.PIPELINE_INTERFACE)


def test_an_unknown_pipeline_is_refused_before_set_up(tmp_path):
    with pytest.raises(SystemExit) as refused:
        harness.load_cell(_tmp_root(tmp_path, "no_such_graph"), "minilm-backfill")
    assert "no_such_graph" in str(refused.value) and "live_index" in str(refused.value)


def test_a_pipeline_file_without_the_interface_is_refused(tmp_path):
    (tmp_path / "half.py").write_text("def weights(cell, seed):\n    return {}\n")
    with pytest.raises(SystemExit) as refused:
        harness.find_pipeline("half", str(tmp_path))
    assert "set_up" in str(refused.value) and "compare" in str(refused.value)


@pytest.mark.parametrize("search_rows_max, searched", [(None, [8, 16]), (32, [8, 16, 32]), (8, [8])])
def test_warm_up_searches_at_the_buckets_the_mix_names(search_rows_max, searched, monkeypatch):
    from pathway_tpu.engine import external_index as ext

    cell = toy.cell("rag")
    if search_rows_max is not None:
        cell.mix["queries"]["search_rows_max"] = search_rows_max
    sizes = []
    inner = ext.DeviceKnnIndex.search
    monkeypatch.setattr(ext.DeviceKnnIndex, "search", lambda self, queries, k: (sizes.append(len(queries)), inner(self, queries, k))[1])
    state = cell.pipeline.weights(cell, 3)
    cell.pipeline.set_up(cell, 3, traffic.build(cell.mix, 3, 1.0), state, None, lambda name: None)
    assert sizes == searched
    assert len(state["index"]) == state["prefilled"] == cell.config["index"]["prefilled"]
