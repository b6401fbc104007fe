"""The harness end to end at the toy size, and the faults it has to see."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

import toy
from conftest import ROOT


@pytest.fixture(scope="module")
def sound():
    return toy.run("rag")


def test_sound_run_is_correct_and_the_line_has_the_contracts_keys(sound):
    assert sound["correct"] is True, sound["compared"]
    assert list(sound)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(sound)[-1] == "compared"
    assert sound["failed"] == 0 and sound["attempted"] > 100
    assert set(sound["metrics"]) == {"setup_s", "index_lag_p95_ms", "query_p50_ms", "docs_per_s"}
    assert all(set(m) == {"value", "unit"} and m["value"] > 0 for m in sound["metrics"].values())
    assert set(sound["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    json.dumps(sound)


def test_backfill_and_sharded_runs_are_correct():
    assert toy.run("backfill")["correct"] is True
    sharded = toy.run("rag", chips=4)
    assert sharded["correct"] is True, sharded["compared"]


def _failed(result: dict) -> set:
    return {name for name, (value, limit) in result["compared"].items()
            if limit is None or not value <= limit}


@pytest.mark.parametrize("fault", ["answer_altered", "score_altered", "embedding_altered",
                                   "two_rows_of_a_batch_altered", "half_the_batch_left_out", "update_returns_state_unchanged",
                                   "exchange_left_out"])
def test_a_broken_timed_path_is_not_correct(fault, monkeypatch):
    """Drive a whole run with the timed path broken underneath."""
    from pathway_tpu.engine import external_index as ext
    from pathway_tpu.ops import knn
    from pathway_tpu.xpacks.llm import embedders

    chips = 1
    if fault in ("answer_altered", "score_altered"):
        inner = ext.DeviceKnnIndex.search

        def search(self, queries, k):
            out = inner(self, queries, k)
            if fault == "answer_altered":  # the best hit is dropped for the 11th
                return [hits[1:] + [(hits[0][0], hits[-1][1] - 1e-3)] for hits in out]
            return [[(key, score + 1e-4) for key, score in hits] for hits in out]

        monkeypatch.setattr(ext.DeviceKnnIndex, "search", search)
    elif fault == "embedding_altered":  # every text gets its neighbour's vector
        inner_rows = embedders._rows_from_device

        def rows(vecs_dev, real, device_resident):
            import jax.numpy as jnp

            return inner_rows(jnp.roll(vecs_dev, 1, axis=0), real, device_resident)

        monkeypatch.setattr(embedders, "_rows_from_device", rows)
    elif fault == "two_rows_of_a_batch_altered":  # under half of the rows: a median would pass it
        inner_rows = embedders._rows_from_device

        def rows(vecs_dev, real, device_resident):
            if vecs_dev.shape[0] >= 8:
                vecs_dev = vecs_dev.at[0].set(vecs_dev[1]).at[1].set(vecs_dev[0])
            return inner_rows(vecs_dev, real, device_resident)

        monkeypatch.setattr(embedders, "_rows_from_device", rows)
    elif fault == "half_the_batch_left_out":
        inner_add = ext.DeviceKnnIndex.add

        def add(self, keys, vectors):
            return inner_add(self, keys[: len(keys) // 2], vectors[: len(keys) // 2])

        monkeypatch.setattr(ext.DeviceKnnIndex, "add", add)
    elif fault == "update_returns_state_unchanged":  # the scatter into the index does nothing
        import pathway_tpu.ops as ops

        monkeypatch.setattr(ops, "knn_update", lambda state, *args, **kwargs: state)
    elif fault == "exchange_left_out":  # each shard's own best, never gathered
        chips = 4
        from jax import lax

        monkeypatch.setattr(lax, "all_gather", lambda x, *a, **kw: x)
        knn.knn_search_sharded.clear_cache() if hasattr(knn.knn_search_sharded, "clear_cache") else None
    result = toy.run("rag", chips=chips, seed=2**31 + 8)
    assert result["correct"] is False
    assert _failed(result), result["compared"]


def test_the_control_reads_over_the_limit():
    """The reference in the program's place, one precision down, is refused:
    float8 operands in the encoder (on the CPU a product's passes cannot be
    lowered, so the index's control is read on the chip alone)."""
    import jax

    import control

    cell = toy.cell("rag")
    out = control.readings(cell, 5, 1.5, jax.devices())
    assert out["program"]["correct"] is True, out["program"]
    control_ = out["control_float8_encoder"]
    assert control_["correct"] is False
    for name in ("embed_gap_docs", "embed_gap_queries"):
        assert name in control_["failed"]
        assert control_["numbers"][name] > 3 * out["program"]["numbers"][name]


def test_without_a_chip_run_py_exits_non_zero_and_prints_no_metric():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "minilm-backfill", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert done.returncode == 2
    assert done.stdout.strip() == ""
    assert "needs 1 TPU chip" in done.stderr
