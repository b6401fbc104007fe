"""chip_smoke.py's functions at CPU sizes: the smoke is the repo's only
check on the chip, so what it accepts and what it refuses is pinned here,
on the virtual CPU mesh, where every tier-1 run sees it."""

from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

import pytest

import pathway_tpu  # noqa: F401  (jax cpu config via conftest)

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402

from pathway_tpu.engine import device_ops as dops  # noqa: E402

N_DOCS, N_QUERIES, DOC_WORDS = 64, 4, (10, 20)


@pytest.fixture(scope="module")
def embedder():
    from pathway_tpu.xpacks.llm.embedders import TpuEncoderEmbedder

    return TpuEncoderEmbedder(
        model="all-MiniLM-L6-v2", max_len=16, max_batch_size=64
    )


@pytest.fixture(scope="module")
def one_device(embedder):
    return chip_smoke.run_rag_pipeline(
        embedder, n_docs=N_DOCS, n_queries=N_QUERIES, capacity=N_DOCS,
        doc_words=DOC_WORDS,
    )


def test_pipeline_invariants_hold_on_one_device(one_device):
    assert (
        chip_smoke.rag_failures(
            one_device, n_docs=N_DOCS, n_queries=N_QUERIES,
            platform="cpu", n_devices=1,
        )
        == []
    )
    assert chip_smoke.recall_at_k(one_device) >= 0.95


def test_sharded_phase_matches_one_device(embedder, one_device, monkeypatch):
    """The four-chip phase on four virtual devices: the index grows from a
    quarter of its final capacity and stays spread over the mesh, answers
    equal the one-device run, four workers count like one over the
    collective exchange (forced: on the CPU it is off by default)."""
    monkeypatch.setenv("PATHWAY_TPU_COLLECTIVE_EXCHANGE", "1")
    dops.reset_counters()
    facts, failures = chip_smoke.run_sharded_phase(
        embedder, one_device, n_docs=N_DOCS, n_queries=N_QUERIES,
        n_words=2000, doc_words=DOC_WORDS,
    )
    assert failures == []
    assert facts["index_capacity"] == [16, 64]
    assert facts["index_devices"] == 4
    assert facts["collective"]["exchanges"] > 0


def test_attention_check_names_what_is_compiled(embedder):
    facts, failures = chip_smoke.check_attention(
        embedder, 8, 16, [((2, 16, 2, 8), "float32", True)]
    )
    assert failures == []
    # off the chip the default is dense and nothing lowers to Mosaic
    assert facts["implementation"] == "dense_attention"
    assert facts["tpu_custom_call_in_embed_step"] is False


def test_cpu_run_exits_nonzero_and_names_the_missing_chip():
    proc = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(REPO)},
        cwd=str(REPO),
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr and "'cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout  # no result without the chip


@pytest.mark.parametrize("outcome", ["pass", "failed check", "phase raises"])
def test_last_line_is_the_verdict_and_nothing_else(
    outcome, monkeypatch, capfd
):
    """The driver reads the last line of stdout and refuses any key beyond
    ``ok`` and ``device`` {platform, kind, count} (PR 21 was refused once
    for carrying the summary there). ``main`` with the chip and the phases
    stubbed: the summary is the line before, the verdict is the last."""
    import json

    from pathway_tpu.internals import accelerator

    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}

    def phases(device, out, failures):
        out["rag"] = {"docs_embedded_and_indexed": 4096}
        if outcome == "failed check":
            failures.append("rag: recall@10 0.5 < 0.95")
        if outcome == "phase raises":
            raise RuntimeError("boom")

    monkeypatch.setattr(accelerator, "require_tpu", lambda: dict(device))
    monkeypatch.setattr(chip_smoke, "run_phases", phases)
    monkeypatch.setattr(sys, "argv", ["chip_smoke.py"])
    rc = chip_smoke.main()
    lines = capfd.readouterr().out.strip().splitlines()
    ok = outcome == "pass"
    assert rc == (0 if ok else 1)
    assert json.loads(lines[-1]) == {"ok": ok, "device": device}
    summary = json.loads(lines[-2])["summary"]
    assert summary["rag"] == {"docs_embedded_and_indexed": 4096}
    assert bool(summary["failures"]) is not ok
    assert list(summary)[-1] == "claim" and summary["claim"] is None


def test_raising_embedder_fails_the_checks():
    """Finding 2 of ISSUE 21: a batch UDF that raises on every chunk used
    to leave pw.run() returning normally with empty sinks."""
    from pathway_tpu.internals.udfs import UDF, batch_executor

    class Broken(UDF):
        def __init__(self) -> None:
            def embed_batch(texts: list) -> list:
                raise RuntimeError("attention kernel refused")

            super().__init__(
                embed_batch,
                executor=batch_executor(max_batch_size=64),
                deterministic=True,
            )

        def get_embedding_dimension(self) -> int:
            return 384

    facts = chip_smoke.run_rag_pipeline(
        Broken(), n_docs=N_DOCS, n_queries=N_QUERIES, capacity=N_DOCS,
        doc_words=DOC_WORDS,
    )
    failures = chip_smoke.rag_failures(
        facts, n_docs=N_DOCS, n_queries=N_QUERIES, platform="cpu", n_devices=1
    )
    assert any(
        "pw.run raised" in f and "attention kernel refused" in f
        for f in failures
    )
    assert any("0 of 64 documents" in f for f in failures)


def test_relational_tail_runs_the_device_kernels(monkeypatch):
    monkeypatch.setenv("PATHWAY_TPU_DEVICE_OPS", "1")
    dops.reset_counters()
    facts, failures = chip_smoke.run_relational(4000)
    assert failures == []
    assert facts["device_ops"]["hit_counts"]["segment_reduce"] > 0
    assert facts["device_ops"]["hit_counts"]["match_pairs"] > 0


@pytest.mark.parametrize(
    "kernel,site",
    [
        ("segment_reduce_dispatch", "segment_reduce"),
        ("match_pairs", "match_pairs"),
    ],
)
def test_device_path_exception_is_counted_not_hidden(
    kernel, site, monkeypatch
):
    """An exception inside a device kernel still yields the host result —
    the sinks equal NumPy — but it is an error the smoke fails on, not a
    quiet decline."""

    def boom(*args, **kwargs):
        raise RuntimeError("injected device failure")

    monkeypatch.setenv("PATHWAY_TPU_DEVICE_OPS", "1")
    monkeypatch.setattr(dops, kernel, boom)
    dops.reset_counters()
    facts, failures = chip_smoke.run_relational(4000)
    assert facts["device_ops"]["errors"] == {site: 1}
    assert not any("differs from NumPy" in f for f in failures)
    assert any("device path errors" in f for f in failures)
    dops.reset_counters()
