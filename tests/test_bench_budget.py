"""bench.py must leave a parseable JSON verdict when it is killed or when
a leg overruns: a SIGTERM mid-leg flushes every completed leg with
``truncated: true``, and a leg that cannot finish inside its budget is
marked in ``leg_errors`` while the run goes on. Both cases here skip every
device leg, so they run without a chip; with a device leg enabled bench.py
exits 2 on anything but a TPU (the check chip_smoke.py makes too).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent

pytestmark = pytest.mark.skipif(
    not (REPO / "bench.py").exists(), reason="bench.py not present"
)


def test_sigterm_mid_leg_flushes_completed_partials():
    """Killing bench.py mid-leg (SIGTERM, the harness-timeout signal)
    must still land one final VALID JSON line carrying ``truncated:
    true`` plus every leg that already completed — a killed bench
    parses, it never leaves half a line or nothing."""
    import signal

    env = dict(os.environ)
    env.pop("BENCH_WALL_BUDGET_S", None)
    env.update(
        JAX_PLATFORMS="cpu",
        # a small serving leg completes quickly (emitting its partial),
        # then the dataflow suite — pinned to an absurd row count —
        # holds the bench mid-leg for minutes: a deterministic window
        # to land the SIGTERM in
        BENCH_SKIP_PIPELINE="1",
        BENCH_SKIP_QUERY_LOAD="1",
        BENCH_SKIP_FLASH_PARITY="1",
        BENCH_SKIP_DECODE="1",
        BENCH_SKIP_MULTIMODAL="1",
        BENCH_SKIP_VECTOR_STORE="1",
        BENCH_SKIP_RERANKER="1",
        BENCH_SKIP_DEVICE_ONLY="1",
        BENCH_SERVING_DOCS="200",
        BENCH_SERVING_QUERIES="10",
        BENCH_SERVING_CLIENTS="2",
        BENCH_DATAFLOW_ROWS="200000000",
        PYTHONPATH=str(REPO),
        PYTHONUNBUFFERED="1",
    )
    proc = subprocess.Popen(
        [sys.executable, str(REPO / "bench.py")],
        env=env,
        cwd=str(REPO),
        stdout=subprocess.PIPE,
        stderr=subprocess.DEVNULL,
        text=True,
    )
    lines = []
    deadline = time.time() + 600.0
    saw_partial = False
    try:
        # wait for the serving leg's incremental partial line, then
        # kill the bench while the dataflow suite is still mid-leg
        while time.time() < deadline:
            line = proc.stdout.readline()
            if not line:
                break
            lines.append(line)
            if '"partial"' in line and "serving_plane" in line:
                saw_partial = True
                break
        assert saw_partial, (proc.poll(), lines)
        # give the dataflow suite a moment to be well inside its leg
        time.sleep(2.0)
        proc.send_signal(signal.SIGTERM)
        rest, _ = proc.communicate(timeout=60.0)
        lines.extend(rest.splitlines(keepends=True))
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30.0)
    assert proc.returncode == 3, (proc.returncode, lines)
    # every emitted line is individually valid JSON (nothing half-written)
    parsed = [json.loads(ln) for ln in lines if ln.strip()]
    final = parsed[-1]
    assert final.get("truncated") is True, final
    assert "SIGTERM" in (final.get("error") or ""), final
    # the completed leg's numbers survived into the truncated flush
    assert "serving_plane" in (final.get("extra") or {}), final


def test_slow_serving_leg_is_marked_not_killed():
    """A serving leg that cannot finish inside its per-leg budget must be
    abandoned and MARKED in ``leg_errors`` — the run still exits 0 with a
    parseable JSON verdict, never an rc=124 harness kill."""
    env = dict(os.environ)
    env.pop("BENCH_WALL_BUDGET_S", None)
    env.update(
        JAX_PLATFORMS="cpu",
        # every other leg off: this test times ONLY the serving leg path
        BENCH_SKIP_PIPELINE="1",
        BENCH_SKIP_QUERY_LOAD="1",
        BENCH_SKIP_FLASH_PARITY="1",
        BENCH_SKIP_DECODE="1",
        BENCH_SKIP_MULTIMODAL="1",
        BENCH_SKIP_VECTOR_STORE="1",
        BENCH_SKIP_RERANKER="1",
        BENCH_SKIP_DEVICE_ONLY="1",
        BENCH_SKIP_DATAFLOW="1",
        # a deliberately unfinishable leg: far more paced-ingest work
        # than the leg budget allows
        BENCH_SERVING_DOCS="2000000",
        BENCH_SERVING_INGEST_RATE="500",
        BENCH_LEG_TIMEOUT_SERVING_PLANE_S="10",
        PYTHONPATH=str(REPO),
    )
    proc = subprocess.run(
        [sys.executable, str(REPO / "bench.py")],
        env=env,
        cwd=str(REPO),
        capture_output=True,
        text=True,
        timeout=240,  # outer net only — the leg budget must do the work
    )
    assert proc.returncode == 0, (proc.returncode, proc.stderr[-2000:])
    verdicts = [
        json.loads(line)
        for line in proc.stdout.splitlines()
        if line.startswith("{") and "leg_errors" in line
    ]
    assert verdicts, proc.stdout
    leg_errors = verdicts[-1]["extra"]["leg_errors"]
    assert "serving_plane" in leg_errors, leg_errors
    assert "did not complete" in leg_errors["serving_plane"], leg_errors


def test_device_leg_without_a_tpu_exits_2_before_any_number():
    """The measured path fails without a chip: no fallback leg, no CPU
    number under the device metric's name."""
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO))
    proc = subprocess.run(
        [sys.executable, str(REPO / "bench.py")],
        env=env,
        cwd=str(REPO),
        capture_output=True,
        text=True,
        timeout=240,
    )
    assert proc.returncode == 2, (proc.returncode, proc.stderr[-2000:])
    assert "no TPU" in proc.stderr
    assert "streaming_rag_pipeline_docs_per_sec" not in proc.stdout
