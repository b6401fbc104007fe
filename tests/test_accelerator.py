"""internals/accelerator.py: where the compile cache goes, what counts as
the chip, and which chip a spawned worker is given."""

from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

from pathway_tpu.internals import accelerator

REPO = Path(__file__).resolve().parent.parent


def _jax_cache_dir():
    import jax

    return jax.config.jax_compilation_cache_dir


def test_compile_cache_leaves_a_set_directory_alone(monkeypatch):
    before = _jax_cache_dir()
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert accelerator.configure_compile_cache() == "/some/dir"
    assert _jax_cache_dir() == before


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = accelerator.configure_compile_cache()
    assert path == str(REPO / ".jax_cache")
    # exported for worker processes, and told to the JAX already imported
    assert accelerator.os.environ["JAX_COMPILATION_CACHE_DIR"] == path
    assert _jax_cache_dir() == path


def test_require_tpu_refuses_the_cpu():
    with pytest.raises(RuntimeError, match="no TPU.*'cpu'"):
        accelerator.require_tpu()


def test_chip_env_gives_each_worker_one_chip():
    assert accelerator.chip_env(0, 1, {}) == {}
    assert accelerator.chip_env(1, 2, {"TPU_VISIBLE_CHIPS": "3"}) == {}
    envs = [accelerator.chip_env(i, 4, {}) for i in range(4)]
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
    assert all(e["TPU_PROCESS_BOUNDS"] == "1,1,1" for e in envs)


def test_spawn_hands_the_chip_env_to_its_workers(tmp_path):
    from pathway_tpu.cli import spawn

    code = (
        "import json, os; json.dump("
        "{k: v for k, v in os.environ.items() if k.startswith('TPU_')}, "
        f"open(os.path.join({str(tmp_path)!r}, "
        "os.environ['PATHWAY_PROCESS_ID']), 'w'))"
    )
    assert spawn(sys.executable, ["-c", code], processes=2, env={}) == 0
    for i in range(2):
        got = json.loads((tmp_path / str(i)).read_text())
        assert got == accelerator.chip_env(i, 2, {})
