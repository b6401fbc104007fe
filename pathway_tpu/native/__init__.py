"""Native (C++) engine-core kernels with transparent build + fallback.

The reference's hot loop is native (Rust, src/engine/dataflow.rs); this
package provides the equivalent native floor for the TPU build's host
control plane: CPython C++ kernels for per-row object plumbing
(enginecore.cpp), compiled on first import with g++ and cached next to the
source under a name that carries the source's content hash — so a copied
or freshly checked-out tree (whose mtimes mean nothing) builds exactly
when the source it holds has no binary yet. Binaries are not committed.
Everything degrades gracefully to the pure-Python implementations
when no toolchain is available — behavior is identical, only slower.

A failed build or import is NOT silent: the first failure logs one
structured warning (module path + exception) on the
``pathway_tpu.native`` logger, and the reason stays queryable via
:func:`load_error` — a several-fold slowdown should never have to be
bisected back to a missing compiler.

``PATHWAY_TPU_NATIVE_SO`` overrides the shared-object path entirely
(tools/check.py points it at an ASan/UBSan-instrumented build so the
parity suite exercises the sanitized kernels).

Public surface:
- ``available()`` — True when the compiled kernels are loaded.
- ``kernels`` — the extension module or None.
- ``load_error()`` — why the native module is absent (None when loaded).
"""

from __future__ import annotations

import contextlib
import glob
import hashlib
import importlib.util
import logging
import os
import subprocess
import sys
import sysconfig

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "enginecore.cpp")

kernels = None

#: why the native module is absent (None when loaded); see load_error()
_load_error: str | None = None
_warned = False


def load_error() -> str | None:
    """The reason the native extension is unavailable: a build/import
    failure description, the disable-flag notice, or None when loaded."""
    return _load_error


def _note_failure(message: str, *, warn: bool = True) -> None:
    global _load_error, _warned
    _load_error = message
    if warn and not _warned:
        _warned = True
        logging.getLogger("pathway_tpu.native").warning(
            "native kernels unavailable, falling back to pure-Python "
            "implementations (identical results, slower): %s",
            message,
        )


_CXXFLAGS = ("-O3", "-std=c++17", "-shared", "-fPIC")


def _so_path() -> str:
    """The binary for THIS source, interpreter and flag set."""
    tag = f"cpython-{sys.version_info.major}{sys.version_info.minor}"
    digest = hashlib.sha256()
    with open(_SRC, "rb") as src:
        digest.update(src.read())
    digest.update(" ".join(_CXXFLAGS).encode())
    return os.path.join(
        _DIR, f"_enginecore.{tag}.{digest.hexdigest()[:16]}.so"
    )


def _build() -> str | None:
    so = _so_path()
    if os.path.exists(so):
        return so
    include = sysconfig.get_path("include")
    import numpy as np

    # a private temporary name: processes that start together on a fresh
    # tree each compile, and whichever renames last wins an identical file
    tmp = f"{so}.{os.getpid()}.tmp"
    cmd = [
        "g++",
        *_CXXFLAGS,
        f"-I{include}",
        f"-I{np.get_include()}",
        _SRC,
        "-o",
        tmp,
    ]
    try:
        subprocess.run(
            cmd, check=True, capture_output=True, text=True, timeout=120
        )
        os.replace(tmp, so)
        for stale in glob.glob(os.path.join(_DIR, "_enginecore.*.so")):
            if stale != so:
                with contextlib.suppress(OSError):  # a sibling got there first
                    os.remove(stale)
        return so
    except (subprocess.SubprocessError, OSError) as e:
        detail = getattr(e, "stderr", "") or str(e)
        _note_failure(
            f"compiling {_SRC} failed: {type(e).__name__}: "
            f"{detail.strip()[:500]}"
        )
        return None


def _load():
    global kernels
    so = os.environ.get("PATHWAY_TPU_NATIVE_SO")
    if so:
        if not os.path.exists(so):
            _note_failure(f"PATHWAY_TPU_NATIVE_SO={so} does not exist")
            return
    else:
        so = _build()
        if so is None:
            return
    try:
        spec = importlib.util.spec_from_file_location("_enginecore", so)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        kernels = mod
    except Exception as e:  # noqa: BLE001 — any load failure -> pure Python
        kernels = None
        _note_failure(f"importing {so} failed: {type(e).__name__}: {e}")


if os.environ.get("PATHWAY_TPU_DISABLE_NATIVE") != "1":
    _load()
else:
    # explicit opt-out is not a failure: record why, but don't warn
    _note_failure("disabled via PATHWAY_TPU_DISABLE_NATIVE=1", warn=False)


def available() -> bool:
    return kernels is not None


def hit_counts() -> dict[str, int]:
    """Per-kernel invocation counters since process start (or the last
    :func:`reset_hit_counts`); empty when the native module is absent.
    bench_dataflow records this next to EXCHANGE_STATS so a silent import
    regression shows up in the bench JSON, not just as a slowdown."""
    if kernels is None or not hasattr(kernels, "hit_counts"):
        return {}
    return kernels.hit_counts()


def kernel_ns() -> dict[str, int]:
    """Cumulative wall nanoseconds spent inside each native kernel since
    process start (or the last :func:`reset_hit_counts`); empty when the
    native module is absent or the .so predates the timers."""
    if kernels is None or not hasattr(kernels, "kernel_ns"):
        return {}
    return kernels.kernel_ns()


def reset_hit_counts() -> None:
    if kernels is not None and hasattr(kernels, "reset_hit_counts"):
        kernels.reset_hit_counts()
