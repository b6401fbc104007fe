"""What a process settles before it touches the accelerator: where XLA's
compiled programs are kept between runs, whether the device JAX found is
the chip the program was written for, and which chip a worker process gets.

Importing this module does not import JAX.
"""

from __future__ import annotations

import os
import sys

_CACHE_VAR = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def configure_compile_cache() -> str:
    """Place JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    the directory is left alone. Otherwise the cache is
    ``<checkout>/.jax_cache``, next to the package: a fixed path, because
    a cache that moves between runs is never found again. Unless the
    process is held to the CPU, every program is kept, not only those that
    took a second to compile: a pipeline compiles a few hundred small
    ones, and a process that finds them all starts in a fraction of the
    time (CHANGES.md, PR 21). Settings are exported so that worker
    processes inherit them, and a JAX that was imported before this call
    is told through its config."""
    settings = {}
    path = os.environ.get(_CACHE_VAR)
    if not path:
        path = os.path.join(_CHECKOUT, ".jax_cache")
        settings["jax_compilation_cache_dir"] = path
    if not os.environ.get("JAX_PLATFORMS", "").startswith("cpu"):
        settings["jax_persistent_cache_min_compile_time_secs"] = 0
    jax = sys.modules.get("jax")
    for name, value in settings.items():
        if name.upper() not in os.environ:
            os.environ[name.upper()] = str(value)
            if jax is not None:
                jax.config.update(name, value)
    return path


def require_tpu() -> dict:
    """``{"platform", "kind", "count"}`` of the accelerator as JAX reports
    it. Raises ``RuntimeError`` unless it is a TPU: what measures the chip
    does not answer from anything else."""
    import jax

    devices = jax.devices()
    first = devices[0]
    if first.platform != "tpu":
        raise RuntimeError(
            f"no TPU: JAX reports {len(devices)} {first.platform!r} "
            f"device(s) ({first.device_kind}); JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS')!r}"
        )
    return {
        "platform": first.platform,
        "kind": first.device_kind,
        "count": len(devices),
    }


def chip_env(process_id: int, processes: int, env: dict) -> dict:
    """What to add to worker ``process_id``'s environment so that it
    initialises chip ``process_id`` of the host and no other.

    libtpu gives a chip to one process, and a process that names none
    takes every chip of the host — so of several sibling workers that
    each build a device model, the second fails at start-up (on a v5e:
    ``Unable to initialize backend 'tpu': ... libtpu multi-process
    lockfile``). With one chip each, ``pathway spawn --processes N`` runs
    on a host with N chips; on a host with fewer, worker ``N-1`` fails at
    start-up with ``No jellyfish device found`` — a chip belongs to one
    process (README "Processes and chips"). Nothing is added for a single
    process, or where the caller already chose (``TPU_VISIBLE_CHIPS``);
    without a TPU the variables are read by nobody."""
    if processes < 2 or "TPU_VISIBLE_CHIPS" in env:
        return {}
    one = "1,1,1"
    return {
        "TPU_VISIBLE_CHIPS": str(process_id),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": one,
        "TPU_PROCESS_BOUNDS": one,
        # the same two bounds under the names older libtpu reads, which a
        # TPU VM image may export for the whole host
        "TPU_CHIPS_PER_HOST_BOUNDS": one,
        "TPU_HOST_BOUNDS": one,
    }
