"""Env-driven runtime configuration.

Reference: python/pathway/internals/config.py:58 PathwayConfig — the env
flags a deployment sets instead of code: persistence location/mode,
replay, license key, monitoring endpoint, worker topology, assertion and
typechecking switches. ``pw.run`` consults the active config for anything
not passed explicitly.
"""

from __future__ import annotations

import os
from contextvars import ContextVar
from dataclasses import dataclass, field
from typing import Any


def _env_field(name: str, default: str | None = None):
    return field(default_factory=lambda: os.environ.get(name, default))


def _env_bool_field(name: str, default: str = "false"):
    return field(
        default_factory=lambda: os.environ.get(name, default).lower()
        in ("1", "true", "yes", "on")
    )


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, str(default)))
    except ValueError:
        return default


@dataclass
class PathwayConfig:
    continue_after_replay: bool = _env_bool_field(
        "PATHWAY_CONTINUE_AFTER_REPLAY", "true"
    )
    ignore_asserts: bool = _env_bool_field("PATHWAY_IGNORE_ASSERTS")
    runtime_typechecking: bool = _env_bool_field("PATHWAY_RUNTIME_TYPECHECKING")
    persistence_mode: str = _env_field("PATHWAY_PERSISTENCE_MODE", "persisting")
    persistent_storage: str | None = _env_field("PATHWAY_PERSISTENT_STORAGE")
    replay_storage: str | None = _env_field("PATHWAY_REPLAY_STORAGE")
    snapshot_access: str | None = _env_field("PATHWAY_SNAPSHOT_ACCESS")
    license_key: str | None = _env_field("PATHWAY_LICENSE_KEY")
    monitoring_server: str | None = _env_field("PATHWAY_MONITORING_SERVER")
    #: raise at the first row-level error instead of poisoning the row to
    #: ERROR (``pw.run(terminate_on_error=...)`` overrides). Off unless
    #: asked for: per-row ERROR values and the error-log tables are the
    #: semantics every pipeline and test here is written against.
    terminate_on_error: bool = _env_bool_field("PATHWAY_TERMINATE_ON_ERROR")
    process_id: str = _env_field("PATHWAY_PROCESS_ID", "0")
    threads: int = field(default_factory=lambda: _env_int("PATHWAY_THREADS", 1))
    processes: int = field(
        default_factory=lambda: _env_int("PATHWAY_PROCESSES", 1)
    )
    first_port: int = field(
        default_factory=lambda: _env_int("PATHWAY_FIRST_PORT", 10000)
    )

    @property
    def replay_config(self) -> Any:
        """Persistence Config implied by the env, or None (reference
        config.py:76 replay_config)."""
        storage = self.persistent_storage or self.replay_storage
        if not storage:
            return None
        from pathway_tpu.persistence import Backend, Config, PersistenceMode

        mode = {
            "persisting": PersistenceMode.PERSISTING,
            "operator_persisting": PersistenceMode.OPERATOR_PERSISTING,
            "udf_caching": PersistenceMode.UDF_CACHING,
        }.get(self.persistence_mode.lower(), PersistenceMode.PERSISTING)
        return Config(
            Backend.filesystem(storage),
            persistence_mode=mode,
            continue_after_replay=self.continue_after_replay,
        )


_pathway_config: ContextVar[PathwayConfig | None] = ContextVar(
    "pathway_config", default=None
)


def get_pathway_config() -> PathwayConfig:
    """Explicitly-set config if any, else a FRESH read of the environment —
    env changes between runs must take effect (the reference re-reads env
    per run too)."""
    config = _pathway_config.get()
    if config is None:
        return PathwayConfig()
    return config


def set_pathway_config(config: PathwayConfig | None) -> None:
    _pathway_config.set(config)


def set_license_key(key: str | None) -> None:
    config = get_pathway_config()
    config.license_key = key
    set_pathway_config(config)
