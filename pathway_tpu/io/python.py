"""pw.io.python — user-defined push sources.

(reference: python/pathway/io/python/__init__.py, 527 LoC — ConnectorSubject
:49 with next()/commit()/close(), backed by the engine PythonSubject.)
Here the subject runs in a thread writing parsed events to a queue drained by
the streaming run loop.
"""

from __future__ import annotations

import json as _json
import threading
from typing import Any, Sequence

from pathway_tpu.engine.connectors import INSERT, DELETE, ParsedEvent, Parser, QueueReader
from pathway_tpu.engine.value import Json
from pathway_tpu.internals import schema as schema_mod
from pathway_tpu.internals.table import Table
from pathway_tpu.io._utils import input_table


class ConnectorSubject:
    """Subclass and implement ``run()``, calling ``self.next(**fields)``."""

    def __init__(self) -> None:
        self._reader = QueueReader()
        self._thread: threading.Thread | None = None

    # -- user API -----------------------------------------------------------

    def next(self, **kwargs: Any) -> None:
        self._reader.push(("insert", kwargs))

    def next_json(self, message: dict | str) -> None:
        if isinstance(message, str):
            message = _json.loads(message)
        self.next(**message)

    def next_str(self, message: str) -> None:
        self.next(data=message)

    def next_bytes(self, message: bytes) -> None:
        self.next(data=message)

    def _remove(self, **kwargs: Any) -> None:
        self._reader.push(("delete", kwargs))

    def commit(self) -> None:
        self._reader.push(("commit", None))

    def close(self) -> None:
        self._reader.close()

    def run(self) -> None:
        raise NotImplementedError

    # -- engine integration --------------------------------------------------

    def _start(self) -> None:
        def runner() -> None:
            try:
                self.run()
            finally:
                self.close()

        self._thread = threading.Thread(target=runner, daemon=True)
        self._thread.start()


class _SubjectParser(Parser):
    def __init__(self, column_names: Sequence[str], dtypes: dict) -> None:
        super().__init__(column_names)
        self.dtypes = dtypes

    def parse(self, payload: Any) -> list[ParsedEvent]:
        kind, fields = payload
        if kind == "commit" or fields is None:
            return []
        values = []
        for name in self.column_names:
            v = fields.get(name)
            if isinstance(v, (dict, list)):
                v = Json(v)
            values.append(v)
        return [ParsedEvent(INSERT if kind == "insert" else DELETE, tuple(values))]


def read(
    subject: ConnectorSubject,
    *,
    schema: schema_mod.SchemaMetaclass,
    autocommit_duration_ms: int | None = 1500,
    persistent_id: str | None = None,
    **kwargs: Any,
) -> Table:
    """A table fed by ``subject`` (reference: pw.io.python.read).

    ``autocommit_duration_ms`` is the maximum time between two commits, as
    upstream documents it, counted **from a row's arrival**: ``next()``
    stamps the row on the subject's thread, and the run loop starts the
    commit at that stamp plus the window, or as soon after as it is free
    (while the loop is inside an earlier commit the row's window keeps
    running; the poll that finds the row does not start it over). Rows
    that arrive inside one window share a commit. ``None`` or 0 commits
    at the poll that finds a row."""
    dtypes = schema.dtypes()

    started = False

    def make_reader():
        nonlocal started
        if not started:
            subject._start()
            started = True
        return subject._reader

    def make_parser(names):
        return _SubjectParser(names, dtypes)

    return input_table(
        schema,
        make_reader,
        make_parser,
        source_name="python-connector",
        persistent_id=persistent_id,
        autocommit_duration_ms=autocommit_duration_ms,
    )
