"""Connector framework: Reader → Parser → InputSession and
Subscribe → Formatter → Writer.

New implementation of the reference connector subsystem
(reference: src/connectors/mod.rs:428 `Connector::run` pull loop,
data_storage.rs Reader/Writer traits :372/:600, data_format.rs
Parser/Formatter traits :262/:452). The reference spawns one thread per
source plus a poller closure stepped by the worker loop; here each source is
an :class:`InputDriver` polled by the streaming run loop between commits —
same contract (bounded batches per commit, commit timestamps), simpler
machinery. Python push-sources use a thread + queue like the reference's
PythonSubject (python_api.rs PythonSubject).
"""

from __future__ import annotations

import csv as _csv
import glob as _glob
import io as _io
import json as _json
import os
import queue
import threading
import time as _time
from typing import Any, Callable, Iterable, Sequence

from pathway_tpu.engine.graph import InputSession, Node, Scope
from pathway_tpu.engine.value import Json, Pointer, hash_values, ref_scalar
from pathway_tpu.internals import metrics as _metrics

# -- parsed events ----------------------------------------------------------

INSERT = "insert"
DELETE = "delete"
UPSERT = "upsert"


class ParsedEvent:
    """``key`` is an optional tuple of key values (CDC streams carry the row
    identity explicitly); ``values`` may be None for an upsert deletion
    (reference ParsedEvent Insert/Delete/Upsert, data_format.rs:175)."""

    __slots__ = ("kind", "values", "key")

    def __init__(
        self, kind: str, values: tuple | None, key: tuple | None = None
    ) -> None:
        self.kind = kind
        self.values = values
        self.key = key


# -- parsers ----------------------------------------------------------------


class Parser:
    """payload (str/bytes) → list of ParsedEvent with values in schema order.

    ``session_type`` mirrors the reference's Parser::session_type
    (data_format.rs:262): "native" feeds insert/remove diffs, "upsert"
    feeds an overlay session keyed by the event key.
    """

    session_type = "native"

    def __init__(self, column_names: Sequence[str]) -> None:
        self.column_names = list(column_names)

    def parse(self, payload: Any) -> list[ParsedEvent]:
        raise NotImplementedError


class DsvParser(Parser):
    """Delimiter-separated values with a header row (reference: DsvParser
    data_format.rs:500)."""

    def __init__(
        self,
        column_names: Sequence[str],
        converters: Sequence[Callable[[str], Any]] | None = None,
        delimiter: str = ",",
    ) -> None:
        super().__init__(column_names)
        self.delimiter = delimiter
        self.converters = list(converters) if converters else None
        self._header: list[str] | None = None

    def reset(self) -> None:
        self._header = None

    def parse(self, payload: str) -> list[ParsedEvent]:
        rows = list(_csv.reader(_io.StringIO(payload), delimiter=self.delimiter))
        if not rows:
            return []
        events = []
        start = 0
        if self._header is None:
            self._header = [h.strip() for h in rows[0]]
            start = 1
        positions = [self._header.index(c) for c in self.column_names]
        for row in rows[start:]:
            if not row:
                continue
            raw = tuple(row[p] if p < len(row) else "" for p in positions)
            if self.converters:
                values = tuple(conv(v) for conv, v in zip(self.converters, raw))
            else:
                values = raw
            events.append(ParsedEvent(INSERT, values))
        return events


class JsonLinesParser(Parser):
    """One JSON object per line (reference: JsonLinesParser data_format.rs:1439)."""

    def __init__(
        self, column_names: Sequence[str], defaults: dict[str, Any] | None = None
    ) -> None:
        super().__init__(column_names)
        self.defaults = defaults or {}

    def parse(self, payload: str) -> list[ParsedEvent]:
        events = []
        for line in payload.splitlines():
            line = line.strip()
            if not line:
                continue
            obj = _json.loads(line)
            values = []
            for name in self.column_names:
                if name in obj:
                    v = obj[name]
                    values.append(Json(v) if isinstance(v, (dict, list)) else v)
                elif name in self.defaults:
                    values.append(self.defaults[name])
                else:
                    values.append(None)
            events.append(ParsedEvent(INSERT, tuple(values)))
        return events


class IdentityParser(Parser):
    """Whole payload → one `data` column (plaintext/binary,
    reference: IdentityParser data_format.rs:831)."""

    def __init__(self, binary: bool = False, split_lines: bool = False) -> None:
        super().__init__(["data"])
        self.binary = binary
        self.split_lines = split_lines

    def parse(self, payload: Any) -> list[ParsedEvent]:
        if self.split_lines:
            return [
                ParsedEvent(INSERT, (line,))
                for line in payload.splitlines()
                if line.strip()
            ]
        return [ParsedEvent(INSERT, (payload,))]


# -- readers ----------------------------------------------------------------


class Reader:
    """Produces (payload, source_id, metadata) tuples per poll."""

    #: True when a later payload with the same source_id REPLACES the earlier
    #: one (file re-read) — the driver then retracts the old rows first.
    replaces_sources = False

    #: ``time.monotonic`` arrival of the oldest entry the last ``poll()``
    #: returned, for a reader that sees its entries arrive (QueueReader:
    #: the feed's thread stamps each push). None — files, object stores,
    #: the wire clients — means "unknown": the driver then counts a row's
    #: autocommit window from the poll that found it.
    polled_arrival: float | None = None

    def poll(self) -> tuple[list[tuple[Any, str, dict]], bool]:
        """Returns (entries, done)."""
        raise NotImplementedError


class FsReader(Reader):
    """File/directory/glob scanner with static and streaming modes
    (reference: posix_like.rs + scanner/filesystem.rs — streaming mode diffs
    the directory on each poll: new files insert, changed files replace,
    deleted files retract)."""

    replaces_sources = True

    def __init__(self, path: str | os.PathLike, mode: str = "static", binary: bool = False) -> None:
        self.path = os.fspath(path)
        self.mode = mode
        self.binary = binary
        self._seen: dict[str, tuple[float, int]] = {}  # path -> (mtime, size)
        self._done_static = False

    def _list_files(self) -> list[str]:
        if os.path.isdir(self.path):
            out = []
            for root, _dirs, files in os.walk(self.path):
                out.extend(os.path.join(root, f) for f in sorted(files))
            return sorted(out)
        matches = sorted(_glob.glob(self.path))
        return matches

    def _read_file(self, path: str) -> Any:
        if self.binary:
            with open(path, "rb") as f:
                return f.read()
        with open(path, "r", encoding="utf-8", errors="replace") as f:
            return f.read()

    def poll(self) -> tuple[list[tuple[Any, str, dict]], bool]:
        if self.mode == "static":
            if self._done_static:
                return [], True
            self._done_static = True
            entries = []
            for path in self._list_files():
                try:
                    stat = os.stat(path)
                except FileNotFoundError:
                    continue
                sig = (stat.st_mtime, stat.st_size)
                if self._seen.get(path) == sig:
                    continue  # consumed before a resume; journal replays it
                self._seen[path] = sig
                entries.append(
                    (self._read_file(path), path, {"path": path, "deleted": False})
                )
            return entries, True
        # streaming: diff the directory
        entries = []
        current: dict[str, tuple[float, int]] = {}
        for path in self._list_files():
            try:
                stat = os.stat(path)
            except FileNotFoundError:
                continue
            current[path] = (stat.st_mtime, stat.st_size)
        for path, sig in current.items():
            if self._seen.get(path) != sig:
                entries.append(
                    (self._read_file(path), path, {"path": path, "deleted": False})
                )
        for path in set(self._seen) - set(current):
            entries.append((None, path, {"path": path, "deleted": True}))
        self._seen = current
        return entries, False

    # -- persistence (engine/persistence.py PersistentDriver) ---------------

    def state(self) -> dict:
        return {"seen": dict(self._seen), "done_static": self._done_static}

    def restore_state(self, state: dict) -> None:
        self._seen = dict(state.get("seen", {}))
        # a resumed static read re-scans once: already-consumed files are
        # skipped via _seen, files that appeared/changed while down are read
        self._done_static = False


class QueueReader(Reader):
    """Thread-fed queue (python ConnectorSubject, demo streams)."""

    def __init__(self) -> None:
        self.queue: "queue.Queue[Any]" = queue.Queue()
        self.closed = False

    def push(self, payload: Any, source_id: str = "q", metadata: dict | None = None) -> None:
        # stamped on the feed's thread: the pump may be inside a commit
        self.queue.put((_time.monotonic(), (payload, source_id, metadata or {})))

    def close(self) -> None:
        self.closed = True

    def poll(self) -> tuple[list[tuple[Any, str, dict]], bool]:
        stamped = []
        while True:
            try:
                stamped.append(self.queue.get_nowait())
            except queue.Empty:
                break
        # FIFO: the first is the oldest
        self.polled_arrival = stamped[0][0] if stamped else None
        entries = [entry for _arrived, entry in stamped]
        return entries, self.closed and self.queue.empty()


# -- input driver -----------------------------------------------------------


class InputDriver:
    """Pumps one Reader+Parser into an InputSession; polled between commits
    (the analog of the reference's poller closure, connectors/mod.rs:720)."""

    def __init__(
        self,
        session: InputSession,
        reader: Reader,
        parser: Parser,
        *,
        primary_key_indices: Sequence[int] | None = None,
        source_name: str = "input",
        append_metadata: bool = False,
        autocommit_duration_ms: int | None = None,
    ) -> None:
        self.session = session
        self.reader = reader
        self.parser = parser
        self.pk = list(primary_key_indices) if primary_key_indices else None
        self.source_name = source_name
        #: max seconds this connector's rows may wait, from their arrival,
        #: before a commit (the pump loop batches accordingly); 0 commits
        #: on every poll
        self.autocommit_s = (autocommit_duration_ms or 0) / 1000.0
        self.append_metadata = append_metadata
        self._per_source_rows: dict[str, list[tuple[Pointer, tuple]]] = {}
        self._seq = 0
        self.done = False
        # monitoring counters (internals/monitoring.py reads these)
        self.entries_total = 0
        self.batches_total = 0
        self.last_entry_wall: float | None = None
        #: arrival (``time.monotonic``) of the oldest row fed to the session
        #: and not yet committed: when its reader saw it arrive, or the poll
        #: that found it where the reader cannot say. The pump counts the
        #: autocommit window from it; the runner pops it per commit, with
        #: the one below (``take_pending``)
        self.first_pending_wall: float | None = None
        #: when the poll that set ``first_pending_wall`` ran: the two differ
        #: by the time the row queued while the pump was away
        self.first_pending_polled: float | None = None
        self._m_entries = _metrics.REGISTRY.counter(
            "pathway_connector_entries_total",
            "entries ingested per connector",
            connector=self.source_name,
        )
        self._m_batches = _metrics.REGISTRY.counter(
            "pathway_connector_batches_total",
            "reader poll batches per connector",
            connector=self.source_name,
        )
        # synchronization group pacing (io/_synchronization.py): events
        # whose sync column runs ahead of the group wait here in order
        self.sync_group: Any = None
        self.sync_col: int | None = None
        # (kind, key, values, track, source_id) held back by the group;
        # deque: drains are O(1) per released event
        import collections as _collections

        self._sync_backlog: Any = _collections.deque()
        self._done_pending = False

    def effective_autocommit_s(self) -> float:
        """The autocommit window scaled by device-pipeline pressure: a
        congested device stage wants fewer, fatter commits, so the
        adaptive controller widens ingest windows (up to 4x) while
        commits are staged in flight. Host-only programs and the
        synchronous path (``PATHWAY_TPU_ASYNC_DEVICE=0``) always see the
        configured window unchanged; a 0-window connector (queries)
        stays immediate — scaling zero keeps retrieval overlapped with
        ingest instead of stalled behind it."""
        if self.autocommit_s <= 0.0:
            return self.autocommit_s
        from pathway_tpu.engine import device_pipeline

        return self.autocommit_s * device_pipeline.ingest_window_scale()

    def _key_for(self, values: tuple, source_id: str, index: int) -> Pointer:
        if self.pk is not None:
            return ref_scalar(*[values[i] for i in self.pk])
        self._seq += 1
        return hash_values(
            (self.source_name, source_id, index, self._seq), salt=b"connector"
        )

    def _feed(self, kind: str, key: Pointer, values: tuple | None, track: list | None) -> None:
        if kind == UPSERT:
            # upsert session: insert overlays, None deletes by key
            if values is None:
                self.session.remove(key)
            else:
                self.session.insert(key, values)
        elif kind == INSERT:
            self.session.insert(key, values)
            if track is not None:
                track.append((key, values))
        else:
            self.session.remove(key, values)

    def _sync_admit(self, values: tuple | None) -> bool:
        """Synchronization-group gate: once anything is backlogged, later
        events queue behind it to preserve order. Events without a usable
        sync time (None) are not paced."""
        if self.sync_group is None:
            return True
        if self._sync_backlog:
            return False
        if values is None or values[self.sync_col] is None:
            return True
        return self.sync_group.admit(self, values[self.sync_col])

    def _drain_backlog(self) -> bool:
        produced = False
        while self._sync_backlog:
            kind, key, values, track, _src = self._sync_backlog[0]
            t = values[self.sync_col] if values is not None else None
            if t is not None and not self.sync_group.admit(self, t):
                break
            self._sync_backlog.popleft()
            self._feed(kind, key, values, track)
            produced = True
        self._note_pending()
        return produced

    def _note_pending(self) -> None:
        if self.sync_group is None:
            return
        head_t = None
        if self._sync_backlog:
            head_values = self._sync_backlog[0][2]
            if head_values is not None:
                head_t = head_values[self.sync_col]
        self.sync_group.note_pending(self, head_t)

    def _poll_reader(self) -> tuple[list, bool]:
        """``reader.poll()`` with graceful degradation: transient I/O
        errors (``OSError`` — a network filesystem hiccup, a vanished NFS
        mount, a refused socket) get ``PATHWAY_TPU_CONNECTOR_RETRIES``
        bounded retries (default 3, 0 disables) with exponential backoff
        + jitter, counted in ``pathway_connector_retries_total``.  When
        retries exhaust, the original error re-raises: fail-stop stays
        the explicit fallback.  Non-I/O errors (parse bugs, type errors)
        never retry."""
        try:
            return self.reader.poll()
        except OSError:
            retries = int(
                os.environ.get("PATHWAY_TPU_CONNECTOR_RETRIES", "3")
            )
            if retries <= 0:
                raise
            import random as _random

            counter = _metrics.REGISTRY.counter(
                "pathway_connector_retries_total",
                "connector reader polls retried after transient I/O "
                "errors",
            )
            delay = 0.05
            for attempt in range(retries):
                counter.inc(1)
                _time.sleep(delay * (0.5 + _random.random()))
                delay = min(delay * 2, 2.0)
                try:
                    return self.reader.poll()
                except OSError:
                    if attempt == retries - 1:
                        raise
            raise  # unreachable; keeps the type checker honest

    def poll(self) -> str:
        if self.done:
            return "done"
        produced = False
        took = False  # an entry of this poll reached the session
        if self._sync_backlog:
            produced = self._drain_backlog()
        if self._done_pending:
            entries, done = [], True
        else:
            entries, done = self._poll_reader()
        if entries:
            self.entries_total += len(entries)
            self.batches_total += 1
            self.last_entry_wall = _time.monotonic()
            self._m_entries.inc(len(entries))
            self._m_batches.inc(1)
        replaces = self.reader.replaces_sources
        notify_source = getattr(self.session, "on_source", None)
        for payload, source_id, metadata in entries:
            if notify_source is not None:
                notify_source(source_id)
            # retract previously-emitted rows of a replaced/deleted source
            old_rows = self._per_source_rows.pop(source_id, None) if replaces else None
            if old_rows:
                for key, row in old_rows:
                    self.session.remove(key, row)
                produced = took = True
            if replaces and self._sync_backlog:
                # held-back events of the replaced source version must not
                # surface later: they were superseded before emission
                self._sync_backlog = type(self._sync_backlog)(
                    e for e in self._sync_backlog if e[4] != source_id
                )
            if metadata.get("deleted"):
                continue
            if hasattr(self.parser, "reset"):
                self.parser.reset()
            events = self.parser.parse(payload)
            new_rows: list[tuple[Pointer, tuple]] = []
            for i, event in enumerate(events):
                values = event.values
                if values is not None and self.append_metadata:
                    values = values + (Json(dict(metadata)),)
                if event.key is not None:
                    if len(event.key) == 1 and isinstance(event.key[0], Pointer):
                        key = event.key[0]  # loopback streams keep row ids
                    else:
                        key = ref_scalar(*event.key)
                elif values is not None:
                    key = self._key_for(values, source_id, i)
                else:
                    raise ValueError(
                        "connector event without values needs an explicit key"
                    )
                track = new_rows if (event.kind == INSERT and replaces) else None
                if self._sync_admit(values):
                    self._feed(event.kind, key, values, track)
                    produced = took = True
                else:
                    self._sync_backlog.append(
                        (event.kind, key, values, track, source_id)
                    )
            if replaces and events:
                # backlogged inserts append into this same list when released
                self._per_source_rows[source_id] = new_rows
        self._note_pending()
        if produced and self.first_pending_wall is None:
            # events a synchronization group held back count from the poll
            # that released them, like the entries of a reader with no stamp
            polled = _time.monotonic()
            arrived = (
                getattr(self.reader, "polled_arrival", None) if took else None
            )
            self.first_pending_wall = polled if arrived is None else arrived
            self.first_pending_polled = polled
        if done:
            if self._sync_backlog:
                # the group still holds events back; report idle until the
                # other sources release them
                self._done_pending = True
                return "data" if produced else "idle"
            self.done = True
            if self.sync_group is not None:
                self.sync_group.mark_done(self)
            return "done"
        return "data" if produced else "idle"

    def take_pending(self) -> tuple[float | None, float | None]:
        """Pop what a commit takes from this driver: its oldest pending
        row's arrival and the time of the poll that took it (both by
        ``time.monotonic``; None where no row reached the session)."""
        taken = self.first_pending_wall, self.first_pending_polled
        self.first_pending_wall = self.first_pending_polled = None
        return taken


class BatchScheduleDriver:
    """Feeds predefined batches, one per commit (debug.StreamGenerator)."""

    def __init__(self, session: InputSession, batches: list[list[tuple[str, Pointer, tuple]]]):
        self.session = session
        self.batches = list(batches)

    def poll(self) -> str:
        if not self.batches:
            return "done"
        batch = self.batches.pop(0)
        for kind, key, values in batch:
            if kind == INSERT:
                self.session.insert(key, values)
            else:
                self.session.remove(key, values)
        return "data" if batch or self.batches else "done"


# -- formatters / writers ---------------------------------------------------


class Formatter:
    def header(self, column_names: Sequence[str]) -> str | None:
        return None

    def format(
        self, key: Pointer, values: tuple, column_names: Sequence[str], time: int, diff: int
    ) -> str:
        raise NotImplementedError


def _plain(value: Any) -> Any:
    if isinstance(value, Json):
        return value.value
    if isinstance(value, Pointer):
        return repr(value)
    if isinstance(value, tuple):
        return [_plain(v) for v in value]
    return value


class JsonLinesFormatter(Formatter):
    """(reference: JsonLinesFormatter data_format.rs:1822 — row + diff + time)"""

    def format(self, key, values, column_names, time, diff):
        obj = {name: _plain(v) for name, v in zip(column_names, values)}
        obj["diff"] = diff
        obj["time"] = time
        return _json.dumps(obj, default=str)


class DsvFormatter(Formatter):
    """(reference: DsvFormatter data_format.rs:938 — row + time + diff cols)"""

    def __init__(self, delimiter: str = ",") -> None:
        self.delimiter = delimiter

    @staticmethod
    def _row(out: _io.StringIO) -> str:
        # keep the default \r\n lineterminator while writing: the csv
        # module only quotes fields containing the delimiter, quotechar or
        # lineterminator characters, so with lineterminator="" an embedded
        # newline would be emitted RAW and split the record. Strip the
        # terminator afterwards (FileWriter adds its own "\n").
        return out.getvalue().rstrip("\r\n")

    def header(self, column_names: Sequence[str]) -> str:
        out = _io.StringIO()
        _csv.writer(out, delimiter=self.delimiter).writerow(
            list(column_names) + ["time", "diff"]
        )
        return self._row(out)

    def format(self, key, values, column_names, time, diff):
        out = _io.StringIO()
        _csv.writer(out, delimiter=self.delimiter).writerow(
            [_plain(v) for v in values] + [time, diff]
        )
        return self._row(out)


#: every live FileWriter, registered at construction.  Sink attachment
#: returns no driver handle (subscribe_table wires callbacks directly),
#: so mesh recovery reaches file sinks through this registry to rewind
#: them past rolled-back commits.
import weakref as _weakref

FILE_WRITERS: "_weakref.WeakSet[FileWriter]" = _weakref.WeakSet()


class FileWriter:
    """Line-oriented file sink (reference: FileWriter data_storage.rs:630).

    Tracks the byte offset at each commit boundary (a bounded trail of
    recent commits) so a mesh-recovery rollback can truncate exactly the
    lines of un-happened commits — the recovered run re-emits them with
    identical timestamps, keeping outputs bit-identical to a fault-free
    run.

    The trail is also made *durable*: every commit atomically rewrites a
    ``<path>.pw-offsets`` sidecar (run id + header end + trail).  A
    process relaunched under the SAME ``PATHWAY_RUN_ID`` (supervised
    restart after a full-mesh crash, or a rescale relaunch) resumes the
    existing sink file instead of truncating it: the tail past the last
    recorded commit boundary is dropped (those lines belonged to commits
    that never became durable) and the restored trail lets the startup
    rollback rewind to the mesh's last common commit — exactly-once
    output across a cold restart.  A fresh run gets a fresh run id, so it
    never resumes a stale file."""

    #: commit-boundary offsets kept per writer (matches the snapshot
    #: ring depth with slack; older commits can no longer be rolled to)
    _OFFSET_TRAIL = 8

    def __init__(self, path: str | os.PathLike, formatter: Formatter, column_names: Sequence[str]):
        self.path = os.fspath(path)
        self.formatter = formatter
        self.column_names = list(column_names)
        self._offsets_path = self.path + ".pw-offsets"
        self._run_id = os.environ.get("PATHWAY_RUN_ID", "")
        resumed = self._try_resume()
        if not resumed:
            self._file = open(self.path, "w", encoding="utf-8")
            header = formatter.header(self.column_names)
            if header:
                self._file.write(header + "\n")
            self._header_end = self._file.tell()
            self._commit_offsets: dict[int, int] = {}
        FILE_WRITERS.add(self)

    def _try_resume(self) -> bool:
        """Reopen an existing sink file when the durable offset sidecar
        proves it belongs to THIS run (same ``PATHWAY_RUN_ID``)."""
        if not self._run_id or not os.path.exists(self.path):
            return False
        try:
            with open(self._offsets_path, "r", encoding="utf-8") as fh:
                meta = _json.load(fh)
        except (OSError, ValueError):
            return False
        if meta.get("run_id") != self._run_id:
            return False
        try:
            offsets = {
                int(t): int(o) for t, o in meta["offsets"].items()
            }
            header_end = int(meta["header_end"])
        except (KeyError, TypeError, ValueError):
            return False
        self._file = open(self.path, "r+", encoding="utf-8")
        self._header_end = header_end
        self._commit_offsets = offsets
        # drop any partially written tail: bytes past the newest durable
        # commit boundary belong to a commit that never became durable
        durable_end = max(offsets.values()) if offsets else header_end
        self._file.truncate(durable_end)
        self._file.seek(durable_end)
        return True

    def _persist_offsets(self) -> None:
        """Atomically rewrite the sidecar (tmp + replace) so a crash
        leaves either the old or the new trail, never a torn one."""
        if not self._run_id:
            return
        tmp = self._offsets_path + ".tmp"
        try:
            with open(tmp, "w", encoding="utf-8") as fh:
                _json.dump(
                    {
                        "run_id": self._run_id,
                        "header_end": self._header_end,
                        "offsets": {
                            str(t): o
                            for t, o in self._commit_offsets.items()
                        },
                    },
                    fh,
                )
            os.replace(tmp, self._offsets_path)
        except OSError:
            pass

    def on_change(self, key: Pointer, values: tuple, time: int, diff: int) -> None:
        self._file.write(
            self.formatter.format(key, values, self.column_names, time, diff) + "\n"
        )

    def on_time_end(self, time: int) -> None:
        if not self._file.closed:
            self._file.flush()
            self._commit_offsets[time] = self._file.tell()
            while len(self._commit_offsets) > self._OFFSET_TRAIL:
                del self._commit_offsets[min(self._commit_offsets)]
            self._persist_offsets()

    def rewind_to(self, time: int) -> None:
        """Truncate everything written after commit ``time`` (``-1`` =
        back to the header).  No-op when nothing newer was written."""
        if self._file.closed:
            return
        if time < 0:
            offset = self._header_end
        elif time in self._commit_offsets:
            offset = self._commit_offsets[time]
        else:
            newer = [t for t in self._commit_offsets if t > time]
            if not newer:
                return  # nothing after `time` reached this sink
            raise ValueError(
                f"sink {self.path}: cannot rewind to commit {time} — "
                f"its boundary offset is no longer tracked (trail keeps "
                f"{self._OFFSET_TRAIL} commits)"
            )
        self._file.flush()
        self._file.truncate(offset)
        self._file.seek(offset)
        self._commit_offsets = {
            t: o for t, o in self._commit_offsets.items() if t <= time
        }
        self._persist_offsets()

    def on_end(self) -> None:
        if not self._file.closed:
            self._file.flush()
            self._file.close()
