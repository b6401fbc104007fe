"""From a profiler trace to numbers: device busy time, time per program and
per operation, and what the host was doing while the device sat idle.

``load_events`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote into
plain tuples; everything after it is arithmetic on those tuples, checked in
``tests/`` on a small trace recorded on the chip (``tests/trace_small.json``).
"""

from __future__ import annotations

import glob
import os
from typing import Iterable, NamedTuple

#: the benchmark's own host spans carry this prefix (harness.run_window's ``span``)
SPAN_PREFIX = "bench:"
#: the program's stages (``pathway_tpu.internals.tracing``) carry this one
STAGE_PREFIX = "pw:"
OTHER = "engine: other"


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float


def load_events(trace_dir: str) -> list[Event]:
    """Device events, the program's stages and the benchmark's host spans of
    the newest trace under ``trace_dir``. A host thread is a line, and every
    Python thread's line has the same name: a host event's ``line`` is
    ``<name>#<place among the plane's lines>``, which tells them apart."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    events = []
    for plane in data.planes:
        device = plane.name.startswith("/device:")
        for i, line in enumerate(plane.lines):
            name = line.name if device else f"{line.name}#{i}"
            for ev in line.events:
                if device or ev.name.startswith((STAGE_PREFIX, SPAN_PREFIX)):
                    events.append(Event(plane.name, name, ev.name, ev.start_ns, ev.duration_ns))
    return events


def device_planes(events: Iterable[Event]) -> list[str]:
    """Planes of chips (``/device:TPU:0``), without the chips' side planes
    (``/device:TPU:0 SparseCore``...)."""
    names = {e.plane for e in events if e.plane.startswith("/device:")}
    return sorted(n for n in names if " " not in n.split("/device:")[1])


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[tuple[float, float]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1] = (merged[-1][0], end)
        else:
            merged.append((start, end))
    return merged


def _ops(events: Iterable[Event], plane: str) -> list[Event]:
    return [e for e in events if e.plane == plane and e.line == "XLA Ops"]


def _modules(events: Iterable[Event], plane: str) -> list[Event]:
    return [e for e in events if e.plane == plane and e.line == "XLA Modules"]


def busy_seconds(events: list[Event]) -> dict[str, float]:
    """Per chip, the seconds in which some operation ran on it."""
    out = {}
    for plane in device_planes(events):
        spans = _union([(e.start_ns, e.start_ns + e.dur_ns) for e in _ops(events, plane)])
        out[plane] = sum(end - start for start, end in spans) / 1e9
    return out


def module_seconds(events: list[Event], patterns: list[str]) -> tuple[float, int]:
    """Device seconds and count of the program executions whose name holds
    one of ``patterns``, on the chip that ran most of them."""
    best = (0.0, 0)
    for plane in device_planes(events):
        hits = [e for e in _modules(events, plane) if any(p in e.name for p in patterns)]
        total = sum(e.dur_ns for e in hits) / 1e9
        if total > best[0]:
            best = (total, len(hits))
    return best


def op_family(name: str) -> str:
    """An operation's name without its HLO text and its serial number:
    ``%convert_reduce_fusion.9 = (f32[...`` is ``convert_reduce_fusion``."""
    head = name.split(" = ", 1)[0].lstrip("%")
    stem, _, serial = head.rpartition(".")
    return stem if stem and serial.isdigit() else head


def top_device_ops(events: list[Event], limit: int = 10) -> list[list]:
    """Operation families by total device time on the busiest chip."""
    busy = busy_seconds(events)
    if not busy:
        return []
    plane = max(busy, key=busy.get)
    totals: dict[str, float] = {}
    for e in _ops(events, plane):
        family = op_family(e.name)
        totals[family] = totals.get(family, 0.0) + e.dur_ns / 1e9
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:limit]
    return [[name, seconds] for name, seconds in ranked]


def run_thread(events: Iterable[Event]) -> tuple[str, str] | None:
    """The (plane, line) of the thread that commits: the one that holds most
    ``pw:commit`` stages."""
    counts: dict[tuple[str, str], int] = {}
    for e in events:
        if e.name == STAGE_PREFIX + "commit":
            counts[(e.plane, e.line)] = counts.get((e.plane, e.line), 0) + 1
    return max(counts, key=counts.get) if counts else None


def innermost_segments(spans: Iterable[tuple[float, float, str]]) -> list[tuple[float, float, str]]:
    """``(start, end, name)`` spans, nested as one thread's are (or lapping,
    as two threads' may), as segments that do not overlap, each under the
    name of the span opened last in it, in time order."""
    out: list[tuple[float, float, str]] = []
    stack: list[tuple[float, str]] = []  # (end, name) of the open spans
    cursor = 0.0

    def emit(until: float) -> None:
        nonlocal cursor
        if stack and until > cursor:
            out.append((cursor, until, stack[-1][1]))
        cursor = max(cursor, until)

    for start, neg_end, name in sorted((start, -end, name) for start, end, name in spans):
        while stack and stack[-1][0] <= start:
            emit(stack[-1][0])
            stack.pop()
        emit(start)
        cursor = max(cursor, start)
        stack.append((-neg_end, name))
    while stack:
        emit(stack[-1][0])
        stack.pop()
    return out


def _lay(intervals: list[tuple[float, float]], segments: list[tuple[float, float, str]], totals: dict):
    """Give every instant of ``intervals`` (sorted, apart) to the segment
    that holds it, adding nanoseconds to ``totals`` by segment name; returns
    the parts no segment holds."""
    bare: list[tuple[float, float]] = []
    first = 0
    for lo, hi in intervals:
        while first < len(segments) and segments[first][1] <= lo:
            first += 1
        cursor, i = lo, first
        while i < len(segments) and segments[i][0] < hi:
            start, end, name = segments[i]
            if start > cursor:
                bare.append((cursor, start))
            lap = min(hi, end) - max(cursor, start)
            if lap > 0:
                totals[name] = totals.get(name, 0.0) + lap
            cursor = max(cursor, min(hi, end))
            i += 1
        if hi > cursor:
            bare.append((cursor, hi))
    return bare


def idle_split(events: list[Event]) -> dict:
    """The idle nanoseconds of the busiest chip — the gaps between its
    operations — by what the host was doing. Each idle instant goes to the
    innermost ``pw:`` stage open then on the thread that commits (never to
    whatever covers most of a gap: ``pw:run`` covers everything); an instant
    under no stage to the benchmark's ``bench:`` span open then, on any
    thread; what is left to ``engine: other``. ``by_name`` holds stages as
    ``pw:<stage>`` and spans bare."""
    busy = busy_seconds(events)
    if not busy:
        return {}
    plane = max(busy, key=busy.get)
    ops = _union([(e.start_ns, e.start_ns + e.dur_ns) for e in _ops(events, plane)])
    gaps = [(a_end, b_start) for (_, a_end), (b_start, _) in zip(ops, ops[1:])]
    thread = run_thread(events)
    stages = innermost_segments(
        (e.start_ns, e.start_ns + e.dur_ns, e.name)
        for e in events
        if e.name.startswith(STAGE_PREFIX) and (e.plane, e.line) == thread
    )
    spans = innermost_segments(
        (e.start_ns, e.start_ns + e.dur_ns, e.name[len(SPAN_PREFIX):])
        for e in events
        if e.name.startswith(SPAN_PREFIX)
    )
    by_name: dict[str, float] = {}
    no_stage = _lay(gaps, stages, by_name)
    no_span = _lay(no_stage, spans, by_name)
    if no_span:
        by_name[OTHER] = sum(hi - lo for lo, hi in no_span)
    return {
        "plane": plane, "busy_s": busy[plane], "thread": thread, "ops": ops, "stages": stages, "by_name": by_name,
        "idle_ns": sum(hi - lo for lo, hi in gaps),
        "no_stage_ns": sum(hi - lo for lo, hi in no_stage),
    }


def idle_gaps(events: list[Event], limit: int = 10) -> list[list]:
    """Idle seconds of the busiest chip by what the host was doing
    (:func:`idle_split`), the ``limit`` largest."""
    totals = idle_split(events).get("by_name", {})
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:limit]
    return [[name, ns / 1e9] for name, ns in ranked]


def summarize(events: list[Event], window_s: float, chips: int) -> dict:
    """What the result line's ``device`` and ``breakdown`` take from a trace."""
    busy = busy_seconds(events)
    used = sorted(busy.values(), reverse=True)[:chips]
    return {
        "busy_s": sum(used) / max(len(used), 1),
        "window_s": window_s,
        "device_ops": top_device_ops(events),
        "idle_gaps": idle_gaps(events),
    }
