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

#: the benchmark's own host spans carry this prefix (harness.annotate)
SPAN_PREFIX = "bench:"
OTHER = "engine: other"


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float


def load_events(trace_dir: str) -> list[Event]:
    """Device events and the benchmark's host spans of the newest trace
    under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    events = []
    for plane in data.planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            for ev in line.events:
                if device or ev.name.startswith(SPAN_PREFIX):
                    events.append(
                        Event(plane.name, line.name, ev.name, ev.start_ns, ev.duration_ns)
                    )
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


def idle_gaps(events: list[Event], limit: int = 10) -> list[list]:
    """Idle seconds of the busiest chip by what the host was doing: each gap
    between device operations is given to the benchmark's host span that
    covers most of it, or to ``engine: other``."""
    busy = busy_seconds(events)
    if not busy:
        return []
    plane = max(busy, key=busy.get)
    spans = _union([(e.start_ns, e.start_ns + e.dur_ns) for e in _ops(events, plane)])
    host = sorted(
        (e.start_ns, e.start_ns + e.dur_ns, e.name[len(SPAN_PREFIX):])
        for e in events
        if e.name.startswith(SPAN_PREFIX)
    )
    totals: dict[str, float] = {}
    first = 0
    for (_, gap_start), (gap_end, _) in zip(spans, spans[1:]):
        while first < len(host) and host[first][1] <= gap_start:
            first += 1
        cover: dict[str, float] = {}
        i = first
        while i < len(host) and host[i][0] < gap_end:
            lap = min(gap_end, host[i][1]) - max(gap_start, host[i][0])
            if lap > 0:
                cover[host[i][2]] = cover.get(host[i][2], 0.0) + lap
            i += 1
        name = max(cover, key=cover.get) if cover else OTHER
        if cover and cover[name] < 0.5 * (gap_end - gap_start):
            name = OTHER
        totals[name] = totals.get(name, 0.0) + (gap_end - gap_start) / 1e9
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:limit]
    return [[name, seconds] for name, seconds in ranked]


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
