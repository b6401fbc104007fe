"""Reader of a generate call's two halves on the device, and of a query's
wait for its first token. The program's time line (``pathway_tpu.internals
.tracing.commit_timeline()``: one record a commit, with the interval of the
``chat.batch`` stage inside it) is on the host's ``perf_counter_ns``; the
device's executions are on the profiler's clock. The run thread's
``pw:commit`` events in the trace are the same commits as the records that
lie whole inside the traced part of the window, so the two sequences are
laid over each other, in order, at the shift where their durations agree
(a commit cut by either end of the trace has an event and no record, or a
record and no event, and is left out), and the distance between the clocks
is the median of ``event.start_ns - record.t0_ns`` over the matched
commits; its spread goes to standard error. Then the ``XLA Modules``
executions whose name holds one of ``patterns`` and whose middle lies inside
a matched record's ``chat.batch`` interval are that commit's calls'.

``what``:

- ``prefill_ms``, ``decode_ms``: those executions' device ms over the
  matched records' ``chat.batch`` calls (``patterns``: ``jit_chat_prefill``,
  ``jit_chat_decode``);
- ``first_token_ms``: over the queries a matched commit answered, the median
  of (the end of that commit's last ``patterns`` execution, brought back to
  the host's clock) - the feed's send: a prefill's end is when every row of
  the call has its first token. It is read over the traced seconds alone.

``None`` where there is no trace, the program keeps no time line, no commit
matches, or no matched commit made a call.
"""

from __future__ import annotations

import statistics
import sys

import numpy as np

import trace as trace_mod

COMMIT_EVENT = trace_mod.STAGE_PREFIX + "commit"
CALL_STAGE = "chat.batch"
#: a record and its event are the same commit where their lengths differ by
#: less than this: the annotation opens before the stage's clock is read and
#: closes after it (microseconds), and two commits differ by far more
SAME_COMMIT_NS = 250_000.0


def match_commits(events: list, records: list[dict], start_s: float, stop_s: float):
    """``(pairs, offset_ns, spread_ns)``: the ``(record, event)`` pairs of
    the commits that are both a record whole inside ``start_s``..``stop_s``
    (``perf_counter`` seconds) and a ``pw:commit`` event of the run thread,
    the clocks' distance and how far a matched commit's own lies from it
    at most. No pair: ``([], None, None)``."""
    thread = trace_mod.run_thread(events)
    marks = sorted(
        (e for e in events if e.name == COMMIT_EVENT and (e.plane, e.line) == thread),
        key=lambda e: e.start_ns,
    )
    inside = [r for r in records if r["t0_ns"] >= start_s * 1e9 and r["t1_ns"] <= stop_s * 1e9]
    best: list = []
    for shift in range(-len(inside) + 1, len(marks)):  # marks[i + shift] over inside[i]
        pairs = [
            (record, marks[i + shift])
            for i, record in enumerate(inside)
            if 0 <= i + shift < len(marks)
            and abs(marks[i + shift].dur_ns - (record["t1_ns"] - record["t0_ns"])) < SAME_COMMIT_NS
        ]
        if len(pairs) > len(best):
            best = pairs
    if not best:
        return [], None, None
    distances = [event.start_ns - record["t0_ns"] for record, event in best]
    offset = statistics.median(distances)
    # a shift that pairs the wrong commits leaves distances that differ by
    # whole commits: keep the pairs that agree on the clocks' distance
    agreed = [pair for pair, d in zip(best, distances) if abs(d - offset) < SAME_COMMIT_NS]
    spread = max(abs(event.start_ns - record["t0_ns"] - offset) for record, event in agreed)
    return agreed, offset, spread


def calls_on_device(events: list, pairs: list, offset_ns: float, patterns: list[str]) -> list[tuple]:
    """For every matched record that made a call: ``(record, device ns of
    its executions, end of the last one on the host's clock)``."""
    chips = set(trace_mod.device_planes(events))
    runs = [
        e for e in events
        if e.plane in chips and e.line == "XLA Modules" and any(p in e.name for p in patterns)
    ]
    out = []
    for record, _event in pairs:
        call = record["stages"].get(CALL_STAGE)
        if call is None:
            continue
        lo, hi = call["first_t0_ns"] + offset_ns, call["last_t1_ns"] + offset_ns
        own = [e for e in runs if lo <= e.start_ns + e.dur_ns / 2 <= hi]
        if own:
            last_end = max(e.start_ns + e.dur_ns for e in own) - offset_ns
            out.append((record, sum(e.dur_ns for e in own), last_end))
    return out


def read(ctx, what: str, patterns: list[str]):
    if ctx.trace is None:
        return None
    from pathway_tpu.internals import tracing

    commit_timeline = getattr(tracing, "commit_timeline", None)
    if commit_timeline is None:
        print("chat_call_device: the program keeps no time line of its commits", file=sys.stderr)
        return None
    events = ctx.trace["events"]
    pairs, offset, spread = match_commits(
        events, commit_timeline(), ctx.trace["start"], ctx.trace["stop"]
    )
    if not pairs:
        print("chat_call_device: no commit of the time line matches a pw:commit event of the trace", file=sys.stderr)
        return None
    calls = calls_on_device(events, pairs, offset, patterns)
    made = sum(record["stages"][CALL_STAGE]["calls"] for record, _ns, _end in calls)
    print(
        f"chat_call_device {what}: {len(pairs)} commits matched, the clocks' distance within "
        f"{spread / 1e3:.1f} us over them; {made} calls in {len(calls)} of them",
        file=sys.stderr,
    )
    if not calls:
        return None
    if what in ("prefill_ms", "decode_ms"):
        return sum(ns for _record, ns, _end in calls) / 1e6 / made
    if what != "first_token_ms":
        raise ValueError(f"chat_call_device: unknown reading {what!r}")
    seen = ctx.obs.queries
    sent, commit = seen.sent[:-1], seen.commit[:-1]  # without the primer
    ended = {record["time"]: end for record, _ns, end in calls}
    waits = [
        (ended[int(time)] - sent[i] * 1e9) / 1e6
        for i, time in enumerate(commit)
        if int(time) in ended and not np.isnan(sent[i])
    ]
    return float(np.median(waits)) if waits else None
