"""Readers of the per-layer metrics. A metric's file under
``layer_metrics/`` names one of these and gives its parameters; a reader
that finds nothing to read returns ``None`` and the metric is left out of
the line — never 0 for a share of a peak.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import os
from typing import Any

import numpy as np

import costs
import trace as trace_mod


@dataclasses.dataclass
class Context:
    cell: Any
    obs: Any
    schedule: Any
    seconds: float  # the window's length
    chips: int
    peak: dict | None  # this device kind's row of peaks.json
    trace: dict | None  # events, start, stop, window_s of the traced part
    flops: float  # model FLOPs of the work finished in the window (the pipeline's work_flops)


def _bucket(n: int, minimum: int = 8) -> int:
    b = minimum
    while b < n:
        b *= 2
    return b


def counter_ratio(ctx: Context, numerator: list[str], denominator: list[str]):
    """Sum of some counters over the sum of others, over the whole window."""
    den = sum(ctx.obs.counters[name] for name in denominator)
    if den == 0:
        return None
    return sum(ctx.obs.counters[name] for name in numerator) / den


def docs_per_commit(ctx: Context):
    """Documents at the sink over the distinct commit times it saw."""
    commits = ctx.obs.counters.get("doc_commits", 0)
    return ctx.obs.documents.acked / commits if commits else None


def generator_late_p95(ctx: Context, stream: str):
    """95th percentile of (actual send - due time) of an open-loop feed, ms."""
    plan = getattr(ctx.schedule, stream)
    if plan is None or plan.due_s is None:
        return None
    sent = getattr(ctx.obs, stream).sent[:-1]  # without the primer
    late = (sent - ctx.obs.t0 - plan.due_s)[~np.isnan(sent)]
    return float(np.percentile(late, 95) * 1e3) if len(late) else None


def wait_percentile(ctx: Context, stream: str, q: float):
    """A percentile of (the sink's callback - due time) over all the events
    of an open-loop feed, ms: the end-to-end latencies' arithmetic
    (``Observed.waits_ms``), for a tail too unsteady to carry a bound."""
    plan = getattr(ctx.schedule, stream)
    if plan is None or plan.due_s is None or not len(plan.due_s):
        return None
    return float(np.percentile(ctx.obs.waits_ms(stream, plan), q))


def step_mfu(ctx: Context):
    """The whole step's share of the peak: model FLOPs of the real tokens
    embedded in the window over the window, the chips and their peak."""
    if ctx.peak is None or ctx.flops <= 0:
        return None
    return 100.0 * ctx.flops / ctx.seconds / (ctx.chips * ctx.peak["flops_per_s"])


def _traced_calls(ctx: Context, kind: str) -> list[tuple]:
    start, stop = ctx.trace["start"], ctx.trace["stop"]
    return [c for c in ctx.obs.device_calls if c[1] == kind and start <= c[0] <= stop]


def encoder_program_roofline(ctx: Context, patterns: list[str]):
    """Least time for the padded embed steps dispatched in the traced part
    over the device time of the embed programs in it."""
    if ctx.trace is None or ctx.peak is None:
        return None
    seconds, _ = trace_mod.module_seconds(ctx.trace["events"], patterns)
    calls = _traced_calls(ctx, "embed")
    if seconds <= 0 or not calls:
        return None
    enc = ctx.cell.config["encoder"]
    least = sum(
        costs.roofline_seconds(
            batch * costs.encoder_flops(seq, enc), costs.encoder_step_bytes(batch, seq, enc), ctx.peak
        )[0]
        for _, _, batch, seq in calls
    )
    return 100.0 * least / seconds


def knn_search_roofline(ctx: Context, patterns: list[str]):
    """Least time for the scans dispatched in the traced part over the
    device time of the search programs in it; per chip for a sharded index."""
    if ctx.trace is None or ctx.peak is None:
        return None
    seconds, _ = trace_mod.module_seconds(ctx.trace["events"], patterns)
    calls = _traced_calls(ctx, "search")
    if seconds <= 0 or not calls:
        return None
    dim = ctx.cell.config["encoder"]["hidden_size"]
    rows = ctx.cell.config["index"]["capacity"] // ctx.chips
    least = sum(
        costs.roofline_seconds(
            costs.knn_search_flops(_bucket(n), rows, dim), costs.knn_search_bytes(_bucket(n), rows, dim), ctx.peak
        )[0]
        for _, _, n, _ in calls
    )
    return 100.0 * least / seconds


def device_idle_share(ctx: Context):
    """1 - busy over the traced part, on the fullest chip."""
    if ctx.trace is None:
        return None
    busy = trace_mod.busy_seconds(ctx.trace["events"])
    if not busy:
        return None
    return 100.0 * (1.0 - max(busy.values()) / ctx.trace["window_s"])


def load_module(name: str, path: str):
    """The file at ``path`` as a module of its own, outside ``sys.modules``."""
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def find(name: str, directory: str):
    """The reader a metric's file names: one of this module's, or — so that a
    later PR adds a reader without editing this file — the function ``read``
    of ``<directory>/<name>.py``, called as ``read(ctx, **params)``."""
    if name in READERS:
        return READERS[name]
    path = os.path.join(directory, name + ".py")
    if not os.path.exists(path):
        raise KeyError(f"no reader {name!r}: neither in readers.py nor at {path}")
    return load_module("layer_metric_reader_" + name, path).read


READERS = {
    "counter_ratio": counter_ratio,
    "docs_per_commit": docs_per_commit,
    "generator_late_p95": generator_late_p95,
    "wait_percentile": wait_percentile,
    "step_mfu": step_mfu,
    "encoder_program_roofline": encoder_program_roofline,
    "knn_search_roofline": knn_search_roofline,
    "device_idle_share": device_idle_share,
}
