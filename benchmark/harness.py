"""One run of one cell: what every pipeline shares. The cell's files, the
feeds and their clock, what the feeds and the sinks saw, the traced part of
the window, ``pw.run()`` and the drain, the end-to-end and per-layer
metrics, the result line.

What belongs to one graph — weights, set-up and warm-up, the graph and its
sinks, the work a step counts, the comparison with the reference — is the
cell's pipeline: ``benchmark/pipelines/<name>.py``, named by the
configuration's ``"pipeline"`` key (``pipelines/live_index.py`` where it has
none; its docstring has the interface).

``run.py`` looks for the chip and calls :func:`run_cell`; the tests call it
without the look, at a toy size.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import json
import os
import sys
import tempfile
import threading
import time
from typing import Any

import numpy as np

import check
import readers
import trace as trace_mod
import traffic

HERE = os.path.dirname(os.path.abspath(__file__))
#: how long past the window's close an event may still be acknowledged
GRACE_S = 60.0
#: the traced part of a ``--trace 1`` window: starts this long after the
#: window opens (or a quarter into a shorter one) and lasts this long
TRACE_START_S, TRACE_LENGTH_S = 2.0, 4.0
PIPELINES = os.path.join(HERE, "pipelines")
#: the pipeline of a configuration that names none
DEFAULT_PIPELINE = "live_index"
#: what a pipeline's module defines (``pipelines/live_index.py`` says what each is)
PIPELINE_INTERFACE = ("weights", "set_up", "build", "restore", "work_flops", "facts", "compare")


# -- what a cell is -----------------------------------------------------------


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    limits: dict  # of the numbers ``correct`` compares (limits/<cell>.json)
    end_to_end: list[dict]  # this cell's end-to-end metrics (BENCHMARK.json)
    per_layer: list[dict]  # this cell's per-layer metrics, each with its file
    pipeline: Any  # the module the configuration names (find_pipeline)


def find_pipeline(name: str, directory: str = PIPELINES):
    """The module ``<directory>/<name>.py``, as ``readers.find`` finds a
    reader; a name with no file, or a file without the interface, ends the
    run here, before any device call."""
    path = os.path.join(directory, name + ".py")
    if not os.path.exists(path):
        known = sorted(f[:-3] for f in os.listdir(directory) if f.endswith(".py"))
        raise SystemExit(f"unknown pipeline {name!r}: no {path}; known: {known}")
    module = readers.load_module("pipeline_" + name, path)
    missing = [part for part in PIPELINE_INTERFACE if not callable(getattr(module, part, None))]
    if missing:
        raise SystemExit(f"pipeline {name!r} ({path}) does not define {missing}")
    return module


def load_cell(root: str, workload: str) -> Cell:
    """Everything about a cell, found by the names in ``BENCHMARK.json``."""
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: {sorted(cells)}")
    entry = cells[workload]
    config_file = {c["name"]: c["file"] for c in bench["configs"]}[entry["config"]]
    with open(os.path.join(root, config_file)) as fh:
        config = json.load(fh)
    with open(os.path.join(HERE, "traffic", entry["traffic"] + ".json")) as fh:
        mix = json.load(fh)
    with open(os.path.join(HERE, "limits", workload + ".json")) as fh:
        limits = json.load(fh)

    def reports(metric: dict) -> bool:
        return "workloads" not in metric or workload in metric["workloads"]

    end_to_end = [m for m in bench["end_to_end"] if reports(m)]
    reported = {m["name"] for m in end_to_end}
    per_layer = []
    for metric in bench["per_layer"]:
        if reports(metric) if "workloads" in metric else metric["moves"] in reported:
            with open(os.path.join(HERE, "layer_metrics", metric["name"] + ".json")) as fh:
                per_layer.append({**metric, **json.load(fh)})
    pipeline = find_pipeline(config.get("pipeline", DEFAULT_PIPELINE))
    return Cell(workload, entry["chips"], config, mix, limits, end_to_end, per_layer, pipeline)


# -- what the feeds, the sinks and the wrappers saw ---------------------------


class Seen:
    """One stream's events as its feed and its sink saw them. The last slot
    of every array is the feed's primer."""

    def __init__(self, stream: traffic.Stream | None, field: str) -> None:
        n = len(stream.texts) + 1 if stream is not None else 0
        self.field = field  # the column that carries an event's number
        self.sent = np.full(n, np.nan)
        self.ack = np.full(n, np.nan)
        self.commit = np.full(n, -1, np.int64)
        self.commits: set = set()  # distinct commit times at the sink
        self.repeats = 0
        self.acked = 0

    def n_sent(self) -> int:
        return int(np.count_nonzero(~np.isnan(self.sent)))


class Observed:
    """Filled by the feeds, the sinks and the pipeline's wrappers while the
    window runs; read once it has closed. One writer a field: the engine's
    thread for the sinks and wrappers, each feed for its own send times.

    Before the window opens each feed sends one primer (its first text
    again, under the id one past its last) and waits for it at the sink, so
    that the whole path has run once; a primer went through the graph like
    any event (the live index holds it and may answer with it), and is in no
    metric."""

    def __init__(self, schedule: traffic.Schedule) -> None:
        self.documents = Seen(schedule.documents, "doc_id")
        self.queries = Seen(schedule.queries, "query_id")
        self.primers = 2 if schedule.queries else 1
        self.compiles_at_open = 0
        self.opened_at = 0.0  # time.time() at the window's start, for setup_s
        self.t0 = 0.0  # the same instant by time.perf_counter(), for everything else
        self.t_end = 0.0
        self.errors: list[str] = []
        self.pool_exhausted = False
        #: the pipeline's counts (zeroed when the window opens) and, after the
        #: run, ``doc_commits``
        self.counters: dict[str, int] = {}
        #: (time, kind, *shape) of the pipeline's device calls, for the traced
        #: part of the window
        self.device_calls: list[tuple] = []
        #: what the pipeline's sinks keep for its comparison; the pipeline's own
        self.evidence: dict = {}

    def streams(self) -> tuple[Seen, Seen]:
        return self.documents, self.queries

    def waits_ms(self, stream: str, plan: traffic.Stream) -> np.ndarray:
        """How long every event of an open loop waited, ms: from the due time
        its schedule gave it to the sink's callback; an event never
        acknowledged waited the whole grace."""
        ack = getattr(self, stream).ack[:-1]  # without the primer
        ack = np.where(np.isnan(ack), self.t_end + GRACE_S, ack)
        return (ack - (self.t0 + plan.due_s)) * 1e3


# -- the window ---------------------------------------------------------------


class Clock:
    """Opens the window when every feed has started, and acknowledges what
    the sinks see."""

    def __init__(self, schedule: traffic.Schedule, seconds: float, obs: Observed, compiles, span) -> None:
        feeds = sum(s is not None for s in (schedule.documents, schedule.queries))
        self._barrier = threading.Barrier(feeds, action=self._open)
        self.schedule = schedule
        self.seconds = seconds
        self.obs = obs
        self.compiles = compiles
        self.span = span
        self.primed = {seen.field: threading.Event() for seen in obs.streams()}
        self.opened = threading.Event()
        self.drained = threading.Event()
        self.acked = threading.Condition()
        self.sending = feeds

    def _open(self) -> None:
        for name in self.obs.counters:
            self.obs.counters[name] = 0
        self.obs.device_calls.clear()
        self.obs.compiles_at_open = self.compiles.requests()
        self.obs.opened_at = time.time()
        self.obs.t0 = time.perf_counter()
        self.obs.t_end = self.obs.t0 + self.seconds
        self.opened.set()

    def start(self) -> float:
        self._barrier.wait()
        return self.obs.t0

    def finish(self) -> None:
        """A feed has sent its last event: wait until everything sent has
        been acknowledged, or the grace has passed."""
        with self.acked:
            self.sending -= 1
        deadline = self.obs.t_end + GRACE_S
        while not self.drained.is_set() and time.perf_counter() < deadline:
            if self.sending == 0 and all(s.acked >= s.n_sent() for s in self.obs.streams()):
                self.drained.set()
            self.drained.wait(0.02)

    def sink(self, stream: str, take):
        """The ``on_change`` of the sink of ``"documents"`` or ``"queries"``:
        ``take(i, key, row)`` keeps the pipeline's evidence of event ``i``
        (waiting for whatever the row still has on the device), then the
        event is acknowledged. A retraction, or an event seen before, is a
        repeat and is not taken."""
        seen: Seen = getattr(self.obs, stream)
        field, ack, commit, commits = seen.field, seen.ack, seen.commit, seen.commits
        primer, primed = len(ack) - 1, self.primed[seen.field]
        span, name, now = self.span, "sink_" + stream, time.perf_counter

        def on_change(key, row, time, is_addition):  # the names pw.io.subscribe calls it by
            with span(name):
                i = row[field]
                if not is_addition or commit[i] != -1:
                    seen.repeats += 1
                    return
                take(i, key, row)
                commit[i] = time
                ack[i] = now()
                seen.acked += 1
                commits.add(time)
                if i == primer:
                    primed.set()

        return on_change

    def on_time_end(self, time_) -> None:
        """The ``on_time_end`` of a closed loop's sink: a commit has been
        acknowledged, the feed may have room again."""
        with self.acked:
            self.acked.notify_all()


def _make_feed(pw, stream: traffic.Stream, seen: Seen, clock: Clock):
    obs, span, field, sent = clock.obs, clock.span, seen.field, seen.sent

    class Feed(pw.io.python.ConnectorSubject):
        def run(self) -> None:
            primer = len(stream.texts)
            self.next(**{field: primer, "text": stream.texts[0]})
            sent[primer] = time.perf_counter()
            clock.primed[field].wait(timeout=GRACE_S)
            t0 = clock.start()
            try:
                if stream.loop == "open":
                    self._open_loop(t0)
                else:
                    self._closed_loop()
            finally:
                clock.finish()

        def _open_loop(self, t0: float) -> None:
            texts, due = stream.texts, stream.due_s
            for i in range(len(texts)):
                delay = t0 + due[i] - time.perf_counter()
                if delay > 0:
                    time.sleep(delay)
                with span("generator_send"):
                    self.next(**{field: i, "text": texts[i]})
                sent[i] = time.perf_counter()

        def _closed_loop(self) -> None:
            texts, budget = stream.texts, stream.in_flight
            i = 0
            while time.perf_counter() < obs.t_end:
                room = budget - (i + 1 - seen.acked)  # 1: the primer
                if room <= 0:
                    with clock.acked:
                        clock.acked.wait(0.01)
                    continue
                stop = min(i + room, len(texts))
                if stop == i:
                    obs.pool_exhausted = True
                    return
                with span("generator_send"):
                    while i < stop and time.perf_counter() < obs.t_end:
                        self.next(**{field: i, "text": texts[i]})
                        sent[i] = time.perf_counter()
                        i += 1

    return Feed()


def run_window(cell: Cell, state, schedule: traffic.Schedule, seconds: float, trace: bool, compiles):
    """Have the pipeline build its graph, run it under ``pw.run()`` for
    ``seconds`` and until what was sent is acknowledged. Returns what was
    observed and, for a traced run, the trace's events and the traced
    seconds."""
    import jax
    import pathway_tpu as pw
    from pathway_tpu.internals.parse_graph import G

    G.clear()
    obs = Observed(schedule)

    def span(name: str):
        if trace:
            return jax.profiler.TraceAnnotation(trace_mod.SPAN_PREFIX + name)
        return contextlib.nullcontext()

    clock = Clock(schedule, seconds, obs, compiles, span)
    feeds = {
        name: _make_feed(pw, stream, getattr(obs, name), clock) if stream is not None else None
        for name, stream in (("documents", schedule.documents), ("queries", schedule.queries))
    }
    cell.pipeline.build(pw, cell, state, feeds, clock)
    pw.io.subscribe(
        pw.global_error_log(),
        on_change=lambda key, row, time, is_addition: obs.errors.append(str(row["message"])),
    )

    # -- the traced part of the window
    traced: dict = {}
    tracer = None
    trace_dir = None
    if trace:
        trace_dir = tempfile.TemporaryDirectory(prefix="bench-trace-")
        start_after = min(TRACE_START_S, seconds / 4)
        length = min(TRACE_LENGTH_S, seconds / 2)

        def trace_part() -> None:
            clock.opened.wait()
            time.sleep(max(0.0, obs.t0 + start_after - time.perf_counter()))
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0  # the program's stages and the benchmark's spans are enough
            jax.profiler.start_trace(trace_dir.name, profiler_options=options)
            traced["start"] = time.perf_counter()
            time.sleep(length)
            traced["stop"] = time.perf_counter()
            jax.profiler.stop_trace()

        tracer = threading.Thread(target=trace_part, name="bench-tracer", daemon=True)
        tracer.start()

    run_error = None
    try:
        pw.run(terminate_on_error=True)
    except Exception as exc:  # noqa: BLE001 - a run that raises is a run that failed
        run_error = repr(exc)
    finally:
        clock.drained.set()
        cell.pipeline.restore(state)
    G.clear()  # the graph holds the program's state; the reference needs its room
    if run_error:
        obs.errors.append(f"pw.run raised: {run_error}")
    obs.counters["doc_commits"] = len(obs.documents.commits)
    trace_info = None
    if tracer is not None:
        tracer.join(timeout=120.0)
        if "stop" in traced:
            events = trace_mod.load_events(trace_dir.name)
            trace_info = {
                "events": events,
                "start": traced["start"],
                "stop": traced["stop"],
                "window_s": traced["stop"] - traced["start"],
            }
        trace_dir.cleanup()
    return obs, trace_info


# -- metrics ------------------------------------------------------------------


def _percentile(values: np.ndarray, q: float) -> float:
    return float(np.percentile(values, q)) if len(values) else float("nan")


def end_to_end_metrics(cell: Cell, schedule, obs: Observed, seconds: float, setup_s: float) -> dict:
    """Every end-to-end metric this cell reports, over the whole window and
    all its events; an event never acknowledged waited the whole grace."""
    out = {"setup_s": setup_s}
    doc_ack = obs.documents.ack[:-1]
    in_window = (doc_ack >= obs.t0) & (doc_ack <= obs.t_end)
    out["docs_per_s"] = float(np.count_nonzero(in_window)) / seconds
    if schedule.documents.loop == "open":
        out["index_lag_p95_ms"] = _percentile(obs.waits_ms("documents", schedule.documents), 95)
    if schedule.queries is not None:
        wait_ms = obs.waits_ms("queries", schedule.queries)
        out["query_p50_ms"] = _percentile(wait_ms, 50)
        # not end to end since PR 27 (per layer: query_wait_p95_ms.query); here for the window: line
        out["query_p95_ms"] = _percentile(wait_ms, 95)
        # does the backlog grow through the window?
        half = schedule.queries.due_s < seconds / 2
        out["query_p50_ms_first_half"] = _percentile(wait_ms[half], 50)
        out["query_p50_ms_second_half"] = _percentile(wait_ms[~half], 50)
    print("window: " + json.dumps(out), file=sys.stderr)
    wanted = {m["name"] for m in cell.end_to_end}
    return {name: value for name, value in out.items() if name in wanted}


def per_layer_metrics(cell: Cell, ctx: "readers.Context") -> dict:
    out = {}
    for metric in cell.per_layer:
        read = readers.find(metric["reader"], os.path.join(HERE, "layer_metrics"))
        value = read(ctx, **metric.get("params", {}))
        if value is not None and np.isfinite(value):
            out[metric["name"]] = {"value": float(value), "unit": metric["unit"]}
        else:  # left out of the line, as the contract has it, but not in silence
            print(
                f"per-layer metric {metric['name']}: reader {metric['reader']} found nothing to "
                f"read ({value!r}); a check refuses a traced line without it",
                file=sys.stderr,
            )
    return out


# -- one run ------------------------------------------------------------------


def measure(cell: Cell, seed: int, seconds: float, trace: bool, devices, t_start: float):
    """Set up and run the window. Returns the result line without its
    verdict, and the evidence the pipeline's comparison needs: the schedule,
    what was observed, and the facts the pipeline read off its state."""
    pipeline = cell.pipeline
    compiles = check.CompileCounter()
    mesh = None
    if cell.chips > 1:
        from pathway_tpu.parallel import make_mesh

        mesh = make_mesh(data=cell.chips, devices=list(devices[: cell.chips]))
    with open(os.path.join(HERE, "peaks.json")) as fh:
        peaks = json.load(fh)["devices"]
    kind = devices[0].device_kind
    if kind not in peaks and devices[0].platform == "tpu":
        raise RuntimeError(f"no peaks for device kind {kind!r} in peaks.json")
    phases = [("start", time.time() - t_start)]

    def phase(name: str) -> None:
        phases.append((name, time.time() - t_start - sum(s for _, s in phases)))

    state = pipeline.weights(cell, seed)
    phase("weights")
    schedule = traffic.build(cell.mix, seed, seconds)
    phase("traffic")
    pipeline.set_up(cell, seed, schedule, state, mesh, phase)
    print(
        "set-up: " + ", ".join(f"{n} {s:.2f} s" for n, s in phases)
        + f"; {compiles.requests()} compile requests, {compiles.hits()} found in the cache",
        file=sys.stderr,
    )

    obs, trace_info = run_window(cell, state, schedule, seconds, trace, compiles)

    for message in obs.errors[:5]:
        print("error log: " + message[:600], file=sys.stderr)
    setup_s = obs.opened_at - t_start  # process start to window start, the primers' trip included
    in_window = compiles.requests() - obs.compiles_at_open
    metrics = end_to_end_metrics(cell, schedule, obs, seconds, setup_s)
    used = list(devices[: cell.chips])
    peak_bytes = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in used)
    device = {
        "platform": devices[0].platform,
        "kind": kind,
        "count": len(devices),
        "memory_peak_bytes": int(peak_bytes),
    }
    acked = sum(s.acked for s in obs.streams())
    result = {"correct": False, "attempted": sum(s.n_sent() for s in obs.streams()) - obs.primers}
    result["failed"] = result["attempted"] + obs.primers - acked
    if trace:
        ctx = readers.Context(
            cell=cell, obs=obs, schedule=schedule, seconds=seconds, chips=cell.chips,
            peak=peaks.get(kind), trace=trace_info, flops=pipeline.work_flops(cell, schedule, obs),
        )
        result["metrics"] = per_layer_metrics(cell, ctx)
        if trace_info is not None:
            summary = trace_mod.summarize(trace_info["events"], trace_info["window_s"], cell.chips)
            device["busy_s"] = summary["busy_s"]
            device["window_s"] = summary["window_s"]
            result["breakdown"] = {
                "device_ops": summary["device_ops"], "idle_gaps": summary["idle_gaps"],
            }
    else:
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        result["metrics"] = {
            name: {"value": value, "unit": units[name]} for name, value in metrics.items()
        }
    result["device"] = device
    # the comparison reads what it needs of the program's state here; the
    # state itself goes before the reference runs
    facts = pipeline.facts(cell, state, obs, seed, schedule)
    facts["compiles_in_window"] = in_window
    state.clear()
    gc.collect()
    return result, {"schedule": schedule, "obs": obs, "facts": facts}


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, devices, t_start: float) -> dict:
    """Set up, measure, compare; returns the result line as a dict, the
    numbers compared last."""
    result, evidence = measure(cell, seed, seconds, trace, devices, t_start)
    began = time.time()
    numbers = cell.pipeline.compare(cell, seed, **evidence)
    print(f"comparison with the reference: {time.time() - began:.2f} s", file=sys.stderr)
    result["correct"] = all(n["ok"] for n in numbers)
    result["compared"] = {n["name"]: [n["value"], n["limit"]] for n in numbers}
    return result
