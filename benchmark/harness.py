"""One run of one cell: set-up, the measured window under ``pw.run()``, the
end-to-end and per-layer metrics, and the hand-over to ``check.py``.

The graph is the one ``chip_smoke.py`` proved on the chip, built through
the public API:

    pw.io.python.read(documents) -> TpuEncoderEmbedder -> DataIndex(TpuKnnFactory)
      -> index.query_as_of_now(queries, k) <- pw.io.python.read(queries)
      -> pw.io.subscribe

``run.py`` looks for the chip and calls :func:`run_cell`; the tests call it
without the look, at a toy size.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import gc
import json
import os
import sys
import tempfile
import threading
import time

import numpy as np

import check
import costs
import readers
import reference
import trace as trace_mod
import traffic

HERE = os.path.dirname(os.path.abspath(__file__))
#: how long past the window's close an event may still be acknowledged
GRACE_S = 60.0
#: the traced part of a ``--trace 1`` window: starts this long after the
#: window opens (or a quarter into a shorter one) and lasts this long
TRACE_START_S, TRACE_LENGTH_S = 2.0, 4.0


def _buckets_up_to(limit: int) -> list[int]:
    out, b = [], 8
    while b <= limit:
        out.append(b)
        b *= 2
    return out


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
    return Cell(workload, entry["chips"], config, mix, limits, end_to_end, per_layer)


# -- what the sinks and the wrappers saw --------------------------------------


class Observed:
    """Filled by the feeds, the sinks and the wrappers while the window
    runs; read once it has closed. One writer a field: the engine's thread
    for the sinks and wrappers, each feed for its own send times.

    Before the window opens each feed sends one primer (its first text
    again, under the id one past its last) and waits for it at the sink, so
    that the whole path has run once; a primer is in the index and may be
    in an answer, and is in no metric."""

    def __init__(self, schedule: traffic.Schedule) -> None:
        # the last slot of every per-event list is the feed's primer
        n_docs = len(schedule.documents.texts) + 1
        n_queries = len(schedule.queries.texts) + 1 if schedule.queries else 0
        self.primers = 2 if schedule.queries else 1
        self.compiles_at_open = 0
        self.opened_at = 0.0  # time.time() at the window's start, for setup_s
        self.t0 = 0.0  # the same instant by time.perf_counter(), for everything else
        self.t_end = 0.0
        self.doc_sent = np.full(n_docs, np.nan)
        self.doc_ack = np.full(n_docs, np.nan)
        self.doc_commit = np.full(n_docs, -1, np.int64)
        self.doc_key: list = [None] * n_docs
        self.doc_emb: list = [None] * n_docs
        self.doc_repeats = 0
        self.docs_acked = 0
        self.query_sent = np.full(n_queries, np.nan)
        self.query_ack = np.full(n_queries, np.nan)
        self.query_commit = np.full(n_queries, -1, np.int64)
        self.query_ids: list = [None] * n_queries
        self.query_scores: list = [None] * n_queries
        self.query_emb: list = [None] * n_queries
        self.query_repeats = 0
        self.queries_acked = 0
        self.errors: list[str] = []
        self.pool_exhausted = False
        self.counters = {
            "embed_calls_doc": 0, "embed_rows_doc": 0,
            "embed_calls_query": 0, "embed_rows_query": 0,
            "search_calls": 0, "search_queries": 0,
        }
        #: (time, "embed", batch, seq) and (time, "search", queries, 0), for
        #: the traced part of the window
        self.device_calls: list[tuple] = []

    def sent_docs(self) -> int:
        return int(np.count_nonzero(~np.isnan(self.doc_sent)))

    def sent_queries(self) -> int:
        return int(np.count_nonzero(~np.isnan(self.query_sent)))


# -- set-up -------------------------------------------------------------------


def make_embedder(config: dict, params):
    """The program's embedder with the benchmark's weights; refuses a
    program whose preset is not the configuration's file."""
    from pathway_tpu.xpacks.llm.embedders import TpuEncoderEmbedder

    enc = config["encoder"]
    embedder = TpuEncoderEmbedder(
        model=enc["model"],
        max_len=config["embedder"]["max_len"],
        max_batch_size=config["embedder"]["max_batch_size"],
        seq_bucket_min=config["embedder"]["seq_bucket_min"],
        params=params,
    )
    have = embedder.config
    want = (
        enc["hidden_size"], enc["num_hidden_layers"], enc["num_attention_heads"],
        enc["intermediate_size"], enc["vocab_size"], enc["pooling"],
    )
    got = (have.hidden, have.layers, have.heads, have.intermediate, have.vocab_size, have.pooling)
    if got != want or np.dtype(have.dtype).name != enc["compute_dtype"]:
        raise RuntimeError(f"the program's {enc['model']} is {got}, the configuration says {want}")
    return embedder


def prefilled_index(config: dict, seed: int, moments, mesh):
    """A ``DeviceKnnIndex`` at the configuration's capacity holding its
    prefilled rows, through ``restore_op_state`` — the path a restarted
    deployment takes — with device arrays made from the seed round
    ``moments`` (``reference.prefill_moments``)."""
    from pathway_tpu.engine.external_index import DeviceKnnIndex
    from pathway_tpu.engine.value import Pointer

    spec = config["index"]
    dim = config["encoder"]["hidden_size"]
    vectors, valid, norms = reference.make_prefill(
        seed, spec["capacity"], spec["prefilled"], moments, mesh
    )
    # int.__new__ skips Pointer's masking to 128 bits, which these need not
    keys = map(
        functools.partial(int.__new__, Pointer),
        range(reference.PREFILL_KEY_BASE, reference.PREFILL_KEY_BASE + spec["prefilled"]),
    )
    index = DeviceKnnIndex(dim=dim, metric=spec["metric"], capacity=8, mesh=mesh)
    index.restore_op_state(
        {
            "vectors": vectors,
            "valid": valid,
            "norms": norms,
            "key_to_slot": dict(zip(keys, range(spec["prefilled"]))),
            "free": range(spec["capacity"] - 1, spec["prefilled"] - 1, -1),
            "capacity": spec["capacity"],
        }
    )
    return index


def warm_up(embedder, index, cell: Cell) -> None:
    """Run every shape this cell's traffic can produce, on the index the
    window will use: the encoder at each batch bucket by each sequence
    bucket of the mix's lengths, the gather and update at each batch
    bucket, the search at each batch bucket. The rows it adds are removed
    again."""
    from pathway_tpu.engine.value import Pointer

    mix, k = cell.mix, cell.config["index"]["k"]
    batches = _buckets_up_to(cell.config["embedder"]["max_batch_size"])
    least = cell.config["embedder"]["seq_bucket_min"]
    doc_seqs = traffic.seq_buckets(mix["documents"], least)
    query_seqs = traffic.seq_buckets(mix["queries"], least) if mix.get("queries") else []
    added = []
    for batch in batches:
        for seq in sorted(set(doc_seqs) | set(query_seqs)):
            text = " ".join(["w0"] * (seq - traffic.SPECIAL_TOKENS))
            rows = embedder._fn([text] * batch)
        if query_seqs:
            index.search(rows, k)
        keys = [Pointer(reference.PREFILL_KEY_BASE - 1 - len(added) - i) for i in range(batch)]
        index.add(keys, rows)
        added += keys
    index.remove(added)
    np.asarray(index.state.valid[:1])  # wait for the device to finish


# -- the window ---------------------------------------------------------------


class _Clock:
    """Opens the window when every feed has started."""

    def __init__(self, feeds: int, seconds: float, obs: Observed, compiles) -> None:
        self._barrier = threading.Barrier(feeds, action=self._open)
        self.seconds = seconds
        self.obs = obs
        self.compiles = compiles
        self.primed = {"doc_id": threading.Event(), "query_id": threading.Event()}
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
            obs = self.obs
            if (
                self.sending == 0
                and obs.docs_acked >= obs.sent_docs()
                and obs.queries_acked >= obs.sent_queries()
            ):
                self.drained.set()
            self.drained.wait(0.02)


def _make_feed(pw, stream: traffic.Stream, field: str, sent: np.ndarray, clock: _Clock, span):
    obs = clock.obs

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
                room = budget - (i + 1 - obs.docs_acked)  # 1: the primer
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


def run_window(cell: Cell, embedder, index, schedule: traffic.Schedule, seconds: float, trace: bool, compiles):
    """Build the graph, run it under ``pw.run()`` for ``seconds`` and until
    what was sent is acknowledged. Returns what was observed and, for a
    traced run, the trace's events and the traced seconds."""
    import jax
    import pathway_tpu as pw
    from pathway_tpu.internals.parse_graph import G
    from pathway_tpu.stdlib.indexing import DataIndex, TpuKnnFactory

    G.clear()
    obs = Observed(schedule)
    counters = obs.counters
    k = cell.config["index"]["k"]
    streams = [s for s in (schedule.documents, schedule.queries) if s is not None]
    clock = _Clock(len(streams), seconds, obs, compiles)

    def span(name: str):
        if trace:
            return jax.profiler.TraceAnnotation(trace_mod.SPAN_PREFIX + name)
        return contextlib.nullcontext()

    # -- wrappers: counts in every run, host spans in a traced one
    doc_texts = set(schedule.documents.texts)
    inner_fn = embedder._fn

    def embed_fn(texts):
        kind = "doc" if texts[0] in doc_texts else "query"
        counters[f"embed_calls_{kind}"] += 1
        counters[f"embed_rows_{kind}"] += len(texts)
        with span("embed_call"):
            return inner_fn(texts)

    wrapped_jits = {}
    for attr in ("_jit_embed_ids", "_jit_embed"):
        inner = getattr(embedder, attr, None)
        if inner is not None:
            wrapped_jits[attr] = inner

            def jit_call(ids, *rest, _inner=inner):
                obs.device_calls.append((time.perf_counter(), "embed", *ids.shape))
                return _inner(ids, *rest)

            setattr(embedder, attr, jit_call)
    if not wrapped_jits:
        raise RuntimeError(
            "the embedder has neither _jit_embed_ids nor _jit_embed: the benchmark cannot see "
            "its device calls, and the encoder's roofline would have nothing to read"
        )
    embedder._fn = embed_fn

    class Factory(TpuKnnFactory):
        def build(self):
            inner_add, inner_search = index.add, index.search

            def add(keys, vectors):
                with span("index_add"):
                    return inner_add(keys, vectors)

            def search(queries, k_):
                counters["search_calls"] += 1
                counters["search_queries"] += len(queries)
                obs.device_calls.append((time.perf_counter(), "search", len(queries), 0))
                with span("index_search"):
                    return inner_search(queries, k_)

            index.add, index.search = add, search
            return index

    # -- the graph
    doc_commits: set = set()
    docs = pw.io.python.read(
        _make_feed(pw, schedule.documents, "doc_id", obs.doc_sent, clock, span),
        schema=pw.schema_from_types(doc_id=int, text=str),
        autocommit_duration_ms=cell.config["doc_autocommit_ms"],
    )
    docs = docs.select(doc_id=pw.this.doc_id, emb=embedder(pw.this.text))
    data_index = DataIndex(
        docs,
        Factory(
            dimensions=embedder.get_embedding_dimension(),
            metric=cell.config["index"]["metric"],
            capacity=cell.config["index"]["capacity"],
            mesh=index.mesh,
        ),
        docs.emb,
    )

    def on_doc(key, row, time_, is_addition):
        with span("sink_doc"):
            i = row["doc_id"]
            if not is_addition or obs.doc_key[i] is not None:
                obs.doc_repeats += 1
                return
            obs.doc_emb[i] = np.asarray(row["emb"], np.float32)
            obs.doc_key[i] = key
            obs.doc_commit[i] = time_
            obs.doc_ack[i] = time.perf_counter()
            obs.docs_acked += 1
            doc_commits.add(time_)
            if i == len(obs.doc_key) - 1:
                clock.primed["doc_id"].set()

    def on_doc_commit(time_):
        with clock.acked:
            clock.acked.notify_all()

    pw.io.subscribe(
        docs,
        on_change=lambda key, row, time, is_addition: on_doc(key, row, time, is_addition),
        on_time_end=on_doc_commit,
    )
    if schedule.queries is not None:
        queries = pw.io.python.read(
            _make_feed(pw, schedule.queries, "query_id", obs.query_sent, clock, span),
            schema=pw.schema_from_types(query_id=int, text=str),
            autocommit_duration_ms=schedule.queries.autocommit_ms,
        )
        queries = queries.select(query_id=pw.this.query_id, qemb=embedder(pw.this.text))
        answers = data_index.query_as_of_now(queries, queries.qemb, number_of_matches=k)

        def on_answer(key, row, time_, is_addition):
            with span("sink_answer"):
                i = row["query_id"]
                if not is_addition or obs.query_ids[i] is not None:
                    obs.query_repeats += 1
                    return
                obs.query_ids[i] = tuple(row["_pw_index_reply_ids"])
                obs.query_scores[i] = tuple(row["_pw_index_reply_scores"])
                obs.query_emb[i] = np.asarray(row["qemb"], np.float32)
                obs.query_commit[i] = time_
                obs.query_ack[i] = time.perf_counter()
                obs.queries_acked += 1
                if i == len(obs.query_ids) - 1:
                    clock.primed["query_id"].set()

        pw.io.subscribe(
            answers,
            on_change=lambda key, row, time, is_addition: on_answer(key, row, time, is_addition),
        )
    else:
        # no query feed: build the index operator all the same, over no queries
        none = pw.debug.table_from_rows(pw.schema_from_types(query_id=int, text=str), [])
        none = none.select(query_id=pw.this.query_id, qemb=embedder(pw.this.text))
        answers = data_index.query_as_of_now(none, none.qemb, number_of_matches=k)
        pw.io.subscribe(answers, on_change=lambda key, row, time, is_addition: None)
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
            options.python_tracer_level = 0  # the benchmark's spans are enough
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
        embedder._fn = inner_fn
        for attr, inner in wrapped_jits.items():
            setattr(embedder, attr, inner)
    G.clear()  # the graph holds the index; the reference needs its room
    if run_error:
        obs.errors.append(f"pw.run raised: {run_error}")
    counters["doc_commits"] = len(doc_commits)
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
    never = obs.t_end + GRACE_S
    doc_ack = obs.doc_ack[:-1]
    in_window = (doc_ack >= obs.t0) & (doc_ack <= obs.t_end)
    out["docs_per_s"] = float(np.count_nonzero(in_window)) / seconds
    if schedule.documents.loop == "open":
        due = obs.t0 + schedule.documents.due_s
        ack = np.where(np.isnan(doc_ack), never, doc_ack)
        out["index_lag_p95_ms"] = _percentile((ack - due) * 1e3, 95)
    if schedule.queries is not None:
        due = obs.t0 + schedule.queries.due_s
        ack = np.where(np.isnan(obs.query_ack[:-1]), never, obs.query_ack[:-1])
        wait_ms = (ack - due) * 1e3
        out["query_p50_ms"] = _percentile(wait_ms, 50)
        out["query_p95_ms"] = _percentile(wait_ms, 95)
        # does the backlog grow through the window?
        half = due < obs.t0 + seconds / 2
        out["query_p50_ms_first_half"] = _percentile(wait_ms[half], 50)
        out["query_p50_ms_second_half"] = _percentile(wait_ms[~half], 50)
    print("window: " + json.dumps(out), file=sys.stderr)
    wanted = {m["name"] for m in cell.end_to_end}
    return {name: value for name, value in out.items() if name in wanted}


def embedded_flops(cell: Cell, schedule, obs: Observed) -> float:
    """Model FLOPs of the real tokens of every text embedded inside the
    window (documents at the sink, queries answered)."""
    enc = cell.config["encoder"]
    total = 0.0
    for stream, ack in ((schedule.documents, obs.doc_ack), (schedule.queries, obs.query_ack)):
        if stream is None:
            continue
        done = (ack[:-1] >= obs.t0) & (ack[:-1] <= obs.t_end)
        counts = np.bincount(stream.tokens[done])
        total += sum(n * costs.encoder_flops(t, enc) for t, n in enumerate(counts) if n)
    return total


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
    verdict, and the evidence the comparison needs: the schedule, what was
    observed, what the index held, the weights."""
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

    params = reference.make_params(seed, cell.config["encoder"])
    embedder = make_embedder(cell.config, params)
    phase("weights")
    schedule = traffic.build(cell.mix, seed, seconds)
    phase("traffic")
    # the prefilled rows take their distribution from the reference's own
    # embeddings of the run's first documents, so that they compete with the
    # window's documents for a place in an answer
    moments = reference.prefill_moments(
        params, schedule.documents.texts[: reference.PREFILL_SAMPLE],
        cell.config["encoder"], cell.config["embedder"]["max_len"],
    )
    index = prefilled_index(cell.config, seed, moments, mesh)
    phase("prefill")
    warm_up(embedder, index, cell)
    phase("warm_up")
    print(
        "set-up: " + ", ".join(f"{n} {s:.2f} s" for n, s in phases)
        + f"; {compiles.requests()} compile requests, {compiles.hits()} found in the cache",
        file=sys.stderr,
    )
    prefilled = len(index)

    obs, trace_info = run_window(cell, embedder, index, schedule, seconds, trace, compiles)

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
    result = {"correct": False, "attempted": obs.sent_docs() + obs.sent_queries() - obs.primers}
    result["failed"] = result["attempted"] + obs.primers - obs.docs_acked - obs.queries_acked
    if trace:
        ctx = readers.Context(
            cell=cell, obs=obs, schedule=schedule, seconds=seconds, chips=cell.chips,
            peak=peaks.get(kind), trace=trace_info, flops=embedded_flops(cell, schedule, obs),
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
    facts = check.index_facts(index, obs, prefilled, seed, schedule)
    facts["compiles_in_window"] = in_window
    del index, embedder
    gc.collect()
    facts["prefill_moments"] = moments
    return result, {"schedule": schedule, "obs": obs, "facts": facts, "params": params}


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, devices, t_start: float) -> dict:
    """Set up, measure, compare; returns the result line as a dict, the
    numbers compared last."""
    result, evidence = measure(cell, seed, seconds, trace, devices, t_start)
    began = time.time()
    numbers = check.compare(cell, seed, **evidence)
    print(f"comparison with the reference: {time.time() - began:.2f} s", file=sys.stderr)
    result["correct"] = all(n["ok"] for n in numbers)
    result["compared"] = {n["name"]: [n["value"], n["limit"]] for n in numbers}
    return result
