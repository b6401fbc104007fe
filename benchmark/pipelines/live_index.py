"""The live-index pipeline: the graph ``chip_smoke.py`` proved on the chip,
built through the public API:

    pw.io.python.read(documents) -> TpuEncoderEmbedder -> DataIndex(TpuKnnFactory)
      -> index.query_as_of_now(queries, k) <- pw.io.python.read(queries)
      -> pw.io.subscribe

A pipeline is what belongs to one graph: its weights, its set-up and
warm-up, the wrappers that count its device calls, the graph and its sinks,
the work its step's share of the peak counts, and its comparison with the
plain reference. A configuration names its pipeline under ``"pipeline"``
(none: this one) and ``harness.find_pipeline`` loads
``benchmark/pipelines/<name>.py``, which defines :data:`harness.PIPELINE_INTERFACE`:

``weights(cell, seed) -> state``
    the dict this pipeline keeps its state in, holding the weights made from
    the seed and the program's object that serves them.
``set_up(cell, seed, schedule, state, mesh, phase)``
    everything else before the window, on this cell's shapes and no others;
    ``phase(name)`` closes a phase of the ``set-up:`` line.
``build(pw, cell, state, feeds, clock)``
    the graph, from ``feeds["documents"]`` and ``feeds["queries"]`` (a
    ``ConnectorSubject`` each, or ``None``; ``clock.schedule`` has what they
    send) to its sinks: ``clock.sink(stream, take)`` is a sink's ``on_change``
    and acknowledges event ``row[<field>]`` once ``take(i, key, row)`` has
    kept the pipeline's evidence of it in ``clock.obs.evidence``. Counts go
    to ``clock.obs.counters``, device calls to ``clock.obs.device_calls``;
    ``clock.span(name)`` is a host span of a traced run.
``restore(state)``
    undo what ``build`` hung on the program's objects, after ``pw.run()``.
``work_flops(cell, schedule, obs) -> float``
    model FLOPs of the work finished inside the window (``step_mfu``).
``facts(cell, state, obs, seed, schedule) -> dict``
    what the comparison needs of the program's state; the harness clears
    ``state`` right after, so that the reference finds the device's room.
``compare(cell, seed, *, schedule, obs, facts, ...) -> [{"name", "value", "limit", "ok"}]``
    every number compared, against ``cell.limits``.
"""

from __future__ import annotations

import functools
import time

import numpy as np

import check
import costs
import reference
import traffic


def _buckets_up_to(limit: int) -> list[int]:
    out, b = [], 8
    while b <= limit:
        out.append(b)
        b *= 2
    return out


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


def warm_up(embedder, index, cell) -> None:
    """Run every shape this cell's traffic can produce, on the index the
    window will use: the encoder at each batch bucket by each sequence
    bucket of the mix's lengths, the gather and update at each batch
    bucket, the search at each batch bucket up to the mix's
    ``queries.search_rows_max`` (the embedder's ``max_batch_size`` where the
    mix has no such key: a search takes a whole commit's queries, which a
    burst can make more than one embed step's). The rows it adds are
    removed again."""
    from pathway_tpu.engine.value import Pointer

    mix, k = cell.mix, cell.config["index"]["k"]
    most = cell.config["embedder"]["max_batch_size"]
    least = cell.config["embedder"]["seq_bucket_min"]
    doc_seqs = traffic.seq_buckets(mix["documents"], least)
    query_seqs = traffic.seq_buckets(mix["queries"], least) if mix.get("queries") else []
    search_most = mix["queries"].get("search_rows_max", most) if query_seqs else 0
    added = []
    for batch in _buckets_up_to(most):
        for seq in sorted(set(doc_seqs) | set(query_seqs)):
            text = " ".join(["w0"] * (seq - traffic.SPECIAL_TOKENS))
            rows = embedder._fn([text] * batch)
        if batch <= search_most:
            index.search(rows, k)
        keys = [Pointer(reference.PREFILL_KEY_BASE - 1 - len(added) - i) for i in range(batch)]
        index.add(keys, rows)
        added += keys
    for batch in _buckets_up_to(search_most):
        if batch > most:  # a search wider than an embed step: the widest step's rows, over again
            index.search((list(rows) * -(-batch // len(rows)))[:batch], k)
    index.remove(added)
    np.asarray(index.state.valid[:1])  # wait for the device to finish


def weights(cell, seed: int) -> dict:
    params = reference.make_params(seed, cell.config["encoder"])
    return {"params": params, "embedder": make_embedder(cell.config, params)}


def set_up(cell, seed: int, schedule, state: dict, mesh, phase) -> None:
    config = cell.config
    # the prefilled rows take their distribution from the reference's own
    # embeddings of the run's first documents, so that they compete with the
    # window's documents for a place in an answer
    state["moments"] = reference.prefill_moments(
        state["params"], schedule.documents.texts[: reference.PREFILL_SAMPLE],
        config["encoder"], config["embedder"]["max_len"],
    )
    state["index"] = prefilled_index(config, seed, state["moments"], mesh)
    phase("prefill")
    warm_up(state["embedder"], state["index"], cell)
    phase("warm_up")
    state["prefilled"] = len(state["index"])


# -- the graph ----------------------------------------------------------------

COUNTERS = (
    "embed_calls_doc", "embed_rows_doc", "embed_calls_query", "embed_rows_query",
    "search_calls", "search_queries",
)


def build(pw, cell, state: dict, feeds: dict, clock) -> None:
    from pathway_tpu.stdlib.indexing import DataIndex, TpuKnnFactory

    obs, span = clock.obs, clock.span
    embedder, index = state["embedder"], state["index"]
    k = cell.config["index"]["k"]
    counters = obs.counters
    counters.update(dict.fromkeys(COUNTERS, 0))
    n_docs, n_queries = len(obs.documents.ack), len(obs.queries.ack)
    doc_key = obs.evidence["doc_key"] = [None] * n_docs
    doc_emb = obs.evidence["doc_emb"] = [None] * n_docs
    query_ids = obs.evidence["query_ids"] = [None] * n_queries
    query_scores = obs.evidence["query_scores"] = [None] * n_queries
    query_emb = obs.evidence["query_emb"] = [None] * n_queries

    # -- wrappers: counts in every run, host spans in a traced one
    doc_texts = set(clock.schedule.documents.texts)
    inner_fn = embedder._fn

    def embed_fn(texts):
        kind = "doc" if texts[0] in doc_texts else "query"
        counters[f"embed_calls_{kind}"] += 1
        counters[f"embed_rows_{kind}"] += len(texts)
        with span("embed_call"):
            return inner_fn(texts)

    wrapped = state["wrapped"] = {}
    for attr in ("_jit_embed_ids", "_jit_embed"):
        inner = getattr(embedder, attr, None)
        if inner is not None:
            wrapped[attr] = inner

            def jit_call(ids, *rest, _inner=inner):
                obs.device_calls.append((time.perf_counter(), "embed", *ids.shape))
                return _inner(ids, *rest)

            setattr(embedder, attr, jit_call)
    if not wrapped:
        raise RuntimeError(
            "the embedder has neither _jit_embed_ids nor _jit_embed: the benchmark cannot see "
            "its device calls, and the encoder's roofline would have nothing to read"
        )
    wrapped["_fn"] = inner_fn
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
    docs = pw.io.python.read(
        feeds["documents"],
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

    def take_doc(i, key, row):
        doc_emb[i] = np.asarray(row["emb"], np.float32)
        doc_key[i] = key

    pw.io.subscribe(docs, on_change=clock.sink("documents", take_doc), on_time_end=clock.on_time_end)
    if feeds["queries"] is not None:
        queries = pw.io.python.read(
            feeds["queries"],
            schema=pw.schema_from_types(query_id=int, text=str),
            autocommit_duration_ms=clock.schedule.queries.autocommit_ms,
        )
        queries = queries.select(query_id=pw.this.query_id, qemb=embedder(pw.this.text))
        answers = data_index.query_as_of_now(queries, queries.qemb, number_of_matches=k)

        def take_answer(i, key, row):
            query_ids[i] = tuple(row["_pw_index_reply_ids"])
            query_scores[i] = tuple(row["_pw_index_reply_scores"])
            query_emb[i] = np.asarray(row["qemb"], np.float32)

        pw.io.subscribe(answers, on_change=clock.sink("queries", take_answer))
    else:
        # no query feed: build the index operator all the same, over no queries
        none = pw.debug.table_from_rows(pw.schema_from_types(query_id=int, text=str), [])
        none = none.select(query_id=pw.this.query_id, qemb=embedder(pw.this.text))
        answers = data_index.query_as_of_now(none, none.qemb, number_of_matches=k)
        pw.io.subscribe(answers, on_change=lambda key, row, time, is_addition: None)


def restore(state: dict) -> None:
    for attr, inner in state.pop("wrapped", {}).items():
        setattr(state["embedder"], attr, inner)


# -- the step's work, and the hand-over to the comparison ---------------------


def work_flops(cell, schedule, obs) -> float:
    """Model FLOPs of the real tokens of every text embedded inside the
    window (documents at the sink, queries answered)."""
    enc = cell.config["encoder"]
    total = 0.0
    for stream, ack in ((schedule.documents, obs.documents.ack), (schedule.queries, obs.queries.ack)):
        if stream is None:
            continue
        done = (ack[:-1] >= obs.t0) & (ack[:-1] <= obs.t_end)
        counts = np.bincount(stream.tokens[done])
        total += sum(n * costs.encoder_flops(t, enc) for t, n in enumerate(counts) if n)
    return total


def facts(cell, state: dict, obs, seed: int, schedule) -> dict:
    out = check.index_facts(state["index"], obs, state["prefilled"], seed, schedule)
    out["prefill_moments"] = state["moments"]
    out["params"] = state["params"]
    return out


compare = check.compare
