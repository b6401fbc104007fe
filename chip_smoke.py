#!/usr/bin/env python3
"""chip_smoke.py — does the ``pw.run`` RAG path start and answer correctly
on the TPU? Run it first in any session that touches the chip:

    python3 chip_smoke.py            # one chip; four if four are visible
    python3 chip_smoke.py --chips 4  # fails unless four chips are visible

One process, no child that needs the chip, no network, weights from a seed.
It drives the product path through the entry points a user calls —
``pw.io.python.read`` (autocommit 100 ms) -> ``TpuEncoderEmbedder``
(all-MiniLM-L6-v2 as published: hidden 384, 6 layers, 12 heads, FFN 1536,
vocab 30,522, bf16 compute; sequence 128, batch 256) ->
``DataIndex(TpuKnnFactory)`` -> ``query_as_of_now`` -> ``pw.io.subscribe``,
under ``pw.run()`` — then a relational tail (groupby and join large enough
for the device operator kernels) and, on four chips, the index sharded over
the mesh plus a four-worker run over the collective exchange.

It checks what came out by the repo's own means (exact NumPy search, the
dense attention reference, NumPy groupby/join) and exits non-zero, naming
each failed check, if anything is off. Without a TPU it exits non-zero at
once and prints no result. The last two lines of stdout are one JSON object
each: the summary (every phase's facts, set-up and steady seconds, the
compile cache, the failures), then the verdict, which holds exactly
``{"ok": ..., "device": {"platform": ..., "kind": ..., "count": ...}}``
with the device as JAX reports it — the line the driver reads. They state
counts and seconds as facts about this run; the smoke measures no rate,
compares with nothing and claims nothing.

CPU tests import the functions below at small sizes
(tests/test_chip_smoke.py); there is no flag that lets ``main`` run without
the chip.
"""

from __future__ import annotations

import argparse
import collections
import faulthandler
import json
import sys
import threading
import time
import traceback

import numpy as np

K = 10
N_DOCS = 4096
N_QUERIES = 16
MAX_LEN = 128
MAX_BATCH = 256
#: every document tokenizes past 64 tokens, so every document batch runs at
#: the full sequence bucket (128); queries are 6 words, the smallest (8)
DOC_WORDS = (70, 140)
QUERY_WORDS = 6
#: the contract's limit is 1200 s; a hang dumps every thread and exits 1
DEADLINE_S = 1100


def doc_text(i: int, words: tuple[int, int] = DOC_WORDS) -> str:
    rng = np.random.default_rng(i)
    n = int(rng.integers(words[0], words[1]))
    return " ".join(f"w{j}" for j in rng.integers(0, 5000, n))


def query_text(i: int, n_docs: int, words: tuple[int, int]) -> str:
    return " ".join(doc_text(i * 37 % n_docs, words).split()[:QUERY_WORDS])


class CompileCounter:
    """Compile requests and persistent-cache hits, as JAX reports them."""

    def __init__(self) -> None:
        import jax

        self.events: collections.Counter = collections.Counter()
        jax.monitoring.register_event_listener(
            lambda name, **_kw: self.events.update([name])
        )

    def snapshot(self) -> dict:
        requests = self.events["/jax/compilation_cache/compile_requests_use_cache"]
        hits = self.events["/jax/compilation_cache/cache_hits"]
        return {"requests": requests, "hits": hits, "misses": requests - hits}


# -- attention ----------------------------------------------------------------


def check_attention(
    embedder,
    batch: int,
    seq: int,
    parity_shapes: list[tuple[tuple[int, int, int, int], str, bool]],
) -> tuple[dict, list[str]]:
    """Name the attention in the embedder's compiled step and prove it.

    If the code says flash, the lowered embed step must hold a Mosaic
    ``tpu_custom_call`` (interpret mode lowers to plain HLO, so its
    presence is the proof that nothing was interpreted). The Pallas kernels
    are compared with ``dense_attention`` on this device at
    ``parity_shapes``: ``((b, t, h, d), dtype, with_backward)``, the second
    row of each batch padded to 3/4 of its length."""
    import importlib

    import jax
    import jax.numpy as jnp

    from pathway_tpu.models.transformer import default_attn_fn, dense_attention

    fa = importlib.import_module("pathway_tpu.ops.flash_attention")
    failures: list[str] = []
    name = default_attn_fn().__name__
    ids = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    lowered = embedder._jit_embed_ids.func.lower(embedder._params, ids)
    has_call = "tpu_custom_call" in lowered.as_text()
    interpret = fa._interpret()
    on_tpu = jax.default_backend() == "tpu"
    if on_tpu and interpret:
        failures.append("Pallas would run in interpret mode on a TPU")
    if on_tpu and has_call != (name == "flash_attention"):
        failures.append(
            f"default_attn_fn is {name} but the lowered embed step "
            f"{'holds' if has_call else 'holds no'} tpu_custom_call"
        )
    errs = {}
    for shape, dtype, backward in parity_shapes:
        b, t, _h, _d = shape
        rng = np.random.default_rng(t)
        q, k, v = (
            jnp.asarray(rng.normal(size=shape), dtype) for _ in range(3)
        )
        lens = np.full((b,), t)
        lens[1 % b] = max(1, 3 * t // 4)
        mask = jnp.asarray(np.arange(t)[None, :] < lens[:, None])
        tag = f"{list(shape)} {dtype}"
        real = np.asarray(mask)[:, :, None, None]  # pad queries are unused
        ours = np.asarray(fa.flash_attention(q, k, v, mask), np.float32)
        ref = np.asarray(dense_attention(q, k, v, mask), np.float32)
        errs[f"fwd {tag}"] = err = float(np.abs((ours - ref) * real).max())
        if not err < 2e-2:
            failures.append(f"flash forward vs dense at {tag}: {err}")
        if not backward:
            continue

        def loss(fn, q_, k_, v_):
            out = fn(q_, k_, v_, mask).astype(jnp.float32)
            return (out * out * jnp.asarray(real, jnp.float32)).sum()

        g_ours = jax.grad(lambda *a: loss(fa.flash_attention, *a), (0, 1, 2))(
            q, k, v
        )
        g_ref = jax.grad(lambda *a: loss(dense_attention, *a), (0, 1, 2))(
            q, k, v
        )
        errs[f"bwd {tag}"] = err = max(
            float(np.abs(np.asarray(a, np.float32) - np.asarray(r, np.float32)).max())
            for a, r in zip(g_ours, g_ref)
        )
        if not err < 5e-2:
            failures.append(f"flash backward vs dense at {tag}: {err}")
    facts = {
        "implementation": name,
        "tpu_custom_call_in_embed_step": has_call,
        "interpret": interpret,
        "max_abs_err_vs_dense": errs,
    }
    return facts, failures


# -- the RAG pipeline ---------------------------------------------------------


def warm_up(
    embedder, capacity: int, n_docs: int, doc_words: tuple[int, int]
) -> None:
    """Compile every shape the one-chip pipeline can produce — the encoder
    per batch bucket at the document and the query sequence bucket, the
    index update and gather per batch bucket, the search — on a throwaway
    index, so that compilation is set-up time and not part of the run."""
    from pathway_tpu.engine.external_index import DeviceKnnIndex
    from pathway_tpu.engine.value import ref_scalar

    dim = embedder.get_embedding_dimension()
    index = DeviceKnnIndex(dim=dim, capacity=capacity)
    b = 8
    while b <= MAX_BATCH:
        rows = embedder._fn([doc_text(i, doc_words) for i in range(b)])
        index.add([ref_scalar((b, i)) for i in range(b)], rows)
        b *= 2
    index.search(embedder._fn([query_text(0, n_docs, doc_words)]), k=K)
    index.search([np.ones(dim, np.float32)], k=K)


def run_rag_pipeline(
    embedder,
    *,
    n_docs: int,
    n_queries: int,
    capacity: int,
    mesh=None,
    doc_words: tuple[int, int] = DOC_WORDS,
) -> dict:
    """The graph of the module docstring under ``pw.run()``. Returns what
    the sinks saw: ``docs`` {doc_id: embedding}, ``answers`` {query_id:
    (doc ids, scores, query embedding)}, ``errors`` (the global error log),
    ``index`` (the engine's index object), ``run_error`` and
    ``steady_seconds`` — for :func:`rag_failures` to judge."""
    import pathway_tpu as pw
    from pathway_tpu.internals.parse_graph import G
    from pathway_tpu.stdlib.indexing import DataIndex, TpuKnnFactory

    G.clear()
    built: list = []

    class Factory(TpuKnnFactory):
        def build(self):
            built.append(super().build())
            return built[-1]

    ingest_done = threading.Event()
    answer_seen = threading.Event()
    run_over = threading.Event()
    docs_seen: dict = {}  # doc key -> (doc_id, embedding)
    answers: dict = {}
    errors: list[str] = []
    unanswered: list[int] = []

    class DocFeed(pw.io.python.ConnectorSubject):
        def run(self) -> None:
            for i in range(n_docs):
                self.next(doc_id=i, text=doc_text(i, doc_words))

    class QueryFeed(pw.io.python.ConnectorSubject):
        def run(self) -> None:
            ingest_done.wait(timeout=600.0)
            for i in range(n_queries):
                if run_over.is_set() or not ingest_done.is_set():
                    return
                answer_seen.clear()
                self.next(query_id=i, text=query_text(i, n_docs, doc_words))
                if not answer_seen.wait(timeout=120.0):
                    unanswered.append(i)

    docs = pw.io.python.read(
        DocFeed(),
        schema=pw.schema_from_types(doc_id=int, text=str),
        autocommit_duration_ms=100,
    )
    docs = docs.select(doc_id=pw.this.doc_id, emb=embedder(pw.this.text))
    # one query per commit: the feed waits for each answer
    queries = pw.io.python.read(
        QueryFeed(),
        schema=pw.schema_from_types(query_id=int, text=str),
        autocommit_duration_ms=None,
    )
    queries = queries.select(
        query_id=pw.this.query_id, qemb=embedder(pw.this.text)
    )
    index = DataIndex(
        docs,
        Factory(
            dimensions=embedder.get_embedding_dimension(),
            capacity=capacity,
            mesh=mesh,
        ),
        docs.emb,
    )
    res = index.query_as_of_now(queries, queries.qemb, number_of_matches=K)

    def on_doc(key, row, time, is_addition):
        if is_addition:
            docs_seen[key] = (row["doc_id"], np.asarray(row["emb"], np.float32))
            if len(docs_seen) == n_docs:
                ingest_done.set()

    def on_answer(key, row, time, is_addition):
        if is_addition:
            answers[row["query_id"]] = (
                tuple(row["_pw_index_reply_ids"]),
                tuple(float(s) for s in row["_pw_index_reply_scores"]),
                np.asarray(row["qemb"], np.float32),
            )
            answer_seen.set()

    def on_error(key, row, time, is_addition):
        errors.append(row["message"])

    pw.io.subscribe(docs, on_change=on_doc)
    pw.io.subscribe(res, on_change=on_answer)
    pw.io.subscribe(pw.global_error_log(), on_change=on_error)
    run_error = None
    t0 = time.perf_counter()
    try:
        # an error must end the run: a sink that never fills would
        # otherwise leave the query feed waiting for documents
        pw.run(terminate_on_error=True)
    except Exception as exc:  # noqa: BLE001 — reported as a failed check
        run_error = repr(exc)
    finally:
        run_over.set()
        ingest_done.set()
        answer_seen.set()
    return {
        "docs": {doc_id: emb for doc_id, emb in docs_seen.values()},
        "answers": {
            qid: (tuple(docs_seen[k][0] for k in keys if k in docs_seen), s, q)
            for qid, (keys, s, q) in answers.items()
        },
        "errors": errors,
        "unanswered": unanswered,
        "index": built[0] if built else None,
        "run_error": run_error,
        "steady_seconds": time.perf_counter() - t0,
    }


def recall_at_k(facts: dict) -> float:
    """Agreement of the answers with exact NumPy search over the vectors
    the document sink saw."""
    ids = list(facts["docs"])
    mat = np.stack([facts["docs"][i] for i in ids])
    norms = np.linalg.norm(mat, axis=1)
    recalls = []
    for hit_ids, _scores, qvec in facts["answers"].values():
        scores = mat @ qvec / np.maximum(norms * np.linalg.norm(qvec), 1e-30)
        exact = {ids[j] for j in np.argsort(-scores)[:K]}
        recalls.append(len(exact.intersection(hit_ids)) / len(exact))
    return float(np.mean(recalls)) if recalls else 0.0


def rag_failures(
    facts: dict, *, n_docs: int, n_queries: int, platform: str, n_devices: int
) -> list[str]:
    """Every way the pipeline's output can be wrong; empty means right."""
    from pathway_tpu.engine.external_index import DeviceKnnIndex

    failures = []
    if facts["run_error"]:
        failures.append(f"pw.run raised: {facts['run_error']}")
    if facts["errors"]:
        failures.append(
            f"global error log holds {len(facts['errors'])} entries, "
            f"first: {facts['errors'][0]}"
        )
    docs = facts["docs"]
    if len(docs) != n_docs:
        failures.append(f"{len(docs)} of {n_docs} documents reached the sink")
    if docs:
        mat = np.stack(list(docs.values()))
        if not np.isfinite(mat).all():
            failures.append("a document embedding is not finite")
        elif np.abs(np.linalg.norm(mat, axis=1) - 1.0).max() > 1e-3:
            failures.append("a document embedding is not unit-norm")
    if len(facts["answers"]) != n_queries or facts["unanswered"]:
        failures.append(
            f"{len(facts['answers'])} of {n_queries} queries answered"
        )
    if docs and facts["answers"]:
        recall = recall_at_k(facts)
        if not recall >= 0.95:
            failures.append(f"recall@{K} {recall:.4f} < 0.95")
    index = facts["index"]
    if type(index) is not DeviceKnnIndex:
        failures.append(f"the index is {type(index).__name__}")
    else:
        if len(index) != len(docs):
            failures.append(f"index holds {len(index)} of {len(docs)} docs")
        for name, arr in index.state._asdict().items():
            devices = arr.devices()
            if {d.platform for d in devices} != {platform}:
                failures.append(f"index.{name} lives on {devices}")
            if len(devices) != n_devices:
                failures.append(
                    f"index.{name} spans {len(devices)} devices, "
                    f"not {n_devices}"
                )
    return failures


# -- relational tail ----------------------------------------------------------


def run_relational(n_rows: int) -> tuple[dict, list[str]]:
    """Groupby (count, int sum, float sum) and a single-key join over
    ``n_rows`` int-keyed rows in one commit — past the placement policy's
    512-row floor, so the device kernels get the batch — against NumPy.
    Float values are multiples of 0.25: any order of addition is exact."""
    import pathway_tpu as pw
    from pathway_tpu.engine import collective_exchange as cx
    from pathway_tpu.engine import device_ops as dops
    from pathway_tpu.engine import device_residency as dres
    from pathway_tpu.internals.parse_graph import G

    G.clear()
    n_keys = 1000  # n_rows must be at least this
    ks = np.arange(n_rows) % n_keys
    vs = np.arange(n_rows)
    ws = vs * 0.25
    t = pw.debug.table_from_rows(
        pw.schema_from_types(k=int, v=int, w=float),
        list(zip(ks.tolist(), vs.tolist(), ws.tolist())),
    )
    dims = pw.debug.table_from_rows(
        pw.schema_from_types(k=int, region=int),
        [(i, i % 7) for i in range(n_keys)],
    )
    grouped = t.groupby(t.k).reduce(
        k=t.k,
        cnt=pw.reducers.count(),
        vsum=pw.reducers.sum(t.v),
        wsum=pw.reducers.sum(t.w),
    )
    joined = t.join(dims, t.k == dims.k).select(v=t.v, region=dims.region)
    got_groups: dict = {}
    got_join: dict = {}

    def on_group(key, row, time, is_addition):
        if is_addition:
            got_groups[row["k"]] = (row["cnt"], row["vsum"], row["wsum"])

    def on_join(key, row, time, is_addition):
        if is_addition:
            got_join[row["v"]] = row["region"]

    pw.io.subscribe(grouped, on_change=on_group)
    pw.io.subscribe(joined, on_change=on_join)
    hits_before = dops.hit_counts()
    pw.run(terminate_on_error=True)

    failures = []
    cnt = np.bincount(ks, minlength=n_keys)
    vsum = np.zeros(n_keys, np.int64)
    np.add.at(vsum, ks, vs)
    wsum = np.zeros(n_keys, np.float64)
    np.add.at(wsum, ks, ws)
    want_groups = {
        k: (int(cnt[k]), int(vsum[k]), float(wsum[k])) for k in range(n_keys)
    }
    if got_groups != want_groups:
        failures.append("groupby count/sum differs from NumPy")
    if got_join != {int(v): int(k % 7) for v, k in zip(vs, ks)}:
        failures.append("join differs from NumPy")
    stats = dops.stats()
    facts = {
        "rows": n_rows,
        "device_ops": {
            key: stats[key]
            for key in ("enabled", "hit_counts", "errors", "placement")
        },
        "collective": dict(cx.COLLECTIVE_STATS),
        "residency": dict(dres.RESIDENCY_STATS),
    }
    if stats["enabled"]:
        for kernel in ("segment_reduce", "match_pairs"):
            if not stats["hit_counts"].get(kernel, 0) > hits_before.get(kernel, 0):
                failures.append(f"device kernel {kernel} never ran")
    failures += device_path_errors()
    return facts, failures


def device_path_errors() -> list[str]:
    """Non-zero error counters of the three device paths."""
    from pathway_tpu.engine import collective_exchange as cx
    from pathway_tpu.engine import device_ops as dops
    from pathway_tpu.engine import device_residency as dres

    failures = []
    if dops.error_counts():
        failures.append(f"device path errors: {dops.error_counts()}")
    if cx.COLLECTIVE_STATS["errors"]:
        failures.append(
            f"collective exchange errors: {cx.COLLECTIVE_STATS['errors']}"
        )
    if dres.RESIDENCY_STATS["declines"]:
        failures.append(
            f"residency declines: {dres.RESIDENCY_STATS['declines']}"
        )
    return failures


# -- four chips ---------------------------------------------------------------


def run_wordcount(threads: int, n_words: int) -> dict:
    import pathway_tpu as pw
    from pathway_tpu.internals.parse_graph import G

    G.clear()
    rng = np.random.default_rng(0)
    words = [f"w{j}" for j in rng.integers(0, 5000, n_words)]
    t = pw.debug.table_from_rows(
        pw.schema_from_types(word=str), [(w,) for w in words]
    )
    counts = t.groupby(t.word).reduce(word=t.word, cnt=pw.reducers.count())
    got: dict = {}

    def on_count(key, row, time, is_addition):
        if is_addition:
            got[row["word"]] = row["cnt"]

    pw.io.subscribe(counts, on_change=on_count)
    pw.run(threads=threads, terminate_on_error=True)
    return got


def run_sharded_phase(
    embedder, one_chip: dict, *, n_docs: int, n_queries: int, n_words: int,
    doc_words: tuple[int, int] = DOC_WORDS,
) -> tuple[dict, list[str]]:
    """The same pipeline with the index sharded over a four-device mesh,
    started at a quarter of the capacity it needs so it doubles twice;
    answers must equal the one-chip run's. Then a four-worker wordcount
    over the collective exchange against one worker."""
    import jax

    from pathway_tpu.engine import collective_exchange as cx
    from pathway_tpu.parallel import make_mesh

    devices = jax.devices()[:4]
    mesh = make_mesh(data=4, devices=devices)
    platform = devices[0].platform
    capacity = max(8, (1 << (n_docs - 1).bit_length()) // 4)
    facts = run_rag_pipeline(
        embedder,
        n_docs=n_docs,
        n_queries=n_queries,
        capacity=capacity,
        mesh=mesh,
        doc_words=doc_words,
    )
    failures = rag_failures(
        facts, n_docs=n_docs, n_queries=n_queries, platform=platform,
        n_devices=4,
    )
    index = facts["index"]
    grown = index.capacity // capacity if index is not None else 0
    if grown < 4:
        failures.append(f"index capacity grew {grown}x, wanted two doublings")
    if index is not None:
        shards = [s.data.shape[0] for s in index.state.vectors.addressable_shards]
        if shards != [index.capacity // 4] * 4:
            failures.append(f"index rows per device after growth: {shards}")
    for qid, (ids, scores, _q) in one_chip["answers"].items():
        s_ids, s_scores, _q = facts["answers"].get(qid, ((), (), None))
        if set(s_ids) != set(ids) or not np.allclose(
            s_scores, scores, atol=1e-4, rtol=0
        ):
            failures.append(f"query {qid}: sharded answer differs from one chip")
            break
    cx.reset_counters()
    single = run_wordcount(1, n_words)
    exchanges_before = cx.COLLECTIVE_STATS["exchanges"]
    sharded = run_wordcount(4, n_words)
    if sharded != single:
        failures.append("threads=4 wordcount differs from one worker")
    if cx.enabled() and not cx.COLLECTIVE_STATS["exchanges"] > exchanges_before:
        failures.append("the collective exchange never ran under threads=4")
    failures += device_path_errors()
    return {
        "index_capacity": [capacity, index.capacity if index else None],
        "index_devices": len(index.state.vectors.devices()) if index else 0,
        "steady_seconds": round(facts["steady_seconds"], 2),
        "wordcount_groups": len(single),
        "collective": dict(cx.COLLECTIVE_STATS),
    }, failures


# -- main ---------------------------------------------------------------------


def run_phases(device: dict, out: dict, failures: list[str]) -> None:
    """Every phase at full size; facts into ``out``, failed checks into
    ``failures``."""
    from pathway_tpu import native
    from pathway_tpu.xpacks.llm.embedders import TpuEncoderEmbedder

    compiles = CompileCounter()

    def phase(name: str, facts: dict, failed: list[str]) -> None:
        out[name] = facts
        failures.extend(f"{name}: {f}" for f in failed)
        print(json.dumps({name: facts, "failed": failed}), flush=True)

    phase(
        "native",
        {"available": native.available()},
        [] if native.available() else [f"not loaded: {native.load_error()}"],
    )

    t0 = time.perf_counter()
    embedder = TpuEncoderEmbedder(
        model="all-MiniLM-L6-v2", max_len=MAX_LEN, max_batch_size=MAX_BATCH
    )
    phase(
        "attention",
        *check_attention(
            embedder,
            MAX_BATCH,
            MAX_LEN,
            [((MAX_BATCH, MAX_LEN, 12, 32), "bfloat16", False)]
            + [((8, t, 12, 32), "bfloat16", False) for t in (8, 16, 32, 64)]
            + [((2, 256, 4, 32), "float32", True)],
        ),
    )
    warm_up(embedder, N_DOCS, N_DOCS, DOC_WORDS)
    setup_seconds = time.perf_counter() - t0
    setup_compiles = compiles.snapshot()

    facts = run_rag_pipeline(
        embedder, n_docs=N_DOCS, n_queries=N_QUERIES, capacity=N_DOCS
    )
    steady_compiles = compiles.snapshot()
    phase(
        "rag",
        {
            "model": "all-MiniLM-L6-v2",
            "max_len": MAX_LEN,
            "max_batch_size": MAX_BATCH,
            "docs_embedded_and_indexed": len(facts["docs"]),
            "queries_answered": len(facts["answers"]),
            f"recall_at_{K}": (
                round(recall_at_k(facts), 4) if facts["docs"] else None
            ),
            "error_log_entries": len(facts["errors"]),
            "index": type(facts["index"]).__name__,
            "setup_seconds": round(setup_seconds, 2),
            "steady_seconds": round(facts["steady_seconds"], 2),
            "compiles_in_steady": (
                steady_compiles["requests"] - setup_compiles["requests"]
            ),
        },
        rag_failures(
            facts, n_docs=N_DOCS, n_queries=N_QUERIES,
            platform="tpu", n_devices=1,
        ),
    )
    phase("relational", *run_relational(120_000))
    if device["count"] >= 4:
        phase(
            "four_chips",
            *run_sharded_phase(
                embedder, facts, n_docs=N_DOCS, n_queries=N_QUERIES,
                n_words=200_000,
            ),
        )
    else:
        out["four_chips"] = None
        print(
            f"chip_smoke: {device['count']} chip(s) visible, the four-chip "
            "phase did not run",
            flush=True,
        )
    out["compile_cache"] = compiles.snapshot()


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--chips", type=int, choices=(4,),
        help="fail unless this many chips are visible",
    )
    args = parser.parse_args()
    faulthandler.dump_traceback_later(
        DEADLINE_S, exit=True, file=sys.__stderr__
    )

    from pathway_tpu.internals.accelerator import (
        configure_compile_cache,
        require_tpu,
    )

    cache_dir = configure_compile_cache()
    try:
        device = require_tpu()
    except RuntimeError as exc:
        print(f"chip_smoke: {exc}", file=sys.stderr)
        return 2
    print(f"chip_smoke: {device}", flush=True)
    if args.chips and device["count"] < args.chips:
        print(
            f"chip_smoke: --chips {args.chips} but {device['count']} visible",
            file=sys.stderr,
        )
        return 2
    failures: list[str] = []
    out: dict = {"device": device, "compile_cache_dir": cache_dir}
    try:
        run_phases(device, out, failures)
    except Exception as exc:  # noqa: BLE001 — a crashed phase is a failed one
        traceback.print_exc()
        failures.append(f"a phase raised: {exc!r}")
    out["failures"] = failures
    out["claim"] = None
    faulthandler.cancel_dump_traceback_later()
    for failure in failures:
        print(f"chip_smoke: FAILED {failure}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps({"summary": out}), flush=True)
    # the line the driver reads: ``ok`` and require_tpu's device (platform,
    # kind, count), no other key
    print(json.dumps({"ok": not failures, "device": device}), flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
