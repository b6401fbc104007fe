"""The comparison that decides ``correct`` in a live-index cell
(``pipelines/live_index.py`` hands over to it), and the counter of compile
requests that every pipeline's window is held to.

Once the window has closed it compares what the timed path produced — the
embeddings and answers the sinks saw, the rows the index holds — with the
plain reference (``reference.py``), number by number, each against a limit
of its own. Counts are exact (limit 0). The two gaps have limits set from
readings on the chip (``PERF.md``, "How correct is decided") and kept in
the cell's file under ``limits/``:

``embed_gap_docs``, ``embed_gap_queries``  the median, over a sample of the
    documents and over one of the queries, of the distance between the
    embedding at the sink and the reference's float32 embedding of the same
    text (both unit vectors). bfloat16 against float32 reads a few
    thousandths; float8, the step below, over three times that. The median
    and not the widest: the widest swings from seed to seed by half its
    size and leaves less than three times between the two readings.
``embed_rows_off``  how many of those sampled rows lie further from the
    reference than a loose limit of their own (``embed_row_limit``, some
    three times the widest sound reading): none may. A median passes a
    fault in under half of the rows — one sequence bucket, the longest
    documents, the rows past some slot of a batch; this does not.
``knn_gap``  over a sample of answered queries, the widest of
    ``|score_i - exact score of the id returned at rank i|`` and
    ``|score_i - exact i-th best score|``, the exact scores in float64 over
    the prefilled rows and the documents in the index at the query's commit.
    An id that should not be there, a row that is missing, a shard left
    out, a score computed in fewer passes: each widens it.
"""

from __future__ import annotations

import collections
import sys

import numpy as np

import reference

SAMPLE_TEXTS = 64  # documents, and as many queries, whose embeddings are compared
SAMPLE_QUERIES = 64  # answers compared


class CompileCounter:
    """Compile requests, as JAX reports them; the window may make none."""

    def __init__(self) -> None:
        import jax

        self.events: collections.Counter = collections.Counter()
        jax.monitoring.register_event_listener(lambda name, **_kw: self.events.update([name]))

    def requests(self) -> int:
        return self.events["/jax/compilation_cache/compile_requests_use_cache"]

    def hits(self) -> int:
        return self.events["/jax/compilation_cache/cache_hits"]


def draw_sample(seed: int, done: np.ndarray, tokens: np.ndarray, size: int) -> np.ndarray:
    """``size`` of the finished events, from the seed, the longest among them."""
    ids = np.flatnonzero(done)
    if len(ids) <= size:
        return ids
    rng = np.random.default_rng([seed, 3])
    longest = ids[np.argmax(tokens[ids])]
    rest = rng.choice(ids[ids != longest], size - 1, replace=False)
    return np.sort(np.append(rest, longest))


def index_facts(index, obs, prefilled: int, seed: int, schedule) -> dict:
    """What the comparison needs of the program's state, read before the
    state is dropped: the index's size, the rows it holds for the sampled
    documents, the device paths' error counters."""
    import jax.numpy as jnp

    from pathway_tpu.engine import collective_exchange as cx
    from pathway_tpu.engine import device_ops as dops

    done = ~np.isnan(obs.documents.ack[:-1])  # the primer is no sample
    sample = draw_sample(seed, done, schedule.documents.tokens, SAMPLE_TEXTS)
    slots = [index.key_to_slot.get(obs.evidence["doc_key"][i]) for i in sample]
    held = [s for s in slots if s is not None]
    rows = np.asarray(index.state.vectors[jnp.asarray(held, jnp.int32)]) if held else None
    by_slot = dict(zip(held, rows)) if held else {}
    return {
        "doc_sample": sample,
        "index_rows": [by_slot.get(s) for s in slots],
        "index_len": len(index),
        "index_capacity": int(index.state.vectors.shape[0]),
        "prefilled": prefilled,
        "device_errors": sum(dops.error_counts().values()) + int(cx.COLLECTIVE_STATS["errors"]),
    }


def embed_gaps(seen: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """Distance between each embedding at the sink and the reference's."""
    return np.sqrt(((seen.astype(np.float64) - ref.astype(np.float64)) ** 2).sum(-1))


def sampled_texts(seed: int, schedule, obs, facts: dict):
    """The texts whose embeddings are compared — the sampled documents, then
    the sampled queries — with what the sink saw, and how many of them are
    documents."""
    texts = [schedule.documents.texts[i] for i in facts["doc_sample"]]
    seen = [obs.evidence["doc_emb"][i] for i in facts["doc_sample"]]
    n_docs = len(texts)
    if schedule.queries is not None:
        done = ~np.isnan(obs.queries.ack[:-1])
        for i in draw_sample(seed, done, schedule.queries.tokens, SAMPLE_TEXTS):
            texts.append(schedule.queries.texts[i])
            seen.append(obs.evidence["query_emb"][i])
    return texts, np.stack(seen) if seen else np.zeros((0, 1), np.float32), n_docs


def answer_sample(seed: int, schedule, obs):
    """The answered queries that are compared, with which documents the
    index held at each one's commit."""
    done = ~np.isnan(obs.queries.ack[:-1])
    sample = draw_sample(seed, done, schedule.queries.tokens, SAMPLE_QUERIES)
    docs = np.flatnonzero(~np.isnan(obs.documents.ack))  # the primer too: it is in the index
    live = obs.documents.commit[docs][None, :] <= obs.queries.commit[sample][:, None]
    return sample, docs, live


def answer_vectors(obs, sample, docs):
    """The sampled queries' and the indexed documents' vectors, as the sinks saw them."""
    queries = np.stack([obs.evidence["query_emb"][i] for i in sample])
    if not len(docs):
        return queries, np.zeros((0, queries.shape[1]), np.float32)
    return queries, np.stack([obs.evidence["doc_emb"][i] for i in docs])


def knn_gap(seed: int, obs, sample, docs, live, prefilled: int, k: int, moments, best, answers=None):
    """(the gap, how many sampled answers are short or hold an id nobody
    offered, how many distinct prefilled rows the answers hold). ``best`` is
    :func:`reference.exact_top_k`'s scores for the sample. ``answers`` — per
    sampled query ``(ids, scores)`` with ids in ``exact_top_k``'s numbering —
    stands in for the sink's answers when the control is judged."""
    queries, doc_vectors = answer_vectors(obs, sample, docs)
    if answers is None:
        doc_of_key = {obs.evidence["doc_key"][i]: n for n, i in enumerate(docs)}
        answers = []
        for i in sample:
            ids = []
            for key in obs.evidence["query_ids"][i]:
                slot = int(key) - reference.PREFILL_KEY_BASE
                if key in doc_of_key:
                    ids.append(-1 - doc_of_key[key])
                elif 0 <= slot < prefilled:
                    ids.append(slot)
                else:
                    ids.append(None)
            answers.append((ids, obs.evidence["query_scores"][i]))
    wanted = sorted({i for ids, _ in answers for i in ids if i is not None and i >= 0})
    pre_rows = reference.prefill_rows(seed, wanted, prefilled, moments) if wanted else {}
    gap, bad = 0.0, 0
    for n, (ids, scores) in enumerate(answers):
        if len(ids) != k or any(i is None for i in ids):
            bad += 1
            continue
        rows = np.stack([pre_rows[i] if i >= 0 else doc_vectors[-1 - i] for i in ids])
        exact = reference.cos64(queries[n : n + 1], rows)[0]
        scores = np.asarray(scores, np.float64)
        gap = max(gap, float(np.abs(scores - exact).max()), float(np.abs(scores - best[n]).max()))
        bad += sum(1 for i in ids if i < 0 and not live[n, -1 - i])
    return gap, bad, len(wanted)


def stale_answers(obs) -> int:
    """Queries sent after a document was acknowledged at the sink, yet
    answered at a commit before that document's."""
    acked = np.flatnonzero(~np.isnan(obs.documents.ack))
    answered = np.flatnonzero(~np.isnan(obs.queries.ack))
    if not len(acked) or not len(answered):
        return 0
    order = acked[np.argsort(obs.documents.ack[acked])]
    newest = np.maximum.accumulate(obs.documents.commit[order])
    before = np.searchsorted(obs.documents.ack[order], obs.queries.sent[answered])
    must = np.where(before > 0, newest[np.maximum(before, 1) - 1], -1)
    return int(np.count_nonzero(must > obs.queries.commit[answered]))


def compare(cell, seed: int, *, schedule, obs, facts: dict, stand_in=None, memo=None) -> list[dict]:
    """Every number compared, with its limit and whether it holds.

    ``stand_in`` puts a control in the program's place for the numbers it
    is the control of: ``{"embeddings": [n, dim]}`` for the sampled texts'
    embeddings (in :func:`sampled_texts`' order), ``{"answers": [...]}`` for
    the sampled answers (as :func:`knn_gap` takes them). ``memo``, a dict
    the caller keeps, saves the reference's work between such calls."""
    config = cell.config
    enc, limits, params = config["encoder"], cell.limits, facts["params"]
    stand_in = stand_in or {}
    memo = {} if memo is None else memo
    numbers: list[dict] = []

    def exact(name: str, value) -> None:
        numbers.append({"name": name, "value": int(value), "limit": 0, "ok": int(value) == 0})

    def within(name: str, value: float) -> None:
        limit = limits.get(name)
        ok = limit is not None and bool(np.isfinite(value)) and value <= limit
        numbers.append({"name": name, "value": float(value), "limit": limit, "ok": ok})

    exact("docs_lost", obs.documents.n_sent() - obs.documents.acked)
    exact("docs_repeated", obs.documents.repeats)
    exact("error_log", len(obs.errors))
    exact("device_errors", facts["device_errors"])
    exact("compiles_in_window", facts["compiles_in_window"])
    exact("pool_exhausted", obs.pool_exhausted)
    exact("index_grown", facts["index_capacity"] != config["index"]["capacity"])
    exact("index_count_off", facts["index_len"] - facts["prefilled"] - obs.documents.acked)
    exact(
        "index_rows_off",
        sum(
            row is None or not np.array_equal(row, obs.evidence["doc_emb"][i])
            for i, row in zip(facts["doc_sample"], facts["index_rows"])
        ),
    )
    texts, seen, n_docs = sampled_texts(seed, schedule, obs, facts)
    seen = stand_in.get("embeddings", seen)
    if "ref" not in memo:
        memo["ref"] = reference.embed_texts(params, texts, enc, config["embedder"]["max_len"])
    gaps = embed_gaps(seen, memo["ref"]) if texts else np.zeros(0)
    groups = [("docs", gaps[:n_docs])]
    if schedule.queries is not None:
        groups.append(("queries", gaps[n_docs:]))
    for group, values in groups:
        within(f"embed_gap_{group}", float(np.median(values)) if len(values) else float("inf"))
    row_limit = limits.get("embed_row_limit", 0.0)
    exact("embed_rows_off", np.count_nonzero(~(gaps <= row_limit)))
    print(
        "embedding gaps, median and widest: "
        + "; ".join(f"{g} {np.median(v):.6f} {v.max():.6f}" for g, v in groups if len(v))
        + f" (a row's limit {row_limit})",
        file=sys.stderr,
    )
    if schedule.queries is not None:
        exact("queries_lost", obs.queries.n_sent() - obs.queries.acked)
        exact("queries_repeated", obs.queries.repeats)
        sample, docs, live = answer_sample(seed, schedule, obs)
        k, prefilled, moments = config["index"]["k"], facts["prefilled"], facts["prefill_moments"]
        gap, bad, from_prefill = float("inf"), 0, 0
        if len(sample):
            if "best" not in memo:
                memo["best"], _ = reference.exact_top_k(
                    seed, *answer_vectors(obs, sample, docs), live, prefilled, k, moments
                )
            gap, bad, from_prefill = knn_gap(
                seed, obs, sample, docs, live, prefilled, k, moments, memo["best"],
                answers=stand_in.get("answers"),
            )
        print(
            f"answers compared: {len(sample)}, holding {from_prefill} distinct prefilled rows",
            file=sys.stderr,
        )
        exact("answers_unsound", bad)
        exact("stale_answers", stale_answers(obs))
        within("knn_gap", gap)
    return numbers
