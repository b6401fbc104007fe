"""Reader of a query's wait, cut at the program's own boundaries. The
program keeps one record a commit (``pathway_tpu.internals.tracing
.commit_timeline()``: the commit's time, its interval, every stage that
exited inside it folded by name), on the clock the harness stamps ``sent``
and ``ack`` with, and every sink callback is handed its commit's time, which
``harness.Seen`` keeps: so for every query of the window the sink
acknowledged, with ``record`` its commit's and ``serve`` the stage that
answers it there (``chat.batch`` in the answerers' cells, ``knn.search`` in
``bge-live-rag``), in ms:

- ``queued``: ``record.t0 - sent``, from the feed's send to the start of
  the commit that took the query (what is left of the commit before it, the
  autocommit window, the pump's sleep and its poll);
- ``to_serve``: ``serve.first_t0 - record.t0``, the commit's work in front
  of the stage (the documents' steps, the query's own embedding, the search
  and the prompt in an answerer's cell);
- ``serve``: ``serve.last_t1 - serve.first_t0``, the stage itself, from
  its first start to its last end where a commit entered it twice;
- ``to_sink``: ``ack - serve.last_t1``, from there to the sink's callback.

The four sum to ``ack - sent`` for every query by construction. The reading
is the median of ``segment`` over those queries.

``None``, with a line on standard error, where the program has no
``commit_timeline`` (a commit from before it), the cell sends no queries, a
query's commit is no longer in the ring, or its record lacks the stage.
"""

from __future__ import annotations

import sys

import numpy as np

SEGMENTS = ("queued", "to_serve", "serve", "to_sink")


def commits_by_time(timeline: list[dict]) -> dict:
    """The time line's records by commit time (a time met twice, after a
    rollback: the later record)."""
    return {record["time"]: record for record in timeline}


def segments(obs, timeline: list[dict], serve: str):
    """``(queries, cut, why)``: the places in ``obs.queries`` of the
    window's acknowledged queries and their four segments, ms, as an array
    ``[n, 4]`` in :data:`SEGMENTS`' order; ``cut`` is None, and ``why``
    says why, where a query cannot be cut."""
    seen = obs.queries
    sent, ack, commit = seen.sent[:-1], seen.ack[:-1], seen.commit[:-1]  # without the primer
    queries = np.flatnonzero((commit != -1) & ~np.isnan(ack) & ~np.isnan(sent))
    if not len(queries):
        return queries, None, "no query of the window was acknowledged"
    records = commits_by_time(timeline)
    cut = np.empty((len(queries), 4))
    for row, i in enumerate(queries):
        record = records.get(int(commit[i]))
        if record is None:
            return queries, None, f"the commit of query {i} (time {int(commit[i])}) is not in the ring"
        stage = record["stages"].get(serve)
        if stage is None:
            return queries, None, f"the record of commit {record['time']} has no stage {serve!r}"
        sent_ns, ack_ns = sent[i] * 1e9, ack[i] * 1e9
        cut[row] = (
            record["t0_ns"] - sent_ns,
            stage["first_t0_ns"] - record["t0_ns"],
            stage["last_t1_ns"] - stage["first_t0_ns"],
            ack_ns - stage["last_t1_ns"],
        )
    return queries, cut / 1e6, None


def program_timeline():
    """The program's time line, or None where it keeps none."""
    from pathway_tpu.internals import tracing

    commit_timeline = getattr(tracing, "commit_timeline", None)
    return commit_timeline() if commit_timeline is not None else None


def read(ctx, segment: str, serve: str):
    timeline = program_timeline()
    if timeline is None:
        print("query_path: the program keeps no time line of its commits", file=sys.stderr)
        return None
    _queries, cut, why = segments(ctx.obs, timeline, serve)
    if cut is None:
        print(f"query_path: {why}", file=sys.stderr)
        return None
    return float(np.median(cut[:, SEGMENTS.index(segment)]))
