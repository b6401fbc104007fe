"""DataIndex: the retrieval API over as-of-now external indexes.

Reference surface: stdlib/indexing/data_index.py:278 (DataIndex with
``query_as_of_now``), nearest_neighbors.py:65,170 (USearchKnn /
BruteForceKnn factories). Both vector factories here map onto the same
TPU HBM brute-force engine — on TPU the "approximate vs exact" split
disappears because exact masked-matmul search at MiniLM/BGE scales is
faster than CPU HNSW graph walks.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

from pathway_tpu.internals.expression import (
    ColumnExpression,
    ColumnReference,
    apply as pw_apply,
    make_tuple,
)
from pathway_tpu.internals.reducers import sorted_tuple
from pathway_tpu.internals.table import Table
from pathway_tpu.internals.universe import solver


class InnerIndexFactory:
    """Builds an engine-side ExternalIndex instance per graph build."""

    def build(self) -> Any:
        raise NotImplementedError


@dataclasses.dataclass
class TpuKnnFactory(InnerIndexFactory):
    """KNN in TPU HBM (ops/knn.py). ``dimensions`` is the embedding width."""

    dimensions: int
    metric: str = "cos"
    capacity: int = 1024
    mesh: Any = None

    def build(self) -> Any:
        from pathway_tpu.engine.external_index import DeviceKnnIndex

        return DeviceKnnIndex(
            dim=self.dimensions,
            metric=self.metric,
            capacity=self.capacity,
            mesh=self.mesh,
        )


class BruteForceKnnFactory(TpuKnnFactory):
    """Reference-compatible name (nearest_neighbors.py:170); same engine."""


class HostKnnFactory(TpuKnnFactory):
    """CPU/NumPy twin of :class:`TpuKnnFactory` — builds the
    :class:`~pathway_tpu.engine.external_index.HostKnnIndex` bit-exact
    host spec, used by the parity corpus."""

    def build(self) -> Any:
        from pathway_tpu.engine.external_index import HostKnnIndex

        return HostKnnIndex(
            dim=self.dimensions,
            metric=self.metric,
            capacity=self.capacity,
        )


class DataIndex:
    """An index over ``data_table`` with retrieval as engine dataflow.

    ``data_column`` holds the indexable payload (embedding vector for KNN,
    text for BM25). Query results arrive as new columns on the query table.
    """

    def __init__(
        self,
        data_table: Table,
        inner_index_factory: InnerIndexFactory,
        data_column: ColumnReference,
        metadata_column: ColumnReference | None = None,
    ) -> None:
        self.data_table = data_table
        self.factory = inner_index_factory
        self.data_column = data_column
        self.metadata_column = metadata_column

    def query_as_of_now(
        self,
        query_table: Table,
        query_column: ColumnReference,
        number_of_matches: int | ColumnExpression = 3,
        collapse_rows: bool = True,
        with_scores: bool = True,
    ) -> Table:
        """Retrieve for each query row; answers are as-of-arrival.

        Returns (collapse_rows=True) a table keyed by query id with the query
        columns plus ``_pw_index_reply_ids`` (tuple of data-row Pointers) and
        ``_pw_index_reply_scores``. With collapse_rows=False, one output row
        per (query, hit) with ``_pw_index_reply_id`` / ``_pw_index_reply_score``
        columns (row id derives from the query id and rank).
        """
        reply = self.data_table._external_index_as_of_now(
            query_table,
            index_column=self.data_column,
            query_column=query_column,
            index_factory=self.factory.build,
            number_of_matches=number_of_matches,
        )
        if collapse_rows:
            combined = {
                name: query_table[name] for name in query_table.column_names()
            }
            combined["_pw_index_reply_ids"] = reply["_pw_index_reply_ids"]
            combined["_pw_index_reply_scores"] = reply["_pw_index_reply_scores"]
            return query_table.restrict(reply).select(**combined)
        # one row per hit: explode (rank, id, score) triples (zero-hit
        # queries keep a sentinel row so they stay in downstream universes)
        return explode_reply(reply)

    def query_docs_as_of_now(
        self,
        query_table: Table,
        query_column: ColumnReference,
        doc_columns: list[str],
        number_of_matches: int | ColumnExpression = 3,
        with_ids: bool = False,
    ) -> Table:
        """Collapse-with-documents: query columns + per-doc-column tuples
        ordered by rank + a scores tuple (the shape RAG pipelines consume);
        ``with_ids`` adds ``_pw_index_reply_ids`` in the same order (a hit on
        a row the index holds with no row of the data table behind it — a
        restored index — has ``None`` in every document column, and only its
        id says which it was)."""
        flat = self.query_as_of_now(
            query_table,
            query_column,
            number_of_matches=number_of_matches,
            collapse_rows=False,
        )
        return fetch_docs_for_hits(
            self.data_table, query_table, flat, doc_columns, with_ids
        )


def fetch_docs_for_hits(
    data_table: Table,
    query_table: Table,
    flat_hits: Table,
    doc_columns: list[str],
    with_ids: bool = False,
) -> Table:
    """Shared collapse tail: one-row-per-hit table (``_pw_query_id`` /
    ``_pw_index_reply_rank`` / ``_pw_index_reply_id`` / ``_pw_index_reply_score``)
    -> per-query doc-column tuples ordered by rank + scores tuple."""
    # optional: zero-hit sentinel rows carry a None doc id; allow_misses: a
    # restored index holds rows the data table never had
    docs_at = data_table.ix(
        flat_hits["_pw_index_reply_id"], optional=True, allow_misses=True
    )
    fetched = flat_hits.select(
        _pw_query_id=flat_hits["_pw_query_id"],
        _pw_index_reply_rank=flat_hits["_pw_index_reply_rank"],
        _pw_index_reply_score=flat_hits["_pw_index_reply_score"],
        **{name: docs_at[name] for name in doc_columns},
        **({"_pw_index_reply_ids": flat_hits["_pw_index_reply_id"]} if with_ids else {}),
    )

    def strip_ranks(pairs: tuple) -> tuple:
        # rank -1 marks the zero-hit sentinel; it contributes no values
        return tuple(v for rank, v in pairs if rank >= 0)

    grouped = fetched.groupby(id=fetched["_pw_query_id"])
    agg = {
        name: pw_apply(
            strip_ranks,
            sorted_tuple(
                make_tuple(fetched["_pw_index_reply_rank"], fetched[name])
            ),
        )
        for name in doc_columns + (["_pw_index_reply_ids"] if with_ids else [])
    }
    agg["_pw_index_reply_scores"] = pw_apply(
        strip_ranks,
        sorted_tuple(
            make_tuple(
                fetched["_pw_index_reply_rank"],
                fetched["_pw_index_reply_score"],
            )
        ),
    )
    result = grouped.reduce(**agg)
    # group keys ARE query ids (groupby id=_pw_query_id), so the result
    # universe is a subset of the query table's — teach the solver so
    # callers can select query columns next to the reply columns
    solver.register_subset(result._universe, query_table._universe)
    return result


def explode_reply(reply: Table) -> Table:
    """ids/scores tuples -> one row per hit (rank, id, score), with a
    sentinel row for zero-hit queries (mirrors query_as_of_now's
    collapse_rows=False shape)."""

    def hit_triples(ids: tuple, scores: tuple) -> tuple:
        if not ids:
            return ((-1, None, None),)
        return tuple((i, k, s) for i, (k, s) in enumerate(zip(ids, scores)))

    pairs = reply.select(
        _pw_hits=pw_apply(
            hit_triples,
            reply["_pw_index_reply_ids"],
            reply["_pw_index_reply_scores"],
        ),
        _pw_query_id=reply.id,
    )
    flat = pairs.flatten(pairs["_pw_hits"])
    return flat.select(
        _pw_query_id=flat["_pw_query_id"],
        _pw_index_reply_rank=flat["_pw_hits"].get(0),
        _pw_index_reply_id=flat["_pw_hits"].get(1),
        _pw_index_reply_score=flat["_pw_hits"].get(2),
    )
