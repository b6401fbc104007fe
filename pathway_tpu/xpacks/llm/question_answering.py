"""RAG question answering (reference: xpacks/llm/question_answering.py).

- BaseRAGQuestionAnswerer (:314): retrieve -> prompt -> answer as dataflow.
- AdaptiveRAGQuestionAnswerer (:620): geometric document-count expansion
  (answer_with_geometric_rag_strategy :97) — start with few docs, re-ask
  with geometrically more when the model reports insufficient information;
  implemented, as in the reference, inside the answering UDF so each query
  row drives its own expansion loop.
"""

from __future__ import annotations

from typing import Any, Sequence

from pathway_tpu.internals import tracing as _tracing
from pathway_tpu.internals.expression import apply as pw_apply
from pathway_tpu.internals.table import Table
from pathway_tpu.xpacks.llm import prompts
from pathway_tpu.xpacks.llm.document_store import DocumentStore

NOT_FOUND = "No information found."


class BaseRAGQuestionAnswerer:
    """``chunk_store(key) -> str`` reads the text of a hit that has no row of
    the documents table behind it — a row restored into the index, whose
    text a restarted deployment keeps in its chunk store; with none such a
    hit is left out of the context."""

    def __init__(
        self,
        llm: Any,
        indexer: DocumentStore | None,
        *,
        search_topk: int = 6,
        prompt_template: Any = prompts.prompt_qa,
        chunk_store: Any = None,
    ) -> None:
        self.llm = llm
        self.indexer = indexer
        self.search_topk = search_topk
        self.prompt_template = prompt_template
        self.chunk_store = chunk_store

    def _full_prompt(self, question: str, texts: Sequence, keys: Sequence) -> str:
        """The template over the hits' texts in rank order."""
        with _tracing.detail("qa.prompt", docs=len(texts)):
            store = self.chunk_store
            context = []
            for text, key in zip(texts, keys):
                if text is None and store is not None and key is not None:
                    text = store(key)
                if text is not None:
                    context.append(text)
            return self.prompt_template(question, context)

    def answer_query(self, query_table: Table) -> Table:
        """``query_table(prompt: str)`` -> ``(result: str, context_docs)``."""
        topk = self.search_topk
        prepped = query_table.select(
            query=query_table.prompt,
            k=pw_apply(lambda _q: topk, query_table.prompt),
        )
        hits = self.indexer.retrieve_query(prepped)
        with_prompt = query_table.restrict(hits).select(
            prompt=query_table.prompt,
            docs=hits.result,
            full_prompt=pw_apply(
                lambda q, docs: self._full_prompt(
                    q, [d["text"] for d in docs], [None] * len(docs)
                ),
                query_table.prompt,
                hits.result,
            ),
        )
        return with_prompt.select(
            result=self.llm(with_prompt.full_prompt),
            context_docs=with_prompt.docs,
        )

    def answer_index_reply(
        self,
        query_table: Table,
        index: Any,
        query_column: Any,
        *,
        text_column: str = "text",
    ) -> Table:
        """The same answerer over a ``DataIndex``'s reply, for a caller that
        holds the index and the queries' vectors and no ``DocumentStore``:
        ``query_table(prompt: str, ...)`` -> ``(result: str, context_docs)``
        keyed as the queries are, ``context_docs`` a tuple of ``{"text",
        "id", "score"}`` in rank order, as of the query's commit."""
        hits = index.query_docs_as_of_now(
            query_table,
            query_column,
            doc_columns=[text_column],
            number_of_matches=self.search_topk,
            with_ids=True,
        )
        asked = query_table.restrict(hits)
        with_prompt = hits.select(
            docs=pw_apply(
                lambda texts, ids, scores: tuple(
                    {"text": t, "id": i, "score": s}
                    for t, i, s in zip(texts, ids, scores)
                ),
                hits[text_column],
                hits["_pw_index_reply_ids"],
                hits["_pw_index_reply_scores"],
            ),
            full_prompt=pw_apply(
                self._full_prompt,
                asked.prompt,
                hits[text_column],
                hits["_pw_index_reply_ids"],
            ),
        )
        return with_prompt.select(
            result=self.llm(with_prompt.full_prompt),
            context_docs=with_prompt.docs,
        )

    # convenience aliases mirroring the reference server surface
    def summarize_query(self, query_table: Table) -> Table:
        texts = query_table.text_list
        return query_table.select(
            result=self.llm(
                pw_apply(lambda ts: prompts.prompt_summarize(ts), texts)
            )
        )


def answer_with_geometric_rag_strategy(
    question: str,
    documents: Sequence[str],
    llm_call: Any,
    n_starting_documents: int = 2,
    factor: int = 2,
    max_iterations: int = 4,
    not_found_response: str = NOT_FOUND,
) -> str:
    """Reference question_answering.py:97: ask with n docs; if the answer is
    'not found', retry with n*factor docs until exhausted."""
    n = n_starting_documents
    for _ in range(max_iterations):
        docs = list(documents[:n])
        answer = str(llm_call(prompts.prompt_qa(question, docs, not_found_response)))
        if not_found_response.lower() not in answer.lower():
            return answer
        if n >= len(documents):
            break
        n *= factor
    return not_found_response


class AdaptiveRAGQuestionAnswerer(BaseRAGQuestionAnswerer):
    def __init__(
        self,
        llm: Any,
        indexer: DocumentStore,
        *,
        n_starting_documents: int = 2,
        factor: int = 2,
        max_iterations: int = 4,
        search_topk: int = 16,
    ) -> None:
        super().__init__(llm, indexer, search_topk=search_topk)
        self.n_starting_documents = n_starting_documents
        self.factor = factor
        self.max_iterations = max_iterations

    def answer_query(self, query_table: Table) -> Table:
        topk = self.search_topk
        prepped = query_table.select(
            query=query_table.prompt,
            k=pw_apply(lambda _q: topk, query_table.prompt),
        )
        hits = self.indexer.retrieve_query(prepped)
        llm = self.llm
        n0, factor, iters = (
            self.n_starting_documents,
            self.factor,
            self.max_iterations,
        )

        def adaptive_sync(question: str, docs: tuple) -> str:
            def llm_call(prompt: str) -> str:
                results = llm.execute_rows([(prompt,)])
                ok, value = results[0]
                if not ok:
                    raise value
                return str(value)

            return answer_with_geometric_rag_strategy(
                question,
                [d["text"] for d in docs if d["text"] is not None],
                llm_call,
                n_starting_documents=n0,
                factor=factor,
                max_iterations=iters,
            )

        # async UDF so the expansion loops of all queries in a commit fan
        # out concurrently instead of serializing on the scheduler thread
        # (reference runs these as async coroutines too)
        async def adaptive(question: str, docs: tuple) -> str:
            import asyncio

            return await asyncio.to_thread(adaptive_sync, question, docs)

        from pathway_tpu.internals.udfs import UDF

        adaptive_udf = UDF(adaptive, cache_name=f"AdaptiveRAG:{id(self)}")
        base = query_table.restrict(hits)
        return base.select(
            result=adaptive_udf(query_table.prompt, hits.result),
            context_docs=hits.result,
        )


class SummaryQuestionAnswerer(BaseRAGQuestionAnswerer):
    pass


class RAGClient:
    """HTTP client for the QA REST server (reference :854)."""

    def __init__(self, host: str = "127.0.0.1", port: int = 8755) -> None:
        self.base = f"http://{host}:{port}"

    def _post(self, path: str, payload: dict) -> Any:
        import json
        import urllib.request

        req = urllib.request.Request(
            self.base + path,
            data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req) as resp:
            return json.loads(resp.read())

    def answer(self, prompt: str) -> Any:
        return self._post("/v1/pw_ai_answer", {"prompt": prompt})

    pw_ai_answer = answer
