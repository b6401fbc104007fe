"""The answerer's path through the program: the chat over a decoder it is
handed, its buckets and stages, the question answerer over a ``DataIndex``'s
reply, and a hit the index holds with no row of the table behind it."""

from __future__ import annotations

import numpy as np
import pytest

import pathway_tpu as pw
from pathway_tpu.engine.external_index import DeviceKnnIndex
from pathway_tpu.engine.value import Pointer
from pathway_tpu.internals import tracing
from pathway_tpu.internals.runner import GraphRunner
from pathway_tpu.models.decoder import tiny_latent_moe_decoder
from pathway_tpu.stdlib.indexing import DataIndex, TpuKnnFactory
from pathway_tpu.xpacks.llm import BaseRAGQuestionAnswerer, prompts
from pathway_tpu.xpacks.llm.llms import HFPipelineChat, TpuPipelineChat
from pathway_tpu.xpacks.llm.mocks import IdentityMockChat, fake_embeddings_model, FakeEmbedder


@pytest.fixture(scope="module")
def chat():
    return TpuPipelineChat(
        tiny_latent_moe_decoder(), max_new_tokens=5, max_prompt_len=32, max_batch_size=4,
        prompt_buckets=[16, 32], keep_tail=6, eos_id=None,
    )


def _words(n: int, start: int = 0) -> str:
    return " ".join(f"w{start + i}" for i in range(n))


def test_the_chat_takes_a_configuration_and_keeps_bfloat16_parameters(chat):
    import jax
    import jax.numpy as jnp

    assert chat.config.attention == "mla" and chat.config.layer_pattern == ("dense", "experts", "experts")
    matrices = [leaf for leaf in jax.tree.leaves(chat._params) if leaf.ndim >= 2]
    assert matrices and all(leaf.dtype == jnp.bfloat16 for leaf in matrices)
    assert chat.prompt_buckets == (16, 32)
    assert TpuPipelineChat("tiny", max_new_tokens=2).config.attention == "gqa"  # the presets' names still work
    assert issubclass(HFPipelineChat, TpuPipelineChat)
    with pytest.raises(ValueError, match="unknown decoder preset"):
        TpuPipelineChat("no-such-model")


def test_a_call_pads_to_a_bucket_and_to_the_batch_cap_so_the_programs_are_few_and_fixed(chat):
    before = chat._prefill.func._cache_size(), chat._decode.func._cache_size()
    out = chat._fn([_words(5), _words(9, 50)])  # 7 and 11 tokens: the bucket of 16
    made = chat.last_generation
    assert (made["rows"], made["bucket"], made["prompt_tokens"]) == (2, 16, [7, 11])
    assert made["tokens"].shape == (4, 5) and made["logits"].shape == (4, 5)  # the cap's rows, padding included
    assert made["logits"].dtype == np.float32 and made["expert_load"].shape == (2, 8)
    # two expert layers, two choices a token, 18 prompt tokens and 4 decode steps of 2 real rows
    assert made["expert_load"].sum() == 2 * 2 * (18 + 4 * 2)
    assert 2 <= made["prefill_touched"] <= 16 and 4 * 2 <= made["decode_touched"] <= 4 * 16
    assert len(out) == 2 and all(isinstance(text, str) for text in out)
    chat._fn([_words(3)])  # one row, the same bucket: nothing new compiles
    chat._fn([_words(3), _words(4), _words(5)])
    assert (chat._prefill.func._cache_size(), chat._decode.func._cache_size()) == (before[0] + 1, max(before[1], 1))
    chat._fn([_words(20)])  # the next bucket: one more prefill, the same decode loop
    assert chat.last_generation["bucket"] == 32
    assert (chat._prefill.func._cache_size(), chat._decode.func._cache_size()) == (before[0] + 2, max(before[1], 1))


def test_a_rows_answer_does_not_depend_on_the_rows_beside_it(chat):
    alone = chat._fn([_words(6, 7)])[0]
    beside = chat._fn([_words(12), _words(6, 7), _words(3, 30)])[1]
    assert alone == beside


def test_a_prompt_over_the_limit_keeps_its_head_and_its_tail_and_is_counted(chat):
    long = _words(40) + " question: why answer:"
    with tracing.STAGES.stage("probe"):
        chat._fn([long])
    made = chat.last_generation
    assert made["bucket"] == 32 and made["prompt_tokens"] == [32]
    tok = chat.tokenizer
    whole = tok.encode(long, 1 << 30)
    assert len(whole) == 47  # CLS, 40 words, question : why answer :, SEP
    # what the call saw is the first 26 and the last 6 ids: the same answer as that prompt cut by hand
    kept = whole[:26] + whole[-6:]
    by_hand = TpuPipelineChat(
        chat.config, max_new_tokens=5, max_prompt_len=32, max_batch_size=4, prompt_buckets=[32], keep_tail=6,
        eos_id=None, params=chat._params, tokenizer=_Fixed(tok, kept), cache_tag="t",
    )
    assert by_hand._fn(["anything"])[0] == chat._fn([long])[0]


class _Fixed:
    """A tokenizer that gives every text the same ids."""

    def __init__(self, inner, ids):
        self.inner, self.ids = inner, ids

    def encode(self, text, max_len):
        return list(self.ids)

    def decode(self, ids):
        return self.inner.decode(ids)


def _this_threads_stages() -> dict:
    """The calling thread's rows of the stage table: the run thread's, or —
    where an earlier test of this process left a run open — a thread's own."""
    totals = tracing.stage_totals()
    tables = [totals["stages"], *totals["threads"].values()]
    return next((t for t in tables if "chat.batch" in t), {})


def test_the_chats_stages_carry_the_counts_the_metrics_read(chat):
    chat._fn([_words(2)])  # so that the stages have a row before
    before = _this_threads_stages()
    chat._fn([_words(5), _words(9, 50), _words(40)])
    after = _this_threads_stages()

    def added(stage: str) -> dict:
        old = before.get(stage, {"counts": {}})["counts"]
        return {name: value - old.get(name, 0) for name, value in after[stage]["counts"].items()}

    assert added("chat.batch") == {"rows": 3, "prompt_tokens": 7 + 11 + 32, "padded_prompt_tokens": 4 * 32, "new_tokens": 15, "truncated": 1}
    assert added("chat.dispatch")["h2d_bytes"] == 4 * 32 * 4 + 4 * 32 + 4
    fetch = added("chat.fetch")
    assert after["chat.fetch"]["wait"] is True and fetch["d2h_bytes"] > 0
    assert fetch["expert_tokens_max"] >= fetch["expert_tokens_mean"] > 0
    # two expert layers of two choices: 4 x 32 prefilled and 4 x 4 decoded tokens, 50 + 3 x 4 of them real
    assert fetch["expert_pairs"] == 2 * 2 * (4 * 32 + 4 * 4)
    assert fetch["expert_pairs_skipped"] == 2 * 2 * (4 * 32 - 50 + 1 * 4)
    assert fetch["decode_layer_steps"] == 2 * 4
    # every expert is held here: all the real tokens' pairs; three latent caches of 4 x 37 rows of 20 bfloat16
    assert fetch["expert_pairs_held"] == fetch["expert_pairs"] - fetch["expert_pairs_skipped"]
    assert fetch["cache_bytes"] == 3 * 4 * 37 * 20 * 2
    assert 2 * 4 <= fetch["decode_touched"] == chat.last_generation["decode_touched"] <= 2 * 4 * 3 * 2
    assert "chat.tokenize" not in after  # a detail stage: only while someone looks


def test_a_full_batch_of_full_width_prompts_skips_no_pair(chat):
    chat._fn([_words(2)])
    before = _this_threads_stages()["chat.fetch"]["counts"]
    chat._fn([_words(40, 100 * i) for i in range(4)])  # four rows, each cut to the 32 of the widest bucket
    after = _this_threads_stages()["chat.fetch"]["counts"]
    assert chat.last_generation["prompt_tokens"] == [32] * 4
    assert after["expert_pairs"] - before["expert_pairs"] == 2 * 2 * (4 * 32 + 4 * 4)
    assert after["expert_pairs_skipped"] == before["expert_pairs_skipped"]


def test_a_chat_that_holds_a_share_of_the_experts_counts_the_pairs_it_held_and_its_rings_bytes():
    """The windowed, parallel-block decoder behind the same chat: 2 of 8
    experts held, a window of 8 under a cache of 37 positions."""
    from pathway_tpu.models.decoder import DecoderConfig

    hf = {
        "model_type": "cohere2_moe", "vocab_size": 512, "hidden_size": 64, "num_hidden_layers": 4,
        "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 32,
        "num_experts": 2, "num_experts_per_tok": 2, "num_shared_experts": 2, "sliding_window": 8,
        "layer_types": ["sliding_attention"] * 3 + ["full_attention"], "layer_norm_eps": 1e-5, "rope_theta": 50000,
        "norm_topk_prob": True, "expert_selection_fn": "sigmoid", "shared_expert_combination_strategy": "average",
        "position_embedding_type": "rope_gptj", "tie_word_embeddings": True, "use_parallel_block": True,
        "held_here": {"experts": [4, 2], "of_experts": 8},
    }
    chat = TpuPipelineChat(
        DecoderConfig.from_hf(hf), max_new_tokens=5, max_prompt_len=32, max_batch_size=4,
        prompt_buckets=[16, 32], keep_tail=6, eos_id=None,
    )
    chat._fn([_words(2)])
    before = _this_threads_stages()["chat.fetch"]["counts"]
    out = chat._fn([_words(20), _words(9, 50)])  # 22 and 11 tokens, both longer than the window: the bucket of 32
    after = _this_threads_stages()["chat.fetch"]["counts"]
    fetch = {name: value - before.get(name, 0) for name, value in after.items()}
    made = chat.last_generation
    assert len(out) == 2 and made["expert_load"].shape == (4, 2)
    real_pairs = fetch["expert_pairs"] - fetch["expert_pairs_skipped"]
    assert real_pairs == 4 * 2 * (33 + 2 * 4)  # four expert layers, two choices, 33 prompt tokens and 4 steps of 2 rows
    assert 0 < fetch["expert_pairs_held"] == made["expert_load"].sum() < real_pairs  # 2 of 8 experts: some, not all
    assert fetch["expert_pairs_held"] == made["prefill_pairs_held"] + made["decode_pairs_held"]
    assert fetch["decode_touched"] <= 4 * 4 * 2  # of the two held experts, a layer a step
    # three rings of 8 slots and one layer of all 37 positions: keys and values, 2 heads of 16, bfloat16
    assert fetch["cache_bytes"] == 4 * (3 * 8 + 37) * 2 * 2 * 16 * 2


# -- the question answerer over a DataIndex's reply ------------------------------


def _answerer_graph(chunk_store):
    """Three documents in the table; the index also holds a restored row —
    a key with a vector and no row of the table behind it — that lies
    nearest the question."""
    question = "what was restored"
    docs = pw.debug.table_from_rows(
        pw.schema_from_types(text=str), [("alpha beta",), ("gamma delta",), ("epsilon zeta",)]
    )
    embedder = FakeEmbedder(dim=16)
    docs = docs.select(text=pw.this.text, emb=embedder(pw.this.text))
    restored = Pointer(1 << 100)

    class Factory(TpuKnnFactory):
        def build(self):
            index = DeviceKnnIndex(dim=16, metric="cos", capacity=32)
            index.add([restored], [fake_embeddings_model(question, 16)])
            return index

    index = DataIndex(docs, Factory(dimensions=16, metric="cos", capacity=32), docs.emb)
    queries = pw.debug.table_from_rows(pw.schema_from_types(prompt=str), [(question,)])
    queries = queries.select(prompt=pw.this.prompt, qemb=embedder(pw.this.prompt))
    rag = BaseRAGQuestionAnswerer(IdentityMockChat(), None, search_topk=3, chunk_store=chunk_store)
    answers = rag.answer_index_reply(queries, index, queries.qemb)
    rows = list(GraphRunner().capture(answers)[0].values())
    assert len(rows) == 1
    return rows[0], restored, question


def test_the_answerer_reads_a_restored_rows_text_from_the_chunk_store():
    asked = []

    def store(key):
        asked.append(key)
        return "text of the restored chunk"

    (answer, context), restored, question = _answerer_graph(store)
    assert asked == [restored]
    assert [d["id"] for d in context][0] == restored and context[0]["text"] is None
    assert context[0]["score"] == pytest.approx(1.0, abs=1e-5) and len(context) == 3
    texts = ["text of the restored chunk"] + [d["text"] for d in context[1:]]
    assert answer == "mock: " + prompts.prompt_qa(question, texts)


def test_without_a_chunk_store_a_hit_without_a_row_is_left_out_of_the_prompt():
    (answer, context), _, question = _answerer_graph(None)
    assert "None" not in answer
    assert answer == "mock: " + prompts.prompt_qa(question, [d["text"] for d in context[1:]])


def test_answer_querys_prompt_leaves_out_a_hit_whose_text_is_none():
    rag = BaseRAGQuestionAnswerer(IdentityMockChat(), None)
    prompt = rag._full_prompt("q", ["one", None, "three"], [None, None, None])
    assert prompt == prompts.prompt_qa("q", ["one", "three"])


def test_ix_allow_misses_gives_a_row_of_none_and_a_miss_not_allowed_is_an_error():
    data = pw.debug.table_from_rows(pw.schema_from_types(v=int), [(1,), (2,)])
    keys = data.select(ptr=data.id, v=data.v)
    missing = Pointer(12345)
    keys = keys.select(ptr=pw.apply(lambda p, v: p if v == 1 else missing, keys.ptr, keys.v))
    allowed = data.ix(keys.ptr, optional=True, allow_misses=True)
    rows = sorted(GraphRunner().capture(allowed)[0].values(), key=str)
    assert sorted(rows, key=lambda r: (r[0] is None, r[0])) == [(1,), (None,)]
    strict = data.ix(keys.ptr, optional=True)
    assert list(GraphRunner().capture(strict)[0].values()) == [(1,)]
