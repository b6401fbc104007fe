"""An embed chunk goes to the chip as pieces: the planner alone
(``_tokenizer.plan_pieces``), ``embed_batch`` over a plan of several
pieces, the index taking rows of several parent device batches in one
add, and the counts the ``embed.dispatch`` stage carries."""

import numpy as np
import pytest

from pathway_tpu.internals import tracing
from pathway_tpu.xpacks.llm import embedders
from pathway_tpu.xpacks.llm._tokenizer import _bucket, plan_pieces
from pathway_tpu.xpacks.llm.embedders import TpuEncoderEmbedder


def _lognormal_lengths(rng, n, median, sigma, low, high):
    """``n`` token counts of a benchmark mix's law (``benchmark/traffic``:
    a lognormal's floor, clipped), longest first."""
    z = rng.standard_normal(n)
    lengths = np.clip(np.floor(median * np.exp(sigma * z)), low, high)
    return sorted(lengths.astype(int).tolist(), reverse=True)


def _live_rag(rng, n):  # TREC-COVID documents, BGE-base at 512
    return _lognormal_lengths(rng, n, 131.3, 0.7, 32, 512)


def _backfill(rng, n):  # MS MARCO passages, MiniLM at 128
    return _lognormal_lengths(rng, n, 51.1, 0.6, 8, 128)


def _cost(config):
    from pathway_tpu import models

    cfg = getattr(models, config)()
    return lambda seq: embedders._row_flops(cfg, seq)


def _shapes(lengths, plan, seq_bucket_min):
    return [
        (_bucket(stop - start, 8), _bucket(lengths[start], seq_bucket_min))
        for start, stop in plan
    ]


def _plan_cost(lengths, plan, row_cost, dispatch, seq_bucket_min):
    return sum(
        rows * row_cost(seq) + dispatch
        for rows, seq in _shapes(lengths, plan, seq_bucket_min)
    )


def _every_cut(lengths, row_cost, dispatch, seq_bucket_min):
    """The least cost over every way to cut ``lengths`` (longest first)
    into consecutive pieces: the plain search the planner shortens."""
    n = len(lengths)
    best = [0.0] * (n + 1)
    for start in range(n - 1, -1, -1):
        row = row_cost(_bucket(lengths[start], seq_bucket_min))
        best[start] = min(
            _bucket(stop - start, 8) * row + dispatch + best[stop]
            for stop in range(start + 1, n + 1)
        )
    return best[0]


class TestPlanPieces:
    @pytest.mark.parametrize(
        "lengths",
        [
            [],
            [17],
            [512] * 8,
            [128] * 64,  # exactly a bucket of equal lengths
            [40] * 41,  # padding rows, but nothing a cut would save
            [512, 40, 33],  # under the least row bucket: a cut pays twice
        ],
        ids=["empty", "one_row", "bucket_of_8", "bucket_of_64", "equal_41", "three"],
    )
    def test_one_piece(self, lengths):
        plan = plan_pieces(
            lengths, _cost("bge_base"), embedders._DISPATCH_FLOPS, seq_bucket_min=32
        )
        assert plan == [(0, len(lengths))]

    @pytest.mark.parametrize("seed", range(6))
    def test_live_rag_commit_under_bge_base_is_cut(self, seed):
        lengths = _live_rag(np.random.default_rng(seed), 41)
        plan = plan_pieces(
            lengths, _cost("bge_base"), embedders._DISPATCH_FLOPS, seq_bucket_min=32
        )
        assert 2 <= len(plan) <= 4
        # consecutive, covering every row once
        assert plan[0][0] == 0 and plan[-1][1] == 41
        assert all(a[1] == b[0] for a, b in zip(plan, plan[1:]))
        shapes = _shapes(lengths, plan, 32)
        # every piece is a shape the uncut path makes and a warm-up runs
        assert all(rows in (8, 16, 32, 64) for rows, _ in shapes)
        assert all(seq in (32, 64, 128, 256, 512) for _, seq in shapes)
        # every row fits its piece
        assert all(
            lengths[start] <= seq for (start, _), (_, seq) in zip(plan, shapes)
        )
        today = 64 * _bucket(lengths[0], 32)
        assert sum(rows * seq for rows, seq in shapes) < 0.7 * today

    @pytest.mark.parametrize("seed", range(6))
    def test_backfill_chunk_under_minilm_stays_whole(self, seed):
        """A MiniLM token costs a ninth of a BGE-base token and a dispatch
        costs the host the same: the cut a count of tokens would make
        (some 8,000 padded tokens saved) is not worth a dispatch."""
        lengths = _backfill(np.random.default_rng(seed), 256)
        cost = _cost("minilm_l6")
        plan = plan_pieces(lengths, cost, embedders._DISPATCH_FLOPS, seq_bucket_min=8)
        assert plan == [(0, 256)]
        # the same rule under BGE-base's widths would cut the same chunk
        assert len(plan_pieces(lengths, _cost("bge_base"), embedders._DISPATCH_FLOPS)) > 1

    @pytest.mark.parametrize(
        "n,dispatch",
        [(9, 0.0), (23, 1e9), (41, 0.35e12), (64, 0.35e12), (100, 1e11), (200, 2e12)],
    )
    def test_no_cheaper_cut_exists(self, n, dispatch):
        """The planner looks only at plans whose pieces but the last are
        exact row buckets; none of all the others is cheaper."""
        rng = np.random.default_rng(n)
        cost = _cost("bge_base")
        for _ in range(5):
            lengths = _live_rag(rng, n)
            plan = plan_pieces(lengths, cost, dispatch, seq_bucket_min=32)
            got = _plan_cost(lengths, plan, cost, dispatch, 32)
            assert got == pytest.approx(_every_cut(lengths, cost, dispatch, 32), rel=1e-12)

    def test_a_free_dispatch_cuts_at_every_saving(self):
        lengths = [500] * 8 + [30] * 8
        assert plan_pieces(lengths, float, 0.0, seq_bucket_min=8) == [(0, 8), (8, 16)]
        # and a dear one leaves the chunk whole
        assert plan_pieces(lengths, float, 1e9, seq_bucket_min=8) == [(0, 16)]


@pytest.fixture
def cheap_dispatch(monkeypatch):
    """MiniLM at a few tokens is too small for a cut to be worth a real
    dispatch: make the dispatch worth next to nothing, so that the toy
    takes the path BGE-base takes at 512 tokens."""
    monkeypatch.setattr(embedders, "_DISPATCH_FLOPS", 1e6)


def _texts(rng, lengths):
    # the tokenizer adds CLS and SEP to a text's words
    return [
        " ".join(f"w{rng.integers(0, 500)}" for _ in range(n - 2)) for n in lengths
    ]


def _dispatched_shapes(emb):
    shapes = []
    for attr in ("_jit_embed_ids", "_jit_embed"):
        inner = getattr(emb, attr)

        def call(ids, *rest, _inner=inner):
            shapes.append(tuple(ids.shape))
            return _inner(ids, *rest)

        setattr(emb, attr, call)
    return shapes


@pytest.mark.parametrize("device_resident", [True, False], ids=["lazy", "eager"])
def test_embed_batch_in_pieces_returns_rows_in_input_order(
    cheap_dispatch, device_resident
):
    rng = np.random.default_rng(1)
    lengths = [60, 4, 9, 33, 5, 64, 7, 7, 12, 31, 6, 5, 17, 4, 8, 40, 3, 6, 10, 5, 22]
    texts = _texts(rng, lengths)
    emb = TpuEncoderEmbedder(
        "minilm_l6", max_len=64, max_batch_size=32, device_resident=device_resident
    )
    shapes = _dispatched_shapes(emb)
    rows = emb._fn(list(texts))
    assert len(shapes) >= 2
    assert all(b in (8, 16, 32) and t in (8, 16, 32, 64) for b, t in shapes)
    assert sum(b * t for b, t in shapes) < 32 * 64
    assert len(rows) == len(texts)
    if device_resident:
        # a piece is a parent device batch; the rows of one lie scattered
        assert len({id(row.batch) for row in rows}) == len(shapes)
    got = np.stack([np.asarray(row, np.float32) for row in rows])
    alone = np.stack([np.asarray(emb._fn([text])[0], np.float32) for text in texts])
    # bfloat16 compute under other padding: rounding, no more
    assert np.abs(got - alone).max() < 5e-3
    # a row in another row's place would be far off
    assert np.abs(got - np.roll(alone, 1, axis=0)).max() > 0.05


def test_embed_batch_of_one_piece_is_the_padded_chunk():
    """Where no cut is worth a dispatch the chunk goes as it came:
    ``pad_to_buckets`` of the whole, rows in their order, one parent."""
    rng = np.random.default_rng(2)
    texts = _texts(rng, [16, 3, 9, 12, 5, 7, 16, 4, 11, 8])
    emb = TpuEncoderEmbedder("minilm_l6", max_len=16)
    shapes = _dispatched_shapes(emb)
    rows = emb._fn(list(texts))
    assert shapes == [(16, 16)]
    assert [row.index for row in rows] == list(range(len(texts)))
    assert len({id(row.batch) for row in rows}) == 1


def test_a_piece_is_as_wide_as_its_rows_reach_not_as_their_counts():
    """A tokenizer may pad on the left: a row's count of tokens then says
    less than where its last token stands."""
    from pathway_tpu.xpacks.llm._tokenizer import HashTokenizer

    class LeftPadded(HashTokenizer):
        def encode_batch(self, texts, max_len):
            ids, mask = super().encode_batch(texts, max_len)
            for i, n in enumerate(mask.sum(axis=1)):
                ids[i] = np.roll(ids[i], ids.shape[1] - n)
                mask[i] = np.roll(mask[i], mask.shape[1] - n)
            return ids, mask

    rng = np.random.default_rng(3)
    texts = _texts(rng, [60] * 8 + [5] * 8)
    emb = TpuEncoderEmbedder(
        "minilm_l6", max_len=64, tokenizer=LeftPadded(), device_resident=False
    )
    seen = []
    inner = emb._jit_embed_ids

    def call(ids):
        seen.append(np.asarray(ids))
        return inner(ids)

    emb._jit_embed_ids = call
    emb._fn(list(texts))
    assert sum(int((ids != 0).sum()) for ids in seen) == 8 * 60 + 8 * 5


class TestDispatchCounts:
    @staticmethod
    def _dispatch_row(emb, texts):
        root = tracing.STAGES.begin_run()
        try:
            rows = emb._fn(list(texts))
        finally:
            tracing.STAGES.end_run(root)
        assert len(rows) == len(texts)
        return tracing.stage_totals()["stages"]["embed.dispatch"]

    @pytest.mark.parametrize(
        "lengths,pieces",
        [
            ([16] * 8, 1),
            ([5, 9, 3], 1),
            ([60, 4, 9, 33, 5, 64, 7, 7, 12, 31, 6, 5, 17, 4, 8, 40, 3, 6, 10, 5, 22], 3),
        ],
        ids=["bucket", "three_rows", "mixed_21"],
    )
    def test_tokens_and_padded_tokens_sum_to_the_chunk(
        self, cheap_dispatch, lengths, pieces
    ):
        texts = _texts(np.random.default_rng(4), lengths)
        emb = TpuEncoderEmbedder(
            "minilm_l6", max_len=64, max_batch_size=32, device_resident=False
        )
        shapes = _dispatched_shapes(emb)
        row = self._dispatch_row(emb, texts)
        assert row["calls"] == len(shapes) == pieces
        assert row["counts"]["tokens"] == sum(lengths)
        assert row["counts"]["padded_tokens"] == sum(b * t for b, t in shapes)
        assert row["counts"]["h2d_bytes"] == 4 * row["counts"]["padded_tokens"]

    def test_the_pad_stage_counts_the_pieces_while_someone_looks(self, cheap_dispatch):
        texts = _texts(np.random.default_rng(5), [60] * 8 + [5] * 8)
        emb = TpuEncoderEmbedder("minilm_l6", max_len=64, device_resident=False)
        root = tracing.STAGES.begin_run()
        tracing.TRACER.configure(enabled=True, sample=1, clear=True)
        try:
            assert tracing.TRACER.begin(1) is not None  # a sampled commit
            emb._fn(list(texts))
            tracing.TRACER.end(1)
        finally:
            tracing.TRACER.drop()
            tracing.TRACER.configure(enabled=False, clear=True)
            tracing.TRACER.epoch = 0
            tracing.STAGES.end_run(root)
        pad = tracing.stage_totals()["stages"]["embed.pad"]["counts"]
        assert pad == {
            "rows": 16, "padded_rows": 16, "padded_tokens": 8 * 64 + 8 * 8, "pieces": 2,
        }


class TestIndexTakesSeveralParents:
    @pytest.mark.parametrize("parents", [1, 2, 3])
    def test_one_add_one_dispatch_a_parent_every_row_found(self, parents):
        import jax.numpy as jnp

        from pathway_tpu.engine.device import lazy_rows
        from pathway_tpu.engine.external_index import DeviceKnnIndex
        from pathway_tpu.engine.value import Pointer

        dim, real = 16, [5, 8, 3][:parents]
        rng = np.random.default_rng(parents)
        vectors, cells = [], []
        for n in real:
            batch = rng.standard_normal((8, dim)).astype(np.float32)
            vectors.append(batch[:n])
            cells.append(lazy_rows(jnp.asarray(batch), n, prefetch=False))
        vectors = np.concatenate(vectors)
        cells = [cell for parent in cells for cell in parent]
        # as ``embed_batch`` hands them back: the parents' rows interleaved
        order = rng.permutation(len(cells))
        keys = [Pointer(1000 + int(i)) for i in order]
        index = DeviceKnnIndex(dim=dim, metric="cos", capacity=64)
        root = tracing.STAGES.begin_run()
        try:
            index.add(keys, [cells[i] for i in order])
        finally:
            tracing.STAGES.end_run(root)
        dispatch = tracing.stage_totals()["stages"]["knn.add.dispatch"]
        assert dispatch["calls"] == parents
        assert dispatch["counts"]["rows"] == len(cells)
        assert len(index) == len(cells)
        found = index.search(list(vectors), 1)
        assert [hits[0][0] for hits in found] == [Pointer(1000 + i) for i in range(len(cells))]
        assert all(hits[0][1] == pytest.approx(1.0, abs=1e-5) for hits in found)
