"""Embedders: text -> vector UDFs.

Reference: python/pathway/xpacks/llm/embedders.py — SentenceTransformerEmbedder
(:270, local torch), OpenAIEmbedder (:85), LiteLLMEmbedder (:180),
GeminiEmbedder (:330). The local embedder here is the TPU-native JAX encoder
(models/transformer.py) jit-compiled and driven by the engine's batch
executor, so every commit becomes one padded MXU call instead of a torch
row loop. Remote embedders are async UDFs with capacity/retry/cache knobs;
in this zero-egress environment they require an injected ``client`` callable.
"""

from __future__ import annotations

from typing import Any, Callable

import numpy as np

from pathway_tpu.engine import device_residency as _dres
from pathway_tpu.internals import tracing as _tracing
from pathway_tpu.internals.udfs import (
    UDF,
    AsyncRetryStrategy,
    CacheStrategy,
    async_executor,
    batch_executor,
)
from pathway_tpu.xpacks.llm._tokenizer import (
    HashTokenizer,
    Tokenizer,
    pad_to_buckets,
    plan_pieces,
)

_ENCODER_PRESETS = {
    "all-MiniLM-L6-v2": "minilm_l6",
    "sentence-transformers/all-MiniLM-L6-v2": "minilm_l6",
    "BAAI/bge-base-en": "bge_base",
    "BAAI/bge-base-en-v1.5": "bge_base",
    "BAAI/bge-small-en-v1.5": "bge_small",
}


def _resolve_device_resident(device_resident: "bool | None") -> bool:
    """Shared default for the device-resident lazy-row mode (text and
    image embedders must agree on the env contract)."""
    if device_resident is not None:
        return device_resident
    import os

    return os.environ.get("PATHWAY_DEVICE_RESIDENT_UDF", "1").lower() in (
        "1",
        "true",
        "yes",
        "on",
    )


#: padded tokens the encoder takes at a time inside one jitted step.  XLA's
#: dense attention costs more device time a row as a batch grows (TPU v5e:
#: MiniLM at 128 tokens 24 us a row in blocks of 32 rows, 36 as one block
#: of 256; BGE-base at 512 tokens 0.95 ms in blocks of 8, 1.04 in one of
#: 32, 1.17 in one of 64), so a step of ``max_batch_size`` rows is one
#: dispatch whose program walks the batch in blocks of this many tokens:
#: the size that was fastest a row at every shape timed (PERF.md, PR 26)
_STEP_BLOCK_TOKENS = 4096

#: what one more dispatch of a chunk is worth, in encoder FLOPs: a chunk is
#: cut into a further piece (``plan_pieces``) only where that saves more
#: padded work than this.  A piece more costs the pump some 3.4 ms (TPU
#: v5e host, MiniLM chunks of 256 rows: 0.9 ms of ``embed.dispatch`` and
#: one more ``knn.add.dispatch`` of 2.5 ms, since the index gathers once a
#: parent device batch), and the chip walks a step block in 7.6 ms for
#: BGE-base (0.78 TFLOP) and 0.8 ms for MiniLM: some 103 TFLOP/s, at which
#: 3.4 ms are 0.35 TFLOP (PERF.md, PRs 26, 33 and 36).  Counted in FLOPs,
#: not tokens: a small encoder's padding is cheap and a dispatch is not
_DISPATCH_FLOPS = 0.35e12


def _row_flops(cfg: Any, seq: int) -> int:
    """Forward FLOPs of one padded row of ``seq`` tokens through an
    encoder of ``cfg``'s widths: a layer's QKV, output and two FFN
    products, its scores and its weighted values."""
    h, f = cfg.hidden, cfg.intermediate
    return cfg.layers * (seq * (8 * h * h + 4 * h * f) + 4 * seq * seq * h)


def _in_row_blocks(step: Callable, *arrays: Any) -> Any:
    """``step(*arrays)`` for ``[b, t]`` arrays (``b`` and ``t`` powers of
    two from 8 up, ``pad_to_buckets``), run inside the traced program as
    successive passes over row blocks of ``_STEP_BLOCK_TOKENS`` tokens
    when the batch holds more than one."""
    import jax

    b, t = arrays[0].shape
    rows = max(8, _STEP_BLOCK_TOKENS // t)
    if b <= rows:
        return step(*arrays)
    blocks = tuple(a.reshape(b // rows, rows, t) for a in arrays)
    out = jax.lax.map(lambda block: step(*block), blocks)
    return out.reshape(b, *out.shape[2:])


def _rows_from_device(vecs_dev: Any, real: int, device_resident: bool) -> list:
    """Device batch -> per-row cells: lazy device rows (prefetched host
    twin) or eager numpy."""
    if device_resident:
        from pathway_tpu.engine.device import lazy_rows

        return lazy_rows(vecs_dev, real)
    vecs = np.asarray(vecs_dev, np.float32)
    return [vecs[i] for i in range(real)]


class TpuEncoderEmbedder(UDF):
    """Local sentence embedder running on TPU.

    ``model`` picks the architecture preset (weights are randomly
    initialised unless ``params`` is given — pass imported checkpoint
    pytrees for real semantics; throughput and the full pipeline shape are
    identical either way).
    """

    def __init__(
        self,
        model: str = "all-MiniLM-L6-v2",
        *,
        max_len: int = 128,
        max_batch_size: int = 256,
        tokenizer: Tokenizer | None = None,
        params: Any = None,
        seed: int = 0,
        cache_strategy: CacheStrategy | None = None,
        device_resident: bool | None = None,
        seq_bucket_min: int = 8,
    ) -> None:
        import jax
        import jax.numpy as jnp

        from pathway_tpu.models import (
            bge_base,
            bge_small,
            embed,
            init_encoder_params,
            minilm_l6,
        )

        import os

        weights_tag = None
        if os.path.isdir(model):
            # locally cached HF / sentence-transformers directory: import
            # real weights + WordPiece vocab (models/hf_import.py)
            from pathway_tpu.models.hf_import import load_sentence_transformer

            if params is None or tokenizer is None:
                loaded_params, cfg, wp_tokenizer = load_sentence_transformer(
                    model
                )
                self.config = cfg
                if params is None:
                    params = loaded_params
                if tokenizer is None:
                    tokenizer = wp_tokenizer
            else:
                # both params and tokenizer given: the dir would contribute
                # nothing but a (large) deserialization — reject ambiguity
                raise ValueError(
                    "pass either a checkpoint dir or explicit "
                    "params+tokenizer, not both"
                )
            # cache key must identify the WEIGHTS, not the dir name: two
            # different checkpoints can share a basename
            import hashlib

            h = hashlib.blake2s(digest_size=8)
            for entry in sorted(os.listdir(model)):
                st = os.stat(os.path.join(model, entry))
                h.update(f"{entry}:{st.st_size}:{st.st_mtime_ns}".encode())
            weights_tag = h.hexdigest()
            preset = os.path.basename(os.path.normpath(model))
        else:
            preset = _ENCODER_PRESETS.get(model, model)
            cfg_fn = {
                "minilm_l6": minilm_l6,
                "bge_base": bge_base,
                "bge_small": bge_small,
            }.get(preset)
            if cfg_fn is None:
                raise ValueError(
                    f"unknown encoder preset {model!r}; "
                    f"known: {sorted(_ENCODER_PRESETS)} + "
                    f"minilm_l6/bge_base/bge_small, or a local checkpoint dir"
                )
            self.config = cfg_fn()
        # a checkpoint's positional table caps the usable sequence length
        self.max_len = min(max_len, self.config.max_len)
        #: minimum pow-2 seq padding bucket — raise (up to max_len) to trade
        #: padding FLOPs for fewer jit specializations (one compile per
        #: (batch bucket, seq bucket) pair, seconds each)
        self.seq_bucket_min = min(seq_bucket_min, self.max_len)
        self.tokenizer = tokenizer or HashTokenizer(self.config.vocab_size)
        if params is None:
            params = init_encoder_params(jax.random.key(seed), self.config)
        self._params = params
        cfg = self.config
        # params ride as a runtime argument, NOT a closure: jit inlines
        # closed-over arrays as HLO constants, which bloats every bucket's
        # module with the full weight tree (measured 13-39 s per compile
        # for MiniLM-L6 vs ~2 s with params as inputs)
        import functools

        # when the tokenizer pads with id 0 (both built-ins do; bucket
        # padding is 0 too), the mask is derivable ON DEVICE as ids != 0 —
        # halving the host->device uploads per chunk. A tokenizer that
        # declares NO pad id gets the safe default (explicit mask).
        pad = getattr(
            self.tokenizer,
            "pad_id",
            getattr(self.tokenizer, "pad_token_id", None),
        )
        self._mask_from_ids = pad == 0
        if self._mask_from_ids:
            self._jit_embed_ids = functools.partial(
                jax.jit(
                    lambda p, ids: _in_row_blocks(
                        lambda x: embed(p, x, x != 0, cfg), ids
                    )
                ),
                params,
            )
        self._jit_embed = functools.partial(
            jax.jit(
                lambda p, ids, mask: _in_row_blocks(
                    lambda x, m: embed(p, x, m, cfg), ids, mask
                )
            ),
            params,
        )

        # device-resident rows skip the device→host→device round trip
        # into the index, and lazy_rows' background prefetch overlaps
        # the host copy with the next batch's tokenize+dispatch (on the
        # local chip: not measured). Default on; PATHWAY_DEVICE_
        # RESIDENT_UDF=0 restores eager host materialisation.
        self.device_resident = _resolve_device_resident(device_resident)

        row_flops = functools.partial(_row_flops, cfg)

        def embed_batch(texts: list) -> list:
            # the steps of a call are stages only while someone looks
            # (tracing.detail); ``embed.dispatch`` is always one
            with _tracing.detail("embed.tokenize") as st:
                ids, mask = self.tokenizer.encode_batch(
                    [str(t) for t in texts], self.max_len
                )
                if st:
                    st.add(tokens=int(np.count_nonzero(mask)))
            with _tracing.detail("embed.pad") as st:
                # the chunk goes to the chip as the pieces that hold the
                # least padding: rows ordered longest first (stable: equal
                # lengths keep their order) and cut where ``plan_pieces``
                # finds a dispatch worth its while
                lengths = mask.sum(axis=1)
                order = np.argsort(-lengths, kind="stable")
                plan = plan_pieces(
                    lengths[order].tolist(),
                    row_flops,
                    _DISPATCH_FLOPS,
                    seq_bucket_min=self.seq_bucket_min,
                )

                def cut(rows: np.ndarray) -> tuple:
                    # as wide as the rows' last tokens reach (their counts
                    # say less under a tokenizer that pads on the left)
                    p_mask = mask[rows]
                    used = np.flatnonzero(p_mask.any(axis=0))
                    width = int(used.max(initial=0)) + 1
                    return rows, ids[rows, :width], p_mask[:, :width]

                if len(plan) == 1:
                    # the whole chunk, in the order it came
                    cuts = [(None, ids, mask)]
                else:
                    cuts = [cut(order[start:stop]) for start, stop in plan]
                pieces = []
                for rows, p_ids, p_mask in cuts:
                    tokens = int(np.count_nonzero(p_mask))
                    p_ids, p_mask, real = pad_to_buckets(
                        p_ids, p_mask, seq_bucket_min=self.seq_bucket_min
                    )
                    if self._mask_from_ids and np.array_equal(
                        p_mask, p_ids != 0
                    ):
                        p_mask = None
                    pieces.append((rows, p_ids, p_mask, real, tokens))
                st.add(
                    rows=len(texts),
                    padded_rows=sum(len(p[1]) for p in pieces),
                    padded_tokens=sum(p[1].size for p in pieces),
                    pieces=len(pieces),
                )
            # every piece is enqueued before any row is looked at
            on_device = []
            for rows, p_ids, p_mask, real, tokens in pieces:
                with _tracing.stage("embed.dispatch") as st:
                    # the jitted steps are looked up here, at call time: the
                    # benchmark wraps these two attributes
                    if p_mask is None:
                        h2d = p_ids.nbytes
                        vecs_dev = self._jit_embed_ids(jnp.asarray(p_ids))
                    else:
                        h2d = p_ids.nbytes + p_mask.nbytes
                        vecs_dev = self._jit_embed(
                            jnp.asarray(p_ids), jnp.asarray(p_mask)
                        )
                    _dres.record_h2d(h2d)
                    st.add(
                        h2d_bytes=h2d, tokens=tokens, padded_tokens=p_ids.size
                    )
                on_device.append((rows, vecs_dev, real))
            with _tracing.detail("embed.rows_out", rows=len(texts)):
                out: list = [None] * len(texts)
                for rows, vecs_dev, real in on_device:
                    cells = _rows_from_device(
                        vecs_dev, real, self.device_resident
                    )
                    if rows is None:
                        return cells
                    for i, cell in zip(rows.tolist(), cells):
                        out[i] = cell
                return out

        super().__init__(
            embed_batch,
            executor=batch_executor(max_batch_size=max_batch_size),
            deterministic=True,
            cache_strategy=cache_strategy,
            cache_name=(
                f"TpuEncoderEmbedder:{preset}:{max_len}:"
                + (f"ckpt{weights_tag}" if weights_tag else f"seed{seed}")
            ),
        )

    def get_embedding_dimension(self) -> int:
        return self.config.hidden


class SentenceTransformerEmbedder(TpuEncoderEmbedder):
    """Reference-compatible name (embedders.py:270); TPU-native engine."""


_VISION_PRESETS = {
    "vit-b16": "clip_vit_b16",
    "clip-vit-b16": "clip_vit_b16",
    "openai/clip-vit-base-patch16": "clip_vit_b16",
    "vit-tiny": "vit_tiny",
}


class TpuImageEmbedder(UDF):
    """Image bytes -> L2-normalised vector on TPU (models/vision.py ViT).

    The vision leg of the multimodal RAG path (reference: CLIP embedders
    feeding the multimodal vector store, python/pathway/xpacks/llm/
    vector_store.py:588). Weights are seeded-random unless ``params`` is
    given — embeddings are content-dependent either way (a random ViT is
    a locality-preserving projection), so retrieval pipelines measure the
    true ingest/query shape."""

    def __init__(
        self,
        model: str = "vit-b16",
        *,
        params: Any = None,
        seed: int = 0,
        max_batch_size: int = 64,
        cache_strategy: CacheStrategy | None = None,
        device_resident: bool | None = None,
    ) -> None:
        import io as _io
        import os

        import jax
        import jax.numpy as jnp

        from pathway_tpu.models.vision import (
            clip_vit_b16,
            init_vision_params,
            normalize_u8,
            preprocess_image_u8,
            vision_forward,
            vit_tiny,
        )

        preset = _VISION_PRESETS.get(model, model)
        cfg_fn = {"clip_vit_b16": clip_vit_b16, "vit_tiny": vit_tiny}.get(
            preset
        )
        if cfg_fn is None:
            raise ValueError(
                f"unknown vision preset {model!r}; known: "
                f"{sorted(_VISION_PRESETS)}"
            )
        self.config = cfg_fn()
        params_custom = params is not None
        if params is None:
            params = init_vision_params(jax.random.key(seed), self.config)
        self._params = params
        cfg = self.config
        import functools

        # uint8 pixels ride to the device; normalisation fuses into the
        # forward (4x smaller transfer than f32 pixels)
        self._jit_forward = functools.partial(
            jax.jit(lambda p, x8: vision_forward(p, normalize_u8(x8), cfg)),
            params,
        )
        self.device_resident = _resolve_device_resident(device_resident)

        def embed_batch(blobs: list) -> list:
            from PIL import Image

            pixels = np.stack(
                [
                    preprocess_image_u8(
                        Image.open(_io.BytesIO(b))
                        if isinstance(b, (bytes, bytearray))
                        else b,
                        cfg,
                    )
                    for b in blobs
                ]
            )
            return self.embed_pixels(pixels)

        if params_custom:
            # the namespace must identify the WEIGHTS (same rule as the
            # text embedder's weights_tag): a content fingerprint keeps
            # cached embeddings from different checkpoints apart
            from pathway_tpu.xpacks.llm.llms import _checkpoint_digest

            weights_part = f"ckpt{_checkpoint_digest(params, None)}"
        else:
            weights_part = f"seed{seed}"
        super().__init__(
            embed_batch,
            executor=batch_executor(max_batch_size=max_batch_size),
            deterministic=True,
            cache_strategy=cache_strategy,
            cache_name=f"TpuImageEmbedder:{preset}:{weights_part}",
        )

    def embed_pixels(self, pixels: "np.ndarray") -> list:
        """``[b, H, W, 3]`` uint8 pixels -> per-row embeddings
        (lazy device rows by default, like the text embedder)."""
        import jax.numpy as jnp

        real = pixels.shape[0]
        b = 8
        while b < real:
            b *= 2
        if b != real:
            pad = np.zeros((b - real,) + pixels.shape[1:], pixels.dtype)
            pixels = np.concatenate([pixels, pad])
        vecs_dev = self._jit_forward(jnp.asarray(pixels))
        return _rows_from_device(vecs_dev, real, self.device_resident)

    def embed_images(self, images: list) -> "np.ndarray":
        """PIL images -> ``[n, out_dim]`` numpy (host), for direct use by
        the parsers' vision seam."""
        return np.stack(
            [np.asarray(v, np.float32) for v in self._fn(list(images))]
        )

    def get_embedding_dimension(self) -> int:
        return self.config.out_dim


class _RemoteEmbedder(UDF):
    """Shared shape of OpenAI/LiteLLM/Gemini embedders: an async UDF over an
    injected client (``client(model=..., input=[text]) -> list[float]``)."""

    def __init__(
        self,
        model: str,
        client: Callable[..., Any] | None = None,
        *,
        capacity: int | None = None,
        timeout: float | None = None,
        cache_strategy: CacheStrategy | None = None,
        retry_strategy: AsyncRetryStrategy | None = None,
        **client_kwargs: Any,
    ) -> None:
        self.model = model
        self.kwargs = client_kwargs
        if client is None:
            raise ValueError(
                f"{type(self).__name__} needs an async `client` callable "
                "(this environment has no network egress); use "
                "xpacks.llm.mocks.fake_embeddings_model for offline runs"
            )

        async def call(text: str) -> Any:
            result = client(model=self.model, input=str(text), **self.kwargs)
            if hasattr(result, "__await__"):
                result = await result
            return np.asarray(result, np.float32)

        super().__init__(
            call,
            executor=async_executor(capacity=capacity, timeout=timeout),
            cache_strategy=cache_strategy,
            retry_strategy=retry_strategy,
            cache_name=f"{type(self).__name__}:{model}",
        )


class OpenAIEmbedder(_RemoteEmbedder):
    """Reference: embedders.py:85."""

    def __init__(self, model: str = "text-embedding-3-small", **kw: Any):
        super().__init__(model, **kw)


class LiteLLMEmbedder(_RemoteEmbedder):
    """Reference: embedders.py:180."""

    def __init__(self, model: str = "", **kw: Any):
        super().__init__(model, **kw)


class GeminiEmbedder(_RemoteEmbedder):
    """Reference: embedders.py:330."""

    def __init__(self, model: str = "models/text-embedding-004", **kw: Any):
        super().__init__(model, **kw)
