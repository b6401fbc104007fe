"""BERT-family text encoder in pure JAX (pytree params, functional forward).

Model math for the LLM xpack's *local* models. The reference delegates local
embedding/reranking to CPU/GPU torch via sentence-transformers
(reference: python/pathway/xpacks/llm/embedders.py:270, rerankers.py:186);
here the models are native JAX so they jit onto the MXU, batch with the UDF
microbatcher, and shard over the mesh (tensor parallel via PartitionSpecs,
sequence parallel via ring attention).

Configs mirror the architectures the reference's defaults load:
``minilm_l6`` (all-MiniLM-L6-v2) and ``bge_base`` (BGE-base-en / BERT-base).
Weights are randomly initialised (benchmarks measure architecture
throughput); the param tree uses HF BERT naming-compatible structure so a
checkpoint importer can be added without changing the forward pass.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from pathway_tpu.parallel.mesh import MODEL_AXIS

Params = dict  # nested dict pytree of jnp arrays


@dataclasses.dataclass(frozen=True)
class EncoderConfig:
    vocab_size: int = 30522
    hidden: int = 384
    layers: int = 6
    heads: int = 12
    intermediate: int = 1536
    max_len: int = 512
    type_vocab: int = 2
    layer_norm_eps: float = 1e-12
    dtype: Any = jnp.bfloat16  # compute dtype; params stay float32
    pooling: str = "mean"  # mean | cls

    @property
    def head_dim(self) -> int:
        return self.hidden // self.heads


def minilm_l6() -> EncoderConfig:
    return EncoderConfig(hidden=384, layers=6, heads=12, intermediate=1536)


def bge_base() -> EncoderConfig:
    return EncoderConfig(
        hidden=768, layers=12, heads=12, intermediate=3072, pooling="cls"
    )


def bge_small() -> EncoderConfig:
    return EncoderConfig(
        hidden=384, layers=12, heads=12, intermediate=1536, pooling="cls"
    )


# -- init ---------------------------------------------------------------------


def _dense_init(rng, shape, scale=0.02):
    return scale * jax.random.normal(rng, shape, jnp.float32)


def init_encoder_params(rng: jax.Array, cfg: EncoderConfig) -> Params:
    keys = iter(jax.random.split(rng, 6 + 8 * cfg.layers))
    p: Params = {
        "tok_emb": _dense_init(next(keys), (cfg.vocab_size, cfg.hidden)),
        "pos_emb": _dense_init(next(keys), (cfg.max_len, cfg.hidden)),
        "type_emb": _dense_init(next(keys), (cfg.type_vocab, cfg.hidden)),
        "emb_ln": _ln_init(cfg.hidden),
        "layers": [],
    }
    for _ in range(cfg.layers):
        p["layers"].append(
            {
                "qkv_w": _dense_init(next(keys), (cfg.hidden, 3 * cfg.hidden)),
                "qkv_b": jnp.zeros((3 * cfg.hidden,), jnp.float32),
                "out_w": _dense_init(next(keys), (cfg.hidden, cfg.hidden)),
                "out_b": jnp.zeros((cfg.hidden,), jnp.float32),
                "attn_ln": _ln_init(cfg.hidden),
                "fc1_w": _dense_init(next(keys), (cfg.hidden, cfg.intermediate)),
                "fc1_b": jnp.zeros((cfg.intermediate,), jnp.float32),
                "fc2_w": _dense_init(next(keys), (cfg.intermediate, cfg.hidden)),
                "fc2_b": jnp.zeros((cfg.hidden,), jnp.float32),
                "mlp_ln": _ln_init(cfg.hidden),
            }
        )
    return p


def _ln_init(dim: int) -> Params:
    return {
        "scale": jnp.ones((dim,), jnp.float32),
        "bias": jnp.zeros((dim,), jnp.float32),
    }


# -- partition specs (tensor parallelism) -------------------------------------


def encoder_param_spec(path: tuple, leaf: Any) -> P:
    """PartitionSpec per parameter: attention/MLP matrices split over the
    ``model`` axis (Megatron-style column/row split); embeddings split over
    the vocab/position dim; everything 1-D replicated."""
    name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
    if name in ("qkv_w", "fc1_w"):
        return P(None, MODEL_AXIS)
    if name in ("out_w", "fc2_w"):
        return P(MODEL_AXIS, None)
    if name in ("tok_emb", "pos_emb", "type_emb"):
        return P(MODEL_AXIS, None)
    return P()


# -- forward ------------------------------------------------------------------


def layer_norm(x: jax.Array, p: Params, eps: float) -> jax.Array:
    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True)
    var = x32.var(-1, keepdims=True)
    out = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (out * p["scale"] + p["bias"]).astype(x.dtype)


def dense_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, mask: jax.Array | None
) -> jax.Array:
    """Plain masked attention: q/k/v ``[b, t, h, d]``, mask ``[b, t]``."""
    d = q.shape[-1]
    s = jnp.einsum("bthd,bshd->bhts", q, k).astype(jnp.float32) / math.sqrt(d)
    if mask is not None:
        s = jnp.where(mask[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bhts,bshd->bthd", p, v)


AttnFn = Callable[[jax.Array, jax.Array, jax.Array, jax.Array | None], jax.Array]


def default_attn_fn() -> AttnFn:
    """The attention the models use when the caller names none: XLA's
    dense attention, on every backend. The Pallas flash kernel
    (``ops/flash_attention.py``) compiles and agrees with it on a TPU v5e,
    but lost to it at every shape timed there — MiniLM's
    ``[256, 128, 12, 32]`` bf16 (whole embed step 18.9 ms against 9.2 ms),
    sequence 512, and 2,048-4,096 tokens at head width 64 (CHANGES.md,
    PR 21) — so it stays off this path until a measurement says otherwise
    (ROADMAP S5). Pass ``attn_fn=flash_attention`` to use it. The choice is
    made here, in code; nothing falls back at run time."""
    return dense_attention


def encoder_forward(
    params: Params,
    token_ids: jax.Array,  # [b, t] int32
    mask: jax.Array | None,  # [b, t] bool (True = real token)
    cfg: EncoderConfig,
    attn_fn: AttnFn | None = None,
) -> jax.Array:
    """Token-level hidden states ``[b, t, hidden]`` (compute in cfg.dtype)."""
    if attn_fn is None:
        attn_fn = default_attn_fn()
    b, t = token_ids.shape
    x = (
        params["tok_emb"][token_ids]
        + params["pos_emb"][None, :t]
        + params["type_emb"][0][None, None]
    ).astype(cfg.dtype)
    x = layer_norm(x, params["emb_ln"], cfg.layer_norm_eps)
    for lp in params["layers"]:
        qkv = x @ lp["qkv_w"].astype(cfg.dtype) + lp["qkv_b"].astype(cfg.dtype)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(b, t, cfg.heads, cfg.head_dim)
        k = k.reshape(b, t, cfg.heads, cfg.head_dim)
        v = v.reshape(b, t, cfg.heads, cfg.head_dim)
        a = attn_fn(q, k, v, mask).reshape(b, t, cfg.hidden)
        a = a @ lp["out_w"].astype(cfg.dtype) + lp["out_b"].astype(cfg.dtype)
        x = layer_norm(x + a, lp["attn_ln"], cfg.layer_norm_eps)
        h = x @ lp["fc1_w"].astype(cfg.dtype) + lp["fc1_b"].astype(cfg.dtype)
        h = jax.nn.gelu(h, approximate=True)
        h = h @ lp["fc2_w"].astype(cfg.dtype) + lp["fc2_b"].astype(cfg.dtype)
        x = layer_norm(x + h, lp["mlp_ln"], cfg.layer_norm_eps)
    return x


def pool(
    hidden: jax.Array, mask: jax.Array | None, cfg: EncoderConfig
) -> jax.Array:
    """Sentence embedding from token states, L2-normalised ``[b, hidden]``."""
    h32 = hidden.astype(jnp.float32)
    if cfg.pooling == "cls":
        emb = h32[:, 0]
    else:
        if mask is None:
            emb = h32.mean(axis=1)
        else:
            m = mask.astype(jnp.float32)[..., None]
            emb = (h32 * m).sum(axis=1) / jnp.maximum(m.sum(axis=1), 1e-9)
    return emb / jnp.maximum(
        jnp.linalg.norm(emb, axis=-1, keepdims=True), 1e-12
    )


def embed(
    params: Params,
    token_ids: jax.Array,
    mask: jax.Array | None,
    cfg: EncoderConfig,
    attn_fn: AttnFn | None = None,
) -> jax.Array:
    """The embedder entry point: tokens -> normalised sentence embeddings.
    ``attn_fn=None`` picks :func:`default_attn_fn`."""
    return pool(encoder_forward(params, token_ids, mask, cfg, attn_fn), mask, cfg)


# -- cross-encoder (reranker) -------------------------------------------------


def init_cross_encoder_params(rng: jax.Array, cfg: EncoderConfig) -> Params:
    k1, k2 = jax.random.split(rng)
    p = init_encoder_params(k1, cfg)
    p["head_w"] = _dense_init(k2, (cfg.hidden, 1))
    p["head_b"] = jnp.zeros((1,), jnp.float32)
    return p


def cross_encode(
    params: Params,
    token_ids: jax.Array,  # [b, t] — query [SEP] doc pairs
    mask: jax.Array | None,
    cfg: EncoderConfig,
) -> jax.Array:
    """Relevance score per pair ``[b]`` (pre-sigmoid logit)."""
    hidden = encoder_forward(params, token_ids, mask, cfg)
    cls = hidden[:, 0].astype(jnp.float32)
    return (cls @ params["head_w"] + params["head_b"])[:, 0]
