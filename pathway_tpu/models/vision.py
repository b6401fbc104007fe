"""ViT/CLIP-style image encoder — the vision leg of the multimodal stack.

The reference's multimodal path sends slide/image bytes to a remote vision
LLM (reference: python/pathway/xpacks/llm/parsers.py:396,569 and the CLIP
embedders of vector_store.py:588). This environment has no egress, so the
vision seam's DEFAULT is this TPU-native ViT: patchify -> pre-LN
transformer -> CLS -> projection -> L2-normalised embedding, the CLIP
image-tower shape (patch 16, learned positions, quick-GELU lineage kept as
plain GELU).

Design notes (TPU-first):
- patchify is a reshape + one [p*p*3, hidden] matmul — no conv primitive,
  so XLA sees a single MXU-friendly GEMM per image batch.
- pre-LN blocks share layer_norm/dense_attention with transformer.py; all
  activations in cfg.dtype (bf16 by default) with f32 layer norms.
- params carry PartitionSpec rules (vision_param_spec) so the tower
  tensor-shards over the model axis exactly like the text encoders.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

from pathway_tpu.models.transformer import (
    Params,
    dense_attention,
    layer_norm,
)
from pathway_tpu.parallel.mesh import MODEL_AXIS
from pathway_tpu.parallel.sharding import P


@dataclasses.dataclass(frozen=True)
class VisionConfig:
    image_size: int = 224
    patch: int = 16
    hidden: int = 768
    layers: int = 12
    heads: int = 12
    intermediate: int = 3072
    out_dim: int = 512
    layer_norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    @property
    def head_dim(self) -> int:
        return self.hidden // self.heads

    @property
    def n_patches(self) -> int:
        return (self.image_size // self.patch) ** 2


def clip_vit_b16() -> VisionConfig:
    """CLIP ViT-B/16 image tower shape."""
    return VisionConfig()


def vit_tiny() -> VisionConfig:
    """Small config for tests/dry runs."""
    return VisionConfig(
        image_size=32,
        patch=8,
        hidden=64,
        layers=2,
        heads=4,
        intermediate=128,
        out_dim=32,
    )


def init_vision_params(rng: jax.Array, cfg: VisionConfig) -> Params:
    def dense(key, shape, scale=0.02):
        return scale * jax.random.normal(key, shape, jnp.float32)

    def ln():
        return {
            "scale": jnp.ones((cfg.hidden,), jnp.float32),
            "bias": jnp.zeros((cfg.hidden,), jnp.float32),
        }

    keys = iter(jax.random.split(rng, 5 + 8 * cfg.layers))
    patch_dim = cfg.patch * cfg.patch * 3
    p: Params = {
        "patch_w": dense(next(keys), (patch_dim, cfg.hidden)),
        "cls": dense(next(keys), (cfg.hidden,)),
        "pos_emb": dense(next(keys), (cfg.n_patches + 1, cfg.hidden)),
        "pre_ln": ln(),
        "final_ln": ln(),
        "proj": dense(next(keys), (cfg.hidden, cfg.out_dim)),
        "layers": [],
    }
    for _ in range(cfg.layers):
        p["layers"].append(
            {
                "ln1": ln(),
                "qkv_w": dense(next(keys), (cfg.hidden, 3 * cfg.hidden)),
                "qkv_b": jnp.zeros((3 * cfg.hidden,), jnp.float32),
                "out_w": dense(next(keys), (cfg.hidden, cfg.hidden)),
                "out_b": jnp.zeros((cfg.hidden,), jnp.float32),
                "ln2": ln(),
                "fc1_w": dense(next(keys), (cfg.hidden, cfg.intermediate)),
                "fc1_b": jnp.zeros((cfg.intermediate,), jnp.float32),
                "fc2_w": dense(next(keys), (cfg.intermediate, cfg.hidden)),
                "fc2_b": jnp.zeros((cfg.hidden,), jnp.float32),
            }
        )
    return p


def vision_param_spec(path: tuple, leaf: Any) -> P:
    """Megatron-style split over the model axis, matching
    transformer.encoder_param_spec."""
    name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
    if name in ("qkv_w", "fc1_w", "proj"):
        return P(None, MODEL_AXIS)
    if name in ("out_w", "fc2_w"):
        return P(MODEL_AXIS, None)
    # pos_emb is replicated: its row count (n_patches + 1, e.g. 197) is
    # prime, so a model-axis split can never divide it
    return P()


def patchify(pixels: jax.Array, cfg: VisionConfig) -> jax.Array:
    """``[b, H, W, 3]`` -> ``[b, n_patches, patch*patch*3]`` by reshape
    (rows of patches, then columns) — the conv-free patch embed feed."""
    b = pixels.shape[0]
    s, p = cfg.image_size, cfg.patch
    g = s // p
    x = pixels.reshape(b, g, p, g, p, 3)
    x = x.transpose(0, 1, 3, 2, 4, 5)  # [b, g, g, p, p, 3]
    return x.reshape(b, g * g, p * p * 3)


def vision_forward(
    params: Params,
    pixels: jax.Array,
    cfg: VisionConfig,
    attn_fn=None,
) -> jax.Array:
    """``pixels [b, H, W, 3]`` (normalised floats) -> L2-normalised
    embeddings ``[b, out_dim]``. ``attn_fn=None`` picks
    ``default_attn_fn()`` (dense attention)."""
    if attn_fn is None:
        from pathway_tpu.models.transformer import default_attn_fn

        attn_fn = default_attn_fn()
    b = pixels.shape[0]
    patches = patchify(pixels.astype(cfg.dtype), cfg)
    x = patches @ params["patch_w"].astype(cfg.dtype)
    cls = jnp.broadcast_to(
        params["cls"].astype(cfg.dtype)[None, None], (b, 1, cfg.hidden)
    )
    x = jnp.concatenate([cls, x], axis=1)
    x = x + params["pos_emb"].astype(cfg.dtype)[None]
    x = layer_norm(x, params["pre_ln"], cfg.layer_norm_eps)
    t = x.shape[1]
    for lp in params["layers"]:
        h = layer_norm(x, lp["ln1"], cfg.layer_norm_eps)
        qkv = h @ lp["qkv_w"].astype(cfg.dtype) + lp["qkv_b"].astype(cfg.dtype)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(b, t, cfg.heads, cfg.head_dim)
        k = k.reshape(b, t, cfg.heads, cfg.head_dim)
        v = v.reshape(b, t, cfg.heads, cfg.head_dim)
        a = attn_fn(q, k, v, None).reshape(b, t, cfg.hidden)
        x = x + a @ lp["out_w"].astype(cfg.dtype) + lp["out_b"].astype(cfg.dtype)
        h = layer_norm(x, lp["ln2"], cfg.layer_norm_eps)
        h = h @ lp["fc1_w"].astype(cfg.dtype) + lp["fc1_b"].astype(cfg.dtype)
        h = jax.nn.gelu(h, approximate=True)
        x = x + h @ lp["fc2_w"].astype(cfg.dtype) + lp["fc2_b"].astype(cfg.dtype)
    x = layer_norm(x, params["final_ln"], cfg.layer_norm_eps)
    emb = (x[:, 0] @ params["proj"].astype(cfg.dtype)).astype(jnp.float32)
    return emb / jnp.maximum(
        jnp.linalg.norm(emb, axis=-1, keepdims=True), 1e-12
    )


#: CLIP preprocessing constants (OpenAI CLIP mean/std)
CLIP_MEAN = (0.48145466, 0.4578275, 0.40821073)
CLIP_STD = (0.26862954, 0.26130258, 0.27577711)


def preprocess_image(img: Any, cfg: VisionConfig):
    """PIL image -> normalised ``[H, W, 3]`` float32 numpy (resize +
    centre-value scaling, CLIP statistics)."""
    import numpy as np

    arr = preprocess_image_u8(img, cfg).astype(np.float32) / 255.0
    return (arr - np.asarray(CLIP_MEAN, np.float32)) / np.asarray(
        CLIP_STD, np.float32
    )


def preprocess_image_u8(img: Any, cfg: VisionConfig):
    """PIL image -> resized ``[H, W, 3]`` uint8. Host keeps bytes small;
    CLIP normalisation happens on device (normalize_u8) — a 4x smaller
    host->device transfer than shipping f32 pixels (38 MB -> 9.6 MB per
    64-image batch at 224px)."""
    import numpy as np

    img = img.convert("RGB").resize(
        (cfg.image_size, cfg.image_size), resample=2  # bilinear
    )
    return np.asarray(img, np.uint8)


def normalize_u8(pixels_u8: jax.Array) -> jax.Array:
    """Device-side CLIP normalisation of uint8 pixels ``[b, H, W, 3]``."""
    x = pixels_u8.astype(jnp.float32) / 255.0
    mean = jnp.asarray(CLIP_MEAN, jnp.float32)
    std = jnp.asarray(CLIP_STD, jnp.float32)
    return (x - mean) / std
