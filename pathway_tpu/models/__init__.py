"""Model family: pure-JAX transformers for the LLM xpack's local models.

- transformer.py — BERT-family encoder (MiniLM/BGE configs): embeddings,
  cross-encoder reranking head.
- decoder.py — causal LM over a layer pattern (grouped-query or latent
  attention; dense or routed-and-shared-expert feed-forward) for local chat.
- train.py — contrastive (InfoNCE) train step over the mesh (dp/tp/sp).

All models are param-pytree + functional-forward with PartitionSpec rules for
tensor parallelism, so the same code runs single-chip and pod-sharded.
"""

from pathway_tpu.models.transformer import (
    EncoderConfig,
    bge_base,
    bge_small,
    cross_encode,
    embed,
    encoder_forward,
    encoder_param_spec,
    init_cross_encoder_params,
    init_encoder_params,
    minilm_l6,
)
from pathway_tpu.models.decoder import (
    DecoderConfig,
    decoder_forward,
    decoder_param_spec,
    greedy_generate,
    sample_generate,
    init_decoder_params,
    mistral_7b,
    tiny_decoder,
)
from pathway_tpu.models.train import (
    ContrastiveBatch,
    TrainState,
    info_nce_loss,
    make_train_step,
)
from pathway_tpu.models.vision import (
    VisionConfig,
    clip_vit_b16,
    init_vision_params,
    normalize_u8,
    preprocess_image,
    preprocess_image_u8,
    vision_forward,
    vision_param_spec,
    vit_tiny,
)

__all__ = [
    "VisionConfig",
    "clip_vit_b16",
    "init_vision_params",
    "normalize_u8",
    "preprocess_image",
    "preprocess_image_u8",
    "vision_forward",
    "vision_param_spec",
    "vit_tiny",
    "ContrastiveBatch",
    "DecoderConfig",
    "EncoderConfig",
    "TrainState",
    "bge_base",
    "bge_small",
    "cross_encode",
    "decoder_forward",
    "decoder_param_spec",
    "embed",
    "encoder_forward",
    "encoder_param_spec",
    "greedy_generate",
    "sample_generate",
    "info_nce_loss",
    "init_cross_encoder_params",
    "init_decoder_params",
    "init_encoder_params",
    "make_train_step",
    "minilm_l6",
    "mistral_7b",
    "tiny_decoder",
]
