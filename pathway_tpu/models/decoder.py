"""Causal decoder LM in pure JAX, one module for a pattern of layers.

The chat path of the LLM xpack. The reference's local chat wraps a HF
``pipeline`` on CPU/GPU torch (reference: python/pathway/xpacks/llm/llms.py:441
HFPipelineChat); here decode is native JAX on TPU. A ``DecoderConfig`` states
the attention every layer uses, layer by layer the feed-forward kind
(``layer_pattern``) and, with ``layer_types``, layer by layer the operator
in attention's place (``attention_pattern``):

- attention ``"gqa"``: grouped-query heads over RoPE (the Mistral-style
  layer); the layer's cache holds keys and values, ``[b, slots, kv_heads,
  head_dim]`` each. With ``layer_types`` each layer is one of two kinds of
  it: ``"sliding_attention"`` (RoPE; key ``j`` is seen by query ``i`` iff
  ``0 <= i - j < sliding_window``; the cache is a ring of ``min(window,
  max_len)`` slots, position ``p`` in slot ``p mod slots``) and
  ``"full_attention"`` (causal, ``max_len`` slots); ``"gqa"`` there is the
  rotating layer itself. How a kind turns by position is the configuration's
  (``rope_of``): by default plain RoPE at ``rope_theta``, and ``layer_rope``
  gives a kind a rotation of its own or none (Command A's full layer takes no
  positions at all; Mellum's turns by YaRN, times its attention factor).
  ``qk_norm`` puts an RMS norm over each head's values of the queries and of
  the keys before the rotation. A chunk over its own keys (a prefill, a pass
  without a cache) is walked in square tiles, each query tile visiting only
  the key tiles its row's real queries need (``_walked_attention``); over a
  cache's keys every score is evaluated and masked (``_grouped_query``).
- operator ``"conv"``: a gated short convolution in attention's place. ``B, C,
  u`` are thirds of one projection, ``z = B * u``, a causal depthwise filter
  of ``conv_taps`` taps runs over ``z`` channel by channel, and ``C`` gates
  what it gives. **The layer's cache holds the last ``conv_taps`` ``z`` of a
  row, ``[b, hidden, conv_taps]``, whatever ``max_len``**; a padding position's
  ``z`` is zero, so a left-padded row's first tokens see what an unpadded
  row's see.
- attention ``"mla"``: latent attention. Keys and values are expanded from
  one compressed row a token, and **the layer's cache holds that row and the
  rotated shared key only** (``kv_lora_rank + qk_rope_head_dim`` values a
  token). A chunk of several tokens attends in the expanded form; a single
  decode token attends in the absorbed form, which reads the latent cache
  once and never expands it. RoPE may carry YaRN scaling.
- feed-forward ``"dense"``: a gated SiLU MLP; ``"experts"``: a float32
  router over all routed experts, the ``experts_per_token`` largest, a
  grouped product over the experts that were chosen (``ops/moe.py``; no
  capacity, no token dropped) plus the shared experts (where there are any)
  as one gated MLP, summed or averaged. ``router`` ``"sigmoid"`` scores each
  expert by itself; with ``router_bias`` the layer's ``expert_bias`` is added
  for the choice and left out of the weights.
  With ``held_experts`` the layer holds a share of its experts: it routes
  over all of them and computes the part of the result its own give.

``norm`` ``"layer"`` is a mean-subtracting LayerNorm (weight only);
``parallel_block`` reads one norm a layer for attention and feed-forward
both and adds both at once; ``tie_embeddings`` takes the embedding for the
head, times ``logit_scale``.

Each layer is handed its own cache state; ``init_cache`` makes the list.
``prefill`` and ``decode_step`` compute the head at one position a row (the
last), ``decoder_forward`` at every position. Tensor-parallel weight specs
go over the ``model`` mesh axis, the experts' over ``expert``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from pathway_tpu.ops.moe import in_blocks, route_top_k, routed_experts, rows_walked
from pathway_tpu.ops.prefill_attention import walked_attention
from pathway_tpu.parallel.mesh import EXPERT_AXIS, MODEL_AXIS

Params = dict

#: prefill walks a batch in groups of rows of about this many tokens, so that
#: attention scores and the experts' sorted rows of one group, not of the
#: batch, are what the device holds at once
PREFILL_BLOCK_TOKENS = 4096


@dataclasses.dataclass(frozen=True)
class YarnScaling:
    """YaRN's RoPE scaling, by the published keys of ``rope_scaling``."""

    factor: float
    original_max_len: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


@dataclasses.dataclass(frozen=True)
class LayerRope:
    """How one kind of attention layer turns its queries and keys by
    position: RoPE at ``theta``, with YaRN's frequencies where ``yarn`` is
    given, cos and sin times ``scale`` (YaRN's attention factor)."""

    theta: float
    yarn: YarnScaling | None = None
    scale: float = 1.0


@dataclasses.dataclass(frozen=True)
class DecoderConfig:
    vocab_size: int = 32000
    hidden: int = 4096
    layers: int = 32
    heads: int = 32
    kv_heads: int = 8
    intermediate: int = 14336
    max_len: int = 8192
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    attention: str = "gqa"  # "gqa" | "mla"
    # -- latent attention
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    rope_scaling: YarnScaling | None = None
    # -- routed and shared experts (none: every layer is dense)
    n_routed_experts: int = 0
    experts_per_token: int = 0
    n_shared_experts: int = 0
    moe_intermediate: int = 0
    first_dense_layers: int = 0
    norm_topk_prob: bool = False
    routed_scaling_factor: float = 1.0
    router: str = "softmax"  # "softmax" | "sigmoid"
    shared_combine: str = "sum"  # "sum" | "average"
    #: (first, count) of the routed experts this chip holds (none: all)
    held_experts: tuple[int, int] | None = None
    # -- the block
    head_size: int = 0  # a head's width where it is not hidden / heads
    #: each layer's operator: "sliding_attention" | "full_attention" | "gqa" | "conv"
    layer_types: tuple[str, ...] | None = None
    sliding_window: int = 0
    norm: str = "rms"  # "rms" | "layer"
    parallel_block: bool = False
    rope_interleaved: bool = False
    tie_embeddings: bool = False
    logit_scale: float = 1.0
    qk_norm: bool = False  # an RMS norm over each head of the queries and of the keys
    router_bias: bool = False  # the experts are chosen by score + expert_bias
    conv_taps: int = 0  # the length of a "conv" layer's filter, and of its state
    #: (kind, rotation) where an attention kind turns otherwise than by plain
    #: RoPE at ``rope_theta``; a rotation of ``None``: the kind takes no positions
    layer_rope: tuple[tuple[str, LayerRope | None], ...] = ()

    @property
    def head_dim(self) -> int:
        return self.head_size or self.hidden // self.heads

    @property
    def experts_held(self) -> int:
        return self.held_experts[1] if self.held_experts else self.n_routed_experts

    @property
    def layer_pattern(self) -> tuple[str, ...]:
        """The feed-forward kind of each layer, ``"dense"`` or ``"experts"``."""
        if not self.n_routed_experts:
            return ("dense",) * self.layers
        dense = min(self.first_dense_layers, self.layers)
        return ("dense",) * dense + ("experts",) * (self.layers - dense)

    @property
    def attention_pattern(self) -> tuple[str, ...]:
        """The operator of each layer: ``attention`` for every layer, or
        with ``layer_types`` ``"sliding"``, ``"full"`` or ``"gqa"`` (all
        ``"gqa"`` heads), or ``"conv"``."""
        if self.layer_types is None:
            return (self.attention,) * self.layers
        return tuple(kind.removesuffix("_attention") for kind in self.layer_types)

    def rope_of(self, kind: str) -> LayerRope | None:
        """The rotation of an attention layer of ``kind`` (``attention_pattern``'s
        names), ``None`` where it takes no positions: ``layer_rope``'s where it
        names the kind, else plain RoPE at ``rope_theta``."""
        return dict(self.layer_rope).get(kind, LayerRope(self.rope_theta))

    @property
    def cache_width(self) -> int:
        """Values a token a layer keeps in an ``"mla"`` layer's cache."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def softmax_scale(self) -> float:
        if self.attention == "gqa":
            return 1.0 / math.sqrt(self.head_dim)
        scale = (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5
        yarn = self.rope_scaling
        if yarn is not None and yarn.mscale_all_dim:
            scale *= yarn_mscale(yarn.factor, yarn.mscale_all_dim) ** 2
        return scale

    @classmethod
    def from_hf(cls, hf: dict, **overrides: Any) -> "DecoderConfig":
        """From the keys of a published ``config.json`` (``model_type``
        ``deepseek_v2``: latent attention without a query projection rank,
        greedy softmax routing in one group; ``cohere2_moe``: windowed beside
        full grouped-query attention in a parallel block, sigmoid routing,
        averaged shared experts, and under ``held_here`` the share of the
        experts this chip holds; ``lfm2_moe``: gated short convolutions
        beside grouped-query attention that rotates, whatever its
        ``layer_types`` string, and norms each head of its queries and keys,
        sigmoid routing with a selection bias, no shared expert; ``mellum``:
        sliding beside full grouped-query attention in a sequential block,
        each kind turned by its own section of ``rope_parameters`` (the
        full layer by YaRN times its ``attention_factor``), softmax routing
        renormalised, no shared expert, an untied head; ``mistral`` /
        ``llama``). What ``"full_attention"`` is follows the family: in
        ``cohere2_moe`` a layer that takes no positions, in ``lfm2_moe`` the
        one kind of attention, turned by plain RoPE, in ``mellum`` a layer
        turned by YaRN."""
        kind = hf.get("model_type", "mistral")
        common = dict(
            vocab_size=hf["vocab_size"],
            hidden=hf["hidden_size"],
            layers=hf["num_hidden_layers"],
            heads=hf["num_attention_heads"],
            kv_heads=hf.get("num_key_value_heads", hf["num_attention_heads"]),
            intermediate=hf["intermediate_size"],
            max_len=hf.get("max_position_embeddings", 8192),
            rope_theta=float(hf.get("rope_theta") or (hf.get("rope_parameters") or {}).get("rope_theta", 10000.0)),
            rms_eps=hf.get("rms_norm_eps") or hf.get("layer_norm_eps", 1e-5),
        )

        def require(required: dict) -> None:
            for key, only in required.items():
                if hf.get(key, only) != only:
                    raise ValueError(f"{kind} with {key}={hf[key]!r}: only {only!r} is implemented")

        if kind == "cohere2_moe":
            require({
                "use_qk_norm": False, "use_parallel_block": True, "first_k_dense_replace": 0,
                "use_gated_activation": True, "shared_expert_combination_strategy": "average",
                "position_embedding_type": "rope_gptj", "order_of_interleaved_layers": "local_attn_first",
                "expert_selection_fn": "sigmoid", "hidden_act": "silu", "attention_bias": False,
                "rotary_pct": 1, "tie_word_embeddings": True,
            })
            layer_types = tuple(hf["layer_types"][: hf["num_hidden_layers"]])
            unknown = set(layer_types) - {"sliding_attention", "full_attention"}
            if unknown or len(layer_types) != hf["num_hidden_layers"]:
                raise ValueError(f"cohere2_moe with layer_types={hf['layer_types']!r}: a kind a layer, sliding or full")
            # the chip's share of a deployment: num_experts counts the experts
            # held here, held_here says which they are and of how many
            share = hf.get("held_here")
            held = tuple(share["experts"]) if share else None
            if held is not None and held[1] != hf["num_experts"]:
                raise ValueError(f"held_here.experts={share['experts']!r} beside num_experts={hf['num_experts']!r}")
            common.update(
                head_size=hf["head_dim"],
                layer_types=layer_types,
                sliding_window=hf["sliding_window"],
                layer_rope=(("full", None),),  # this family's full layer takes no positions
                norm="layer",
                parallel_block=True,
                rope_interleaved=True,
                tie_embeddings=True,
                logit_scale=float(hf.get("logit_scale", 1.0)),
                n_routed_experts=share["of_experts"] if share else hf["num_experts"],
                held_experts=held,
                experts_per_token=hf["num_experts_per_tok"],
                n_shared_experts=hf["num_shared_experts"],
                moe_intermediate=hf["intermediate_size"],  # one expert's width: the config has no key of its own
                norm_topk_prob=hf.get("norm_topk_prob", False),
                router="sigmoid",
                shared_combine="average",
            )
        elif kind == "deepseek_v2":
            require({
                "q_lora_rank": None, "hidden_act": "silu", "scoring_func": "softmax",
                "topk_method": "greedy", "n_group": 1, "moe_layer_freq": 1,
                "attention_bias": False, "tie_word_embeddings": False,
            })
            scaling = hf.get("rope_scaling")
            if scaling is not None and scaling.get("type") != "yarn":
                raise ValueError(f"rope_scaling of type {scaling.get('type')!r}: only 'yarn' is implemented")
            common.update(
                attention="mla",
                kv_lora_rank=hf["kv_lora_rank"],
                qk_nope_head_dim=hf["qk_nope_head_dim"],
                qk_rope_head_dim=hf["qk_rope_head_dim"],
                v_head_dim=hf["v_head_dim"],
                rope_scaling=None if scaling is None else YarnScaling(
                    factor=scaling["factor"],
                    original_max_len=scaling["original_max_position_embeddings"],
                    beta_fast=scaling.get("beta_fast", 32),
                    beta_slow=scaling.get("beta_slow", 1),
                    mscale=scaling.get("mscale", 1.0),
                    mscale_all_dim=scaling.get("mscale_all_dim", 0.0),
                ),
                n_routed_experts=hf.get("n_routed_experts") or 0,
                experts_per_token=hf.get("num_experts_per_tok") or 0,
                n_shared_experts=hf.get("n_shared_experts") or 0,
                moe_intermediate=hf.get("moe_intermediate_size") or 0,
                first_dense_layers=hf.get("first_k_dense_replace", 0),
                norm_topk_prob=hf.get("norm_topk_prob", False),
                routed_scaling_factor=float(hf.get("routed_scaling_factor", 1.0)),
            )
        elif kind == "lfm2_moe":
            require({
                "conv_bias": False, "use_expert_bias": True, "tie_embedding": True, "hidden_act": "silu",
                "attention_bias": False,
            })
            if (hf.get("rope_parameters") or {}).get("rope_type", "default") != "default":
                raise ValueError(f"lfm2_moe with rope_parameters={hf['rope_parameters']!r}: only 'default' is implemented")
            layer_types = tuple(hf["layer_types"])
            if set(layer_types) - {"conv", "full_attention"} or len(layer_types) != hf["num_hidden_layers"]:
                raise ValueError(f"lfm2_moe with layer_types={hf['layer_types']!r}: a kind a layer, conv or full_attention")
            common.update(
                rms_eps=hf["norm_eps"],
                head_size=hf.get("head_dim") or 0,
                # this family's "full_attention" turns by position: the kind is the model's, not the string's
                layer_types=tuple("conv" if t == "conv" else "gqa" for t in layer_types),
                conv_taps=hf["conv_L_cache"],
                qk_norm=True,
                tie_embeddings=True,
                n_routed_experts=hf["num_experts"],
                experts_per_token=hf["num_experts_per_tok"],
                moe_intermediate=hf["moe_intermediate_size"],
                first_dense_layers=hf["num_dense_layers"],
                norm_topk_prob=hf.get("norm_topk_prob", False),
                routed_scaling_factor=float(hf.get("routed_scaling_factor", 1.0)),
                router="sigmoid",
                router_bias=True,
            )
        elif kind == "mellum":
            require({
                "attention_bias": False, "hidden_act": "silu", "tie_word_embeddings": False,
                "use_sliding_window": True,
            })
            if set(hf["mlp_layer_types"][: hf["num_hidden_layers"]]) != {"sparse"}:
                raise ValueError(f"mellum with mlp_layer_types={hf['mlp_layer_types']!r}: only 'sparse' is implemented")
            layer_types = tuple(hf["layer_types"][: hf["num_hidden_layers"]])
            if set(layer_types) - {"sliding_attention", "full_attention"} or len(layer_types) != hf["num_hidden_layers"]:
                raise ValueError(f"mellum with layer_types={hf['layer_types']!r}: a kind a layer, sliding or full")
            sections = hf["rope_parameters"]
            full, sliding = sections.get("full_attention") or {}, sections.get("sliding_attention") or {}
            if full.get("rope_type") != "yarn" or sliding.get("rope_type", "default") != "default":
                raise ValueError(
                    f"mellum with rope_parameters={sections!r}: only a 'yarn' full_attention section "
                    "beside a 'default' sliding_attention section is implemented"
                )
            yarn = YarnScaling(
                factor=full["factor"], original_max_len=full["original_max_position_embeddings"],
                beta_fast=full.get("beta_fast", 32), beta_slow=full.get("beta_slow", 1),
            )
            common.update(
                rope_theta=float(sliding["rope_theta"]),
                head_size=hf["head_dim"],
                layer_types=layer_types,
                sliding_window=hf["sliding_window"],
                layer_rope=(("full", LayerRope(
                    float(full["rope_theta"]), yarn, float(full.get("attention_factor") or yarn_mscale(yarn.factor, 1.0)),
                )),),
                n_routed_experts=hf["num_experts"],
                experts_per_token=hf["num_experts_per_tok"],
                moe_intermediate=hf["moe_intermediate_size"],
                norm_topk_prob=hf.get("norm_topk_prob", False),
            )
        elif kind not in ("mistral", "llama"):
            raise ValueError(f"no decoder layer for model_type {kind!r}")
        common.update(overrides)
        return cls(**common)


def mistral_7b() -> DecoderConfig:
    return DecoderConfig()


def tiny_decoder(vocab_size: int = 512) -> DecoderConfig:
    """Small config for tests/dry runs."""
    return DecoderConfig(
        vocab_size=vocab_size,
        hidden=64,
        layers=2,
        heads=4,
        kv_heads=2,
        intermediate=128,
        max_len=128,
    )


def tiny_latent_moe_decoder(vocab_size: int = 512) -> DecoderConfig:
    """The latent-attention, routed-experts layer pattern at a size for
    tests: one dense layer, then two expert layers."""
    return DecoderConfig(
        vocab_size=vocab_size,
        hidden=64,
        layers=3,
        heads=4,
        kv_heads=4,
        intermediate=160,
        max_len=4096,
        rms_eps=1e-6,
        attention="mla",
        kv_lora_rank=16,
        qk_nope_head_dim=8,
        qk_rope_head_dim=4,
        v_head_dim=8,
        rope_scaling=YarnScaling(40.0, 128, 32.0, 1.0, 0.707, 0.707),
        n_routed_experts=8,
        experts_per_token=2,
        n_shared_experts=1,
        moe_intermediate=32,
        first_dense_layers=1,
    )


def tiny_hybrid_moe_decoder(vocab_size: int = 512) -> DecoderConfig:
    """The gated-short-convolution layer pattern at a size for tests: a
    convolution over a dense layer, then one period of attention (rotating,
    a norm a head) and three convolutions over routed experts that a biased
    sigmoid router picks, no shared expert, the head tied."""
    return DecoderConfig(
        vocab_size=vocab_size,
        hidden=64,
        layers=5,
        heads=4,
        kv_heads=2,
        intermediate=160,
        max_len=4096,
        rope_theta=1000000.0,
        layer_types=("conv", "gqa", "conv", "conv", "conv"),
        conv_taps=3,
        qk_norm=True,
        tie_embeddings=True,
        n_routed_experts=8,
        experts_per_token=2,
        moe_intermediate=32,
        first_dense_layers=1,
        norm_topk_prob=True,
        router="sigmoid",
        router_bias=True,
    )


# -- parameters ---------------------------------------------------------------


def _layer_shapes(cfg: DecoderConfig, kind: str, operator: str) -> dict[str, tuple]:
    """Matrix shapes of one layer, by parameter name (norms apart): its
    feed-forward ``kind`` and its ``operator`` (``attention_pattern``)."""
    h = cfg.hidden
    if operator == "conv":
        shapes = _conv_shapes(cfg)
    elif cfg.attention == "mla":
        shapes = {
            "q_w": (h, cfg.heads * (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)),
            "kva_w": (h, cfg.cache_width),
            "kvb_w": (cfg.kv_lora_rank, cfg.heads * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
            "o_w": (cfg.heads * cfg.v_head_dim, h),
        }
    else:
        shapes = {
            "q_w": (h, cfg.heads * cfg.head_dim),
            "kv_w": (h, 2 * cfg.kv_heads * cfg.head_dim),
            "o_w": (cfg.heads * cfg.head_dim, h),
        }
    if kind == "dense":
        shapes.update(gate_w=(h, 2 * cfg.intermediate), down_w=(cfg.intermediate, h))
    else:
        e, w = cfg.n_routed_experts, cfg.moe_intermediate
        held, shared = cfg.experts_held, cfg.n_shared_experts * w
        shapes.update(router_w=(h, e), experts_gate_w=(held, h, 2 * w), experts_down_w=(held, w, h))
        if shared:
            shapes.update(shared_gate_w=(h, 2 * shared), shared_down_w=(shared, h))
    return shapes


def init_decoder_params(
    rng: jax.Array, cfg: DecoderConfig, dtype: Any = jnp.float32
) -> Params:
    """``dtype=jnp.bfloat16`` stores weights half-size (7B fits a single
    16 GB chip); each tensor is drawn in f32 and cast immediately, so the
    f32 peak is one tensor, not the model. A matrix is scaled by the width
    it contracts over (its last but one axis; a filter ``conv_w`` by its
    taps). A router's ``expert_bias`` is drawn a twentieth wide: a buffer
    that moves some choices, as a trained one does."""

    def dense(key, shape, contracts=-2):
        scale = 1.0 / math.sqrt(shape[contracts])
        return (scale * jax.random.normal(key, shape, jnp.float32)).astype(dtype)

    pattern = list(zip(cfg.layer_pattern, cfg.attention_pattern))
    n_keys = 2 + sum(len(_layer_shapes(cfg, *kinds)) + (kinds[0] == "experts" and cfg.router_bias) for kinds in pattern)
    keys = iter(jax.random.split(rng, n_keys))
    p: Params = {
        "tok_emb": (
            0.02
            * jax.random.normal(
                next(keys), (cfg.vocab_size, cfg.hidden), jnp.float32
            )
        ).astype(dtype),
        "final_norm": jnp.ones((cfg.hidden,), jnp.float32),
        "layers": [],
    }
    head_key = next(keys)
    if not cfg.tie_embeddings:
        p["lm_head"] = dense(head_key, (cfg.hidden, cfg.vocab_size))
    for kind, operator in pattern:
        lp = {
            name: dense(next(keys), shape, -1 if name == "conv_w" else -2)
            for name, shape in _layer_shapes(cfg, kind, operator).items()
        }
        lp["attn_norm"] = jnp.ones((cfg.hidden,), jnp.float32)
        if not cfg.parallel_block:
            lp["mlp_norm"] = jnp.ones((cfg.hidden,), jnp.float32)
        if cfg.attention == "mla":
            lp["kv_norm"] = jnp.ones((cfg.kv_lora_rank,), jnp.float32)
        if cfg.qk_norm and operator != "conv":
            lp["q_norm"] = jnp.ones((cfg.head_dim,), jnp.float32)
            lp["k_norm"] = jnp.ones((cfg.head_dim,), jnp.float32)
        if kind == "experts" and cfg.router_bias:
            lp["expert_bias"] = 0.05 * jax.random.normal(next(keys), (cfg.n_routed_experts,), jnp.float32)
        p["layers"].append(lp)
    return p


def decoder_param_spec(path: tuple, leaf: Any) -> P:
    name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
    if name in ("q_w", "kv_w", "kvb_w", "gate_w", "shared_gate_w", "conv_in_w"):
        return P(None, MODEL_AXIS)
    if name in ("o_w", "down_w", "shared_down_w", "conv_w"):
        return P(MODEL_AXIS, None)
    if name in ("experts_gate_w", "experts_down_w"):
        return P(EXPERT_AXIS, None, None)
    if name in ("tok_emb",):
        return P(MODEL_AXIS, None)
    if name in ("lm_head",):
        return P(None, MODEL_AXIS)
    return P()


# -- pieces -------------------------------------------------------------------


def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    x32 = x.astype(jnp.float32)
    out = x32 * lax.rsqrt((x32 * x32).mean(-1, keepdims=True) + eps)
    return (out * scale).astype(x.dtype)


def layer_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    """Mean-subtracting LayerNorm in float32, weight only."""
    x32 = x.astype(jnp.float32)
    centred = x32 - x32.mean(-1, keepdims=True)
    out = centred * lax.rsqrt((centred * centred).mean(-1, keepdims=True) + eps)
    return (out * scale).astype(x.dtype)


def _norm(x: jax.Array, scale: jax.Array, cfg: DecoderConfig) -> jax.Array:
    return (layer_norm if cfg.norm == "layer" else rms_norm)(x, scale, cfg.rms_eps)


def rope_frequencies(dim: int, theta: float, yarn: YarnScaling | None = None) -> np.ndarray:
    """The ``dim / 2`` rotary frequencies. With YaRN each is a blend of the
    plain frequency and that frequency over ``factor``, by a linear ramp
    between the two correction dimensions (the dimensions that turn
    ``beta_fast`` and ``beta_slow`` times over the original length)."""
    plain = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if yarn is None:
        return plain.astype(np.float32)

    def correction_dim(rotations: float) -> float:
        return dim * math.log(yarn.original_max_len / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(yarn.beta_fast)), 0)
    high = min(math.ceil(correction_dim(yarn.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / (high - low), 0.0, 1.0)
    return (plain / yarn.factor * ramp + plain * (1.0 - ramp)).astype(np.float32)


def _rope_table_scale(cfg: DecoderConfig) -> float:
    yarn = cfg.rope_scaling
    if yarn is None:
        return 1.0
    return yarn_mscale(yarn.factor, yarn.mscale) / yarn_mscale(yarn.factor, yarn.mscale_all_dim)


def rope(
    x: jax.Array, positions: jax.Array, theta: float,
    yarn: YarnScaling | None = None, table_scale: float = 1.0, interleaved: bool = False,
) -> jax.Array:
    """Rotary embedding of x ``[b, t, h, d]`` at positions ``[b, t]``: pair
    ``i`` is ``(x[i], x[i + d/2])`` in the half-split layout (``x1 | x2``),
    ``(x[2i], x[2i + 1])`` in the ``interleaved`` one."""
    freqs = jnp.asarray(rope_frequencies(x.shape[-1], theta, yarn))
    angles = positions[..., None].astype(jnp.float32) * freqs  # [b, t, d/2]
    cos = (jnp.cos(angles) * table_scale)[:, :, None, :]
    sin = (jnp.sin(angles) * table_scale)[:, :, None, :]
    x32 = x.astype(jnp.float32)
    if interleaved:
        x1, x2 = x32[..., 0::2], x32[..., 1::2]
        out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1).reshape(x.shape)
    else:
        x1, x2 = jnp.split(x32, 2, axis=-1)
        out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def _gated_mlp(h: jax.Array, gate_w: jax.Array, down_w: jax.Array) -> jax.Array:
    gate, up = jnp.split(h @ gate_w.astype(h.dtype), 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ down_w.astype(h.dtype)


def _experts_layer(h: jax.Array, lp: Params, cfg: DecoderConfig, counted: jax.Array | None):
    """Routed plus shared experts over ``h`` ``[b, t, hidden]``; also how
    many of the ``counted`` ``[b, t]`` tokens' choices each expert held here
    took (``None``: every token's), how many of them took any, and the sorted
    rows the grouped products were handed for them. The other tokens are
    padding and go through the shared experts alone, and where the call is
    walked in blocks (``ops.moe.in_blocks``: a prefill) a row of the batch
    that holds none but padding goes through none either: its result is
    zero. The shared experts (where the layer has any) are one
    gated MLP as wide as all of them, which is their sum; an average is that
    over their number."""
    b, t, hidden = h.shape
    flat = h.reshape(b * t, hidden)
    weights, experts = route_top_k(
        flat, lp["router_w"], cfg.experts_per_token,
        renormalize=cfg.norm_topk_prob, scale=cfg.routed_scaling_factor, scoring=cfg.router,
        select_bias=lp["expert_bias"] if cfg.router_bias else None,
    )
    y, load = routed_experts(
        flat, weights, experts, lp["experts_gate_w"], lp["experts_down_w"],
        None if counted is None else counted.reshape(-1), cfg.held_experts,
    )
    if cfg.n_shared_experts:
        def shared(rows: jax.Array) -> jax.Array:
            return _gated_mlp(rows, lp["shared_gate_w"], lp["shared_down_w"])

        if counted is None or not in_blocks(experts.size):
            shared_y = shared(flat)
        else:
            shared_y = lax.map(lambda row: lax.cond(row[1].any(), shared, jnp.zeros_like, row[0]), (h, counted))
            shared_y = shared_y.reshape(b * t, hidden)
        y = y + (shared_y / cfg.n_shared_experts if cfg.shared_combine == "average" else shared_y)
    walked = rows_walked(load, experts.size)
    return y.reshape(b, t, hidden), load, jnp.count_nonzero(load).astype(jnp.int32), walked


# -- cache --------------------------------------------------------------------


class Cache(NamedTuple):
    """Static-shape cache, one state a layer: ``{"k", "v"}`` ``[b, slots,
    kv_heads, head_dim]`` for a ``"gqa"`` layer (``max_len`` slots; a
    ``"sliding"`` layer ``min(sliding_window, max_len)``, as a ring: position
    ``p`` lies in slot ``p mod slots``), ``{"latent"}`` ``[b, max_len,
    kv_lora_rank + qk_rope_head_dim]`` for an ``"mla"`` layer, ``{"conv"}``
    ``[b, hidden, conv_taps]`` for a ``"conv"`` layer: the inputs of its
    filter at the row's last ``conv_taps`` positions, a size that does not
    follow ``max_len``.

    ``valid`` marks usable positions: left-pad positions of shorter prompts in a
    batch stay False forever, so generated tokens never attend to pads.
    """

    layers: list
    length: jax.Array  # [] int32 — filled prefix
    valid: jax.Array  # [b, max_len] bool — non-pad filled positions


def init_cache(cfg: DecoderConfig, batch: int, max_len: int) -> Cache:
    def state(kind: str) -> dict:
        if kind == "conv":
            return _conv_state(cfg, batch)
        if kind == "mla":
            return {"latent": jnp.zeros((batch, max_len, cfg.cache_width), cfg.dtype)}
        slots = min(cfg.sliding_window, max_len) if kind == "sliding" else max_len
        shape = (batch, slots, cfg.kv_heads, cfg.head_dim)
        return {"k": jnp.zeros(shape, cfg.dtype), "v": jnp.zeros(shape, cfg.dtype)}

    return Cache(
        layers=[state(kind) for kind in cfg.attention_pattern],
        length=jnp.zeros((), jnp.int32),
        valid=jnp.zeros((batch, max_len), bool),
    )


def _write(buffer: jax.Array, chunk: jax.Array, start: jax.Array) -> jax.Array:
    """``chunk`` ``[b, t, ...]`` into ``buffer`` ``[b, max_len, ...]`` at slots
    ``[start, start + t)``."""
    at = (jnp.zeros((), jnp.int32), start) + (jnp.zeros((), jnp.int32),) * (buffer.ndim - 2)
    return lax.dynamic_update_slice(buffer, chunk.astype(buffer.dtype), at)


def _ring_write(buffer: jax.Array, chunk: jax.Array, start: jax.Array, wraps: bool) -> jax.Array:
    """``chunk`` ``[b, t, ...]``, which holds positions ``[start, start + t)``,
    into the ring ``buffer`` ``[b, slots, ...]``: position ``p`` into slot ``p
    mod slots``; of a chunk longer than the ring its last ``slots`` positions.
    A ring that never ``wraps`` (as long as the positions there are) is
    written as any buffer is."""
    slots, t = buffer.shape[1], chunk.shape[1]
    if not wraps:
        return _write(buffer, chunk, start)
    if t == 1:
        return _write(buffer, chunk, start % slots)
    kept = min(t, slots)
    at = (start + (t - kept) + jnp.arange(kept, dtype=jnp.int32)) % slots
    return buffer.at[:, at].set(chunk[:, t - kept :].astype(buffer.dtype))


def _ring_positions(slots: int, last: jax.Array) -> jax.Array:
    """The position each slot of a ring holds once position ``last`` is
    written: the newest ``p <= last`` with ``p mod slots`` its slot; negative
    where nothing was written yet."""
    slot = jnp.arange(slots, dtype=jnp.int32)
    return last - (last - slot) % slots


# -- attention ----------------------------------------------------------------


def _mask(q_slot: jax.Array, k_valid: jax.Array, k_slot: jax.Array | None = None, window: int = 0) -> jax.Array:
    """``[b, t, s]``: causal by slot, only slots that hold a real token, and
    of a ``window`` the keys under it back. ``k_slot`` ``[s]`` where the keys
    do not lie in slot order (a ring)."""
    if k_slot is None:
        k_slot = jnp.arange(k_valid.shape[1])
    back = q_slot[:, :, None] - k_slot[None, None, :]
    seen = (back >= 0) & k_valid[:, None, :]
    return seen & (back < window) if window else seen


def _softmax(scores: jax.Array, mask: jax.Array, dtype: Any) -> jax.Array:
    """Masked softmax in float32; ``scores`` ``[b, ..., t, s]`` with the heads
    between, ``mask`` ``[b, t, s]``."""
    mask = mask.reshape(mask.shape[:1] + (1,) * (scores.ndim - 3) + mask.shape[1:])
    return jax.nn.softmax(jnp.where(mask, scores.astype(jnp.float32), -1e30), axis=-1).astype(dtype)


#: grouped-query attention walks its queries in blocks whose scores (a batch's,
#: every head's, against every key) number at most this
ATTENTION_BLOCK_SCORES = 1 << 27


def _grouped_query(q, k, v, q_slot, k_valid, k_slot, window, cfg):
    """Softmax attention of ``q`` ``[b, t, heads, d]`` over ``k``, ``v`` ``[b, s,
    kv_heads, d]``, ``heads / kv_heads`` query heads a key head: ``[b, t, heads
    * d]``. The queries are walked in blocks, so that the scores held at
    once are a block's.

    This masked product evaluates every (query, key) score; a chunk over its
    own keys takes ``_walked_attention`` instead. It stays where the keys are
    the cache's: a decode step (one query: a row's keys are one tile, and
    nothing is left to skip), a chunk after a cache whose ring has wrapped
    (``k_slot``: the keys do not lie in slot order, so no tile bound is a
    range of slots), and latent attention (``_mla_attention``, which keeps
    its own product)."""
    b, t, heads, d = q.shape
    s, kv = k.shape[1], k.shape[2]

    def block(args):
        q, q_slot = args
        qg = q.reshape(b, -1, kv, heads // kv, d)
        scores = jnp.einsum("btkgd,bskd->bkgts", qg, k) * cfg.softmax_scale
        probs = _softmax(scores, _mask(q_slot, k_valid, k_slot, window), v.dtype)
        return jnp.einsum("bkgts,bskd->btkgd", probs, v).reshape(b, -1, heads * d)

    rows = t
    while b * heads * rows * s > ATTENTION_BLOCK_SCORES and rows % 2 == 0:
        rows //= 2
    if rows == t:
        return block((q, q_slot))
    split = lambda a: jnp.moveaxis(a.reshape((b, t // rows, rows) + a.shape[2:]), 1, 0)  # noqa: E731
    out = lax.map(block, (split(q), split(q_slot)))
    return jnp.moveaxis(out, 0, 1).reshape(b, t, heads * d)


#: the side of the square tiles a chunk over its own keys is walked in: at the
#: answerers' buckets (768 to 2,560 slots) and head widths (64 and 128) the
#: kernel ran fastest at 256 of 128, 256 and 512, but at two shapes where 512
#: was as fast within 0.05 ms (``tools/prefill_attention_sweep.py``)
PREFILL_TILE = 256


def prefill_tile(t: int) -> int:
    """The tile a chunk of ``t`` slots is walked in: ``PREFILL_TILE``, or the
    whole chunk where it is shorter."""
    return min(t, PREFILL_TILE)


def prefill_attention_tiles(first, t: int, tile: int, window: int):
    """Which key tiles each query tile of a chunk visits: the one bound the
    walk (``_walked_attention``) and its counts (``prefill_window_scores``,
    ``prefill_attention_scores``) share, on the device or on the host alike.

    ``first`` ``[b]`` is each row's first real slot (rows are left-padded:
    ``t`` less its real tokens; ``t`` for a row of padding). The chunk's
    ``t`` slots are cut into ``ceil(t / tile)`` tiles of ``tile``; query
    tile ``i`` visits key tiles ``lo[r, i]`` to ``lo[r, i] + count[r, i] -
    1`` (``[b, tiles]`` each), and only those can hold a score one of its
    real queries needs: none before the row's first real slot (padding),
    none after the query tile (causal), and with a ``window`` none wholly
    more than ``window - 1`` slots behind the tile's first real query. A
    query tile that holds only padding visits nothing."""
    xp = jnp if isinstance(first, jax.Array) else np
    tiles = xp.arange(-(-t // tile))
    first = first[:, None]
    last_query = xp.minimum((tiles + 1) * tile, t) - 1
    first_query = xp.maximum(tiles * tile, first)  # of the tile's real queries
    from_slot = xp.maximum(first, first_query - (window - 1)) if window else xp.broadcast_to(first, first_query.shape)
    lo = from_slot // tile
    count = xp.where(first <= last_query, tiles - lo + 1, 0)
    return lo, count


def _walked_pairs(first, t: int, tile: int, window: int) -> int:
    """The (query, key) scores the walk evaluates a head, over rows whose
    first real slot is ``first``: ``tile x tile`` a key tile visited."""
    _, count = prefill_attention_tiles(np.asarray(first, np.int64), t, tile, window)
    return int(count.sum()) * tile * tile


def prefill_window_scores(cfg: DecoderConfig, width: int, lengths) -> tuple[int, int, int]:
    """Over the ``"sliding"`` layers, the (query, key) scores a prefill of
    rows of ``lengths`` real tokens, left-padded to ``width``, evaluates for
    those rows, the causal pairs of their real tokens, and the scores their
    windows need. The walk evaluates a visited key tile's every score
    (``prefill_attention_tiles``); a row's real token ``i`` (from 0) is
    causal with ``i + 1`` keys and needs ``min(i + 1, sliding_window)``."""
    layers, window = cfg.attention_pattern.count("sliding"), cfg.sliding_window
    if not layers:
        return 0, 0, 0

    def needed(n: int) -> int:
        inside = min(n, window)
        return inside * (inside + 1) // 2 + (n - inside) * window

    first = [width - n for n in lengths]
    walked = layers * _walked_pairs(first, width, prefill_tile(width), window)
    return walked, layers * sum(n * (n + 1) // 2 for n in lengths), layers * sum(needed(n) for n in lengths)


def prefill_attention_scores(cfg: DecoderConfig, width: int, lengths, rows: int) -> tuple[int, int]:
    """Over every grouped-query layer and every head, for a prefill of
    ``rows`` rows of ``width`` slots of which the first ``len(lengths)``
    hold ``lengths`` real tokens (the rest are padding): the scores the walk
    evaluates, and those the masked product evaluated (``rows x heads x
    width²`` a layer). Latent attention and the ``"conv"`` operator count
    nothing."""
    kinds = [kind for kind in cfg.attention_pattern if kind not in ("mla", "conv")]
    first = [width - n for n in lengths] + [width] * (rows - len(lengths))
    tile = prefill_tile(width)
    walked = sum(
        _walked_pairs(first, width, tile, cfg.sliding_window if kind == "sliding" else 0) for kind in kinds
    )
    return cfg.heads * walked, len(kinds) * rows * cfg.heads * width * width


def _walked_attention(q, k, v, k_valid, window, scale, tile):
    """Softmax attention of a chunk over its own keys, ``q`` ``[b, t, heads,
    d]`` over ``k``, ``v`` ``[b, t, kv_heads, d]`` in slot order, walked in
    tiles of ``tile`` queries by ``tile`` keys: each (row, query tile) visits
    the key tiles ``prefill_attention_tiles`` gives it
    (``ops.prefill_attention.walked_attention``, one kernel), under a float32
    online softmax and ``_mask``'s mask inside a tile. A skipped tile holds
    only scores the masked product sets to ``-1e30``, which give 0 in
    float32: the same sums in another order. A query tile that visits
    nothing gives zeros: a padding position's output is a key and a value in
    the cache, never NaN. ``[b, t, heads * d]``."""
    t = q.shape[1]
    first = jnp.where(k_valid.any(1), jnp.argmax(k_valid, axis=1), t).astype(jnp.int32)
    lo, count = prefill_attention_tiles(first, t, tile, window)
    return walked_attention(q, k, v, k_valid, lo, count, window=window, scale=scale, tile=tile)


def _gqa_attention(h, lp, cfg, state, start, q_slot, q_pos, k_valid, chunk_only, kind="gqa", wraps=False):
    """Grouped-query attention over ``h`` ``[b, t, hidden]``; ``kind``
    ``"sliding"`` sees a window back, and each kind turns by position as
    ``rope_of`` says. ``k_valid``
    is the chunk's own where it is ``chunk_only``, else every position's
    (``[b, max_len]``). A state that ``wraps`` is a ring shorter than the
    positions the cache counts."""
    b, t, _ = h.shape
    q = (h @ lp["q_w"].astype(cfg.dtype)).reshape(b, t, cfg.heads, cfg.head_dim)
    k, v = jnp.split(h @ lp["kv_w"].astype(cfg.dtype), 2, axis=-1)
    k = k.reshape(b, t, cfg.kv_heads, cfg.head_dim)
    v = v.reshape(b, t, cfg.kv_heads, cfg.head_dim)
    if cfg.qk_norm:  # over each head's own values, before the rotation
        q, k = rms_norm(q, lp["q_norm"], cfg.rms_eps), rms_norm(k, lp["k_norm"], cfg.rms_eps)
    turn = cfg.rope_of(kind)
    if turn is not None:
        q = rope(q, q_pos, turn.theta, turn.yarn, turn.scale, interleaved=cfg.rope_interleaved)
        k = rope(k, q_pos, turn.theta, turn.yarn, turn.scale, interleaved=cfg.rope_interleaved)
    window = cfg.sliding_window if kind == "sliding" else 0
    k_slot = None
    if state is not None:
        slots = state["k"].shape[1]
        if wraps and t > 1 and not chunk_only:
            raise NotImplementedError(
                f"a chunk of {t} tokens into a ring of {slots} slots that wraps: prefill a prompt whole"
            )
        state = {"k": _ring_write(state["k"], k, start, wraps), "v": _ring_write(state["v"], v, start, wraps)}
        if not chunk_only:
            k, v = state["k"], state["v"]
            if wraps:  # what each slot holds now, and whether that is a real token
                k_slot = _ring_positions(slots, start)
                k_valid = jnp.take(k_valid, jnp.maximum(k_slot, 0), axis=1) & (k_slot >= 0)
    if t > 1 and (state is None or chunk_only):  # the keys are the chunk's own, in slot order
        return _walked_attention(q, k, v, k_valid, window, cfg.softmax_scale, prefill_tile(t)), state
    out = _grouped_query(q, k, v, q_slot, k_valid, k_slot, window, cfg)
    return out, state


def _mla_attention(h, lp, cfg, state, start, q_slot, q_pos, k_valid, chunk_only):
    """Latent attention over ``h`` ``[b, t, hidden]``. The layer's state is
    the latent cache; with ``chunk_only`` (a prompt into an empty cache) the
    keys are the chunk's own rows."""
    b, t, _ = h.shape
    nope, rot, vd, rank = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank
    yarn, table = cfg.rope_scaling, _rope_table_scale(cfg)
    q = (h @ lp["q_w"].astype(cfg.dtype)).reshape(b, t, cfg.heads, nope + rot)
    q_nope, q_rope = q[..., :nope], rope(q[..., nope:], q_pos, cfg.rope_theta, yarn, table)
    kva = h @ lp["kva_w"].astype(cfg.dtype)
    c = rms_norm(kva[..., :rank], lp["kv_norm"], cfg.rms_eps)
    k_rope = rope(kva[..., None, rank:], q_pos, cfg.rope_theta, yarn, table)[:, :, 0]
    latent = jnp.concatenate([c, k_rope], axis=-1)  # [b, t, rank + rot]: what the cache keeps
    if state is not None:
        state = {"latent": _write(state["latent"], latent, start)}
        if not chunk_only:
            latent = state["latent"]
    kvb = lp["kvb_w"].astype(cfg.dtype).reshape(rank, cfg.heads, nope + vd)
    mask = _mask(q_slot, k_valid)
    if t == 1 and state is not None:
        # absorbed: the query goes into the latent space, the cache is read as it lies
        q_lat = jnp.einsum("bthn,rhn->bthr", q_nope, kvb[..., :nope])
        q_cat = jnp.concatenate([q_lat, q_rope], axis=-1)  # [b, 1, heads, rank + rot]
        scores = jnp.einsum("bthc,bsc->bhts", q_cat, latent) * cfg.softmax_scale
        probs = _softmax(scores, mask, latent.dtype)
        out_lat = jnp.einsum("bhts,bsr->bthr", probs, latent[..., :rank])
        out = jnp.einsum("bthr,rhv->bthv", out_lat, kvb[..., nope:])
    else:
        kv = jnp.einsum("bsr,rhd->bshd", latent[..., :rank], kvb)
        k_nope, v = kv[..., :nope], kv[..., nope:]
        scores = jnp.einsum("bthn,bshn->bhts", q_nope, k_nope)
        scores = scores + jnp.einsum("bthr,bsr->bhts", q_rope, latent[..., rank:])
        probs = _softmax(scores * cfg.softmax_scale, mask, v.dtype)
        out = jnp.einsum("bhts,bshv->bthv", probs, v)
    return out.reshape(b, t, cfg.heads * vd), state


# -- the gated short convolution ----------------------------------------------
# The kind in one place: its parameters' shapes, its cache state, its step.


def _conv_shapes(cfg: DecoderConfig) -> dict[str, tuple]:
    """``conv_in_w`` is ``B | C | u`` side by side, ``conv_w`` the filter, a
    row a channel and ``conv_w[:, -1]`` the tap on the newest position;
    ``o_w`` is the projection out, under attention's name for it."""
    h = cfg.hidden
    return {"conv_in_w": (h, 3 * h), "conv_w": (h, cfg.conv_taps), "o_w": (h, h)}


def _conv_state(cfg: DecoderConfig, batch: int) -> dict:
    return {"conv": jnp.zeros((batch, cfg.hidden, cfg.conv_taps), cfg.dtype)}


def _short_conv(h, lp, cfg, state, real):
    """The gated short convolution over ``h`` ``[b, t, hidden]``, a chunk or
    one token alike, before ``o_w``: ``C * c`` with ``c[i] = sum_j conv_w[:, j] *
    z[i - (taps - 1) + j]`` (float32 sums), ``z = B * u``. What lies before
    the chunk is the state, the row's last ``taps`` ``z`` (zeros in an empty
    cache and where there is none); the state that comes back is the last
    ``taps`` of both. ``z`` is zero at a position that is not ``real``
    ``[b, t]``, so padding on the left adds nothing to the first tokens'
    sums, to the bit: a row's state and what it is served do not follow
    how far it was padded."""
    taps, t = cfg.conv_taps, h.shape[1]
    gate_in, gate_out, u = jnp.split(h @ lp["conv_in_w"].astype(cfg.dtype), 3, axis=-1)
    z = gate_in * u
    if real is not None:
        z = jnp.where(real[:, :, None], z, 0)
    before = jnp.zeros((h.shape[0], taps, cfg.hidden), z.dtype) if state is None else state["conv"].transpose(0, 2, 1)
    seen = jnp.concatenate([before.astype(z.dtype), z], axis=1)  # [b, taps + t, hidden]: z[i] lies at taps + i
    filt = lp["conv_w"].astype(jnp.float32)
    c = sum(filt[:, j] * seen[:, 1 + j : 1 + j + t].astype(jnp.float32) for j in range(taps))
    if state is not None:
        state = {"conv": seen[:, t:].transpose(0, 2, 1).astype(state["conv"].dtype)}
    return gate_out * c.astype(cfg.dtype), state


# -- the layer stack ----------------------------------------------------------


class ExpertStats(NamedTuple):
    """What the expert layers of one forward pass took: ``load`` ``[expert
    layers, experts held here]`` int32, the choices of the real tokens each
    expert got; ``touched`` ``[]`` int32, over the expert layers the experts
    a real token chose: those whose weights the pass had to read (padding
    takes no routed expert, nor does a choice of an expert held elsewhere);
    ``walked`` ``[]`` int32, over the expert layers the sorted (token,
    choice) rows the grouped products gathered and multiplied
    (``ops.moe.rows_walked``)."""

    load: jax.Array
    touched: jax.Array
    walked: jax.Array

    @staticmethod
    def none(cfg: DecoderConfig) -> "ExpertStats":
        n = sum(kind == "experts" for kind in cfg.layer_pattern)
        zero = jnp.zeros((), jnp.int32)
        return ExpertStats(jnp.zeros((n, max(cfg.experts_held, 1)), jnp.int32), zero, zero)

    def __add__(self, other: "ExpertStats") -> "ExpertStats":  # type: ignore[override]
        return ExpertStats(*(mine + theirs for mine, theirs in zip(self, other)))


def _stack(
    params: Params,
    token_ids: jax.Array,  # [b, t]
    cfg: DecoderConfig,
    cache: Cache | None,
    attn_mask: jax.Array | None,
    pos_offset: jax.Array | None,
    chunk_only: bool = False,
) -> tuple[jax.Array, Cache | None, ExpertStats]:
    """The layers over a chunk: hidden states ``[b, t, hidden]`` before the
    final norm, the cache with the chunk appended (a ``"conv"`` layer's state
    moved on by it), the experts' counts."""
    b, t = token_ids.shape
    x = params["tok_emb"][token_ids].astype(cfg.dtype)
    start = cache.length if cache is not None else jnp.zeros((), jnp.int32)
    # slot index (causal order) vs rotary position (logical, pad-corrected)
    q_slot = jnp.broadcast_to(start + jnp.arange(t, dtype=jnp.int32)[None, :], (b, t))
    if pos_offset is not None:
        q_pos = jnp.maximum(q_slot - pos_offset[:, None].astype(jnp.int32), 0)
    else:
        q_pos = q_slot
    real = attn_mask if attn_mask is not None else jnp.ones((b, t), bool)
    if cache is None:
        k_valid, valid_full = real, None
    else:
        valid_full = _write(cache.valid, real, start)
        k_valid = real if chunk_only else valid_full
        if chunk_only:
            q_slot = q_slot - start  # slots within the chunk
    states, loads, touched, walked = [], [], jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32)
    for i, (lp, kind, attention) in enumerate(zip(params["layers"], cfg.layer_pattern, cfg.attention_pattern)):
        h = _norm(x, lp["attn_norm"], cfg)
        state = cache.layers[i] if cache is not None else None
        if attention == "conv":
            a, state = _short_conv(h, lp, cfg, state, attn_mask)
        elif attention == "mla":
            a, state = _mla_attention(h, lp, cfg, state, start, q_slot, q_pos, k_valid, chunk_only)
        else:
            wraps = state is not None and state["k"].shape[1] < cache.valid.shape[1]
            a, state = _gqa_attention(h, lp, cfg, state, start, q_slot, q_pos, k_valid, chunk_only, attention, wraps)
        states.append(state)
        a = a @ lp["o_w"].astype(cfg.dtype)
        if not cfg.parallel_block:  # the feed-forward reads a norm of its own, over x + a
            x, a = x + a, None
            h = _norm(x, lp["mlp_norm"], cfg)
        if kind == "experts":
            y, load, n_touched, n_walked = _experts_layer(h, lp, cfg, attn_mask)
            loads.append(load)
            touched, walked = touched + n_touched, walked + n_walked
        else:
            y = _gated_mlp(h, lp["gate_w"], lp["down_w"])
        x = x + y if a is None else x + a + y
    stats = ExpertStats(jnp.stack(loads), touched, walked) if loads else ExpertStats.none(cfg)
    if cache is not None:
        cache = Cache(layers=states, length=start + t, valid=valid_full)
    return x, cache, stats


def _head(params: Params, x: jax.Array, cfg: DecoderConfig) -> jax.Array:
    x = _norm(x, params["final_norm"], cfg)
    if cfg.tie_embeddings:
        logits = jnp.einsum("...h,vh->...v", x, params["tok_emb"].astype(cfg.dtype))
        return logits.astype(jnp.float32) * cfg.logit_scale
    return (x @ params["lm_head"].astype(cfg.dtype)).astype(jnp.float32)


def decoder_forward(
    params: Params,
    token_ids: jax.Array,  # [b, t]
    cfg: DecoderConfig,
    cache: Cache | None = None,
    *,
    attn_mask: jax.Array | None = None,  # [b, t] True = real (non-pad) token
    pos_offset: jax.Array | None = None,  # [b] per-row left-pad count
) -> tuple[jax.Array, Cache | None]:
    """Logits ``[b, t, vocab]``; appends to ``cache`` when given.

    Without a cache this is plain causal training/scoring forward. With a
    cache, ``token_ids`` is the next chunk (often t=1) starting at
    ``cache.length``. Left-padded batches pass ``attn_mask`` (False on pads,
    which are excluded from attention forever) and ``pos_offset`` (pad count
    per row, subtracted from RoPE positions so token 0 of every prompt sits
    at rotary position 0).
    """
    x, cache, _ = _stack(params, token_ids, cfg, cache, attn_mask, pos_offset)
    return _head(params, x, cfg), cache


def prefill(
    params: Params,
    prompt_ids: jax.Array,  # [b, t] left-padded
    prompt_mask: jax.Array | None,  # [b, t] True = real token
    cfg: DecoderConfig,
    max_len: int,
) -> tuple[jax.Array, Cache, jax.Array, ExpertStats]:
    """A batch of prompts into an empty cache of ``max_len`` slots: the
    logits of each row's last position ``[b, vocab]`` (the head runs there
    and nowhere else), the cache, each row's pad count ``[b]`` and the
    experts' counts. The batch is walked in groups of rows of about
    ``PREFILL_BLOCK_TOKENS`` tokens."""
    b, t = prompt_ids.shape
    if prompt_mask is None:
        prompt_mask = jnp.ones((b, t), bool)
    pos_offset = t - prompt_mask.sum(axis=1).astype(jnp.int32)
    rows = max(1, min(b, PREFILL_BLOCK_TOKENS // t))
    while b % rows:
        rows -= 1

    def group(args):
        ids, mask, offset = args
        x, cache, stats = _stack(
            params, ids, cfg, init_cache(cfg, rows, max_len), mask, offset, chunk_only=True
        )
        return _head(params, x[:, -1], cfg), cache.layers, cache.valid, stats

    split = lambda a: a.reshape((b // rows, rows) + a.shape[1:])  # noqa: E731
    logits, layers, valid, stats = lax.map(group, (split(prompt_ids), split(prompt_mask), split(pos_offset)))
    join = lambda a: a.reshape((b,) + a.shape[2:])  # noqa: E731
    cache = Cache(jax.tree.map(join, layers), jnp.asarray(t, jnp.int32), join(valid))
    return join(logits), cache, pos_offset, jax.tree.map(lambda a: a.sum(0), stats)


def decode_step(
    params: Params,
    tokens: jax.Array,  # [b] the tokens chosen last
    cache: Cache,
    pos_offset: jax.Array,  # [b]
    cfg: DecoderConfig,
    real: jax.Array | None = None,  # [b] False: a row of padding, never valid nor counted
) -> tuple[jax.Array, Cache, ExpertStats]:
    """One token a row through the cache: the next logits ``[b, vocab]``."""
    mask = None if real is None else real[:, None]
    x, cache, stats = _stack(params, tokens[:, None], cfg, cache, mask, pos_offset)
    return _head(params, x[:, 0], cfg), cache, stats


class Generation(NamedTuple):
    tokens: jax.Array  # [b, new] int32
    logits: jax.Array  # [b, new] float32: the logit of each token chosen
    prefill_stats: ExpertStats
    decode_stats: ExpertStats


def _generate_loop(
    params: Params,
    prompt_ids: jax.Array,
    cfg: DecoderConfig,
    max_new_tokens: int,
    eos_id: int | None,
    prompt_mask: jax.Array | None,
    choose,
) -> Generation:
    """Shared decode scaffold: prompt prefill, per-step cache decode,
    EOS padding. ``choose(logits [b, vocab], step_no) -> [b] int32`` picks
    each next token (argmax for greedy, filtered categorical for
    sampling).

    ``prompt_mask`` handles left-padded batches of unequal-length prompts:
    pad slots are never attended to and RoPE positions are shifted so every
    prompt starts at rotary position 0 (ADVICE r1). Tokens after EOS are
    padded with ``eos_id``.
    """
    t_prompt = prompt_ids.shape[1]
    logits, cache, pos_offset, prefill_stats = prefill(
        params, prompt_ids, prompt_mask, cfg, t_prompt + max_new_tokens
    )
    first = choose(logits, 0)
    first_logit = logit_of(logits, first)
    tokens, chosen, decode_stats = decode_loop(
        params, cache, first, pos_offset, cfg, max_new_tokens - 1, choose, eos_id
    )
    return Generation(
        jnp.concatenate([first[:, None], tokens], axis=1),
        jnp.concatenate([first_logit[:, None], chosen], axis=1),
        prefill_stats,
        decode_stats,
    )


def logit_of(logits: jax.Array, tokens: jax.Array) -> jax.Array:
    """``logits[row, tokens[row]]``: the logit of the token each row chose."""
    return jnp.take_along_axis(logits, tokens[:, None], axis=1)[:, 0]


def decode_loop(
    params: Params,
    cache: Cache,
    first: jax.Array,  # [b] the token prefill chose
    pos_offset: jax.Array,
    cfg: DecoderConfig,
    steps: int,
    choose,
    eos_id: int | None = None,
    real: jax.Array | None = None,  # [b] as decode_step takes it
) -> tuple[jax.Array, jax.Array, ExpertStats]:
    """``steps`` further tokens a row after ``first``: tokens and their
    logits, ``[b, steps]`` each, and the experts' counts over the steps."""

    def step(carry, step_no):
        cache, tok, done, stats = carry
        logits, cache, step_stats = decode_step(params, tok, cache, pos_offset, cfg, real)
        new_tok = choose(logits, step_no + 1)
        new_logit = logit_of(logits, new_tok)
        if eos_id is not None:
            done = done | (tok == eos_id)
            new_tok = jnp.where(done, eos_id, new_tok)
        return (cache, new_tok, done, stats + step_stats), (new_tok, new_logit)

    done = jnp.zeros(first.shape, bool)
    (_, _, _, stats), (tokens, chosen) = lax.scan(
        step, (cache, first, done, ExpertStats.none(cfg)), jnp.arange(steps)
    )
    return tokens.transpose(1, 0), chosen.transpose(1, 0), stats


def greedy(logits: jax.Array, _step: Any) -> jax.Array:
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def greedy_generate(
    params: Params,
    prompt_ids: jax.Array,  # [b, t_prompt]
    cfg: DecoderConfig,
    max_new_tokens: int,
    eos_id: int | None = None,
    prompt_mask: jax.Array | None = None,  # [b, t_prompt] True = real token
) -> jax.Array:
    """Greedy decode with a static-shape cache; returns ``[b, max_new]``."""
    return _generate_loop(
        params, prompt_ids, cfg, max_new_tokens, eos_id, prompt_mask, greedy
    ).tokens


def _filter_logits(
    logits: jax.Array, top_k: int | None, top_p: float | None
) -> jax.Array:
    """HF-style logit filtering: keep the top-k logits and/or the nucleus
    whose cumulative probability reaches top_p; everything else -> -inf."""
    if top_k is not None and 0 < top_k < logits.shape[-1]:
        kth = lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p is not None and top_p < 1.0:
        order = jnp.argsort(-logits, axis=-1)
        sorted_desc = jnp.take_along_axis(logits, order, axis=-1)
        probs = jax.nn.softmax(sorted_desc, axis=-1)
        cumulative = jnp.cumsum(probs, axis=-1)
        # keep tokens up to and including the one crossing top_p; the
        # exclusive-cumulative test against a positive threshold always
        # keeps the argmax (HF's min_tokens_to_keep=1) — clamp guards
        # top_p<=0, which would otherwise mask EVERY logit to -inf.
        # Keep flags map back through the inverse permutation (index-based
        # like HF, so boundary-logit TIES outside the nucleus are dropped
        # rather than kept by a value threshold).
        keep_sorted = (cumulative - probs) < max(top_p, 1e-9)
        inverse = jnp.argsort(order, axis=-1)
        keep = jnp.take_along_axis(keep_sorted, inverse, axis=-1)
        logits = jnp.where(keep, logits, -jnp.inf)
    return logits


def sample_generate(
    params: Params,
    prompt_ids: jax.Array,  # [b, t_prompt]
    cfg: DecoderConfig,
    max_new_tokens: int,
    row_seeds: jax.Array,  # [b] uint32 — per-row PRNG seeds
    *,
    temperature: float = 1.0,
    top_k: int | None = None,
    top_p: float | None = None,
    eos_id: int | None = None,
    prompt_mask: jax.Array | None = None,
) -> jax.Array:
    """Sampling decode (reference HFPipelineChat forwards do_sample/
    temperature/top_k/top_p to HF generate, llms.py:441): temperature
    scaling then top-k/top-p filtering then categorical sampling, with a
    per-ROW PRNG key folded per step — so each row's generation is a
    deterministic function of (params, its prompt, its seed), independent
    of how rows are batched (the engine's retraction consistency needs
    deterministic UDF outputs)."""
    keys = jax.vmap(jax.random.key)(row_seeds)
    inv_temp = 1.0 / max(temperature, 1e-6)

    def choose(logits: jax.Array, step_no: Any) -> jax.Array:
        step_keys = jax.vmap(jax.random.fold_in, (0, None))(keys, step_no)
        filtered = _filter_logits(logits * inv_temp, top_k, top_p)
        return jax.vmap(jax.random.categorical)(step_keys, filtered).astype(
            jnp.int32
        )

    return _generate_loop(
        params, prompt_ids, cfg, max_new_tokens, eos_id, prompt_mask, choose
    ).tokens
