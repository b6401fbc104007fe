"""The plain reference of Mellum2-12B-A2.5B's language model (``model_type``
``mellum``), as its published ``config.json`` gives it and ``transformers``
computes a sequential pre-norm block with two kinds of rotary attention.

It imports nothing of the program. The forward pass, written out in
``jax.numpy``: float32 under ``jax.default_matmul_precision("highest")``, one
sequence at a time, no cache (every position attends over the whole
sequence; the window is an explicit mask over absolute positions), no
kernels, no batching, every routed expert applied to every token through a
plain loop and weighted by the router's choice (zero where it was not
chosen). Attention walks its queries and the experts their tokens in
blocks, so that a sequence of the cell's length fits beside the index at the
published widths. The bfloat16 parameters the benchmark made from the seed
are upcast a matrix at a time.

A layer ``l`` of kind ``layer_types[l]``, over ``x`` ``[t, hidden]``, RMS norms
of ``rms_norm_eps``:

    h = rms(x) * g_attn
    q, k, v = h W_q, h W_k, h W_v                         32 | 4 | 4 heads of 128, no bias
    sliding_attention: q, k turned by position, split halves (rotate_half),
        theta of rope_parameters.sliding_attention (default RoPE);
        key j seen by query i iff 0 <= i - j < sliding_window
    full_attention: q, k turned by position with YaRN's frequencies
        (rope_parameters.full_attention: factor, original_max_position_embeddings,
        beta_fast, beta_slow), cos and sin times attention_factor; causal
    a = concat(softmax(q k^T / sqrt(128)) v) W_o
    x = x + a
    h = rms(x) * g_mlp
    p = softmax(h W_r)                    float32, over all num_experts
    the num_experts_per_tok largest (a tie to the lower id), w_k = p_k / sum of the chosen (norm_topk_prob)
    f = sum_k w_k E_k(h),  E(h) = W_2 (silu(W_1 h) * W_3 h)   at moe_intermediate_size
    x' = x + f

and the head: one more RMS norm, then ``x W_head`` (untied).

YaRN, as ``transformers``' ``_compute_yarn_parameters`` has it: the plain
frequencies ``theta^(-2i/d)``; the correction dimensions ``d ln(L / (2 pi
beta)) / (2 ln theta)`` of ``beta_fast`` (floored) and ``beta_slow``
(ceiled), ``L`` the original length; a linear ramp between them; a pair's
frequency is the plain one where the ramp is 0 and the plain one over
``factor`` where it is 1. At theta 500,000, d 128, factor 16 over 8,192:
pairs 0-18 plain, 35-63 over 16, 19-34 between.

Departures from the published description, each ``assumed`` in the
configuration: no per-head norm of the queries or keys (the config has no
key for one); no multi-token-prediction head (the config names none;
serving generates one token a step); the tokenizer is the hashing rule of
``reference.py`` over the vocabulary. The tree is laid out as the program's
``params=`` takes it: ``kv_w`` is ``W_k | W_v``, ``experts_gate_w`` is ``W_1 |
W_3`` and ``experts_down_w`` ``W_2``, ``lm_head`` ``[hidden, vocab]``: under
random weights a naming of columns, on both sides.

``forward`` takes the controls of the comparison (``control_mellum.py``):
``operand`` rounds the routed experts' products' operands (float8, the step
below bfloat16), and ``cut`` names a corner left out: ``"sliding_as_full"``
(the window's mask dropped), ``"full_without_yarn"`` (the full layer turned
by plain RoPE, its attention factor kept), ``"no_attention_factor"`` (YaRN's
frequencies kept, cos and sin not scaled).
"""

from __future__ import annotations

import functools
import math

import numpy as np

#: attention's queries and the experts' tokens are walked this many at a time
BLOCK = 256
#: the corners a control may cut
CUTS = ("sliding_as_full", "full_without_yarn", "no_attention_factor")


def head_dim(dec: dict) -> int:
    return dec.get("head_dim") or dec["hidden_size"] // dec["num_attention_heads"]


def layer_kinds(dec: dict) -> list[str]:
    """``"sliding"`` or ``"full"``, each layer that is run."""
    n = dec["num_hidden_layers"]
    types = list(dec["layer_types"][:n])
    if len(types) != n or set(types) - {"sliding_attention", "full_attention"}:
        raise ValueError(f"layer_types {dec['layer_types']!r} for {n} layers")
    if list(dec["mlp_layer_types"][:n]) != ["sparse"] * n:
        raise ValueError(f"mlp_layer_types {dec['mlp_layer_types']!r}: every layer is sparse")
    return [kind.removesuffix("_attention") for kind in types]


# -- weights ------------------------------------------------------------------


def _layer_shapes(dec: dict) -> dict:
    h, d = dec["hidden_size"], head_dim(dec)
    heads, kv, e, w = dec["num_attention_heads"], dec["num_key_value_heads"], dec["num_experts"], dec["moe_intermediate_size"]
    return {
        "q_w": (h, heads * d), "kv_w": (h, 2 * kv * d), "o_w": (heads * d, h),
        "router_w": (h, e), "experts_gate_w": (e, h, 2 * w), "experts_down_w": (e, w, h),
    }


def make_params(seed: int, dec: dict):
    """The bfloat16 parameters from the seed, a layer a jitted call on the
    device; the tree is the one the program's ``params=`` takes. A matrix is
    drawn in float32 with standard deviation ``1 / sqrt(rows it contracts
    over)``, the embedding 0.02, and rounded once; norms are ones."""
    import jax
    import jax.numpy as jnp

    h, vocab = dec["hidden_size"], dec["vocab_size"]

    def draw(key, shape, scale):
        return (scale * jax.random.normal(key, shape, jnp.float32)).astype(jnp.bfloat16)

    @jax.jit
    def make_layer(key):
        shapes = _layer_shapes(dec)
        keys = jax.random.split(key, len(shapes))
        lp = {name: draw(k, shape, 1.0 / math.sqrt(shape[-2])) for k, (name, shape) in zip(keys, shapes.items())}
        lp["attn_norm"], lp["mlp_norm"] = jnp.ones((h,), jnp.float32), jnp.ones((h,), jnp.float32)
        return lp

    root = jax.random.fold_in(jax.random.key(seed % (1 << 63)), 0x3E11)
    return {
        "tok_emb": jax.jit(lambda key: draw(key, (vocab, h), 0.02))(jax.random.fold_in(root, 0)),
        "lm_head": jax.jit(lambda key: draw(key, (h, vocab), 1.0 / math.sqrt(h)))(jax.random.fold_in(root, 1)),
        "final_norm": jnp.ones((h,), jnp.float32),
        "layers": [make_layer(jax.random.fold_in(root, 2 + i)) for i in range(dec["num_hidden_layers"])],
    }


# -- the forward pass ---------------------------------------------------------


def quantize_fp8(x):
    """Round a matmul operand to float8 (e4m3), the step below bfloat16."""
    import jax.numpy as jnp

    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def rms(x, g, eps: float):
    import jax

    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * g


def plain_frequencies(d: int, theta: float) -> np.ndarray:
    """The ``d / 2`` rotary frequencies ``theta^(-2i/d)`` (float64)."""
    return np.asarray([theta ** (-2.0 * i / d) for i in range(d // 2)])


def yarn_frequencies(d: int, section: dict) -> np.ndarray:
    """The ``d / 2`` rotary frequencies of a ``yarn`` section (float64)."""
    theta, factor, length = float(section["rope_theta"]), float(section["factor"]), section["original_max_position_embeddings"]
    plain = plain_frequencies(d, theta)

    def correction(rotations: float) -> float:
        return d * math.log(length / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction(section.get("beta_fast", 32))), 0)
    high = min(math.ceil(correction(section.get("beta_slow", 1))), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0.0, 1.0)
    return plain / factor * ramp + plain * (1.0 - ramp)


def turn(x, positions, frequencies, scale: float = 1.0):
    """``rotate_half`` over the whole head of ``x`` ``[t, heads, d]``: pair ``i``
    is ``(x[i], x[i + d/2])``, turned by ``position * frequencies[i]``; cos
    and sin times ``scale``."""
    import jax.numpy as jnp

    d = x.shape[-1]
    angles = positions.astype(jnp.float32)[:, None] * jnp.asarray(frequencies, jnp.float32)  # [t, d/2]
    cos, sin = (jnp.cos(angles) * scale)[:, None, :], (jnp.sin(angles) * scale)[:, None, :]
    first, second = x[..., : d // 2], x[..., d // 2 :]
    return jnp.concatenate([first * cos - second * sin, first * sin + second * cos], axis=-1)


def rotation(dec: dict, kind: str, cut: str | None = None) -> tuple[np.ndarray, float]:
    """The frequencies and the cos/sin scale of a layer of ``kind``, by its
    section of ``rope_parameters``."""
    d, section = head_dim(dec), dec["rope_parameters"][f"{kind}_attention"]
    if section["rope_type"] == "yarn" and cut != "full_without_yarn":
        freqs = yarn_frequencies(d, section)
    else:
        freqs = plain_frequencies(d, float(section["rope_theta"]))
    return freqs, 1.0 if cut == "no_attention_factor" else float(section.get("attention_factor", 1.0))


def _blocks(t: int) -> int:
    """The fewest equal blocks of at most ``BLOCK`` that make up ``t``."""
    return next(n for n in range(-(-t // BLOCK), t + 1) if t % n == 0)


def attention(h, lp, dec: dict, kind: str, cut: str | None = None):
    """One attention layer's operator over ``h`` ``[t, hidden]``."""
    import jax
    import jax.numpy as jnp

    t = h.shape[0]
    heads, kv, d = dec["num_attention_heads"], dec["num_key_value_heads"], head_dim(dec)
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    q = (h @ f32(lp["q_w"])).reshape(t, heads, d)
    k, v = jnp.split(h @ f32(lp["kv_w"]), 2, axis=-1)
    k, v = k.reshape(t, kv, d), v.reshape(t, kv, d)
    at = jnp.arange(t)
    freqs, scale = rotation(dec, kind, cut)
    q, k = turn(q, at, freqs, scale), turn(k, at, freqs, scale)
    # query head n reads key head n // (heads / kv)
    k, v = jnp.repeat(k, heads // kv, axis=1), jnp.repeat(v, heads // kv, axis=1)
    window = dec["sliding_window"] if kind == "sliding" and cut != "sliding_as_full" else None

    def block(args):
        q_blk, q_at = args
        back = q_at[:, None] - at[None, :]
        seen = (back >= 0) if window is None else (back >= 0) & (back < window)
        scores = jnp.einsum("thd,shd->hts", q_blk, k) / math.sqrt(d)
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("hts,shd->thd", probs, v).reshape(-1, heads * d)

    n = _blocks(t)
    out = jax.lax.map(block, (q.reshape(n, t // n, heads, d), at.reshape(n, t // n)))
    return out.reshape(t, heads * d) @ f32(lp["o_w"])


def router(h, lp, dec: dict):
    """Each token's weight for each expert ``[t, experts]``: the softmax over
    every expert, the ``num_experts_per_tok`` largest kept (a tie to the
    lower id) and divided by their sum where ``norm_topk_prob``; zero
    elsewhere."""
    import jax
    import jax.numpy as jnp

    p = jax.nn.softmax(h @ lp["router_w"].astype(jnp.float32), axis=-1)
    top, chosen = jax.lax.top_k(p, dec["num_experts_per_tok"])
    if dec["norm_topk_prob"]:
        top = top / top.sum(-1, keepdims=True)
    rows = jnp.arange(h.shape[0])[:, None]
    return jnp.zeros_like(p).at[rows, chosen].set(top)


def gated_mlp(x, gate_up, down_w, cast=lambda a: a):
    import jax
    import jax.numpy as jnp

    w = gate_up.shape[-1] // 2
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    gate, up = cast(x) @ cast(f32(gate_up[:, :w])), cast(x) @ cast(f32(gate_up[:, w:]))
    return cast(jax.nn.silu(gate) * up) @ cast(f32(down_w))


def experts(h, lp, dec: dict, operand=None):
    """The routed experts over ``h`` ``[t, hidden]``, each over every token
    and weighted by the router (zero where it was not chosen)."""
    import jax
    import jax.numpy as jnp

    cast = operand if operand is not None else (lambda a: a)
    t = h.shape[0]
    weight = router(h, lp, dec)

    def tokens(args):
        x, wt = args  # [block, hidden], [block, experts]

        def one_expert(y, e):
            gate_up, down_w, we = e
            return y + we[:, None] * gated_mlp(x, gate_up, down_w, cast), None

        y, _ = jax.lax.scan(one_expert, jnp.zeros_like(x), (lp["experts_gate_w"], lp["experts_down_w"], wt.T))
        return y

    n = _blocks(t)
    out = jax.lax.map(tokens, (h.reshape(n, t // n, -1), weight.reshape(n, t // n, -1)))
    return out.reshape(t, -1)


def forward(params, ids, positions_out, dec: dict, operand=None, cut: str | None = None):
    """Logits ``[len(positions_out), vocab]`` float32 of one sequence ``ids``
    ``[t]`` at the positions named, the whole forward pass over all ``t``
    positions. ``operand`` rounds both inputs of every product of the
    routed experts, ``cut`` leaves out one of ``CUTS`` (the controls)."""
    import jax
    import jax.numpy as jnp

    if cut is not None and cut not in CUTS:
        raise ValueError(f"no control {cut!r}; known: {CUTS}")
    eps = dec["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        x = params["tok_emb"][ids].astype(jnp.float32)
        for lp, kind in zip(params["layers"], layer_kinds(dec)):
            x = x + attention(rms(x, lp["attn_norm"], eps), lp, dec, kind, cut)
            x = x + experts(rms(x, lp["mlp_norm"], eps), lp, dec, operand)
        x = rms(x[positions_out], params["final_norm"], eps)
        return x @ params["lm_head"].astype(jnp.float32)


def served_logits(params, sequences: list[tuple[list[int], list[int]]], dec: dict, pad_to: int, operand=None, cut=None):
    """For each ``(prompt ids, served tokens)``: the reference's logits
    ``[new, vocab]`` at the positions that predict each served token, the
    sequence being the prompt followed by the tokens served before it. One
    compiled shape: every sequence is padded on the right to ``pad_to``
    (attention is causal: a position never sees what follows it)."""
    import jax
    import jax.numpy as jnp

    fn = jax.jit(functools.partial(forward, dec=dec, operand=operand, cut=cut))
    out = []
    for prompt, served in sequences:
        seq = list(prompt) + list(served[:-1])
        ids = np.zeros(pad_to, np.int32)
        ids[: len(seq)] = seq
        at = len(prompt) - 1 + np.arange(len(served))
        out.append(np.asarray(fn(params, jnp.asarray(ids), jnp.asarray(at, jnp.int32))))
    return out
