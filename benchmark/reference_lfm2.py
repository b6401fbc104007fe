"""The plain reference of LFM2-24B-A2B's language model (``model_type``
``lfm2_moe``), as its published ``config.json`` gives it and
``transformers``' ``modeling_lfm2_moe.py`` computes it.

It imports nothing of the program. The forward pass, written out in
``jax.numpy``: float32 under ``jax.default_matmul_precision("highest")``, one
sequence at a time, no cache (the filter runs over the whole sequence, every
position attends over the whole sequence), no kernels, no batching, the
filter as three shifted multiply-adds, every routed expert applied to every
token through a plain loop and weighted by the router's choice (zero where it
was not chosen). Attention walks its queries and the experts their tokens in
blocks, so that a sequence of the cell's length fits beside the index at the
published widths. The bfloat16 parameters the benchmark made from the seed
are upcast a matrix at a time.

A layer ``l`` of kind ``layer_types[l]``, over ``x`` ``[t, hidden]``, RMS norms
of ``norm_eps``:

    h = rms(x) * g_operator
    conv:            B, C, u = thirds of h W_in               (no bias)
                     z = B * u
                     c[i] = sum_{j=0..L-1} w[:, j] * z[i - (L - 1) + j]   zeros before the sequence; L = conv_L_cache
                     a = (C * c) W_out
    full_attention:  q, k, v = h W_q, h W_k, h W_v            32 | 8 | 8 heads of 64
                     q, k = rms over each head's 64 values * g_q, g_k
                     q, k turned by position, split halves (rotate_half), theta 1e6
                     a = concat(softmax(q k^T / sqrt(64)) v) W_out          causal
    x = x + a
    h = rms(x) * g_ffn
    l < num_dense_layers:   f = W_2 (silu(W_1 h) * W_3 h)     at intermediate_size
    else:                   s = sigmoid(h W_r)                float32, num_experts wide
                            the num_experts_per_tok largest of s + expert_bias are chosen (a tie to the lower id)
                            w_k = s_k / (sum of the chosen s + 1e-6) * routed_scaling_factor     the bias is in no weight
                            f = sum_k w_k E_k(h),  E(h) = W_2 (silu(W_1 h) * W_3 h)  at moe_intermediate_size
    x' = x + f

and the head: one more RMS norm (``embedding_norm``), then ``x E^T`` with ``E``
the embedding.

Departures from the published description, each ``assumed`` in the
configuration: a head is ``hidden_size / num_attention_heads`` = 64 wide (the
catalog's config has no ``head_dim``); the head is tied to the embedding
(the family's ``tie_embedding``; the catalog's config lacks the key);
``expert_bias`` is drawn from the seed (upstream ships the trained buffer),
at a scale at which it changes the choice of experts for a share of the
tokens that ``served_logits`` prints; the tokenizer is the hashing rule of
``reference.py`` over the vocabulary. The tree is laid out as the program's
``params=`` takes it: ``conv_in_w`` is ``W_B | W_C | W_u`` (``in_proj``'s
thirds in its own order), ``conv_w`` ``[hidden, L]`` (``conv.weight`` without
its middle axis; column ``L - 1`` is the tap on the newest position),
``kv_w`` is ``W_k | W_v``, ``gate_w`` / ``experts_gate_w`` are ``W_1 | W_3``
and ``down_w`` / ``experts_down_w`` ``W_2``: under random weights a naming of
columns, on both sides.

A function that a lower precision could tempt takes ``operand``: the same
code with float8 operands in the experts' products is the control
(``control_lfm2.py``).
"""

from __future__ import annotations

import functools
import math
import sys

import numpy as np

#: attention's queries and the experts' tokens are walked this many at a time
BLOCK = 256
#: the standard deviation ``expert_bias`` is drawn with where the
#: configuration states none (``expert_bias_std``): against sigmoid scores of
#: unit-variance logits over 64 experts it moves the four chosen for some
#: three tokens in ten (0.005: one in eight; 0.02: four in ten)
EXPERT_BIAS_SCALE = 0.01


def head_dim(dec: dict) -> int:
    return dec.get("head_dim") or dec["hidden_size"] // dec["num_attention_heads"]


def layer_kinds(dec: dict) -> list[tuple[str, str]]:
    """(operator, feed-forward) of each layer that is run."""
    n = dec["num_hidden_layers"]
    types = list(dec["layer_types"][:n])
    if len(types) != n or set(types) - {"conv", "full_attention"}:
        raise ValueError(f"layer_types {dec['layer_types']!r} for {n} layers")
    return [(kind, "dense" if i < dec["num_dense_layers"] else "experts") for i, kind in enumerate(types)]


# -- weights ------------------------------------------------------------------


def _layer_shapes(dec: dict, operator: str, ff: str) -> dict:
    h, d = dec["hidden_size"], head_dim(dec)
    if operator == "conv":
        shapes = {"conv_in_w": (h, 3 * h), "conv_w": (h, dec["conv_L_cache"]), "o_w": (h, h)}
    else:
        heads, kv = dec["num_attention_heads"], dec["num_key_value_heads"]
        shapes = {"q_w": (h, heads * d), "kv_w": (h, 2 * kv * d), "o_w": (heads * d, h)}
    if ff == "dense":
        shapes.update(gate_w=(h, 2 * dec["intermediate_size"]), down_w=(dec["intermediate_size"], h))
    else:
        e, w = dec["num_experts"], dec["moe_intermediate_size"]
        shapes.update(router_w=(h, e), experts_gate_w=(e, h, 2 * w), experts_down_w=(e, w, h))
    return shapes


def make_params(seed: int, dec: dict):
    """The bfloat16 parameters from the seed, a layer a jitted call on the
    device; the tree is the one the program's ``params=`` takes (the
    embedding is the head's too). A matrix is drawn in float32 with standard
    deviation ``1 / sqrt(rows it contracts over)`` (the filter ``1 /
    sqrt(taps)``), the embedding 0.02, and rounded once; norms are ones;
    ``expert_bias`` is float32, ``expert_bias_std`` wide."""
    import jax
    import jax.numpy as jnp

    h, d, bias_scale = dec["hidden_size"], head_dim(dec), dec.get("expert_bias_std", EXPERT_BIAS_SCALE)

    def draw(key, shape, scale):
        return (scale * jax.random.normal(key, shape, jnp.float32)).astype(jnp.bfloat16)

    @functools.partial(jax.jit, static_argnums=(1, 2))
    def make_layer(key, operator, ff):
        shapes = _layer_shapes(dec, operator, ff)
        keys = jax.random.split(key, len(shapes) + 1)
        lp = {
            name: draw(k, shape, 1.0 / math.sqrt(shape[-1] if name == "conv_w" else shape[-2]))
            for k, (name, shape) in zip(keys, shapes.items())
        }
        lp["attn_norm"], lp["mlp_norm"] = jnp.ones((h,), jnp.float32), jnp.ones((h,), jnp.float32)
        if operator != "conv":
            lp["q_norm"], lp["k_norm"] = jnp.ones((d,), jnp.float32), jnp.ones((d,), jnp.float32)
        if ff == "experts":
            lp["expert_bias"] = bias_scale * jax.random.normal(keys[-1], (dec["num_experts"],), jnp.float32)
        return lp

    root = jax.random.fold_in(jax.random.key(seed % (1 << 63)), 0x1F2)
    return {
        "tok_emb": jax.jit(lambda key: draw(key, (dec["vocab_size"], h), 0.02))(jax.random.fold_in(root, 0)),
        "final_norm": jnp.ones((h,), jnp.float32),
        "layers": [make_layer(jax.random.fold_in(root, 1 + i), *kinds) for i, kinds in enumerate(layer_kinds(dec))],
    }


# -- the forward pass ---------------------------------------------------------


def quantize_fp8(x):
    """Round a matmul operand to float8 (e4m3), the step below bfloat16."""
    import jax.numpy as jnp

    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def rms(x, g, eps: float):
    import jax

    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * g


def rotate_halves(x, positions, theta: float):
    """``rotate_half`` over the whole head of ``x`` ``[t, heads, d]``: pair ``i``
    is ``(x[i], x[i + d/2])``, turned by ``position * theta^(-2i / d)``."""
    import jax.numpy as jnp

    d = x.shape[-1]
    freqs = jnp.asarray([theta ** (-2.0 * i / d) for i in range(d // 2)], jnp.float32)
    angles = positions.astype(jnp.float32)[:, None] * freqs  # [t, d/2]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    first, second = x[..., : d // 2], x[..., d // 2 :]
    return jnp.concatenate([first * cos - second * sin, first * sin + second * cos], axis=-1)


def _blocks(t: int) -> int:
    """The fewest equal blocks of at most ``BLOCK`` that make up ``t``."""
    return next(n for n in range(-(-t // BLOCK), t + 1) if t % n == 0)


def filter_input(h, lp):
    """``z = B * u`` and the gate ``C`` of a conv layer over ``h`` ``[t, hidden]``."""
    import jax.numpy as jnp

    gate_in, gate_out, u = jnp.split(h @ lp["conv_in_w"].astype(jnp.float32), 3, axis=-1)
    return gate_in * u, gate_out


def short_conv(h, lp, dec: dict):
    """One conv layer's operator over ``h`` ``[t, hidden]``, before the
    residual add: the causal depthwise filter as shifted multiply-adds, the
    oldest tap first."""
    import jax.numpy as jnp

    z, gate_out = filter_input(h, lp)
    t, n = z.shape[0], dec["conv_L_cache"]
    w = lp["conv_w"].astype(jnp.float32)
    c = jnp.zeros_like(z)
    for j in range(n):
        back = n - 1 - j  # tap j reads the position this many before
        c = c + w[:, j] * jnp.concatenate([jnp.zeros((back, z.shape[1]), z.dtype), z[: t - back]], axis=0)
    return (gate_out * c) @ lp["o_w"].astype(jnp.float32)


def attention(h, lp, dec: dict):
    """One attention layer's operator over ``h`` ``[t, hidden]``."""
    import jax
    import jax.numpy as jnp

    t = h.shape[0]
    heads, kv, d = dec["num_attention_heads"], dec["num_key_value_heads"], head_dim(dec)
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    q = (h @ f32(lp["q_w"])).reshape(t, heads, d)
    k, v = jnp.split(h @ f32(lp["kv_w"]), 2, axis=-1)
    k, v = k.reshape(t, kv, d), v.reshape(t, kv, d)
    q, k = rms(q, lp["q_norm"], dec["norm_eps"]), rms(k, lp["k_norm"], dec["norm_eps"])
    at, theta = jnp.arange(t), float(dec["rope_parameters"]["rope_theta"])
    q, k = rotate_halves(q, at, theta), rotate_halves(k, at, theta)
    # query head n reads key head n // (heads / kv)
    k, v = jnp.repeat(k, heads // kv, axis=1), jnp.repeat(v, heads // kv, axis=1)

    def block(args):
        q_blk, q_at = args
        seen = q_at[:, None] >= at[None, :]
        scores = jnp.einsum("thd,shd->hts", q_blk, k) / math.sqrt(d)
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("hts,shd->thd", probs, v).reshape(-1, heads * d)

    n = _blocks(t)
    out = jax.lax.map(block, (q.reshape(n, t // n, heads, d), at.reshape(n, t // n)))
    return out.reshape(t, heads * d) @ f32(lp["o_w"])


def router(h, lp, dec: dict):
    """Each token's weight for each expert ``[t, experts]`` (its sigmoid
    score over the sum of the chosen scores ``+ 1e-6`` where it is one of the
    ``num_experts_per_tok`` largest of score + ``expert_bias``, a tie to the
    lower id; zero elsewhere), and whether the bias changed its choice."""
    import jax
    import jax.numpy as jnp

    s = jax.nn.sigmoid(h @ lp["router_w"].astype(jnp.float32))
    k = dec["num_experts_per_tok"]
    _, chosen = jax.lax.top_k(s + lp["expert_bias"], k)
    _, unbiased = jax.lax.top_k(s, k)
    top = jnp.take_along_axis(s, chosen, axis=-1)
    if dec["norm_topk_prob"]:
        top = top / (top.sum(-1, keepdims=True) + 1e-6)
    top = top * dec["routed_scaling_factor"]
    rows = jnp.arange(h.shape[0])[:, None]
    weight = jnp.zeros_like(s).at[rows, chosen].set(top)
    moved = (jnp.sort(chosen, axis=-1) != jnp.sort(unbiased, axis=-1)).any(-1)
    return weight, moved


def gated_mlp(x, gate_up, down_w, cast=lambda a: a):
    import jax
    import jax.numpy as jnp

    w = gate_up.shape[-1] // 2
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    gate, up = cast(x) @ cast(f32(gate_up[:, :w])), cast(x) @ cast(f32(gate_up[:, w:]))
    return cast(jax.nn.silu(gate) * up) @ cast(f32(down_w))


def experts(h, lp, dec: dict, operand=None):
    """The routed experts over ``h`` ``[t, hidden]``, each over every token
    and weighted by the router (zero where it was not chosen), and the
    tokens whose choice the bias changed."""
    import jax
    import jax.numpy as jnp

    cast = operand if operand is not None else (lambda a: a)
    t = h.shape[0]
    weight, moved = router(h, lp, dec)

    def tokens(args):
        x, wt = args  # [block, hidden], [block, experts]

        def one_expert(y, e):
            gate_up, down_w, we = e
            return y + we[:, None] * gated_mlp(x, gate_up, down_w, cast), None

        y, _ = jax.lax.scan(one_expert, jnp.zeros_like(x), (lp["experts_gate_w"], lp["experts_down_w"], wt.T))
        return y

    n = _blocks(t)
    out = jax.lax.map(tokens, (h.reshape(n, t // n, -1), weight.reshape(n, t // n, -1)))
    return out.reshape(t, -1), moved


def forward(params, ids, positions_out, dec: dict, operand=None):
    """Logits ``[len(positions_out), vocab]`` float32 of one sequence ``ids``
    ``[t]`` at the positions named, the whole forward pass over all ``t``
    positions, and per expert layer and position whether ``expert_bias``
    changed the choice ``[expert layers, t]``. ``operand`` rounds both inputs
    of every product of the routed experts (the control)."""
    import jax
    import jax.numpy as jnp

    eps, moved = dec["norm_eps"], []
    with jax.default_matmul_precision("highest"):
        x = params["tok_emb"][ids].astype(jnp.float32)
        for lp, (operator, ff) in zip(params["layers"], layer_kinds(dec)):
            h = rms(x, lp["attn_norm"], eps)
            x = x + (short_conv(h, lp, dec) if operator == "conv" else attention(h, lp, dec))
            h = rms(x, lp["mlp_norm"], eps)
            if ff == "dense":
                x = x + gated_mlp(h, lp["gate_w"], lp["down_w"])
            else:
                y, layer_moved = experts(h, lp, dec, operand)
                x = x + y
                moved.append(layer_moved)
        x = rms(x[positions_out], params["final_norm"], eps)
        logits = x @ params["tok_emb"].astype(jnp.float32).T
    return logits, jnp.stack(moved) if moved else jnp.zeros((0, ids.shape[0]), bool)


def served_logits(params, sequences: list[tuple[list[int], list[int]]], dec: dict, pad_to: int, operand=None):
    """For each ``(prompt ids, served tokens)``: the reference's logits
    ``[new, vocab]`` at the positions that predict each served token, the
    sequence being the prompt followed by the tokens served before it. One
    compiled shape: every sequence is padded on the right to ``pad_to`` (the
    filter and attention are causal: a position never sees what follows it).
    Prints for what share of the sequences' tokens ``expert_bias`` changed
    the choice of experts in an expert layer."""
    import jax
    import jax.numpy as jnp

    fn = jax.jit(functools.partial(forward, dec=dec, operand=operand))
    out, moved, seen = [], 0, 0
    for prompt, served in sequences:
        seq = list(prompt) + list(served[:-1])
        ids = np.zeros(pad_to, np.int32)
        ids[: len(seq)] = seq
        at = len(prompt) - 1 + np.arange(len(served))
        logits, layer_moved = fn(params, jnp.asarray(ids), jnp.asarray(at, jnp.int32))
        out.append(np.asarray(logits))
        moved += int(np.asarray(layer_moved)[:, : len(seq)].sum())
        seen += layer_moved.shape[0] * len(seq)
    if seen:
        print(f"reference_lfm2: expert_bias changed the choice of experts for {moved / seen:.3f} of {seen} (token, expert layer) pairs", file=sys.stderr)
    return out
