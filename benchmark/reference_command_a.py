"""The plain reference of Command A+'s language model (``model_type``
``cohere2_moe``), as its published ``config.json`` gives it.

It imports nothing of the program. The forward pass, written out in
``jax.numpy``: float32 under ``jax.default_matmul_precision("highest")``, one
sequence at a time, no cache (every position attends over the whole
sequence, the window a mask), no kernels, no batching, every routed expert
applied to every token through a plain loop and weighted by the router's
choice (zero where it was not chosen), the four shared experts one after
another and averaged. Attention walks its queries and the experts their
tokens in blocks, so that a sequence longer than the window fits at the
published widths. The bfloat16 parameters the benchmark made from the seed
are upcast a matrix at a time.

A layer ``l`` of kind ``layer_types[l]``, over ``x`` ``[t, hidden]``:

    h = (x - mean(x)) / sqrt(var(x) + layer_norm_eps) * g     one norm a layer
    q, k, v = h W_q, h W_k, h W_v                             128 | 8 | 8 heads of 128
    sliding_attention: q, k turned by position over the whole head, pairs
        (2i, 2i + 1) (rope_gptj); key j seen by query i iff 0 <= i - j < sliding_window
    full_attention: no positions at all; causal
    a = concat(softmax(q k^T / sqrt(128)) v) W_o
    s = sigmoid(h W_r) over all num_experts; the 8 largest; w_k = s_k / sum of the 8
    r = sum_k w_k E_k(h),  E(h) = W_down (silu(W_gate h) * W_up h)
    sbar = (S_1(h) + S_2(h) + S_3(h) + S_4(h)) / 4
    x' = x + a + r + sbar                                      parallel block

and the head: the same norm, then ``logit_scale * x E^T`` with ``E`` the
embedding. ``held`` ``(first, count)`` is a chip's share of the experts: the
router, the choice of 8 and the normalisation are over all of them, ``r``
sums over the chosen experts among the held ones, and what the others would
add is left out (``None``: every expert is held).

Departures from the published description, each ``assumed`` in the
configuration: ``shared_expert_combination_strategy: "average"`` is read as
the mean of the shared experts' outputs; ``intermediate_size`` as one
expert's width (shared and routed alike); no router bias (the config has no
key for one); ``prefix_dense_*`` have no effect at ``first_k_dense_replace``
0; the vision tower is not there (the config holds no key of it); the
tokenizer is the hashing rule of ``reference.py`` over the vocabulary rows
held. The tree is laid out as the program's ``params=`` takes it: ``kv_w``
is ``W_k | W_v``, ``*_gate_w`` is ``W_gate | W_up``, the shared experts lie
side by side in ``shared_gate_w`` (gates, then ups) and ``shared_down_w``.

A function that a lower precision could tempt takes ``operand``: the same
code with float8 operands in the experts' products is the control
(``control_command_a.py``).
"""

from __future__ import annotations

import functools
import math

import numpy as np

#: attention's queries and the experts' tokens are walked this many at a time
BLOCK = 256


def held_experts(dec: dict) -> tuple[int, int] | None:
    share = dec.get("held_here")
    return tuple(share["experts"]) if share else None


def router_width(dec: dict) -> int:
    share = dec.get("held_here")
    return share["of_experts"] if share else dec["num_experts"]


# -- weights ------------------------------------------------------------------


def _layer_shapes(dec: dict) -> dict:
    h, w = dec["hidden_size"], dec["intermediate_size"]
    heads, kv, d = dec["num_attention_heads"], dec["num_key_value_heads"], dec["head_dim"]
    shared = dec["num_shared_experts"] * w
    return {
        "q_w": (h, heads * d), "kv_w": (h, 2 * kv * d), "o_w": (heads * d, h),
        "router_w": (h, router_width(dec)),
        "experts_gate_w": (dec["num_experts"], h, 2 * w), "experts_down_w": (dec["num_experts"], w, h),
        "shared_gate_w": (h, 2 * shared), "shared_down_w": (shared, h),
    }


def make_params(seed: int, dec: dict):
    """The bfloat16 parameters from the seed, a layer a jitted call on the
    device; the tree is the one the program's ``params=`` takes (the
    embedding is the head's too). A matrix is drawn in float32 with standard
    deviation ``1 / sqrt(rows it contracts over)``, the embedding 0.02, and
    rounded once; norms are ones."""
    import jax
    import jax.numpy as jnp

    h = dec["hidden_size"]

    def draw(key, shape, scale=None):
        scale = 1.0 / math.sqrt(shape[-2]) if scale is None else scale
        return (scale * jax.random.normal(key, shape, jnp.float32)).astype(jnp.bfloat16)

    @jax.jit
    def make_layer(key):
        shapes = _layer_shapes(dec)
        keys = jax.random.split(key, len(shapes))
        lp = {name: draw(k, shape) for k, (name, shape) in zip(keys, shapes.items())}
        lp["attn_norm"] = jnp.ones((h,), jnp.float32)
        return lp

    root = jax.random.fold_in(jax.random.key(seed % (1 << 63)), 0xC0A)
    return {
        "tok_emb": jax.jit(lambda key: draw(key, (dec["vocab_size"], h), 0.02))(jax.random.fold_in(root, 0)),
        "final_norm": jnp.ones((h,), jnp.float32),
        "layers": [make_layer(jax.random.fold_in(root, 1 + i)) for i in range(dec["num_hidden_layers"])],
    }


# -- the forward pass ---------------------------------------------------------


def quantize_fp8(x):
    """Round a matmul operand to float8 (e4m3), the step below bfloat16."""
    import jax.numpy as jnp

    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def layer_norm(x, g, eps: float):
    import jax

    centred = x - x.mean(-1, keepdims=True)
    return centred * jax.lax.rsqrt((centred * centred).mean(-1, keepdims=True) + eps) * g


def rotate_pairs(x, positions, theta: float):
    """``rope_gptj`` over the whole head of ``x`` ``[t, heads, d]``: pair ``i``
    is ``(x[2i], x[2i + 1])``, turned by ``position * theta^(-2i / d)``."""
    import jax.numpy as jnp

    d = x.shape[-1]
    freqs = jnp.asarray([theta ** (-2.0 * i / d) for i in range(d // 2)], jnp.float32)
    angles = positions.astype(jnp.float32)[:, None] * freqs  # [t, d/2]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos], axis=-1).reshape(x.shape)


def router(h, router_w, dec: dict):
    """Each token's weight for each of the router's experts ``[t, experts]``:
    its sigmoid score over the sum of its ``num_experts_per_tok`` largest
    where it is one of them (a tie to the lower id), zero elsewhere."""
    import jax
    import jax.numpy as jnp

    s = jax.nn.sigmoid(h @ router_w.astype(jnp.float32))
    top, chosen = jax.lax.top_k(s, dec["num_experts_per_tok"])
    if dec["norm_topk_prob"]:
        top = top / top.sum(-1, keepdims=True)
    return jnp.zeros_like(s).at[jnp.arange(h.shape[0])[:, None], chosen].set(top)


def _blocks(t: int) -> int:
    """The fewest equal blocks of at most ``BLOCK`` that make up ``t``."""
    return next(n for n in range(-(-t // BLOCK), t + 1) if t % n == 0)


def attention(h, lp, kind: str, dec: dict):
    """One layer's attention over ``h`` ``[t, hidden]``, before ``W_o``'s
    residual add: ``[t, hidden]``."""
    import jax
    import jax.numpy as jnp

    t = h.shape[0]
    heads, kv, d = dec["num_attention_heads"], dec["num_key_value_heads"], dec["head_dim"]
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    q = (h @ f32(lp["q_w"])).reshape(t, heads, d)
    k, v = jnp.split(h @ f32(lp["kv_w"]), 2, axis=-1)
    k, v = k.reshape(t, kv, d), v.reshape(t, kv, d)
    at = jnp.arange(t)
    if kind == "sliding_attention":
        q, k = rotate_pairs(q, at, float(dec["rope_theta"])), rotate_pairs(k, at, float(dec["rope_theta"]))
    elif kind != "full_attention":
        raise ValueError(f"unknown layer type {kind!r}")
    # query head n reads key head n // (heads / kv)
    k, v = jnp.repeat(k, heads // kv, axis=1), jnp.repeat(v, heads // kv, axis=1)

    def block(args):
        q_blk, q_at = args
        back = q_at[:, None] - at[None, :]
        seen = back >= 0
        if kind == "sliding_attention":
            seen = seen & (back < dec["sliding_window"])
        scores = jnp.einsum("thd,shd->hts", q_blk, k) / math.sqrt(d)
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("hts,shd->thd", probs, v).reshape(-1, heads * d)

    n = _blocks(t)
    out = jax.lax.map(block, (q.reshape(n, t // n, heads, d), at.reshape(n, t // n)))
    return out.reshape(t, heads * d) @ f32(lp["o_w"])


def experts(h, lp, dec: dict, held=None, operand=None):
    """``r + sbar`` over ``h`` ``[t, hidden]``: the routed experts held here,
    each over every token and weighted by the router (zero where it was not
    chosen), plus the mean of the shared experts."""
    import jax
    import jax.numpy as jnp

    cast = operand if operand is not None else (lambda a: a)
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    t, w = h.shape[0], dec["intermediate_size"]

    def mlp(x, gate_w, up_w, down_w):
        gate, up = cast(x) @ cast(f32(gate_w)), cast(x) @ cast(f32(up_w))
        return cast(jax.nn.silu(gate) * up) @ cast(f32(down_w))

    weight = router(h, lp["router_w"], dec)  # [t, all experts]
    first, count = held if held is not None else (0, weight.shape[1])
    weight = weight[:, first : first + count]

    def tokens(args):
        x, wt = args  # [block, hidden], [block, held]

        def one_expert(y, e):
            gate_up, down_w, we = e
            return y + we[:, None] * mlp(x, gate_up[:, :w], gate_up[:, w:], down_w), None

        r, _ = jax.lax.scan(one_expert, jnp.zeros_like(x), (lp["experts_gate_w"], lp["experts_down_w"], wt.T))
        n_shared = dec["num_shared_experts"]
        gate_w, up_w = lp["shared_gate_w"][:, : n_shared * w], lp["shared_gate_w"][:, n_shared * w :]
        shared = sum(
            mlp(x, gate_w[:, j * w : (j + 1) * w], up_w[:, j * w : (j + 1) * w], lp["shared_down_w"][j * w : (j + 1) * w])
            for j in range(n_shared)
        )
        return r + shared / n_shared

    n = _blocks(t)
    return jax.lax.map(tokens, (h.reshape(n, t // n, -1), weight.reshape(n, t // n, count))).reshape(t, -1)


def layer(x, lp, kind: str, dec: dict, held=None, operand=None):
    """``x' = x + a + r + sbar``: attention and experts read the one norm."""
    h = layer_norm(x, lp["attn_norm"], dec["layer_norm_eps"])
    return x + attention(h, lp, kind, dec) + experts(h, lp, dec, held, operand)


def forward(params, ids, positions_out, dec: dict, held=None, operand=None):
    """Logits ``[len(positions_out), vocab]`` float32 of one sequence ``ids``
    ``[t]`` at the positions named: the whole forward pass over all ``t``
    positions. ``operand`` rounds both inputs of every product of the routed
    and shared experts (the control)."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        x = params["tok_emb"][ids].astype(jnp.float32)
        for lp, kind in zip(params["layers"], dec["layer_types"]):
            x = layer(x, lp, kind, dec, held, operand)
        x = layer_norm(x[positions_out], params["final_norm"], dec["layer_norm_eps"])
        return dec["logit_scale"] * (x @ params["tok_emb"].astype(jnp.float32).T)


def served_logits(params, sequences: list[tuple[list[int], list[int]]], dec: dict, pad_to: int, operand=None):
    """For each ``(prompt ids, served tokens)``: the reference's logits
    ``[new, vocab]`` at the positions that predict each served token, the
    sequence being the prompt followed by the tokens served before it, under
    the configuration's own share of the experts. One compiled shape: every
    sequence is padded on the right to ``pad_to`` (causal attention: a
    position never sees what follows it)."""
    import jax
    import jax.numpy as jnp

    fn = jax.jit(functools.partial(forward, dec=dec, held=held_experts(dec), operand=operand))
    out = []
    for prompt, served in sequences:
        seq = list(prompt) + list(served[:-1])
        ids = np.zeros(pad_to, np.int32)
        ids[: len(seq)] = seq
        at = len(prompt) - 1 + np.arange(len(served))
        out.append(np.asarray(fn(params, jnp.asarray(ids), jnp.asarray(at, jnp.int32))))
    return out
