"""The plain reference of the answerer's decoder, and of what feeds it.

It imports nothing of the program. The forward pass is the published one
(DeepSeek-V2's ``config.json`` keys, ``q_lora_rank: null``), written out in
``jax.numpy``: float32 with every product at ``Precision.HIGHEST``, one
sequence at a time, no cache (every position attends in the expanded form
over the whole sequence), no kernels, no batching, every routed expert
applied to every token through a plain loop and weighted by the router's
choice (zero where it was not chosen). It upcasts the bfloat16 parameters
the benchmark made from the seed (the configuration states that the decoder
stores bfloat16) one layer at a time, so that it fits beside them.

Departures from the published modelling code, both ``assumed`` in the
configuration: the rotary halves are laid out split (``x1 | x2``) on both
sides, where the HF code first de-interleaves pairs — under random weights a
fixed permutation of ``W_q``'s and ``W_kva``'s rotary columns; the tokenizer
is the hashing rule of ``reference.py`` over the decoder's vocabulary.

A function that a lower precision could tempt takes ``operand``: the same
code with float8 operands in the experts' products is the control
(``control_decoder.py``).
"""

from __future__ import annotations

import functools
import math
import statistics

import numpy as np

import reference

#: upstream's ``prompts.prompt_qa``, written out
PROMPT_QA = (
    "Use the below articles to answer the subsequent question. If the answer cannot be found in "
    'the articles, write "No information found.".\n\nArticles:\n{context}\n\nQuestion: {query}\nAnswer:'
)


# -- texts, prompts, tokens ---------------------------------------------------


def chunk_text(seed: int, slot: int, mix: dict) -> str:
    """The text of prefilled row ``slot``: what a restarted deployment reads
    from its chunk store by key. Words of the mix's vocabulary, as many as
    the mix's document lengths give at a quantile drawn from (seed, slot)."""
    spec = mix["documents"]["tokens"]
    rng = np.random.default_rng([seed % (1 << 63), 5, slot])
    z = statistics.NormalDist().inv_cdf(min(max(float(rng.random()), 1e-12), 1 - 1e-12))
    tokens = int(np.clip(math.floor(spec["median"] * math.exp(spec["sigma"] * z)), spec["min"], spec["max"]))
    words = rng.integers(0, mix["vocabulary_words"], tokens - 2)
    return " ".join(f"w{i}" for i in words.tolist())


def build_prompt(question: str, chunks: list[str]) -> str:
    return PROMPT_QA.format(context="\n\n".join(chunks), query=question)


def prompt_ids(prompt: str, vocab_size: int, max_prompt_len: int, keep_tail: int) -> tuple[list[int], bool]:
    """The prompt's ids (CLS, one id a word or sign, SEP) and whether it was
    cut: a prompt over ``max_prompt_len`` keeps its first ``max_prompt_len -
    keep_tail`` ids and its last ``keep_tail`` (the question and the cue),
    and loses the tail of its context between them."""
    words = reference._WORD_RE.findall(prompt.lower())
    ids = [reference.CLS_ID] + [reference._hash_token(w, vocab_size) for w in words] + [reference.SEP_ID]
    if len(ids) <= max_prompt_len:
        return ids, False
    return ids[: max_prompt_len - keep_tail] + ids[-keep_tail:], True


# -- weights ------------------------------------------------------------------


def layer_kinds(dec: dict) -> list[str]:
    dense = dec["first_k_dense_replace"]
    return ["dense" if i < dense else "experts" for i in range(dec["num_hidden_layers"])]


def _layer_shapes(dec: dict, kind: str) -> dict:
    h, heads = dec["hidden_size"], dec["num_attention_heads"]
    nope, rot, vd, rank = dec["qk_nope_head_dim"], dec["qk_rope_head_dim"], dec["v_head_dim"], dec["kv_lora_rank"]
    shapes = {
        "q_w": (h, heads * (nope + rot)),
        "kva_w": (h, rank + rot),
        "kvb_w": (rank, heads * (nope + vd)),
        "o_w": (heads * vd, h),
    }
    if kind == "dense":
        f = dec["intermediate_size"]
        shapes.update(gate_w=(h, 2 * f), down_w=(f, h))
    else:
        e, w = dec["n_routed_experts"], dec["moe_intermediate_size"]
        s = dec["n_shared_experts"] * w
        shapes.update(
            router_w=(h, e), experts_gate_w=(e, h, 2 * w), experts_down_w=(e, w, h),
            shared_gate_w=(h, 2 * s), shared_down_w=(s, h),
        )
    return shapes


def make_params(seed: int, dec: dict):
    """The decoder's bfloat16 parameters from the seed, a layer a jitted
    call on the device (one program a layer kind); the tree is the one the
    program's ``params=`` takes. A matrix is drawn in float32 with standard
    deviation ``1 / sqrt(rows it contracts over)`` and rounded once."""
    import jax
    import jax.numpy as jnp

    h = dec["hidden_size"]

    def draw(key, shape, scale=None):
        scale = 1.0 / math.sqrt(shape[-2]) if scale is None else scale
        return (scale * jax.random.normal(key, shape, jnp.float32)).astype(jnp.bfloat16)

    @functools.partial(jax.jit, static_argnums=1)
    def make_layer(key, kind):
        shapes = _layer_shapes(dec, kind)
        keys = jax.random.split(key, len(shapes))
        lp = {name: draw(k, shape) for k, (name, shape) in zip(keys, shapes.items())}
        lp["attn_norm"] = jnp.ones((h,), jnp.float32)
        lp["mlp_norm"] = jnp.ones((h,), jnp.float32)
        lp["kv_norm"] = jnp.ones((dec["kv_lora_rank"],), jnp.float32)
        return lp

    @jax.jit
    def make_ends(key):
        k1, k2 = jax.random.split(key)
        return {
            "tok_emb": draw(k1, (dec["vocab_size"], h), 0.02),
            "final_norm": jnp.ones((h,), jnp.float32),
            "lm_head": draw(k2, (h, dec["vocab_size"])),
        }

    root = jax.random.fold_in(jax.random.key(seed % (1 << 63)), 0xDEC)
    params = make_ends(jax.random.fold_in(root, 0))
    params["layers"] = [
        make_layer(jax.random.fold_in(root, 1 + i), kind) for i, kind in enumerate(layer_kinds(dec))
    ]
    return params


# -- the forward pass ---------------------------------------------------------


def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def rotary_frequencies(dec: dict) -> np.ndarray:
    """``qk_rope_head_dim / 2`` frequencies, YaRN's blend where the
    configuration scales: ``f_i = theta^(-2i/d)``; ``g_i = f_i / factor``;
    the ramp ``r_i = clip((i - low) / (high - low), 0, 1)`` between the
    correction dimensions ``d ln(L / (2 pi beta)) / (2 ln theta)`` of
    ``beta_fast`` (floored) and ``beta_slow`` (ceiled); ``g_i r_i + f_i (1 - r_i)``."""
    d, theta = dec["qk_rope_head_dim"], float(dec["rope_theta"])
    f = np.array([theta ** (-2.0 * i / d) for i in range(d // 2)], np.float64)
    yarn = dec.get("rope_scaling")
    if not yarn:
        return f.astype(np.float32)
    length = yarn["original_max_position_embeddings"]

    def correction(beta: float) -> float:
        return d * math.log(length / (2 * math.pi * beta)) / (2 * math.log(theta))

    low = max(math.floor(correction(yarn["beta_fast"])), 0)
    high = min(math.ceil(correction(yarn["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    r = np.clip((np.arange(d // 2) - low) / (high - low), 0.0, 1.0)
    return (f / yarn["factor"] * r + f * (1 - r)).astype(np.float32)


def softmax_scale(dec: dict) -> float:
    scale = (dec["qk_nope_head_dim"] + dec["qk_rope_head_dim"]) ** -0.5
    yarn = dec.get("rope_scaling")
    if yarn and yarn.get("mscale_all_dim"):
        scale *= yarn_mscale(yarn["factor"], yarn["mscale_all_dim"]) ** 2
    return scale


def quantize_fp8(x):
    """Round a matmul operand to float8 (e4m3), the step below bfloat16."""
    import jax.numpy as jnp

    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def forward(params, ids, positions_out, dec: dict, operand=None):
    """Logits ``[len(positions_out), vocab]`` float32 of one sequence ``ids``
    ``[t]`` at the positions named: the whole published forward pass over
    all ``t`` positions, causal. ``operand`` rounds both inputs of every
    product of the routed and shared experts (the control)."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    cast = operand if operand is not None else (lambda a: a)
    eps = dec["rms_norm_eps"]
    heads = dec["num_attention_heads"]
    nope, rot, vd, rank = dec["qk_nope_head_dim"], dec["qk_rope_head_dim"], dec["v_head_dim"], dec["kv_lora_rank"]
    top_k, n_experts = dec["num_experts_per_tok"], dec["n_routed_experts"]
    t = ids.shape[0]

    def norm(x, scale):
        return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale

    def mm(a, b):
        return jnp.matmul(a, b, precision=hi)

    def expert_mm(a, b):
        return jnp.matmul(cast(a), cast(b), precision=hi)

    def mlp(x, gate_w, down_w, product):
        gate, up = jnp.split(product(x, f32(gate_w)), 2, axis=-1)
        return product(jax.nn.silu(gate) * up, f32(down_w))

    yarn = dec.get("rope_scaling")
    table = 1.0
    if yarn:
        table = yarn_mscale(yarn["factor"], yarn["mscale"]) / yarn_mscale(yarn["factor"], yarn["mscale_all_dim"])
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * jnp.asarray(rotary_frequencies(dec))
    cos, sin = jnp.cos(angles) * table, jnp.sin(angles) * table

    def rotate(x):  # [t, ..., rot]
        shape = (t,) + (1,) * (x.ndim - 2) + (rot // 2,)
        c, s = cos.reshape(shape), sin.reshape(shape)
        x1, x2 = x[..., : rot // 2], x[..., rot // 2 :]
        return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)

    causal = jnp.tril(jnp.ones((t, t), bool))
    x = f32(params["tok_emb"][ids])
    for lp, kind in zip(params["layers"], layer_kinds(dec)):
        h = norm(x, lp["attn_norm"])
        q = mm(h, f32(lp["q_w"])).reshape(t, heads, nope + rot)
        q_nope, q_rope = q[..., :nope], rotate(q[..., nope:])
        kva = mm(h, f32(lp["kva_w"]))
        c = norm(kva[:, :rank], lp["kv_norm"])
        k_rope = rotate(kva[:, rank:])
        kv = mm(c, f32(lp["kvb_w"])).reshape(t, heads, nope + vd)
        k_nope, v = kv[..., :nope], kv[..., nope:]
        scores = jnp.einsum("thn,shn->hts", q_nope, k_nope, precision=hi)
        scores = scores + jnp.einsum("thr,sr->hts", q_rope, k_rope, precision=hi)
        probs = jax.nn.softmax(jnp.where(causal[None], scores * softmax_scale(dec), -jnp.inf), axis=-1)
        a = jnp.einsum("hts,shv->thv", probs, v, precision=hi).reshape(t, heads * vd)
        x = x + mm(a, f32(lp["o_w"]))
        h = norm(x, lp["mlp_norm"])
        if kind == "dense":
            x = x + mlp(h, lp["gate_w"], lp["down_w"], mm)
            continue
        s = jax.nn.softmax(mm(h, f32(lp["router_w"])), axis=-1)  # over all the experts
        top, chosen = jax.lax.top_k(s, top_k)
        if dec["norm_topk_prob"]:
            top = top / top.sum(-1, keepdims=True)
        top = top * dec["routed_scaling_factor"]
        # each token's weight for each expert: its score where chosen, zero elsewhere
        weight = jnp.zeros((t, n_experts), jnp.float32).at[jnp.arange(t)[:, None], chosen].set(top)

        def one_expert(y, args):
            gate_w, down_w, w = args
            return y + w[:, None] * mlp(h, gate_w, down_w, expert_mm), None

        y, _ = jax.lax.scan(
            one_expert, jnp.zeros_like(h), (lp["experts_gate_w"], lp["experts_down_w"], weight.T)
        )
        x = x + y + mlp(h, lp["shared_gate_w"], lp["shared_down_w"], expert_mm)
    x = norm(x[positions_out], params["final_norm"])
    return mm(x, f32(params["lm_head"]))


def served_logits(params, sequences: list[tuple[list[int], list[int]]], dec: dict, pad_to: int, operand=None):
    """For each ``(prompt ids, served tokens)``: the reference's logits
    ``[new, vocab]`` at the positions that predict each served token, the
    sequence being the prompt followed by the tokens served before it. One
    compiled shape: every sequence is padded on the right to ``pad_to``
    (causal attention: a position never sees what follows it)."""
    import jax
    import jax.numpy as jnp

    fn = jax.jit(functools.partial(forward, dec=dec, operand=operand))
    out = []
    for prompt, served in sequences:
        seq = list(prompt) + list(served[:-1])
        ids = np.zeros(pad_to, np.int32)
        ids[: len(seq)] = seq
        at = len(prompt) - 1 + np.arange(len(served))
        out.append(np.asarray(fn(params, jnp.asarray(ids), jnp.asarray(at, jnp.int32))))
    return out
