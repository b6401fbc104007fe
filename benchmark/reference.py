"""The plain reference: what the timed path's output is compared with.

It imports nothing of the program. It has its own tokenizer (the hashing
rule, written out), its own encoder forward pass in float32 at
``Precision.HIGHEST``, its own generator of the weights and of the
prefilled index rows (both made from the seed by the benchmark, and handed
to the program as its input), and an exact search whose last step is
float64 on the host.

Each function that a lower precision could tempt takes the precision as an
argument, so that the same code, one step down, is the control that
``correct`` has to refuse (``control.py``).
"""

from __future__ import annotations

import functools
import hashlib
import math
import re

import numpy as np

CLS_ID, SEP_ID = 1, 2
_WORD_RE = re.compile(r"[^\W_]+|[^\w\s]|_")
#: rows of the prefilled index are made, and searched, this many at a time
PREFILL_BLOCK = 262144
#: keys of prefilled rows: far above nothing, below 2**128, never a hash
PREFILL_KEY_BASE = 1 << 120
#: documents of the run whose reference embeddings shape the prefilled rows
PREFILL_SAMPLE = 128


# -- tokenizer ----------------------------------------------------------------


@functools.lru_cache(maxsize=1 << 16)
def _hash_token(word: str, vocab_size: int) -> int:
    digest = hashlib.blake2s(word.encode(), digest_size=4).digest()
    return 4 + int.from_bytes(digest, "little") % (vocab_size - 4)


def tokenize(texts: list[str], vocab_size: int, max_len: int, pad_to: int):
    """ids ``[n, pad_to]`` int32 (0 pads) and the mask of real tokens."""
    ids = np.zeros((len(texts), pad_to), np.int32)
    for row, text in enumerate(texts):
        words = _WORD_RE.findall(text.lower())[: max_len - 2]
        toks = [CLS_ID] + [_hash_token(w, vocab_size) for w in words] + [SEP_ID]
        ids[row, : len(toks)] = toks
    return ids, ids != 0


# -- weights ------------------------------------------------------------------


def make_params(seed: int, enc: dict):
    """The encoder's float32 parameters, from the seed, in one jitted call
    on the device; the tree is the one the program's ``params=`` takes."""
    import jax
    import jax.numpy as jnp

    h, f = enc["hidden_size"], enc["intermediate_size"]
    n_layers = enc["num_hidden_layers"]

    @jax.jit
    def make(key):
        keys = iter(jax.random.split(key, 3 + 4 * n_layers))

        def dense(shape):
            return 0.02 * jax.random.normal(next(keys), shape, jnp.float32)

        def ln():
            return {"scale": jnp.ones((h,), jnp.float32), "bias": jnp.zeros((h,), jnp.float32)}

        params = {
            "tok_emb": dense((enc["vocab_size"], h)),
            "pos_emb": dense((enc["max_position_embeddings"], h)),
            "type_emb": dense((enc["type_vocab_size"], h)),
            "emb_ln": ln(),
            "layers": [],
        }
        for _ in range(n_layers):
            params["layers"].append(
                {
                    "qkv_w": dense((h, 3 * h)),
                    "qkv_b": jnp.zeros((3 * h,), jnp.float32),
                    "out_w": dense((h, h)),
                    "out_b": jnp.zeros((h,), jnp.float32),
                    "attn_ln": ln(),
                    "fc1_w": dense((h, f)),
                    "fc1_b": jnp.zeros((f,), jnp.float32),
                    "fc2_w": dense((f, h)),
                    "fc2_b": jnp.zeros((h,), jnp.float32),
                    "mlp_ln": ln(),
                }
            )
        return params

    return make(jax.random.key(seed % (1 << 63)))


# -- encoder ------------------------------------------------------------------


def quantize_fp8(x):
    """Round a matmul operand to float8 (e4m3), the step below bfloat16."""
    import jax.numpy as jnp

    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def embed(params, ids, mask, enc: dict, operand=None):
    """Sentence embeddings ``[n, hidden]``, float32, unit length: BERT's
    post-layer-norm encoder as published, tanh GELU (``assumed``), then CLS
    or masked-mean pooling. ``operand`` rounds every matrix product's two
    inputs (the control); the reference passes none."""
    import jax
    import jax.numpy as jnp

    cast = operand if operand is not None else (lambda x: x)
    eps = enc["layer_norm_eps"]
    heads = enc["num_attention_heads"]

    def matmul(a, b):
        return jnp.matmul(cast(a), cast(b), precision=jax.lax.Precision.HIGHEST)

    def layer_norm(x, p):
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]

    n, t = ids.shape
    x = params["tok_emb"][ids] + params["pos_emb"][None, :t] + params["type_emb"][0]
    x = layer_norm(x, params["emb_ln"])
    for lp in params["layers"]:
        q, k, v = jnp.split(matmul(x, lp["qkv_w"]) + lp["qkv_b"], 3, axis=-1)
        split = lambda a: a.reshape(n, t, heads, -1).transpose(0, 2, 1, 3)  # noqa: E731
        q, k, v = split(q), split(k), split(v)
        s = matmul(q, k.transpose(0, 1, 3, 2)) / math.sqrt(q.shape[-1])
        s = jnp.where(mask[:, None, None, :], s, -1e30)
        a = matmul(jax.nn.softmax(s, axis=-1), v)
        a = a.transpose(0, 2, 1, 3).reshape(n, t, -1)
        x = layer_norm(x + matmul(a, lp["out_w"]) + lp["out_b"], lp["attn_ln"])
        hid = jax.nn.gelu(matmul(x, lp["fc1_w"]) + lp["fc1_b"], approximate=True)
        x = layer_norm(x + matmul(hid, lp["fc2_w"]) + lp["fc2_b"], lp["mlp_ln"])
    if enc["pooling"] == "cls":
        emb = x[:, 0]
    else:
        m = mask.astype(jnp.float32)[..., None]
        emb = (x * m).sum(1) / jnp.maximum(m.sum(1), 1e-9)
    return emb / jnp.maximum(jnp.linalg.norm(emb, axis=-1, keepdims=True), 1e-12)


def embed_texts(params, texts: list[str], enc: dict, max_len: int, operand=None, rows: int = 32):
    """:func:`embed` over ``texts`` in blocks of ``rows`` padded to
    ``max_len`` (one compiled shape), as a float32 NumPy array."""
    import jax
    import jax.numpy as jnp

    fn = jax.jit(functools.partial(embed, enc=enc, operand=operand))
    out = []
    for start in range(0, len(texts), rows):
        block = texts[start : start + rows]
        pad = block + [""] * (rows - len(block))
        ids, mask = tokenize(pad, enc["vocab_size"], max_len, max_len)
        out.append(np.asarray(fn(params, jnp.asarray(ids), jnp.asarray(mask)))[: len(block)])
    return np.concatenate(out) if out else np.zeros((0, enc["hidden_size"]), np.float32)


# -- the prefilled index ------------------------------------------------------


def prefill_moments(params, texts: list[str], enc: dict, max_len: int):
    """What gives the prefilled rows the distribution of the encoder's own
    output: the mean of the reference's embeddings of ``texts`` (a sample of
    the run's documents) and their deviations from it, scaled so that
    ``z @ deviations`` with standard normal ``z`` has the sample's
    covariance. Rows drawn so lie among the documents' embeddings and
    compete with them for a place in an answer."""
    import jax.numpy as jnp

    emb = embed_texts(params, texts, enc, max_len).astype(np.float64)
    mean = emb.mean(0)
    deviations = (emb - mean) / math.sqrt(max(len(texts) - 1, 1))
    return jnp.asarray(mean, jnp.float32), jnp.asarray(deviations, jnp.float32)


def prefill_block(key, block: int, prefilled: int, moments):
    """Rows ``[block * PREFILL_BLOCK, (block + 1) * PREFILL_BLOCK)`` of the
    prefilled index: unit vectors drawn round ``moments``' mean with its
    covariance, zero past ``prefilled``."""
    import jax
    import jax.numpy as jnp

    mean, deviations = moments
    z = jax.random.normal(
        jax.random.fold_in(key, block), (PREFILL_BLOCK, deviations.shape[0]), jnp.float32
    )
    rows = mean + jnp.matmul(z, deviations, precision=jax.lax.Precision.HIGHEST)
    rows = rows / jnp.sqrt(jnp.sum(rows * rows, axis=-1, keepdims=True))
    slot = block * PREFILL_BLOCK + jnp.arange(PREFILL_BLOCK)
    return jnp.where((slot < prefilled)[:, None], rows, 0.0)


def prefill_key(seed: int):
    import jax

    return jax.random.fold_in(jax.random.key(seed % (1 << 63)), 0x1DE)


def make_prefill(seed: int, capacity: int, prefilled: int, moments, mesh=None):
    """The index's three arrays at full capacity with ``prefilled`` rows
    set, made block by block on the device — sharded over ``mesh``'s data
    axis where one is given, each device making only its own rows."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    if capacity % PREFILL_BLOCK:
        raise ValueError(f"capacity {capacity} is not a multiple of {PREFILL_BLOCK}")
    shards = 1 if mesh is None else mesh.devices.size
    blocks_local = capacity // PREFILL_BLOCK // shards
    dim = moments[0].shape[0]

    def local(key, shard, moments):
        def body(j, buf):
            rows = prefill_block(key, shard * blocks_local + j, prefilled, moments)
            return jax.lax.dynamic_update_slice(buf, rows, (j * PREFILL_BLOCK, 0))

        buf = jnp.zeros((blocks_local * PREFILL_BLOCK, dim), jnp.float32)
        vectors = jax.lax.fori_loop(0, blocks_local, body, buf)
        slot = shard * blocks_local * PREFILL_BLOCK + jnp.arange(vectors.shape[0])
        valid = slot < prefilled
        return vectors, valid, jnp.sum(vectors * vectors, axis=-1)

    key = prefill_key(seed)
    if mesh is None:
        return jax.jit(lambda k, m: local(k, 0, m))(key, moments)
    axis = mesh.axis_names[0]
    fn = jax.shard_map(
        lambda k, m: local(k, jax.lax.axis_index(axis), m),
        mesh=mesh,
        in_specs=(P(), P()),
        out_specs=(P(axis, None), P(axis), P(axis)),
        check_vma=False,
    )
    shardings = (
        NamedSharding(mesh, P(axis, None)),
        NamedSharding(mesh, P(axis)),
        NamedSharding(mesh, P(axis)),
    )
    return jax.jit(fn, out_shardings=shardings)(key, moments)


# -- exact search -------------------------------------------------------------


def cos64(q: np.ndarray, rows: np.ndarray) -> np.ndarray:
    """Cosine of each query with each row, float64: ``[q, rows]``."""
    q = q.astype(np.float64)
    rows = rows.astype(np.float64)
    dots = q @ rows.T
    qn = np.sqrt((q * q).sum(-1))[:, None]
    rn = np.sqrt((rows * rows).sum(-1))[None, :]
    return dots / np.maximum(qn * rn, 1e-30)


def prefill_candidates(seed: int, queries: np.ndarray, prefilled: int, moments, keep: int = 32):
    """For each query the ``keep`` best prefilled rows of every block, found
    on the device in float32 at ``HIGHEST`` — a shortlist far wider than any
    rounding could reorder — with the rows themselves, for the host to
    score exactly. Returns slots ``[q, c]`` and vectors ``[q, c, dim]``."""
    import jax
    import jax.numpy as jnp

    keep = min(keep, PREFILL_BLOCK)

    # the seed's key and moments go in as arguments: one program for every seed
    @jax.jit
    def shortlist(key, moments, q, block):
        rows = prefill_block(key, block, prefilled, moments)
        dots = jnp.matmul(q, rows.T, precision=jax.lax.Precision.HIGHEST)
        live = (block * PREFILL_BLOCK + jnp.arange(PREFILL_BLOCK)) < prefilled
        _, idx = jax.lax.top_k(jnp.where(live[None, :], dots, -jnp.inf), keep)
        return block * PREFILL_BLOCK + idx, rows[idx]

    key = prefill_key(seed)
    q_dev = jnp.asarray(queries, jnp.float32)
    slots, vecs = [], []
    for block in range(-(-prefilled // PREFILL_BLOCK)):
        s, v = shortlist(key, moments, q_dev, block)
        slots.append(np.asarray(s))
        vecs.append(np.asarray(v))
    return np.concatenate(slots, axis=1), np.concatenate(vecs, axis=1)


def prefill_rows(seed: int, slots: list[int], prefilled: int, moments) -> dict:
    """The vectors of the named prefilled slots, regenerated."""
    import jax

    key = prefill_key(seed)
    make = jax.jit(lambda key, moments, block: prefill_block(key, block, prefilled, moments))
    out: dict = {}
    by_block: dict = {}
    for slot in slots:
        by_block.setdefault(slot // PREFILL_BLOCK, []).append(slot)
    for block, members in by_block.items():
        rows = make(key, moments, block)
        local = np.asarray(rows[np.asarray([s % PREFILL_BLOCK for s in members])])
        out.update(zip(members, local))
    return out


def exact_top_k(
    seed: int,
    queries: np.ndarray,  # [q, dim] float32, as the sink saw them
    doc_vectors: np.ndarray,  # [n, dim] float32, as the sink saw them
    doc_live: np.ndarray,  # [q, n] bool: in the index at the query's commit
    prefilled: int,
    k: int,
    moments,
):
    """Per query the ``k`` best of prefilled rows and live documents, scored
    in float64. Returns scores ``[q, k]`` (descending) and ids ``[q, k]``:
    a prefilled row is its slot, document ``i`` is ``-1 - i``."""
    slots, vecs = prefill_candidates(seed, queries, prefilled, moments)
    pre = np.stack([cos64(queries[i : i + 1], vecs[i])[0] for i in range(len(queries))])
    pre = np.where(slots < prefilled, pre, -np.inf)
    if len(doc_vectors):
        docs = np.where(doc_live, cos64(queries, doc_vectors), -np.inf)
    else:
        docs = np.zeros((len(queries), 0))
    scores = np.concatenate([pre, docs], axis=1)
    ids = np.concatenate(
        [slots, np.broadcast_to(-1 - np.arange(docs.shape[1]), docs.shape)], axis=1
    )
    order = np.argsort(-scores, axis=1, kind="stable")[:, :k]
    return np.take_along_axis(scores, order, 1), np.take_along_axis(ids, order, 1)


def low_precision_top_k(
    seed: int,
    queries: np.ndarray,
    doc_vectors: np.ndarray,
    doc_live: np.ndarray,
    prefilled: int,
    k: int,
    moments,
    precision: str,
):
    """The control for the index: the same search over the same rows with
    the products at ``precision`` (``"high"``: three bf16 passes, the step
    below ``highest``), float32 throughout as the program scores. Returns
    per query ``(ids, scores)`` in :func:`exact_top_k`'s numbering."""
    import jax
    import jax.numpy as jnp

    slots, vecs = prefill_candidates(seed, queries, prefilled, moments)

    @jax.jit
    def scores_of(q, rows):  # [8, dim] (the query, repeated), [n, dim] -> [n]
        # a matrix product as the program makes it (a matrix-vector product
        # would not go through the unit whose passes ``precision`` counts)
        dots = jnp.einsum("qd,cd->qc", q, rows, precision=precision)[0]
        qn = jnp.sqrt(jnp.sum(q[0] * q[0]))
        rn = jnp.sqrt(jnp.sum(rows * rows, axis=-1))
        return dots / jnp.maximum(qn * rn, 1e-30)

    docs_dev = jnp.asarray(doc_vectors, jnp.float32)
    out = []
    for n in range(len(queries)):
        q = jnp.asarray(np.tile(queries[n], (8, 1)), jnp.float32)
        pre = np.where(slots[n] < prefilled, np.asarray(scores_of(q, jnp.asarray(vecs[n]))), -np.inf)
        if len(doc_vectors):
            docs = np.where(doc_live[n], np.asarray(scores_of(q, docs_dev)), -np.inf)
        else:
            docs = np.zeros(0)
        scores = np.concatenate([pre, docs])
        ids = np.concatenate([slots[n], -1 - np.arange(len(docs))])
        order = np.argsort(-scores, kind="stable")[:k]
        out.append(([int(i) for i in ids[order]], [float(x) for x in scores[order]]))
    return out
