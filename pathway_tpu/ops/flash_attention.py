"""Pallas TPU flash attention — fused attention for the encoder/ViT
``attn_fn`` seam (models/transformer.py encoder_forward and
models/vision.py vision_forward both accept any AttnFn; the causal GQA
decoder keeps its own cache-aware attention).

Why a kernel: dense attention materializes the ``[t, t]`` score matrix in
HBM per (batch, head); at long context that matrix dominates bandwidth.
Flash attention streams K/V tiles through VMEM with an online softmax, so
HBM traffic stays O(t·d) (the How-to-Scale-Your-Model recipe; same
algorithm as Dao et al.'s FlashAttention, laid out for the MXU/VPU).

Shape contract matches ``dense_attention``: q/k/v ``[b, t, h, d]``, mask
``[b, t]`` bool (True = real token) or None -> ``[b, t, h, d]``.

Details:
- grid is one program per (batch·head, q tile); K/V ride whole-sequence
  VMEM blocks and the inner loop walks K in ``block_k`` steps.
- every block's last two dims are either a multiple of the (8, 128) tile
  or the array's full extent — the condition the Mosaic lowering checks.
  Per-row statistics (running max, denominator, logsumexp, delta) are
  ``[rows, 1]`` columns and per-key values (bias, dbias) are ``[1, keys]``
  rows, inside the kernels and in HBM, so nothing is relaid out between a
  sublane and a lane vector.
- the padding bias is ``[b, n_k, 1, block_k]`` — the k loop picks its tile
  by a leading-dim index, and the index map folds head into batch
  (``bh // h``), so the h-fold broadcast never materializes.
- sequences that don't divide the 128 tile are padded with masked keys /
  zero queries and sliced back (model paths bucket to powers of two, so
  padding is the exception, not the rule).
- matmuls take the inputs in their own dtype (bf16 stays bf16 on the MXU)
  and accumulate in f32.
- the plain forward writes only the output; under differentiation
  (``jax.custom_vjp``) the forward also emits the per-row logsumexp, and
  two Pallas kernels recompute probabilities tile-by-tile (dQ over q
  tiles, dK/dV over k tiles, the standard flash backward split), so the
  backward's HBM traffic stays O(t·d) like the forward's. The logsumexp
  and delta columns are ``[bh, t, 1]``, which a TPU pads to 128 lanes in
  HBM — a cost the training path pays and inference does not.
- on a TPU the kernels compile; anywhere else (CPU tests, virtual meshes)
  they run in Pallas interpret mode.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_NEG_INF = -1e30
_BLOCK = 128

_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_TN = (((0,), (0,)), ((), ()))  # a.T @ b


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _rows(ref, tile, block):
    """``block`` rows of a ``[1, t, x]`` ref starting at tile ``tile``."""
    return ref[0, pl.ds(pl.multiple_of(tile * block, block), block), :]


def _flash_kernel(
    q_ref,  # [1, block_q, d]
    k_ref,  # [1, t, d]
    v_ref,  # [1, t, d]
    bias_ref,  # [1, n_k, 1, block_k]  additive mask (0 or -inf)
    o_ref,  # [1, block_q, d]
    lse_ref=None,  # [1, block_q, 1]  per-row logsumexp (backward residual)
    *,
    scale: float,
):
    _one, n_k, _one, block_k = bias_ref.shape
    _one, block_q, d = q_ref.shape
    q = q_ref[0]

    def body(j, carry):
        acc, m_prev, l_prev = carry
        k_tile = _rows(k_ref, j, block_k)
        v_tile = _rows(v_ref, j, block_k)
        s = _dot(q, k_tile, _NT) * scale + bias_ref[0, j]  # [block_q, block_k]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + p.sum(axis=-1, keepdims=True)
        acc = acc * alpha + _dot(p.astype(v_tile.dtype), v_tile)
        return acc, m_new, l_new

    acc0 = jnp.zeros((block_q, d), jnp.float32)
    m0 = jnp.full((block_q, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, n_k, body, (acc0, m0, l0))
    l_safe = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / l_safe).astype(o_ref.dtype)
    if lse_ref is not None:
        lse_ref[0] = m + jnp.log(l_safe)


def _specs(t: int, d: int, block: int, h: int):
    """The four BlockSpecs every kernel here shares, for grid (bh, tile)."""
    tile = pl.BlockSpec((1, block, d), lambda b, i: (b, i, 0))
    whole = pl.BlockSpec((1, t, d), lambda b, i: (b, 0, 0))
    bias = pl.BlockSpec(
        (1, t // block, 1, block), lambda b, i, h=h: (b // h, 0, 0, 0)
    )
    col = pl.BlockSpec((1, block, 1), lambda b, i: (b, i, 0))
    return tile, whole, bias, col


def _flash_bhtd(
    q: jax.Array,  # [bh, t, d]
    k: jax.Array,
    v: jax.Array,
    bias: jax.Array,  # [b, n_k, 1, block] — heads fold via the index map
    h: int,
    save_lse: bool,
    interpret: bool,
):
    """-> out ``[bh, t, d]``, plus lse ``[bh, t, 1]`` when ``save_lse``."""
    bh, t, d = q.shape
    block = bias.shape[-1]
    tile, whole, bias_spec, col = _specs(t, d, block, h)
    out_shape = jax.ShapeDtypeStruct((bh, t, d), q.dtype)
    out_specs = tile
    if save_lse:
        out_shape = (out_shape, jax.ShapeDtypeStruct((bh, t, 1), jnp.float32))
        out_specs = (tile, col)
    return pl.pallas_call(
        functools.partial(_flash_kernel, scale=1.0 / math.sqrt(d)),
        out_shape=out_shape,
        grid=(bh, t // block),
        in_specs=[tile, whole, whole, bias_spec],
        out_specs=out_specs,
        interpret=interpret,
    )(q, k, v, bias)


def _flash_bwd_dq_kernel(
    q_ref,  # [1, block_q, d]
    k_ref,  # [1, t, d]
    v_ref,  # [1, t, d]
    bias_ref,  # [1, n_k, 1, block_k]
    do_ref,  # [1, block_q, d]
    lse_ref,  # [1, block_q, 1]
    delta_ref,  # [1, block_q, 1]  rowsum(dO * O)
    dq_ref,  # [1, block_q, d]
    *,
    scale: float,
):
    _one, n_k, _one, block_k = bias_ref.shape
    _one, block_q, d = q_ref.shape
    q = q_ref[0]
    do = do_ref[0]
    lse = lse_ref[0]
    delta = delta_ref[0]

    def body(j, acc):
        k_tile = _rows(k_ref, j, block_k)
        v_tile = _rows(v_ref, j, block_k)
        s = _dot(q, k_tile, _NT) * scale + bias_ref[0, j]
        p = jnp.exp(s - lse)  # true softmax probs via saved lse
        dp = _dot(do, v_tile, _NT)  # [block_q, block_k]
        ds = p * (dp - delta)
        return acc + _dot(ds.astype(k_tile.dtype), k_tile)

    acc0 = jnp.zeros((block_q, d), jnp.float32)
    acc = jax.lax.fori_loop(0, n_k, body, acc0)
    dq_ref[0] = (acc * scale).astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(
    q_ref,  # [1, t, d]
    k_ref,  # [1, block_k, d]
    v_ref,  # [1, block_k, d]
    bias_ref,  # [1, n_k, 1, block_k]
    do_ref,  # [1, t, d]
    lse_ref,  # [1, t, 1]
    delta_ref,  # [1, t, 1]
    dk_ref,  # [1, block_k, d]
    dv_ref,  # [1, block_k, d]
    dbias_ref,  # [1, 1, 1, block_k]  sum of dS over this bh slice's rows
    *,
    scale: float,
):
    _one, block_k, d = k_ref.shape
    block_q = block_k
    n_q = q_ref.shape[1] // block_q
    k = k_ref[0]
    v = v_ref[0]
    bias = bias_ref[0, pl.program_id(1)]  # [1, block_k]

    def body(i, carry):
        dk_acc, dv_acc, db_acc = carry
        q_tile = _rows(q_ref, i, block_q)
        do_tile = _rows(do_ref, i, block_q)
        lse = _rows(lse_ref, i, block_q)
        delta = _rows(delta_ref, i, block_q)
        s = _dot(q_tile, k, _NT) * scale + bias  # [block_q, block_k]
        p = jnp.exp(s - lse)
        dv_acc = dv_acc + _dot(p.astype(do_tile.dtype), do_tile, _TN)
        dp = _dot(do_tile, v, _NT)
        ds = p * (dp - delta)
        dk_acc = dk_acc + _dot(ds.astype(q_tile.dtype), q_tile, _TN)
        db_acc = db_acc + ds.sum(axis=0, keepdims=True)  # bias is unscaled
        return dk_acc, dv_acc, db_acc

    zeros = jnp.zeros((block_k, d), jnp.float32)
    db0 = jnp.zeros((1, block_k), jnp.float32)
    dk, dv, db = jax.lax.fori_loop(0, n_q, body, (zeros, zeros, db0))
    dk_ref[0] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)
    dbias_ref[0, 0] = db


def _flash_bwd_bhtd(
    q: jax.Array,  # [bh, t, d]
    k: jax.Array,
    v: jax.Array,
    bias: jax.Array,  # [b, n_k, 1, block]
    do: jax.Array,  # [bh, t, d]
    lse: jax.Array,  # [bh, t, 1]
    delta: jax.Array,  # [bh, t, 1]
    h: int,
    interpret: bool,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """-> dq, dk, dv ``[bh, t, d]`` and dbias ``[bh, n_k, 1, block]``."""
    bh, t, d = q.shape
    block = bias.shape[-1]
    n = t // block
    scale = 1.0 / math.sqrt(d)
    tile, whole, bias_spec, col = _specs(t, d, block, h)
    whole_col = pl.BlockSpec((1, t, 1), lambda b, j: (b, 0, 0))
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, scale=scale),
        out_shape=jax.ShapeDtypeStruct((bh, t, d), q.dtype),
        grid=(bh, n),
        in_specs=[tile, whole, whole, bias_spec, tile, col, col],
        out_specs=tile,
        interpret=interpret,
    )(q, k, v, bias, do, lse, delta)
    dk, dv, dbias = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, scale=scale),
        out_shape=(
            jax.ShapeDtypeStruct((bh, t, d), k.dtype),
            jax.ShapeDtypeStruct((bh, t, d), v.dtype),
            jax.ShapeDtypeStruct((bh, n, 1, block), jnp.float32),
        ),
        grid=(bh, n),
        in_specs=[whole, tile, tile, bias_spec, whole, whole_col, whole_col],
        out_specs=(
            tile,
            tile,
            pl.BlockSpec((1, 1, 1, block), lambda b, j: (b, j, 0, 0)),
        ),
        interpret=interpret,
    )(q, k, v, bias, do, lse, delta)
    return dq, dk, dv, dbias


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pad_t(x, pad, fill=0.0):
    if not pad:
        return x
    shape = (x.shape[0], pad) + x.shape[2:]
    return jnp.concatenate([x, jnp.full(shape, fill, x.dtype)], axis=1)


def _prepare(q, k, v, bias):
    """Pad to the tile size, fold [b,t,h,d] -> [b*h,t,d] and tile the
    bias [b,t] -> [b, n_k, 1, block]."""
    b, t, h, d = q.shape
    block = min(t, _BLOCK)
    pad = (-t) % block
    # tail tile: masked keys contribute -inf bias; extra query rows
    # compute garbage that is sliced away on exit
    q, k, v = _pad_t(q, pad), _pad_t(k, pad), _pad_t(v, pad)
    bias = _pad_t(bias, pad, fill=_NEG_INF).reshape(b, -1, 1, block)

    def to_bhtd(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, x.shape[1], d)

    return to_bhtd(q), to_bhtd(k), to_bhtd(v), bias


def _from_bhtd(x, b, h, t):
    out = x.reshape(b, h, x.shape[1], -1).transpose(0, 2, 1, 3)
    return out[:, :t]


@jax.custom_vjp
def _flash_diff(q, k, v, bias):
    b, t, h, _d = q.shape
    qb, kb, vb, bias_t = _prepare(q, k, v, bias)
    out_b = _flash_bhtd(
        qb, kb, vb, bias_t, h, save_lse=False, interpret=_interpret()
    )
    return _from_bhtd(out_b, b, h, t)


def _flash_diff_fwd(q, k, v, bias):
    b, t, h, _d = q.shape
    qb, kb, vb, bias_t = _prepare(q, k, v, bias)
    out_b, lse = _flash_bhtd(
        qb, kb, vb, bias_t, h, save_lse=True, interpret=_interpret()
    )
    return _from_bhtd(out_b, b, h, t), (qb, kb, vb, bias_t, out_b, lse, t)


def _flash_diff_bwd(res, g):
    qb, kb, vb, bias_t, out_b, lse, t = res
    b, h = g.shape[0], g.shape[2]
    tt, d = qb.shape[1:]
    do = _pad_t(g, tt - t).transpose(0, 2, 1, 3).reshape(b * h, tt, d)
    delta = (do.astype(jnp.float32) * out_b.astype(jnp.float32)).sum(
        -1, keepdims=True
    )
    dq, dk, dv, dbias_bh = _flash_bwd_bhtd(
        qb, kb, vb, bias_t, do, lse, delta, h, interpret=_interpret()
    )
    dbias = dbias_bh.reshape(b, h, tt).sum(axis=1)[:, :t]
    return (
        _from_bhtd(dq, b, h, t),
        _from_bhtd(dk, b, h, t),
        _from_bhtd(dv, b, h, t),
        dbias,
    )


_flash_diff.defvjp(_flash_diff_fwd, _flash_diff_bwd)


@jax.jit
def flash_attention(
    q: jax.Array,  # [b, t, h, d]
    k: jax.Array,
    v: jax.Array,
    mask: jax.Array | None,  # [b, t] bool
) -> jax.Array:
    """Drop-in ``AttnFn`` (models/transformer.py dense_attention
    contract), differentiable end to end (tiled flash backward)."""
    b, t = q.shape[:2]
    if mask is None:
        bias = jnp.zeros((b, t), jnp.float32)
    else:
        bias = jnp.where(mask, 0.0, _NEG_INF).astype(jnp.float32)
    return _flash_diff(q, k, v, bias)
