"""Routed experts: a router that scores every expert and a grouped product
over the experts tokens chose.

``route_top_k`` scores in float32 (the product at ``Precision.HIGHEST``: on
the chip a float32 product otherwise runs in one bfloat16 pass, and a near
tie would go to another expert than the one the published model takes) and
keeps the ``k`` largest, greedily, with their weights as they are or
renormalised: a softmax over all the experts, or a sigmoid of each. A router
with a selection bias (``select_bias``, one float32 an expert, a buffer the
published model balances its experts' load with) picks by score plus bias
and weighs by the score alone.

``routed_experts`` sorts the ``tokens * k`` (token, expert) pairs by expert
and runs each expert's gated MLP over its own rows with
``jax.lax.ragged_dot``: the work is that of the rows chosen, not of every
expert over every token, and an expert's weights are read only where it has
rows. There is no capacity: every pair is computed whatever the skew, all
tokens on one expert included. Padding takes no expert: given the mask of
real tokens, a padding token's pairs are sorted behind the last expert's
rows and belong to no group, an expert that only padding chose has no row
and its weights are not read, and a padding token's result is zero. The
per-expert row counts of the real tokens come back for the callers'
counters (``expert_sizes``).

What is not computed is not walked either. A call of more pairs than a block
(``BLOCK_ROWS``: a prefill's group of rows) goes through the products in
blocks of the sorted rows, as many blocks as the pairs in a group fill
(``rows_walked``; a traced trip count, so every pair is still computed where
every pair lies in a group): a block gathers its own rows of ``h``, takes of
each expert's group what lies in it, and writes its rows of the result; the
pairs behind the last group are never gathered, multiplied or written. The
way back is walked the same way: the result comes back to (token, choice)
order in blocks of tokens, and a block in which no token took a pair here is
not gathered. An expert whose group straddles two blocks is read twice. A
call no longer than a block (a decode step) is one product over all its rows
and holds no loop.

A grouped product moves an expert's weights in tiles. The compiler's own
tile on a width is the largest power of two up to 512 that divides it, so a
width of 7 x 128 (an expert 896 wide) or 9 x 256 (a hidden size of 2,304)
moves in tiles of 64-128 KB where widths of multiples of 512 move in 512 KB,
and a decode step that reads its touched experts runs far under the memory's
peak. Where a width is not a multiple of 512 the product is given tiles of
its own (``tiling``), chosen by their bytes: of the multiples of 128 that
divide each width, the pair whose tile of weights is at most 3 MiB with the
widest narrower side, where that is 512 or more (an expert 1,408 wide,
11 x 128, moves whole); and as many rows a tile as leave the kernel's tiles,
double-buffered, within 12 MiB of its 16 MiB of scoped vector memory.
Elsewhere the compiler's tiles stand.

A chip that holds a share of a layer's experts says which (``held``: the
first and how many; the weights it hands over are theirs): the router is as
wide as all the experts and chooses among all, and a pair whose expert lies
elsewhere goes where a padding token's goes, behind the last group. The
result is the part the held experts give; what the others would add is
another chip's to compute, and nothing here stands in for it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental.xla_metadata import set_xla_metadata


def route_top_k(
    h: jax.Array,  # [n, hidden]
    gate_w: jax.Array,  # [hidden, experts]
    k: int,
    *,
    renormalize: bool = False,
    scale: float = 1.0,
    scoring: str = "softmax",
    select_bias: jax.Array | None = None,  # [experts] float32
) -> tuple[jax.Array, jax.Array]:
    """Scores of all experts in float32 (a softmax over them, or with
    ``scoring`` ``"sigmoid"`` each expert's own), the ``k`` largest:
    weights ``[n, k]`` float32 and expert ids ``[n, k]`` int32. A tie goes to
    the expert with the lower id (``lax.top_k``). With ``select_bias`` the
    ``k`` largest of ``score + select_bias`` are chosen and their weights
    are the scores without it; renormalised, they are divided by their sum
    ``+ 1e-6``, as the published router that has such a bias divides."""
    logits = jnp.matmul(
        h.astype(jnp.float32),
        gate_w.astype(jnp.float32),
        precision=lax.Precision.HIGHEST,
    )
    scores = jax.nn.sigmoid(logits) if scoring == "sigmoid" else jax.nn.softmax(logits, axis=-1)
    if select_bias is None:
        weights, experts = lax.top_k(scores, k)
    else:
        _, experts = lax.top_k(scores + select_bias.astype(jnp.float32), k)
        weights = jnp.take_along_axis(scores, experts, axis=-1)
    if renormalize:
        weights = weights / (weights.sum(-1, keepdims=True) + (1e-20 if select_bias is None else 1e-6))
    return weights * scale, experts.astype(jnp.int32)


def expert_sizes(experts: jax.Array, n_experts: int) -> jax.Array:
    """How many of the (token, choice) pairs ``experts`` ``[n, k]`` name each
    expert, ``[n_experts]`` int32; an id outside ``[0, n_experts)`` is
    counted nowhere. A compare-and-sum: a scatter-add into so few bins
    collides."""
    hit = experts[:, :, None] == jnp.arange(n_experts, dtype=experts.dtype)
    return hit.sum((0, 1), dtype=jnp.int32)


#: the sorted (token, choice) rows one grouped product is handed where a call
#: holds more: fixed from a sweep on the chip over the prefill shapes of three
#: models (12,288 to 24,576 pairs, 16 and 64 experts, hidden 2,048 and 4,096:
#: 1,024 and 2,048 read alike, 4,096 worse; PERF.md section 6, PR 39)
BLOCK_ROWS = 2048


def in_blocks(pairs: int) -> bool:
    """Whether a call of ``pairs`` pairs is walked in blocks, as far as what
    is computed reaches (a prefill's group of rows), or is no longer than one
    and goes whole, in one product and no loop (a decode step)."""
    return pairs > BLOCK_ROWS


def rows_walked(sizes: jax.Array, pairs: int) -> jax.Array:
    """The sorted rows ``routed_experts`` gathers and multiplies for group
    sizes ``sizes`` in a call of ``pairs`` pairs, ``[]`` int32: whole blocks
    as far as the pairs in a group reach, or all ``pairs`` where the call
    goes whole."""
    if not in_blocks(pairs):
        return jnp.asarray(pairs, jnp.int32)
    return (sizes.sum(dtype=jnp.int32) + (BLOCK_ROWS - 1)) // BLOCK_ROWS * BLOCK_ROWS


#: the bytes of tiles ``tiling`` lets a grouped product's kernel hold: a tile
#: of rows and one of an expert's weights (bfloat16) and one of results
#: (float32), each double-buffered, in three quarters of the kernel's 16 MiB
#: of scoped vector memory; a tile of weights is at most a quarter of it
TILE_BYTES = 12 << 20


def tiling(rows: int, contracted: int, out: int) -> str | None:
    """The tiles (rows, contracted, output) of a grouped product of ``rows``
    sorted rows by bfloat16 weights ``[contracted, out]``, as the compiler's
    ``ragged_dot_tiling`` reads them, where a width is not a multiple of 512:
    of the multiples of 128 that divide each width, the pair whose tile of
    weights stays within a quarter of ``TILE_BYTES`` with the widest
    narrower side, then the larger, where that side is 512 or more; rows:
    the largest power of two up to 512 that divides them, as the
    compiler's, halved until the kernel's tiles fit ``TILE_BYTES``. ``None``
    elsewhere: the compiler's own tiles stand."""
    if contracted % 512 == 0 and out % 512 == 0:
        return None

    def sides(n: int) -> list[int]:
        return [t for t in range(128, n + 1, 128) if n % t == 0]

    pairs = [(c, o) for c in sides(contracted) for o in sides(out) if c * o * 2 <= TILE_BYTES // 4]
    c, o = max(pairs, key=lambda p: (min(p), p[0] * p[1]), default=(0, 0))
    if min(c, o) < 512:
        return None
    r = min(rows & -rows, 512)
    while 2 * (r * c * 2 + c * o * 2 + r * o * 4) > TILE_BYTES:
        r //= 2
    return f"{r},{c},{o}"


def grouped_product(x: jax.Array, w: jax.Array, groups: jax.Array) -> jax.Array:
    """``lax.ragged_dot`` of ``x`` ``[rows, k]`` by ``w`` ``[experts, k, n]``
    over ``groups``, float32, in ``tiling``'s tiles where it gives any."""
    tiles = tiling(*x.shape, w.shape[-1])
    if tiles is None:
        return lax.ragged_dot(x, w, groups, preferred_element_type=jnp.float32)
    with set_xla_metadata(ragged_dot_tiling=tiles):
        return lax.ragged_dot(x, w, groups, preferred_element_type=jnp.float32)


def routed_experts(
    h: jax.Array,  # [n, hidden]
    weights: jax.Array,  # [n, k] float32
    experts: jax.Array,  # [n, k] int32
    gate_up_w: jax.Array,  # [experts, hidden, 2 * width]: gate | up
    down_w: jax.Array,  # [experts, width, hidden]
    counted: jax.Array | None = None,  # [n] bool: False = a padding token
    held: tuple[int, int] | None = None,  # (first, count) of the experts whose weights these are
) -> tuple[jax.Array, jax.Array]:
    """``sum_i weights[:, i] * E_{experts[:, i]}(h)`` with ``E`` a gated SiLU
    MLP, ``[n, hidden]`` in ``h``'s dtype (float32 accumulation), and how
    many (token, choice) pairs each expert took, ``[experts]`` int32. Of the
    ``counted`` tokens alone where given: the others' pairs are in no
    expert's group and their rows of the result are zero. Of the ``held``
    experts alone where given (``experts`` numbers all of a layer's): the sum
    runs over the choices that lie among them."""
    n, k = experts.shape
    n_experts = gate_up_w.shape[0]
    computed = None if counted is None else jnp.broadcast_to(counted[:, None], (n, k))
    if held is not None:
        experts = experts - held[0]  # numbered as the weights are
        here = (experts >= 0) & (experts < n_experts)
        computed = here if computed is None else computed & here
    # a pair that is not computed sorts behind the last expert's rows, into no group
    keys = experts if computed is None else jnp.where(computed, experts, n_experts)
    order = jnp.argsort(keys.reshape(-1))  # stable: an expert's rows stay in token order
    token = order // k
    sizes = expert_sizes(keys, n_experts)
    back = jnp.argsort(order).reshape(n, k)  # where each pair's row lies in the sorted order
    pairs = n * k

    def product(rows_of: jax.Array, groups: jax.Array) -> jax.Array:
        """The experts over sorted rows, each the token's ``rows_of`` names,
        of which each expert's group is ``groups`` long: ``[rows, hidden]``
        float32."""
        gate_up = grouped_product(h[rows_of], gate_up_w.astype(h.dtype), groups)
        gate, up = jnp.split(gate_up, 2, axis=-1)
        act = (jax.nn.silu(gate) * up).astype(h.dtype)
        return grouped_product(act, down_w.astype(h.dtype), groups)

    def combine(out: jax.Array, back: jax.Array, weights: jax.Array, computed: jax.Array | None) -> jax.Array:
        """Some tokens' rows of the sorted-order result ``out`` back in
        (token, choice) order with a gather (the inverse permutation: a
        scatter-add over the sorted rows costs the chip far more), weighed
        and summed: ``[tokens, hidden]`` in ``h``'s dtype."""
        rows = out[back]  # [tokens, k, hidden] float32
        if computed is not None:
            # the rows past the groups hold whatever the device left there:
            # the mask decides what a pair outside every group adds (nothing,
            # whatever its weight), not those rows
            rows = jnp.where(computed[..., None], rows, 0.0)
            weights = jnp.where(computed, weights, 0.0)
        return (rows * weights[..., None]).sum(1).astype(h.dtype)

    if not in_blocks(pairs):
        return combine(product(token, sizes), back, weights, computed), sizes

    # the computed pairs are the first sizes.sum() of the sorted rows: as many
    # blocks as they fill are gathered, multiplied and written, and an
    # expert's group in a block is what of it lies there
    block = BLOCK_ROWS
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    token = jnp.pad(token, (0, -pairs % block))  # a last block's rows past the pairs lie in no group

    def one_block(i, out):
        lo = i * block
        within = jnp.clip(ends, lo, lo + block) - jnp.clip(starts, lo, lo + block)
        rows = product(lax.dynamic_slice(token, (lo,), (block,)), within)
        return lax.dynamic_update_slice(out, rows, (lo, 0))

    out = lax.fori_loop(
        0, rows_walked(sizes, pairs) // block, one_block, jnp.zeros((token.shape[0], h.shape[1]), jnp.float32)
    )
    # and back in blocks of the tokens that name a block of pairs: one in
    # which no token took a pair is not gathered, its rows of the result are zero
    tokens = max(block // k, 1)
    taken = jnp.ones((n, k), bool) if computed is None else computed

    def one_back(args):  # a block's (back, weights, taken)
        return lax.cond(
            args[2].any(), lambda: combine(out, *args), lambda: jnp.zeros((tokens, h.shape[1]), h.dtype)
        )

    y = lax.map(
        one_back, tuple(jnp.pad(a, ((0, -n % tokens), (0, 0))).reshape(-1, tokens, k) for a in (back, weights, taken))
    )
    return y.reshape(-1, h.shape[1])[:n], sizes
