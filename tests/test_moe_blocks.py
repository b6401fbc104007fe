"""``ops.moe.routed_experts`` walks the sorted pairs that lie in a group, in
blocks, as many as they fill: against a plain loop over the experts in
float32, at shapes longer than a block and at every edge of the block count;
at a decode step's shape the program holds no loop; and the count of the rows
walked is the blocks', from the kernel's own arithmetic to ``chat.fetch``;
and the tiles ``tiling`` gives each answerer's products, which change where
the chip runs them and not what they give."""

from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pathway_tpu.internals import tracing
from pathway_tpu.models import decoder as dec_mod
from pathway_tpu.ops import moe

HIDDEN, WIDTH, EXPERTS, OF = 16, 8, 6, 9  # six experts held here, of a router nine wide


def _weights(seed: int = 39):
    rng = np.random.default_rng(seed)
    gate_up = jnp.asarray(rng.normal(size=(EXPERTS, HIDDEN, 2 * WIDTH)) / 4, jnp.float32)
    down = jnp.asarray(rng.normal(size=(EXPERTS, WIDTH, HIDDEN)) / 3, jnp.float32)
    return gate_up, down


def _plain(h, weights, experts, gate_up, down, computed):
    """A loop over the experts, each over the pairs that are computed and
    name it, in float32: what ``routed_experts`` has to give, and the sizes."""
    h, weights, experts = np.asarray(h, np.float32), np.asarray(weights, np.float32), np.asarray(experts)
    out = np.zeros(h.shape, np.float32)
    sizes = np.zeros(gate_up.shape[0], np.int32)
    for e in range(gate_up.shape[0]):
        token, choice = np.nonzero((experts == e) & computed)
        gu = h[token] @ np.asarray(gate_up[e], np.float32)
        act = gu[:, :WIDTH] / (1 + np.exp(-gu[:, :WIDTH])) * gu[:, WIDTH:]
        np.add.at(out, token, weights[token, choice][:, None] * (act @ np.asarray(down[e], np.float32)))
        sizes[e] = len(token)
    return out, sizes


def _first_pairs_here(n: int, k: int, here: int, rng) -> np.ndarray:
    """Choices of which exactly the first ``here`` (in token, choice order)
    name a held expert, the others one held elsewhere."""
    experts = np.full(n * k, EXPERTS + 1, np.int32)
    experts[:here] = rng.integers(0, EXPERTS, here)
    return experts.reshape(n, k)


def _case(name: str):
    """``(n, k, experts [n, k], counted or None, held or None, computed pairs)`` of a named case."""
    rng = np.random.default_rng(sum(map(ord, name)))
    n, k = 1536, 4  # 6,144 pairs: three blocks where every pair is computed
    block = moe.BLOCK_ROWS
    assert n * k > 2 * block, "the cases are made for a call of more than two blocks"
    everywhere = rng.integers(0, OF, (n, k)).astype(np.int32)
    some = rng.random(n) < 0.3
    if name == "no pair computed":
        return n, k, _first_pairs_here(n, k, 0, rng), None, (0, EXPERTS), 0
    if name == "one pair":
        return n, k, _first_pairs_here(n, k, 1, rng), None, (0, EXPERTS), 1
    if name == "exactly one block":
        return n, k, _first_pairs_here(n, k, block, rng), None, (0, EXPERTS), block
    if name == "a block and one pair":
        return n, k, _first_pairs_here(n, k, block + 1, rng), None, (0, EXPERTS), block + 1
    if name == "every pair computed":
        return n, k, rng.integers(0, EXPERTS, (n, k)).astype(np.int32), None, None, n * k
    if name == "all tokens on one expert":
        return n, k, np.full((n, k), 3, np.int32), None, None, n * k
    if name == "counted":
        return n, k, rng.integers(0, EXPERTS, (n, k)).astype(np.int32), some, None, int(some.sum()) * k
    if name == "held":
        return n, k, everywhere, None, (0, EXPERTS), int((everywhere < EXPERTS).sum())
    if name == "held from the third expert on":
        first = 2  # the weights are experts 2..7's of the nine
        inside = (everywhere >= first) & (everywhere < first + EXPERTS)
        return n, k, everywhere, None, (first, EXPERTS), int(inside.sum())
    if name == "counted and held":
        return n, k, everywhere, some, (0, EXPERTS), int(((everywhere < EXPERTS) & some[:, None]).sum())
    if name == "pairs that fill no whole number of blocks":
        n, k = 1100, 3  # 3,300 pairs: the last block is padded
        assert (n * k) % moe.BLOCK_ROWS
        return n, k, rng.integers(0, EXPERTS, (n, k)).astype(np.int32), None, None, n * k
    raise KeyError(name)


CASES = [
    "no pair computed", "one pair", "exactly one block", "a block and one pair", "every pair computed",
    "all tokens on one expert", "counted", "held", "held from the third expert on", "counted and held",
    "pairs that fill no whole number of blocks",
]


@pytest.mark.parametrize("name", CASES)
def test_the_blocks_give_what_a_loop_over_the_experts_gives(name):
    n, k, experts, counted, held, in_groups = _case(name)
    rng = np.random.default_rng(7)
    h = jnp.asarray(rng.normal(size=(n, HIDDEN)), jnp.float32)
    weights = jnp.asarray(rng.uniform(0.1, 1.0, (n, k)), jnp.float32)
    gate_up, down = _weights()
    computed = np.ones((n, k), bool) if counted is None else np.broadcast_to(counted[:, None], (n, k)).copy()
    numbered = experts
    if held is not None:
        numbered = experts - held[0]
        computed &= (numbered >= 0) & (numbered < EXPERTS)
    want, want_sizes = _plain(h, weights, numbered, gate_up, down, computed)
    assert int(want_sizes.sum()) == in_groups
    fn = jax.jit(lambda h, c: moe.routed_experts(h, weights, jnp.asarray(experts), gate_up, down, c, held))
    mask = None if counted is None else jnp.asarray(counted)
    y, sizes = fn(h, mask)
    np.testing.assert_array_equal(np.asarray(sizes), want_sizes)
    np.testing.assert_allclose(np.asarray(y), want, atol=2e-5, rtol=1e-5)
    # the rows of the tokens that took no expert here are zero, not small
    assert not np.asarray(y)[~computed.any(1)].any()
    # what those tokens hold is never gathered into a product: poisoned, the
    # others' rows and the sizes are the same to the bit
    idle = ~computed.any(1)
    if idle.any():
        poison = np.where(np.arange(n) % 2 == 0, np.inf, np.nan).astype(np.float32)[:, None]
        y2, sizes2 = fn(jnp.where(jnp.asarray(idle)[:, None], poison, h), mask)
        np.testing.assert_array_equal(np.asarray(y2)[~idle], np.asarray(y)[~idle])
        assert not np.asarray(y2)[idle].any()
        np.testing.assert_array_equal(np.asarray(sizes2), np.asarray(sizes))
    # and the count of what was walked is whole blocks as far as the pairs in a group reach
    block = moe.BLOCK_ROWS
    assert int(moe.rows_walked(sizes, n * k)) == -(-in_groups // block) * block


def test_one_product_over_all_the_rows_gives_the_same(monkeypatch):
    """The form a call no longer than a block takes, at a longer call's
    shape: the blocks change what is walked, not what comes out."""
    n, k, experts, counted, held, _ = _case("counted and held")
    rng = np.random.default_rng(8)
    h = jnp.asarray(rng.normal(size=(n, HIDDEN)), jnp.float32)
    weights = jnp.asarray(rng.uniform(0.1, 1.0, (n, k)), jnp.float32)
    args = (h, weights, jnp.asarray(experts), *_weights(), jnp.asarray(counted), held)
    blocks, sizes = moe.routed_experts(*args)
    monkeypatch.setattr(moe, "BLOCK_ROWS", n * k)
    whole, whole_sizes = moe.routed_experts(*args)
    np.testing.assert_allclose(np.asarray(blocks), np.asarray(whole), atol=1e-6, rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(sizes), np.asarray(whole_sizes))


def _primitives(jaxpr) -> list[str]:
    """The names of a program's operations, those inside its calls and loops too."""
    names = []
    for eqn in jaxpr.eqns:
        names.append(eqn.primitive.name)
        for param in eqn.params.values():
            for inner in param if isinstance(param, (list, tuple)) else [param]:
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    names.extend(_primitives(inner))
    return names


@pytest.mark.parametrize("rows,k", [(16, 8), (8, 6), (8, 4)])  # a decode step of the three answerers' cells
def test_a_decode_steps_program_holds_no_loop(rows, k):
    gate_up, down = _weights()

    def program(n: int) -> list[str]:
        args = (
            jnp.zeros((n, HIDDEN), jnp.float32), jnp.ones((n, k), jnp.float32), jnp.zeros((n, k), jnp.int32),
            gate_up, down, jnp.ones((n,), bool),
        )
        return _primitives(jax.make_jaxpr(lambda *a: moe.routed_experts(*a, (0, EXPERTS)))(*args).jaxpr)

    step = program(rows)
    assert "while" not in step and "cond" not in step and "scan" not in step
    assert step.count("ragged_dot_general") == 2 and step.count("gather") == 2  # the rows in, the result back
    # where a call is longer than a block there is one loop, with the gather and the two products inside it
    long = program(moe.BLOCK_ROWS // k + 1)
    assert long.count("while") == 1 and long.count("ragged_dot_general") == 2


def test_the_rows_walked_are_whole_blocks_of_a_hand_made_sizes():
    pairs = 5 * moe.BLOCK_ROWS
    block = moe.BLOCK_ROWS
    walked = lambda *sizes: int(moe.rows_walked(jnp.asarray(sizes, jnp.int32), pairs))  # noqa: E731
    assert walked(0, 0, 0) == 0
    assert walked(0, 1, 0) == block
    assert walked(block - 1, 1, 0) == block
    assert walked(block - 1, 1, 1) == 2 * block
    assert walked(block, block, 3 * block) == pairs
    # a call no longer than a block is handed whole, whatever lies in a group
    assert int(moe.rows_walked(jnp.asarray([0, 0, 0], jnp.int32), block)) == block
    assert int(moe.rows_walked(jnp.asarray([3, 0, 1], jnp.int32), 128)) == 128


def _this_threads_stages() -> dict:
    totals = tracing.stage_totals()
    tables = [totals["stages"], *totals["threads"].values()]
    return next((t for t in tables if "chat.batch" in t), {})


def test_a_pass_and_a_chat_count_the_rows_walked_layer_by_layer(monkeypatch):
    from pathway_tpu.xpacks.llm.llms import TpuPipelineChat

    block = 64
    monkeypatch.setattr(moe, "BLOCK_ROWS", block)
    cfg = dec_mod.tiny_latent_moe_decoder()
    expert_layers = sum(kind == "experts" for kind in cfg.layer_pattern)
    params = dec_mod.init_decoder_params(jax.random.key(3), cfg, jnp.float32)
    rng = np.random.default_rng(5)
    ids = jnp.asarray(rng.integers(4, cfg.vocab_size, (4, 32)), jnp.int32)
    mask = jnp.asarray(np.arange(32)[None, :] >= np.asarray([0, 20, 32, 32])[:, None])  # 32, 12, 0 and 0 real tokens
    _, cache, offset, stats = dec_mod.prefill(params, ids, mask, cfg, 40)
    load = np.asarray(stats.load)  # [expert layers, experts]: one group of rows, so a layer's sum is a product's
    assert 4 * 32 * cfg.experts_per_token > block and load.shape[0] == expert_layers
    assert int(stats.walked) == sum(-(-int(layer.sum()) // block) * block for layer in load)
    assert int(stats.walked) < expert_layers * 4 * 32 * cfg.experts_per_token  # the padding's pairs are not walked
    # a decode step is no longer than a block: its pairs are walked whole, padding rows' too
    _, _, step = dec_mod.decode_step(params, ids[:, -1], cache, offset, cfg, jnp.asarray([True, True, False, False]))
    assert int(step.walked) == expert_layers * 4 * cfg.experts_per_token

    chat = TpuPipelineChat(cfg, max_new_tokens=5, max_prompt_len=32, max_batch_size=4, prompt_buckets=[32], eos_id=None)
    chat._fn(["one two three"])
    before = _this_threads_stages()["chat.fetch"]["counts"]
    chat._fn(["a b c d e f g", "h i j"])
    after = _this_threads_stages()["chat.fetch"]["counts"]
    fetch = {name: value - before.get(name, 0) for name, value in after.items()}
    made = chat.last_generation
    assert fetch["expert_rows_walked"] == made["expert_rows_walked"]
    decoded = 4 * expert_layers * 4 * cfg.experts_per_token  # four steps of four rows
    prefilled = fetch["expert_rows_walked"] - decoded
    assert prefilled % block == 0
    least = -(-made["prefill_pairs_held"] // block) * block
    assert least <= prefilled <= least + (expert_layers - 1) * block
    assert fetch["expert_rows_walked"] < fetch["expert_pairs"]  # the share the metric reads is under 100
    for key in ("rows", "bucket", "tokens", "logits", "prompt_tokens", "expert_load", "prefill_touched",
                "decode_touched", "prefill_pairs_held", "decode_pairs_held"):
        assert key in made  # the benchmark's pipelines read these


def test_a_row_of_padding_goes_through_no_shared_expert_where_the_call_is_walked_in_blocks(monkeypatch):
    cfg = dec_mod.tiny_latent_moe_decoder()
    assert cfg.n_shared_experts
    params = dec_mod.init_decoder_params(jax.random.key(4), cfg, jnp.float32)
    lp = next(lp for lp, kind in zip(params["layers"], cfg.layer_pattern) if kind == "experts")
    rng = np.random.default_rng(6)
    h = jnp.asarray(rng.normal(size=(4, 32, cfg.hidden)), jnp.float32)
    counted = jnp.asarray(np.arange(32)[None, :] >= np.asarray([0, 20, 32, 32])[:, None])  # rows 2 and 3 are padding
    whole, load, touched, walked = dec_mod._experts_layer(h, lp, cfg, counted)
    assert int(walked) == 4 * 32 * cfg.experts_per_token  # no longer than a block: as before
    assert np.asarray(whole)[2:].any()  # the shared experts ran over the padding rows
    monkeypatch.setattr(moe, "BLOCK_ROWS", 64)
    blocks, load2, touched2, walked2 = dec_mod._experts_layer(h, lp, cfg, counted)
    real = np.asarray(counted)
    np.testing.assert_allclose(np.asarray(blocks)[real], np.asarray(whole)[real], atol=1e-6, rtol=1e-6)
    assert not np.asarray(blocks)[2:].any()  # neither kind of expert: zero
    np.testing.assert_array_equal(np.asarray(load2), np.asarray(load))
    assert int(touched2) == int(touched) and int(walked2) == -(-int(load.sum()) // 64) * 64
    # whatever the padding rows hold: the real tokens' results are the same to the bit
    poisoned = h.at[2].set(jnp.inf).at[3].set(jnp.nan)
    again, *_ = dec_mod._experts_layer(poisoned, lp, cfg, counted)
    np.testing.assert_array_equal(np.asarray(again)[real], np.asarray(blocks)[real])
    assert not np.asarray(again)[2:].any()


# -- the tiles of a grouped product -------------------------------------------

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark", "configs")

#: the tiles of each answerer's products (a decode step's gate | up and down,
#: then a prefill block's): ``None`` is the compiler's own; DeepSeek-V2-Lite's
#: expert, 1,408 = 11 x 128 wide, moves whole, the others as they did before
TILES = {
    "command-a-plus-rag-answerer": (None, None, None, None),
    "dsv2lite-rag-answerer": ("16,1024,1408", "16,1408,1024", "256,1024,1408", "256,1408,1024"),
    "lfm2-24b-a2b-rag-answerer": (None, None, None, None),
    "mellum2-12b-a2.5b-rag-answerer": ("64,1152,896", "64,896,1152", "512,1152,896", "512,896,1152"),
}


@pytest.mark.parametrize("name", sorted(TILES))
def test_each_answerers_products_take_their_pinned_tiles(name):
    with open(os.path.join(CONFIGS, f"{name}.json")) as fh:
        config = json.load(fh)
    cfg = dec_mod.DecoderConfig.from_hf(config)
    tree = jax.eval_shape(lambda: dec_mod.init_decoder_params(jax.random.key(0), cfg, jnp.bfloat16))
    layer = next(lp for lp, kind in zip(tree["layers"], cfg.layer_pattern) if kind == "experts")
    products = [layer["experts_gate_w"].shape[1:], layer["experts_down_w"].shape[1:]]
    decode = config["chat"]["max_batch_size"] * cfg.experts_per_token  # a step's pairs: one product
    assert not moe.in_blocks(decode)
    got = tuple(moe.tiling(rows, *product) for rows in (decode, moe.BLOCK_ROWS) for product in products)
    assert got == TILES[name]


#: DeepSeek-V2-Lite's widths (hidden 2,048, an expert 1,408 wide) over 4 of its 64 experts, 2 chosen a token
DSV2_HIDDEN, DSV2_WIDTH, DSV2_EXPERTS, DSV2_K = 2048, 1408, 4, 2


@pytest.fixture(scope="module")
def dsv2_experts():
    rng = np.random.default_rng(48)
    shapes = (DSV2_EXPERTS, DSV2_HIDDEN, 2 * DSV2_WIDTH), (DSV2_EXPERTS, DSV2_WIDTH, DSV2_HIDDEN)
    return tuple(jnp.asarray(rng.standard_normal(s, np.float32) * 0.02, jnp.bfloat16) for s in shapes)


@pytest.mark.parametrize("tokens", [8, 40])  # a decode step; a call walked in blocks
def test_at_deepseek_v2_lites_widths_the_tiled_products_give_the_untiled_ones(tokens, dsv2_experts, monkeypatch):
    """Each product carries the rule's tiles and gives what
    ``lax.ragged_dot`` gives without them, and so does the layer."""
    gate_up, down = dsv2_experts
    k = DSV2_K
    monkeypatch.setattr(moe, "BLOCK_ROWS", 64)  # 40 tokens' 80 pairs in two blocks, as a prefill's are
    rng = np.random.default_rng(tokens)
    h = jnp.asarray(rng.standard_normal((tokens, DSV2_HIDDEN), np.float32), jnp.bfloat16)
    choice = jnp.asarray(np.stack([rng.choice(DSV2_EXPERTS, k, replace=False) for _ in range(tokens)]), jnp.int32)
    weights = jnp.asarray(rng.uniform(0.05, 0.3, (tokens, k)), jnp.float32)
    counted = jnp.arange(tokens) >= 2  # two tokens of padding
    rows = min(tokens * k, moe.BLOCK_ROWS)
    groups = moe.expert_sizes(choice[: rows // k], DSV2_EXPERTS)
    for x, w in ((h[: rows // k].repeat(k, 0), gate_up), (jnp.ones((rows, DSV2_WIDTH), jnp.bfloat16), down)):
        assert moe.tiling(*x.shape, w.shape[-1]) is not None
        assert "ragged_dot_tiling" in jax.jit(moe.grouped_product).lower(x, w, groups).as_text()
        np.testing.assert_array_equal(
            np.asarray(jax.jit(moe.grouped_product)(x, w, groups)),
            np.asarray(jax.lax.ragged_dot(x, w, groups, preferred_element_type=jnp.float32)),
        )
    args = (h, weights, choice, gate_up, down, counted)
    tiled, sizes = jax.jit(moe.routed_experts)(*args)
    monkeypatch.setattr(moe, "tiling", lambda *shape: None)
    untiled, untiled_sizes = jax.jit(moe.routed_experts)(*args)
    np.testing.assert_array_equal(np.asarray(tiled), np.asarray(untiled))
    np.testing.assert_array_equal(np.asarray(sizes), np.asarray(untiled_sizes))
    assert np.asarray(tiled)[2:].any() and not np.asarray(tiled)[:2].any()
