"""The tiles ``ops.moe.tiling`` gives each answerer's grouped products compile
for a TPU v5e at the configurations' real widths, inside the kernel's scoped
vector memory: compiled here for a chip that is described and not attached
(nothing runs, so this says nothing of results or times)."""

from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from pathway_tpu.models.decoder import DecoderConfig
from pathway_tpu.ops import moe

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark", "configs")
ANSWERERS = ["dsv2lite-rag-answerer", "mellum2-12b-a2.5b-rag-answerer"]  # the two whose widths take tiles of their own


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps the chip from being described
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _products(name: str) -> list[tuple[int, int, int, int]]:
    """(rows, contracted, out, experts) of a decode step's and a prefill
    block's two products in a cell of this answerer."""
    with open(os.path.join(CONFIGS, f"{name}.json")) as fh:
        config = json.load(fh)
    cfg = DecoderConfig.from_hf(config)
    decode = config["chat"]["max_batch_size"] * cfg.experts_per_token
    widths = [(cfg.hidden, 2 * cfg.moe_intermediate), (cfg.moe_intermediate, cfg.hidden)]
    return [(rows, c, o, cfg.n_routed_experts) for rows in (decode, moe.BLOCK_ROWS) for c, o in widths]


@pytest.mark.parametrize("name", ANSWERERS)
def test_the_rules_tiles_compile_for_a_v5e_at_real_widths(name, one_chip):
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)  # a described chip's program cannot be read back
    try:
        for rows, contracted, out, experts in _products(name):
            tiles = moe.tiling(rows, contracted, out)
            assert tiles is not None
            shapes = ((rows, contracted), jnp.bfloat16), ((experts, contracted, out), jnp.bfloat16), ((experts,), jnp.int32)
            args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip) for shape, dtype in shapes]
            compiled = jax.jit(moe.grouped_product).lower(*args).compile()  # raises where the tiles do not fit
            assert f'ragged_dot_tiling="{tiles}"' in compiled.as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cached)
