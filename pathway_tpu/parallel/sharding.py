"""Sharding helpers: NamedShardings from the standard axis vocabulary.

Instead of the reference's per-record hash exchange (Key::shard, reference:
src/engine/value.rs:94-130), device state is laid out once with
`jax.sharding.NamedSharding` and XLA inserts the collectives. These helpers
keep PartitionSpec construction in one place so models, indexes and UDF
microbatches agree on axis names.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from pathway_tpu.parallel.mesh import DATA_AXIS


def named_sharding(mesh: Mesh, *spec: Any) -> NamedSharding:
    return NamedSharding(mesh, P(*spec))


def shard_map_norep(
    fn: Callable, *, mesh: Mesh, in_specs: Any, out_specs: Any
) -> Callable:
    """``jax.shard_map`` with replication checking off. The kernels here
    all reduce across an axis inside the mapped function, which the
    checker cannot see through — hence always off."""
    return jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        check_vma=False,
    )


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def shard_batch(mesh: Mesh, tree: Any, axis: str = DATA_AXIS) -> Any:
    """Put a host batch on device, sharded along dim 0 over ``axis``.

    Leading dims not divisible by the axis size are the caller's problem —
    microbatch padding (pathway_tpu/internals/udfs) guarantees divisibility
    before anything reaches the device.
    """
    sharding = named_sharding(mesh, axis)
    return jax.tree.map(lambda x: jax.device_put(x, sharding), tree)


def shard_params(
    mesh: Mesh,
    params: Any,
    spec_fn: Callable[[tuple, Any], P],
) -> Any:
    """Place a parameter pytree using ``spec_fn(path, leaf) -> PartitionSpec``."""

    def place(path: tuple, leaf: Any) -> Any:
        spec = spec_fn(path, leaf)
        return jax.device_put(leaf, NamedSharding(mesh, spec))

    return jax.tree_util.tree_map_with_path(place, params)


def tree_specs(params: Any, spec_fn: Callable[[tuple, Any], P]) -> Any:
    """A pytree of PartitionSpecs matching ``params`` (for jit in/out shardings)."""
    return jax.tree_util.tree_map_with_path(spec_fn, params)
