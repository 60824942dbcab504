"""Reduce pattern — deterministic tree reductions.

Cilk reducers give deterministic parallel reductions on CPU; on TPU the
same guarantee comes from XLA's fixed reduction trees and ``lax.psum``
across shards. ``pattern_reduce`` reduces locally then across the mesh.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P


from repro.core.patterns.dist import Dist

_LOCAL_REDUCERS = {
    "sum": jnp.sum,
    "max": jnp.max,
    "min": jnp.min,
}

_CROSS_REDUCERS = {
    "sum": lax.psum,
    "max": lax.pmax,
    "min": lax.pmin,
}


def pattern_reduce(kind: str, dist: Dist = Dist()) -> Callable:
    """Build a full-array reduction of the given kind ("sum"/"max"/"min")."""
    if kind not in _LOCAL_REDUCERS:
        raise ValueError(f"unknown reduction: {kind}")
    local = _LOCAL_REDUCERS[kind]

    if dist.is_local:
        return jax.jit(lambda x: local(x))

    axes = tuple(dist.batch_axes) + (
        (dist.space_axis,) if dist.space_axis else ()
    )
    spec = P(dist.batch_axes, dist.space_axis)
    cross = _CROSS_REDUCERS[kind]

    @jax.jit
    def run(x):
        x = jax.device_put(x, NamedSharding(dist.mesh, spec))
        shard_fn = jax.shard_map(
            lambda xl: cross(local(xl), axes),
            mesh=dist.mesh,
            in_specs=spec,
            out_specs=P(),
            check_vma=False,
        )
        return shard_fn(x)

    return run


def tree_allreduce(x: jax.Array, axis_name: str) -> jax.Array:
    """All-reduce across a mesh axis (for use inside shard_map)."""
    return lax.psum(x, axis_name)
