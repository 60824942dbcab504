"""Map pattern — the ``cilk_for`` analogue.

On a multicore CPU the map pattern distributes loop iterations over cores
via work stealing. On TPU a map is (a) vectorized onto the VPU lanes by
XLA within a shard and (b) distributed across shards by ``shard_map``.
Load balance is static and exact (see ``partition.even_tiles``) instead of
emergent from a scheduler — determinism (paper claim C4) is structural.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P


from repro.core.patterns.dist import Dist


def pattern_map(fn: Callable, dist: Dist = Dist()) -> Callable:
    """Lift an elementwise/per-item ``fn`` into a (possibly sharded) map.

    Locally this is just ``jax.jit(fn)``. With a mesh, inputs are sharded
    over ``dist.batch_axes`` on their leading dim and ``fn`` is applied
    shard-locally (no communication — a map never needs any).
    """
    if dist.is_local:
        return jax.jit(fn)

    spec = P(dist.batch_axes)
    sharding = NamedSharding(dist.mesh, spec)

    @jax.jit
    def run(*args):
        args = tuple(jax.device_put(a, sharding) for a in args)
        shard_fn = jax.shard_map(
            fn, mesh=dist.mesh, in_specs=spec, out_specs=spec, check_vma=False
        )
        return shard_fn(*args)

    return run


def grid_map(fn: Callable, items: jax.Array) -> jax.Array:
    """Apply ``fn`` across the leading axis (vmap — per-image map)."""
    return jax.vmap(fn)(items)
