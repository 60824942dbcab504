"""Stencil pattern — neighbourhood computation with halo exchange.

The Canny stages (Gaussian, Sobel, NMS, hysteresis dilation) are all
stencils. On a multicore CPU the halo is implicit (cache lines); on TPU it
must be staged explicitly. Two levels:

  * across shards — ``lax.ppermute`` halo exchange (this module / StencilCtx)
  * within a shard — Pallas kernels stage HBM→VMEM row strips with
    neighbour-block BlockSpecs (see ``repro.kernels``)

``stencil2d`` lifts a "padded block → block" function into a full array
op, local or sharded.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P


from repro.core.patterns.dist import Dist, StencilCtx, _halo_exchange, _pad_axis


def pad_rows(x: jax.Array, halo: int, axis: int = -2, pad_mode: str = "edge") -> jax.Array:
    """Local row padding (the degenerate, unsharded halo)."""
    return _pad_axis(x, halo, axis, pad_mode)


def halo_exchange(
    x: jax.Array, halo: int, axis_name: str, axis: int = -2, pad_mode: str = "edge"
) -> jax.Array:
    """Exchange halo rows across a named mesh axis (shard_map context)."""
    return _halo_exchange(x, halo, axis, axis_name, pad_mode)


def overlap_strips(
    launch: Callable[[tuple, tuple[jax.Array, jax.Array], int], object],
    operands: tuple[jax.Array, ...],
    halos: tuple[jax.Array, jax.Array],
    *,
    block_rows: int,
) -> object:
    """Split one strip-stage launch so the halo exchange hides under compute.

    ``launch(ops, (top, bot), row_start)`` must run the stage's strip kernel
    on the given row window with the given external halo slabs; ``operands``
    are row-aligned (axis 1) and are sliced together, with ``operands[0]``
    the stencil input the synthetic interior halos are cut from. ``halos``
    is the shard's exchanged (top, bot) slab pair.

    The split: interior rows ``[bh, h-bh)`` launch with halos sliced from the
    shard's OWN rows — no dataflow edge to the ppermuted slabs, so the
    scheduler is free to run the exchange underneath that launch — then the
    two boundary strips finish on slab arrival. Each sub-launch tile sees
    exactly the rows + halo rows it would have seen in the single launch
    (sub-launch boundary slabs are the very rows the neighbour-strip
    BlockSpecs would have read), so every output is bit-identical; per-strip
    maps such as the hysteresis ``changed`` counts concatenate back in strip
    order. Fewer than 3 strips (or a halo wider than a strip) has no
    interior to hide behind, so it falls back to the serialized launch.
    """
    x = operands[0]
    h = x.shape[1]
    bh = block_rows
    n = h // bh
    hs = halos[0].shape[1]  # slab row count (max(halo, 1), see halo_rows)
    if n < 3 or hs > bh:
        return launch(operands, halos, 0)

    top_ops = tuple(a[:, :bh] for a in operands)
    mid_ops = tuple(a[:, bh : h - bh] for a in operands)
    bot_ops = tuple(a[:, h - bh :] for a in operands)

    mid = launch(mid_ops, (x[:, bh - hs : bh], x[:, h - bh : h - bh + hs]), bh)
    top = launch(top_ops, (halos[0], x[:, bh : bh + hs]), 0)
    bot = launch(bot_ops, (x[:, h - bh - hs : h - bh], halos[1]), h - bh)

    if isinstance(mid, tuple):
        return tuple(
            jnp.concatenate([t, m, b], axis=1) for t, m, b in zip(top, mid, bot)
        )
    return jnp.concatenate([top, mid, bot], axis=1)


def stencil2d(
    fn: Callable[[jax.Array, StencilCtx], jax.Array],
    dist: Dist = Dist(),
    pad_mode: str = "edge",
) -> Callable[[jax.Array], jax.Array]:
    """Lift a stencil stage ``fn(x, ctx) -> y`` into a runnable op.

    ``fn`` receives the *local* (sharded) array plus a ``StencilCtx`` it
    must use for any neighbourhood access. Locally ``ctx`` pads; sharded,
    ``ctx`` performs ppermute halo exchange. ``fn``'s output must have the
    same row extent as its input (stencils are shape-preserving here).
    """
    if dist.is_local:
        ctx = StencilCtx(None, pad_mode)
        return jax.jit(lambda x: fn(x, ctx))

    ctx = StencilCtx(dist.space_axis, pad_mode)
    ndim_specs = P(*dist.batch_axes, dist.space_axis)

    @jax.jit
    def run(x):
        sharding = NamedSharding(dist.mesh, ndim_specs)
        x = jax.device_put(x, sharding)
        shard_fn = jax.shard_map(
            lambda xl: fn(xl, ctx),
            mesh=dist.mesh,
            in_specs=ndim_specs,
            out_specs=ndim_specs,
            check_vma=False,
        )
        return shard_fn(x)

    return run
