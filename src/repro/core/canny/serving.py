"""Pure-jnp serving entry — the portable backend on the bucketed plane.

The raw jnp stage plane (``canny_local_stages`` under ``shard_map``)
needs mesh-divisible shapes; this module gives the ``jnp`` backend the
SAME true-size-aware serving contract as the Pallas backends —
``(imgs, true_hw, params, interpret, dist) → edges`` — so the bucketed
serving layer (and every mesh entry point: ``CannyEngine``,
``make_canny(dist=...)``) runs it on arbitrary request shapes,
bit-identical to the unpadded oracle.

True-size anchoring uses the same three arguments as the Pallas kernels
(DESIGN.md §10): bucket padding is edge-replicated, which IS the
oracle's input clamp for the gaussian; the sobel stage folds window
reads past the true extent back to the centre (the 3×3 one-step clamp)
and zeroes magnitudes outside the true region; NMS's zero-neighbour
rule and the hysteresis fixpoint then hold at true borders by
construction. Under a mesh the global row id comes from the shard's
``lax.axis_index`` offset, so the fixes work shard-locally with no
cross-shard fetches.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.canny.gaussian import gaussian_stage
from repro.core.canny.hysteresis import hysteresis_stage
from repro.core.canny.nms import nms_stage
from repro.core.canny.params import CannyParams
from repro.core.canny.sobel import sobel_stage
from repro.core.patterns.dist import LOCAL, Dist, StencilCtx


def _true_size_block(x, hw, params, ectx, zctx, row_off, local_sweeps=1):
    """All four stages on a (shard-)local (b, h_l, w) block, border math
    anchored at the per-image true sizes in ``hw``."""
    ht = hw[:, 0].reshape(-1, 1, 1)
    wt = hw[:, 1].reshape(-1, 1, 1)
    hl, w = x.shape[-2], x.shape[-1]
    grow = lax.broadcasted_iota(jnp.int32, (1, hl, 1), 1) + row_off
    gcol = lax.broadcasted_iota(jnp.int32, (1, 1, w), 2)
    blur = gaussian_stage(x, ectx, params)
    mag, dirs = sobel_stage(blur, ectx, params, clamp=(grow, ht, gcol, wt))
    sup = nms_stage(mag, dirs, zctx)
    return hysteresis_stage(sup, params, zctx, local_sweeps=local_sweeps)


def jnp_serving(
    imgs: jax.Array,
    true_hw: jax.Array,
    params: CannyParams,
    interpret: bool | None = None,
    dist: Dist = LOCAL,
) -> jax.Array:
    """(b, h, w) f32 bucket batch + (b, 2) true sizes → uint8 edges."""
    del interpret  # no Pallas on this path
    imgs = imgs.astype(jnp.float32)
    b, h, w = imgs.shape
    true_hw = true_hw.astype(jnp.int32)
    if dist.is_local:
        ectx = StencilCtx(None, "edge")
        zctx = StencilCtx(None, "zero")
        return _true_size_block(imgs, true_hw, params, ectx, zctx, 0)

    if b % dist.batch_size():
        raise ValueError(
            f"batch {b} not divisible by the {dist.batch_axes} axis size "
            f"{dist.batch_size()}; the serving engine pads bucket batches "
            "to a multiple"
        )
    # rows pad GLOBALLY to the shard grid (edge clones beyond every true
    # height are inert: the sobel clamp zeroes their magnitudes)
    ms = dist.space_size()
    hp = -(-h // ms) * ms
    if hp != h:
        imgs = jnp.pad(imgs, ((0, 0), (0, hp - h), (0, 0)), mode="edge")
    space = dist.space_axis
    ectx = StencilCtx(space, "edge", sync_axes=dist.sync_axes())
    zctx = StencilCtx(space, "zero", sync_axes=dist.sync_axes())

    def local_fn(x, hw):
        off = lax.axis_index(space) * (hp // ms) if space is not None else 0
        return _true_size_block(x, hw, params, ectx, zctx, off, local_sweeps=2)

    fn = jax.shard_map(
        local_fn,
        mesh=dist.mesh,
        in_specs=(dist.batch_spec(), dist.table_spec()),
        out_specs=dist.batch_spec(),
        check_vma=False,
    )
    return lax.slice_in_dim(fn(imgs, true_hw), 0, h, axis=-2)
