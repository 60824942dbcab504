"""Prewitt edge kernel — gradient + threshold in ONE batch-grid pass.

Structurally the Sobel kernel with +-1 taps and the double threshold
fused away (a classical gradient operator has no hysteresis): one
(batch, strip) grid launch emits the uint8 edge map directly. The same
backend-parity plumbing applies — external halo slabs for shard
composition, per-image true-(h, w) border anchoring via the shared
``fold_true_border``/``zero_outside_true`` clamp rule, and the flat
``strip_grid`` b=1 path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.canny.sobel import fold_true_border, zero_outside_true
from repro.kernels import common


def prewitt_math(ext: jax.Array, bh: int, w: int, l2_norm: bool, clamp=None):
    """Prewitt magnitude on a halo-extended (..., bh+2, w+2) tile.

    Mirrors ``sobel_math``: non-zero taps summed left-assoc in the
    oracle's (dy, dx) order, ``clamp`` folds window reads past the
    per-image true extent back to the centre row/col and zeroes
    magnitudes outside the true region.
    """
    win = {}
    for dy in range(3):
        for dx in range(3):
            win[(dy, dx)] = jax.lax.slice_in_dim(
                jax.lax.slice_in_dim(ext, dy, dy + bh, axis=-2), dx, dx + w, axis=-1
            )
    if clamp is not None:
        win = fold_true_border(win, clamp)
    gx = (
        -win[(0, 0)]
        + win[(0, 2)]
        - win[(1, 0)]
        + win[(1, 2)]
        - win[(2, 0)]
        + win[(2, 2)]
    )
    gy = (
        -win[(0, 0)]
        - win[(0, 1)]
        - win[(0, 2)]
        + win[(2, 0)]
        + win[(2, 1)]
        + win[(2, 2)]
    )
    if l2_norm:
        mag = jnp.sqrt(gx * gx + gy * gy)
    else:
        mag = jnp.abs(gx) + jnp.abs(gy)
    if clamp is not None:
        mag = zero_outside_true(mag, clamp)
    return mag.astype(jnp.float32)


def _kernel(
    prev_ref,
    cur_ref,
    nxt_ref,
    top_ref,
    bot_ref,
    hw_ref,
    off_ref,
    out_ref,
    *,
    high: float,
    l2_norm: bool,
    grid_axis: int = common.STRIP_AXIS,
):
    bt, bh, w = cur_ref.shape
    grid_pos = (pl.program_id(grid_axis), pl.num_programs(grid_axis))
    ht, wt = common.true_sizes(hw_ref)  # per-image true (h, w)
    row0 = off_ref[0, 0] + grid_pos[0] * bh
    ext = common.assemble_rows(
        prev_ref[...],
        cur_ref[...],
        nxt_ref[...],
        1,
        "edge",
        top_ext=top_ref[...],
        bot_ext=bot_ref[...],
        grid_pos=grid_pos,
    )
    ext = common.pad_cols(ext, 1, "edge")
    grow = jax.lax.broadcasted_iota(jnp.int32, (1, bh, 1), 1) + row0
    gcol = jax.lax.broadcasted_iota(jnp.int32, (1, 1, w), 2)
    mag = prewitt_math(ext, bh, w, l2_norm, clamp=(grow, ht, gcol, wt))
    out_ref[...] = (mag >= high).astype(jnp.uint8)


def prewitt_strips(
    imgs: jax.Array,
    high: float,
    l2_norm: bool = True,
    block_rows: int | None = None,
    interpret: bool | None = None,
    batch_block: int | None = None,
    true_hw: jax.Array | None = None,
    halos: tuple[jax.Array, jax.Array] | None = None,
    row_offset: jax.Array | None = None,
):
    """(B, H, W) f32 → uint8 edges in ONE pallas_call.

    Same composition contract as ``sobel_strips``: ``true_hw`` anchors the
    border math at per-image pre-padding sizes, ``halos``/``row_offset``
    stitch shard-local grids into one global stencil under ``shard_map``.
    """
    if interpret is None:
        interpret = common.default_interpret()
    b, h, w = imgs.shape
    bh = block_rows or common.pick_block_rows(h)
    if h % bh != 0:
        raise ValueError(f"H={h} not a multiple of block_rows={bh}")
    n = h // bh
    bt = batch_block or common.pick_batch_block(b, bh, w)
    if true_hw is None:
        true_hw = jnp.broadcast_to(jnp.asarray([h, w], jnp.int32), (b, 2))
    if halos is None:
        halo_top, halo_bot = common.default_halos(imgs, 1, "edge")
    else:
        halo_top, halo_bot = common.check_halos(halos, b, 1, w)
    if row_offset is None:
        row_offset = jnp.zeros((1, 1), jnp.int32)
    row_offset = jnp.asarray(row_offset, jnp.int32).reshape(1, 1)

    grid, sx = common.strip_grid(b, bt, n)
    prev, cur, nxt = common.strip_specs(n, bh, w, bt, sx)
    return pl.pallas_call(
        functools.partial(_kernel, high=high, l2_norm=l2_norm, grid_axis=sx),
        grid=grid,
        in_specs=[
            prev,
            cur,
            nxt,
            common.halo_spec(1, w, bt, sx),
            common.halo_spec(1, w, bt, sx),
            common.per_image_spec(2, bt, sx),
            common.offset_spec(bt, sx),
        ],
        out_specs=common.out_strip_spec(bh, w, bt, sx),
        out_shape=jax.ShapeDtypeStruct((b, h, w), jnp.uint8),
        interpret=interpret,
        compiler_params=common.compiler_params(),
    )(
        imgs,
        imgs,
        imgs,
        halo_top.astype(imgs.dtype),
        halo_bot.astype(imgs.dtype),
        common.per_image_table(true_hw),
        row_offset,
    )
