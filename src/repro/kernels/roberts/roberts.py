"""Roberts-cross edge kernel — 2x2 forward stencil + threshold, one pass.

The smallest stencil in the zoo: each output pixel reads itself and its
(+1, +1) neighbourhood, so the strip halo is a single bottom row and the
true-size clamp only has a bottom and a right case (``_fold_forward``
below — the 2x2 analogue of ``fold_true_border``). Rides the same
batch-grid plumbing as every other kernel: external halo slabs, per-image
true-(h, w) anchoring, flat b=1 ``strip_grid`` path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.canny.sobel import zero_outside_true
from repro.kernels import common


def _fold_forward(win: dict, clamp) -> dict:
    """True-border clamp for a 2x2 FORWARD window ``{(dy, dx) in {0,1}²}``:
    the dy=+1 / dx=+1 reads past the true extent fold back to the dy=0 /
    dx=0 row/col (the oracle's one-step bottom/right edge pad). Rows fold
    first so the bottom-right corner lands on the centre pixel."""
    grow, ht, gcol, wt = clamp
    below = grow + 1 >= ht
    for dx in range(2):
        win[(1, dx)] = jnp.where(below, win[(0, dx)], win[(1, dx)])
    right = gcol + 1 >= wt
    for dy in range(2):
        win[(dy, 1)] = jnp.where(right, win[(dy, 0)], win[(dy, 1)])
    return win


def roberts_math(ext: jax.Array, bh: int, w: int, l2_norm: bool, clamp=None):
    """Roberts magnitude on a halo-extended (..., bh+2, w+2) tile whose
    centre pixel sits at local (1, 1) — the shared tile layout, even
    though the operator never reads the dy/dx = -1 ring."""
    win = {}
    for dy in range(2):
        for dx in range(2):
            win[(dy, dx)] = jax.lax.slice_in_dim(
                jax.lax.slice_in_dim(ext, 1 + dy, 1 + dy + bh, axis=-2),
                1 + dx, 1 + dx + w, axis=-1,
            )
    if clamp is not None:
        win = _fold_forward(win, clamp)
    gx = win[(0, 0)] - win[(1, 1)]
    gy = win[(1, 0)] - win[(0, 1)]
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
    mag = roberts_math(ext, bh, w, l2_norm, clamp=(grow, ht, gcol, wt))
    out_ref[...] = (mag >= high).astype(jnp.uint8)


def roberts_strips(
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
    """(B, H, W) f32 → uint8 edges in ONE pallas_call (see
    ``prewitt_strips`` for the composition contract)."""
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
