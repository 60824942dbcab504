"""Fused Laplacian-of-Gaussian kernel — blur + laplacian + zero-crossing
in ONE batch-grid pass.

Structurally the fused Canny front-end with the Sobel/NMS stages swapped
for a Laplacian and a zero-crossing detector. Halo budget for a strip of
``bh`` output rows: the zero-crossing reads ±1 Laplacian rows, the
Laplacian ±1 blur rows, the blur ±radius input rows — radius+2 total,
the same ``h2`` the fused kernel uses, so the strip/halo plumbing (and
the sharded halo exchange) carries over unchanged.

TWO in-register border-fix layers anchor per-image true sizes:

  1. blur replication (identical to the fused kernel's fix 1): the
     oracle edge-replicates the BLURRED image before the Laplacian, but
     rows/cols past the true extent were blurred from padded clones —
     overwrite them with the first/last TRUE blur row/col.
  2. Laplacian replication: the oracle ALSO edge-replicates the
     LAPLACIAN before the zero-crossing, and the Laplacian of a
     replicated blur row is NOT the replicated Laplacian row (its N/S
     neighbours differ) — so the same select-row/col fix is applied
     again at the Laplacian layer. This second fix is what a naive port
     of the fused kernel's border handling would miss.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.canny.reference import gaussian_kernel1d
from repro.kernels import common

# forward (dy, dx) of the four opposite-neighbour zero-crossing pairs
_PAIRS = ((1, 0), (0, 1), (1, 1), (1, -1))


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
    taps: tuple[float, ...],
    radius: int,
    high: float,
    grid_axis: int = common.STRIP_AXIS,
):
    r = radius
    h2 = r + 2
    bt, bh, w = cur_ref.shape
    i = pl.program_id(grid_axis)
    n_strips = pl.num_programs(grid_axis)
    ht, wt = common.true_sizes(hw_ref)  # per-image true (h, w)
    row0 = off_ref[0, 0] + i * bh

    # ---- gaussian on the (bt, bh + 2*h2, w) extended tile ------------------
    ext = common.assemble_rows(
        prev_ref[...],
        cur_ref[...],
        nxt_ref[...],
        h2,
        "edge",
        top_ext=top_ref[...],
        bot_ext=bot_ref[...],
        grid_pos=(i, n_strips),
    )
    xp = common.pad_cols(ext, r, "edge")
    tmp = jnp.zeros_like(ext)
    for t in range(2 * r + 1):
        tmp = tmp + taps[t] * jax.lax.slice_in_dim(xp, t, t + w, axis=-1)
    nblur = bh + 4
    blur = jnp.zeros((bt, nblur, w), jnp.float32)
    for t in range(2 * r + 1):
        blur = blur + taps[t] * jax.lax.slice_in_dim(tmp, t, t + nblur, axis=-2)

    grow = jax.lax.broadcasted_iota(jnp.int32, (1, nblur, 1), 1) + row0 - 2
    gcol = jax.lax.broadcasted_iota(jnp.int32, (1, 1, w), 2)

    # Border fix 1 — replicate the TRUE first/last blur row/col over the
    # virtual rows (rows first, cols second; see fused_canny.py)
    top_fix = jnp.broadcast_to(blur[..., 2:3, :], blur.shape)
    last_local = jnp.clip(ht - 1 - row0 + 2, 0, nblur - 1)
    bot_row = common.select_row(blur, last_local)
    blur2 = jnp.where(grow < 0, top_fix, blur)
    blur2 = jnp.where(grow >= ht, jnp.broadcast_to(bot_row, blur2.shape), blur2)
    right_col = common.select_col(blur2, jnp.clip(wt - 1, 0, w - 1))
    blur2 = jnp.where(gcol >= wt, jnp.broadcast_to(right_col, blur2.shape), blur2)

    # ---- laplacian on blur2 → (bt, bh+2, w), oracle tap order N,W,C,E,S ----
    nlap = bh + 2
    bp = common.pad_cols(blur2, 1, "edge")
    n_ = jax.lax.slice_in_dim(
        jax.lax.slice_in_dim(bp, 0, nlap, axis=-2), 1, 1 + w, axis=-1
    )
    w_ = jax.lax.slice_in_dim(
        jax.lax.slice_in_dim(bp, 1, 1 + nlap, axis=-2), 0, w, axis=-1
    )
    c_ = jax.lax.slice_in_dim(
        jax.lax.slice_in_dim(bp, 1, 1 + nlap, axis=-2), 1, 1 + w, axis=-1
    )
    e_ = jax.lax.slice_in_dim(
        jax.lax.slice_in_dim(bp, 1, 1 + nlap, axis=-2), 2, 2 + w, axis=-1
    )
    s_ = jax.lax.slice_in_dim(
        jax.lax.slice_in_dim(bp, 2, 2 + nlap, axis=-2), 1, 1 + w, axis=-1
    )
    lap = n_ + w_ + (-4.0) * c_ + e_ + s_

    # Border fix 2 — replicate the TRUE first/last LAPLACIAN row/col (the
    # oracle pads the laplacian itself before the zero-crossing)
    lgrow = jax.lax.broadcasted_iota(jnp.int32, (1, nlap, 1), 1) + row0 - 1
    lap_top = jnp.broadcast_to(lap[..., 1:2, :], lap.shape)
    last_lap = jnp.clip(ht - 1 - row0 + 1, 0, nlap - 1)
    lap_bot = common.select_row(lap, last_lap)
    lap2 = jnp.where(lgrow < 0, lap_top, lap)
    lap2 = jnp.where(lgrow >= ht, jnp.broadcast_to(lap_bot, lap2.shape), lap2)
    lap_right = common.select_col(lap2, jnp.clip(wt - 1, 0, w - 1))
    lap2 = jnp.where(gcol >= wt, jnp.broadcast_to(lap_right, lap2.shape), lap2)

    # ---- zero-crossing → (bt, bh, w) ---------------------------------------
    zext = common.pad_cols(lap2, 1, "edge")
    edges = jnp.zeros((bt, bh, w), dtype=bool)
    for dy, dx in _PAIRS:
        a = jax.lax.slice_in_dim(
            jax.lax.slice_in_dim(zext, 1 + dy, 1 + dy + bh, axis=-2),
            1 + dx, 1 + dx + w, axis=-1,
        )
        b = jax.lax.slice_in_dim(
            jax.lax.slice_in_dim(zext, 1 - dy, 1 - dy + bh, axis=-2),
            1 - dx, 1 - dx + w, axis=-1,
        )
        edges = edges | ((a * b < 0) & (jnp.abs(a - b) >= high))

    ogrow = jax.lax.broadcasted_iota(jnp.int32, (1, bh, 1), 1) + row0
    edges = edges & ~((ogrow >= ht) | (gcol >= wt))
    out_ref[...] = edges.astype(jnp.uint8)


def log_strips(
    imgs: jax.Array,
    sigma: float,
    radius: int,
    high: float,
    block_rows: int | None = None,
    interpret: bool | None = None,
    batch_block: int | None = None,
    true_hw: jax.Array | None = None,
    halos: tuple[jax.Array, jax.Array] | None = None,
    row_offset: jax.Array | None = None,
) -> jax.Array:
    """(B, H, W) f32 → uint8 zero-crossing edges in ONE pallas_call (see
    ``fused_canny_strips`` for the halo/true-size composition contract)."""
    if interpret is None:
        interpret = common.default_interpret()
    b, h, w = imgs.shape
    if true_hw is None:
        true_hw = jnp.broadcast_to(jnp.asarray([h, w], jnp.int32), (b, 2))
    h2 = radius + 2
    bh = block_rows or common.pick_block_rows(h, min_rows=h2)
    if h % bh != 0:
        raise ValueError(f"H={h} not a multiple of block_rows={bh}")
    if bh < h2:
        raise ValueError(f"block_rows={bh} must be >= radius+2={h2}")
    if halos is None:
        halo_top, halo_bot = common.default_halos(imgs, h2, "edge")
    else:
        halo_top, halo_bot = common.check_halos(halos, b, h2, w)
    if row_offset is None:
        row_offset = jnp.zeros((1, 1), jnp.int32)
    row_offset = jnp.asarray(row_offset, jnp.int32).reshape(1, 1)
    n = h // bh
    bt = batch_block or common.pick_batch_block(b, bh, w)
    taps = tuple(float(t) for t in gaussian_kernel1d(sigma, radius))
    grid, sx = common.strip_grid(b, bt, n)
    prev, cur, nxt = common.strip_specs(n, bh, w, bt, sx)
    return pl.pallas_call(
        functools.partial(
            _kernel, taps=taps, radius=radius, high=high, grid_axis=sx
        ),
        grid=grid,
        in_specs=[
            prev,
            cur,
            nxt,
            common.halo_spec(h2, w, bt, sx),
            common.halo_spec(h2, w, bt, sx),
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
