"""Separable Gaussian blur — batch-native Pallas row-strip kernel.

One VMEM round-trip per tile: the halo-extended (BT, BH+2r, W) tile is
convolved horizontally (in-register shifts across the full width) then
vertically (static row slices), both passes fused so the intermediate
never touches HBM, and both vectorized across the BT in-block images.
Taps accumulate in ascending order to match the oracle bit-for-bit.

Backend parity plane: the boundary strips bind externally supplied halo
slabs (edge-replicated rows locally; the neighbour SHARD's rows under
``shard_map`` — see ``common.halo_spec``), and the temporal strip-mask
path (``skip_mask``/``prev_out``) lets provably-static strips copy the
previous frame's blur instead of recomputing — the same ``dist``/``skip``
plumbing the fused kernel runs, so the per-stage path composes under
every pattern the fused one does.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.canny.reference import gaussian_kernel1d
from repro.kernels import common


def _kernel(
    prev_ref,
    cur_ref,
    nxt_ref,
    top_ref,
    bot_ref,
    *refs,
    taps: tuple[float, ...],
    radius: int,
    masked: bool = False,
    grid_axis: int = common.STRIP_AXIS,
):
    r = radius
    bt, bh, w = cur_ref.shape
    # grid position binds at kernel top level only — compute() may run
    # inside a pl.when branch, where program_id cannot be staged
    grid_pos = (
        pl.program_id(grid_axis),
        pl.num_programs(grid_axis),
    )
    if masked:
        skip_ref, prev_out_ref, out_ref = refs
    else:
        (out_ref,) = refs
        skip_ref = prev_out_ref = None

    def compute():
        ext = common.assemble_rows(
            prev_ref[...],
            cur_ref[...],
            nxt_ref[...],
            r,
            "edge",
            top_ext=top_ref[...],
            bot_ext=bot_ref[...],
            grid_pos=grid_pos,
        )
        # horizontal pass over the halo-extended tile
        xp = common.pad_cols(ext, r, "edge")
        tmp = jnp.zeros_like(ext)
        for i in range(2 * r + 1):
            tmp = tmp + taps[i] * jax.lax.slice_in_dim(xp, i, i + w, axis=-1)

        # vertical pass consumes the halo rows
        out = jnp.zeros((bt, bh, w), jnp.float32)
        for i in range(2 * r + 1):
            out = out + taps[i] * jax.lax.slice_in_dim(tmp, i, i + bh, axis=-2)
        return (out,)

    common.write_outputs(
        (out_ref,), compute, skip_ref, (prev_out_ref,) if masked else None
    )


def gaussian_blur_strips(
    imgs: jax.Array,
    sigma: float,
    radius: int,
    block_rows: int | None = None,
    interpret: bool | None = None,
    batch_block: int | None = None,
    halos: tuple[jax.Array, jax.Array] | None = None,
    skip_mask: jax.Array | None = None,
    prev_out: jax.Array | None = None,
) -> jax.Array:
    """(B, H, W) f32 → blurred (B, H, W) f32 in ONE pallas_call.

    H must be a multiple of block_rows; the (batch, strip) grid covers
    the whole batch. ``halos`` is an optional (top, bot) pair of
    (B, radius, W) slabs bound by the first/last strips in place of the
    edge-replicate rule — under ``shard_map`` they carry the adjacent
    shard's rows (``StencilCtx.halo_rows``) so the shard-local grid
    stitches into one global stencil bit-identically. ``skip_mask`` +
    ``prev_out`` select the temporal strip-mask path (composes with
    ``halos`` for the sharded temporal step): a strip whose ±radius input
    rows are bitwise unchanged copies the stored previous blur —
    bit-identical by purity.
    """
    if interpret is None:
        interpret = common.default_interpret()
    if (skip_mask is None) != (prev_out is None):
        raise ValueError("skip_mask and prev_out come together")
    b, h, w = imgs.shape
    bh = block_rows or common.pick_block_rows(h)
    if h % bh != 0:
        raise ValueError(f"H={h} not a multiple of block_rows={bh}")
    if bh < radius:
        raise ValueError(f"block_rows={bh} must be >= radius={radius}")
    n = h // bh
    bt = batch_block or common.pick_batch_block(b, bh, w)
    taps = tuple(float(t) for t in gaussian_kernel1d(sigma, radius))

    if halos is None:
        halo_top, halo_bot = common.default_halos(imgs, radius, "edge")
    else:
        halo_top, halo_bot = common.check_halos(halos, b, radius, w)

    grid, sx = common.strip_grid(b, bt, n)
    prev, cur, nxt = common.strip_specs(n, bh, w, bt, sx)
    out_shape = jax.ShapeDtypeStruct((b, h, w), jnp.float32)
    in_specs = [
        prev,
        cur,
        nxt,
        common.halo_spec(radius, w, bt, sx),
        common.halo_spec(radius, w, bt, sx),
    ]
    operands = [
        imgs,
        imgs,
        imgs,
        halo_top.astype(imgs.dtype),
        halo_bot.astype(imgs.dtype),
    ]
    if skip_mask is not None:
        specs, ops = common.skip_specs_operands(
            skip_mask, prev_out, out_shape, bh, bt, sx
        )
        in_specs += specs
        operands += ops
    return pl.pallas_call(
        functools.partial(
            _kernel,
            taps=taps,
            radius=radius,
            masked=skip_mask is not None,
            grid_axis=sx,
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=common.out_strip_spec(bh, w, bt, sx),
        out_shape=out_shape,
        interpret=interpret,
        compiler_params=common.compiler_params(),
    )(*operands)
