"""Non-maximum suppression — batch-native Pallas row-strip kernel.

The serial NMS is an if-ladder per pixel; on the VPU it becomes four
precomputed neighbour pairs + a select on the direction bin. Magnitude
needs a 1-row halo (neighbour-strip trick); directions are only read at
the centre so they bind with a plain strip spec. One launch covers the
whole (B, H, W) batch on a (batch, strip) grid.

Backend parity plane: boundary strips bind external halo slabs — zeros
locally (the oracle's out-of-image rule), the neighbour SHARD's magnitude
rows under ``shard_map``. True-size semantics need no logic here: the
sobel stage already zeroes magnitudes outside each image's true region,
so the zero-neighbour rule holds at true borders by construction.
``skip_mask``/``prev_out`` is the temporal strip-mask path: strips whose
±(radius+2) input rows are unchanged copy the stored suppressed map.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import common


def nms_math(ext: jax.Array, dirs: jax.Array, bh: int, w: int) -> jax.Array:
    """ext: zero-padded (..., bh+2, w+2) magnitudes; dirs: (..., bh, w) bins
    (uint8 from HBM or int32 from the fused sobel; compared as int32)."""
    dirs = dirs.astype(jnp.int32)

    def at(dy, dx):
        return jax.lax.slice_in_dim(
            jax.lax.slice_in_dim(ext, 1 + dy, 1 + dy + bh, axis=-2),
            1 + dx,
            1 + dx + w,
            axis=-1,
        )

    mag = at(0, 0)
    pairs = [
        (at(0, 1), at(0, -1)),
        (at(1, 1), at(-1, -1)),
        (at(1, 0), at(-1, 0)),
        (at(1, -1), at(-1, 1)),
    ]
    # keep ⇔ mag >= BOTH neighbours ⇔ mag >= max(pair): one f32 compare per
    # direction and pure-bool combines — ~3× cheaper than building the
    # selected-neighbour arrays with nested f32 selects.
    keep = jnp.zeros(mag.shape, bool)
    for b, (f, s) in enumerate(pairs):
        keep = keep | ((dirs == b) & (mag >= jnp.maximum(f, s)))
    return jnp.where(keep, mag, 0.0).astype(jnp.float32)


def _kernel(
    mprev_ref,
    mcur_ref,
    mnxt_ref,
    top_ref,
    bot_ref,
    dir_ref,
    *refs,
    masked: bool = False,
    grid_axis: int = common.STRIP_AXIS,
):
    _, bh, w = mcur_ref.shape
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
            mprev_ref[...],
            mcur_ref[...],
            mnxt_ref[...],
            1,
            "zero",
            top_ext=top_ref[...],
            bot_ext=bot_ref[...],
            grid_pos=grid_pos,
        )
        ext = common.pad_cols(ext, 1, "zero")
        return (nms_math(ext, dir_ref[...], bh, w),)

    common.write_outputs(
        (out_ref,), compute, skip_ref, (prev_out_ref,) if masked else None
    )


def nms_strips(
    mag: jax.Array,
    dirs: jax.Array,
    block_rows: int | None = None,
    interpret: bool | None = None,
    batch_block: int | None = None,
    halos: tuple[jax.Array, jax.Array] | None = None,
    skip_mask: jax.Array | None = None,
    prev_out: jax.Array | None = None,
) -> jax.Array:
    """(B, H, W) magnitude + bins → suppressed (B, H, W) in ONE pallas_call."""
    if interpret is None:
        interpret = common.default_interpret()
    if (skip_mask is None) != (prev_out is None):
        raise ValueError("skip_mask and prev_out come together")
    b, h, w = mag.shape
    bh = block_rows or common.pick_block_rows(h)
    if h % bh != 0:
        raise ValueError(f"H={h} not a multiple of block_rows={bh}")
    n = h // bh
    bt = batch_block or common.pick_batch_block(b, bh, w)
    if halos is None:
        halo_top, halo_bot = common.default_halos(mag, 1, "zero")
    else:
        halo_top, halo_bot = common.check_halos(halos, b, 1, w)

    grid, sx = common.strip_grid(b, bt, n)
    prev, cur, nxt = common.strip_specs(n, bh, w, bt, sx)
    out_shape = jax.ShapeDtypeStruct((b, h, w), jnp.float32)
    in_specs = [
        prev,
        cur,
        nxt,
        common.halo_spec(1, w, bt, sx),
        common.halo_spec(1, w, bt, sx),
        common.out_strip_spec(bh, w, bt, sx),
    ]
    operands = [
        mag,
        mag,
        mag,
        halo_top.astype(mag.dtype),
        halo_bot.astype(mag.dtype),
        dirs,
    ]
    if skip_mask is not None:
        specs, ops = common.skip_specs_operands(
            skip_mask, prev_out, out_shape, bh, bt, sx
        )
        in_specs += specs
        operands += ops
    return pl.pallas_call(
        functools.partial(_kernel, masked=skip_mask is not None, grid_axis=sx),
        grid=grid,
        in_specs=in_specs,
        out_specs=common.out_strip_spec(bh, w, bt, sx),
        out_shape=out_shape,
        interpret=interpret,
        compiler_params=common.compiler_params(),
    )(*operands)
