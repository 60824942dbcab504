"""Fused Canny front-end — Gaussian + Sobel + NMS (+ threshold) in ONE pass.

Beyond-paper optimization. The paper (and our paper-faithful baseline)
runs each stage as its own pass: 3 full HBM round-trips of the image
between stages. All four stages are local stencils, so they compose into
a single kernel whose only HBM traffic is the input strip (+2·(r+2) halo
rows) in and one uint8 code map out:

    baseline traffic / px : r4 + (4+1)w + (4+1+4)r + (4+4)rw + 4r+1w ≈ 26 B
    fused traffic  / px   : 4 r + 1 w ≈ 5 B        (≈5× less — memory-bound)

The fused kernel computes on a halo-extended (BT, BH+2·(r+2), W) tile;
halo math per stage (blur needs ±(r+2) input rows to emit bh+4 rows,
sobel eats 1, NMS eats 1) with in-register border fixes replicating the
oracle's exact semantics at image borders (gauss/sobel edge-replicate,
NMS zero neighbours). Batch-native: one launch covers the whole batch on
a (batch, strip) grid, vectorized across the BT in-block images.

Border fixes anchor at PER-IMAGE true sizes read from a (B, 2) int32
table — images bucketed/padded to a common (H, W) by the serving engine
still come out bit-identical to the unpadded oracle, and the padded
region of the code map is guaranteed 0 (inert under hysteresis).

Emits code = (mag>=low) + (mag>=high) ∈ {0,1,2} uint8 — threshold fused
for free, and the downstream hysteresis kernel reads 1 byte/px
instead of 4.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.canny.reference import gaussian_kernel1d
from repro.kernels import common
from repro.kernels.nms.nms import nms_math
from repro.kernels.sobel.sobel import sobel_math


def _kernel(
    prev_ref,
    cur_ref,
    nxt_ref,
    top_ref,
    bot_ref,
    hw_ref,
    off_ref,
    *refs,
    taps: tuple[float, ...],
    radius: int,
    l2_norm: bool,
    low: float,
    high: float,
    emit: str,
    masked: bool = False,
    grid_axis: int = common.STRIP_AXIS,
):
    r = radius
    h2 = r + 2
    bt, bh, w = cur_ref.shape
    # grid position binds at kernel top level only — frontend() may run
    # inside a pl.when branch, where program_id cannot be staged
    i = pl.program_id(grid_axis)
    n_strips = pl.num_programs(grid_axis)
    ht, wt = common.true_sizes(hw_ref)  # per-image true (h, w)
    # First GLOBAL row this kernel's array owns: 0 locally; under shard_map
    # the shard's row offset, so all border logic anchored at per-image
    # true sizes keeps working on a shard-local grid.
    row0 = off_ref[0, 0] + i * bh

    if masked:
        skip_ref, prev_out_ref, out_ref = refs
    else:
        (out_ref,) = refs
        skip_ref = prev_out_ref = None

    def frontend():
        # ---- gaussian on the (bt, bh + 2*h2, w) extended tile -------------
        # Rows >= ht and cols >= wt are edge clones added by ops.py/the
        # engine, so the blur of every real pixel already matches the
        # oracle's edge-replicate semantics. The first/last strips bind the
        # externally supplied halo slabs (edge-replicated rows locally; the
        # neighbour shard's rows under shard_map).
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

        # Global row id of each blur row: g = row0 + idx - 2 (idx = local row).
        grow = jax.lax.broadcasted_iota(jnp.int32, (1, nblur, 1), 1) + row0 - 2
        gcol = jax.lax.broadcasted_iota(jnp.int32, (1, 1, w), 2)

        # Border fix 1: the oracle edge-replicates the *blurred* image for
        # sobel; virtual rows (g < 0 or g >= ht) and cols (>= wt) were
        # instead blurred from replicated/padded inputs. Overwrite with the
        # first/last TRUE blur row/col. The last true row may live in this
        # strip at dynamic per-image local index (ht-1) - row0 + 2 — picked
        # out by an iota mask (``common.select_row``). Rows first, cols
        # second: the bottom-right corner then lands on blur[ht-1, wt-1].
        top_fix = jnp.broadcast_to(blur[..., 2:3, :], blur.shape)
        last_local = jnp.clip(ht - 1 - row0 + 2, 0, nblur - 1)
        bot_row = common.select_row(blur, last_local)
        blur2 = jnp.where(grow < 0, top_fix, blur)
        blur2 = jnp.where(grow >= ht, jnp.broadcast_to(bot_row, blur2.shape), blur2)
        right_col = common.select_col(blur2, jnp.clip(wt - 1, 0, w - 1))
        blur2 = jnp.where(gcol >= wt, jnp.broadcast_to(right_col, blur2.shape), blur2)

        # ---- sobel on blur → (bt, bh+2, w) mag/dirs ------------------------
        sob_ext = common.pad_cols(blur2, 1, "edge")
        mag, dirs = sobel_math(sob_ext, bh + 2, w, l2_norm)

        # Border fix 2: NMS treats out-of-image neighbours as 0 — zero every
        # magnitude row/col outside [0, ht) × [0, wt). This also guarantees
        # a zero code map over the padded region (inert under hysteresis).
        mgrow = jax.lax.broadcasted_iota(jnp.int32, (1, bh + 2, 1), 1) + row0 - 1
        mag = jnp.where((mgrow < 0) | (mgrow >= ht) | (gcol >= wt), 0.0, mag)

        # ---- NMS → (bt, bh, w) ---------------------------------------------
        nms_ext = common.pad_cols(mag, 1, "zero")
        suppressed = nms_math(nms_ext, dirs[..., 1 : bh + 1, :], bh, w)

        if emit == "nms":
            return (suppressed,)
        # fused double threshold, 1 B/px
        code = (suppressed >= low).astype(jnp.int32) + (
            suppressed >= high
        ).astype(jnp.int32)
        return (code,)

    # Strip-mask path (masked): ``skip_ref`` flags per-image STATIC strips
    # — every input row this strip's stencil reads is bitwise identical to
    # the previous frame, so the stored previous output IS this frame's
    # output (purity; DESIGN.md §9). ``common.write_outputs`` skips the
    # stencil math for fully static tiles via ``pl.when``.
    common.write_outputs(
        (out_ref,), frontend, skip_ref, (prev_out_ref,) if masked else None
    )


def fused_canny_strips(
    imgs: jax.Array,
    sigma: float,
    radius: int,
    low: float,
    high: float,
    l2_norm: bool = True,
    emit: str = "code",
    block_rows: int | None = None,
    interpret: bool | None = None,
    true_hw: jax.Array | None = None,
    batch_block: int | None = None,
    halos: tuple[jax.Array, jax.Array] | None = None,
    row_offset: jax.Array | None = None,
    skip_mask: jax.Array | None = None,
    prev_out: tuple[jax.Array, ...] | None = None,
) -> jax.Array:
    """(B, H, W) f32 → NMS magnitudes (f32), threshold code map (uint8),
    or — emit="packed" — the (strong, weak) masks bit-packed 32 px/uint32
    word, ready for the hysteresis kernel (requires W % 32 == 0).

    "packed" runs the kernel's code map and packs it in XLA: packing
    inside the kernel (lane slices of the mask ORed into words) compiles
    for a TPU v5e but drops bit planes 16-22 there, so the words are
    built where they come out right — at the cost of the 1 B/px code map
    crossing HBM once.

    ``true_hw`` is a (B, 2) int32 table of pre-padding (height, width) per
    image: border fixes anchor there, not at the padded grid end. Defaults
    to the full (H, W) for every image.

    ``halos`` is an optional ``(top, bot)`` pair of (B, radius+2, W) slabs
    bound by the first/last strips in place of the clamped neighbour trick
    — under ``shard_map`` they carry the adjacent shard's rows (exchanged
    by ``StencilCtx.halo_rows``) so the shard-local grid stitches into one
    global stencil bit-identically. ``row_offset`` is the matching (1, 1)
    int32 first-global-row scalar (the shard's row offset; 0 locally).
    Defaults reproduce the local path: edge-replicated halo slabs and
    offset 0.

    ``skip_mask`` + ``prev_out`` select the temporal STRIP-MASK path:
    ``skip_mask`` is (B, n_strips) nonzero where the strip is provably
    static — every input row its stencil reads (the strip ± the
    radius+2 halo) is bitwise identical to the previous frame's — and
    ``prev_out`` carries the previous frame's outputs (same structure as
    this emit's outputs). Static strips copy ``prev_out`` instead of
    recomputing (fully-static tiles skip the stencil math via ``pl.when``)
    — bit-identical by purity of the front-end. The mask path composes
    with ``halos``/``row_offset``: a sharded temporal step passes its
    shard-local mask (computed against halo-exchanged frame rows) next to
    the exchanged slabs — the two mechanisms touch disjoint refs.
    """
    if emit not in ("nms", "code", "packed"):
        raise ValueError(emit)
    if (skip_mask is None) != (prev_out is None):
        raise ValueError("skip_mask and prev_out come together")
    if emit == "packed":
        if imgs.shape[-1] % 32:
            raise ValueError(
                f"emit='packed' needs W % 32 == 0, got W={imgs.shape[-1]}"
            )
        prev_code = None
        if prev_out is not None:
            strong_w, weak_w = prev_out
            prev_code = common.unpack_mask(strong_w) + common.unpack_mask(weak_w)
        code = fused_canny_strips(
            imgs, sigma, radius, low, high, l2_norm, "code", block_rows,
            interpret, true_hw, batch_block, halos, row_offset, skip_mask,
            prev_code,
        )
        return common.pack_mask(code >= 2), common.pack_mask(code >= 1)
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
        # edge-replicate = the oracle's border rule; identical to the old
        # in-kernel i==0 / i==n-1 fix, now one uniform externally-fed path
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
    out_specs = common.out_strip_spec(bh, w, bt, sx)
    out_dtype = jnp.float32 if emit == "nms" else jnp.uint8
    out_shape = jax.ShapeDtypeStruct((b, h, w), out_dtype)
    in_specs = [
        prev,
        cur,
        nxt,
        common.halo_spec(h2, w, bt, sx),
        common.halo_spec(h2, w, bt, sx),
        common.per_image_spec(2, bt, sx),
        common.offset_spec(bt, sx),
    ]
    operands = [
        imgs,
        imgs,
        imgs,
        halo_top.astype(imgs.dtype),
        halo_bot.astype(imgs.dtype),
        common.per_image_table(true_hw),
        row_offset,
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
            l2_norm=l2_norm,
            low=low,
            high=high,
            emit=emit,
            masked=skip_mask is not None,
            grid_axis=sx,
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        compiler_params=common.compiler_params(),
    )(*operands)
