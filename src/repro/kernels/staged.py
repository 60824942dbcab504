"""Per-stage Pallas Canny — the paper-faithful stage structure on the
full pattern stack.

The fused kernel (``fused_canny``) buys its HBM savings by collapsing
the stages; this module keeps them separate (one launch per stage, the
paper's farm-of-maps shape) while composing the SAME distribution,
serving, and temporal planes the fused path runs:

  * ``staged_canny``            — true-size-aware serving entry; local or
                                  inside ONE ``shard_map`` (per-stage halo
                                  exchanges between launches).
  * ``staged_canny_warm``       — temporal warm-start step (packed
                                  warm-seed hysteresis fixpoint).
  * ``staged_canny_warm_skip``  — warm + the static-strip front-end skip,
                                  per stage: each stage carries its own
                                  static mask (halo widens as the stencil
                                  deepens: gaussian ±r, sobel ±(r+1),
                                  NMS ±(r+2)) and an all-static frame
                                  skips each stage's launch outright via
                                  ``lax.cond``.

Bit-exactness is by the same three arguments as the fused path
(DESIGN.md §9–10): external halo slabs stitch shard-local grids into the
global stencil; the sobel kernel anchors border semantics at per-image
true sizes (so bucket padding is inert); and the strip skip only ever
reuses outputs whose full stencil input is bitwise unchanged (purity).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core.canny.hysteresis import warm_seed
from repro.core.patterns.dist import LOCAL, Dist, StencilCtx
from repro.core.patterns.stencil import overlap_strips
from repro.kernels import common
from repro.kernels.fused_canny.ops import (
    _check_dist_batch,
    _pad_rows_to,
    _run_sharded,
    _shard_grid,
    sharded_strip_masks,
    static_strip_masks,
    warm_ctxs,
)
from repro.kernels.gaussian.gaussian import gaussian_blur_strips
from repro.kernels.hysteresis.ops import (
    hysteresis_from_masks,
    packed_fixpoint,
    packed_fixpoint_count,
)
from repro.kernels.nms.nms import nms_strips
from repro.kernels.sobel.sobel import sobel_strips


def _pack_thresholds(sup, low, high):
    """Suppressed magnitudes → bit-packed (strong, weak) words. The only
    inter-stage step that is plain jnp (elementwise, no stencil)."""
    return common.pack_mask(sup >= high), common.pack_mask(sup >= low)


def _frontend(
    x, hw, row_off, bh, ctx, zctx,
    sigma, radius, l2_norm, interpret,
    masks=None, prev=None,
):
    """The three stage launches on a (shard-)local block, halos exchanged
    between launches when ``ctx`` is sharded. ``masks``/``prev`` select
    the temporal strip-skip path: per-stage static masks + stored previous
    outputs, each stage launch-skipped entirely via ``lax.cond`` when every
    strip is static (GLOBALLY static under a mesh — the predicate joins
    the tile counts over ``ctx.sync_axes`` so every device takes the same
    branch). Returns ((blur, mag, dirs, sup), fe_launches,
    recomputed_tiles) — mesh counts are the global consensus values.

    Sharded without masks, every stage launches through ``overlap_strips``:
    the stage's interior strips depend only on the previous stage's local
    output, so each ppermute slab exchange is in flight WHILE the interior
    computes, and only the two boundary strips wait on arrival — the
    staged pipeline never serializes a full stage behind its halo
    exchange. With masks the slabs bind whole (the strip-mask grid cannot
    be row-sliced), exchanged BEFORE each stage's cond so no collective
    ever sits inside a branch."""
    sharded = ctx.axis_name is not None

    if sharded and masks is None:
        g_halos = ctx.halo_rows(x, max(radius, 1))
        blur = overlap_strips(
            lambda ops, slabs, r0: gaussian_blur_strips(
                ops[0], sigma, radius, bh, interpret, halos=slabs
            ),
            (x,), g_halos, block_rows=bh,
        )
        s_halos = ctx.halo_rows(blur, 1)
        mag, dirs = overlap_strips(
            lambda ops, slabs, r0: sobel_strips(
                ops[0], l2_norm, bh, interpret, true_hw=hw, halos=slabs,
                row_offset=row_off + r0,
            ),
            (blur,), s_halos, block_rows=bh,
        )
        n_halos = zctx.halo_rows(mag, 1)
        sup = overlap_strips(
            lambda ops, slabs, r0: nms_strips(
                ops[0], ops[1], bh, interpret, halos=slabs
            ),
            (mag, dirs), n_halos, block_rows=bh,
        )
        return (blur, mag, dirs, sup), jnp.int32(3), jnp.int32(0)

    if sharded:
        def stage_sh(compute_fn, reuse_val, mask):
            n_tiles = ctx.sum_global(jnp.asarray(mask.size, jnp.int32))
            n_static = ctx.sum_global(jnp.sum(mask.astype(jnp.int32)))
            out, launches = lax.cond(
                n_static == n_tiles,
                lambda _: (reuse_val, jnp.int32(0)),
                lambda _: (compute_fn(mask.astype(jnp.int32)), jnp.int32(1)),
                None,
            )
            return out, launches, n_tiles - n_static

        g_halos = ctx.halo_rows(x, max(radius, 1))
        blur, lg, sg = stage_sh(
            lambda m: gaussian_blur_strips(
                x, sigma, radius, bh, interpret, halos=g_halos,
                skip_mask=m, prev_out=prev[0],
            ),
            prev[0], masks[0],
        )
        s_halos = ctx.halo_rows(blur, 1)
        (mag, dirs), ls, ss = stage_sh(
            lambda m: sobel_strips(
                blur, l2_norm, bh, interpret, true_hw=hw, halos=s_halos,
                row_offset=row_off, skip_mask=m,
                prev_out=(prev[1], prev[2]),
            ),
            (prev[1], prev[2]), masks[1],
        )
        n_halos = zctx.halo_rows(mag, 1)
        sup, ln, sn = stage_sh(
            lambda m: nms_strips(
                mag, dirs, bh, interpret, halos=n_halos,
                skip_mask=m, prev_out=prev[3],
            ),
            prev[3], masks[2],
        )
        return (blur, mag, dirs, sup), lg + ls + ln, sg + ss + sn

    def stage(compute_fn, reuse_val, mask):
        if mask is None:
            return compute_fn(None), jnp.int32(1), jnp.int32(0)
        n_tiles = jnp.int32(mask.size)
        n_static = jnp.sum(mask.astype(jnp.int32))
        out, launches = lax.cond(
            n_static == n_tiles,
            lambda _: (reuse_val, jnp.int32(0)),
            lambda _: (compute_fn(mask.astype(jnp.int32)), jnp.int32(1)),
            None,
        )
        return out, launches, n_tiles - n_static

    blur, lg, sg = stage(
        lambda m: gaussian_blur_strips(
            x, sigma, radius, bh, interpret,
            skip_mask=m, prev_out=None if m is None else prev[0],
        ),
        None if masks is None else prev[0],
        None if masks is None else masks[0],
    )
    (mag, dirs), ls, ss = stage(
        lambda m: sobel_strips(
            blur, l2_norm, bh, interpret, true_hw=hw,
            row_offset=row_off, skip_mask=m,
            prev_out=None if m is None else (prev[1], prev[2]),
        ),
        None if masks is None else (prev[1], prev[2]),
        None if masks is None else masks[1],
    )
    sup, ln, sn = stage(
        lambda m: nms_strips(
            mag, dirs, bh, interpret,
            skip_mask=m, prev_out=None if m is None else prev[3],
        ),
        None if masks is None else prev[3],
        None if masks is None else masks[2],
    )
    return (blur, mag, dirs, sup), lg + ls + ln, sg + ss + sn


@functools.partial(
    jax.jit,
    static_argnames=(
        "sigma", "radius", "low", "high", "l2_norm", "block_rows", "interpret",
        "dist",
    ),
)
def staged_canny(
    img: jax.Array,
    sigma: float = 1.4,
    radius: int = 2,
    low: float = 0.1,
    high: float = 0.2,
    l2_norm: bool = True,
    block_rows: int | None = None,
    interpret: bool | None = None,
    true_hw: jax.Array | None = None,
    dist: Dist = LOCAL,
) -> jax.Array:
    """Full per-stage Canny: 3 front-end launches + packed hysteresis.

    ``true_hw`` anchors border math at per-image pre-padding sizes, so
    the shape-bucketed serving layer is bit-exact on this path exactly as
    on the fused one. A non-local ``dist`` runs ALL stages inside one
    ``shard_map`` — per-stage ppermute halo exchanges between launches,
    hysteresis on the global changed-map consensus — bit-identical to the
    local path. W % 32 == 0 is required under a mesh (packed hysteresis);
    locally, non-multiple widths fall back to the padded-mask fixpoint.
    """
    imgs, had_batch = common.as_batch(img.astype(jnp.float32))
    b, h, w = imgs.shape
    min_rows = max(radius, 1)
    lo, hi = low, high

    if not dist.is_local:
        if w % 32:
            raise ValueError(
                f"sharded per-stage canny needs W % 32 == 0 (packed "
                f"hysteresis), got W={w}; bucket widths to a multiple of 32"
            )
        # one zero-rule context serves both the NMS halo exchange and the
        # hysteresis consensus (same axis, same sync set)
        zctx = StencilCtx(dist.space_axis, "zero", sync_axes=dist.sync_axes())

        def shard_fn(x, hw, row_off, bh, ctx):
            (_, _, _, sup), _, _ = _frontend(
                x, hw, row_off, bh, ctx, zctx,
                sigma, radius, l2_norm, interpret,
            )
            strong_w, weak_w = _pack_thresholds(sup, lo, hi)
            packed = packed_fixpoint(strong_w, weak_w, bh, interpret, ctx=zctx)
            return common.unpack_mask(packed)

        edges = _run_sharded(imgs, true_hw, min_rows, block_rows, dist, shard_fn)
        return edges if had_batch else edges[0]

    bh = block_rows or common.pick_block_rows(h, min_rows=min_rows)
    padded, h = common.pad_rows_to_multiple(imgs, bh)
    if true_hw is None:
        true_hw = jnp.broadcast_to(jnp.asarray([h, w], jnp.int32), (b, 2))
    row_off = jnp.zeros((1, 1), jnp.int32)
    ctx = StencilCtx(None, "edge")
    (_, _, _, sup), _, _ = _frontend(
        padded, true_hw.astype(jnp.int32), row_off, bh, ctx, ctx,
        sigma, radius, l2_norm, interpret,
    )
    if w % 32:
        edges = hysteresis_from_masks(sup >= hi, sup >= lo, bh, interpret)
    else:
        strong_w, weak_w = _pack_thresholds(sup, lo, hi)
        edges = common.unpack_mask(
            packed_fixpoint(strong_w, weak_w, bh, interpret)
        )
    edges = common.crop_rows(edges, h)
    return edges if had_batch else edges[0]


def _temporal_setup(imgs, radius, block_rows):
    b, h, w = imgs.shape
    if w % 32:
        raise ValueError(f"staged warm path needs W % 32 == 0, got W={w}")
    bh = block_rows or common.pick_block_rows(h, min_rows=radius + 2)
    padded, h = common.pad_rows_to_multiple(imgs, bh)
    return padded, b, h, w, bh


def _sharded_staged_warm(
    imgs, prev_strong_w, prev_weak_w, prev_edges_w,
    sigma, radius, low, high, l2_norm, block_rows, interpret, true_hw, dist,
):
    """``staged_canny_warm`` inside ONE shard_map — per-stage halo
    exchanges between launches, mesh-sharded packed state words, the
    space-axis warm-seed gate and the all-axes fixpoint consensus."""
    b, h, w = imgs.shape
    if w % 32:
        raise ValueError(f"staged warm path needs W % 32 == 0, got W={w}")
    _check_dist_batch(b, dist)
    hp, hl, bh = _shard_grid(h, dist, radius + 2, block_rows)
    padded = _pad_rows_to(imgs, hp, "edge")
    if true_hw is None:
        true_hw = jnp.broadcast_to(jnp.asarray([h, w], jnp.int32), (b, 2))
    fctx, hctx, gctx = warm_ctxs(dist)
    space = dist.space_axis

    def local_fn(x, ps, pw, pe, hw):
        off = lax.axis_index(space) * hl if space is not None else 0
        row_off = jnp.full((1, 1), off, jnp.int32)
        (_, _, _, sup), _, _ = _frontend(
            x, hw, row_off, bh, fctx, hctx, sigma, radius, l2_norm, interpret
        )
        strong_w, weak_w = _pack_thresholds(sup, low, high)
        seed = warm_seed(strong_w, weak_w, ps, pw, pe, ctx=gctx)
        packed, launches, dilations = packed_fixpoint_count(
            seed, weak_w, bh, interpret, ctx=hctx
        )
        return common.unpack_mask(packed), strong_w, weak_w, packed, launches, dilations

    fn = jax.shard_map(
        local_fn,
        mesh=dist.mesh,
        in_specs=(dist.batch_spec(),) * 4 + (dist.table_spec(),),
        out_specs=(dist.batch_spec(),) * 4 + (P(), P()),
        check_vma=False,
    )
    edges, strong_w, weak_w, packed, launches, dilations = fn(
        padded, prev_strong_w, prev_weak_w, prev_edges_w,
        true_hw.astype(jnp.int32),
    )
    edges = common.crop_rows(edges, h)
    cost = (launches, dilations, jnp.int32(3), jnp.int32(0))
    return edges, (strong_w, weak_w, packed), cost


def _sharded_staged_warm_skip(
    imgs, prev_imgs, prev_blur, prev_mag, prev_dirs, prev_sup,
    prev_strong_w, prev_weak_w, prev_edges_w, have_prev,
    sigma, radius, low, high, l2_norm, block_rows, interpret, true_hw, dist,
):
    """``staged_canny_warm_skip`` inside ONE shard_map: per-stage static
    masks from shard-local halo-extended frame diffs
    (``sharded_strip_masks`` — one exchange + cumsum shared by the three
    stencil depths), per-stage globally-uniform launch-skip conds, and
    every stage output sharded with the mesh."""
    b, h, w = imgs.shape
    if w % 32:
        raise ValueError(f"staged warm path needs W % 32 == 0, got W={w}")
    _check_dist_batch(b, dist)
    hp, hl, bh = _shard_grid(h, dist, radius + 2, block_rows)
    padded = _pad_rows_to(imgs, hp, "edge")
    prev_padded = _pad_rows_to(prev_imgs.astype(jnp.float32), hp, "edge")
    if true_hw is None:
        true_hw = jnp.broadcast_to(jnp.asarray([h, w], jnp.int32), (b, 2))
    fctx, hctx, gctx = warm_ctxs(dist)
    space = dist.space_axis

    def local_fn(x, px, pb, pm, pd, psup, ps, pw, pe, hprev, hw):
        off = lax.axis_index(space) * hl if space is not None else 0
        row_off = jnp.full((1, 1), off, jnp.int32)
        masks = tuple(
            m & hprev
            for m in sharded_strip_masks(
                x, px, bh, (max(radius, 1), radius + 1, radius + 2), fctx
            )
        )
        (blur, mag, dirs, sup), fe_launches, fe_strips = _frontend(
            x, hw, row_off, bh, fctx, hctx, sigma, radius, l2_norm, interpret,
            masks=masks, prev=(pb, pm, pd, psup),
        )
        strong_w, weak_w = _pack_thresholds(sup, low, high)
        seed = warm_seed(strong_w, weak_w, ps, pw, pe, ctx=gctx)
        packed, launches, dilations = packed_fixpoint_count(
            seed, weak_w, bh, interpret, ctx=hctx
        )
        return (
            common.unpack_mask(packed), blur, mag, dirs, sup,
            strong_w, weak_w, packed,
            launches, dilations, fe_launches, fe_strips,
        )

    fn = jax.shard_map(
        local_fn,
        mesh=dist.mesh,
        in_specs=(dist.batch_spec(),) * 9 + (P(), dist.table_spec()),
        out_specs=(dist.batch_spec(),) * 8 + (P(),) * 4,
        check_vma=False,
    )
    (
        edges, blur, mag, dirs, sup, strong_w, weak_w, packed,
        launches, dilations, fe_launches, fe_strips,
    ) = fn(
        padded, prev_padded, prev_blur, prev_mag, prev_dirs, prev_sup,
        prev_strong_w, prev_weak_w, prev_edges_w, have_prev,
        true_hw.astype(jnp.int32),
    )
    edges = common.crop_rows(edges, h)
    cost = (launches, dilations, fe_launches, fe_strips)
    return edges, (blur, mag, dirs, sup), (strong_w, weak_w, packed), padded, cost


@functools.partial(
    jax.jit,
    static_argnames=(
        "sigma", "radius", "low", "high", "l2_norm", "block_rows", "interpret",
        "dist",
    ),
)
def staged_canny_warm(
    imgs: jax.Array,
    prev_strong_w: jax.Array,
    prev_weak_w: jax.Array,
    prev_edges_w: jax.Array,
    sigma: float = 1.4,
    radius: int = 2,
    low: float = 0.1,
    high: float = 0.2,
    l2_norm: bool = True,
    block_rows: int | None = None,
    interpret: bool | None = None,
    true_hw: jax.Array | None = None,
    dist: Dist = LOCAL,
):
    """One streaming frame step on the per-stage path: 3 front-end
    launches + the WARM-STARTED packed hysteresis fixpoint — the same
    exactness-gated seed (``core.canny.hysteresis.warm_seed``) the fused
    path threads, so edges are bit-identical to cold on every frame.
    A non-local ``dist`` runs the step inside ``shard_map`` with the
    packed state sharded like the batch (``_sharded_staged_warm``).

    Returns ``(edges, (strong_w, weak_w, edges_w), cost)`` with
    ``cost = (launches, dilations, frontend_launches, frontend_strips)``
    — ``frontend_launches`` is the constant 3 here (every stage ran).
    """
    imgs = imgs.astype(jnp.float32)
    if not dist.is_local:
        return _sharded_staged_warm(
            imgs, prev_strong_w, prev_weak_w, prev_edges_w, sigma, radius,
            low, high, l2_norm, block_rows, interpret, true_hw, dist,
        )
    padded, b, h, w, bh = _temporal_setup(imgs, radius, block_rows)
    if true_hw is None:
        true_hw = jnp.broadcast_to(jnp.asarray([h, w], jnp.int32), (b, 2))
    ctx = StencilCtx(None, "edge")
    row_off = jnp.zeros((1, 1), jnp.int32)
    (_, _, _, sup), fe, _ = _frontend(
        padded, true_hw.astype(jnp.int32), row_off, bh, ctx, ctx,
        sigma, radius, l2_norm, interpret,
    )
    strong_w, weak_w = _pack_thresholds(sup, low, high)
    seed = warm_seed(strong_w, weak_w, prev_strong_w, prev_weak_w, prev_edges_w)
    packed, launches, dilations = packed_fixpoint_count(seed, weak_w, bh, interpret)
    edges = common.crop_rows(common.unpack_mask(packed), h)
    return edges, (strong_w, weak_w, packed), (launches, dilations, fe, jnp.int32(0))


@functools.partial(
    jax.jit,
    static_argnames=(
        "sigma", "radius", "low", "high", "l2_norm", "block_rows", "interpret",
        "dist",
    ),
)
def staged_canny_warm_skip(
    imgs: jax.Array,
    prev_imgs: jax.Array,
    prev_blur: jax.Array,
    prev_mag: jax.Array,
    prev_dirs: jax.Array,
    prev_sup: jax.Array,
    prev_strong_w: jax.Array,
    prev_weak_w: jax.Array,
    prev_edges_w: jax.Array,
    have_prev: jax.Array,
    sigma: float = 1.4,
    radius: int = 2,
    low: float = 0.1,
    high: float = 0.2,
    l2_norm: bool = True,
    block_rows: int | None = None,
    interpret: bool | None = None,
    true_hw: jax.Array | None = None,
    dist: Dist = LOCAL,
):
    """``staged_canny_warm`` + the static-strip front-end skip, PER STAGE.

    Each stage carries its own static mask — a strip is static for a
    stage iff every input row that stage's cumulative stencil reads
    (gaussian ±radius, sobel ±(radius+1), NMS ±(radius+2)) is bitwise
    identical to the previous frame — and reuses the stored stage output
    on static strips (``skip_mask`` kernel path). An all-static frame
    skips each stage's launch entirely (``lax.cond``), so a held stream
    reports ZERO front-end launches after frame 0, exactly like the fused
    path. Bit-identical by purity, stage by stage.

    Returns ``(edges, (blur, mag, dirs, sup), (strong_w, weak_w,
    edges_w), frame, cost)`` — the per-stage outputs to thread into the
    next frame, the packed hysteresis state, the (padded) frame to diff
    against, and ``cost = (launches, dilations, frontend_launches,
    frontend_strips)`` where ``frontend_strips`` sums recomputed
    (image, strip) tiles over the three stages. A non-local ``dist`` runs
    the whole step — masks included — inside ``shard_map``
    (``_sharded_staged_warm_skip``), with per-stage state sharded like
    the batch.
    """
    imgs = imgs.astype(jnp.float32)
    if not dist.is_local:
        return _sharded_staged_warm_skip(
            imgs, prev_imgs, prev_blur, prev_mag, prev_dirs, prev_sup,
            prev_strong_w, prev_weak_w, prev_edges_w, have_prev,
            sigma, radius, low, high, l2_norm, block_rows, interpret,
            true_hw, dist,
        )
    padded, b, h, w, bh = _temporal_setup(imgs, radius, block_rows)
    prev_padded, _ = common.pad_rows_to_multiple(prev_imgs.astype(jnp.float32), bh)
    if true_hw is None:
        true_hw = jnp.broadcast_to(jnp.asarray([h, w], jnp.int32), (b, 2))
    # one frame diff + cumsum shared by all three stencil depths
    masks = tuple(
        m & have_prev
        for m in static_strip_masks(
            padded, prev_padded, bh, (max(radius, 1), radius + 1, radius + 2)
        )
    )
    ctx = StencilCtx(None, "edge")
    row_off = jnp.zeros((1, 1), jnp.int32)
    (blur, mag, dirs, sup), fe_launches, fe_strips = _frontend(
        padded, true_hw.astype(jnp.int32), row_off, bh, ctx, ctx,
        sigma, radius, l2_norm, interpret,
        masks=masks, prev=(prev_blur, prev_mag, prev_dirs, prev_sup),
    )
    strong_w, weak_w = _pack_thresholds(sup, low, high)
    seed = warm_seed(strong_w, weak_w, prev_strong_w, prev_weak_w, prev_edges_w)
    packed, launches, dilations = packed_fixpoint_count(seed, weak_w, bh, interpret)
    edges = common.crop_rows(common.unpack_mask(packed), h)
    cost = (launches, dilations, fe_launches, fe_strips)
    return edges, (blur, mag, dirs, sup), (strong_w, weak_w, packed), padded, cost
