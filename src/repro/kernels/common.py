"""Shared Pallas plumbing for the batch-native row-strip stencil kernels.

TPU adaptation of the paper's stencils: each kernel instance owns a
(BT, BH, W) tile — BT whole-image slots by a BH-row strip — staged
HBM→VMEM by ``pallas_call`` over a 2D ``(batch_tiles, n_strips)`` grid.
The batch is therefore first-class: one launch covers every image, the
strip math vectorizes across the BT in-block images, and the grid only
tiles what VMEM can't hold.

Halos are obtained with the **neighbour-strip trick**: the same input is
bound three times with strip-axis index maps ``i−1, i, i+1`` (clamped at
the grid ends), so the kernel sees its strip plus both neighbours
without dynamic DMA. Clamping is per-image by construction: blocks never
straddle images on the batch axis, so a clamped neighbour always comes
from the same image. Boundary strips bind externally supplied halo slabs
(``halo_spec``): the pad rule (edge-replicate or zero) in local mode, or
— inside ``shard_map`` — the adjacent SHARD's rows exchanged by
``StencilCtx.halo_rows``, which composes the shard-local grids into one
global stencil bit-identically (DESIGN.md §8). ``offset_spec`` carries
the shard's global row offset for true-size border logic.

Strips are (8,128)-aligned for the VPU; BH defaults to 128 rows and
shrinks for small images, and BT is chosen so the working set fits the
VMEM budget. ops.py wrappers pad the row count up to a multiple of BH
with edge-replicated rows — provably output-invariant for every Canny
stage (clone rows neither change gradients in the crop region nor add
connectivity; see DESIGN.md).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


def default_interpret() -> bool:
    """Pallas TPU kernels execute in interpret mode off-TPU (CPU CI)."""
    return not on_tpu()


def pick_block_rows(h: int, target: int = 128, min_rows: int = 1) -> int:
    """Strip height: ``target`` rows, shrunk for small images, never below
    ``min_rows`` (the stage halo — a strip must be able to feed its
    neighbour's halo). Non-divisible heights are edge-padded by ops.py.
    """
    return max(min(h, target), min_rows)


SUBLANES = 8  # a strip height the TPU kernel compiler tiles for any strip count


def pick_block_rows_divisor(h: int, target: int = 128, min_rows: int = 1) -> int:
    """Strip height that exactly divides ``h`` — the shard-local variant.

    Inside ``shard_map`` a shard cannot pad its own rows (local pad rows
    would land BETWEEN shards, breaking global row adjacency), so the
    strip height must divide the shard-local height exactly. Returns the
    largest divisor of ``h`` that is ≤ ``target`` and ≥ ``min_rows``,
    preferring multiples of ``SUBLANES``: the chip takes a strip shorter
    than its array only when it is sublane-aligned.
    """
    if h < min_rows:
        raise ValueError(
            f"shard-local height {h} smaller than the stage halo {min_rows}; "
            "use fewer row shards or a larger image"
        )
    fits = [d for d in range(min(h, target), min_rows - 1, -1) if h % d == 0]
    aligned = [d for d in fits if d % SUBLANES == 0]
    # h itself always divides (a single strip per shard)
    return (aligned or fits or [h])[0]


# Scoped VMEM every strip kernel may claim (v5e has 128 MiB per core; the
# compiler's default scope is 16 MiB, which a 1080p fused strip already
# fills). Passed to every pallas_call via ``compiler_params``.
VMEM_LIMIT_BYTES = 64 << 20
# Live set per image, in f32 (BH, W-rounded-to-128-lanes) tiles: the most
# any strip kernel here needs at the smallest limit it compiles under for
# v5e — measured 12-21 (sobel 20.5, fused 16-19, hysteresis words 17),
# rounded up.
LIVE_TILES = 24


def compiler_params():
    return pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)


def pick_batch_block(b: int, bh: int, w: int) -> int:
    """Images per kernel instance (the BT block dim): the largest divisor
    of ``b`` whose live set (``LIVE_TILES`` lane-padded f32 strip tiles
    per image) fits half the VMEM limit on the chip. Interpret mode gets a
    roomier budget — there the point of BT is amortizing per-grid-cell
    overhead, not VMEM.
    """
    budget = VMEM_LIMIT_BYTES // 2 if on_tpu() else 256 << 20
    per_image = bh * (-(-w // 128) * 128) * 4 * LIVE_TILES
    bt = max(1, min(b, budget // per_image))
    while b % bt:
        bt -= 1
    return bt


def strip_grid(b: int, bt: int, n_strips: int):
    """Launch grid + strip-walking axis for a (batch, strip) kernel.

    Normally the grid is 2D ``(b // bt, n_strips)`` and strips walk axis 1
    (``STRIP_AXIS``). When ONE batch tile covers the whole batch (``bt ==
    b`` — the b=1 serving case, and any batch small enough for a single
    VMEM-resident block) the batch grid axis is degenerate: it buys no
    tiling, but every index map still evaluates a dead batch coordinate
    per grid cell. Dropping it dispatches a flat 1D ``(n_strips,)`` grid —
    the no-batch-axis program a ``jax.vmap`` lifting never produces, which
    is what closes the b=1 batch-grid-vs-vmap gap (BENCH
    ``canny_batchgrid_b1_parity``). Returns ``(grid, strip_axis)``; pass
    ``strip_axis`` to the kernel so ``pl.program_id`` reads the right dim.
    """
    if bt == b:
        return (n_strips,), 0
    return (b // bt, n_strips), 1


def strip_specs(n_strips: int, bh: int, w: int, bt: int = 1, strip_axis: int = 1):
    """(prev, cur, next) BlockSpecs for the neighbour-strip halo trick on
    a 2D ``(batch_tiles, n_strips)`` grid — or the flat 1D ``(n_strips,)``
    grid when ``strip_axis == 0`` (see ``strip_grid``). Blocks are
    (BT, BH, W): the strip-axis clamp is per-image because a block never
    crosses images.
    """
    if strip_axis == 0:
        prev = pl.BlockSpec((bt, bh, w), lambda i: (0, jnp.maximum(i - 1, 0), 0))
        cur = pl.BlockSpec((bt, bh, w), lambda i: (0, i, 0))
        nxt = pl.BlockSpec(
            (bt, bh, w), lambda i: (0, jnp.minimum(i + 1, n_strips - 1), 0)
        )
        return prev, cur, nxt
    prev = pl.BlockSpec((bt, bh, w), lambda b, i: (b, jnp.maximum(i - 1, 0), 0))
    cur = pl.BlockSpec((bt, bh, w), lambda b, i: (b, i, 0))
    nxt = pl.BlockSpec(
        (bt, bh, w), lambda b, i: (b, jnp.minimum(i + 1, n_strips - 1), 0)
    )
    return prev, cur, nxt


def out_strip_spec(bh: int, w: int, bt: int = 1, strip_axis: int = 1):
    if strip_axis == 0:
        return pl.BlockSpec((bt, bh, w), lambda i: (0, i, 0))
    return pl.BlockSpec((bt, bh, w), lambda b, i: (b, i, 0))


def per_image_spec(cols: int, bt: int = 1, strip_axis: int = 1):
    """Spec for per-image metadata rows, e.g. the true-size table in its
    (B, 1, cols) kernel layout (``per_image_table``): every strip of
    image-block b binds the same (BT, 1, cols) slice. The unit sublane
    dim keeps the block legal for any BT under the (8, 128) rule."""
    if strip_axis == 0:
        return pl.BlockSpec((bt, 1, cols), lambda i: (0, 0, 0))
    return pl.BlockSpec((bt, 1, cols), lambda b, i: (b, 0, 0))


def per_image_table(table):
    """(B, cols) per-image table → the (B, 1, cols) int32 kernel layout."""
    return table.astype(jnp.int32).reshape(table.shape[0], 1, table.shape[1])


def true_sizes(hw_ref):
    """Kernel side of the true-size table: per-image (height, width),
    each shaped (BT, 1, 1) to broadcast over a (BT, rows, W) tile."""
    return hw_ref[:, :, 0:1], hw_ref[:, :, 1:2]


def halo_spec(halo: int, w: int, bt: int = 1, strip_axis: int = 1):
    """Spec for an externally supplied (B, halo, W) halo slab: every strip
    of image-block b binds the same rows. The slab feeds the FIRST/LAST
    local strips (where the clamped neighbour trick has no neighbour) —
    under ``shard_map`` it carries the ppermute-exchanged rows of the
    adjacent shard, so the shard-local grid composes into one global
    stencil bit-identically (see ``assemble_rows``)."""
    if strip_axis == 0:
        return pl.BlockSpec((bt, halo, w), lambda i: (0, 0, 0))
    return pl.BlockSpec((bt, halo, w), lambda b, i: (b, 0, 0))


def offset_spec(bt: int = 1, strip_axis: int = 1):
    """Spec for the (1, 1) int32 global-row-offset scalar: the first global
    row this shard owns, added to ``i*bh`` so border logic anchored at
    per-image TRUE sizes keeps working on a shard-local grid."""
    del bt
    if strip_axis == 0:
        return pl.BlockSpec((1, 1), lambda i: (0, 0))
    return pl.BlockSpec((1, 1), lambda b, i: (0, 0))


STRIP_AXIS = 1  # grid axis that walks row strips; axis 0 tiles the batch


def assemble_rows(
    prev,
    cur,
    nxt,
    halo: int,
    mode: str,
    grid_axis: int = STRIP_AXIS,
    top_ext=None,
    bot_ext=None,
    grid_pos: tuple | None = None,
):
    """Build the halo-extended tile (..., BH+2·halo, W) inside the kernel.

    ``prev``/``nxt`` are the clamped neighbour strips; at the grid ends
    they alias ``cur``, so their contribution is replaced either by the
    border rule (edge-replicate or zeros) or — when ``top_ext``/``bot_ext``
    are given — by the externally supplied halo slabs. External slabs are
    how the shard-local grid composes under ``shard_map``: the first/last
    local strips read the neighbour SHARD's rows (exchanged via ppermute,
    boundary shards pre-patched with the pad rule), so the stitched global
    stencil is bit-identical to the unsharded one.

    ``grid_pos`` supplies a precomputed ``(i, n_strips)`` pair. Required
    when the caller sits inside a ``pl.when`` branch: ``pl.program_id``
    may only be bound at the kernel's top level (inside the branch it
    would be staged into the cond jaxpr, which has no lowering).
    """
    if grid_pos is not None:
        i, n = grid_pos
    else:
        i = pl.program_id(grid_axis)
        n = pl.num_programs(grid_axis)
    top = prev[..., -halo:, :]
    bot = nxt[..., :halo, :]
    if top_ext is not None:
        top_fix = top_ext.astype(top.dtype)
        bot_fix = bot_ext.astype(bot.dtype)
    elif mode == "edge":
        top_fix = jnp.broadcast_to(cur[..., 0:1, :], top.shape)
        bot_fix = jnp.broadcast_to(cur[..., -1:, :], bot.shape)
    elif mode == "zero":
        top_fix = jnp.zeros_like(top)
        bot_fix = jnp.zeros_like(bot)
    else:
        raise ValueError(mode)
    top = jnp.where(i == 0, top_fix, top)
    bot = jnp.where(i == n - 1, bot_fix, bot)
    return jnp.concatenate([top, cur, bot], axis=-2)


def default_halos(imgs, halo: int, mode: str):
    """The local-mode (top, bot) halo slabs for a (B, H, W)-like array:
    edge-replicated boundary rows or zeros — the same pad rule the old
    in-kernel i==0 / i==n-1 fix applied, now one uniform externally-fed
    path shared by every strip kernel. Under ``shard_map`` callers pass
    ``StencilCtx.halo_rows`` slabs instead."""
    b, _, w = imgs.shape
    if mode == "edge":
        top = jnp.broadcast_to(imgs[:, :1, :], (b, halo, w))
        bot = jnp.broadcast_to(imgs[:, -1:, :], (b, halo, w))
    elif mode == "zero":
        top = jnp.zeros((b, halo, w), imgs.dtype)
        bot = top
    else:
        raise ValueError(mode)
    return top, bot


def check_halos(halos, b: int, halo: int, w: int):
    top, bot = halos
    if top.shape != (b, halo, w) or bot.shape != (b, halo, w):
        raise ValueError(
            f"halo slabs must be {(b, halo, w)}, got {top.shape} / {bot.shape}"
        )
    return top, bot


def strip_map_spec(bt: int = 1, strip_axis: int = 1):
    """Spec for a per-(image, strip) map — the hysteresis changed counters,
    the temporal skip mask — in its (B, n_strips, 1, 1) kernel layout: one
    (BT, 1, 1, 1) cell per grid point. The unit minor dims equal the
    array's, which is what the (8, 128) block rule admits for any BT and
    strip count; callers view the map as (B, n_strips) outside."""
    if strip_axis == 0:
        return pl.BlockSpec((bt, 1, 1, 1), lambda i: (0, i, 0, 0))
    return pl.BlockSpec((bt, 1, 1, 1), lambda b, i: (b, i, 0, 0))


def skip_specs_operands(
    skip_mask, prev_out, out_shape, bh: int, bt: int, strip_axis: int = 1
):
    """Wrapper-side plumbing for the temporal strip-mask path, shared by
    every masked stencil kernel: validates the (B, n_strips) mask + the
    stored previous outputs (must mirror the kernel's outputs exactly),
    and returns the extra (in_specs, operands) to append.
    """
    shapes = out_shape if isinstance(out_shape, tuple) else (out_shape,)
    b = shapes[0].shape[0]
    n = shapes[0].shape[1] // bh
    if skip_mask.shape != (b, n):
        raise ValueError(f"skip_mask must be {(b, n)}, got {skip_mask.shape}")
    prev_out = tuple(prev_out) if isinstance(prev_out, (tuple, list)) else (prev_out,)
    if len(prev_out) != len(shapes) or any(
        p.shape != s.shape or p.dtype != s.dtype
        for p, s in zip(prev_out, shapes)
    ):
        raise ValueError(
            f"prev_out must mirror the outputs "
            f"{[(s.shape, s.dtype) for s in shapes]}"
        )
    specs = [strip_map_spec(bt, strip_axis)]
    operands = [skip_mask.astype(jnp.int32).reshape(b, n, 1, 1)]
    for p, s in zip(prev_out, shapes):
        specs.append(out_strip_spec(bh, s.shape[-1], bt, strip_axis))
        operands.append(p)
    return specs, operands


def any_per_image(mask):
    """(BT, R, C) bool → (BT, 1, 1) int32, 1 where the image's tile has
    any set element. Lanes reduce first, then sublanes: the TPU kernel
    compiler rejects the fused two-axis reduction for BT > 1."""
    m = jnp.max(mask.astype(jnp.int32), axis=-1, keepdims=True)
    return jnp.max(m, axis=-2, keepdims=True)


def write_outputs(out_refs, compute, skip_ref=None, prev_refs=None):
    """Kernel-side output write, masked or plain.

    Without a mask every output ref takes its computed value, cast to the
    ref's dtype (kernels compute 8-bit outputs at 32 bits). With
    ``skip_ref`` (the (BT, 1, 1, 1) per-image static flags) the temporal
    strip-mask contract applies: a fully static (image-block, strip)
    tile never runs ``compute`` (``pl.when`` predication — the stencil
    math is skipped outright) and copies the stored previous outputs; a
    mixed tile computes once and selects per image. ``compute`` must be
    safe to stage inside ``pl.when`` (hoist ``pl.program_id`` via
    ``assemble_rows(grid_pos=...)``).
    """
    out_refs = tuple(out_refs)
    if skip_ref is None:
        for ref, val in zip(out_refs, compute()):
            ref[...] = val.astype(ref.dtype)
        return
    prev_refs = tuple(prev_refs)
    flags = skip_ref[...].reshape(skip_ref.shape[0], 1, 1)  # (bt, 1, 1)
    skip = flags != 0
    all_skip = jnp.min(flags) != 0

    @pl.when(all_skip)
    def _reuse():
        for ref, prev in zip(out_refs, prev_refs):
            ref[...] = prev[...]

    @pl.when(~all_skip)
    def _compute():
        for ref, prev, val in zip(out_refs, prev_refs, compute()):
            # select at the computed (32-bit) width: 8-bit outputs are an
            # HBM format only, the chip has no 8-bit vector select
            old = prev[...].astype(val.dtype)
            ref[...] = jnp.where(skip, old, val).astype(ref.dtype)


def pad_cols(x, halo: int, mode: str):
    """In-register horizontal halo (width is never sharded across strips)."""
    if halo == 0:
        return x
    lshape = x.shape[:-1] + (halo,)
    if mode == "edge":
        left = jnp.broadcast_to(x[..., 0:1], lshape)
        right = jnp.broadcast_to(x[..., -1:], lshape)
    elif mode == "zero":
        left = jnp.zeros(lshape, x.dtype)
        right = left
    else:
        raise ValueError(mode)
    return jnp.concatenate([left, x, right], axis=-1)


def pad_rows_to_multiple(img, bh: int, mode: str = "edge"):
    """Pad rows so H divides BH; returns (padded, original_h).

    mode="edge" (clone rows) preserves gaussian/sobel border semantics;
    mode="zero" preserves NMS/hysteresis zero-neighbour semantics (clone
    rows would inject non-zero diagonal neighbours at the true border).
    """
    h = img.shape[-2]
    pad = (-h) % bh
    if pad == 0:
        return img, h
    pads = [(0, 0)] * (img.ndim - 2) + [(0, pad), (0, 0)]
    if mode == "edge":
        return jnp.pad(img, pads, mode="edge"), h
    return jnp.pad(img, pads, mode="constant"), h


def crop_rows(x, h: int):
    return jax.lax.slice_in_dim(x, 0, h, axis=-2)


def as_batch(x):
    """Normalize (H, W) | (B, H, W) → ((B, H, W), had_batch_dim)."""
    if x.ndim == 2:
        return x[None], False
    if x.ndim == 3:
        return x, True
    raise ValueError(f"expected (h,w) or (b,h,w), got {x.shape}")


_BITS = 32


def pad_cols_to_multiple(x, m: int):
    """Zero-pad the last axis up to a multiple of ``m``; returns
    (padded, original_w). Zero cols are inert for mask stages."""
    w = x.shape[-1]
    pad = (-w) % m
    if pad == 0:
        return x, w
    pads = [(0, 0)] * (x.ndim - 1) + [(0, pad)]
    return jnp.pad(x, pads), w


def pack_mask(x):
    """bool/uint8 mask (..., W) → (..., NW) uint32 words, NW = W//32,
    in the PLANAR bit layout: bit k of word j is pixel k·NW + j.

    Each bit plane is one contiguous lane slice of the mask, so packing
    is 32 shifted ORs, with no lane-splitting reshape. Horizontal
    neighbours are adjacent WORDS of the same bit plane (see the
    hysteresis kernel's ``_hshift``). W must be a multiple of 32 (see
    pad_cols_to_multiple). Runs in XLA: inside a TPU kernel the same
    code compiles but loses bit planes 16-22 (``fused_canny_strips``).
    """
    w = x.shape[-1]
    if w % _BITS:
        raise ValueError(f"W={w} not a multiple of {_BITS}")
    nw = w // _BITS
    bits = (x != 0).astype(jnp.int32)
    words = bits[..., :nw]
    for k in range(1, _BITS):
        words = words | (bits[..., k * nw : (k + 1) * nw] << k)
    return jax.lax.bitcast_convert_type(words, jnp.uint32)


def unpack_mask(words):
    """(..., NW) uint32 planar words → (..., NW·32) uint8 mask."""
    planes = words[..., None, :] >> jnp.arange(_BITS, dtype=jnp.uint32)[:, None]
    bits = planes & jnp.uint32(1)  # (..., 32, NW): plane k = pixels k·NW + j
    return bits.reshape(*words.shape[:-1], words.shape[-1] * _BITS).astype(jnp.uint8)


def select_row(x, idx):
    """Per-image row select: (BT, N, W) + (BT, 1, 1) indices → (BT, 1, W).

    An iota mask and a max-reduction over the rows, which the TPU kernel
    compiler lowers (it has no dynamic slice); the one selected value
    survives the max exactly, ±0 and all.
    """
    rows = jax.lax.broadcasted_iota(jnp.int32, (1, x.shape[-2], 1), 1)
    return jnp.max(jnp.where(rows == idx, x, -jnp.inf), axis=-2, keepdims=True)


def select_col(x, idx):
    """Per-image column select on axis -1 (see ``select_row``)."""
    cols = jax.lax.broadcasted_iota(jnp.int32, (1, 1, x.shape[-1]), 2)
    return jnp.max(jnp.where(cols == idx, x, -jnp.inf), axis=-1, keepdims=True)
