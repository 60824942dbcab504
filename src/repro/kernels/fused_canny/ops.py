"""Jit'd wrappers: fused front-end and the full Pallas Canny detector.

Batch-native: (b, h, w) inputs run in ONE pallas_call per stage (front-
end, then one per hysteresis sweep). ``true_hw`` lets the serving engine
run shape-bucketed batches — images padded to a common bucket are
processed bit-identically to their unpadded selves.

Mesh-native: pass a non-local ``Dist`` and the SAME kernels run inside
``shard_map`` — the batch shards over ``dist.batch_axes``, rows over
``dist.space_axis`` with ``StencilCtx`` ppermute halo exchange feeding
the shard-local strip grids, and the hysteresis loop converges on the
global changed-map consensus. One distribution plane, one code path;
outputs are bit-identical to the local path (pinned by
tests/subproc/sharded_canny.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core.canny.hysteresis import warm_seed
from repro.core.patterns.dist import LOCAL, Dist, StencilCtx
from repro.core.patterns.stencil import overlap_strips
from repro.kernels import common
from repro.kernels.fused_canny.fused_canny import fused_canny_strips
from repro.kernels.hysteresis.ops import (
    hysteresis_from_masks,
    packed_fixpoint,
    packed_fixpoint_count,
)


def _shard_grid(h: int, dist: Dist, h2: int, block_rows: int | None):
    """Shard-local strip geometry for a global height ``h``: → (padded
    global height, shard-local height, block rows). Row padding must be
    GLOBAL (local pads would land between shards), so the padded height
    is a multiple of space_size * bh and each shard's rows divide bh.
    A shard of several strips needs sublane-aligned strips (the TPU
    kernel compiler's block rule); when no aligned divisor of the
    unpadded shard height exists, rows pad to whole 128-row strips."""
    ms = dist.space_size()
    if block_rows is not None:
        bh = block_rows
        hp = -(-h // (ms * bh)) * ms * bh
        hl = hp // ms
        if hl % bh:
            raise ValueError(f"shard-local height {hl} not a multiple of {bh}")
    else:
        hl0 = -(-h // ms)
        bh = common.pick_block_rows_divisor(hl0, min_rows=h2)
        if bh % common.SUBLANES and bh < hl0:
            bh = common.pick_block_rows(hl0)
        hp = -(-h // (ms * bh)) * ms * bh
        hl = hp // ms
        bh = common.pick_block_rows_divisor(hl, min_rows=h2)
    return hp, hl, bh


def _pad_rows_to(imgs: jax.Array, hp: int, mode: str = "edge"):
    h = imgs.shape[-2]
    if h == hp:
        return imgs
    pads = [(0, 0)] * (imgs.ndim - 2) + [(0, hp - h), (0, 0)]
    if mode == "edge":
        return jnp.pad(imgs, pads, mode="edge")
    return jnp.pad(imgs, pads)


def _check_dist_batch(b: int, dist: Dist) -> None:
    dsz = dist.batch_size()
    if b % dsz:
        raise ValueError(
            f"batch {b} not divisible by the {dist.batch_axes} axis size "
            f"{dsz}; the serving engine pads bucket batches to a multiple"
        )


def _run_sharded(imgs, true_hw, min_rows, block_rows, dist, shard_fn):
    """Shared shard_map scaffolding for the Pallas serving entry points
    (fused AND per-stage — see ``kernels/staged.py``).

    Pads rows globally to the shard grid (strip heights ≥ ``min_rows``,
    the widest stage halo), wraps ``shard_fn`` in ``shard_map`` over
    ``dist``, and hands it per-shard ``(x, hw, row_off, bh, ctx)`` — the
    shard's first global row and the stencil context whose
    ``halo_rows`` the stages call to exchange their own halos. Returns
    the global result cropped back to the true height.
    """
    if dist.pod_axis is not None:
        raise ValueError(
            "kernels never see the pod axis — frames dispatch over pods in "
            "the stream layer; build per-rank detectors via Dist.pod_slice "
            "(stream/pod.py)"
        )
    b, h, w = imgs.shape
    _check_dist_batch(b, dist)
    hp, hl, bh = _shard_grid(h, dist, min_rows, block_rows)
    padded = _pad_rows_to(imgs, hp, "edge")
    if true_hw is None:
        true_hw = jnp.broadcast_to(jnp.asarray([h, w], jnp.int32), (b, 2))
    fctx = StencilCtx(dist.space_axis, "edge", sync_axes=dist.sync_axes())
    space = dist.space_axis

    def local_fn(x, hw):
        # x: (B/data, hl, W) shard-local rows
        off = lax.axis_index(space) * hl if space is not None else 0
        row_off = jnp.full((1, 1), off, jnp.int32)
        return shard_fn(x, hw, row_off, bh, fctx)

    fn = jax.shard_map(
        local_fn,
        mesh=dist.mesh,
        in_specs=(dist.batch_spec(), dist.table_spec()),
        out_specs=dist.batch_spec(),
        check_vma=False,
    )
    return common.crop_rows(fn(padded, true_hw.astype(jnp.int32)), h)


def _sharded_fused_canny(
    imgs: jax.Array,
    sigma: float,
    radius: int,
    low: float,
    high: float,
    l2_norm: bool,
    block_rows: int | None,
    interpret: bool | None,
    true_hw: jax.Array | None,
    dist: Dist,
) -> jax.Array:
    """Fused front-end + packed hysteresis, all inside ONE shard_map."""
    if imgs.shape[-1] % 32:
        raise ValueError(
            f"sharded fused canny needs W % 32 == 0 (packed hysteresis), "
            f"got W={imgs.shape[-1]}; bucket widths to a multiple of 32"
        )
    hctx = StencilCtx(dist.space_axis, "zero", sync_axes=dist.sync_axes())
    h2 = radius + 2

    def shard_fn(x, hw, row_off, bh, ctx):
        # interior strips have no dataflow edge to the exchanged slabs, so
        # the frontend's ppermute hides under the interior launch; the
        # sharded fixpoint double-buffers its own exchange (auto overlap)
        strong_w, weak_w = overlap_strips(
            lambda ops, slabs, r0: fused_canny_strips(
                ops[0], sigma, radius, low, high, l2_norm, "packed", bh,
                interpret, hw, halos=slabs, row_offset=row_off + r0,
            ),
            (x,), ctx.halo_rows(x, h2), block_rows=bh,
        )
        packed = packed_fixpoint(strong_w, weak_w, bh, interpret, ctx=hctx)
        return common.unpack_mask(packed)

    return _run_sharded(imgs, true_hw, h2, block_rows, dist, shard_fn)


def _sharded_fused_frontend(
    imgs: jax.Array,
    sigma: float,
    radius: int,
    low: float,
    high: float,
    l2_norm: bool,
    emit: str,
    block_rows: int | None,
    interpret: bool | None,
    true_hw: jax.Array | None,
    dist: Dist,
) -> jax.Array:
    h2 = radius + 2

    def shard_fn(x, hw, row_off, bh, ctx):
        return overlap_strips(
            lambda ops, slabs, r0: fused_canny_strips(
                ops[0], sigma, radius, low, high, l2_norm, emit, bh,
                interpret, hw, halos=slabs, row_offset=row_off + r0,
            ),
            (x,), ctx.halo_rows(x, h2), block_rows=bh,
        )

    return _run_sharded(imgs, true_hw, h2, block_rows, dist, shard_fn)


@functools.partial(
    jax.jit,
    static_argnames=(
        "sigma", "radius", "low", "high", "l2_norm", "emit", "block_rows",
        "interpret", "dist",
    ),
)
def fused_frontend(
    img: jax.Array,
    sigma: float = 1.4,
    radius: int = 2,
    low: float = 0.1,
    high: float = 0.2,
    l2_norm: bool = True,
    emit: str = "code",
    block_rows: int | None = None,
    interpret: bool | None = None,
    true_hw: jax.Array | None = None,
    dist: Dist = LOCAL,
) -> jax.Array:
    """Gauss+Sobel+NMS(+threshold) in one kernel pass (mesh-aware)."""
    if emit not in ("nms", "code"):  # "packed" flows through fused_canny only
        raise ValueError(emit)
    imgs, had_batch = common.as_batch(img.astype(jnp.float32))
    if not dist.is_local:
        out = _sharded_fused_frontend(
            imgs, sigma, radius, low, high, l2_norm, emit, block_rows,
            interpret, true_hw, dist,
        )
        return out if had_batch else out[0]
    h2 = radius + 2
    bh = block_rows or common.pick_block_rows(imgs.shape[-2], min_rows=h2)
    padded, h = common.pad_rows_to_multiple(imgs, bh)
    if true_hw is None:
        true_hw = jnp.broadcast_to(
            jnp.asarray([h, imgs.shape[-1]], jnp.int32), (imgs.shape[0], 2)
        )
    out = fused_canny_strips(
        padded, sigma, radius, low, high, l2_norm, emit, bh, interpret, true_hw
    )
    out = common.crop_rows(out, h)
    return out if had_batch else out[0]


@functools.partial(
    jax.jit,
    static_argnames=(
        "sigma", "radius", "low", "high", "l2_norm", "block_rows", "interpret",
        "dist",
    ),
)
def fused_canny(
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
    """Full Canny: fused front-end + in-VMEM-fixpoint hysteresis. uint8 edges.

    When W divides 32 the front-end's code map is packed into strong/weak
    words (``fused_canny_strips(emit="packed")``) for the packed fixpoint;
    otherwise it goes through ``hysteresis_from_masks``.

    With a non-local ``dist`` the whole detector runs inside ``shard_map``
    (batch over ``dist.batch_axes``, rows over ``dist.space_axis``) and
    stays bit-identical to the local path; this path requires W % 32 == 0.
    """
    imgs, had_batch = common.as_batch(img.astype(jnp.float32))
    if not dist.is_local:
        edges = _sharded_fused_canny(
            imgs, sigma, radius, low, high, l2_norm, block_rows, interpret,
            true_hw, dist,
        )
        return edges if had_batch else edges[0]
    w = imgs.shape[-1]
    if w % 32:
        code = fused_frontend(
            imgs, sigma, radius, low, high, l2_norm, "code", block_rows, interpret,
            true_hw,
        )
        edges = hysteresis_from_masks(code >= 2, code >= 1, block_rows, interpret)
        return edges if had_batch else edges[0]

    h2 = radius + 2
    bh = block_rows or common.pick_block_rows(imgs.shape[-2], min_rows=h2)
    padded, h = common.pad_rows_to_multiple(imgs, bh)
    if true_hw is None:
        true_hw = jnp.broadcast_to(
            jnp.asarray([h, w], jnp.int32), (imgs.shape[0], 2)
        )
    # rows beyond each image's true height carry zero code by kernel
    # construction, so the fixpoint can run on the padded grid directly
    strong_w, weak_w = fused_canny_strips(
        padded, sigma, radius, low, high, l2_norm, "packed", bh, interpret, true_hw
    )
    packed = packed_fixpoint(strong_w, weak_w, bh, interpret)
    edges = common.crop_rows(common.unpack_mask(packed), h)
    return edges if had_batch else edges[0]


def static_strip_masks(
    cur: jax.Array, prev: jax.Array, block_rows: int, halos: tuple[int, ...]
) -> tuple[jax.Array, ...]:
    """Per-(image, strip) frame-diff masks for SEVERAL stencil widths at
    once: (B, Hp, W) current + previous frames → one (B, n_strips) bool
    mask per halo in ``halos``, each True iff EVERY input row the strip's
    stencil reads — rows [i·bh − halo, (i+1)·bh + halo), clamped to the
    grid — is bitwise identical between the frames. Exactly those strips
    may reuse the previous stage output (purity; DESIGN.md §9).

    The full-frame row compare and its cumulative sum are computed ONCE
    and shared by every width — per extra stencil depth only the O(n)
    range gather differs, which is what lets the per-stage skip path
    (gaussian ±r, sobel ±(r+1), NMS ±(r+2)) pay a single frame diff.
    """
    if cur.shape != prev.shape:
        raise ValueError(f"frame shapes differ: {cur.shape} vs {prev.shape}")
    b, hp, _ = cur.shape
    if hp % block_rows:
        raise ValueError(f"H={hp} not a multiple of block_rows={block_rows}")
    n = hp // block_rows
    eq = jnp.all(cur == prev, axis=-1).astype(jnp.int32)  # (B, Hp) row match
    csum = jnp.concatenate(
        [jnp.zeros((b, 1), jnp.int32), jnp.cumsum(eq, axis=1)], axis=1
    )
    out = []
    for halo in halos:
        lo = np.maximum(np.arange(n) * block_rows - halo, 0)
        hi = np.minimum((np.arange(n) + 1) * block_rows + halo, hp)
        out.append((csum[:, hi] - csum[:, lo]) == jnp.asarray(hi - lo, jnp.int32))
    return tuple(out)


def static_strip_mask(
    cur: jax.Array, prev: jax.Array, block_rows: int, halo: int
) -> jax.Array:
    """Single-width ``static_strip_masks`` (the fused path's one mask)."""
    return static_strip_masks(cur, prev, block_rows, (halo,))[0]


def sharded_strip_masks(
    cur: jax.Array,
    prev: jax.Array,
    block_rows: int,
    halos: tuple[int, ...],
    ctx: StencilCtx,
) -> tuple[jax.Array, ...]:
    """``static_strip_masks`` under ``shard_map``: shard-local (B, Hl, W)
    row strips + ONE halo exchange per frame → the same per-(image, local
    strip) masks the local path computes for the matching global strips.

    Interior shard boundaries compare the neighbour shard's actual rows
    (exchanged via ``ctx.pad_rows``) — exactly the rows the global-grid
    mask reads across the seam. Global boundaries extend with
    edge-replicated rows, which is bit-equal to the local path's range
    clamping: the replicated rows mirror row 0 / the last row, whose
    equality is already counted inside the clamped range, so the AND over
    the extended range equals the AND over the clamped one.
    """
    if cur.shape != prev.shape:
        raise ValueError(f"frame shapes differ: {cur.shape} vs {prev.shape}")
    b, hl, _ = cur.shape
    if hl % block_rows:
        raise ValueError(f"H={hl} not a multiple of block_rows={block_rows}")
    n = hl // block_rows
    hm = max(halos)
    # one exchange (per frame) at the widest stencil; every width gathers
    # from the same extended row-equality cumsum, like the local helper
    eq = jnp.all(
        ctx.pad_rows(cur, hm, pad_mode="edge")
        == ctx.pad_rows(prev, hm, pad_mode="edge"),
        axis=-1,
    ).astype(jnp.int32)
    csum = jnp.concatenate(
        [jnp.zeros((b, 1), jnp.int32), jnp.cumsum(eq, axis=1)], axis=1
    )
    out = []
    for halo in halos:
        lo = np.arange(n) * block_rows + (hm - halo)
        hi = (np.arange(n) + 1) * block_rows + hm + halo
        out.append((csum[:, hi] - csum[:, lo]) == jnp.asarray(hi - lo, jnp.int32))
    return tuple(out)


def warm_ctxs(dist: Dist) -> tuple[StencilCtx, StencilCtx, StencilCtx | None]:
    """The three stencil contexts of a sharded temporal step: (frontend
    edge-pad exchange, hysteresis zero-pad consensus, warm-seed gate).

    The first two join over ALL sync axes (trip counts must be globally
    uniform); the gate context joins over the SPACE axis ONLY — batch
    shards hold different images, and each image's grow-only verdict is
    decided by the shards that hold its rows (None when rows unsharded:
    the local per-image gate is already exact).
    """
    fctx = StencilCtx(dist.space_axis, "edge", sync_axes=dist.sync_axes())
    hctx = StencilCtx(dist.space_axis, "zero", sync_axes=dist.sync_axes())
    gctx = (
        StencilCtx(dist.space_axis, "zero", sync_axes=(dist.space_axis,))
        if dist.space_axis is not None
        else None
    )
    return fctx, hctx, gctx


def _sharded_fused_warm(
    imgs: jax.Array,
    prev_strong_w: jax.Array,
    prev_weak_w: jax.Array,
    prev_edges_w: jax.Array,
    sigma: float,
    radius: int,
    low: float,
    high: float,
    l2_norm: bool,
    block_rows: int | None,
    interpret: bool | None,
    true_hw: jax.Array | None,
    dist: Dist,
):
    """``fused_canny_warm`` inside ONE shard_map: the packed temporal
    state words live sharded with the mesh (batch over ``batch_axes``,
    rows over ``space_axis``) and never rendezvous on a host — only the
    halo slabs and the consensus scalars cross shards."""
    b, h, w = imgs.shape
    _check_dist_batch(b, dist)
    h2 = radius + 2
    hp, hl, bh = _shard_grid(h, dist, h2, block_rows)
    padded = _pad_rows_to(imgs, hp, "edge")
    if true_hw is None:
        true_hw = jnp.broadcast_to(jnp.asarray([h, w], jnp.int32), (b, 2))
    fctx, hctx, gctx = warm_ctxs(dist)
    space = dist.space_axis

    def local_fn(x, ps, pw, pe, hw):
        off = lax.axis_index(space) * hl if space is not None else 0
        row_off = jnp.full((1, 1), off, jnp.int32)
        strong_w, weak_w = overlap_strips(
            lambda ops, slabs, r0: fused_canny_strips(
                ops[0], sigma, radius, low, high, l2_norm, "packed", bh,
                interpret, hw, halos=slabs, row_offset=row_off + r0,
            ),
            (x,), fctx.halo_rows(x, h2), block_rows=bh,
        )
        seed = warm_seed(strong_w, weak_w, ps, pw, pe, ctx=gctx)
        packed, launches, dilations = packed_fixpoint_count(
            seed, weak_w, bh, interpret, ctx=hctx
        )
        edges = common.unpack_mask(packed)
        return edges, strong_w, weak_w, packed, launches, dilations

    fn = jax.shard_map(
        local_fn,
        mesh=dist.mesh,
        in_specs=(dist.batch_spec(),) * 4 + (dist.table_spec(),),
        # launch/dilation counts are the psum'd consensus values —
        # identical on every device (packed_fixpoint_count), so P()
        out_specs=(dist.batch_spec(),) * 4 + (P(), P()),
        check_vma=False,
    )
    edges, strong_w, weak_w, packed, launches, dilations = fn(
        padded, prev_strong_w, prev_weak_w, prev_edges_w,
        true_hw.astype(jnp.int32),
    )
    edges = common.crop_rows(edges, h)
    return edges, (strong_w, weak_w, packed), (launches, dilations)


def _sharded_fused_warm_skip(
    imgs: jax.Array,
    prev_imgs: jax.Array,
    prev_strong_w: jax.Array,
    prev_weak_w: jax.Array,
    prev_edges_w: jax.Array,
    have_prev: jax.Array,
    sigma: float,
    radius: int,
    low: float,
    high: float,
    l2_norm: bool,
    block_rows: int | None,
    interpret: bool | None,
    true_hw: jax.Array | None,
    dist: Dist,
):
    """``fused_canny_warm_skip`` inside ONE shard_map.

    The static-strip mask is computed shard-locally from halo-extended
    frame diffs (``sharded_strip_masks``); the all-static launch-skip gate
    joins the per-shard tile counts over EVERY sync axis so the
    ``lax.cond`` predicate is globally uniform — mandatory, because the
    compute branch holds a pallas launch and non-uniform branching under
    shard_map deadlocks the surrounding collectives. The frontend halo
    slabs are exchanged BEFORE the cond for the same reason; a skipped
    frame pays one h2-row exchange and two psum scalars, nothing else.
    """
    b, h, w = imgs.shape
    _check_dist_batch(b, dist)
    h2 = radius + 2
    hp, hl, bh = _shard_grid(h, dist, h2, block_rows)
    padded = _pad_rows_to(imgs, hp, "edge")
    prev_padded = _pad_rows_to(prev_imgs.astype(jnp.float32), hp, "edge")
    if true_hw is None:
        true_hw = jnp.broadcast_to(jnp.asarray([h, w], jnp.int32), (b, 2))
    fctx, hctx, gctx = warm_ctxs(dist)
    space = dist.space_axis

    def local_fn(x, px, ps, pw, pe, hprev, hw):
        off = lax.axis_index(space) * hl if space is not None else 0
        row_off = jnp.full((1, 1), off, jnp.int32)
        slabs = fctx.halo_rows(x, h2)  # exchange OUTSIDE the cond
        (static,) = sharded_strip_masks(x, px, bh, (h2,), fctx)
        static = static & hprev
        n_static = fctx.sum_global(jnp.sum(static.astype(jnp.int32)))
        n_tiles = fctx.sum_global(jnp.asarray(static.size, jnp.int32))

        def reuse(_):
            return ps, pw, jnp.int32(0)

        def compute(_):
            # masks slice the grid per-strip, so no overlap_strips here:
            # the slabs bind whole and static tiles copy stored words
            s_w, wk_w = fused_canny_strips(
                x, sigma, radius, low, high, l2_norm, "packed", bh,
                interpret, hw, halos=slabs, row_offset=row_off,
                skip_mask=static.astype(jnp.int32), prev_out=(ps, pw),
            )
            return s_w, wk_w, jnp.int32(1)

        strong_w, weak_w, fe_launches = lax.cond(
            n_static == n_tiles, reuse, compute, None
        )
        fe_strips = n_tiles - n_static
        seed = warm_seed(strong_w, weak_w, ps, pw, pe, ctx=gctx)
        packed, launches, dilations = packed_fixpoint_count(
            seed, weak_w, bh, interpret, ctx=hctx
        )
        edges = common.unpack_mask(packed)
        return (
            edges, strong_w, weak_w, packed,
            launches, dilations, fe_launches, fe_strips,
        )

    fn = jax.shard_map(
        local_fn,
        mesh=dist.mesh,
        in_specs=(dist.batch_spec(),) * 5 + (P(), dist.table_spec()),
        out_specs=(dist.batch_spec(),) * 4 + (P(),) * 4,
        check_vma=False,
    )
    edges, strong_w, weak_w, packed, launches, dilations, fe_launches, fe_strips = fn(
        padded, prev_padded, prev_strong_w, prev_weak_w, prev_edges_w,
        have_prev, true_hw.astype(jnp.int32),
    )
    edges = common.crop_rows(edges, h)
    state = (strong_w, weak_w, packed, padded)
    return edges, state, (launches, dilations, fe_launches, fe_strips)


@functools.partial(
    jax.jit,
    static_argnames=(
        "sigma", "radius", "low", "high", "l2_norm", "block_rows", "interpret",
        "dist",
    ),
)
def fused_canny_warm_skip(
    imgs: jax.Array,
    prev_imgs: jax.Array,
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
    """``fused_canny_warm`` + the static-strip FRONT-END skip.

    Carries the previous frame itself alongside the packed state: strips
    whose stencil input rows are bitwise unchanged (``static_strip_mask``)
    reuse the previous frame's packed strong/weak words instead of
    re-running gaussian+sobel+NMS — bit-identical because the front-end
    is a pure function of those rows. Two savings tiers, both visible in
    the returned cost:

      * an ALL-static frame skips the front-end pallas launch entirely
        (``lax.cond`` — the branch never executes), and
      * a partially-static frame runs one launch where static tiles skip
        the stencil math (``pl.when``) and copy stored words.

    ``have_prev`` is a device bool scalar gating the whole mechanism so
    frame 0 (all-zero state) runs fresh through the same compiled program.

    Returns ``(edges, state, cost)`` like ``fused_canny_warm`` but with
    ``state = (strong_w, weak_w, edges_w, frame)`` (the frame to diff
    against next step) and ``cost = (launches, dilations,
    frontend_launches, frontend_strips)`` int32 scalars —
    ``frontend_strips`` counts recomputed (image, strip) tiles.

    A non-local ``dist`` runs the whole step inside ``shard_map`` with the
    state words sharded like the batch (``_sharded_fused_warm_skip``);
    both mechanisms and all four cost scalars survive sharding
    bit-identically. Note the sharded grid pads rows to a multiple of
    ``space_size * block_rows``, so partially-static tile counts can
    differ from the local grid's (the masks are exact either way).
    """
    imgs = imgs.astype(jnp.float32)
    b, h, w = imgs.shape
    if w % 32:
        raise ValueError(f"fused_canny_warm_skip needs W % 32 == 0, got W={w}")
    if not dist.is_local:
        return _sharded_fused_warm_skip(
            imgs, prev_imgs, prev_strong_w, prev_weak_w, prev_edges_w,
            have_prev, sigma, radius, low, high, l2_norm, block_rows,
            interpret, true_hw, dist,
        )
    h2 = radius + 2
    bh = block_rows or common.pick_block_rows(h, min_rows=h2)
    padded, h = common.pad_rows_to_multiple(imgs, bh)
    prev_padded, _ = common.pad_rows_to_multiple(prev_imgs.astype(jnp.float32), bh)
    if true_hw is None:
        true_hw = jnp.broadcast_to(jnp.asarray([h, w], jnp.int32), (b, 2))
    static = static_strip_mask(padded, prev_padded, bh, h2) & have_prev
    n_tiles = static.size
    n_static = jnp.sum(static.astype(jnp.int32))

    def reuse(_):
        return prev_strong_w, prev_weak_w, jnp.int32(0)

    def compute(_):
        s_w, wk_w = fused_canny_strips(
            padded, sigma, radius, low, high, l2_norm, "packed", bh, interpret,
            true_hw, skip_mask=static.astype(jnp.int32),
            prev_out=(prev_strong_w, prev_weak_w),
        )
        return s_w, wk_w, jnp.int32(1)

    strong_w, weak_w, fe_launches = lax.cond(
        n_static == n_tiles, reuse, compute, None
    )
    fe_strips = jnp.int32(n_tiles) - n_static
    seed = warm_seed(strong_w, weak_w, prev_strong_w, prev_weak_w, prev_edges_w)
    packed, launches, dilations = packed_fixpoint_count(seed, weak_w, bh, interpret)
    edges = common.crop_rows(common.unpack_mask(packed), h)
    state = (strong_w, weak_w, packed, padded)
    return edges, state, (launches, dilations, fe_launches, fe_strips)


@functools.partial(
    jax.jit,
    static_argnames=(
        "sigma", "radius", "low", "high", "l2_norm", "block_rows", "interpret",
        "dist",
    ),
)
def fused_canny_warm(
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
    """One streaming frame step: fused front-end + WARM-STARTED hysteresis.

    The previous frame's packed (strong, weak, edges) words are threaded
    into the hysteresis fixpoint as an extra seed, gated per image by the
    grow-only check (``core.canny.hysteresis.warm_seed``) that keeps the
    result bit-identical to the cold path on every frame. All-zero prev
    words are the valid "no history" state (frame 0 runs cold), so the
    same compiled program serves cold and warm frames.

    (b, h, w) f32 with W % 32 == 0 (the stream layer pads + anchors via
    ``true_hw``) → (edges uint8 (b, h, w),
                    state  = (strong_w, weak_w, edges_w) packed
                             (b, Hp, W//32) words to thread into the next
                             frame,
                    cost   = (launches, dilations) int32 scalars — see
                             ``packed_fixpoint_count`` — for the
                             warm-savings stats).

    A non-local ``dist`` keeps the state words sharded with the mesh
    (``_sharded_fused_warm``): the warm-seed gate joins over the space
    axis, the fixpoint over every sync axis, and the result — edges,
    state AND counts — is bit-identical to the local step.
    """
    imgs = imgs.astype(jnp.float32)
    b, h, w = imgs.shape
    if w % 32:
        raise ValueError(f"fused_canny_warm needs W % 32 == 0, got W={w}")
    if not dist.is_local:
        return _sharded_fused_warm(
            imgs, prev_strong_w, prev_weak_w, prev_edges_w, sigma, radius,
            low, high, l2_norm, block_rows, interpret, true_hw, dist,
        )
    h2 = radius + 2
    bh = block_rows or common.pick_block_rows(h, min_rows=h2)
    padded, h = common.pad_rows_to_multiple(imgs, bh)
    if true_hw is None:
        true_hw = jnp.broadcast_to(jnp.asarray([h, w], jnp.int32), (b, 2))
    strong_w, weak_w = fused_canny_strips(
        padded, sigma, radius, low, high, l2_norm, "packed", bh, interpret, true_hw
    )
    seed = warm_seed(strong_w, weak_w, prev_strong_w, prev_weak_w, prev_edges_w)
    packed, launches, dilations = packed_fixpoint_count(seed, weak_w, bh, interpret)
    edges = common.crop_rows(common.unpack_mask(packed), h)
    return edges, (strong_w, weak_w, packed), (launches, dilations)
