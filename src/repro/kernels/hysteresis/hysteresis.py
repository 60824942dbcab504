"""Hysteresis — bit-parallel Pallas kernel with in-tile fixpoint convergence.

The paper's Amdahl-bottleneck stage, made parallel (see
core/canny/hysteresis.py for the algorithm), then made *bit-parallel*:
edge/weak masks are packed 32 pixels per uint32 word, so one VPU lane
propagates 32 columns per op. A masked 8-neighbour dilation becomes a
3-row OR + adjacent-word ORs (planar bit layout) — ~32× fewer elements
per sweep than the uint8 formulation, and 8× less HBM traffic (1 bit/px
end-to-end: ops.py packs once, every sweep launch reads/writes words,
unpack happens once at the end).

One kernel launch converges each (BT-image, strip) tile to its LOCAL
fixpoint entirely in VMEM (``lax.while_loop`` over masked packed
dilations — zero HBM traffic per local sweep), so the number of
HBM-level launches drops from the pixel-path length to the strip-graph
diameter. The XLA-level outer loop (ops.py) drives the ENTIRE batch with
one loop, re-launching until no (image, strip) tile reports a change —
the per-launch changed flags come back as a (B, n_strips) map reduced
once per sweep.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.kernels import common

def _hshift(v):
    """OR of v with its left/right pixel neighbours in the planar layout
    (``common.pack_mask``: bit k of word j is pixel k·NW + j). A pixel's
    neighbours sit in the adjacent WORDS of the same bit plane; only the
    first/last word wraps to the previous/next plane (a one-bit shift),
    and the bits shifted in there are the zero border outside the image."""
    left = jnp.concatenate([v[..., -1:] << 1, v[..., :-1]], axis=-1)
    right = jnp.concatenate([v[..., 1:], v[..., :1] >> 1], axis=-1)
    return v | left | right


def _kernel(
    eprev_ref, ecur_ref, enxt_ref, weak_ref, top_ref, bot_ref, out_ref,
    changed_ref, *, grid_axis=common.STRIP_AXIS,
):
    bt, bh, nw = ecur_ref.shape
    ext = common.assemble_rows(
        eprev_ref[...],
        ecur_ref[...],
        enxt_ref[...],
        1,
        "zero",
        grid_axis=grid_axis,
        top_ext=top_ref[...],
        bot_ext=bot_ref[...],
    )  # (bt, bh+2, nw) uint32; halo rows stay FIXED during this launch
    top = ext[..., 0:1, :]
    bot = ext[..., -1:, :]
    weak = weak_ref[...]
    init = ecur_ref[...]

    def dilate_masked(e):
        full = jnp.concatenate([top, e, bot], axis=-2)  # (bt, bh+2, nw)
        up = jax.lax.slice_in_dim(full, 0, bh, axis=-2)
        dn = jax.lax.slice_in_dim(full, 2, bh + 2, axis=-2)
        v = e | up | dn  # vertical OR, then horizontal spread: 3x3 box
        return (_hshift(v) & weak) | e

    def body(carry):
        e, _, n = carry
        new = dilate_masked(e)
        return new, jnp.max(common.any_per_image(new != e)) > 0, n + 1

    final, _, trips = lax.while_loop(
        lambda c: c[1], body, (init, jnp.asarray(True), jnp.asarray(0, jnp.int32))
    )
    out_ref[...] = final
    # Per-image change report doubling as a WORK metric: 0 if the image's
    # tile was already at its local fixpoint, else the number of productive
    # masked dilations the tile ran (trips minus the verifying one). The
    # outer loop only tests > 0, so control is unchanged; summed, it is the
    # in-VMEM sweep work a warm start saves.
    changed = common.any_per_image(final != init)  # (bt, 1, 1)
    changed_ref[...] = jnp.where(changed > 0, trips - 1, 0).reshape(bt, 1, 1, 1)


def hysteresis_sweep_strips(
    edges: jax.Array,
    weak: jax.Array,
    block_rows: int | None = None,
    interpret: bool | None = None,
    batch_block: int | None = None,
    halos: tuple[jax.Array, jax.Array] | None = None,
):
    """One launch, whole batch: local fixpoint per (image, strip) tile.

    Operates on PACKED masks (see ``common.pack_mask``): (B, H, W//32)
    uint32 edges/weak → (edges', changed[B, n_strips]). A ``changed``
    entry is 0 for an already-converged tile, else the tile's productive
    in-VMEM dilation count (so the map is both the outer-loop convergence
    test and the sweep-work metric the streaming stats report).

    ``halos`` is an optional ``(top, bot)`` pair of (B, 1, W//32) packed
    halo ROWS bound by the first/last strips in place of the zero border
    rule — under ``shard_map`` they carry the neighbour shard's boundary
    edge words (exchanged per sweep by the driving fixpoint loop), which
    is how edge chains propagate across row shards. The changed map stays
    shard-local; the fixpoint loop joins it with the global consensus.
    """
    if interpret is None:
        interpret = common.default_interpret()
    b, h, nw = edges.shape
    bh = block_rows or common.pick_block_rows(h)
    if h % bh != 0:
        raise ValueError(f"H={h} not a multiple of block_rows={bh}")
    if halos is None:
        top = jnp.zeros((b, 1, nw), jnp.uint32)  # zero rule: no edges outside
        bot = top
    else:
        top, bot = halos
        if top.shape != (b, 1, nw) or bot.shape != (b, 1, nw):
            raise ValueError(
                f"halo rows must be {(b, 1, nw)}, got {top.shape} / {bot.shape}"
            )
    n = h // bh
    bt = batch_block or common.pick_batch_block(b, bh, nw)
    grid, sx = common.strip_grid(b, bt, n)
    prev, cur, nxt = common.strip_specs(n, bh, nw, bt, sx)
    out, changed = pl.pallas_call(
        functools.partial(_kernel, grid_axis=sx),
        grid=grid,
        in_specs=[
            prev,
            cur,
            nxt,
            common.out_strip_spec(bh, nw, bt, sx),
            common.halo_spec(1, nw, bt, sx),
            common.halo_spec(1, nw, bt, sx),
        ],
        out_specs=(
            common.out_strip_spec(bh, nw, bt, sx),
            common.strip_map_spec(bt, sx),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((b, h, nw), jnp.uint32),
            jax.ShapeDtypeStruct((b, n, 1, 1), jnp.int32),
        ),
        interpret=interpret,
        compiler_params=common.compiler_params(),
    )(edges, edges, edges, weak, top.astype(jnp.uint32), bot.astype(jnp.uint32))
    return out, changed.reshape(b, n)
