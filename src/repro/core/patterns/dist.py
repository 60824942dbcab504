"""Distribution spec + stencil context shared by all patterns.

``Dist`` names the mesh axes a pattern may use; ``StencilCtx`` gives stage
code a uniform "extend my rows by a halo" primitive that is a plain
``jnp.pad`` locally and a ``lax.ppermute`` halo exchange when the row axis
is sharded. Stage code written against ``StencilCtx`` runs unchanged in
both worlds — this is the property the paper attributes to structured
patterns ("parallelism on any underlying parallel architecture").
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import AxisType, Mesh, PartitionSpec as P


def auto_mesh(axis_shapes, axis_names, devices=None) -> Mesh:
    """Every mesh this repo builds: all axes ``AxisType.Auto``.

    The patterns place data through ``shard_map`` specs and leave the
    rest to the partitioner; ``jax.make_mesh`` defaults to ``Explicit``
    axes, under which plain slicing of a sharded dim (e.g. cropping the
    global row padding) is refused. ``devices`` — a flat list or an
    array of the right size — pins the mesh to those devices.
    """
    shape, names = tuple(axis_shapes), tuple(axis_names)
    types = (AxisType.Auto,) * len(names)
    if devices is None:
        return jax.make_mesh(shape, names, axis_types=types)
    return Mesh(np.asarray(devices).reshape(shape), names, axis_types=types)


@dataclasses.dataclass(frozen=True)
class Dist:
    """Where a pattern's data lives.

    Attributes:
      mesh: the device mesh (None → local mode).
      batch_axes: mesh axes the leading batch dim is sharded over.
      space_axis: mesh axis the spatial row axis is sharded over (stencil
        halos cross this axis). None → rows unsharded.
      pod_axis: mesh axis the streaming farm dispatches FRAMES over — the
        host-level axis. Unlike batch/space it is never seen by
        ``shard_map``: each pod rank owns its slice of the devices
        (``pod_slice``) and runs an independent detector over its slice
        of the frame stream (see ``stream/pod.py``).
    """

    mesh: Mesh | None = None
    batch_axes: tuple[str, ...] = ()
    space_axis: str | None = None
    pod_axis: str | None = None

    @property
    def is_local(self) -> bool:
        return self.mesh is None

    def space_size(self) -> int:
        if self.mesh is None or self.space_axis is None:
            return 1
        return self.mesh.shape[self.space_axis]

    def batch_size(self) -> int:
        """Total shards of the leading batch dim (1 in local mode)."""
        if self.mesh is None or not self.batch_axes:
            return 1
        return math.prod(self.mesh.shape[a] for a in self.batch_axes)

    def pod_size(self) -> int:
        """Pod ranks in the streaming farm (1 when there is no pod axis)."""
        if self.mesh is None or self.pod_axis is None:
            return 1
        return self.mesh.shape[self.pod_axis]

    def pod_devices(self, rank: int) -> np.ndarray:
        """Pod ``rank``'s devices, laid out as its sub-mesh."""
        if self.mesh is None or self.pod_axis is None:
            raise ValueError("pod_slice needs a Dist with a mesh and a pod axis")
        n = self.pod_size()
        if not 0 <= rank < n:
            raise ValueError(f"pod rank {rank} out of range for {n} pods")
        names = list(self.mesh.axis_names)
        return np.take(self.mesh.devices, rank, axis=names.index(self.pod_axis))

    def pod_slice(self, rank: int) -> "Dist":
        """The per-pod sub-``Dist``: pod ``rank``'s devices, pod axis gone.

        The sub-mesh keeps the batch/space axes over the rank's device
        slice; axes that collapse to size 1 are dropped, and a fully
        trivial sub-mesh degrades to LOCAL — so a ``PODx1x1`` farm runs
        one plain single-device detector per rank while ``2x2x4`` gives
        every rank its own data×model shard_map detector.
        """
        devs = self.pod_devices(rank)
        rest = tuple(a for a in self.mesh.axis_names if a != self.pod_axis)
        if devs.size == 1:
            return Dist()
        sub = auto_mesh(devs.shape, rest, devs)
        batch = tuple(a for a in self.batch_axes if sub.shape.get(a, 1) > 1)
        space = self.space_axis
        if space is not None and sub.shape.get(space, 1) == 1:
            space = None
        if not batch and space is None:
            return Dist()
        return Dist(mesh=sub, batch_axes=batch, space_axis=space)

    def sync_axes(self) -> tuple[str, ...]:
        """Every mesh axis a convergence decision must be agreed over.

        The pod axis is deliberately absent: pods never rendezvous — each
        rank's detector converges on its own frames.
        """
        space = (self.space_axis,) if self.space_axis is not None else ()
        return tuple(self.batch_axes) + space

    def batch_spec(self) -> P:
        """PartitionSpec for a (B, H, W) batch under this distribution."""
        return P(self.batch_axes or None, self.space_axis, None)

    def table_spec(self) -> P:
        """PartitionSpec for per-image metadata rows, e.g. (B, 2) tables."""
        return P(self.batch_axes or None, None)


LOCAL = Dist()


def on_device_of(x):
    """A context under which new arrays are made where ``x`` lives: on
    the device of an array committed to one device, else on JAX's
    default device. State made for a frame on chip k is then made on
    chip k, with no module on, and no copy from, the default device."""
    devices = x.devices() if getattr(x, "committed", False) else ()
    if len(devices) == 1:
        return jax.default_device(next(iter(devices)))
    return contextlib.nullcontext()


class StencilCtx:
    """Halo provider for stencil stages.

    ``axis_name=None`` → local mode: halos come from ``jnp.pad``.
    Otherwise the context is being traced inside ``shard_map`` and halos
    come from neighbour shards via ``lax.ppermute`` (boundary shards are
    patched with the requested pad mode so results match local mode
    bit-exactly).
    """

    def __init__(
        self,
        axis_name: str | None = None,
        pad_mode: str = "edge",
        sync_axes: tuple[str, ...] | None = None,
    ):
        if pad_mode not in ("edge", "zero"):
            raise ValueError(f"unsupported pad_mode: {pad_mode}")
        self.axis_name = axis_name
        self.pad_mode = pad_mode
        # Axes that convergence decisions must be agreed over. Data-dependent
        # trip counts (hysteresis) MUST be identical on every device of the
        # shard_map, or collectives inside the loop body deadlock — so the
        # consensus spans every mesh axis in use, not just the stencil axis.
        if sync_axes is None:
            sync_axes = (axis_name,) if axis_name is not None else ()
        self.sync_axes = tuple(a for a in sync_axes if a is not None)

    # -- row halo ----------------------------------------------------------
    def pad_rows(
        self, x: jax.Array, halo: int, axis: int = -2, pad_mode: str | None = None
    ) -> jax.Array:
        """Return ``x`` extended by ``halo`` rows on both sides of ``axis``."""
        if halo == 0:
            return x
        mode = pad_mode or self.pad_mode
        if self.axis_name is None:
            return _pad_axis(x, halo, axis, mode)
        return _halo_exchange(x, halo, axis, self.axis_name, mode)

    def halo_rows(
        self, x: jax.Array, halo: int, axis: int = -2, pad_mode: str | None = None
    ) -> tuple[jax.Array, jax.Array]:
        """The two halo slabs alone: ``(top, bot)``, each ``halo`` rows.

        This is ``pad_rows`` for consumers that need the halos as SEPARATE
        arrays — e.g. a shard-local Pallas grid whose boundary strips bind
        externally supplied halo blocks instead of clamped neighbour strips
        (see ``kernels/common.py:strip_specs``). Same bit-exactness contract
        as ``pad_rows``: neighbour rows under ``shard_map``, the pad rule at
        the global boundary / in local mode.
        """
        ext = self.pad_rows(x, max(halo, 1), axis, pad_mode)
        h = max(halo, 1)
        axis = axis % x.ndim
        top = lax.slice_in_dim(ext, 0, h, axis=axis)
        size = ext.shape[axis]
        bot = lax.slice_in_dim(ext, size - h, size, axis=axis)
        return top, bot

    # -- width halo (never sharded) ----------------------------------------
    def pad_cols(
        self, x: jax.Array, halo: int, axis: int = -1, pad_mode: str | None = None
    ) -> jax.Array:
        if halo == 0:
            return x
        return _pad_axis(x, halo, axis, pad_mode or self.pad_mode)

    # -- global consensus ---------------------------------------------------
    def _live_sync_axes(self) -> tuple[str, ...]:
        """sync_axes minus trivial (size-1) mesh axes — a psum over a
        size-1 axis is an identity that still costs a collective, so
        consensus no-ops cheaply on them (and on an all-trivial mesh)."""
        return tuple(a for a in self.sync_axes if lax.axis_size(a) > 1)

    def any_global(self, flag: jax.Array) -> jax.Array:
        """OR-reduce a boolean across ALL sync axes (identity locally)."""
        axes = self._live_sync_axes()
        if not axes:
            return flag
        return lax.psum(flag.astype(jnp.int32), axes) > 0

    def sum_global(self, value: jax.Array) -> jax.Array:
        axes = self._live_sync_axes()
        if not axes:
            return value
        return lax.psum(value, axes)


def _pad_axis(x: jax.Array, halo: int, axis: int, pad_mode: str) -> jax.Array:
    pads = [(0, 0)] * x.ndim
    pads[axis % x.ndim] = (halo, halo)
    mode = "edge" if pad_mode == "edge" else "constant"
    return jnp.pad(x, pads, mode=mode)


def _halo_exchange(
    x: jax.Array, halo: int, axis: int, axis_name: str, pad_mode: str
) -> jax.Array:
    """Exchange ``halo`` rows with mesh neighbours along ``axis_name``.

    Shard i receives the last ``halo`` rows of shard i-1 (its top halo)
    and the first ``halo`` rows of shard i+1 (its bottom halo). Boundary
    shards synthesize the missing halo from the pad mode, making the
    sharded stencil bit-identical to the unsharded one.
    """
    axis = axis % x.ndim
    n = lax.axis_size(axis_name)
    if n == 1:
        return _pad_axis(x, halo, axis, pad_mode)

    size = x.shape[axis]
    if size < halo:
        raise ValueError(
            f"shard extent {size} along axis {axis} smaller than halo {halo}; "
            "use fewer shards or a smaller stencil radius"
        )
    top = lax.slice_in_dim(x, 0, halo, axis=axis)
    bot = lax.slice_in_dim(x, size - halo, size, axis=axis)
    # ppermute fills non-receivers with zeros.
    halo_above = lax.ppermute(bot, axis_name, perm=[(i, i + 1) for i in range(n - 1)])
    halo_below = lax.ppermute(top, axis_name, perm=[(i, i - 1) for i in range(1, n)])

    if pad_mode == "edge":
        idx = lax.axis_index(axis_name)
        first = lax.slice_in_dim(x, 0, 1, axis=axis)
        last = lax.slice_in_dim(x, size - 1, size, axis=axis)
        reps = [1] * x.ndim
        reps[axis] = halo
        edge_top = jnp.tile(first, reps)
        edge_bot = jnp.tile(last, reps)
        halo_above = jnp.where(idx == 0, edge_top, halo_above)
        halo_below = jnp.where(idx == n - 1, edge_bot, halo_below)

    return jnp.concatenate([halo_above, x, halo_below], axis=axis)
