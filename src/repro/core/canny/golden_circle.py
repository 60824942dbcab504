"""Golden Circle of Parallelism (GCP) — the paper's layering, concretely.

The paper's model: Shell (synthesize the problem into a parallel
algorithm), Kernel (optimize it for the concrete parallel architecture),
Core (the hardware). Mapped here:

  Shell  — ``plan()``: problem spec (image shape, batch, params) →
           a ``CannyPlan``: which axes to shard, tile sizes, pad amounts,
           backend choice, with the even-balance invariant checked.
  Kernel — ``compile_plan()``: plan → jitted SPMD executable (traces,
           shards, lowers through XLA/Pallas).
  Core   — the jax device mesh handed in (``launch/mesh.py``).

This is the layer launchers talk to; stages never see raw meshes.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Callable

import jax
import numpy as np
from jax.sharding import Mesh

from repro.core.canny.backends import default_backend
from repro.core.canny.params import CannyParams
from repro.core.canny.pipeline import make_canny
from repro.core.patterns.dist import Dist
from repro.core.patterns.partition import even_tiles, assert_balanced


@dataclasses.dataclass(frozen=True)
class CannyPlan:
    """Shell output: a validated parallel schedule for one problem shape."""

    params: CannyParams
    dist: Dist
    backend: str
    batch: int
    height: int
    width: int
    pad_rows: int  # rows appended so height divides the space axis
    shard_rows: int  # rows per shard after padding

    def describe(self) -> str:
        d = self.dist
        mesh = "local" if d.is_local else f"{dict(d.mesh.shape)}"
        return (
            f"CannyPlan(batch={self.batch}, image={self.height}x{self.width}, "
            f"mesh={mesh}, batch_axes={d.batch_axes}, space_axis={d.space_axis}, "
            f"shard_rows={self.shard_rows}, pad_rows={self.pad_rows}, "
            f"backend={self.backend})"
        )


def plan(
    batch: int,
    height: int,
    width: int,
    params: CannyParams = CannyParams(),
    mesh: Mesh | None = None,
    backend: str | None = None,
    batch_axes: tuple[str, ...] = ("data",),
    space_axis: str | None = "model",
) -> CannyPlan:
    """Shell layer: choose a schedule and verify its balance invariant."""
    backend = backend or default_backend("jnp")

    if mesh is None:
        dist = Dist()
        return CannyPlan(params, dist, backend, batch, height, width, 0, height)

    axes = dict(mesh.shape)
    use_batch = tuple(a for a in batch_axes if a in axes and batch % axes[a] == 0)
    # batch must divide the product of used axes; drop axes greedily if not
    bprod = math.prod(axes[a] for a in use_batch) if use_batch else 1
    while use_batch and batch % bprod != 0:
        use_batch = use_batch[:-1]
        bprod = math.prod(axes[a] for a in use_batch) if use_batch else 1

    space = space_axis if (space_axis in axes) else None
    nspace = axes.get(space, 1) if space else 1
    # stencils need shard extent >= halo; rows are padded up to divisibility
    pad = (-height) % nspace if space else 0
    shard_rows = (height + pad) // nspace
    min_rows = params.radius + 2  # largest stage halo
    if space and shard_rows < min_rows:
        space = None
        pad, shard_rows = 0, height

    dist = Dist(mesh=mesh, batch_axes=use_batch, space_axis=space)

    # the paper's fig-11/12 claim as an invariant: even work per shard
    if space:
        tiles = even_tiles(height + pad, nspace)
        counts = np.array([(b - a) * width for a, b in tiles])
        assert_balanced(counts)

    return CannyPlan(params, dist, backend, batch, height, width, pad, shard_rows)


def compile_plan(p: CannyPlan) -> Callable[[jax.Array], jax.Array]:
    """Kernel layer: trace + shard + lower the plan into an executable."""
    inner = make_canny(p.params, p.dist, p.backend)
    if p.pad_rows == 0:
        return inner

    def run(img):
        import jax.numpy as jnp

        pads = [(0, 0)] * (img.ndim - 2) + [(0, p.pad_rows), (0, 0)]
        out = inner(jnp.pad(img, pads, mode="edge"))
        return jax.lax.slice_in_dim(out, 0, p.height, axis=-2)

    return run
