"""The composed Canny pipeline — GCP shell layer output.

``make_canny`` builds a jitted detector for a given ``CannyParams`` +
``Dist`` + backend:

  backend="jnp"    — pure-jnp stages (XLA fuses them); the portable path
  backend="pallas" — per-stage Pallas TPU kernels (kernels/ must register)
  backend="fused"  — single fused Pallas kernel for gauss+sobel+nms
                     (beyond-paper: one HBM round-trip instead of three)

Backends resolve through the ``BackendSpec`` registry
(``core/canny/backends.py``): capabilities are validated at construction
time, so an unsupported backend × feature combination raises
``UnsupportedFeature`` before any work is queued. Sharded mode either
wraps the jnp stages in one ``shard_map`` (``stage_dist`` backends) or
routes through the backend's mesh-aware serving entry — images are
batch-sharded over ``dist.batch_axes`` and row-sharded over
``dist.space_axis``; halos cross shards via ppermute inside the stages.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.canny.backends import (
    BackendSpec,
    UnsupportedFeature,
    backend_spec,
    default_backend,
    op_backend,
    register_backend_spec,
    _SPECS,
)
from repro.core.canny.params import CannyParams
from repro.core.canny.gaussian import gaussian_stage
from repro.core.canny.sobel import sobel_stage
from repro.core.canny.nms import nms_stage
from repro.core.canny.hysteresis import hysteresis_stage
from repro.core.patterns.dist import Dist, StencilCtx


def canny_local_stages(
    img: jax.Array, params: CannyParams, ctx: StencilCtx, local_sweeps: int = 1
) -> jax.Array:
    """Run the 4 stages on a (possibly shard-local) block."""
    blurred = gaussian_stage(img, ctx, params)
    mag, dirs = sobel_stage(blurred, ctx, params)
    nms = nms_stage(mag, dirs, ctx)
    return hysteresis_stage(nms, params, ctx, local_sweeps=local_sweeps)


def _jnp_temporal(params, **kw):
    # stream/ imports core at module level; core reaches back lazily
    from repro.stream.temporal import JnpTemporal

    return JnpTemporal(params, **kw)


def _jnp_serving(*args, **kw):
    from repro.core.canny.serving import jnp_serving

    return jnp_serving(*args, **kw)


# The portable backend registers here, capabilities complete: its stage
# plane composes under shard_map directly (mesh-divisible shapes), its
# serving entry handles arbitrary bucketed shapes on any mesh
# (core/canny/serving.py), and its temporal plane carries warm state +
# the whole-frame NMS-carry skip (stream/temporal.py).
register_backend_spec(
    BackendSpec(
        name="jnp",
        stage_fn=canny_local_stages,
        serving_fn=_jnp_serving,
        temporal_fn=_jnp_temporal,
        dist=True,
        warm=True,
        skip=True,
        stage_dist=True,
        skip_granularity="frame",
    )
)


# -- legacy plane-function registration (kept: kernels + tests use it) -------
def register_backend(name: str, fn: Callable, override: bool = False) -> None:
    """Attach a stage-plane function. Creates a capability-less spec when
    ``name`` is new (kernels/canny_backends.py upgrades its own specs)."""
    spec = _SPECS.get(name)
    if spec is None:
        register_backend_spec(BackendSpec(name=name, stage_fn=fn))
        return
    if spec.stage_fn is not None and not override:
        raise ValueError(
            f"canny backend {name!r} is already registered; pass "
            "override=True to replace it deliberately"
        )
    spec.stage_fn = fn


def register_serving_backend(name: str, fn: Callable, override: bool = False) -> None:
    spec = _SPECS.get(name)
    if spec is None:
        register_backend_spec(BackendSpec(name=name, serving_fn=fn))
        return
    if spec.serving_fn is not None and not override:
        raise ValueError(
            f"serving backend {name!r} is already registered; pass "
            "override=True to replace it deliberately"
        )
    spec.serving_fn = fn


def resolve_serving_backend(name: str) -> Callable | None:
    """The true-size-aware entry for ``name``, or None if it has none."""
    try:
        return backend_spec(name).serving_fn
    except ValueError:
        return None


def _resolve_stage_fn(backend: str) -> Callable:
    spec = backend_spec(backend)
    if spec.stage_fn is None:
        raise UnsupportedFeature(
            f"backend {backend!r} has no stage-plane entry"
        )
    return spec.stage_fn


def make_canny(
    params: CannyParams = CannyParams(),
    dist: Dist = Dist(),
    backend: str | None = None,
    local_sweeps: int = 2,
    bucket_multiple: int | None = 64,
) -> Callable[[jax.Array], jax.Array]:
    """Build a jitted canny detector for images shaped (h, w) or (b, h, w).

    Serving-capable backends (``fused``, ``pallas``) return a shape-
    bucketed runner: any (b, h, w) is padded to a bucket and cropped back
    (bit-exact via per-image true sizes), so new shapes inside a bucket
    never recompile. Pass ``bucket_multiple=None`` to force exact-shape
    compilation.

    ``dist`` is the one distribution plane: a non-local Dist makes a
    serving-capable backend run its batch-grid kernels inside shard_map
    (bucket batches shard over the data axes, rows over the space axis),
    while the jnp stage path wraps the stages in shard_map as before —
    either way, one queue of work drains across the whole mesh. A backend
    whose spec does not claim ``dist`` raises ``UnsupportedFeature`` here,
    at construction.
    """
    if dist.pod_axis is not None:
        raise ValueError(
            "make_canny builds ONE detector; a pod-axis Dist describes a "
            "farm of them — use FarmScheduler(dist=...) or stream/pod.py "
            "with per-rank Dist.pod_slice"
        )
    backend = backend or default_backend("jnp")
    spec = backend_spec(backend)
    if not dist.is_local:
        spec.require(dist=True)

    serve_fn = spec.serving_fn if bucket_multiple else None
    if serve_fn is None and not dist.is_local and not spec.stage_dist:
        raise UnsupportedFeature(
            f"backend {backend!r} distributes through its serving entry "
            "only; pass a bucket_multiple (its stage plane is shard-local)"
        )
    if serve_fn is not None:
        from repro.serve.engine import BucketedCanny

        return BucketedCanny(serve_fn, params, bucket_multiple, dist=dist)

    stage_fn = _resolve_stage_fn(backend)
    if dist.is_local:
        ctx = StencilCtx(None, "edge")

        @jax.jit
        def run_local(img):
            return stage_fn(img.astype(jnp.float32), params, ctx)

        return run_local

    ctx = StencilCtx(dist.space_axis, "edge", sync_axes=dist.sync_axes())
    mesh = dist.mesh
    cache: dict[int, Callable] = {}

    def build(ndim: int) -> Callable:
        if ndim == 2:
            spec_ = P(dist.space_axis, None)
        elif ndim == 3:
            batch = dist.batch_axes if dist.batch_axes else None
            spec_ = P(batch, dist.space_axis, None)
        else:
            raise ValueError(f"expected (h,w) or (b,h,w); got ndim={ndim}")

        local = jax.shard_map(
            lambda x: stage_fn(x, params, ctx, local_sweeps=local_sweeps)
            if stage_fn is canny_local_stages
            else stage_fn(x, params, ctx),
            mesh=mesh,
            in_specs=spec_,
            out_specs=spec_,
            check_vma=False,
        )
        sharding = NamedSharding(mesh, spec_)
        return jax.jit(
            lambda x: local(x.astype(jnp.float32)),
            in_shardings=sharding,
            out_shardings=sharding,
        )

    def run(img):
        fn = cache.get(img.ndim)
        if fn is None:
            fn = cache[img.ndim] = build(img.ndim)
        return fn(img)

    return run


def registered_ops() -> list[str]:
    """Every edge operator the registry can serve (``"canny"`` plus the
    operator zoo once the kernel package registers)."""
    from repro.core.canny.backends import backend_specs

    return sorted({s.op for s in backend_specs()})


def make_detector(
    params: CannyParams = CannyParams(),
    dist: Dist = Dist(),
    op: str = "canny",
    backend: str | None = None,
    local_sweeps: int = 2,
    bucket_multiple: int | None = 64,
) -> Callable[[jax.Array], jax.Array]:
    """Operator-aware ``make_canny``: resolve ``op`` through the registry.

    ``backend=None`` picks the operator's default backend
    (``backends.op_backend``: fused for Canny on a TPU, ``"jnp"`` — the
    portable path — elsewhere, and the registered spec for each zoo
    operator); an explicit ``backend`` is validated against ``op`` so a
    detector never silently computes a different operator than it was
    asked for. Everything downstream — buckets, mesh, capability
    validation — is ``make_canny``, one construction path for the whole
    zoo.
    """
    backend = op_backend(op, backend, cpu_default="jnp")
    return make_canny(
        params,
        dist,
        backend=backend,
        local_sweeps=local_sweeps,
        bucket_multiple=bucket_multiple,
    )


def canny(
    img: jax.Array,
    params: CannyParams = CannyParams(),
    dist: Dist = Dist(),
    backend: str | None = None,
) -> jax.Array:
    """One-shot convenience wrapper around ``make_canny``."""
    return make_canny(params, dist, backend)(img)
