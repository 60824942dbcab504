"""Backend parity plane — one capability registry for every Canny backend.

A ``BackendSpec`` declares, per backend, its entry points on the three
execution planes and the features it supports on each:

  stage_fn    — (img, params, ctx, **kw) → edges; the per-image stage
                plane ``make_canny(bucket_multiple=None)`` compiles.
  serving_fn  — (imgs, true_hw, params, interpret, dist) → edges; the
                true-size-aware entry the shape-bucketed serving layer
                (and every mesh path) drives.
  temporal_fn — (params, warm=, skip=, block_rows=, interpret=,
                donate=) → impl
                with ``reset()`` and ``step(x) → (edges, cost)``; the
                stateful streaming plane behind ``TemporalCanny``.

Capabilities (the paper's claim, made checkable: every pattern composes
over every backend, or the combination FAILS LOUDLY):

  dist — the backend runs under a non-local ``Dist``: its serving entry
         executes inside ``shard_map`` (or, ``stage_dist``, its stage
         plane composes under ``shard_map`` directly — the jnp stages).
  warm — temporal warm-start state threading (exactness-gated seeds).
  skip — the static-strip front-end skip on top of warm.

``warm_dist`` (warm state under a mesh detector) is declared separately
because it is a genuinely distinct capability: the temporal state words
must live SHARDED with the mesh and every temporal decision (warm-seed
gate, skip gate, fixpoint trip count) must be a cross-shard consensus.
The Pallas backends claim it (DESIGN.md §14); the jnp backend keeps its
temporal state worker-local. The conformance matrix
(tests/test_differential.py) derives its
parametrization from these declarations — a cell a spec claims must be
bit-identical to the reference; a cell it does not claim must raise
``UnsupportedFeature``. Silent fallbacks cannot hide in either case.

Consumers validate at CONSTRUCTION time via ``BackendSpec.require`` so a
backend that cannot serve a requested feature fails before any work is
queued, with the feature named.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Iterator

import jax


class UnsupportedFeature(ValueError):
    """A backend was asked for a feature its BackendSpec does not claim."""


@dataclasses.dataclass
class BackendSpec:
    """One backend's declared surface. Mutable so the legacy
    ``register_backend``/``register_serving_backend`` entry points can
    attach plane functions to an existing spec (duplicate-checked)."""

    name: str
    stage_fn: Callable | None = None
    serving_fn: Callable | None = None
    temporal_fn: Callable | None = None
    dist: bool = False
    warm: bool = False
    skip: bool = False
    # which edge operator this backend computes ("canny", "sobel",
    # "prewitt", "roberts", "log"); the ``make_detector(op=...)`` resolver
    # and the CLIs' ``--op`` flag group backends by this field
    op: str = "canny"
    # numpy oracle for conformance cells: (img_u8_2d, params) → edges u8.
    # None means the classic ``canny_reference`` — set it for non-Canny
    # operators so the generated matrix pins each against ITS own math.
    ref_fn: Callable | None = None
    # stage plane composes under shard_map directly (jnp stages do; the
    # Pallas stage fns distribute through their serving entry instead)
    stage_dist: bool = False
    warm_dist: bool = False
    # how fine the temporal front-end skip reuses: "strip" (per row strip,
    # the Pallas backends) or "frame" (whole-frame lax.cond, the jnp path)
    skip_granularity: str = "strip"

    # -- capability queries --------------------------------------------------
    def features(self) -> dict[str, bool]:
        return {"dist": self.dist, "warm": self.warm, "skip": self.skip}

    def supports(self, *, dist: bool = False, warm: bool = False,
                 skip: bool = False) -> bool:
        try:
            self.require(dist=dist, warm=warm, skip=skip)
        except UnsupportedFeature:
            return False
        return True

    def require(self, *, dist: bool = False, warm: bool = False,
                skip: bool = False, serving: bool = False,
                temporal: bool = False) -> "BackendSpec":
        """Raise ``UnsupportedFeature`` naming the first feature this
        backend cannot provide; return self so call sites can chain."""
        def missing(feature: str, detail: str):
            return UnsupportedFeature(
                f"backend {self.name!r} does not support {feature!r}: "
                f"{detail} (declared capabilities: {self.features()})"
            )

        if serving and self.serving_fn is None:
            raise missing(
                "serving", "no true-size-aware serving entry is registered"
            )
        if temporal and self.temporal_fn is None:
            raise missing("temporal", "no streaming temporal plane is registered")
        if dist and not self.dist:
            raise missing("dist", "it cannot run under a non-local Dist")
        if warm and not self.warm:
            raise missing("warm", "no temporal warm-start state threading")
        if skip and not self.skip:
            raise missing("skip", "no static-strip front-end skip")
        if skip and not warm:
            # not a capability gap — a caller contract violation
            raise ValueError(
                "skip=True needs warm=True: the front-end skip reuses the "
                "threaded per-frame state"
            )
        if warm and dist and not self.warm_dist:
            raise missing(
                "warm+dist",
                "temporal warm-start state is worker-local; mesh detectors "
                "run cold",
            )
        return self


_SPECS: dict[str, BackendSpec] = {}


def register_backend_spec(spec: BackendSpec, override: bool = False) -> BackendSpec:
    if spec.name in _SPECS and not override:
        raise ValueError(
            f"canny backend {spec.name!r} is already registered; pass "
            "override=True to replace it deliberately"
        )
    _SPECS[spec.name] = spec
    return spec


def _load_kernel_specs() -> None:
    """Import the kernel package's registrations (idempotent)."""
    import repro.kernels.canny_backends  # noqa: F401  (registers)


def _platform() -> str:
    return jax.devices()[0].platform


def default_backend(cpu_default: str) -> str:
    """The Canny backend an entry point runs when its caller names none.

    On a TPU every entry point runs the fused Pallas kernel. Elsewhere
    each entry keeps its own portable default (``cpu_default``: ``"jnp"``
    for the detector/plan builders, ``"fused"`` for the serving and
    streaming planes, which the CPU suite drives in interpret mode).
    """
    return "fused" if _platform() == "tpu" else cpu_default


def op_backend(op: str, backend: str | None, cpu_default: str) -> str:
    """The backend computing operator ``op``: an explicit ``backend`` is
    validated against ``op`` (a detector never silently computes another
    operator); ``None`` resolves Canny through ``default_backend`` and a
    zoo operator to its registered backend."""
    if backend is not None:
        spec = backend_spec(backend)
        if spec.op != op:
            raise ValueError(
                f"backend {backend!r} computes operator {spec.op!r}, "
                f"not {op!r}"
            )
        return backend
    if op == "canny":
        return default_backend(cpu_default)
    candidates = [s.name for s in backend_specs() if s.op == op]
    if not candidates:
        raise ValueError(
            f"no backend registered for operator {op!r} (registered "
            f"operators: {sorted({s.op for s in backend_specs()})})"
        )
    return candidates[0]


def backend_spec(name: str) -> BackendSpec:
    """The registered spec for ``name``; kernels are imported lazily."""
    if name not in _SPECS:
        _load_kernel_specs()
    if name not in _SPECS:
        raise ValueError(
            f"unknown canny backend: {name!r} (registered: "
            f"{sorted(_SPECS)})"
        )
    return _SPECS[name]


def backend_specs() -> Iterator[BackendSpec]:
    """Every registered spec, kernels imported — the conformance matrix's
    source of truth (deterministic registration order)."""
    _load_kernel_specs()
    return iter(list(_SPECS.values()))


def conformance_cells():
    """The full backend × dist × temporal feature lattice, each cell
    tagged supported/unsupported straight from the specs. The test
    harness parametrizes from THIS — cells are generated, never
    hand-enumerated, so a new backend is covered the moment its spec
    registers.

    The generator reads the LIVE registry at yield time: a
    ``register_backend_spec(..., override=True)`` after the generator was
    created (or between cells) is reflected in every cell not yet
    yielded — materialized snapshots cannot go stale against the specs
    they claim to describe."""
    _load_kernel_specs()
    for name in list(_SPECS):
        for dist in (False, True):
            for mode in ("cold", "warm", "warm+skip"):
                spec = _SPECS.get(name)
                if spec is None:  # deregistered mid-iteration
                    continue
                warm = mode != "cold"
                skip = mode == "warm+skip"
                yield {
                    "backend": spec.name,
                    "dist": dist,
                    "mode": mode,
                    "supported": spec.supports(dist=dist, warm=warm, skip=skip),
                }
