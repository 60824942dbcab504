"""Production meshes. Functions only — importing this never touches jax
device state (the dry-run must set XLA_FLAGS before any device query)."""

from __future__ import annotations

import jax

from repro.core.patterns.dist import auto_mesh


def make_production_mesh(*, multi_pod: bool = False):
    """v5e pod meshes: 16×16 = 256 chips single-pod; 2×16×16 multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_host_mesh(data: int = 2, model: int = 4):
    """Small mesh for multi-device CPU tests (8 virtual devices)."""
    return auto_mesh((data, model), ("data", "model"))


def dist_from_spec(spec: str | None):
    """``--mesh [POD x] DATA x MODEL`` CLI flag → a ``Dist`` (the one
    distribution plane every serving/stream entry point accepts).

    ``None``/empty → local. ``"2x4"`` → batch over a 2-way ``data`` axis,
    rows over a 4-way ``model`` axis; ``"8x1"``/``"8"`` → data-only.
    Three components (``"2x2x2"`` = POD×DATA×MODEL) add the streaming
    farm's pod axis: frames dispatch over ``pod`` ranks, each rank
    driving its own detector over its DATA×MODEL device slice
    (``Dist.pod_slice``; ``2x1x1`` = two plain per-host workers).
    Size-1 axes are dropped from the Dist so consensus and halo exchange
    no-op on them. Raises if the host has fewer devices than the mesh.
    """
    from repro.core.patterns.dist import LOCAL, Dist

    if not spec:
        return LOCAL
    parts = [int(p) for p in spec.lower().split("x")]
    if len(parts) == 1:
        parts.append(1)
    if len(parts) == 2:
        parts.insert(0, 1)
    if len(parts) != 3 or any(p < 1 for p in parts):
        raise ValueError(
            f"--mesh expects DATAxMODEL or PODxDATAxMODEL (e.g. 2x4, "
            f"2x2x2), got {spec!r}"
        )
    pod, data, model = parts
    n = pod * data * model
    have = len(jax.devices())
    if have < n:
        raise ValueError(
            f"--mesh {spec} needs {n} devices, host has {have} "
            "(hint: XLA_FLAGS=--xla_force_host_platform_device_count=N)"
        )
    if n == 1:
        return LOCAL
    if pod > 1:
        mesh = auto_mesh((pod, data, model), ("pod", "data", "model"))
        return Dist(
            mesh=mesh,
            batch_axes=("data",) if data > 1 else (),
            space_axis="model" if model > 1 else None,
            pod_axis="pod",
        )
    mesh = auto_mesh((data, model), ("data", "model"))
    return Dist(
        mesh=mesh,
        batch_axes=("data",) if data > 1 else (),
        space_axis="model" if model > 1 else None,
    )
