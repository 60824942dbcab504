"""What each entry point picks when its caller names nothing: the Canny
backend (one resolver, ``backends.default_backend``), the compile cache
directory, and the mesh axis types."""

from __future__ import annotations

import sys

import jax
import pytest
from jax.sharding import AxisType

from repro.core.canny import CannyParams, backends, pipeline
from repro.core.canny.golden_circle import plan
from repro.core.patterns.dist import auto_mesh
from repro.launch import canny_serve, canny_stream, compile_cache
from repro.serve.aot import AotCannyEngine
from repro.serve.engine import CannyEngine
from repro.stream import FarmScheduler, TemporalCanny

PARAMS = CannyParams()


class _Resolved(Exception):
    """Raised by a stand-in once it has seen the resolved backend."""


def _make_detector(monkeypatch):
    monkeypatch.setattr(pipeline, "make_canny", lambda p, d, backend, **kw: backend)
    return pipeline.make_detector(PARAMS)


def _make_canny(monkeypatch):
    seen = []
    real = pipeline.backend_spec
    monkeypatch.setattr(
        pipeline, "backend_spec", lambda name: seen.append(name) or real(name)
    )
    pipeline.make_canny(PARAMS)
    return seen[0]


def _canny_serve_cli(monkeypatch):
    seen = []
    monkeypatch.setattr(canny_serve, "use_compile_cache", lambda: None)
    monkeypatch.setattr(
        canny_serve, "serve_aot", lambda args, *a: seen.append(args.backend)
    )
    monkeypatch.setattr(sys, "argv", ["canny_serve", "--aot"])
    canny_serve.main()
    return seen[0]


def _canny_stream_cli(monkeypatch):
    def farm(*a, backend=None, **kw):
        raise _Resolved(backend)

    monkeypatch.setattr(canny_stream, "use_compile_cache", lambda: None)
    monkeypatch.setattr(canny_stream, "FarmScheduler", farm)
    monkeypatch.setattr(sys, "argv", ["canny_stream", "--frames", "1"])
    with pytest.raises(_Resolved) as e:
        canny_stream.main()
    return e.value.args[0]


# entry → (how to read what it resolved, its default off the TPU)
ENTRIES = {
    "make_detector": (_make_detector, "jnp"),
    "make_canny": (_make_canny, "jnp"),
    "plan": (lambda mp: plan(1, 64, 64, PARAMS).backend, "jnp"),
    "TemporalCanny": (lambda mp: TemporalCanny(PARAMS).backend, "fused"),
    "FarmScheduler": (
        lambda mp: FarmScheduler(PARAMS, n_workers=1).detectors[0].backend,
        "fused",
    ),
    "CannyEngine": (lambda mp: CannyEngine(PARAMS).backend, "fused"),
    "AotCannyEngine": (
        lambda mp: AotCannyEngine(PARAMS, buckets=[(32, 32)], lanes=[1]).backend,
        "fused",
    ),
    "canny_serve CLI": (_canny_serve_cli, "fused"),
    "canny_stream CLI": (_canny_stream_cli, "fused"),
}


@pytest.mark.parametrize("platform", ["cpu", "tpu"])
@pytest.mark.parametrize("entry", sorted(ENTRIES))
def test_default_backend_per_entry(monkeypatch, entry, platform):
    """On a TPU every entry point runs the fused kernel; elsewhere each
    keeps its portable default."""
    monkeypatch.setattr(backends, "_platform", lambda: platform)
    resolve, cpu_default = ENTRIES[entry]
    want = "fused" if platform == "tpu" else cpu_default
    assert resolve(monkeypatch) == want


def test_explicit_backend_is_validated_against_the_operator():
    with pytest.raises(ValueError, match="computes operator"):
        backends.op_backend("sobel", "fused", cpu_default="jnp")
    assert backends.op_backend("canny", "pallas", cpu_default="jnp") == "pallas"


def _config_updates(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda k, v: calls.append((k, v)))
    return calls


def test_compile_cache_honours_the_environment(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    calls = _config_updates(monkeypatch)
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert calls == []  # JAX reads the variable itself; nothing else is set


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    calls = _config_updates(monkeypatch)
    path = compile_cache.use_compile_cache()
    root = compile_cache.CHECKOUT
    assert (root / "chip_smoke.py").is_file() and (root / "src" / "repro").is_dir()
    assert path == str(root / ".jax_cache")
    assert calls == [("jax_compilation_cache_dir", path)]


def test_auto_mesh_axes_are_auto():
    for mesh in (
        auto_mesh((1, 1), ("data", "model")),
        auto_mesh((1,), ("pod",), jax.devices()[:1]),
    ):
        assert set(mesh.axis_types) == {AxisType.Auto}
