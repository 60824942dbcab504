"""Multi-device (8 virtual CPU) integration tests, subprocess-isolated."""

import functools

from tests.subproc_utils import run_with_devices


@functools.lru_cache(maxsize=1)
def _sharded_canny_out() -> str:
    """One subprocess run shared by the canny assertions below (the 8-dev
    payload is slow; each test pins a different marker of the same run)."""
    return run_with_devices("sharded_canny.py", n_devices=8)


def test_sharded_canny_and_patterns():
    out = _sharded_canny_out()
    assert "ALL-OK" in out
    assert "sharded batched: OK" in out
    assert "sharded stage plane: OK" in out
    assert "distributed scan: OK" in out


def test_fused_kernels_under_shard_map_bit_identical():
    """The tentpole property: fused batch-grid Pallas kernels inside
    shard_map (data-only AND data x model meshes) == local fused path."""
    out = _sharded_canny_out()
    assert "fused shard_map data-only: OK" in out
    assert "fused shard_map data x model: OK" in out
    assert "fused shard_map odd height: OK" in out


def test_mesh_builders_give_auto_axes():
    """dist_from_spec, pod slices, the host mesh and the elastic pod plan
    all build ``AxisType.Auto`` meshes (the shard_map paths need them)."""
    assert "mesh builders: Auto axes OK" in _sharded_canny_out()


def test_mesh_engine_and_serving_registry():
    out = _sharded_canny_out()
    assert "mesh engine mixed sizes: OK" in out
    assert "make_canny mesh serving: OK" in out


def test_elastic_checkpoint_restore():
    out = run_with_devices("elastic.py", n_devices=8)
    assert "ALL-OK" in out
    assert "elastic restore: OK" in out
    assert "elastic pod re-bucketing (4 -> 3 -> 4 ranks): OK" in out

