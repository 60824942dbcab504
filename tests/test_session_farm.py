"""Session mode and pod-rank placement on 4 virtual devices,
subprocess-isolated (see tests/subproc/session_farm.py)."""

import functools

from tests.subproc_utils import run_with_devices


@functools.lru_cache(maxsize=1)
def _session_farm_out() -> str:
    return run_with_devices("session_farm.py", n_devices=4, timeout=600)


def test_session_mode_pins_each_camera_to_one_device():
    out = _session_farm_out()
    assert "ALL-OK" in out
    for backend in ("fused", "jnp"):
        assert f"session mode ({backend}): bit-exact, each camera on one device: OK" in out


def test_session_mode_serves_on_all_four_devices():
    out = _session_farm_out()
    assert "session mode (fused): all 4 devices serve" in out
    assert "session mode (jnp): all 4 devices serve" in out


def test_session_state_lives_on_its_cameras_device():
    """State, gates and true-size table on the camera's device, and no
    device-to-device copy in any step (the transfer guard is on)."""
    out = _session_farm_out()
    assert "session mode (fused): every session's state on its own device: OK" in out
    assert "session mode (jnp): every session's state on its own device: OK" in out


def test_pod_farm_places_each_one_device_rank_on_its_own_device():
    assert "pod farm: each one-device rank on its own device: OK" in _session_farm_out()
