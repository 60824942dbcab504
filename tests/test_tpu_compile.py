"""The main-path kernels compile for a TPU v5e, at real frame sizes.

Interpret mode (what every other test runs) accepts kernels the TPU
kernel compiler refuses: unaligned blocks, 8-bit vector ops, dynamic
slices, more VMEM than the scoped limit. These tests compile each kernel
with ``interpret=False`` for a described ``v5e:2x2`` topology — no chip
is needed, nothing runs — and check that a Pallas TPU kernel
(``tpu_custom_call``) is in the compiled program.

The topology is described inside a module fixture only: the TPU library
may be loaded by one process at a time, so describing it while modules
are imported would make parallel test workers collect different tests.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.core.patterns.dist import Dist, auto_mesh
from repro.kernels import common
from repro.kernels.fused_canny.ops import (
    fused_canny,
    fused_canny_warm_skip,
    fused_frontend,
)
from repro.kernels.hysteresis.ops import packed_fixpoint
from repro.kernels.log.ops import log_edges
from repro.kernels.prewitt.ops import prewitt_edges
from repro.kernels.roberts.ops import roberts_edges
from repro.kernels.sobel.ops import sobel_edges
from repro.kernels.staged import staged_canny

HD = (1, 1080, 1920)  # ITU-R BT.709 camera frame
BSDS = (8, 321, 481)  # BSDS500 image shape, a batch of eight
UHD2 = (2, 2160, 3840)  # two ITU-R BT.2020 frames
HD_ROWS = -(-HD[1] // 128) * 128  # 1080 rows padded to whole 128-row strips


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # the TPU library writes no logs
        try:
            desc = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc


@pytest.fixture(scope="module")
def chip(topo):
    """Compile as on the chip: the kernels' platform-derived choices
    (interpret off, the chip's VMEM batch budget) take their TPU branch,
    and the persistent compile cache is off — an entry compiled for a
    described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(common, "on_tpu", lambda: True)
        yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _f32(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def _u32(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.uint32, sharding=sharding)


def _warm_skip_args(sh):
    b, h, w = HD
    state = _u32((b, HD_ROWS, w // 32), sh)
    have = jax.ShapeDtypeStruct((), jnp.bool_, sharding=sh)
    return (_f32(HD, sh), _f32(HD, sh), state, state, state, have)


# name → (function, sharding → its argument shapes)
CASES = {
    "fused_canny_1080p": (fused_canny, lambda sh: (_f32(HD, sh),)),
    "fused_canny_bsds_b8": (fused_canny, lambda sh: (_f32(BSDS, sh),)),
    "fused_frontend_code": (
        lambda x: fused_frontend(x, emit="code"), lambda sh: (_f32(HD, sh),)
    ),
    "staged_canny": (staged_canny, lambda sh: (_f32(HD, sh),)),
    "packed_fixpoint": (
        lambda s, w: packed_fixpoint(s, w, 120),
        lambda sh: (_u32((1, 1080, 60), sh),) * 2,
    ),
    "sobel_edges": (sobel_edges, lambda sh: (_f32(HD, sh),)),
    "prewitt_edges": (prewitt_edges, lambda sh: (_f32(HD, sh),)),
    "roberts_edges": (roberts_edges, lambda sh: (_f32(HD, sh),)),
    "log_edges": (log_edges, lambda sh: (_f32(HD, sh),)),
    "fused_warm_skip_masked_frontend": (fused_canny_warm_skip, _warm_skip_args),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(chip, name):
    fn, args = CASES[name]
    text = jax.jit(fn).lower(*args(chip)).compile().as_text()
    assert "tpu_custom_call" in text, f"{name}: no Pallas TPU kernel compiled"


def test_sharded_fused_detector_compiles_for_2x2_mesh(topo, chip):
    """The mesh path (``--mesh 2x2``): batch over ``data``, rows over
    ``model``, halo exchange between row shards — two 4K frames."""
    del chip  # only its chip-like compile settings are needed
    mesh = auto_mesh((2, 2), ("data", "model"), topo.devices)
    dist = Dist(mesh=mesh, batch_axes=("data",), space_axis="model")
    x = _f32(UHD2, NamedSharding(mesh, P("data", "model", None)))
    compiled = jax.jit(lambda v: fused_canny(v, dist=dist)).lower(x).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "collective-permute" in text  # the row-halo exchange
