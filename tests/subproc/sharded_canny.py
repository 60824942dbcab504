"""Subprocess test: sharded canny == oracle, on an 8-virtual-device mesh.

Run with XLA_FLAGS=--xla_force_host_platform_device_count=8 (the parent
test sets it). Verifies halo exchange, boundary patching, distributed
hysteresis consensus, the GCP planner, AND the one-distribution-plane
tentpole: fused batch-grid Pallas kernels inside shard_map (data-only
and data x model meshes) bit-identical to the local fused path, plus the
mesh-aware serving engine on mixed-size bucket batches (DESIGN.md §8).
"""

import os

assert "xla_force_host_platform_device_count" in os.environ.get("XLA_FLAGS", ""), (
    "run me via tests/test_sharded.py"
)

import numpy as np
import jax
import jax.numpy as jnp


from repro.core.canny import CannyParams, canny_reference
from repro.core.canny.golden_circle import plan, compile_plan
from repro.core.canny.pipeline import make_canny
from repro.core.patterns.dist import Dist, auto_mesh
from repro.data.images import synthetic_batch, synthetic_image
from repro.launch.mesh import dist_from_spec
from repro.stream import elastic_pod_dist
from repro.kernels.fused_canny.ops import fused_canny
from repro.serve.engine import CannyEngine

PARAMS = CannyParams(sigma=1.4, radius=2, low=0.08, high=0.2)
ARGS = (1.4, 2, 0.08, 0.2)


def check_fused_under_shard_map():
    """Fused batch-grid Pallas kernels inside shard_map == local fused
    path, bit for bit: data-only mesh, data×model mesh, row-sharding only,
    and odd heights that force global row padding."""
    imgs = synthetic_batch(8, 64, 96, seed=3)
    local = np.asarray(fused_canny(jnp.asarray(imgs), *ARGS))

    mesh_d = auto_mesh((8,), ("data",))
    dist_d = Dist(mesh=mesh_d, batch_axes=("data",), space_axis=None)
    got = np.asarray(fused_canny(jnp.asarray(imgs), *ARGS, dist=dist_d))
    assert (got == local).all(), "data-only mesh diverged from local fused"
    print("fused shard_map data-only: OK")

    mesh_dm = auto_mesh((2, 4), ("data", "model"))
    dist_dm = Dist(mesh=mesh_dm, batch_axes=("data",), space_axis="model")
    got = np.asarray(fused_canny(jnp.asarray(imgs), *ARGS, dist=dist_dm))
    assert (got == local).all(), "data x model mesh diverged from local fused"
    print("fused shard_map data x model: OK")

    # rows sharded only (batch replicated over the size-1 usage of data)
    dist_m = Dist(mesh=mesh_dm, batch_axes=(), space_axis="model")
    got = np.asarray(fused_canny(jnp.asarray(imgs), *ARGS, dist=dist_m))
    assert (got == local).all(), "model-only sharding diverged"

    # odd height: global row padding must land AFTER the last shard's rows
    odd = synthetic_batch(4, 70, 64, seed=9)  # 70 % 4 != 0
    want = np.asarray(fused_canny(jnp.asarray(odd), *ARGS))
    got = np.asarray(fused_canny(jnp.asarray(odd), *ARGS, dist=dist_dm))
    assert (got == want).all(), "odd-height sharded fused diverged"
    print("fused shard_map odd height: OK")

    return dist_d, dist_dm


def check_mesh_engine(dist_d, dist_dm):
    """Mixed-size bucket batches through a mesh-aware CannyEngine: one
    queue drains across the mesh, outputs == per-request serial oracle,
    and every bucket batch divides the data-axis size."""
    sizes = [(33, 47), (64, 64), (50, 70), (33, 47), (21, 90), (70, 33)]
    reqs = [synthetic_image(h, w, seed=20 + i) for i, (h, w) in enumerate(sizes)]
    for dist in (dist_d, dist_dm):
        engine = CannyEngine(PARAMS, bucket_multiple=32, max_batch=8, dist=dist)
        out = engine.process(reqs)
        for r, e in zip(reqs, out):
            assert e.shape == r.shape and (e == canny_reference(r, PARAMS)).all()
        assert engine.stats.batches >= 1
    print("mesh engine mixed sizes: OK")

    # make_canny(dist=...) returns the mesh-aware bucketed detector
    det = make_canny(PARAMS, dist_dm, backend="fused", bucket_multiple=32)
    img = synthetic_image(70, 80, seed=5)
    assert (np.asarray(det(jnp.asarray(img))) == canny_reference(img, PARAMS)).all()
    # batched call through the same detector
    batch = synthetic_batch(3, 40, 64, seed=6)
    got = np.asarray(det(jnp.asarray(batch)))
    for i in range(3):
        assert (got[i] == canny_reference(batch[i], PARAMS)).all()
    print("make_canny mesh serving: OK")


def check_mesh_builders_auto():
    """Every mesh builder gives ``Auto`` axes: jax 0.9's ``make_mesh``
    defaults to ``Explicit``, under which the kernels' row crops on a
    sharded dim are refused."""
    from jax.sharding import AxisType

    from repro.launch.mesh import make_host_mesh

    meshes = {
        "dist_from_spec 2x4": dist_from_spec("2x4").mesh,
        "dist_from_spec 2x2x2": dist_from_spec("2x2x2").mesh,
        "pod_slice": dist_from_spec("2x2x2").pod_slice(1).mesh,
        "make_host_mesh": make_host_mesh(),
        "elastic_pod_dist": elastic_pod_dist(2)[0].mesh,
    }
    for name, m in meshes.items():
        assert set(m.axis_types) == {AxisType.Auto}, (name, m.axis_types)
    print("mesh builders: Auto axes OK")


def main():
    devs = jax.devices()
    assert len(devs) == 8, devs
    mesh = auto_mesh((2, 4), ("data", "model"))

    check_mesh_builders_auto()

    dist_d, dist_dm = check_fused_under_shard_map()
    check_mesh_engine(dist_d, dist_dm)

    # --- batched, rows sharded 4-way, batch sharded 2-way ---------------
    imgs = synthetic_batch(4, 128, 96, seed=11)
    dist = Dist(mesh=mesh, batch_axes=("data",), space_axis="model")
    out = np.asarray(make_canny(PARAMS, dist)(jnp.asarray(imgs)))
    for i in range(imgs.shape[0]):
        want = canny_reference(imgs[i], PARAMS)
        assert (out[i] == want).all(), f"image {i} mismatch"
    print("sharded batched: OK")

    # --- the RAW jnp stage plane under shard_map (bucket_multiple=None):
    # mesh-divisible shapes wrap canny_local_stages directly — the
    # serving entry must not be the only mesh path left standing
    out_raw = np.asarray(
        make_canny(PARAMS, dist, bucket_multiple=None)(jnp.asarray(imgs))
    )
    assert (out_raw == out).all(), "raw stage plane diverged from serving"
    print("sharded stage plane: OK")

    # --- single image, rows sharded only ---------------------------------
    img = synthetic_batch(1, 64, 80, seed=5)[0]
    dist1 = Dist(mesh=mesh, batch_axes=(), space_axis="model")
    out1 = np.asarray(make_canny(PARAMS, dist1)(jnp.asarray(img)))
    assert (out1 == canny_reference(img, PARAMS)).all()
    print("sharded single: OK")

    # --- GCP planner with a non-divisible height (pad path, exactness) ---
    imgs2 = synthetic_batch(2, 70, 64, seed=7)  # 70 % 4 != 0
    p = plan(2, 70, 64, PARAMS, mesh=mesh)
    assert p.pad_rows == 2, p
    fn = compile_plan(p)
    out2 = np.asarray(fn(jnp.asarray(imgs2)))
    for i in range(2):
        want = canny_reference(imgs2[i], PARAMS)
        assert (out2[i] == want).all(), f"padded image {i} mismatch"
    print("gcp padded plan: OK")

    # --- halo exchange unit check across pattern_scan --------------------
    from repro.core.patterns.scan import pattern_scan
    from jax.sharding import PartitionSpec as P

    x = np.arange(32, dtype=np.float32)
    want_scan = np.cumsum(x)
    scan_fn = jax.jit(
        jax.shard_map(
            lambda xl: pattern_scan(jnp.add, xl, axis_name="model"),
            mesh=mesh,
            in_specs=P("model"),
            out_specs=P("model"),
            check_vma=False,
        )
    )
    got_scan = np.asarray(scan_fn(jnp.asarray(x)))
    np.testing.assert_allclose(got_scan, want_scan, rtol=1e-6)
    print("distributed scan: OK")

    print("ALL-OK")


if __name__ == "__main__":
    main()
