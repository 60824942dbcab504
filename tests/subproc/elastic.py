"""Subprocess test: checkpoint saved on an 8-device mesh restores onto a
4-device mesh (elastic rescale) with identical logical values — and the
streaming side of the same story: ``elastic_pod_dist`` re-buckets the
device pool as the pod roster shrinks/grows, every roster size yielding
usable per-rank sub-meshes that detect bit-identically."""

import os

assert "xla_force_host_platform_device_count" in os.environ.get("XLA_FLAGS", "")

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint import Checkpointer
from repro.core.canny import CannyParams, canny_reference
from repro.core.canny.pipeline import make_canny
from repro.data.images import synthetic_image
from repro.stream import elastic_pod_dist
from repro.core.patterns.dist import auto_mesh
import tempfile


def check_elastic_pod_rebucketing():
    """Roster 4 → 3 → 4: each re-bucketing yields a pod-axis Dist whose
    per-rank slice drives a real detector to the exact reference."""
    params = CannyParams(sigma=1.4, radius=2, low=0.08, high=0.2)
    img = synthetic_image(48, 64, seed=3)
    want = canny_reference(img, params)
    for n_ranks, want_per_rank in ((4, 2), (3, 2), (4, 2)):
        dist, plan = elastic_pod_dist(n_ranks, global_batch=8, prefer_model=2)
        assert dist.pod_size() == n_ranks, (n_ranks, dist.mesh.shape)
        data, model = plan.mesh_shape
        assert data * model == want_per_rank, plan
        assert f"/{8 // n_ranks} devices" in plan.note
        # every rank's slice is a REAL detector-bearing sub-mesh
        for r in range(n_ranks):
            sl = dist.pod_slice(r)
            assert sl.pod_axis is None
            det = make_canny(params, sl, backend="fused")
            got = np.asarray(det(jnp.asarray(img, jnp.float32)))
            assert (got == want).all(), f"ranks={n_ranks} rank {r} diverged"
    print("elastic pod re-bucketing (4 -> 3 -> 4 ranks): OK")


def main():
    devs = jax.devices()
    assert len(devs) == 8

    mesh_a = auto_mesh((2, 4), ("data", "model"), devices=devs)
    w = jnp.arange(64.0).reshape(8, 8)
    sh_a = NamedSharding(mesh_a, P("data", "model"))
    w_a = jax.device_put(w, sh_a)

    with tempfile.TemporaryDirectory() as d:
        ck = Checkpointer(d)
        ck.save(3, {"w": w_a}, blocking=True)

        # elastic: restore onto a 4-device mesh (half the pod "failed")
        mesh_b = auto_mesh((2, 2), ("data", "model"), devices=devs[:4])
        sh_b = NamedSharding(mesh_b, P("data", "model"))
        got, step = ck.restore(
            template={"w": w}, shardings={"w": sh_b}
        )
        assert step == 3
        np.testing.assert_allclose(np.asarray(got["w"]), np.asarray(w))
        assert got["w"].sharding == sh_b
        print("elastic restore: OK")

    check_elastic_pod_rebucketing()
    print("ALL-OK")


if __name__ == "__main__":
    main()
