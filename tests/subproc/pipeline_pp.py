"""Subprocess test: GPipe pipeline over 4 stages == sequential reference."""

import os

assert "xla_force_host_platform_device_count" in os.environ.get("XLA_FLAGS", "")

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed.pipeline_parallel import make_pipelined_fn
from repro.core.patterns.dist import auto_mesh


def main():
    devs = jax.devices()
    assert len(devs) >= 4
    mesh = auto_mesh((4,), ("pod",), devices=np.array(devs[:4]))

    # 4 pipeline stages, each an affine map with its own params
    rng = np.random.default_rng(0)
    S, M, MB, D = 4, 6, 2, 8
    ws = jnp.asarray(rng.normal(size=(S, D, D)) * 0.3, jnp.float32)
    bs = jnp.asarray(rng.normal(size=(S, D)) * 0.1, jnp.float32)

    def stage_fn(p, x):
        w, b = p
        return jnp.tanh(x @ w + b)

    x = jnp.asarray(rng.normal(size=(M, MB, D)), jnp.float32)

    # sequential reference
    ref = x
    for s in range(S):
        ref = jax.vmap(lambda xi: stage_fn((ws[s], bs[s]), xi))(ref)

    run = make_pipelined_fn(stage_fn, mesh, stage_axis="pod")
    got = run((ws, bs), x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-5, atol=1e-5)
    print("gpipe 4-stage == sequential: OK")

    # bubble accounting: 1 microbatch still works (all bubble, 1 real)
    x1 = x[:1]
    ref1 = ref[:1]
    got1 = run((ws, bs), x1)
    np.testing.assert_allclose(np.asarray(got1), np.asarray(ref1), rtol=1e-5, atol=1e-5)
    print("gpipe M=1: OK")
    print("ALL-OK")


if __name__ == "__main__":
    main()
