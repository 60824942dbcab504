"""Subprocess test: EP MoE variants (psum + a2a) == global MoE, 8 devices."""

import os

assert "xla_force_host_platform_device_count" in os.environ.get("XLA_FLAGS", "")

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.common import cast_float, init_params
from repro.models.hints import clear_hints, set_hints
from repro.models.moe import _moe_ffn_global, moe_ffn, moe_schema
from repro.core.patterns.dist import auto_mesh


def main():
    mesh = auto_mesh((2, 4), ("data", "model"))
    cfg = ModelConfig(
        name="tiny-moe", family="moe", n_layers=1, d_model=16, n_heads=2,
        n_kv_heads=2, d_ff=32, vocab_size=64, n_experts=8, top_k=2, moe_d_ff=24,
        n_shared_experts=1,
    )
    p = cast_float(init_params(moe_schema(cfg), jax.random.PRNGKey(0)), jnp.float32)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(4, 16, cfg.d_model)) * 0.3, jnp.float32)

    clear_hints()
    want, want_aux = jax.jit(lambda p, x: _moe_ffn_global(p, x, cfg, 8.0))(p, x)

    xs = NamedSharding(mesh, P("data", None, None))
    for impl in (None, "a2a"):
        clear_hints()
        set_hints(batch=("data",), ep_axis="model", mesh=mesh)
        if impl:
            set_hints(moe_impl=impl)
        with mesh:
            got, aux = jax.jit(lambda p, x: moe_ffn(p, x, cfg, 8.0))(
                p, jax.device_put(x, xs)
            )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4,
            err_msg=f"impl={impl}",
        )
        assert np.isfinite(float(aux))
        print(f"ep impl={impl or 'psum'}: OK (aux={float(aux):.4f} vs {float(want_aux):.4f})")
    clear_hints()
    print("ALL-OK")


if __name__ == "__main__":
    main()
