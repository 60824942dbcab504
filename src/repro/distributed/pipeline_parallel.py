"""Pipeline parallelism over a mesh axis (GPipe-style microbatching).

The multi-pod mesh's "pod" axis can act as a pipeline-stage axis instead
of pure DP: each pod holds a contiguous slice of layers, microbatches
stream through, and activations hop stage→stage via ``lax.ppermute`` —
the same pattern primitive the canny stencils use for halos (DESIGN.md:
the pipeline pattern at pod scale).

Schedule: plain GPipe fill-and-drain. With S stages and M microbatches
the loop runs S+M−1 ticks; every device executes its stage function each
tick (SPMD), with masking selecting real vs bubble work. Bubble fraction
(S−1)/(S+M−1) — the §Perf lever is raising M.

``pipeline_apply`` is deliberately model-agnostic: it takes one
``stage_fn(stage_params, x) -> x`` plus stage-stacked params, so the LM
stack and tests share it.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P



def pipeline_apply(
    stage_fn: Callable,
    stage_params,
    x_micro: jax.Array,
    axis_name: str,
):
    """Run inside shard_map: stream microbatches through pipeline stages.

    stage_params: THIS device's stage params (already sharded by stage).
    x_micro: (M, mb, ...) microbatches — meaningful on stage 0 (others
      may pass zeros; only stage 0's values enter the pipe).
    Returns (M, mb, ...) outputs — meaningful on the LAST stage.
    """
    n_stages = jax.lax.axis_size(axis_name)
    stage = lax.axis_index(axis_name)
    # shard_map leaves a leading (1, ...) stage dim on the params — drop it
    stage_params = jax.tree_util.tree_map(
        lambda a: jnp.squeeze(a, 0) if (a.ndim > 0 and a.shape[0] == 1) else a,
        stage_params,
    )
    m = x_micro.shape[0]
    ticks = m + n_stages - 1
    out_buf = jnp.zeros_like(x_micro)
    # one-hop ring: stage s → s+1 (last stage's send is dropped)
    perm = [(i, i + 1) for i in range(n_stages - 1)]

    def tick(t, carry):
        recv, out_buf = carry
        # stage 0 injects microbatch t (while t < m); others use recv
        inject_idx = jnp.clip(t, 0, m - 1)
        x0 = lax.dynamic_index_in_dim(x_micro, inject_idx, 0, keepdims=False)
        x_in = jnp.where(stage == 0, x0, recv)
        y = stage_fn(stage_params, x_in)
        # last stage writes microbatch (t - (S-1)) when it's real
        out_idx = jnp.clip(t - (n_stages - 1), 0, m - 1)
        is_real = (t >= n_stages - 1) & (stage == n_stages - 1)
        cur = lax.dynamic_index_in_dim(out_buf, out_idx, 0, keepdims=False)
        upd = jnp.where(is_real, y, cur)
        out_buf = lax.dynamic_update_index_in_dim(out_buf, upd, out_idx, 0)
        nxt = lax.ppermute(y, axis_name, perm)
        return (nxt, out_buf)

    recv0 = jnp.zeros_like(
        lax.dynamic_index_in_dim(x_micro, 0, 0, keepdims=False)
    )
    _, out_buf = lax.fori_loop(0, ticks, tick, (recv0, out_buf))
    # only the last stage holds real outputs — broadcast them to all
    # stages so the result is genuinely replicated over the axis
    out_buf = jnp.where(stage == n_stages - 1, out_buf, jnp.zeros_like(out_buf))
    return lax.psum(out_buf, axis_name)


def make_pipelined_fn(
    stage_fn: Callable,
    mesh: Mesh,
    stage_axis: str = "pod",
    data_spec: P | None = None,
):
    """Wrap ``stage_fn`` into a jitted pipelined executor.

    stage-stacked params (S, ...) shard over ``stage_axis``; microbatched
    input (M, mb, ...) is replicated over the stage axis (stage 0 reads
    it) and may shard its batch dims over the remaining axes via
    ``data_spec``.
    """
    dspec = data_spec if data_spec is not None else P()

    inner = jax.shard_map(
        lambda p, x: pipeline_apply(stage_fn, p, x, stage_axis),
        mesh=mesh,
        in_specs=(P(stage_axis), dspec),
        out_specs=dspec,
        check_vma=False,
    )

    def run(stacked_params, x_micro):
        return inner(stacked_params, x_micro)

    return jax.jit(run)
