"""Scan pattern — blocked associative scans.

Used two ways in this framework:
  * Chunked scans: work within each chunk + an associative carry across
    chunks — exactly the tile-then-combine structure the paper's
    patterns advocate.
  * Distributed scans across a sharded sequence axis: local scan, then a
    log-step Hillis–Steele carry across shards via ppermute.
"""

from __future__ import annotations

from typing import Callable, TypeVar

import jax
import jax.numpy as jnp
from jax import lax


T = TypeVar("T")


def blocked_assoc_scan(
    combine: Callable[[T, T], T], elems: T, block: int, axis: int = 0
) -> T:
    """Associative scan over ``axis`` processed in blocks of ``block``.

    Equivalent to ``lax.associative_scan(combine, elems, axis=axis)`` but
    structured as (intra-block scan) + (scan over block summaries) +
    (carry combine), the memory-friendly blocked schedule — each block's
    working set stays in fast memory. ``combine`` must be associative and
    operate leaf-wise (broadcasting over the block dim is used to apply
    carries).
    """
    leaves = jax.tree_util.tree_leaves(elems)
    n = leaves[0].shape[axis]
    if n % block != 0:
        raise ValueError(f"scan length {n} not divisible by block {block}")
    nblocks = n // block

    def split(x):
        x = jnp.moveaxis(x, axis, 0)
        return x.reshape((nblocks, block) + x.shape[1:])

    def unsplit(x):
        x = x.reshape((nblocks * block,) + x.shape[2:])
        return jnp.moveaxis(x, 0, axis)

    blocked = jax.tree_util.tree_map(split, elems)

    # intra-block inclusive scan (axis=1 of the blocked layout)
    intra = lax.associative_scan(combine, blocked, axis=1)

    # block summaries = last element of each intra scan; inclusive scan
    # over them gives each block the carry *through* itself.
    last = jax.tree_util.tree_map(lambda x: x[:, -1], intra)
    carries = lax.associative_scan(combine, last, axis=0)

    # combine block b's intra results with the carry through block b-1
    def shift_back(x):
        return x[:-1]

    carry_prev = jax.tree_util.tree_map(shift_back, carries)  # for blocks 1..
    tail = jax.tree_util.tree_map(lambda x: x[1:], intra)
    cb = jax.tree_util.tree_map(lambda a: a[:, None], carry_prev)
    tail_fixed = combine(cb, tail)
    head = jax.tree_util.tree_map(lambda x: x[:1], intra)
    out = jax.tree_util.tree_map(
        lambda h, t: jnp.concatenate([h, t], axis=0), head, tail_fixed
    )
    return jax.tree_util.tree_map(unsplit, out)


def pattern_scan(
    combine: Callable[[T, T], T], elems: T, axis_name: str | None = None, axis: int = 0
) -> T:
    """Associative scan; if ``axis_name`` is given, continue across shards.

    Local part: ``lax.associative_scan``. Cross-shard: Hillis–Steele over
    shard totals in log2(n) ppermute hops, then each shard folds the
    exclusive prefix of earlier shards into its local results. ``combine``
    must be leaf-wise (it is applied with the carry broadcast over the
    scanned axis), which covers cumsum/cummax/log-sum-exp style monoids;
    structured monoids (e.g. a linear recurrence's (A, Bx) pairs) should
    use their own carry chain.
    """
    local = lax.associative_scan(combine, elems, axis=axis)
    if axis_name is None:
        return local

    n = jax.lax.axis_size(axis_name)
    if n == 1:
        return local

    def take_last(x):
        return lax.index_in_dim(x, x.shape[axis] - 1, axis=axis, keepdims=False)

    total = jax.tree_util.tree_map(take_last, local)

    # inclusive prefix of shard totals (Hillis–Steele, log2(n) hops)
    prefix = total
    hop = 1
    idx = lax.axis_index(axis_name)
    while hop < n:
        moved = jax.tree_util.tree_map(
            lambda x: lax.ppermute(
                x, axis_name, perm=[(j, j + hop) for j in range(n - hop)]
            ),
            prefix,
        )
        has = idx >= hop
        prefix = jax.tree_util.tree_map(
            lambda p, m: jnp.where(has, combine(m, p), p), prefix, moved
        )
        hop *= 2

    # exclusive prefix: shift down one shard; shard 0 keeps local results
    excl = jax.tree_util.tree_map(
        lambda x: lax.ppermute(x, axis_name, perm=[(j, j + 1) for j in range(n - 1)]),
        prefix,
    )

    def fold(e, l):
        eb = jnp.broadcast_to(jnp.expand_dims(e, axis), l.shape)
        return jnp.where(idx > 0, combine(eb, l), l)

    return jax.tree_util.tree_map(fold, excl, local)
