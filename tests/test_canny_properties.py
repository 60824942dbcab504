"""Hypothesis property tests on the Canny system's invariants."""

import jax.numpy as jnp
import numpy as np
from _hypothesis_compat import given, settings, st

from repro.core.canny import (
    CannyParams,
    canny_reference,
    gaussian_reference,
    hysteresis_reference,
    nms_reference,
    sobel_reference,
)
from repro.core.canny.gaussian import gaussian_stage
from repro.core.canny.hysteresis import double_threshold, hysteresis_fixpoint
from repro.core.canny.nms import nms_stage
from repro.core.patterns.dist import StencilCtx
from repro.data.images import synthetic_image

SETTINGS = dict(max_examples=15, deadline=None)
CTX = StencilCtx(None, "edge")


@given(h=st.integers(8, 64), w=st.integers(8, 64), seed=st.integers(0, 10_000))
@settings(**SETTINGS)
def test_gaussian_preserves_mean_range(h, w, seed):
    """Blur is an averaging filter: output within input range; a constant
    image is a fixed point."""
    img = synthetic_image(h, w, seed=seed)
    p = CannyParams()
    out = np.asarray(gaussian_stage(jnp.asarray(img), CTX, p))
    assert out.min() >= img.min() - 1e-5
    assert out.max() <= img.max() + 1e-5
    const = np.full((h, w), 0.37, np.float32)
    outc = np.asarray(gaussian_stage(jnp.asarray(const), CTX, p))
    np.testing.assert_allclose(outc, 0.37, rtol=1e-5)


@given(h=st.integers(8, 48), w=st.integers(8, 48), seed=st.integers(0, 10_000))
@settings(**SETTINGS)
def test_constant_image_has_no_edges(h, w, seed):
    rng = np.random.default_rng(seed)
    img = np.full((h, w), float(rng.uniform(0, 1)), np.float32)
    assert canny_reference(img, CannyParams()).sum() == 0


@given(seed=st.integers(0, 10_000))
@settings(**SETTINGS)
def test_nms_output_subset_of_magnitudes(seed):
    """NMS only suppresses: every surviving value equals its input."""
    rng = np.random.default_rng(seed)
    mag = rng.uniform(0, 1, size=(24, 24)).astype(np.float32)
    dirs = rng.integers(0, 4, size=(24, 24)).astype(np.uint8)
    out = np.asarray(nms_stage(jnp.asarray(mag), jnp.asarray(dirs), CTX))
    surviving = out > 0
    np.testing.assert_array_equal(out[surviving], mag[surviving])


@given(
    h=st.integers(6, 32), w=st.integers(6, 32),
    p_weak=st.floats(0.05, 0.95), seed=st.integers(0, 10_000),
)
@settings(**SETTINGS)
def test_hysteresis_invariants(h, w, p_weak, seed):
    """strong ⊆ edges ⊆ weak, monotone in thresholds, == BFS oracle."""
    rng = np.random.default_rng(seed)
    weak = rng.uniform(size=(h, w)) < p_weak
    strong = weak & (rng.uniform(size=(h, w)) < 0.3)
    got = np.asarray(
        hysteresis_fixpoint(jnp.asarray(strong), jnp.asarray(weak), CTX)
    ).astype(bool)
    assert (got | ~strong).all() or (strong <= got).all()  # strong ⊆ edges
    assert (got <= weak).all()  # edges ⊆ weak
    # oracle equivalence on an equivalent magnitude encoding
    mag = np.where(strong, 1.0, np.where(weak, 0.5, 0.0)).astype(np.float32)
    want = hysteresis_reference(mag, CannyParams(low=0.4, high=0.9)).astype(bool)
    assert (got == want).all()


@given(seed=st.integers(0, 10_000))
@settings(**SETTINGS)
def test_more_permissive_thresholds_give_superset(seed):
    img = synthetic_image(48, 48, seed=seed)
    tight = canny_reference(img, CannyParams(low=0.15, high=0.3)).astype(bool)
    loose = canny_reference(img, CannyParams(low=0.05, high=0.3)).astype(bool)
    assert (tight <= loose).all()


@given(seed=st.integers(0, 10_000), flip=st.booleans())
@settings(**SETTINGS)
def test_geometric_equivariance(seed, flip):
    """Canny commutes with horizontal/vertical flips (symmetric stencils,
    symmetric tie-breaking under >= on both neighbours)."""
    img = synthetic_image(40, 40, seed=seed)
    p = CannyParams(low=0.08, high=0.2)
    a = canny_reference(img[::-1] if flip else img[:, ::-1], p)
    b = canny_reference(img, p)
    b = b[::-1] if flip else b[:, ::-1]
    assert (a == b).all()


# ---------------- odd/tiny shapes through the kernel path -------------------
@given(h=st.integers(1, 9), w=st.integers(1, 40), seed=st.integers(0, 10_000))
@settings(max_examples=10, deadline=None)
def test_fused_tiny_and_odd_shapes_bit_exact(h, w, seed):
    """The untested shape edges: h below the stage halo (radius+2 = 4)
    forces the min_rows clamp + row padding of ``pick_block_rows``, and
    w not a multiple of 32 forces the packed-word tail fallback (uint8
    code map + zero-padded packed hysteresis). All must stay bit-exact."""
    from repro.core.canny.pipeline import make_canny

    img = synthetic_image(h, w, seed=seed)
    p = CannyParams(low=0.08, high=0.2)
    det = make_canny(p, backend="fused", bucket_multiple=None)
    got = np.asarray(det(jnp.asarray(img)))
    assert got.shape == img.shape
    assert (got == canny_reference(img, p)).all()


@given(
    h=st.integers(1, 40), w=st.integers(1, 70),
    p_weak=st.floats(0.1, 0.9), seed=st.integers(0, 10_000),
)
@settings(**SETTINGS)
def test_hysteresis_packed_word_tail_any_width(h, w, p_weak, seed):
    """Bit-packed hysteresis on widths that do NOT divide 32: the zero
    pad of the packed tail must neither create nor destroy connectivity
    (vs the unpacked BFS-equivalent fixpoint)."""
    from repro.kernels.hysteresis import hysteresis_from_masks, hysteresis_ref

    rng = np.random.default_rng(seed)
    weak = rng.uniform(size=(h, w)) < p_weak
    strong = weak & (rng.uniform(size=(h, w)) < 0.25)
    got = np.asarray(
        hysteresis_from_masks(jnp.asarray(strong), jnp.asarray(weak), block_rows=8)
    )
    want = np.asarray(hysteresis_ref(jnp.asarray(strong), jnp.asarray(weak)))
    assert (got == want).all()


# ---------------- shard/strip geometry contracts ----------------------------
@given(h=st.integers(1, 300), target=st.integers(1, 128), min_rows=st.integers(1, 8))
@settings(max_examples=40, deadline=None)
def test_pick_block_rows_divisor_contract(h, target, min_rows):
    """Divides h exactly, respects the halo floor, prefers ≤ target: the
    invariants the shard-local strip grid is built on."""
    from repro.kernels.common import pick_block_rows_divisor

    if h < min_rows:
        with __import__("pytest").raises(ValueError):
            pick_block_rows_divisor(h, target, min_rows)
        return
    bh = pick_block_rows_divisor(h, target, min_rows)
    assert h % bh == 0
    assert bh >= min_rows
    # bh only exceeds target when NO divisor fits the [min_rows, target]
    # window (then the whole height is one strip)
    if bh > target:
        assert bh == h
        assert all(h % d for d in range(min_rows, min(target, h) + 1))


@given(
    h=st.integers(1, 200), ms=st.integers(1, 8), radius=st.integers(1, 3),
    block_rows=st.one_of(st.none(), st.integers(4, 32)),
)
@settings(max_examples=40, deadline=None)
def test_shard_grid_random_mesh_shapes(h, ms, radius, block_rows):
    """``_shard_grid`` over random mesh extents: the padded global height
    splits exactly into ms equal shard-local heights, each an exact
    multiple of the strip height, which respects the stage halo — or the
    configuration is rejected loudly (shards thinner than the halo)."""
    import types

    import pytest

    from repro.kernels.fused_canny.ops import _shard_grid

    h2 = radius + 2
    dist = types.SimpleNamespace(space_size=lambda: ms)
    try:
        hp, hl, bh = _shard_grid(h, dist, h2, block_rows)
    except ValueError:
        # legal only when the shard-local rows cannot hold the halo, or
        # an explicit block_rows does not divide the shard-local height
        assert -(-h // ms) < h2 or block_rows is not None
        return
    assert hp >= h and hp % ms == 0
    assert hl == hp // ms and hl % bh == 0
    assert bh >= h2 or block_rows is not None
    # several strips per shard only at sublane-aligned heights (the TPU
    # kernel compiler's block rule)
    assert bh % 8 == 0 or bh == hl or block_rows is not None
