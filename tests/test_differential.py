"""Conformance matrix — every backend × dist × warm/skip cell, generated.

The parametrization is DERIVED from the ``BackendSpec`` registry
(``core/canny/backends.py``), never hand-enumerated: for every
registered backend and every (local | data×model mesh) × (cold | warm |
warm+skip) cell,

  * a cell the spec CLAIMS must run and produce bits identical to the
    serial numpy reference (``core/canny/reference.py``) on the corpus
    images AND on adversarial synthetic streams;
  * a cell the spec does NOT claim must raise ``UnsupportedFeature`` at
    construction — asserted too, so a silent fallback (e.g. warm state
    quietly dropped under a mesh) cannot hide behind a passing bit-exact
    check.

A new backend therefore gets full conformance coverage the moment its
spec registers; an over-claiming spec fails the matrix; an under-claiming
one fails the unsupported-cell assertion.

The mesh cells build a data×model mesh over however many devices the
host exposes (1×1 in tier-1 CI — the shard_map composition, halo
plumbing and consensus still execute; the CI conformance job forces 8
virtual devices for a real 2×4 split; tests/test_sharded.py pins the
multi-device bit-identity separately).

The stream axes are chosen adversarially for the temporal paths
(``tests/_conformance.py``): all-static (maximal skip), all-changing (skip
must never fire wrongly), and single-pixel flicker (destructive edits
every frame — the warm gate must fall back cold AND the strip mask must
recompute exactly the touched strips). The cost-counter tests at the
bottom parametrize over every skip-capable backend and pin the
acceptance property: the per-stage path shows the SAME launch/strip
savings as fused on a static stream.
"""

import jax
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.canny import (
    UnsupportedFeature,
    backend_specs,
    canny_reference,
    conformance_cells,
    make_canny,
)
from repro.core.patterns.dist import LOCAL, Dist, auto_mesh
from repro.data.images import synthetic_image
from repro.stream import TemporalCanny

from _conformance import (
    CORPUS_SIZES,
    PARAMS,
    STREAMS,
    all_changing,
    all_static,
    single_pixel_flicker,
)

CELLS = list(conformance_cells())
BY_NAME = {s.name: s for s in backend_specs()}
ZOO = ("sobel_op", "prewitt", "roberts", "log_op")
SKIP_BACKENDS = [s.name for s in backend_specs() if s.skip and s.temporal_fn]
STRIP_SKIP_BACKENDS = [
    s.name for s in backend_specs()
    if s.skip and s.temporal_fn and s.skip_granularity == "strip"
]


def _cell_id(cell) -> str:
    return f"{cell['backend']}-{'mesh' if cell['dist'] else 'local'}-{cell['mode']}"


def _mesh_dist() -> Dist:
    """A data×model mesh over whatever this host has: 1×1 in tier-1 CI
    (the shard_map composition itself), 2×4 under the conformance job's
    8 forced devices."""
    n = len(jax.devices())
    data = 2 if n >= 2 else 1
    model = max(d for d in (1, 2, 4) if data * d <= n)
    mesh = auto_mesh((data, model), ("data", "model"))
    return Dist(mesh=mesh, batch_axes=("data",), space_axis="model")


def _make_detector(cell):
    """Construct the cell's detector — the call that must either succeed
    (supported) or raise UnsupportedFeature (unsupported)."""
    dist = _mesh_dist() if cell["dist"] else LOCAL
    if cell["mode"] == "cold":
        return make_canny(PARAMS, dist, backend=cell["backend"], bucket_multiple=32)
    return TemporalCanny(
        PARAMS,
        warm=True,
        skip=cell["mode"] == "warm+skip",
        backend=cell["backend"],
        block_rows=16,
        dist=dist,
    )


# ---------------- the generated matrix --------------------------------------
def test_matrix_is_generated_not_enumerated():
    """Every registered backend contributes exactly the 6-cell lattice,
    and at least the three shipped backends are present — the harness
    cannot silently drop a backend or a feature axis."""
    names = {c["backend"] for c in CELLS}
    assert {"jnp", "pallas", "fused"} <= names
    # ...and the operator zoo registers alongside the Canny backends
    assert set(ZOO) <= names
    for name in names:
        assert sum(c["backend"] == name for c in CELLS) == 6
    # the shipped support surface, derived from the specs' own claims (the
    # matrix may not second-guess the registry)...
    by_name = BY_NAME
    for c in CELLS:
        warm = c["mode"] != "cold"
        skip = c["mode"] == "warm+skip"
        want = by_name[c["backend"]].supports(
            dist=c["dist"], warm=warm, skip=skip
        )
        assert c["supported"] == want, c
    # ...and the claims themselves, pinned so a regression in a spec is a
    # test failure, not a silently shrunk matrix: the Pallas backends
    # carry their temporal state sharded with the mesh (warm_dist,
    # DESIGN.md §14); the jnp backend keeps it worker-local.
    for name in ("fused", "pallas"):
        assert by_name[name].warm_dist, name
        for mode in ("warm", "warm+skip"):
            assert {"backend": name, "dist": True, "mode": mode,
                    "supported": True} in CELLS
    assert not by_name["jnp"].warm_dist
    for mode in ("warm", "warm+skip"):
        assert {"backend": "jnp", "dist": True, "mode": mode,
                "supported": False} in CELLS
    # the zoo's honest claims, pinned: cold serving everywhere (local AND
    # mesh, each against the operator's OWN oracle), and NO temporal
    # cells — a single-pass operator has no fixpoint state to warm-seed,
    # so a warm/skip claim would be a lie
    for name in ZOO:
        assert by_name[name].ref_fn is not None, name
        for dist in (False, True):
            assert {"backend": name, "dist": dist, "mode": "cold",
                    "supported": True} in CELLS
            for mode in ("warm", "warm+skip"):
                assert {"backend": name, "dist": dist, "mode": mode,
                        "supported": False} in CELLS


@pytest.mark.parametrize("cell", CELLS, ids=_cell_id)
def test_conformance_corpus(cell):
    if not cell["supported"]:
        with pytest.raises(UnsupportedFeature):
            _make_detector(cell)
        return
    det = _make_detector(cell)
    ref_fn = BY_NAME[cell["backend"]].ref_fn or canny_reference
    for i, (h, w) in enumerate(CORPUS_SIZES):
        img = synthetic_image(h, w, seed=100 + i)
        got = np.asarray(det(jnp.asarray(img)))
        want = ref_fn(img, PARAMS)
        assert got.shape == want.shape
        assert (got == want).all(), (
            f"{_cell_id(cell)} diverged on corpus image {h}x{w}"
        )


@pytest.mark.parametrize("stream_name", list(STREAMS))
@pytest.mark.parametrize(
    "cell",
    [c for c in CELLS if c["supported"]],
    ids=_cell_id,
)
def test_conformance_streams(cell, stream_name):
    det = _make_detector(cell)
    ref_fn = BY_NAME[cell["backend"]].ref_fn or canny_reference
    for i, frame in enumerate(STREAMS[stream_name]()):
        got = np.asarray(det(jnp.asarray(frame)))
        want = ref_fn(frame, PARAMS)
        assert (got == want).all(), (
            f"{_cell_id(cell)} diverged on {stream_name} frame {i}"
        )


def test_override_is_visible_to_an_already_created_generator():
    """``register_backend_spec(..., override=True)`` after a
    ``conformance_cells()`` generator exists must be reflected in every
    cell not yet yielded — the generator reads the LIVE registry at yield
    time, so a materialized snapshot cannot go stale against the spec it
    claims to describe (the historical bug: an override between cell
    generation and consumption kept serving the OLD claims)."""
    from repro.core.canny.backends import _SPECS

    from repro.core.canny import BackendSpec, register_backend_spec

    name = "override-probe"
    register_backend_spec(BackendSpec(name=name, serving_fn=lambda *a: None))
    try:
        gen = conformance_cells()
        next(gen)  # the generator is live BEFORE the override lands
        register_backend_spec(
            BackendSpec(name=name, serving_fn=lambda *a: None, dist=True),
            override=True,
        )
        cells = [c for c in gen if c["backend"] == name]
        assert len(cells) == 6
        # pre-override the probe did not claim dist; the override does,
        # and the not-yet-yielded cells must say so
        assert {"backend": name, "dist": True, "mode": "cold",
                "supported": True} in cells, cells
    finally:  # the registry is process-global — leave no probe behind
        _SPECS.pop(name, None)


# ---------------- fail-fast construction (no silent fallbacks) --------------
def test_serving_requires_a_serving_entry():
    """A stage-plane-only registration (the legacy register_backend path)
    yields a capability-less spec: the engine must reject it at
    construction with the missing feature named."""
    from repro.core.canny.backends import _SPECS
    from repro.core.canny.pipeline import register_backend
    from repro.serve.engine import CannyEngine

    register_backend("stub-stage-only", lambda img, params, ctx, **_: img)
    try:
        with pytest.raises(UnsupportedFeature, match="serving"):
            CannyEngine(PARAMS, backend="stub-stage-only")
    finally:  # the registry is process-global — leave no stub behind
        _SPECS.pop("stub-stage-only", None)


def test_jnp_backend_serves_everywhere():
    """The portable backend is serving-complete too: CannyEngine with
    backend='jnp' (no Pallas anywhere) stays bit-exact on mixed sizes."""
    from repro.serve.engine import CannyEngine

    engine = CannyEngine(PARAMS, backend="jnp", bucket_multiple=32, max_batch=4)
    reqs = [synthetic_image(h, w, seed=60 + i)
            for i, (h, w) in enumerate([(33, 47), (64, 64), (21, 90)])]
    for req, edges in zip(reqs, engine.process(reqs)):
        assert (edges == canny_reference(req, PARAMS)).all()


def test_scheduler_rejects_skip_under_a_shared_mesh_detector():
    """A backend WITHOUT warm_dist ('jnp') cannot honour skip on the
    non-pod mesh farm — the shared detector would silently run cold, so
    construction must raise with the missing capability named."""
    from repro.stream import FarmScheduler

    with pytest.raises(UnsupportedFeature, match="warm_dist"):
        FarmScheduler(PARAMS, skip=True, dist=_mesh_dist(), backend="jnp")


def test_scheduler_builds_a_single_lane_warm_mesh_temporal():
    """A warm_dist backend (the default 'fused') turns the non-pod mesh
    farm into ONE sharded TemporalCanny on ONE worker lane (concurrent
    shard_map launches would deadlock the collectives) — and the stream
    stays bit-identical to the serial reference."""
    from repro.stream import FarmScheduler

    sched = FarmScheduler(
        PARAMS, skip=True, dist=_mesh_dist(), block_rows=16
    )
    assert len(sched.farm.workers) == 1
    assert len(sched.detectors) == 1
    assert not sched.detectors[0].dist.is_local
    frames = all_static(frames=3)
    for i, edges in enumerate(sched.run(iter(frames))):
        assert (edges == canny_reference(frames[i], PARAMS)).all(), i
    assert sched.detectors[0].cost_totals()["frames"] == 3


def test_pod_worker_rejects_skip_on_a_mesh_rank():
    from repro.stream import PodCtx, PodWorker

    with pytest.raises(UnsupportedFeature, match="warm_dist"):
        PodWorker(
            PodCtx(0, 2), PARAMS, dist=_mesh_dist(), skip=True,
            backend="jnp",
        )


def test_pod_worker_builds_a_warm_mesh_temporal():
    """With a warm_dist backend the mesh rank gets a stateful sharded
    TemporalCanny (w.temporal set), not the stateless cold fallback."""
    from repro.stream import PodCtx, PodWorker

    w = PodWorker(
        PodCtx(0, 2), PARAMS, dist=_mesh_dist(), skip=True, block_rows=16
    )
    assert w.temporal is not None
    assert not w.temporal.dist.is_local


def test_stage_plane_mesh_requires_stage_dist():
    """pallas/fused distribute through their serving entry only: asking
    for their stage plane (bucket_multiple=None) under a mesh must fail
    at construction, not at trace time."""
    for name in ("pallas", "fused"):
        with pytest.raises(UnsupportedFeature, match="serving entry"):
            make_canny(PARAMS, _mesh_dist(), backend=name, bucket_multiple=None)


# ---------------- skip-path cost assertions ---------------------------------
def _frontend_launches_per_frame(name: str) -> int:
    """Measured, not assumed: frame 0 of a fresh stream reports how many
    front-end launches one full recompute costs (1 fused, 3 per-stage)."""
    det = TemporalCanny(PARAMS, warm=True, skip=True, backend=name, block_rows=16)
    cost = det.step(jnp.asarray(all_static(frames=1)[0]))[1]
    return int(cost[2])


@pytest.mark.parametrize("name", SKIP_BACKENDS)
def test_warm_skip_static_stream_saves_frontend_launches(name):
    """All-static: every frame after the first skips the whole front-end
    (0 launches, 0 recomputed strips) and converges in one verifying
    hysteresis sweep with zero productive dilations — the SAME savings
    counters on every backend, per-stage included (acceptance criterion)."""
    det = TemporalCanny(PARAMS, warm=True, skip=True, backend=name, block_rows=16)
    costs = [det.step(jnp.asarray(f))[1] for f in all_static(frames=5)]
    tot = det.cost_totals()
    assert tot["frontend_launches"] == int(costs[0][2]), tot
    for cost in costs[1:]:
        launches, dilations = int(cost[0]), int(cost[1])
        fe_launches = int(cost[2]) if len(cost) > 2 else 1
        fe_strips = int(cost[3]) if len(cost) > 3 else 0
        assert fe_launches == 0 and fe_strips == 0
        assert launches == 1 and dilations == 0


def test_per_stage_static_savings_match_fused():
    """The acceptance row, explicitly: on a static stream the per-stage
    warm+skip path reports bit-identical per-frame cost tuples to fused
    from frame 1 on — (1 verify launch, 0 dilations, 0 front-end
    launches, 0 recomputed strips)."""
    costs = {}
    for name in ("pallas", "fused"):
        det = TemporalCanny(PARAMS, warm=True, skip=True, backend=name, block_rows=16)
        costs[name] = [
            tuple(int(c) for c in det.step(jnp.asarray(f))[1])
            for f in all_static(frames=5)
        ]
    assert costs["pallas"][1:] == costs["fused"][1:]
    assert all(c == (1, 0, 0, 0) for c in costs["fused"][1:])


@pytest.mark.parametrize(
    "name", [s.name for s in backend_specs() if s.warm_dist and s.skip]
)
def test_warm_mesh_launch_parity_on_static_stream(name):
    """Launch-count parity, sharded vs local: from frame 1 on, a static
    stream costs the SAME per-frame tuple (1 verify launch, 0 dilations,
    0 front-end launches, 0 recomputed strips) whether the temporal state
    lives locally or sharded with the mesh — the sharded skip gate and
    consensus counters add no hidden work. Frame 0 is excluded: the
    sharded row grid may pad to a different strip count (documented on
    ``fused_canny_warm_skip``), so only the steady state is comparable."""
    det_m = TemporalCanny(
        PARAMS, warm=True, skip=True, backend=name, block_rows=16,
        dist=_mesh_dist(),
    )
    det_l = TemporalCanny(PARAMS, warm=True, skip=True, backend=name, block_rows=16)
    costs_m, costs_l = [], []
    for f in all_static(frames=5):
        costs_m.append(tuple(int(c) for c in det_m.step(jnp.asarray(f))[1]))
        costs_l.append(tuple(int(c) for c in det_l.step(jnp.asarray(f))[1]))
    assert costs_m[1:] == costs_l[1:]
    assert all(c == (1, 0, 0, 0) for c in costs_m[1:])


@pytest.mark.parametrize("name", SKIP_BACKENDS)
def test_warm_skip_changing_stream_never_skips(name):
    det = TemporalCanny(PARAMS, warm=True, skip=True, backend=name, block_rows=16)
    frames = all_changing(frames=4)
    per_frame = _frontend_launches_per_frame(name)
    for frame in frames:
        det.step(jnp.asarray(frame))
    tot = det.cost_totals()
    assert tot["frontend_launches"] == per_frame * len(frames), tot


@pytest.mark.parametrize("name", STRIP_SKIP_BACKENDS)
def test_warm_skip_flicker_recomputes_only_touched_strips(name):
    """The flicker pixel sits in one 16-row strip; with its stage halo it
    can dirty at most the two neighbouring strips per stage launch. Every
    other strip must come from the stored front-end output — on the
    per-stage path this holds PER STAGE (each stage its own mask)."""
    det = TemporalCanny(PARAMS, warm=True, skip=True, backend=name, block_rows=16)
    frames = single_pixel_flicker(frames=5, h=48, w=64)
    n_strips = 48 // 16
    per_frame = _frontend_launches_per_frame(name)
    for frame in frames:
        det.step(jnp.asarray(frame))
    tot = det.cost_totals()
    full = len(frames) * n_strips * per_frame
    assert 0 < tot["frontend_strips"] < full, tot
    # frame 0 pays all strips of every stage launch; later frames pay only
    # the ≤2 strips per launch whose halo sees the flicker pixel
    bound = per_frame * (n_strips + (len(frames) - 1) * 2)
    assert tot["frontend_strips"] <= bound, tot


def test_jnp_warm_skip_static_stream_saves_frontend_launches():
    det = TemporalCanny(PARAMS, warm=True, skip=True, backend="jnp")
    for frame in all_static(frames=4):
        det.step(jnp.asarray(frame))
    tot = det.cost_totals()
    assert tot["frontend_launches"] == 1, tot


def test_skip_requires_warm():
    with pytest.raises(ValueError, match="skip"):
        TemporalCanny(PARAMS, warm=False, skip=True)


def test_over_claiming_spec_fails_loudly():
    """A spec that claims a feature its backend cannot deliver is caught
    by the matrix contract: require() passes (the claim), so the cell
    RUNS — meaning a bogus claim surfaces as a hard failure, not a skip.
    Here: claims are internally consistent for all shipped specs."""
    for spec in backend_specs():
        if spec.skip:
            assert spec.warm, f"{spec.name}: skip without warm is incoherent"
        if spec.temporal_fn is None:
            assert not (spec.warm or spec.skip), spec.name
        if spec.warm_dist:
            # sharded temporal state presupposes both of its halves
            assert spec.warm and spec.dist, (
                f"{spec.name}: warm_dist without warm+dist is incoherent"
            )
