"""Register the Pallas execution backends with the core Canny pipeline.

backend="pallas" — per-stage kernels (paper-faithful stage structure,
                   each stage one HBM round-trip; kernels/staged.py)
backend="fused"  — single-pass front-end + hysteresis kernel
                   (beyond-paper; ~5× less HBM traffic)

Both register complete ``BackendSpec``s — dist, warm, and skip on every
stage path: a non-local ``Dist`` runs the same batch-grid kernels inside
``shard_map`` (batch over the data axes, rows over the space axis via
ppermute halo exchange — per-stage halos exchanged BETWEEN launches on
the staged path; DESIGN.md §8/§10), and the temporal plane threads the
packed warm-seed fixpoint plus the static-strip front-end skip through
one shared ``PackedTemporal`` state machine — locally or with the state
sharded across the mesh (``warm_dist``; DESIGN.md §14). The two backends differ
only in their front-end step functions; everything else — capabilities
included — is declared, not special-cased.
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

from repro.core.canny.backends import BackendSpec, register_backend_spec
from repro.core.canny.params import CannyParams
from repro.core.patterns.dist import LOCAL, Dist, StencilCtx, on_device_of
from repro.kernels import common
from repro.kernels.gaussian.ops import gaussian_blur
from repro.kernels.sobel.ops import sobel
from repro.kernels.nms.ops import nms
from repro.kernels.hysteresis.ops import hysteresis_from_masks
from repro.kernels.fused_canny.ops import (
    _shard_grid,
    fused_canny,
    fused_canny_warm,
    fused_canny_warm_skip,
    fused_frontend,
)
from repro.kernels.staged import (
    staged_canny,
    staged_canny_warm,
    staged_canny_warm_skip,
)


def _require_local(ctx: StencilCtx, name: str) -> None:
    if ctx.axis_name is not None:
        raise NotImplementedError(
            f"canny backend {name!r} is shard-local inside the stage plane; "
            "mesh execution routes through the serving entry "
            "(make_canny(dist=...) / CannyEngine(dist=...)) or backend='jnp'"
        )


def _staged(img: jax.Array, params: CannyParams, ctx: StencilCtx, **_):
    _require_local(ctx, "pallas")
    blur = gaussian_blur(img, sigma=params.sigma, radius=params.radius)
    mag, dirs = sobel(blur, l2_norm=params.l2_norm)
    s = nms(mag, dirs)
    return hysteresis_from_masks(s >= params.high, s >= params.low)


def _fused(img: jax.Array, params: CannyParams, ctx: StencilCtx, **_):
    _require_local(ctx, "fused")
    code = fused_frontend(
        img,
        sigma=params.sigma,
        radius=params.radius,
        low=params.low,
        high=params.high,
        l2_norm=params.l2_norm,
        emit="code",
    )
    return hysteresis_from_masks(code >= 2, code >= 1)


def _params_kw(params: CannyParams) -> dict:
    return dict(
        sigma=params.sigma,
        radius=params.radius,
        low=params.low,
        high=params.high,
        l2_norm=params.l2_norm,
    )


def _fused_serving(
    imgs: jax.Array,
    true_hw: jax.Array,
    params: CannyParams,
    interpret: bool | None = None,
    dist: Dist = LOCAL,
) -> jax.Array:
    """True-size-aware fused path for the bucketed serving layer: border
    math anchors at per-image (h, w), so bucket padding is bit-exact.
    ``dist`` places the bucket batch on a mesh — the same kernels run
    inside shard_map, bit-identical to the local path."""
    return fused_canny(
        imgs.astype(jnp.float32),
        interpret=interpret,
        true_hw=true_hw,
        dist=dist,
        **_params_kw(params),
    )


def _staged_serving(
    imgs: jax.Array,
    true_hw: jax.Array,
    params: CannyParams,
    interpret: bool | None = None,
    dist: Dist = LOCAL,
) -> jax.Array:
    """The SAME serving contract on the per-stage path: true-size border
    anchoring lives in the sobel kernel, so bucket padding stays
    bit-exact; a non-local ``dist`` runs all four stages inside one
    shard_map with per-stage halo exchanges."""
    return staged_canny(
        imgs.astype(jnp.float32),
        interpret=interpret,
        true_hw=true_hw,
        dist=dist,
        **_params_kw(params),
    )


# -- temporal plane: one state machine, per-backend step fns -----------------
def _fused_warm_step(x, strong_w, weak_w, edges_w, **kw):
    return fused_canny_warm(x, strong_w, weak_w, edges_w, **kw)


def _fused_warm_skip_step(x, prev_frame, fe, strong_w, weak_w, edges_w, have, **kw):
    # the fused front-end's reusable output IS the packed word state, so
    # its extra front-end state tuple is empty
    del fe
    edges, (s_w, wk_w, packed, frame), cost = fused_canny_warm_skip(
        x, prev_frame, strong_w, weak_w, edges_w, have, **kw
    )
    return edges, (), (s_w, wk_w, packed), frame, cost


def _staged_warm_skip_step(x, prev_frame, fe, strong_w, weak_w, edges_w, have, **kw):
    return staged_canny_warm_skip(
        x, prev_frame, *fe, strong_w, weak_w, edges_w, have, **kw
    )


def _staged_zero_fe(b: int, hp: int, wp: int):
    return (
        jnp.zeros((b, hp, wp), jnp.float32),  # blur
        jnp.zeros((b, hp, wp), jnp.float32),  # sobel magnitude
        jnp.zeros((b, hp, wp), jnp.uint8),  # sobel direction bins
        jnp.zeros((b, hp, wp), jnp.float32),  # NMS suppressed magnitude
    )


_STEP_CACHE: dict = {}


def _make_step_fn(warm_step, warm_skip_step, skip, donate, kw_items):
    """MODULE-level jitted-step cache, keyed by (backend step fns, skip,
    donation, static params + geometry). Temporal state machines are
    created per stream; if each built its own ``jax.jit`` wrapper, every
    fresh stream would retrace a program some earlier stream already
    compiled — a per-stream compile tax big enough to flip the warm+skip
    economics at small frame sizes. Sharing the wrapper restores the
    compile-once behaviour of the underlying kernel entry points."""
    key = (warm_step, warm_skip_step, skip, donate, kw_items)
    fn = _STEP_CACHE.get(key)
    if fn is not None:
        return fn
    kw = dict(kw_items)
    if skip:

        def run(x, prev_frame, fe, s_w, wk_w, e_w, have, true_hw):
            return warm_skip_step(
                x, prev_frame, fe, s_w, wk_w, e_w, have, true_hw=true_hw,
                **kw,
            )

        fn = jax.jit(run, donate_argnums=(1, 2, 3, 4, 5) if donate else ())
    else:

        def run(x, s_w, wk_w, e_w, true_hw):
            return warm_step(x, s_w, wk_w, e_w, true_hw=true_hw, **kw)

        fn = jax.jit(run, donate_argnums=(1, 2, 3) if donate else ())
    _STEP_CACHE[key] = fn
    return fn


class PackedTemporal:
    """Temporal state machine shared by every packed-words backend.

    Owns the per-stream device state — the packed (strong, weak, edges)
    words, and in skip mode the previous (padded) frame plus whatever
    front-end outputs the backend reuses (``zero_fe``) — and drives the
    backend's jitted step functions. Inputs are (b, h, w) f32; widths pad
    to a multiple of 32 with edge cols (bit-exact: the kernels anchor at
    ``true_hw``). ``warm=False`` keeps the zero state so every frame runs
    the cold seed — the answer must not change, only the cost counters.

    The hot loop is host-free: the state, the skip gate (``have_prev``)
    and the true-size table are made once per reset, on the device of the
    frame that opens the stream (or with the mesh's sharding), so a
    stream pinned to chip k never touches the default device; the skip
    DECISION is a traced ``lax.cond`` inside the step program, and in
    warm mode the threaded
    state buffers (packed words, stored frame, front-end outputs) are
    DONATED to the step — on donation-capable platforms (TPU/GPU; the
    default gate) each stream updates its state in place instead of
    allocating fresh HBM every frame. ``donate=None`` auto-selects by
    platform (CPU ignores donation, harmlessly).

    A non-local ``dist`` shards the WHOLE state plane with the mesh: the
    state buffers allocate at the sharded-grid padded height (rows split
    over the space axis inside the step's shard_map) and the batch pads
    to a multiple of the data-axis size with zero frames (static after
    frame 0, cropped from the returned edges). Donation and the step
    cache are unchanged — ``dist`` is just one more static key.
    """

    def __init__(
        self,
        params: CannyParams,
        warm: bool,
        skip: bool,
        block_rows: int | None,
        interpret: bool | None,
        warm_step,
        warm_skip_step,
        zero_fe,
        donate: bool | None = None,
        dist: Dist = LOCAL,
    ):
        if dist.pod_axis is not None:
            raise ValueError(
                "temporal state machines never see the pod axis — build "
                "per-rank detectors via Dist.pod_slice (stream/pod.py)"
            )
        self.params = params
        self.warm = warm
        self.skip = skip
        self.block_rows = block_rows
        self.interpret = interpret
        self.dist = dist
        self._warm_step = warm_step
        self._warm_skip_step = warm_skip_step
        self._zero_fe = zero_fe
        if donate is None:
            donate = jax.devices()[0].platform in ("tpu", "gpu")
        self.donate = bool(donate)
        self._steps: dict = {}
        self.reset()

    def reset(self) -> None:
        self._state = None
        self._fe = None
        self._prev_frame = None
        self._have_prev = None
        self._have_true = None
        self._true_hw = None

    def _step_fn(self, bh: int):
        """One jitted step per (skip, block geometry), resolved through
        the module-level cache (shared across instances): closes over the
        static params and, in warm mode, donates the threaded state args —
        the gate scalar and ``true_hw`` are deliberately NOT donated (they
        persist across frames)."""
        key = (self.skip, bh)
        fn = self._steps.get(key)
        if fn is not None:
            return fn
        p = self.params
        kw_items = (
            ("sigma", p.sigma),
            ("radius", p.radius),
            ("low", p.low),
            ("high", p.high),
            ("l2_norm", p.l2_norm),
            ("block_rows", bh),
            ("interpret", self.interpret),
            ("dist", self.dist),  # hashable (frozen dataclass) → static
        )
        fn = _make_step_fn(
            self._warm_step,
            self._warm_skip_step,
            self.skip,
            self.donate and self.warm,
            kw_items,
        )
        self._steps[key] = fn
        return fn

    def step(self, x: jax.Array):
        b, h, w = x.shape
        p = self.params
        if self.dist.is_local:
            bh = self.block_rows or common.pick_block_rows(
                h, min_rows=p.radius + 2
            )
            hp = -(-h // bh) * bh
            bp = b
        else:
            # state allocates at the SHARDED grid's padded height (rows
            # pad to a multiple of space_size * block_rows, see
            # _shard_grid) and the batch pads to the data-axis multiple
            hp, _, bh = _shard_grid(h, self.dist, p.radius + 2, self.block_rows)
            dsz = self.dist.batch_size()
            bp = -(-b // dsz) * dsz
        wp = -(-w // 32) * 32
        if wp != w:  # edge cols + the true-size table keep this bit-exact
            x = jnp.pad(x, ((0, 0), (0, 0), (0, wp - w)), mode="edge")
        if bp != b:
            # zero pad frames: static after frame 0 (no sweeps, no strips,
            # consensus counters unaffected), cropped from the edges below
            x = jnp.pad(x, ((0, bp - b), (0, 0), (0, 0)))
        if self._state is None:
            # three DISTINCT zero buffers: donation rejects the same buffer
            # appearing under two donated arguments. Locally the state is
            # made on the frame's own device (a session on chip k keeps
            # it there). Under a mesh it is placed with the SAME
            # NamedSharding the step's out_specs produce — otherwise frame
            # 0 (default-sharded zeros) and frame 1 (sharded step outputs)
            # present different input shardings and jit silently compiles
            # the whole step twice
            if self.dist.is_local:
                where, shard = on_device_of(x), lambda v: v
            else:
                sharding = jax.sharding.NamedSharding(
                    self.dist.mesh, self.dist.batch_spec()
                )
                where = contextlib.nullcontext()
                shard = lambda v: jax.device_put(v, sharding)  # noqa: E731
            with where:
                self._state = tuple(
                    shard(jnp.zeros((bp, hp, wp // 32), jnp.uint32))
                    for _ in range(3)
                )
                self._prev_frame = shard(jnp.zeros((bp, hp, wp), jnp.float32))
                self._fe = jax.tree_util.tree_map(
                    shard, self._zero_fe(bp, hp, wp)
                )
                # device-resident gate and true-size table: made once per
                # reset, no transfer and no eager op per frame
                self._have_prev = jnp.zeros((), bool)
                self._have_true = jnp.ones((), bool)
                self._true_hw = jnp.broadcast_to(
                    jnp.asarray([h, w], jnp.int32), (bp, 2)
                )
        step_fn = self._step_fn(bh)
        if self.skip:
            edges, fe, state, frame, cost = step_fn(
                x, self._prev_frame, self._fe, *self._state,
                self._have_prev, self._true_hw,
            )
            if self.warm:
                self._fe = fe
                self._prev_frame = frame
                self._have_prev = self._have_true
        else:
            edges, state, cost = step_fn(x, *self._state, self._true_hw)
        if self.warm:
            self._state = tuple(state)
        edges = edges[..., :w]
        return (edges[:b] if bp != b else edges), cost


def _fused_temporal(params, *, warm=True, skip=False, block_rows=None,
                    interpret=None, donate=None, dist=LOCAL):
    return PackedTemporal(
        params, warm, skip, block_rows, interpret,
        _fused_warm_step, _fused_warm_skip_step, lambda b, hp, wp: (),
        donate=donate, dist=dist,
    )


def _staged_temporal(params, *, warm=True, skip=False, block_rows=None,
                     interpret=None, donate=None, dist=LOCAL):
    return PackedTemporal(
        params, warm, skip, block_rows, interpret,
        staged_canny_warm, _staged_warm_skip_step, _staged_zero_fe,
        donate=donate, dist=dist,
    )


# the operator zoo (sobel_op/prewitt/roberts/log_op) registers alongside
# the Canny backends — one lazy kernel import brings in the whole zoo
import repro.kernels.operator_backends  # noqa: E402,F401  (registers)

register_backend_spec(
    BackendSpec(
        name="pallas",
        stage_fn=_staged,
        serving_fn=_staged_serving,
        temporal_fn=_staged_temporal,
        dist=True,
        warm=True,
        skip=True,
        warm_dist=True,
    )
)
register_backend_spec(
    BackendSpec(
        name="fused",
        stage_fn=_fused,
        serving_fn=_fused_serving,
        temporal_fn=_fused_temporal,
        dist=True,
        warm=True,
        skip=True,
        warm_dist=True,
    )
)
