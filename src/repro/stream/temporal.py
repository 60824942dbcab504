"""Temporal warm-start Canny — per-stream state threading between frames.

``TemporalCanny`` is the stateful frame detector the streaming subsystem
schedules: each call runs one frame (or frame batch) and threads the
previous frame's state into the next frame's hysteresis fixpoint as a
warm seed. The seed is gated by the grow-only monotonicity check
(``core.canny.hysteresis.warm_seed``), so the output is bit-identical to
the cold detector on EVERY frame — warm-start changes only how many
sweeps the fixpoint needs (~1 on static/grow-only frames). ``warm=False``
turns the threading off for correctness comparisons; the answer must not
change, only the sweep counts.

``skip=True`` additionally carries the previous FRAME and the previous
front-end outputs, so provably-static input is never recomputed
(DESIGN.md §9): the fused backend runs the strip-mask kernel path, the
per-stage "pallas" backend runs it PER STAGE (each stage its own static
mask and launch skip — ``kernels/staged.py``), and the jnp backend
carries the previous frame's NMS magnitudes, reusing them when the whole
frame is unchanged. All are exact by purity — identical input rows ⇒
identical front-end output — so edges stay bit-identical to cold on
every frame; only the ``frontend_launches``/``frontend_strips`` cost
counters move.

Backends resolve through the ``BackendSpec`` registry: the spec's
``temporal_fn`` builds the state machine (``PackedTemporal`` for the
Pallas backends, ``JnpTemporal`` below for the portable fallback), and
capability validation happens at CONSTRUCTION — asking a backend for
warm/skip (or a non-local ``dist``) it does not declare raises
``UnsupportedFeature`` before any frame runs.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.canny.backends import backend_spec, default_backend
from repro.core.canny.hysteresis import (
    double_threshold,
    hysteresis_fixpoint_count,
    warm_seed,
)
from repro.core.canny.params import CannyParams
from repro.core.patterns.dist import LOCAL, Dist, StencilCtx, on_device_of


class JnpTemporal:
    """The portable temporal plane: plain-JAX stages + seeded bool
    fixpoint. Skip mode carries the previous frame's NMS magnitudes; the
    jnp stages have no strip structure, so the skip decision is
    whole-frame — an unchanged frame reuses them inside ``lax.cond`` (the
    front-end never executes: 0 launches) and everything downstream is
    bit-identical by purity."""

    def __init__(self, params: CannyParams, *, warm=True, skip=False,
                 block_rows=None, interpret=None, donate=None, dist=LOCAL):
        del block_rows, interpret  # no strip grid / Pallas on this path
        if not dist.is_local:
            # defensive: the jnp spec does not claim warm_dist, so the
            # registry rejects this before construction — keep the state
            # machine itself honest should that gate ever be bypassed
            from repro.core.canny.backends import UnsupportedFeature

            raise UnsupportedFeature(
                "backend 'jnp' keeps its temporal state worker-local; "
                "sharded warm state needs a warm_dist backend "
                "('fused'/'pallas')"
            )
        self.params = params
        self.warm = warm
        self.skip = skip
        if donate is None:
            donate = jax.devices()[0].platform in ("tpu", "gpu")
        self.donate = bool(donate) and warm
        self._step = self._make_step()
        self.reset()

    def reset(self) -> None:
        self._state = None
        self._prev_frame = None
        self._prev_nms = None
        self._have_prev = None
        self._have_true = None

    def _make_step(self) -> Callable:
        from repro.core.canny.gaussian import gaussian_stage
        from repro.core.canny.nms import nms_stage
        from repro.core.canny.sobel import sobel_stage

        params, ctx = self.params, StencilCtx(None, "edge")

        def frontend(imgs):
            blur = gaussian_stage(imgs, ctx, params)
            mag, dirs = sobel_stage(blur, ctx, params)
            return nms_stage(mag, dirs, ctx)

        donated = (1, 2, 3) if self.donate else ()
        if not self.skip:

            @functools.partial(jax.jit, donate_argnums=donated)
            def step(imgs, prev_strong, prev_weak, prev_edges):
                sup = frontend(imgs)
                strong, weak = double_threshold(sup, params)
                seed = warm_seed(strong, weak, prev_strong, prev_weak, prev_edges)
                edges, n = hysteresis_fixpoint_count(strong, weak, ctx, seed=seed)
                return edges, (strong, weak, edges.astype(bool)), (n, n - 1)

            return step

        # prev_frame is the CALLER's frame array (stored by reference), so it
        # is never donated — only buffers this state machine itself produced
        donated = (2, 3, 4, 5) if self.donate else ()

        @functools.partial(jax.jit, donate_argnums=donated)
        def step_skip(imgs, prev_frame, prev_nms, prev_s, prev_w, prev_e, have):
            same = have & jnp.all(imgs == prev_frame)
            sup, fe = lax.cond(
                same,
                lambda _: (prev_nms, jnp.int32(0)),
                lambda _: (frontend(imgs), jnp.int32(1)),
                None,
            )
            strong, weak = double_threshold(sup, params)
            seed = warm_seed(strong, weak, prev_s, prev_w, prev_e)
            edges, n = hysteresis_fixpoint_count(strong, weak, ctx, seed=seed)
            state = (strong, weak, edges.astype(bool))
            return edges, sup, state, (n, n - 1, fe, fe)

        return step_skip

    def step(self, x: jax.Array):
        b, h, w = x.shape
        if self._state is None:
            # made on the frame's device; distinct zero buffers: donated
            # args must not share a buffer; the device-resident gate: one
            # transfer per reset, none per frame
            with on_device_of(x):
                self._state = tuple(jnp.zeros((b, h, w), bool) for _ in range(3))
                self._prev_frame = jnp.zeros((b, h, w), jnp.float32)
                self._prev_nms = jnp.zeros((b, h, w), jnp.float32)
                self._have_prev = jnp.zeros((), bool)
                self._have_true = jnp.ones((), bool)
        if self.skip:
            edges, nms, state, cost = self._step(
                x, self._prev_frame, self._prev_nms, *self._state,
                self._have_prev,
            )
            if self.warm:
                self._prev_frame, self._prev_nms = x, nms
                self._have_prev = self._have_true
        else:
            edges, state, cost = self._step(x, *self._state)
        if self.warm:
            self._state = tuple(state)
        return edges, cost


class TemporalCanny:
    """Stateful streaming detector: cold-exact edges + warm sweep counts.

    ``step`` maps an (h, w) or (b, h, w) frame to (edges, cost) where
    ``cost = (launches, dilations)`` int32 device scalars (see
    ``packed_fixpoint_count``; the jnp path reports its sweep count as
    both launches and productive dilations-1), extended by
    ``(frontend_launches, frontend_strips)`` in skip mode (and on the
    per-stage backend, whose front-end is 3 launches/frame). State resets
    whenever the input shape changes; ``reset()`` forces the next frame
    cold.

    A non-local ``dist`` keeps the temporal state SHARDED with the mesh:
    the spec must claim ``warm_dist`` (validated at construction) and the
    state machine's step runs inside ``shard_map`` with the same halo
    exchange and consensus joins as the cold mesh detector — edges,
    state and cost counters all bit-identical to the local stream.

    The backend resolves through the ``BackendSpec`` registry and its
    warm/skip (and ``dist``) capabilities are validated here, at
    construction — no backend-name ``if`` chains, no silent fallbacks.
    """

    def __init__(
        self,
        params: CannyParams = CannyParams(),
        warm: bool = True,
        backend: str | None = None,
        block_rows: int | None = None,
        interpret: bool | None = None,
        skip: bool = False,
        dist: Dist = LOCAL,
        donate: bool | None = None,
    ):
        if skip and not warm:
            raise ValueError(
                "skip=True needs warm=True: the front-end skip reuses the "
                "threaded per-frame state"
            )
        self.backend = backend or default_backend("fused")
        spec = backend_spec(self.backend).require(
            temporal=True, warm=warm, skip=skip
        )
        if not dist.is_local:
            # sharded temporal state: the spec must claim warm_dist (the
            # registry raises UnsupportedFeature naming the warm+dist
            # cell otherwise) and the state machine threads dist through
            # to the sharded step entries
            spec.require(dist=True, warm=warm, skip=skip)
        self.params = params
        self.warm = warm
        self.skip = skip
        self.block_rows = block_rows
        self.interpret = interpret
        self.dist = dist
        self.donate = donate
        self._impl = spec.temporal_fn(
            params, warm=warm, skip=skip, block_rows=block_rows,
            interpret=interpret, donate=donate, dist=dist,
        )
        self._shape: tuple[int, int, int] | None = None
        self._cost_log: list = []  # device scalars; folded lazily so the
        self._cost_done = [0, 0, 0, 0, 0]  # hot loop never blocks on a sync

    # -- state plane ---------------------------------------------------------
    def reset(self) -> None:
        """Force the next frame cold: drop the device state AND the
        host-side shape latch (a stale latch would let a same-shaped
        stream skip the reset path) and fold any pending cost scalars so
        a reset stream never leaves unsynced device references behind."""
        self._impl.reset()
        self._shape = None
        self._fold_costs()

    # -- frame plane ---------------------------------------------------------
    def step(self, frame: jax.Array):
        x = jnp.asarray(frame, jnp.float32)
        squeeze = x.ndim == 2
        if squeeze:
            x = x[None]
        if x.ndim != 3:
            raise ValueError(f"expected (h,w) or (b,h,w), got {frame.shape}")
        if self._shape != x.shape:
            self.reset()
        try:
            edges, cost = self._impl.step(x)
        except BaseException:
            # commit the shape latch only AFTER a successful step: a step
            # that died mid-flight may have partially threaded (or, under
            # donation, invalidated) the impl state, and a committed latch
            # would let the NEXT same-shaped frame run against it
            self.reset()
            raise
        self._shape = x.shape
        self._cost_log.append(cost)
        if len(self._cost_log) >= 1024:  # bound the pending-scalar window
            self._fold_costs()
        return (edges[0] if squeeze else edges), cost

    def __call__(self, frame: jax.Array) -> jax.Array:
        return self.step(frame)[0]

    # -- stats plane ---------------------------------------------------------
    def _fold_costs(self) -> None:
        log, self._cost_log = self._cost_log, []
        if not log:
            return
        self._cost_done[0] += len(log)
        # ONE batched transfer for the whole window: per-scalar int()
        # casts would block on up to 1024×4 separate device syncs
        for c in jax.device_get([tuple(c) for c in log]):
            self._cost_done[1] += int(c[0])
            self._cost_done[2] += int(c[1])
            # without an explicit counter, every frame is exactly one
            # front-end launch (the fused cold/warm path)
            self._cost_done[3] += int(c[2]) if len(c) > 2 else 1
            self._cost_done[4] += int(c[3]) if len(c) > 3 else 0

    def cost_totals(self) -> dict[str, int]:
        """Cumulative (synced) fixpoint + front-end cost over all frames.

        ``frontend_strips`` counts recomputed (image, strip) tiles and is
        reported by the skip mode only (0 otherwise).
        """
        self._fold_costs()
        frames, launches, dilations, fe_launches, fe_strips = self._cost_done
        return {
            "frames": frames,
            "launches": launches,
            "dilations": dilations,
            "frontend_launches": fe_launches,
            "frontend_strips": fe_strips,
        }
