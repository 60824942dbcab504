"""Smoke of the main paths on one TPU chip, through the user entry points.

    python chip_smoke.py [--seed N]              # one chip: serve, stream, zoo
    python chip_smoke.py --four-chip [--seed N]  # four chips: the 2x2 mesh path

Phases (inputs generated from ``--seed``, nothing downloaded):

* serve  — ``AotCannyEngine`` on its default backend (fused on a TPU) over
  1080x1920 (BT.709 camera frames) and 321x481 (BSDS500) buckets, mixed
  requests through ``ContinuousBatcher``. No trace after warmup, a Pallas
  custom call in the served executable, every output equal to the jnp
  backend on the same chip, the 321x481 ones equal to ``canny_reference``.
* stream — ``FarmScheduler`` with fused warm+skip over held 1080p frames:
  every frame equal to the cold fused detector, one equal to jnp, and
  fewer front-end launches than frames.
* zoo    — one 1080p request through each classical operator's serving
  path, equal to that operator's jnp fallback.
* ``--four-chip`` runs only the mesh path (``--mesh 2x2``): the fused
  mesh detector on two 3840x2160 (BT.2020) frames and a sharded warm+skip
  stream of 1080p frames, both equal to the one-chip fused output.

The first mismatch exits nonzero with its pixel count and place; nothing
is caught. The last line of stdout is one JSON object naming the device.
Times printed along the way are smoke wall times with compilation
included: set-up figures, not metrics. Without a TPU the script exits
nonzero before any phase; ``--rehearse`` runs it at tiny sizes on any
platform (Pallas in interpret mode) to check the paths themselves.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.canny import CannyParams, canny_reference, make_canny, make_detector  # noqa: E402
from repro.core.canny.backends import default_backend  # noqa: E402
from repro.data.images import synthetic_batch, synthetic_image  # noqa: E402
from repro.kernels import common  # noqa: E402
from repro.kernels.log import log_edges_jnp  # noqa: E402
from repro.kernels.prewitt import prewitt_edges_jnp  # noqa: E402
from repro.kernels.roberts import roberts_edges_jnp  # noqa: E402
from repro.kernels.sobel import sobel_edges_jnp  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.launch.mesh import dist_from_spec  # noqa: E402
from repro.serve.admission import ContinuousBatcher  # noqa: E402
from repro.serve.aot import AotCannyEngine  # noqa: E402
from repro.stream import FarmScheduler, SyntheticStream  # noqa: E402

# (height, width) per phase: the published sizes, and the tiny shapes a
# CPU rehearsal runs (same bucket structure: an odd BSDS-like size, a
# camera-like one, a 2x larger one for the mesh).
SIZES = {
    "chip": {"camera": (1080, 1920), "bsds": (321, 481), "uhd": (2160, 3840)},
    "rehearse": {"camera": (72, 128), "bsds": (33, 49), "uhd": (144, 256)},
}


def same(what: str, got, want) -> None:
    """Exit at the first mismatch, saying how many pixels and where."""
    got, want = np.asarray(got), np.asarray(want)
    if got.shape != want.shape:
        raise SystemExit(f"MISMATCH {what}: shape {got.shape} != {want.shape}")
    bad = np.argwhere(got != want)
    if len(bad):
        lo, hi = bad.min(axis=0).tolist(), bad.max(axis=0).tolist()
        raise SystemExit(
            f"MISMATCH {what}: {len(bad)} of {got.size} pixels differ, "
            f"first at {bad[0].tolist()}, bounding box {lo}..{hi}"
        )


def phase_serve(params, sizes, seed: int, interpret: bool) -> str:
    cam, bsds = sizes["camera"], sizes["bsds"]
    engine = AotCannyEngine(params, buckets=[cam, bsds], max_batch=4)
    if engine.backend != "fused":
        raise SystemExit(f"serving resolved to {engine.backend!r}, not fused")
    rng = np.random.default_rng(seed)
    reqs = [
        synthetic_image(*(cam if i % 2 else bsds), seed=int(rng.integers(1 << 31)))
        for i in range(8)
    ]
    with ContinuousBatcher(engine, linger_ms=5.0, timeout=600.0) as batcher:
        tickets = [batcher.submit(r) for r in reqs]
        batcher.drain()
        outs = [t.result() for t in tickets]
    if engine.post_warmup_traces != 0:
        raise SystemExit(f"{engine.post_warmup_traces} traces after warmup")
    if not interpret:
        text = next(iter(engine._exe.values())).as_text()
        if "tpu_custom_call" not in text:
            raise SystemExit("served executable holds no Pallas TPU kernel")
    jnp_det = make_canny(params, backend="jnp")
    for i, (req, out) in enumerate(zip(reqs, outs)):
        same(f"serve request {i} {req.shape} vs jnp", out, jnp_det(req))
        if req.shape == bsds:
            same(f"serve request {i} {req.shape} vs numpy oracle", out,
                 canny_reference(req, params))
    return (
        f"backend={engine.backend} executables={len(engine._exe)} "
        f"requests={len(reqs)} post_warmup_traces=0"
    )


def phase_stream(params, sizes, seed: int) -> str:
    h, w = sizes["camera"]
    source = SyntheticStream(8, h, w, seed=seed, hold=4)
    sched = FarmScheduler(params, warm=True, skip=True)
    backends = {d.backend for d in sched.detectors}
    if backends != {"fused"}:
        raise SystemExit(f"stream resolved to {backends}, not fused")
    outs = list(sched.run(source))
    cold = make_canny(params, backend="fused")
    for i, edges in enumerate(outs):
        same(f"stream frame {i} vs cold fused", edges, cold(source.frame(i)))
    same("stream frame 5 vs jnp", outs[5],
         make_canny(params, backend="jnp")(source.frame(5)))
    launches, frames = sched.stats.frontend_launches, sched.stats.frames
    if not launches < frames:
        raise SystemExit(f"skip never engaged: {launches}/{frames} launches")
    return f"backend=fused frames={frames} frontend_launches={launches}"


def phase_zoo(params, sizes, seed: int) -> str:
    h, w = sizes["camera"]
    img = synthetic_image(h, w, seed=seed + 7)
    true_hw = jnp.asarray([[h, w]], jnp.int32)
    fallbacks = {
        "sobel": sobel_edges_jnp,
        "prewitt": prewitt_edges_jnp,
        "roberts": roberts_edges_jnp,
        "log": log_edges_jnp,
    }
    for op, ref in fallbacks.items():
        got = make_detector(params, op=op)(img)
        want = jax.jit(ref, static_argnums=2)(jnp.asarray(img)[None], true_hw, params)
        same(f"zoo {op} vs {ref.__name__}", got, want[0])
    return f"ops={','.join(fallbacks)} size={h}x{w}"


def phase_four_chip(params, sizes, seed: int) -> str:
    dist = dist_from_spec("2x2")
    one_chip = make_canny(params, backend="fused")

    uh, uw = sizes["uhd"]
    frames = synthetic_batch(2, uh, uw, seed=seed)
    mesh_det = make_canny(params, dist, backend="fused")
    same(f"mesh fused {frames.shape} vs one chip", mesh_det(frames),
         one_chip(frames))

    h, w = sizes["camera"]
    source = SyntheticStream(6, h, w, seed=seed, hold=3)
    sched = FarmScheduler(params, warm=True, skip=True, dist=dist)
    backends = {d.backend for d in sched.detectors}
    if backends != {"fused"}:
        raise SystemExit(f"mesh stream resolved to {backends}, not fused")
    for i, edges in enumerate(sched.run(source)):
        same(f"mesh warm+skip frame {i} vs one chip", edges,
             one_chip(source.frame(i)))
    launches, n = sched.stats.frontend_launches, sched.stats.frames
    if not launches < n:
        raise SystemExit(f"mesh skip never engaged: {launches}/{n} launches")
    return (
        f"mesh=2x2 uhd_batch=2x{uh}x{uw} stream_frames={n} "
        f"frontend_launches={launches}"
    )


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--four-chip", action="store_true",
        help="run only the 2x2 mesh path and its one-chip comparison",
    )
    ap.add_argument(
        "--rehearse", action="store_true",
        help="tiny sizes on any platform (CPU: Pallas interpret mode)",
    )
    args = ap.parse_args()

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        print(f"no TPU: JAX found {dev.platform}", file=sys.stderr)
        raise SystemExit(2)
    cache_dir = use_compile_cache()
    interpret = common.default_interpret()
    if interpret != (dev.platform != "tpu"):
        raise SystemExit(f"interpret={interpret} on {dev.platform}")
    sizes = SIZES["rehearse" if args.rehearse else "chip"]
    params = CannyParams()
    print(f"jax {jax.__version__} on {dev.platform} ({dev.device_kind}) "
          f"x{len(jax.devices())}")
    print(f"default backend: detectors {default_backend('jnp')}, serving and "
          f"streams {default_backend('fused')}; interpret: {interpret}")
    print(f"compile cache: {cache_dir}")

    if args.four_chip:
        if len(jax.devices()) < 4:
            raise SystemExit(f"--four-chip needs 4 devices, have {len(jax.devices())}")
        phases = [("four-chip", lambda: phase_four_chip(params, sizes, args.seed))]
    else:
        phases = [
            ("serve", lambda: phase_serve(params, sizes, args.seed, interpret)),
            ("stream", lambda: phase_stream(params, sizes, args.seed)),
            ("zoo", lambda: phase_zoo(params, sizes, args.seed)),
        ]
    for name, run in phases:
        t0 = time.perf_counter()
        detail = run()
        print(f"phase {name}: OK {detail} "
              f"(smoke wall time incl. compile: {time.perf_counter() - t0:.1f} s)",
              flush=True)

    print(json.dumps({
        "ok": True,
        "device": {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": len(jax.devices()),
        },
    }))


if __name__ == "__main__":
    main()
