"""Canny launcher — the paper's application, through the GCP layers.

``python -m repro.launch.canny_run --height 512 --width 512 --batch 4``
Shell (plan) → Kernel (compile) → Core (devices); prints the plan and
writes PGM outputs.
"""

from __future__ import annotations

import argparse
import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.canny import CannyParams
from repro.core.canny.golden_circle import compile_plan, plan
from repro.launch.compile_cache import use_compile_cache
from repro.data.images import save_pgm, synthetic_batch


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--height", type=int, default=512)
    ap.add_argument("--width", type=int, default=512)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--sigma", type=float, default=1.4)
    ap.add_argument("--low", type=float, default=0.08)
    ap.add_argument("--high", type=float, default=0.2)
    ap.add_argument("--backend", default=None, choices=[None, "jnp", "pallas", "fused"])
    ap.add_argument("--out-dir", default="canny_out")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    use_compile_cache()

    params = CannyParams(sigma=args.sigma, low=args.low, high=args.high)
    p = plan(args.batch, args.height, args.width, params, mesh=None, backend=args.backend)
    print(p.describe())
    detector = compile_plan(p)

    imgs = synthetic_batch(args.batch, args.height, args.width, seed=args.seed)
    t0 = time.perf_counter()
    edges = np.asarray(detector(jnp.asarray(imgs)))
    dt = time.perf_counter() - t0
    mpx = args.batch * args.height * args.width / 1e6
    print(f"{mpx:.2f} MPx in {dt*1e3:.1f} ms → {mpx/dt:.2f} MPx/s (incl. compile)")

    out = pathlib.Path(args.out_dir)
    out.mkdir(exist_ok=True)
    for i in range(args.batch):
        save_pgm(str(out / f"input_{i}.pgm"), imgs[i])
        save_pgm(str(out / f"edges_{i}.pgm"), edges[i] * 255)
    print(f"wrote {2*args.batch} PGMs to {out}/")


if __name__ == "__main__":
    main()
