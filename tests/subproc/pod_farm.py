"""Multi-host pod farm harness — N real JAX processes, one per pod rank.

Two faces, one file:

  * **orchestrator** (no ``--rank``): computes the single-host reference
    stream, checks the IN-PROCESS pod farm (``FarmScheduler`` over
    pod-axis meshes — thread pods driving per-rank ``Dist.pod_slice``
    detectors), then FORKS one JAX process per pod rank and reassembles
    their rank-tagged outputs — proving the multi-host farm emits frames
    bit-identical and in order vs one host, and that the warm+skip path
    converges with fewer front-end launches on held (static) frames.
  * **rank child** (``--rank R --pods P``): what a real host would run —
    derives its strided slice of the deterministic source, processes it
    with its own detector (local warm+skip ``TemporalCanny``, or a
    DATAxMODEL shard_map detector with ``--mesh``), and writes
    rank-tagged results. No coordination with siblings whatsoever: the
    frame→rank map is a pure function of the sequence number.

Run via tests/test_pod_farm.py (which forces the virtual device count)
or the CI pod-farm smoke.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import subprocess
import sys
import tempfile

assert "xla_force_host_platform_device_count" in os.environ.get("XLA_FLAGS", ""), (
    "run me via tests/test_pod_farm.py (or set "
    "XLA_FLAGS=--xla_force_host_platform_device_count=4)"
)

import numpy as np
import jax

from repro.core.canny import CannyParams, canny_reference
from repro.core.patterns.dist import Dist, auto_mesh
from repro.launch.mesh import dist_from_spec
from repro.stream import (
    FarmScheduler,
    PodCtx,
    PodWorker,
    SyntheticStream,
    TemporalCanny,
    reassemble,
)

PARAMS = CannyParams(sigma=1.4, radius=2, low=0.08, high=0.2)
FRAMES, H, W, HOLD, SEED, BLOCK_ROWS = 12, 64, 64, 4, 0, 16


def make_source() -> SyntheticStream:
    """The shared deterministic stream: every process derives the SAME
    frames from these constants — the pure-function property the pod
    plane's coordinator-free dispatch rests on."""
    return SyntheticStream(FRAMES, H, W, seed=SEED, hold=HOLD)


# ---------------------------------------------------------------------------
def run_rank(rank: int, pods: int, mesh: str | None, out: str) -> None:
    """One pod rank = one real JAX process over its strided slice."""
    dist = dist_from_spec(mesh)
    worker = PodWorker(
        PodCtx(rank, pods), PARAMS, dist,
        warm=True, skip=dist.is_local, block_rows=BLOCK_ROWS,
    )
    seqs, edges = [], []
    for seq, e in worker.run(make_source()):
        seqs.append(seq)
        edges.append(e)
    np.savez(
        out,
        seqs=np.asarray(seqs, np.int64),
        edges=np.stack(edges) if edges else np.zeros((0, H, W), np.uint8),
        cost=json.dumps(worker.cost_totals()),
    )


def fork_ranks(pods: int, mesh: str | None, tmp: pathlib.Path) -> list[dict]:
    """Spawn one child process per rank; return their loaded outputs."""
    env = dict(os.environ)  # inherits the forced device count
    env["PYTHONPATH"] = str(pathlib.Path(__file__).resolve().parents[2] / "src")
    procs = []
    for r in range(pods):
        out = tmp / f"rank{r}{'_mesh' if mesh else ''}.npz"
        cmd = [sys.executable, __file__, "--rank", str(r), "--pods", str(pods),
               "--out", str(out)]
        if mesh:
            cmd += ["--mesh", mesh]
        procs.append((r, out, subprocess.Popen(
            cmd, env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True
        )))
    ranks = []
    for r, out, p in procs:
        stdout, stderr = p.communicate(timeout=900)
        assert p.returncode == 0, (
            f"rank {r} failed (rc={p.returncode})\n{stdout}\n{stderr[-3000:]}"
        )
        with np.load(out, allow_pickle=False) as z:
            ranks.append({
                "seqs": z["seqs"].tolist(),
                "edges": z["edges"],
                "cost": json.loads(str(z["cost"])),
            })
    return ranks


# ---------------------------------------------------------------------------
def single_host_reference() -> list[np.ndarray]:
    det = TemporalCanny(PARAMS, warm=True, block_rows=BLOCK_ROWS)
    ref = [np.asarray(det(np.asarray(f, np.float32))) for f in make_source()]
    # anchor the whole chain to the semantic oracle on a sample frame
    want = canny_reference(make_source().frame(5), PARAMS)
    assert (ref[5] == want).all(), "single-host reference diverged from oracle"
    return ref


def check_inprocess_pod_farm(ref: list[np.ndarray]) -> None:
    """Thread pods over pod-axis meshes: per-rank TemporalCanny (pod x 1)
    and per-rank shard_map sub-meshes (pod x data, pod x model)."""
    mesh_pd = auto_mesh((2, 2), ("pod", "data"))
    mesh_pm = auto_mesh((2, 2), ("pod", "model"))
    dists = {
        "podx d": Dist(mesh=mesh_pd, batch_axes=("data",), pod_axis="pod"),
        "podx m": Dist(mesh=mesh_pm, space_axis="model", pod_axis="pod"),
    }
    for name, dist in dists.items():
        sched = FarmScheduler(
            PARAMS, warm=True, skip=False, block_rows=BLOCK_ROWS, dist=dist
        )
        got = list(sched.run(make_source()))
        assert len(got) == len(ref), f"{name}: frame count {len(got)}"
        for i, (g, w) in enumerate(zip(got, ref)):
            assert (np.asarray(g) == w).all(), f"{name}: frame {i} diverged"
    print("in-process pod farm (pod x data, pod x model): OK")

    # local per-pod slices WITH warm+skip state, via the CLI spec parser
    sched = FarmScheduler(
        PARAMS, warm=True, skip=True, block_rows=BLOCK_ROWS,
        dist=dist_from_spec("2x1x1"),
    )
    got = list(sched.run(make_source()))
    for i, (g, w) in enumerate(zip(got, ref)):
        assert (np.asarray(g) == w).all(), f"pod skip: frame {i} diverged"
    assert sched.stats.frontend_launches < FRAMES, (
        f"warm+skip pod farm recomputed every frame "
        f"({sched.stats.frontend_launches}/{FRAMES} front-end launches on a "
        f"hold={HOLD} stream)"
    )
    print(
        f"in-process pod farm warm+skip: OK "
        f"(frontend launches {sched.stats.frontend_launches}/{FRAMES})"
    )


def check_forked_ranks(ref: list[np.ndarray], tmp: pathlib.Path) -> None:
    pods = 2
    ranks = fork_ranks(pods, None, tmp)
    # rank r must own exactly frames r, r+P, … (pure-function dispatch)
    for r, data in enumerate(ranks):
        assert data["seqs"] == list(range(r, FRAMES, pods)), (
            f"rank {r} owned {data['seqs']}"
        )
    merged = list(reassemble(
        [zip(d["seqs"], d["edges"]) for d in ranks]
    ))
    assert len(merged) == FRAMES
    for i, (g, w) in enumerate(zip(merged, ref)):
        assert (g == w).all(), f"forked pods: frame {i} diverged from single-host"
    print("forked 2-rank farm: bit-identical + in-order OK")

    # warm+skip savings, pod-local: each rank held static repeats of its
    # own frames (hold=4, P=2 → pairs r, r+2 are identical), so its
    # front-end must have launched on fewer than all its frames
    for r, data in enumerate(ranks):
        cost = data["cost"]
        owned = len(data["seqs"])
        assert cost["frames"] == owned
        assert 0 < cost["frontend_launches"] < owned, (
            f"rank {r}: {cost['frontend_launches']} front-end launches "
            f"for {owned} frames — skip never engaged"
        )
    total = sum(d["cost"]["frontend_launches"] for d in ranks)
    print(f"forked warm+skip savings: OK (frontend launches {total}/{FRAMES})")


def check_forked_mesh_ranks(ref: list[np.ndarray], tmp: pathlib.Path) -> None:
    """Each forked rank drives its own DATAxMODEL shard_map detector —
    the 'pod of meshes' configuration of a real multi-host deployment."""
    ranks = fork_ranks(2, "2x2", tmp)
    merged = list(reassemble([zip(d["seqs"], d["edges"]) for d in ranks]))
    for i, (g, w) in enumerate(zip(merged, ref)):
        assert (g == w).all(), f"forked mesh pods: frame {i} diverged"
    print("forked 2-rank data x model farm: bit-identical + in-order OK")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rank", type=int, default=None)
    ap.add_argument("--pods", type=int, default=2)
    ap.add_argument("--mesh", default=None)
    ap.add_argument("--out", default=None)
    ap.add_argument(
        "--skip-mesh-ranks", action="store_true",
        help="orchestrator: skip the forked shard_map-per-rank round",
    )
    args = ap.parse_args()

    if args.rank is not None:
        run_rank(args.rank, args.pods, args.mesh, args.out)
        return

    ref = single_host_reference()
    print("single-host reference: OK")
    check_inprocess_pod_farm(ref)
    with tempfile.TemporaryDirectory() as d:
        tmp = pathlib.Path(d)
        check_forked_ranks(ref, tmp)
        if not args.skip_mesh_ranks:
            check_forked_mesh_ranks(ref, tmp)
    print("ALL-OK")


if __name__ == "__main__":
    main()
