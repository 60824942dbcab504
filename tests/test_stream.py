"""Streaming subsystem invariants.

The three properties the farm-of-pipelines design rests on:

  1. **Order + identity**: a farm with any worker count emits frames in
     input order, bit-identical to the single-worker path.
  2. **Warm-start exactness**: temporal warm-start hysteresis matches
     cold hysteresis exactly on EVERY frame of EVERY stream — the
     grow-only gate makes the seed choice invisible except in sweep
     counts (property-tested over random mask streams, where stale seeds
     would poison an ungated warm start).
  3. **Sources are deterministic/seekable** so streams replay exactly.
"""

import collections
import functools

import jax
import numpy as np
import jax.numpy as jnp
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.canny import CannyParams, canny_reference
from repro.core.canny.hysteresis import warm_seed
from repro.core.patterns.farm import Farm, farm_map
from repro.kernels import common
from repro.kernels.fused_canny import fused_canny
from repro.kernels.hysteresis import hysteresis_ref, packed_fixpoint_count
from repro.stream import (
    CorpusReplay,
    FarmScheduler,
    NpySequence,
    Prefetcher,
    SyntheticStream,
    TemporalCanny,
    write_npy_sequence,
)

PARAMS = CannyParams(sigma=1.4, radius=2, low=0.08, high=0.2)


# ---------------- farm pattern ----------------------------------------------
def test_farm_emits_in_order_and_matches_serial():
    items = list(range(23))
    fn = lambda x: x * x  # noqa: E731
    for n_workers in (1, 2, 4):
        got = list(farm_map(fn, items, n_workers=n_workers))
        assert got == [x * x for x in items]


def test_farm_backpressure_bounds_inflight():
    """The feeder may never run more than n·(depth+1) items ahead of the
    slowest consumer — the queue bound, not the stream length."""
    import threading
    import time

    n_workers, depth = 2, 1
    fed = []
    release = threading.Event()

    def feed():
        for i in range(100):
            fed.append(i)
            yield i

    def slow(x):
        release.wait(timeout=10.0)
        return x

    farm = Farm([slow] * n_workers, queue_depth=depth)
    it = iter(farm.run(feed()))
    time.sleep(0.3)  # let the feeder run as far ahead as it can
    # in flight: per worker ≤ depth queued + 1 executing (+1 feeder-held)
    assert len(fed) <= n_workers * (depth + 1) + 1
    release.set()
    assert list(it) == list(range(100))


def test_farm_propagates_worker_errors():
    def boom(x):
        if x == 3:
            raise ValueError("worker died")
        return x

    with pytest.raises(ValueError, match="worker died"):
        list(farm_map(boom, range(8), n_workers=2))


def test_farm_scheduler_bit_identical_across_worker_counts():
    frames = list(SyntheticStream(6, 64, 64, seed=5, hold=2))
    outs = {}
    for n_workers in (1, 3):
        sched = FarmScheduler(PARAMS, n_workers=n_workers, block_rows=16)
        outs[n_workers] = list(sched.run(frames))
        assert sched.stats.frames == len(frames)
    assert all((a == b).all() for a, b in zip(outs[1], outs[3]))
    # and the farm output is the true answer, not merely self-consistent
    want = canny_reference(frames[0], PARAMS)
    assert (outs[3][0] == want).all()


def test_farm_scheduler_shared_bucketed_detector():
    """Single-device config: every worker drives ONE BucketedCanny, so the
    compile cache is shared and outputs stay bit-exact."""
    from repro.core.canny import make_canny

    det = make_canny(PARAMS, backend="fused")
    frames = list(SyntheticStream(5, 64, 96, seed=9))
    det(jnp.asarray(frames[0]))  # warm the bucket before threads race
    sched = FarmScheduler(PARAMS, n_workers=2, detector=lambda x: np.asarray(det(x)))
    got = list(sched.run(frames))
    for f, e in zip(frames, got):
        assert (np.asarray(e) == canny_reference(f, PARAMS)).all()


# ---------------- temporal warm-start: exactness ----------------------------
def _random_mask_stream(rng, frames, b, h, w):
    """Adversarial mask streams: dense weak fields plus region edits, so
    warm seeds regularly go stale (removed bits) and regularly stay valid
    (grow-only frames)."""
    weak = rng.uniform(size=(b, h, w)) < 0.45
    strong = weak & (rng.uniform(size=(b, h, w)) < 0.1)
    for _ in range(frames):
        mode = rng.integers(0, 3)
        if mode == 0:  # static frame
            pass
        elif mode == 1:  # grow-only: add weak + strong bits
            weak = weak | (rng.uniform(size=weak.shape) < 0.05)
            strong = (strong | (weak & (rng.uniform(size=weak.shape) < 0.02)))
        else:  # destructive: clear a random rectangle (stale seeds!)
            y0, x0 = int(rng.integers(0, h // 2)), int(rng.integers(0, w // 2))
            weak = weak.copy()
            strong = strong.copy()
            weak[:, y0 : y0 + h // 2, x0 : x0 + w // 2] = False
            strong &= weak
        yield strong, weak


@functools.partial(jax.jit, static_argnames=("block_rows",))
def _warm_step(sw, ww, prev_s, prev_w, prev_e, block_rows=8):
    seed = warm_seed(sw, ww, prev_s, prev_w, prev_e)
    return packed_fixpoint_count(seed, ww, block_rows)


def _warm_chain(stream, block_rows=8):
    """Run the packed fixpoint over a mask stream, threading warm state.

    Pads rows/cols with zeros (inert for hysteresis) so any (h, w) works;
    the zero prev-state makes frame 0 cold through the same code path.
    """
    prev = None
    for strong, weak in stream:
        sp, h = common.pad_rows_to_multiple(
            jnp.asarray(strong).astype(jnp.uint8), block_rows, mode="zero"
        )
        wp, _ = common.pad_rows_to_multiple(
            jnp.asarray(weak).astype(jnp.uint8), block_rows, mode="zero"
        )
        sp, w = common.pad_cols_to_multiple(sp, 32)
        wp, _ = common.pad_cols_to_multiple(wp, 32)
        sw, ww = common.pack_mask(sp), common.pack_mask(wp)
        if prev is None:
            prev = (jnp.zeros_like(sw),) * 3
        packed, n, work = _warm_step(sw, ww, *prev, block_rows=block_rows)
        prev = (sw, ww, packed)
        edges = common.crop_rows(common.unpack_mask(packed)[..., :w], h)
        yield strong, weak, edges, int(n), int(work)


def test_warm_equals_cold_on_adversarial_mask_streams():
    rng = np.random.default_rng(1234)
    for trial in range(4):
        for strong, weak, warm_edges, _, _ in _warm_chain(
            _random_mask_stream(rng, frames=5, b=2, h=24, w=32)
        ):
            for i in range(strong.shape[0]):
                want = np.asarray(
                    hysteresis_ref(jnp.asarray(strong[i]), jnp.asarray(weak[i]))
                )
                got = np.asarray(warm_edges)[i]
                assert (got == want).all(), f"trial {trial}: warm diverged from cold"


def test_warm_static_frames_converge_in_one_sweep():
    """Serpentine chain: cold needs ~n_strips launches; a repeated frame
    warm-starts at the answer — 1 verification launch, 0 dilations."""
    h, w = 48, 32
    strong = np.zeros((1, h, w), bool)
    weak = np.zeros((1, h, w), bool)
    for r in range(h):
        if r % 2 == 0:
            weak[0, r, :] = True
        else:
            weak[0, r, -1 if (r // 2) % 2 == 0 else 0] = True
    strong[0, 0, 0] = weak[0, 0, 0] = True
    stream = [(strong, weak)] * 3
    stats = [(n, work) for *_, n, work in _warm_chain(iter(stream))]
    (n0, w0), (n1, w1), (n2, w2) = stats
    assert n0 >= 5 and w0 > 0  # cold start pays the chain
    assert n1 == 1 and w1 == 0  # warm static: one verifying launch
    assert n2 == 1 and w2 == 0


def test_temporal_canny_warm_equals_cold_on_moving_stream():
    src = SyntheticStream(6, 61, 77, seed=3, hold=2, noise=0.01)
    warm = TemporalCanny(PARAMS, warm=True, block_rows=16)
    cold = TemporalCanny(PARAMS, warm=False, block_rows=16)
    for i, frame in enumerate(src):
        ew, _ = warm.step(jnp.asarray(frame))
        ec, _ = cold.step(jnp.asarray(frame))
        assert (np.asarray(ew) == np.asarray(ec)).all(), f"frame {i}"
        want = canny_reference(frame, PARAMS)  # and both match the oracle
        assert (np.asarray(ew) == want).all(), f"frame {i} vs oracle"


def test_temporal_canny_jnp_backend_matches_fused():
    src = SyntheticStream(4, 48, 64, seed=7, hold=2)
    fused = TemporalCanny(PARAMS, warm=True, backend="fused", block_rows=16)
    jnpp = TemporalCanny(PARAMS, warm=True, backend="jnp")
    for frame in src:
        ef, _ = fused.step(jnp.asarray(frame))
        ej, _ = jnpp.step(jnp.asarray(frame))
        assert (np.asarray(ef) == np.asarray(ej)).all()


def test_temporal_canny_resets_on_shape_change():
    t = TemporalCanny(PARAMS, warm=True, block_rows=16)
    a = SyntheticStream(1, 48, 64, seed=1).frame(0)
    b = SyntheticStream(1, 64, 96, seed=2).frame(0)
    for frame in (a, b, a):  # shape flips must not poison the state
        e, _ = t.step(jnp.asarray(frame))
        assert (np.asarray(e) == canny_reference(frame, PARAMS)).all()


@given(data=st.data())
@settings(max_examples=10, deadline=None)
def test_warm_equals_cold_property(data):
    """Hypothesis drives the stream edits; exactness must survive all."""
    h = data.draw(st.integers(12, 28), label="h")
    w = data.draw(st.integers(8, 40), label="w")
    seed = data.draw(st.integers(0, 2**31 - 1), label="seed")
    rng = np.random.default_rng(seed)
    for strong, weak, warm_edges, _, _ in _warm_chain(
        _random_mask_stream(rng, frames=4, b=1, h=h, w=w)
    ):
        want = np.asarray(
            hysteresis_ref(jnp.asarray(strong[0]), jnp.asarray(weak[0]))
        )
        assert (np.asarray(warm_edges)[0] == want).all()


# ---------------- fused warm step vs full fused detector --------------------
def test_fused_canny_warm_zero_state_equals_fused_canny():
    from repro.kernels.fused_canny.ops import fused_canny_warm

    imgs = jnp.asarray(
        np.stack([SyntheticStream(1, 64, 64, seed=s).frame(0) for s in (1, 2)])
    )
    bh = 16
    z = jnp.zeros((2, 64, 2), jnp.uint32)
    edges, state, (n, d) = fused_canny_warm(
        imgs, z, z, z, sigma=1.4, radius=2, low=0.08, high=0.2, block_rows=bh
    )
    want = fused_canny(imgs, 1.4, 2, 0.08, 0.2, block_rows=bh)
    assert (np.asarray(edges) == np.asarray(want)).all()


# ---------------- sources ---------------------------------------------------
def test_synthetic_stream_deterministic_and_held():
    a = list(SyntheticStream(6, 32, 48, seed=11, hold=3))
    b = list(SyntheticStream(6, 32, 48, seed=11, hold=3))
    assert all((x == y).all() for x, y in zip(a, b))
    assert (a[0] == a[1]).all() and (a[1] == a[2]).all()  # held
    assert not (a[2] == a[3]).all()  # motion between hold groups
    src = SyntheticStream(6, 32, 48, seed=11, hold=3)
    assert (src.frame(4) == a[4]).all()  # seekable


def test_corpus_replay_seekable():
    full = list(CorpusReplay(steps=5, height=16, width=16, seed=3, batch=2))
    tail = list(CorpusReplay(steps=5, height=16, width=16, seed=3, batch=2, start=3))
    assert len(full) == 5 and len(tail) == 2
    assert all((x == y).all() for x, y in zip(full[3:], tail))


def test_npy_sequence_roundtrip(tmp_path):
    frames = list(SyntheticStream(4, 16, 24, seed=2))
    assert write_npy_sequence(tmp_path / "seq", frames) == 4
    back = list(NpySequence(tmp_path / "seq"))
    assert len(back) == 4
    assert all((x == y).all() for x, y in zip(frames, back))


def test_prefetcher_transparent():
    src = SyntheticStream(7, 16, 16, seed=4)
    direct = list(src)
    fetched = list(Prefetcher(src, depth=3))
    assert len(fetched) == 7
    assert all((x == y).all() for x, y in zip(direct, fetched))


def test_prefetcher_propagates_source_errors():
    def bad():
        yield np.zeros((4, 4), np.float32)
        raise RuntimeError("disk on fire")

    it = iter(Prefetcher(bad(), depth=2))
    next(it)
    with pytest.raises(RuntimeError, match="disk on fire"):
        list(it)


# ---------------- engine micro-batch path -----------------------------------
def test_run_engine_in_order_and_exact():
    frames = list(SyntheticStream(5, 64, 64, seed=6))
    sched = FarmScheduler(PARAMS)
    got = list(sched.run_engine(frames, max_batch=2))
    assert len(got) == 5
    for f, e in zip(frames, got):
        assert (e == canny_reference(f, PARAMS)).all()


class _DepthStub:
    """Frame source with a scripted ``qsize`` backlog signal."""

    def __init__(self, frames, depths):
        self.frames = frames
        self.depths = list(depths)
        self._i = 0

    def qsize(self):
        d = self.depths[min(self._i, len(self.depths) - 1)]
        return d

    def __iter__(self):
        for f in self.frames:
            yield f
            self._i += 1


def test_run_engine_adaptive_batches_follow_queue_depth():
    """Empty backlog → single-frame waves (latency); deep backlog → waves
    grow toward max_batch (throughput). Order and bits never change."""
    frames = list(SyntheticStream(6, 32, 32, seed=7))

    # backlog always empty → every wave is a single frame
    idle = _DepthStub(frames, [0] * 6)
    sched = FarmScheduler(PARAMS)
    got = list(sched.run_engine(idle, max_batch=4))
    assert len(got) == 6
    for f, e in zip(frames, got):
        assert (e == canny_reference(f, PARAMS)).all()
    assert sched.stats.batch_sizes == {1: 6}
    assert sched.stats.mean_batch_size() == 1.0

    # backlog always deep → waves fill to max_batch
    busy = _DepthStub(frames, [10] * 6)
    sched = FarmScheduler(PARAMS)
    got = list(sched.run_engine(busy, max_batch=4))
    assert len(got) == 6
    for f, e in zip(frames, got):
        assert (e == canny_reference(f, PARAMS)).all()
    assert sched.stats.batch_sizes == {4: 1, 2: 1}


def test_run_engine_adaptive_without_backlog_signal_fills_waves():
    """A plain iterable has no qsize(): adaptive degrades to fixed waves."""
    frames = list(SyntheticStream(5, 32, 32, seed=8))
    sched = FarmScheduler(PARAMS)
    got = list(sched.run_engine(frames, max_batch=2, adaptive=True))
    assert len(got) == 5
    for f, e in zip(frames, got):
        assert (e == canny_reference(f, PARAMS)).all()
    assert sched.stats.batch_sizes == {2: 2, 1: 1}


def test_run_engine_fixed_mode_ignores_backlog():
    frames = list(SyntheticStream(4, 32, 32, seed=9))
    idle = _DepthStub(frames, [0] * 4)
    sched = FarmScheduler(PARAMS)
    got = list(sched.run_engine(idle, max_batch=4, adaptive=False))
    assert len(got) == 4
    assert sched.stats.batch_sizes == {4: 1}


def test_prefetcher_exposes_backlog_depth():
    from repro.stream import Prefetcher

    src = Prefetcher(SyntheticStream(3, 16, 16, seed=10), depth=2)
    assert src.qsize() == 0  # before iteration starts
    out = list(src)
    assert len(out) == 3
    assert src.qsize() == 0  # fully drained


# ---------------- stream failure paths ---------------------------------------
def test_farm_worker_error_while_feeder_backpressure_blocked():
    """A worker dying MID-STREAM, with the feeder parked on a full queue
    (infinite source), must cancel cleanly: the consumer sees the error
    promptly and the feeder's put_cancellable unblocks — no deadlock."""
    import time

    def endless():
        i = 0
        while True:
            yield i
            i += 1

    def boom(x):
        if x >= 2:
            raise RuntimeError("worker died mid-stream")
        return x

    farm = Farm([boom, boom], queue_depth=1)
    t0 = time.perf_counter()
    with pytest.raises(RuntimeError, match="worker died mid-stream"):
        list(farm.run(endless()))
    assert time.perf_counter() - t0 < 30.0  # cancelled, not deadlocked


def test_farm_consumer_abandons_iteration_cleanly():
    """Closing the result iterator early (consumer bails) must cancel the
    feeder and join the workers — the infinite source proves it."""

    def endless():
        i = 0
        while True:
            yield np.float32(i)
            i += 1

    farm = Farm([lambda x: x, lambda x: x], queue_depth=1)
    it = farm.run(endless())
    assert next(it) == 0.0
    it.close()  # Farm.run's finally: cancel + sentinel + join


def test_prefetcher_empty_and_exhausted_sources():
    from repro.stream import Prefetcher

    assert list(Prefetcher([], depth=2)) == []  # empty source

    one_shot = iter([np.zeros((2, 2), np.float32)])
    pf = Prefetcher(one_shot, depth=2)
    assert len(list(pf)) == 1
    assert list(pf) == []  # exhausted iterator: clean empty replay

    replayable = SyntheticStream(2, 8, 8, seed=1)
    pf = Prefetcher(replayable, depth=1)
    assert len(list(pf)) == 2
    assert len(list(pf)) == 2  # re-iterable sources replay through it


def test_run_engine_flush_on_early_consumer_exit():
    """The consumer breaking out of run_engine mid-stream must unwind the
    generator (and the Prefetcher feeding it) without deadlock, and a
    fresh run must still be exact."""
    from repro.stream import Prefetcher

    frames = SyntheticStream(6, 32, 32, seed=11)
    sched = FarmScheduler(PARAMS)
    it = sched.run_engine(Prefetcher(frames, depth=2), max_batch=2)
    first = next(it)
    it.close()  # GeneratorExit at the yield point; pending work abandoned
    assert (first == canny_reference(frames.frame(0), PARAMS)).all()

    got = list(sched.run_engine(Prefetcher(frames, depth=2), max_batch=2))
    assert len(got) == 6
    for i, e in enumerate(got):
        assert (e == canny_reference(frames.frame(i), PARAMS)).all()


# ---------------- pod plane (unit level; processes in test_pod_farm) --------
def test_pod_ctx_round_robin_partition():
    from repro.stream import PodCtx

    with pytest.raises(ValueError):
        PodCtx(2, 2)
    with pytest.raises(ValueError):
        PodCtx(0, 0)
    pods = [PodCtx(r, 3) for r in range(3)]
    for seq in range(12):
        owners = [p.owns(seq) for p in pods]
        assert sum(owners) == 1 and owners[seq % 3]


def test_strided_slices_partition_the_stream():
    from repro.stream import PodCtx, strided

    frames = [np.full((2, 2), i, np.float32) for i in range(7)]
    a = list(strided(frames, PodCtx(0, 2)))
    b = list(strided(frames, PodCtx(1, 2)))
    assert [s for s, _ in a] == [0, 2, 4, 6]
    assert [s for s, _ in b] == [1, 3, 5]
    assert all((f == frames[s]).all() for s, f in a + b)


def test_reassemble_merges_in_global_order():
    from repro.stream import reassemble

    a = [(0, "f0"), (2, "f2"), (4, "f4")]
    b = [(1, "f1"), (3, "f3")]
    assert list(reassemble([a, b])) == ["f0", "f1", "f2", "f3", "f4"]
    assert list(reassemble([])) == []


def test_reassemble_rejects_gaps_and_leftovers():
    from repro.stream import reassemble

    # rank 1 produced the wrong seq (a dropped frame shifts everything)
    with pytest.raises(RuntimeError, match="out-of-order or missing"):
        list(reassemble([[(0, "a")], [(3, "x")]]))
    # rank 1 holds frames past the global end (rank 0 under-produced)
    with pytest.raises(RuntimeError, match="still holds"):
        list(reassemble([[(0, "a")], [(1, "b"), (3, "x")]]))


def test_pod_dist_rejected_by_single_detector_layers():
    """A pod-axis Dist describes a FARM of detectors; every layer that
    builds exactly one detector/queue must reject it loudly rather than
    silently replicate work over the pod axis."""
    from repro.core.canny import make_canny
    from repro.core.patterns.dist import Dist, auto_mesh
    from repro.serve.engine import CannyEngine

    mesh = auto_mesh((1, 1), ("pod", "data"))
    pod_dist = Dist(mesh=mesh, batch_axes=("data",), pod_axis="pod")
    with pytest.raises(ValueError, match="pod"):
        make_canny(PARAMS, pod_dist, backend="fused")
    with pytest.raises(ValueError, match="pod"):
        CannyEngine(PARAMS, bucket_multiple=32, dist=pod_dist)


def test_farm_scheduler_skip_matches_cold():
    frames = list(SyntheticStream(6, 48, 48, seed=13, hold=3))
    cold = FarmScheduler(PARAMS, n_workers=2, warm=False, block_rows=16)
    want = list(cold.run(frames))
    skip = FarmScheduler(PARAMS, n_workers=2, warm=True, skip=True, block_rows=16)
    got = list(skip.run(frames))
    assert all((a == b).all() for a, b in zip(want, got))
    # hold=3 with 2 workers: each worker sees held repeats → must skip
    assert skip.stats.frontend_launches < len(frames)
    assert cold.stats.frontend_launches == len(frames)


# ---------------- session mode: many cameras, one session each --------------
SESSION_RATES = (30.0, 30.0, 25.0, 25.0, 12.5, 30.0)  # Hz, camera c at SESSION_RATES[c]


def _capture_order(streams, rates, seconds):
    """``(camera, frame_index)`` of every frame captured in ``[0,
    seconds)``, ordered by capture time (camera c's frame i at ``(i +
    phase_c) / rate_c``, phases spread over a frame period)."""
    stamps = []
    for c, rate in enumerate(rates):
        phase = (c % 3) / 3.0
        i = 0
        while (i + phase) / rate < seconds and i < len(streams[c]):
            stamps.append(((i + phase) / rate, c, i))
            i += 1
    return [(c, i) for _, c, i in sorted(stamps)]


@functools.lru_cache(maxsize=1)
def _session_feed():
    # one moving object in 8 strips: most strips of a camera's frame are static
    streams = [list(SyntheticStream(8, 128, 64, seed=40 + c, n_moving=1))
               for c in range(len(SESSION_RATES))]
    order = _capture_order(streams, SESSION_RATES, 0.25)
    return streams, order


def test_session_mode_exact_in_order_and_cheaper_than_round_robin():
    streams, order = _session_feed()
    sched = FarmScheduler(PARAMS, warm=True, skip=True, block_rows=16)
    got = list(sched.run_sessions((c, streams[c][i]) for c, i in order))
    # one result per frame fed, in feed order: each camera's in its own order
    assert [c for c, _ in got] == [c for c, _ in order]
    counts = collections.Counter(c for c, _ in order)
    assert counts[0] > counts[2] > counts[4]  # 30 : 25 : 12.5 Hz
    cold = {c: TemporalCanny(PARAMS, warm=False, block_rows=16) for c in counts}
    for (c, i), (_, edges) in zip(order, got):
        frame = streams[c][i]
        assert (edges == canny_reference(frame, PARAMS)).all(), (c, i)
        assert (edges == np.asarray(cold[c](jnp.asarray(frame)))).all(), (c, i)
    # one session per camera, each on the one worker the router names
    assert sched.stats.sessions_opened == len(counts)
    held = {c: k for k, table in enumerate(sched.sessions) for c in table.table}
    assert held == {c: sched.route(c) for c in counts}
    # the same feed dispatched seq % n: every worker's previous frame is
    # another camera's, so the skip finds fewer static strips
    plain = FarmScheduler(PARAMS, n_workers=len(sched.sessions), warm=True,
                          skip=True, block_rows=16)
    list(plain.run(streams[c][i] for c, i in order))
    assert sched.stats.frontend_strips < plain.stats.frontend_strips


def test_session_route_is_pure_in_camera_and_roster():
    from repro.stream.pod import session_route

    roster = (0, 1, 2, 3)
    workers = [session_route(c, roster, 2) for c in range(40)]
    assert workers == [session_route(c, roster, 2) for c in reversed(range(40))][::-1]
    # worker k serves chip k % 4; every chip 10 cameras, every worker 5
    assert all(w % 4 == c % 4 for c, w in enumerate(workers))
    assert collections.Counter(workers) == dict.fromkeys(range(8), 5)


def test_farm_route_dispatches_by_key_in_feed_order():
    seen = collections.defaultdict(list)

    def worker(k):
        def run(item):
            seen[k].append(item)
            return item * 10
        return run

    farm = Farm([worker(k) for k in range(3)], queue_depth=1)
    got = list(farm.run(range(30), route=lambda item: item % 7 % 3))
    assert got == [x * 10 for x in range(30)]
    assert {k: sorted(v) for k, v in seen.items()} == {
        k: [x for x in range(30) if x % 7 % 3 == k] for k in range(3)
    }


class _Lookahead:
    """A ``.stream`` worker that, like ``PatternPipeline``, holds an item's
    result until it has the next item, unless ``ready()`` says none is
    queued (``WorkerFeed``)."""

    def stream(self, items):
        held = None
        for item in items:
            if held is not None:
                yield held * 10
            held = item
            if not items.ready():
                yield held * 10
                held = None
        if held is not None:
            yield held * 10


def test_farm_route_reaches_a_pipelined_workers_next_item_far_ahead():
    """Worker 0's next item comes 50 items after its first; the routed
    feeder, which keeps at most n · (queue_depth + 2) items fed but not
    emitted, gets there because the worker hands its first result back
    when nothing is queued behind it."""
    route = lambda item: 0 if item in (0, 51) else 1  # noqa: E731
    farm = Farm([_Lookahead(), _Lookahead()], queue_depth=1, timeout=30.0)
    assert list(farm.run(range(60), route=route)) == [x * 10 for x in range(60)]


def test_farm_route_emits_a_pipelined_workers_result_before_its_next_item():
    """Worker 0 gets item 0 and nothing after it (its camera paused), the
    other workers every later item: item 0 comes out while the feed still
    runs, and the feeder never runs more than n · (queue_depth + 2) items
    ahead of the consumer, so the reorder buffer stays bounded."""
    from repro.stream.scheduler import StreamStats, StreamWorker

    n, depth, total = 3, 1, 60
    stats = StreamStats()
    farm = Farm([StreamWorker(lambda x: x + 0, stats) for _ in range(n)],
                queue_depth=depth, timeout=30.0)
    pulled = []

    def feed():
        for k in range(total):
            pulled.append(k)
            yield np.full((4, 4), k, np.float32)

    def route(frame):
        k = int(frame[0, 0])
        return 0 if k == 0 else 1 + k % (n - 1)

    got, ahead = [], []
    for edges in farm.run(feed(), route=route):
        got.append(int(edges[0, 0]))
        ahead.append(len(pulled) - len(got))
    assert got == list(range(total))
    assert ahead[0] + 1 < total // 2  # item 0 out with most of the feed still to come
    # one more than the window: the feeder holds the item it waits to enqueue
    assert max(ahead) <= n * (depth + 2) + 1


def test_session_mode_survives_a_camera_far_ahead_in_the_feed():
    streams, _ = _session_feed()
    feed = [(0, streams[0][0])] + [(1, streams[1][i % 8]) for i in range(40)] + [(0, streams[0][1])]
    sched = FarmScheduler(PARAMS, warm=True, skip=True, block_rows=16, timeout=60.0)
    got = list(sched.run_sessions(feed))
    assert [c for c, _ in got] == [c for c, _ in feed]
    assert all((e == canny_reference(f, PARAMS)).all() for (_, f), (_, e) in zip(feed, got))


def test_session_mode_needs_the_stateful_local_path():
    sched = FarmScheduler(PARAMS, n_workers=2, detector=lambda x: x)
    with pytest.raises(ValueError, match="session mode"):
        list(sched.run_sessions([(0, np.zeros((8, 8), np.float32))]))


# ---------------- elastic plane ----------------------------------------------
def test_farm_scheduler_recovers_from_injected_kill_bit_identical():
    """A FaultInjector-planted worker death mid-stream, with restarts
    on: the replacement runs cold and the output stays bit-identical to
    the healthy run — warm state never owned any bits."""
    from repro.distributed import FaultInjector

    frames = list(SyntheticStream(8, 48, 64, seed=11, hold=2))
    healthy = [np.asarray(e).copy() for e in FarmScheduler(
        PARAMS, n_workers=2, block_rows=16
    ).run(frames)]
    inj = FaultInjector(kill={(0, 2)})
    sched = FarmScheduler(
        PARAMS, n_workers=2, block_rows=16,
        max_restarts=2, timeout=60.0, injector=inj,
    )
    got = [np.asarray(e).copy() for e in sched.run(frames)]
    assert len(got) == len(healthy)
    assert all((a == b).all() for a, b in zip(got, healthy))
    assert sched.farm.restarts == 1
    assert sched.stats.restarts == 1
    assert [k for k, _, _ in inj.fired] == ["kill"]
    assert "restarts=1" in sched.stats.summary()


def test_farm_scheduler_exhausted_restarts_raise_injected_fault():
    from repro.distributed import FaultInjector
    from repro.distributed.fault_tolerance import InjectedFault

    inj = FaultInjector(drop={0: 0, 1: 0})  # both workers always die
    sched = FarmScheduler(
        PARAMS, n_workers=2, block_rows=16, max_restarts=1, timeout=30.0,
        injector=inj,
    )
    with pytest.raises(InjectedFault):
        list(sched.run(SyntheticStream(4, 48, 64, seed=1)))


def test_stream_stats_watchdog_counts_slow_steps_and_stragglers():
    """The StepWatchdog report lands in StreamStats and the summary
    line — one worker consistently 3x slower gets named."""
    from repro.stream.scheduler import StreamStats
    from repro.distributed.fault_tolerance import StepWatchdog

    stats = StreamStats()
    stats.watchdog = StepWatchdog(k=3.0, clock=lambda: 0.0)
    for _ in range(12):
        stats.record_compute(10.0, "worker0")  # the uniform baseline
    for _ in range(4):
        stats.record_compute(40.0, "worker1")  # the consistent straggler
    assert stats.slow_steps >= 1
    assert stats.straggler_counts and stats.straggler_counts.most_common(1)[0][0] == "worker1"
    line = stats.summary()
    assert "slow_steps=" in line and "worker1" in line


def test_stream_stats_empty_windows_render_cleanly():
    """A scoreboard rendered before the first request completes must not
    invent a perfect 0.0ms latency: quantiles of empty windows are nan
    and the summary renders ``-`` for them."""
    import math

    from repro.stream.scheduler import StreamStats

    stats = StreamStats()
    assert math.isnan(stats.latency_ms(0.50))
    assert math.isnan(stats.latency_ms(0.99))
    line = stats.summary()  # must not crash on a fresh object
    assert "prep_p50=- " in line
    assert "compute_p50=- " in line
    assert "compute_p95=- " in line
    assert "0.0ms" not in line
    # once a sample lands the real numbers come back
    stats.record_compute(12.0)
    stats.prep_ms.append(3.0)
    line = stats.summary()
    assert "compute_p50=12.0ms" in line
    assert "prep_p50=3.0ms" in line


def test_elastic_pod_farm_kill_and_revive_bit_identical():
    """The in-process tentpole: rank death mid-stream, deterministic
    re-ownership, cold revival — output equals the healthy oracle."""
    from repro.distributed import FaultInjector
    from repro.stream import ElasticPodFarm

    frames = list(SyntheticStream(10, 48, 64, seed=7, hold=2))
    oracle = [np.asarray(e).copy() for e in ElasticPodFarm(
        PARAMS, ranks=2, block_rows=16, timeout=120.0
    ).run(frames)]
    inj = FaultInjector(kill={(1, 1)})
    farm = ElasticPodFarm(
        PARAMS, ranks=2, block_rows=16, timeout=120.0,
        injector=inj, revive_after=3,
    )
    got = [np.asarray(e).copy() for e in farm.run(frames)]
    assert len(got) == len(oracle)
    assert all((a == b).all() for a, b in zip(got, oracle))
    assert farm.deaths == 1
    kinds = [k for k, _, _ in farm.events]
    assert "death" in kinds and "join" in kinds
    assert farm.membership.epoch == 2  # death + rejoin
    assert len(farm.recoveries_s) == 1


def test_elastic_pod_farm_heartbeat_declares_stalled_rank_dead():
    """The heartbeat path with cheap fake workers: a rank stalled past
    the timeout is swept dead, its frame re-owned — no InjectedFault is
    ever raised (the stall is not an exception), yet the farm heals."""
    import time as _time

    from repro.distributed import FaultInjector
    from repro.stream import ElasticPodFarm

    class Fake:
        def step(self, x):
            return np.asarray(x) * 0 + 7, None

        def reset(self):
            pass

    inj = FaultInjector(stall={(1, 1): 1.2})
    farm = ElasticPodFarm(
        ranks=2, heartbeat_timeout=0.3, timeout=30.0,
        injector=inj, make_worker=lambda rank: Fake(),
    )
    frames = [np.full((4, 4), i, np.float32) for i in range(6)]
    got = list(farm.run(frames))
    assert len(got) == 6
    assert all((g == 7).all() for g in got)
    assert farm.deaths == 1
    _, _, reason = farm.membership.history[1]
    assert "heartbeat timeout" in reason
    assert inj.fired and inj.fired[0][0] == "stall"


def test_elastic_pod_farm_revival_races_the_feeder():
    """A frame fed while a dead rank rejoins must reach the new
    incarnation: the feeder looks the owner up right after the rank is
    back on the roster, and the frame must land on a queue a live thread
    reads, never on the dead incarnation's."""
    import threading as _threading

    from repro.distributed import FaultInjector
    from repro.stream import ElasticPodFarm

    class Fake:
        def step(self, x):
            return np.asarray(x), None

        def reset(self):
            pass

    farm = ElasticPodFarm(
        ranks=3, timeout=10.0, revive_after=1,
        injector=FaultInjector(kill={(1, 0): "first frame"}),
        make_worker=lambda rank: Fake(),
    )
    rejoined, fed = _threading.Event(), _threading.Event()
    join = farm.membership.join

    def join_then_let_the_feeder_in(rank, reason="joined"):
        out = join(rank, reason)
        rejoined.set()
        fed.wait(5.0)
        return out

    farm.membership.join = join_then_let_the_feeder_in
    frames = [np.full((4, 4), i, np.float32) for i in range(6)]

    def source():
        yield from frames[:4]
        assert rejoined.wait(10.0), "rank 1 never rejoined"
        yield frames[4]  # seq 4 → rank 1 under the (0, 1, 2) roster
        fed.set()
        yield frames[5]

    got = list(farm.run(source()))
    assert [int(g[0, 0]) for g in got] == list(range(6))
    assert [e[0] for e in farm.events] == ["death", "join"]


def test_elastic_pod_farm_last_rank_death_raises():
    from repro.distributed import FaultInjector
    from repro.distributed.fault_tolerance import InjectedFault
    from repro.stream import ElasticPodFarm

    class Fake:
        def step(self, x):
            return np.asarray(x), None

    inj = FaultInjector(drop={0: 0, 1: 0})  # every rank dies on sight
    farm = ElasticPodFarm(
        ranks=2, timeout=30.0, injector=inj,
        make_worker=lambda rank: Fake(),
    )
    with pytest.raises(InjectedFault):
        list(farm.run([np.zeros((4, 4), np.float32)] * 4))


def test_elastic_pod_farm_stream_timeout_is_bounded():
    """A farm whose ranks never produce must raise StreamTimeout within
    the budget — the no-deadlock guarantee."""
    import time as _time

    from repro.distributed.fault_tolerance import StreamTimeout
    from repro.stream import ElasticPodFarm

    class Hang:
        def step(self, x):
            _time.sleep(3.0)  # long enough to trip the 0.5s budget; short
            return np.asarray(x), None  # enough that thread cleanup joins

    farm = ElasticPodFarm(
        ranks=2, timeout=0.5, heartbeat_timeout=1e9,
        make_worker=lambda rank: Hang(),
    )
    t0 = _time.perf_counter()
    with pytest.raises(StreamTimeout, match="seq 0"):
        list(farm.run([np.zeros((4, 4), np.float32)] * 2))
    assert _time.perf_counter() - t0 < 10.0
