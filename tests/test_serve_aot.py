"""AOT serving plane tests (`serve/aot.py` + `serve/admission.py`).

Pins the PR's three contracts: the NO-RETRACE contract (every executable
compiles at warmup, the trace counter stays frozen for any admissible
stream, off-lattice requests are rejected — never traced), CONTINUOUS
admission semantics (fill-or-linger dispatch, bounded admission, poison
propagation from dead workers, bit-identity with the synchronous wave),
and the per-request SLO accounting grown onto ``StreamStats``.
"""

import threading
import time

import numpy as np
import pytest

from repro.core.canny import CannyParams, canny_reference
from repro.core.canny.backends import UnsupportedFeature
from repro.data.images import synthetic_image
from repro.distributed.fault_tolerance import StreamTimeout
from repro.serve import (
    AotCannyEngine,
    CannyEngine,
    ContinuousBatcher,
    default_lanes,
    infer_buckets,
)

PARAMS = CannyParams(sigma=1.4, radius=2, low=0.08, high=0.2)


def make_engine(**kw):
    kw.setdefault("buckets", [(32, 32)])
    kw.setdefault("bucket_multiple", 32)
    kw.setdefault("max_batch", 4)
    return AotCannyEngine(PARAMS, **kw)


# ---------------- warmup lattice ---------------------------------------------
def test_default_lanes_is_pow2_ladder():
    assert default_lanes(1) == (1,)
    assert default_lanes(4) == (1, 2, 4)
    assert default_lanes(6) == (1, 2, 4, 8)  # ladder covers max_batch
    # a mesh data axis folds every lane up to a shardable multiple
    assert default_lanes(4, lane_multiple=2) == (2, 4)
    with pytest.raises(ValueError):
        default_lanes(0)


def test_infer_buckets_first_seen_order():
    frames = [np.zeros((40, 40)), (33, 90), np.zeros((20, 20)), (64, 64)]
    assert infer_buckets(frames, 32) == [(64, 64), (64, 96), (32, 32)]
    with pytest.raises(ValueError, match="no buckets"):
        infer_buckets([], 32)


def test_warmup_compiles_full_lattice_exactly_once():
    engine = make_engine(buckets=[(32, 32), (30, 60)], max_batch=4)
    assert engine.hw_buckets == ((32, 32), (32, 64))
    assert engine.lanes == (1, 2, 4)
    # one trace per (bucket, lane) cell, all during construction
    assert engine.warmup_traces == len(engine.hw_buckets) * len(engine.lanes)
    assert engine.stats.compiles == engine.warmup_traces
    assert engine.post_warmup_traces == 0


def test_warmup_from_calibration_stream():
    cal = [synthetic_image(40, 40, seed=i) for i in range(3)] + [(20, 60)]
    engine = make_engine(buckets=None, calibration=cal)
    assert engine.hw_buckets == ((64, 64), (32, 64))


def test_warmup_requires_a_lattice():
    with pytest.raises(ValueError, match="bucket lattice up front"):
        AotCannyEngine(PARAMS)


# ---------------- fail-fast rejection ----------------------------------------
def test_off_lattice_request_is_rejected_not_traced():
    engine = make_engine(buckets=[(32, 32)])
    before = engine.traces
    with pytest.raises(UnsupportedFeature, match=r"\(64, 32\)"):
        engine.process([synthetic_image(40, 20, seed=1)])
    with pytest.raises(UnsupportedFeature, match="fresh trace"):
        engine.bucket_for(100, 100)
    assert engine.traces == before  # rejection never touched jit


def test_oversized_batch_has_no_lane():
    engine = make_engine(max_batch=2)
    with pytest.raises(UnsupportedFeature, match="batch lane"):
        engine.lane_for(5)


def test_run_packed_rejects_unwarmed_shape():
    engine = make_engine(buckets=[(32, 32)])
    with pytest.raises(UnsupportedFeature, match="no executable"):
        engine.run_packed(
            np.zeros((1, 64, 64), np.float32), np.full((1, 2), 64, np.int32)
        )


# ---------------- the acceptance property ------------------------------------
def test_mixed_stream_bit_identical_to_lazy_engine_with_zero_traces():
    """THE acceptance test: a mixed-size stream through the AOT wave path
    is bit-identical to the lazy ``CannyEngine`` wave path, with zero
    post-warmup traces (the counting hook pins the no-retrace contract)."""
    sizes = [(33, 47), (64, 64), (50, 70), (33, 47), (21, 90), (64, 64)]
    reqs = [synthetic_image(h, w, seed=50 + i) for i, (h, w) in enumerate(sizes)]

    lazy = CannyEngine(PARAMS, bucket_multiple=32, max_batch=4)
    want = lazy.process(reqs)

    engine = make_engine(buckets=sizes)
    got = engine.process(reqs)
    assert engine.post_warmup_traces == 0
    for g, w, r in zip(got, want, reqs):
        assert g.shape == r.shape and g.dtype == np.uint8
        assert (g == w).all()
    # replay: still zero traces, stats accumulate
    engine.process(reqs)
    assert engine.post_warmup_traces == 0
    assert engine.stats.requests == 2 * len(reqs)


def test_continuous_batcher_matches_wave_bit_exact():
    sizes = [(33, 47), (30, 30), (64, 64), (33, 47), (21, 60)] * 2
    reqs = [synthetic_image(h, w, seed=70 + i) for i, (h, w) in enumerate(sizes)]
    engine = make_engine(buckets=sizes)
    want = engine.process(reqs)

    with ContinuousBatcher(engine, linger_ms=1.0, timeout=60.0) as batcher:
        tickets = [batcher.submit(r) for r in reqs]
        assert batcher.drain() == len(reqs)
    assert engine.post_warmup_traces == 0
    for t, w in zip(tickets, want):
        assert (t.result() == w).all()
        # the SLO timestamps are complete and ordered
        assert t.t_enqueue <= t.t_dispatch <= t.t_complete
        assert t.latency_ms() >= 0.0


# ---------------- dispatch policy --------------------------------------------
def test_full_slot_dispatches_without_waiting_for_linger():
    engine = make_engine(max_batch=2)
    # linger far beyond the test budget: only the FILL trigger can fire
    with ContinuousBatcher(engine, linger_ms=60_000.0, timeout=30.0) as b:
        tickets = [b.submit(synthetic_image(30, 30, seed=i)) for i in range(2)]
        t0 = time.perf_counter()
        for t in tickets:
            t.result(timeout=30.0)
        assert time.perf_counter() - t0 < 30.0
        assert [t.done for t in tickets] == [True, True]
    occ = list(b.stats.slot_occupancy)
    assert occ and occ[0] == 1.0  # the slot was packed


def test_lingering_partial_slot_dispatches_at_deadline():
    engine = make_engine(max_batch=4)
    with ContinuousBatcher(engine, linger_ms=20.0, timeout=30.0) as b:
        # 3 of 4: the slot can't fill, so only the linger deadline fires
        tickets = [b.submit(synthetic_image(30, 30, seed=3)) for _ in range(3)]
        out = tickets[0].result(timeout=30.0)
        # the oldest request waited out (at least most of) its linger
        assert (tickets[0].t_dispatch - tickets[0].t_enqueue) >= 0.010
    assert (out == canny_reference(synthetic_image(30, 30, seed=3), PARAMS)).all()
    # 3 requests ride the smallest covering lane (4): a partial slot
    assert list(b.stats.slot_occupancy) == [0.75]


def test_zero_linger_dispatches_immediately():
    """``linger_ms=0`` is the latency-floor fast path: a lone request on
    a wide lane has ``deadline <= now`` the moment it enqueues, so the
    dispatcher fires at its next pass without waiting for the slot to
    fill OR any linger window. With max_batch=4 the fill trigger cannot
    fire for one request — if the zero-linger deadline path regressed,
    this would hang until the timeout instead of answering instantly."""
    engine = make_engine(max_batch=4)
    with ContinuousBatcher(engine, linger_ms=0.0, timeout=30.0) as b:
        ticket = b.submit(synthetic_image(30, 30, seed=5))
        out = ticket.result(timeout=30.0)
    assert (out == canny_reference(synthetic_image(30, 30, seed=5), PARAMS)).all()
    # no linger window rode the queue wait
    assert (ticket.t_dispatch - ticket.t_enqueue) < 1.0
    assert list(b.stats.slot_occupancy)  # the dispatch was recorded


def test_buckets_never_share_a_slot():
    """Requests only pack with same-bucket requests: two buckets × two
    requests each dispatch as two launches, never one mixed launch."""
    engine = make_engine(buckets=[(32, 32), (32, 64)], max_batch=2)
    reqs = [
        synthetic_image(30, 30, seed=0), synthetic_image(30, 60, seed=1),
        synthetic_image(32, 32, seed=2), synthetic_image(20, 50, seed=3),
    ]
    with ContinuousBatcher(engine, linger_ms=60_000.0, timeout=30.0) as b:
        tickets = [b.submit(r) for r in reqs]
        b.drain(timeout=30.0)
    assert engine.stats.batches == 2
    for t, r in zip(tickets, reqs):
        assert (t.result() == canny_reference(r, PARAMS)).all()


# ---------------- bounded admission + poisoning ------------------------------
def test_batcher_submit_fail_fast_on_unwarmed_bucket():
    engine = make_engine(buckets=[(32, 32)])
    with ContinuousBatcher(engine, timeout=5.0) as b:
        with pytest.raises(UnsupportedFeature, match="no executable"):
            b.submit(synthetic_image(100, 100, seed=1))
        assert b.submitted == 0  # rejected before admission


def test_batcher_bounded_admission_sheds_load_and_names_itself():
    engine = make_engine(max_batch=2)
    # a slot that can never dispatch (linger is huge, slot stays 1/2 full)
    b = ContinuousBatcher(
        engine, linger_ms=60_000.0, max_pending=1, timeout=0.15,
        name="front-door",
    )
    try:
        b.submit(synthetic_image(30, 30, seed=1))
        with pytest.raises(StreamTimeout, match="admission") as ei:
            b.submit(synthetic_image(30, 30, seed=2))
        assert "front-door" in ei.value.what
        assert "max_pending=1" in ei.value.what
    finally:
        b._stop.set()
        with b._cond:
            b._cond.notify_all()
        b._dispatcher.join(timeout=5.0, reraise=False)
        b._drainer.join(timeout=5.0, reraise=False)


def test_batcher_concurrent_submitters_bounded_no_drops():
    """N submitter threads against a small max_pending: every request
    resolves exactly once (no deadlock, no dropped ticket) and the bound
    held — the batcher never carried more than max_pending unresolved."""
    engine = make_engine(max_batch=2)
    want = canny_reference(synthetic_image(30, 30, seed=0), PARAMS)
    results: list = []
    lock = threading.Lock()

    with ContinuousBatcher(
        engine, linger_ms=2.0, max_pending=3, timeout=60.0
    ) as b:
        def submitter():
            for _ in range(4):
                t = b.submit(synthetic_image(30, 30, seed=0))
                with lock:
                    results.append(t)

        threads = [threading.Thread(target=submitter) for _ in range(5)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60.0)
        assert not any(t.is_alive() for t in threads), "submitters deadlocked"
        assert b.drain(timeout=60.0) == 20
    assert len(results) == 20
    assert all((t.result() == want).all() for t in results)
    assert engine.post_warmup_traces == 0


def test_worker_death_poisons_batcher_not_a_silent_hang():
    engine = make_engine()

    def boom(batch, true_hw):
        raise RuntimeError("device fell over")

    engine.run_packed = boom
    b = ContinuousBatcher(engine, linger_ms=1.0, timeout=5.0)
    ticket = b.submit(synthetic_image(30, 30, seed=1))
    with pytest.raises(RuntimeError, match="device fell over"):
        ticket.result(timeout=5.0)
    with pytest.raises(RuntimeError, match="device fell over"):
        b.drain(timeout=5.0)
    with pytest.raises(RuntimeError, match="device fell over"):
        b.submit(synthetic_image(30, 30, seed=2))  # poisoned, fail fast
    with pytest.raises(RuntimeError, match="device fell over"):
        b.close()


def test_batcher_rejects_after_close():
    engine = make_engine()
    b = ContinuousBatcher(engine, timeout=5.0)
    b.close()
    with pytest.raises(RuntimeError, match="closed"):
        b.submit(synthetic_image(30, 30, seed=1))
    b.close()  # idempotent


def test_batcher_validates_knobs():
    engine = make_engine()
    for kw in (
        {"linger_ms": -1.0}, {"max_pending": 0}, {"backlog": 0}, {"timeout": 0.0},
    ):
        with pytest.raises(ValueError):
            ContinuousBatcher(engine, **kw)


# ---------------- SLO accounting ---------------------------------------------
def test_stream_stats_slo_scoreboard():
    from repro.stream.scheduler import StreamStats

    stats = StreamStats(slo_ms=10.0)
    stats.record_request(3.0)   # pass
    stats.record_request(25.0)  # fail
    stats.record_occupancy(2, 4)
    assert stats.slo() == {
        "slo_ms": 10.0, "pass": 1, "fail": 1, "attainment": 0.5,
    }
    assert stats.latency_ms(0.5) == pytest.approx(14.0)
    assert list(stats.slot_occupancy) == [0.5]
    s = stats.summary()
    assert "req_p99" in s and "slo<10ms" in s


def test_batcher_scores_requests_against_slo():
    engine = make_engine()
    with ContinuousBatcher(engine, linger_ms=1.0, slo_ms=1e6, timeout=30.0) as b:
        for i in range(3):
            b.submit(synthetic_image(30, 30, seed=i))
        b.drain(timeout=30.0)
        assert b.stats.slo()["pass"] == 3 and b.stats.slo()["fail"] == 0
    # an impossible bound fails everything — the counter, not an error
    engine2 = make_engine()
    with ContinuousBatcher(engine2, linger_ms=1.0, slo_ms=0.0, timeout=30.0) as b2:
        b2.submit(synthetic_image(30, 30, seed=9))
        b2.drain(timeout=30.0)
        assert b2.stats.slo() == {
            "slo_ms": 0.0, "pass": 0, "fail": 1, "attainment": 0.0,
        }


def test_batcher_packs_every_lane_into_one_arena(monkeypatch):
    """Lanes of two buckets, lane 4 each, the last one partial (flushed
    at close), all packed into the arena made at construction: each
    result equals the synchronous wave's, and results kept from a lane
    do not move when later lanes overwrite the arena."""
    from repro.serve import admission

    engine = make_engine(buckets=[(32, 32), (32, 64)], max_batch=4)
    outs = []
    real = admission.pack_requests

    def spy(*args, **kw):
        outs.append(kw["out"])
        return real(*args, **kw)

    monkeypatch.setattr(admission, "pack_requests", spy)
    groups = [
        [synthetic_image(30, 60, seed=80 + i) for i in range(4)],   # whole arena
        [synthetic_image(30, 30, seed=90 + i) for i in range(4)],   # leading half
        [synthetic_image(20, 50, seed=100 + i) for i in range(4)],
        [synthetic_image(32, 32, seed=110 + i) for i in range(3)],  # phantom slot
    ]
    want = [engine.process(g) for g in groups]
    kept, copies = [], []
    b = ContinuousBatcher(engine, linger_ms=60_000.0, timeout=60.0)
    arena = b._arena
    assert arena.dtype == np.float32 and arena.size == 4 * 32 * 64
    with b:
        for g in groups[:3]:  # full lanes: only the fill trigger fires
            got = [t.result(timeout=60.0) for t in [b.submit(img) for img in g]]
            kept.append(got)
            copies.append([r.copy() for r in got])
        last = [b.submit(img) for img in groups[3]]
    kept.append([t.result() for t in last])
    copies.append([r.copy() for r in kept[-1]])
    assert b._arena is arena
    for got, was, w in zip(kept, copies, want):
        for r, c, ref in zip(got, was, w):
            assert (r == c).all() and (r == ref).all()
    assert len(outs) == 4
    for out in outs:
        assert out.ctypes.data == arena.ctypes.data and np.shares_memory(out, arena)
    assert [o.shape for o in outs] == [(4, 32, 64), (4, 32, 32), (4, 32, 64), (4, 32, 32)]
    assert b.stats.arena_lanes == len(b.stats.slot_occupancy) == 4


# ---------------- scheduler integration --------------------------------------
def test_run_engine_aot_mode_in_order_and_exact():
    from repro.stream.scheduler import FarmScheduler

    frames = [synthetic_image(40, 40, seed=100 + i) for i in range(8)]
    sched = FarmScheduler(PARAMS)
    got = list(
        sched.run_engine(
            iter(frames), max_batch=4, aot=True, linger_ms=1.0,
            slo_ms=1e6, buckets=[(40, 40)], timeout=60.0,
        )
    )
    assert len(got) == len(frames)
    for g, f in zip(got, frames):
        assert (g == canny_reference(f, PARAMS)).all()
    # the batcher's SLO plane landed in the scheduler's stats
    assert sched.stats.frames == len(frames)
    assert len(sched.stats.request_ms) == len(frames)
    assert sched.stats.slo()["pass"] == len(frames)


def test_run_engine_aot_infers_bucket_from_source_dims():
    from repro.stream import SyntheticStream
    from repro.stream.scheduler import FarmScheduler

    source = SyntheticStream(4, 32, 32, seed=0)
    sched = FarmScheduler(PARAMS)
    got = list(sched.run_engine(source, max_batch=2, aot=True, timeout=60.0))
    assert len(got) == 4

    sched2 = FarmScheduler(PARAMS)
    with pytest.raises(ValueError, match="bucket lattice up front"):
        list(sched2.run_engine(iter([np.zeros((32, 32))]), aot=True))
