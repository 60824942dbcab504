"""Direct unit tests for the serving engine (`serve/engine.py`).

Pins the request-plane contracts on their own, away from the kernel
tests: the shape helpers' edge cases, the bucket-cache hit/miss
accounting, mixed-size ``process`` crop exactness vs the serial oracle,
and the async submit/drain plane the stream scheduler rides.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.canny import CannyParams, canny_reference
from repro.data.images import synthetic_image
from repro.serve.engine import (
    BucketedCanny,
    CannyEngine,
    bucket_batch,
    next_pow2,
    pack_requests,
    round_up,
)

PARAMS = CannyParams(sigma=1.4, radius=2, low=0.08, high=0.2)


# ---------------- shape helpers ---------------------------------------------
@pytest.mark.parametrize(
    "x,m,want",
    [(0, 64, 0), (1, 64, 64), (63, 64, 64), (64, 64, 64), (65, 64, 128), (1, 1, 1)],
)
def test_round_up(x, m, want):
    assert round_up(x, m) == want


@pytest.mark.parametrize(
    "x,want", [(0, 1), (1, 1), (2, 2), (3, 4), (4, 4), (5, 8), (8, 8), (9, 16)]
)
def test_next_pow2(x, want):
    assert next_pow2(x) == want


@pytest.mark.parametrize(
    "n,lane,want",
    [
        (0, 1, 1), (1, 1, 1), (3, 1, 4),          # local: plain next_pow2
        (1, 2, 2), (3, 2, 4), (5, 8, 8),          # pow2 lanes fold in
        (1, 3, 3), (4, 3, 6), (9, 3, 18),         # non-pow2 lanes still divide
        (6, 4, 8),
    ],
)
def test_bucket_batch_always_divisible_by_lane(n, lane, want):
    got = bucket_batch(n, lane)
    assert got == want
    assert got % lane == 0 and got >= max(n, 1)


def test_bucket_batch_rejects_negative():
    with pytest.raises(ValueError):
        bucket_batch(-1)


# ---------------- request packing -------------------------------------------
def _pack_oracle(images, hb, wb, bb):
    """The plain packing: edge-pad each page with np.pad into a zero batch."""
    batch = np.zeros((bb, hb, wb), np.float32)
    for slot, img in enumerate(images):
        h, w = img.shape
        batch[slot] = np.pad(
            img.astype(np.float32), ((0, hb - h), (0, wb - w)), mode="edge"
        )
    return batch


def _page(rng, shape, dtype):
    if dtype == np.uint8:
        return rng.integers(0, 256, shape, dtype=np.uint8)
    return rng.random(shape).astype(dtype)


@pytest.mark.parametrize(
    "shapes,dtype,hb,wb,bb,lane",
    [
        ([(5, 7)], np.float32, 8, 12, 1, 1),           # h < hb and w < wb
        ([(8, 7)], np.float32, 8, 12, 1, 1),           # h == hb
        ([(5, 12)], np.float32, 8, 12, 1, 1),          # w == wb
        ([(8, 12)], np.float32, 8, 12, 1, 1),          # both equal
        ([(1, 1)], np.float32, 4, 6, 1, 1),            # one pixel
        ([(5, 7), (8, 3)], np.uint8, 8, 12, 2, 1),
        ([(5, 7), (8, 3)], np.float64, 8, 12, 2, 1),
        ([(3, 4), (6, 9), (2, 2)], np.float32, 8, 12, 4, 1),  # phantom slot
        ([(3, 4), (6, 9)], np.float32, 8, 12, None, 3),        # bb from lane
    ],
)
def test_pack_requests_matches_np_pad(shapes, dtype, hb, wb, bb, lane):
    rng = np.random.default_rng(len(shapes) * 100 + hb)
    images = [_page(rng, s, dtype) for s in shapes]
    before = [img.copy() for img in images]
    batch, true_hw = pack_requests(images, hb, wb, bb=bb, lane=lane)
    want_bb = bucket_batch(len(images), lane) if bb is None else bb
    assert batch.dtype == np.float32 and batch.shape == (want_bb, hb, wb)
    np.testing.assert_array_equal(batch, _pack_oracle(images, hb, wb, want_bb))
    assert not batch[len(images):].any()
    want_hw = np.full((want_bb, 2), (hb, wb), np.int32)
    want_hw[: len(shapes)] = shapes
    np.testing.assert_array_equal(true_hw, want_hw)
    for img, was in zip(images, before):
        np.testing.assert_array_equal(img, was)
        assert not np.shares_memory(batch, img)


def test_pack_requests_rejects_too_many_requests():
    images = [np.zeros((4, 4), np.float32)] * 3
    with pytest.raises(ValueError, match="exceed batch bucket"):
        pack_requests(images, 4, 4, bb=2)


# ---------------- backend registry ------------------------------------------
def test_register_serving_backend_rejects_duplicates():
    from repro.core.canny.pipeline import (
        register_backend,
        register_serving_backend,
        resolve_serving_backend,
    )

    fn = resolve_serving_backend("fused")  # forces kernel registration
    assert fn is not None
    with pytest.raises(ValueError, match="already registered"):
        register_serving_backend("fused", lambda *a: None)
    with pytest.raises(ValueError, match="already registered"):
        register_backend("fused", lambda *a: None)
    # the originals survive the rejected overwrite
    assert resolve_serving_backend("fused") is fn
    # deliberate replacement is allowed, then restored
    register_serving_backend("fused", fn, override=True)
    assert resolve_serving_backend("fused") is fn


# ---------------- bucket cache accounting -----------------------------------
def test_bucketed_canny_cache_hit_miss_counts():
    from repro.core.canny.pipeline import resolve_serving_backend

    det = BucketedCanny(resolve_serving_backend("fused"), PARAMS, bucket_multiple=32)
    assert det.compiles == 0
    det(jnp.asarray(synthetic_image(40, 40, seed=1)))  # miss → (1, 64, 64)
    assert det.compiles == 1
    det(jnp.asarray(synthetic_image(33, 50, seed=2)))  # hit: same bucket
    assert det.compiles == 1
    det(jnp.asarray(synthetic_image(40, 70, seed=3)))  # miss → (1, 64, 96)
    assert det.compiles == 2
    det(jnp.asarray(np.stack([synthetic_image(40, 40, seed=4)] * 2)))  # b miss
    assert det.compiles == 3
    det(jnp.asarray(synthetic_image(64, 64, seed=5)))  # hit: exact bucket edge
    assert det.compiles == 3


def test_engine_stats_track_hits_and_misses():
    engine = CannyEngine(PARAMS, bucket_multiple=32, max_batch=4)
    engine.process([synthetic_image(33, 33, seed=0)])
    assert (engine.stats.requests, engine.stats.batches, engine.stats.compiles) == (
        1, 1, 1,
    )
    # same bucket, batch grows 1 → 2: new (batch, h, w) key compiles again
    engine.process([synthetic_image(40, 40, seed=i) for i in range(2)])
    assert (engine.stats.requests, engine.stats.compiles) == (3, 2)
    # replay both profiles: pure cache hits
    engine.process([synthetic_image(35, 60 % 33 + 20, seed=9)])
    engine.process([synthetic_image(41, 44, seed=i) for i in range(2)])
    assert engine.stats.compiles == 2
    assert engine.stats.requests == 6


def test_engine_mixed_size_process_is_bit_exact():
    engine = CannyEngine(PARAMS, bucket_multiple=32, max_batch=4)
    sizes = [(33, 47), (64, 64), (50, 70), (33, 47), (21, 90)]
    reqs = [synthetic_image(h, w, seed=10 + i) for i, (h, w) in enumerate(sizes)]
    out = engine.process(reqs)
    for r, e in zip(reqs, out):
        assert e.shape == r.shape and e.dtype == np.uint8
        assert (e == canny_reference(r, PARAMS)).all()
    assert engine.stats.true_px == sum(h * w for h, w in sizes)
    assert engine.stats.padded_px >= engine.stats.true_px
    assert engine.stats.pad_overhead() >= 0.0


def test_engine_process_rejects_batched_request():
    engine = CannyEngine(PARAMS)
    with pytest.raises(ValueError, match="expected \\(h,w\\)"):
        engine.process([np.zeros((2, 32, 32), np.float32)])


# ---------------- async submit/drain plane ----------------------------------
def test_submit_drain_matches_process():
    sizes = [(33, 47), (64, 64), (33, 47)]
    reqs = [synthetic_image(h, w, seed=20 + i) for i, (h, w) in enumerate(sizes)]

    sync = CannyEngine(PARAMS, bucket_multiple=32, max_batch=4)
    want = sync.process(reqs)

    engine = CannyEngine(PARAMS, bucket_multiple=32, max_batch=4)
    tickets = [engine.submit(r) for r in reqs]
    assert not any(t.done for t in tickets)
    assert engine.drain() == 3
    assert all(t.done for t in tickets)
    for t, w in zip(tickets, want):
        assert (t.result() == w).all()
    # a drained engine drains to zero; results keep resolving
    assert engine.drain() == 0
    assert (tickets[0].result() == want[0]).all()


def test_ticket_result_auto_drains():
    engine = CannyEngine(PARAMS, bucket_multiple=32)
    req = synthetic_image(40, 40, seed=30)
    ticket = engine.submit(req)
    assert (ticket.result() == canny_reference(req, PARAMS)).all()  # no drain()
    assert ticket.done
    assert engine.stats.requests == 1


def test_submit_rejects_batched_frame():
    engine = CannyEngine(PARAMS)
    with pytest.raises(ValueError, match="expected \\(h,w\\)"):
        engine.submit(np.zeros((2, 32, 32), np.float32))


def test_drain_failure_fails_tickets_instead_of_stranding_them():
    """A wave whose process() raises must poison its tickets — a waiter
    in result() gets the exception rather than spinning forever."""
    engine = CannyEngine(PARAMS, bucket_multiple=32)
    ticket = engine.submit(synthetic_image(20, 20, seed=1))

    def boom(images):
        raise RuntimeError("kernel exploded")

    engine.process = boom
    with pytest.raises(RuntimeError, match="kernel exploded"):
        engine.drain()
    assert ticket.done
    with pytest.raises(RuntimeError, match="kernel exploded"):
        ticket.result()


def test_submitted_waves_share_bucket_batches():
    """Requests accumulated between drains batch together: 4 same-bucket
    submits at max_batch=4 run as ONE batch-grid launch."""
    engine = CannyEngine(PARAMS, bucket_multiple=32, max_batch=4)
    tickets = [engine.submit(synthetic_image(33, 40, seed=40 + i)) for i in range(4)]
    engine.drain()
    assert engine.stats.batches == 1
    assert engine.stats.requests == 4
    assert all(t.done for t in tickets)


# ---------------- bounded waits ----------------------------------------------
def test_engine_validates_timeout_knobs():
    with pytest.raises(ValueError):
        CannyEngine(PARAMS, timeout=0.0)
    with pytest.raises(ValueError):
        CannyEngine(PARAMS, max_pending=0)


def test_engine_drain_timeout_zero_is_nonblocking_probe():
    """timeout=0 is the Ticket polling path: a wave in flight elsewhere
    means 'ran 0 requests now', never a block."""
    import threading

    from repro.distributed.fault_tolerance import StreamTimeout

    engine = CannyEngine(PARAMS, bucket_multiple=32)
    engine.submit(synthetic_image(20, 20, seed=7))
    assert engine._drain_lock.acquire(blocking=False)  # simulate a stuck wave
    try:
        assert engine.drain(timeout=0) == 0
        with pytest.raises(StreamTimeout, match="drain"):
            engine.drain(timeout=0.1)
    finally:
        engine._drain_lock.release()
    assert engine.drain() == 1  # the stuck wave cleared; work proceeds


def test_ticket_result_timeout_on_stuck_wave():
    """A ticket whose wave never completes raises a typed StreamTimeout
    (default budget from the engine) instead of hanging the caller."""
    from repro.distributed.fault_tolerance import StreamTimeout

    engine = CannyEngine(PARAMS, bucket_multiple=32, timeout=0.2)
    ticket = engine.submit(synthetic_image(20, 20, seed=8))
    assert engine._drain_lock.acquire(blocking=False)
    try:
        with pytest.raises(StreamTimeout):
            ticket.result()  # engine default budget
        with pytest.raises(StreamTimeout):
            ticket.result(timeout=0.05)  # per-call override
    finally:
        engine._drain_lock.release()
    assert (np.asarray(ticket.result()) == np.asarray(
        canny_reference(synthetic_image(20, 20, seed=8), PARAMS)
    )).all()


def test_drain_probe_interleaved_resolves_in_submission_order(monkeypatch):
    """Regression for the drain(timeout=0) probe: interleaving submits
    with non-blocking probes must resolve tickets in SUBMISSION order —
    the probe is a real wave over whatever is pending, never a reorder."""
    from repro.serve.engine import Ticket

    order: list[int] = []
    orig = Ticket._resolve
    monkeypatch.setattr(
        Ticket, "_resolve", lambda self, res: (order.append(id(self)), orig(self, res))
    )

    engine = CannyEngine(PARAMS, bucket_multiple=32, max_batch=4)
    a = engine.submit(synthetic_image(20, 20, seed=1))
    assert engine.drain(timeout=0) == 1  # probe with work pending runs it
    b = engine.submit(synthetic_image(20, 20, seed=2))
    c = engine.submit(synthetic_image(40, 40, seed=3))  # different bucket
    d = engine.submit(synthetic_image(20, 20, seed=4))
    assert engine.drain(timeout=0) == 3
    assert engine.drain(timeout=0) == 0  # idle probe: no-op, no block
    # resolution order == submission order, across buckets and probes
    assert order == [id(t) for t in (a, b, c, d)]
    assert all(t.done for t in (a, b, c, d))


def test_concurrent_submitters_vs_max_pending_no_drops():
    """N submitter threads against a small max_pending: bounded admission
    may make them wait, but every ticket resolves exactly once — no
    deadlock, no dropped ticket."""
    import threading

    engine = CannyEngine(
        PARAMS, bucket_multiple=32, max_batch=4, max_pending=3, timeout=60.0
    )
    want = canny_reference(synthetic_image(20, 20, seed=0), PARAMS)
    tickets: list = []
    lock = threading.Lock()
    done = threading.Event()

    def submitter():
        for _ in range(4):
            t = engine.submit(synthetic_image(20, 20, seed=0))
            with lock:
                tickets.append(t)

    def drainer():  # frees admission slots until every submitter finishes
        while not done.is_set():
            engine.drain(timeout=0)

    threads = [threading.Thread(target=submitter) for _ in range(5)]
    helper = threading.Thread(target=drainer, daemon=True)
    helper.start()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60.0)
    done.set()
    helper.join(timeout=60.0)
    assert not any(t.is_alive() for t in threads), "submitters deadlocked"
    engine.drain()
    assert len(tickets) == 20
    assert all((t.result() == want).all() for t in tickets)
    assert engine.stats.requests == 20


def test_admission_timeout_names_the_engine():
    """StreamTimeout.what carries the engine's name — under a fleet of
    engines the timeout says WHICH admission queue was full."""
    from repro.distributed.fault_tolerance import StreamTimeout

    engine = CannyEngine(
        PARAMS, bucket_multiple=32, max_pending=1, timeout=0.1,
        name="front-door",
    )
    engine.submit(synthetic_image(20, 20, seed=1))
    with pytest.raises(StreamTimeout) as ei:
        engine.submit(synthetic_image(20, 20, seed=2))
    assert "front-door" in ei.value.what
    assert "max_pending=1" in ei.value.what


def test_submit_max_pending_sheds_load():
    """Bounded admission: a full pending queue times out the submitter
    instead of buffering without limit; a drain frees the slot."""
    from repro.distributed.fault_tolerance import StreamTimeout

    engine = CannyEngine(PARAMS, bucket_multiple=32, max_pending=2, timeout=0.1)
    engine.submit(synthetic_image(20, 20, seed=1))
    engine.submit(synthetic_image(20, 20, seed=2))
    with pytest.raises(StreamTimeout, match="admission"):
        engine.submit(synthetic_image(20, 20, seed=3))
    assert engine.drain() == 2
    engine.submit(synthetic_image(20, 20, seed=3))  # slot freed
    assert engine.drain() == 1
