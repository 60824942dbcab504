"""Metric arithmetic on recorded run records."""

from __future__ import annotations

import math

import pytest

from bench import harness


# the open-loop mix's readers, kept for a tail-latency cell (PERF.md, Open questions)
STEADY = {"end_to_end": [{"name": "req_p95_ms", "unit": "ms"}, {"name": "setup_s", "unit": "s"}],
          "per_layer": [{"name": "admission_wait_ms.steady", "unit": "ms"},
                        {"name": "launch_ms.steady", "unit": "ms"}]}


def _metrics(workload, rec, per_layer=False):
    cell = STEADY if workload == "upload_steady" else harness.load_cell(workload)
    out = harness.read_metrics(cell["per_layer" if per_layer else "end_to_end"], rec)
    return {k: v["value"] for k, v in out.items()}


def _req(due, complete, dispatch=None, enqueue=None, shape=(10, 10), ok=True):
    return {"cls": "x", "shape": shape, "px": shape[0] * shape[1], "due": due,
            "submit": due, "enqueue": due if enqueue is None else enqueue,
            "dispatch": due if dispatch is None else dispatch, "complete": complete, "ok": ok}


def test_p95_is_over_every_request_of_the_window_from_its_due_time():
    # 20 requests due inside a 10 s window, one due after it; latencies 1..20 ms
    reqs = [_req(100 + i * 0.5, 100 + i * 0.5 + (i + 1) / 1e3) for i in range(20)]
    reqs.append(_req(111.0, 111.5))
    rec = {"t0": 100.0, "window_s": 10.0, "requests": reqs, "unanswered": 0, "setup_s": 12.5}
    m = _metrics("upload_steady", rec)
    assert m["req_p95_ms"] == pytest.approx(19.0)  # nearest rank: the 19th of 20
    assert m["setup_s"] == 12.5
    reqs[18]["ok"] = False  # a failed request is a miss: the 19th is now 20 ms
    assert _metrics("upload_steady", rec)["req_p95_ms"] == pytest.approx(20.0)


def test_unanswered_requests_count_as_misses():
    reqs = [_req(100 + i * 0.1, 100 + i * 0.1 + 0.001) for i in range(10)]
    rec = {"t0": 100.0, "window_s": 10.0, "requests": reqs, "unanswered": 1, "setup_s": 1.0}
    assert "req_p95_ms" not in _metrics("upload_steady", rec)


def test_throughput_counts_true_pixels_completed_inside_the_window():
    reqs = [_req(0.0, 1.0, shape=(1000, 1000)), _req(0.0, 2.0, shape=(500, 200)),
            _req(0.0, 2.5, shape=(1000, 1000)), _req(0.0, 1.5, shape=(1000, 1000), ok=False)]
    rec = {"t0": 0.0, "window_s": 2.0, "requests": reqs, "unanswered": 0, "setup_s": 1.0}
    assert _metrics("upload_a4", rec)["mpx_per_s"] == pytest.approx(1.1 / 2.0)


def test_bulk_layer_metrics():
    reqs = [_req(0.0, 0.5, dispatch=0.1, shape=(2000, 1000)) for _ in range(3)]
    rec = {"t0": 0.0, "window_s": 1.0, "requests": reqs, "unanswered": 0,
           "dispatches": [(0.1, 3, 4), (0.2, 2, 2), (1.5, 1, 1)],
           "engine": {"padded_px": 400, "true_px": 300, "batches": 2},
           "device_kind": "TPU v5 lite",
           "trace": {"window_s": 1.0, "busy_s": 0.25, "module_s": 0.03}}
    m = _metrics("upload_a4", rec, per_layer=True)
    assert m["slot_occupancy.bulk"] == pytest.approx((0.75 + 1.0) / 2)
    assert m["padded_px_share.bulk"] == pytest.approx(25.0)
    assert m["device_idle_pct.bulk"] == pytest.approx(75.0)
    # 3 x 2 MPx x 5 B = 30 MB in 30 ms: 1 TB/s would be 122% of 819 GB/s
    assert m["canny_roofline_pct.bulk"] == pytest.approx(100 * 30e6 / 0.03 / 819e9)


def test_steady_layer_metrics():
    reqs = [_req(1.0, 1.1, enqueue=1.0, dispatch=1.0 + w / 1e3) for w in (1, 2, 9)]
    rec = {"t0": 0.0, "window_s": 5.0, "requests": reqs, "launch_ms": [3.0, 4.0, 40.0, 5.0]}
    m = _metrics("upload_steady", rec, per_layer=True)
    assert m["admission_wait_ms.steady"] == pytest.approx(2.0)
    assert m["launch_ms.steady"] == pytest.approx(4.5)


def test_stream_metrics():
    rec = {"t0": 0.0, "window_s": 10.0, "frames_in_window": 2000, "setup_s": 20.0,
           "cold_strips_per_frame": 9.0,
           "stream": {"frames": 2000, "launches": 5000, "frontend_strips": 9000},
           "trace": {"window_s": 10.0, "busy_s": 1.0, "module_s": 1.0}}
    assert _metrics("stream_held", rec) == {"stream_fps": 200.0, "setup_s": 20.0}
    m = _metrics("stream_held", rec, per_layer=True)
    assert m["strip_recompute_share"] == pytest.approx(50.0)
    assert m["sweeps_per_frame"] == pytest.approx(2.5)
    assert m["device_ms_per_frame"] == pytest.approx(0.5)
    assert m["device_idle_pct.stream"] == pytest.approx(90.0)
    assert not any(math.isnan(v) for v in m.values())
