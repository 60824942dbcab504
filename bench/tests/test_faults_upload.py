"""Upload cells: ``correct`` comes out false when the timed path breaks.

Each test drives a whole run (the chip's look skipped, tiny sizes, the
jnp backend) with one fault planted where the program produces its
answer; a sound run beside them shows the same cell passes.
"""

from __future__ import annotations

import numpy as np
import pytest

from bench.tests import tiny


def test_sound_upload_run_is_correct():
    r = tiny.run(tiny.cell("upload_a4"))
    assert r["correct"], r["checks"]
    assert r["attempted"] > 10 and r["failed"] == 0


def test_sound_open_loop_upload_run_is_correct():
    """The open-loop branch (no declared cell yet) on the same pages."""
    r = tiny.run(tiny.cell("upload_a4", rate_per_s=40))
    assert r["correct"], r["checks"]
    assert r["attempted"] > 10 and r["failed"] == 0
    assert r["load"]["generator_late_ms_max"] is not None


@pytest.mark.parametrize("fault", ["answer_altered", "half_batch_left_out"])
def test_upload_fault_is_caught(monkeypatch, fault):
    from repro.serve.aot import AotCannyEngine

    run_packed = AotCannyEngine.run_packed

    def broken(self, batch, true_hw):
        out = np.array(run_packed(self, batch, true_hw))
        if fault == "answer_altered":
            out[0, :4, :4] ^= 1
        else:  # only the lane's first half computed
            out[len(out) // 2 :] = 0
        return out

    monkeypatch.setattr(AotCannyEngine, "run_packed", broken)
    r = tiny.run(tiny.cell("upload_a4"))
    assert not r["correct"]
    assert r["checks"]["mismatch_ppm"]["value"] > r["checks"]["mismatch_ppm"]["limit"]


def test_upload_request_that_never_comes_is_caught(monkeypatch):
    from bench import harness
    from repro.serve.admission import ContinuousBatcher, SloTicket

    submit = ContinuousBatcher.submit
    calls = {"n": 0}

    def lossy(self, image, timeout=None):
        calls["n"] += 1
        if calls["n"] == 12:  # admitted, never answered
            return SloTicket(self, image.shape, self._clock())
        return submit(self, image)

    monkeypatch.setattr(ContinuousBatcher, "submit", lossy)
    entry = harness.load_entry("upload")
    monkeypatch.setattr(entry, "DRAIN_S", 0.5)
    monkeypatch.setattr(harness, "load_entry", lambda name: entry)
    r = tiny.run(tiny.cell("upload_a4"))
    assert not r["correct"]
    assert r["checks"]["unanswered"]["value"] == 1


