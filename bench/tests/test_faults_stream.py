"""Stream cells: ``correct`` comes out false when the timed path breaks.

Each test drives a whole run (the chip's look skipped, tiny sizes, the
jnp backend) with one fault planted where the program produces its
answer; a sound run beside them shows the same cell passes.
"""

from __future__ import annotations

import pytest

from bench.tests import tiny


def test_sound_stream_run_is_correct():
    r = tiny.run(tiny.cell("stream_held"))
    assert r["correct"], r["checks"]
    assert r["load"]["compared_whole_frame_skips"] >= 1


@pytest.mark.parametrize("fault", ["answer_altered", "state_unchanged"])
def test_stream_fault_is_caught(monkeypatch, fault):
    from repro.stream.temporal import TemporalCanny

    step = TemporalCanny.step
    seen = {}

    def broken(self, frame):
        edges, cost = step(self, frame)
        if fault == "answer_altered":
            edges = edges.at[..., :4, :4].set(1 - edges[..., :4, :4])
        else:  # the first frame's edges are handed back for every later one
            edges = seen.setdefault(id(self), edges)
        return edges, cost

    monkeypatch.setattr(TemporalCanny, "step", broken)
    r = tiny.run(tiny.cell("stream_motion"))
    assert not r["correct"]


