"""The control fails the comparison that decides ``correct``.

The control is the plain reference computed in bfloat16, the precision
below the float32 the configurations state, put in the program's place
for the outputs a run compares.
"""

from __future__ import annotations

import pytest

from bench import check
from bench.tests import tiny


@pytest.mark.parametrize("workload", ["upload_a4", "stream_held"])
def test_control_in_the_programs_place_fails(workload, monkeypatch):
    real = check.compare
    monkeypatch.setattr(check, "compare", lambda outputs, config, failed, control=None:
                        real(outputs, config, failed, control="bfloat16"))
    r = tiny.run(tiny.cell(workload), seconds=1.5)
    assert not r["correct"]
    assert r["checks"]["mismatch_ppm"]["value"] > r["checks"]["mismatch_ppm"]["limit"]
