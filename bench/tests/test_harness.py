"""The harness finds every piece by name and refuses to run off the chip."""

from __future__ import annotations

import json
import os
import pathlib
import re
import shutil
import subprocess
import sys

import pytest

from bench import harness

ROOT = pathlib.Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_every_name_in_the_spec_has_its_file():
    for c in SPEC["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert any(w["config"] == c["name"] for w in SPEC["workloads"])
    for w in SPEC["workloads"]:
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
        config = json.loads((ROOT / dict((c["name"], c["file"]) for c in SPEC["configs"])[w["config"]]).read_text())
        assert (ROOT / "bench" / "entries" / f"{config['entry']}.py").is_file()
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()


def test_spec_keeps_the_contract_shapes():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer") for x in SPEC[k]]
    assert len(names) == len(set(names)) and all(NAME.match(n) for n in names)
    cells = {w["name"] for w in SPEC["workloads"]}
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    for m in SPEC["per_layer"]:
        moved = e2e[m["moves"]]
        assert set(m["workloads"]) <= set(moved.get("workloads", cells))
    for w in SPEC["workloads"]:
        reported = [m for m in SPEC["end_to_end"] if w["name"] in m.get("workloads", cells)]
        assert len(reported) >= 2
        assert any(w["name"] in m["workloads"] for m in SPEC["per_layer"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200


def test_cell_pieces_are_found_by_name():
    cell = harness.load_cell("stream_held")
    assert cell["config"]["entry"] == "stream"
    assert cell["traffic"]["replay"] == "pingpong"
    assert [m["name"] for m in cell["end_to_end"]] == ["stream_fps", "setup_s"]
    assert "strip_recompute_share" in [m["name"] for m in cell["per_layer"]]
    with pytest.raises(KeyError):
        harness.load_cell("no_such_cell")


def test_one_new_file_each_adds_a_config_a_mix_and_a_metric(tmp_path):
    """A later change adds a cell by adding files and spec entries only."""
    bench = tmp_path / "bench"
    shutil.copytree(ROOT / "bench", bench, ignore=shutil.ignore_patterns("tests", "__pycache__"))
    config = json.loads((bench / "configs" / "document_scans.json").read_text())
    config["classes"] = {"letter": [[3300, 2550]]}
    (bench / "configs" / "letter_scans.json").write_text(json.dumps(config))
    traffic = json.loads((bench / "traffic" / "a4_bulk.json").read_text())
    traffic["mix"] = {"letter": 1.0}
    (bench / "traffic" / "letter_bulk.json").write_text(json.dumps(traffic))
    (bench / "metrics" / "requests_done.py").write_text("def read(rec):\n    return len(rec['requests'])\n")
    spec = json.loads(json.dumps(SPEC))
    spec["configs"].append({"name": "letter_scans", "source": "ANSI/ASME Y14.1 letter at 300 dpi",
                            "file": "bench/configs/letter_scans.json", "reduced": [], "why": "letter scans"})
    spec["workloads"].append({"name": "upload_letter", "config": "letter_scans", "traffic": "letter_bulk",
                              "chips": 1, "why": "bulk letter scans"})
    spec["per_layer"].append({"name": "requests_done", "unit": "requests", "better": "higher",
                              "source": "program_counter", "layer": "admission", "moves": "mpx_per_s",
                              "workloads": ["upload_letter"]})
    next(m for m in spec["end_to_end"] if m["name"] == "mpx_per_s")["workloads"].append("upload_letter")
    cell = harness.load_cell("upload_letter", spec, bench)
    assert cell["config"]["classes"] == {"letter": [[3300, 2550]]}
    assert cell["traffic"]["mix"] == {"letter": 1.0}
    assert [m["name"] for m in cell["end_to_end"]] == ["mpx_per_s", "setup_s"]
    rec = {"requests": [{}] * 3}
    assert harness.read_metrics(cell["per_layer"], rec, bench) == {"requests_done": {"value": 3.0, "unit": "requests"}}


def test_a_reader_that_finds_nothing_leaves_the_metric_out():
    cell = harness.load_cell("upload_a4")
    rec = {"engine": {"padded_px": 0, "true_px": 0}, "trace": None, "dispatches": [], "t0": 0.0,
           "window_s": 1.0, "requests": []}
    assert harness.read_metrics(cell["per_layer"], rec) == {}


def _run(args, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=120)


def test_run_exits_nonzero_without_a_tpu():
    p = _run(["--workload", "stream_held", "--seed", "3", "--seconds", "1"], ROOT)
    assert p.returncode == 2 and p.stdout == ""
    assert "no TPU" in p.stderr


def test_run_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(["--workload", "upload_a4", "--seed", "3", "--seconds", "1"], tmp_path)
    assert p.returncode != 0 and p.stdout == ""


def test_an_unknown_device_has_no_peaks():
    from bench import costs

    assert costs.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        costs.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        costs.peaks("source")


@pytest.mark.parametrize("bucket", [64, 128, 512])
def test_algorithmic_bytes_follow_true_shapes_only(bucket):
    from bench import costs
    from repro.serve.engine import pack_requests, round_up

    shapes = [(321, 481), (1080, 1920), (3508, 2480)]
    imgs = [__import__("numpy").zeros(s, "float32") for s in shapes]
    padded, true_hw = pack_requests(imgs, round_up(3508, bucket), round_up(2480, bucket), bb=4)
    assert costs.canny_bytes(shapes) == 5 * sum(h * w for h, w in shapes)
    assert costs.canny_bytes([tuple(t) for t in true_hw[:3]]) == costs.canny_bytes(shapes)
    assert costs.canny_bytes(shapes) < 5 * padded.size
