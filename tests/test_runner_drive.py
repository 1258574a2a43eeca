"""Unit tests for scripts/runner_drive.py's runner-output parsing.

The hardware drive itself needs a real plug-in ($PJRT_PLUGIN); what CI can
pin is the contract between the C++ runner's stdout format
(cpp/pjrt_runner/runner.cc printf lines) and the parser that turns it
into the committed artifact — r2's 83k-img/s event-timing artifact showed
how silently a mis-parse can misrepresent a hardware run.
"""

import importlib.util
import os

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load():
    spec = importlib.util.spec_from_file_location(
        "runner_drive", os.path.join(REPO, "scripts", "runner_drive.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


RUNNER_STDOUT = """\
plugin /opt/pjrt/libtpu.so: PJRT API v0.54
devices: 1 (using device 0)
compiled StableHLO (39274.4 KB) in 20.58s
executable outputs: 4
timing: 200 iters, batch 1, depth 4: 55.10 img/s (18.15 ms/batch, incl. per-frame D2H)
det[0] cls=1 score=0.904 box=(50.6, -8.2, 164.6, 94.6)
det[1] cls=0 score=0.733 box=(312.3, 112.7, 458.9, 259.8)
OK
"""


def test_parse_runner_extracts_timing_and_detections():
    rd = _load()
    rec = rd.parse_runner(RUNNER_STDOUT)
    assert rec["artifact_kb"] == 39274.4
    assert rec["compile_s"] == 20.58
    assert rec["iters"] == 200
    assert rec["batch"] == 1
    assert rec["img_per_sec"] == 55.10
    assert rec["ms_per_frame"] == 18.15
    assert len(rec["detections"]) == 2
    cls, score, x1, y1, x2, y2 = rec["detections"][0]
    assert (cls, score) == ("1", "0.904")
    # negative coordinates must survive the regex (r2 real-plugin output
    # contained them)
    assert (x1, y1, x2, y2) == ("50.6", "-8.2", "164.6", "94.6")


def test_parse_runner_tolerates_failure_output():
    rd = _load()
    rec = rd.parse_runner("dlopen failed: no such file\n")
    assert rec["detections"] == []
    assert "img_per_sec" not in rec


def test_serve_smoke_round_trips_every_bucket(tmp_path):
    """ISSUE 8: runner_drive's serve-mode smoke — per-bucket export,
    CPU deserialize, zeros-batch execution, fixed-shape contract."""
    rec = _load().serve_smoke(str(tmp_path / "exp"), imsize=64,
                              buckets=(1, 2))
    assert rec["ok"] is True
    assert set(rec["buckets"]) == {"b1", "b2"}
    assert all(v["ok"] for v in rec["buckets"].values())
    assert rec["meta_serve_buckets"] == [1, 2]
