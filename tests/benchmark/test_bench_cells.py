"""Every cell end to end at toy size on the CPU, through the hook the command
line does not expose (`run_cell(..., allow_cpu=True)`), from a throw-away root
made of new files only (bench_toy.py); and the same runs with the timed path
broken underneath, which must come out `correct: false`."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import bench_toy  # noqa: E402

from benchmark import run as bench_run  # noqa: E402

CELLS = ["flagship-train-b32", "flagship-serve-bulk", "quality-serve-bulk",
         bench_toy.LIVE]
LINE_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_toy.make_root(str(tmp_path_factory.mktemp("bench_root")))


def _cell(root, name, trace=0, sabotage=None, seed=2 ** 31 + 11):
    result = bench_run.run_cell(name, seed, 1.0, trace, root=root,
                                allow_cpu=True, sabotage=sabotage)
    return json.loads(json.dumps(result))  # what the last line would carry


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_and_prints_the_contracts_line(root, name):
    line = _cell(root, name)
    assert list(line) == LINE_KEYS + ["checked"]
    assert line["correct"] is True, line["checked"]
    assert line["attempted"] > 0 and line["failed"] == 0
    manifest = bench_run.load_manifest(root)
    want = {m["name"] for m in
            bench_run.metrics_of(manifest, "end_to_end", name)}
    assert set(line["metrics"]) == want and "setup_s" in want
    for m in line["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    for c in line["checked"].values():
        assert c["value"] <= c["limit"]


@pytest.mark.parametrize("name", CELLS[:2])
def test_traced_run_reports_per_layer_metrics_and_the_new_one(root, name):
    line = _cell(root, name, trace=1)
    assert list(line) == LINE_KEYS + ["breakdown", "checked"]
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    # the metric bench_toy added as a file and an entry, nothing else
    assert line["metrics"]["toy_attempts_per_s"]["value"] > 0
    declared = {m["name"] for m in bench_run.metrics_of(
        bench_run.load_manifest(root), "per_layer", name)}
    assert set(line["metrics"]) <= declared
    # no device trace on the CPU: a roofline is left out, never 0
    assert not any("roofline" in k for k in line["metrics"])


# ---- the timed path broken underneath ---------------------------------------

def _state_unchanged(cell):
    import jax
    import jax.numpy as jnp
    real = cell.runner

    def runner(state, batch, step_idx):
        kept = jax.tree.map(jnp.copy, state)
        _, losses = real(state, batch, step_idx)
        return kept, losses
    runner.stage = real.stage
    cell.runner = runner


def _half_batch(cell):
    real = cell.runner.stage

    def stage(batch):
        half = type(batch)(**{k: v[:len(v) // 2]
                              for k, v in vars(batch).items()})
        return real(half)
    cell.runner.stage = stage


def _altered_answer(cell):
    real = cell.engine._fetch

    def fetch(out, b):
        host = real(out, b)
        boxes = np.array(host.boxes)
        boxes[:, 0, :] += 8.0  # one box of every answer, 8 pixels off
        return host._replace(boxes=boxes)
    cell.engine._fetch = fetch


@pytest.mark.parametrize("name,fault,caught_by", [
    ("flagship-train-b32", _state_unchanged, "change_norm_gap"),
    ("flagship-train-b32", _half_batch, "loss_gap_step1"),
    ("flagship-serve-bulk", _altered_answer, "box_gap_px"),
    ("quality-serve-bulk", _altered_answer, "box_gap_px"),
    (bench_toy.LIVE, _altered_answer, "box_gap_px"),
])
def test_a_broken_timed_path_is_not_correct(root, name, fault, caught_by):
    line = _cell(root, name, sabotage=fault)
    assert line["correct"] is False, line["checked"]
    c = line["checked"][caught_by]
    assert c["value"] > c["limit"], line["checked"]


def test_command_line_refuses_to_run_off_the_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=bench_toy.REPO, env=env, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr
