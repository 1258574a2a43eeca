"""What the next `model_config` PR will do, done here in a temp root with a
family that is not the hourglass: a configuration whose `fields` hold no
hourglass key, a traffic file under a name nothing here has seen, a driver of
its own (token-id payloads through `ServingEngine`, a NamedTuple of two leaves
back), a per-layer metric appended last, and the `workloads` lists of the
shared metrics extended by the new cell. New files and manifest entries alone:
no file of benchmark/ or tests/benchmark/ is edited, none is special-cased.

The cell then runs through `run_cell` at the size its own `toy` blocks give,
is `correct` against the driver's own plain reference and not under a planted
fault, reports the new metric in a traced run, and its manifest passes every
check of test_bench_manifest.py."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import bench_toy  # noqa: E402
import test_bench_manifest as checks  # noqa: E402

from benchmark import drivers as drivers_package  # noqa: E402
from benchmark import run as bench_run  # noqa: E402

CELL = "rows-w64-token-backlog"
TRAFFIC_NAME = "token-backlog"

CONFIG = {
    "name": "rows-w64",
    "source": "a test's own: an embedding table, a sum over the prompt, a head",
    "fields": {"vocab": 1024, "width": 64, "prompt": 16},
    "toy": {"vocab": 32, "width": 8},
    "reduced": [],
}

SOURCE = {"source": CONFIG["source"], "read_from": "this file",
          "widths": {"vocab": 1024, "width": 64}}

TRAFFIC = {
    "driver": "token_rows",
    "engine": {"buckets": [64], "depth": 2, "queue": 256, "max_wait_ms": 5.0},
    "pool_prompts": 128, "sample": 32, "lead_in_requests": 128,
    "limits": {"logit_gap": 1e-3, "value_gap": 1e-3},
    "why": "queue always full of int32[16] prompts; one greedy token back",
    "who": "nobody: the shape of a cell whose payload is not an image",
    "toy": {"engine": {"buckets": [2, 4], "queue": 16},
            "pool_prompts": 8, "sample": 4, "lead_in_requests": 8},
}

DRIVER = '''"""Driver `token_rows`: int32 prompts through ServingEngine, the backlog
window of `serve_backlog` kept as it is."""

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.drivers import serve_backlog


class Answer(NamedTuple):
    token: jax.Array
    logit: jax.Array


@jax.jit
def program(variables, prompts):
    logits = variables["table"][prompts].sum(axis=1) @ variables["head"]
    return Answer(jnp.argmax(logits, -1).astype(jnp.int32), logits.max(-1))


class Cell(serve_backlog.Cell):
    def setup(self):
        from real_time_helmet_detection_tpu.obs.metrics import MetricsRegistry
        from real_time_helmet_detection_tpu.serving import ServingEngine
        ctx, cfg, eng = self.ctx, self.ctx.config, self.p["engine"]
        rng = np.random.default_rng([ctx.seed, 1])
        self.frames = rng.integers(
            0, cfg["vocab"], (int(self.p["pool_prompts"]), cfg["prompt"]),
            dtype=np.int32)
        self.registry = MetricsRegistry()
        self.engine = ServingEngine(
            program, self.weights(), (cfg["prompt"],), np.int32,
            buckets=tuple(eng["buckets"]),
            max_wait_ms=float(eng["max_wait_ms"]), depth=int(eng["depth"]),
            queue_capacity=int(eng["queue"]), metrics=self.registry,
            **({"tracer": ctx.engine_tracer} if ctx.engine_tracer else {}))
        if ctx.sabotage is not None:
            ctx.sabotage(self)
        for b in sorted(self.engine.buckets):
            for f in [self.engine.submit(self.frames[i % len(self.frames)])
                      for i in range(b)]:
                f.result(timeout=600)

    def weights(self):
        cfg = self.ctx.config
        rng = np.random.default_rng([self.ctx.seed, 2])
        return {"table": rng.standard_normal(
                    (cfg["vocab"], cfg["width"])).astype(np.float32),
                "head": rng.standard_normal(
                    (cfg["width"], cfg["vocab"])).astype(np.float32)}

    def check(self):
        """The plain reference: numpy, float64, one prompt at a time. How far
        the served token's logit lies below the reference's best, and the
        served logit from that best."""
        w = {k: v.astype(np.float64) for k, v in self.weights().items()}
        picks = self.sample(self.first)
        token_gap, value_gap = [], []
        for i in picks:
            served = self.futs[i].result()
            prompt = self.frames[i % len(self.frames)]
            logits = w["table"][prompt].sum(axis=0) @ w["head"]
            token_gap.append(logits.max() - logits[int(served.token)])
            value_gap.append(abs(float(served.logit) - logits.max()))
        numbers = {"logit_gap": float(max(token_gap, default=np.nan)),
                   "value_gap": float(max(value_gap, default=np.nan))}
        return numbers, (0 if picks else 1)
'''

READER = '''"""Prompt tokens the engine took in per second of the window."""


def read(rec):
    if not rec.window.get("images"):
        return None
    return rec.window["images"] * rec.config["prompt"] / rec.window["window_s"]
'''

METRIC = {"name": "prompt_tokens_per_s.bulk", "unit": "tokens/s",
          "better": "higher", "source": "host_clock", "layer": "engine",
          "moves": "serve_img_per_s", "workloads": [CELL]}
SHARED = ("serve_img_per_s", "setup_trace_lower_s", "setup_backend_compile_s",
          "engine_batch_fill.bulk")


def add_the_family(root: str, manifest: dict) -> None:
    """New files under `root`, new entries in `manifest`: nothing else."""
    bench = os.path.join(root, "benchmark")
    os.makedirs(os.path.join(bench, "drivers"), exist_ok=True)
    file = "configs/%s.json" % CONFIG["name"]
    for rel, text in (
            (file, json.dumps(CONFIG)),
            ("sources/%s.json" % CONFIG["name"], json.dumps(SOURCE)),
            ("workloads/%s.json" % TRAFFIC_NAME, json.dumps(TRAFFIC)),
            ("drivers/%s.py" % TRAFFIC["driver"], DRIVER),
            ("layer_metrics/%s.py" % METRIC["name"], READER)):
        path = os.path.join(bench, rel)
        assert not os.path.exists(path), path
        with open(path, "w") as f:
            f.write(text)
    manifest["configs"].append({
        "name": CONFIG["name"], "source": CONFIG["source"],
        "file": "benchmark/" + file, "reduced": [],
        "why": "a family with no hourglass field"})
    manifest["workloads"].append({
        "name": CELL, "config": CONFIG["name"], "traffic": TRAFFIC_NAME,
        "chips": 1, "why": TRAFFIC["why"]})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if m["name"] in SHARED:
            m["workloads"].append(CELL)
    manifest["per_layer"].append(METRIC)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)


@pytest.fixture(scope="module")
def later_tree(tmp_path_factory):
    """The tree of that later PR (data and its driver; published sizes), with
    its drivers directory on the package's path as a checkout's would be."""
    root = str(tmp_path_factory.mktemp("later_tree"))
    add_the_family(root, bench_toy.copy_data(bench_toy.REPO, root))
    patch = pytest.MonkeyPatch()
    patch.setattr(drivers_package, "__path__", list(drivers_package.__path__)
                  + [os.path.join(root, "benchmark", "drivers")])
    yield root
    patch.undo()
    sys.modules.pop("benchmark.drivers." + TRAFFIC["driver"], None)


@pytest.fixture(scope="module")
def root(later_tree, tmp_path_factory):
    return bench_toy.make_root(str(tmp_path_factory.mktemp("bench_root")),
                               src=later_tree)


def _cell(root, trace=0, sabotage=None):
    result = bench_run.run_cell(CELL, 2 ** 31 + 27, 1.0, trace, root=root,
                                allow_cpu=True, sabotage=sabotage)
    return json.loads(json.dumps(result))


def test_the_manifest_of_the_later_tree_passes_every_check(later_tree):
    manifest = checks.manifest(later_tree)
    assert manifest["per_layer"][-1] == METRIC
    assert not any(k.startswith("hourglass") or k == "imsize"
                   for k in CONFIG["fields"])
    checks.check_all(manifest, later_tree)


def test_the_new_cell_runs_and_prints_the_contracts_line(root):
    line = _cell(root)
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checked"]
    assert line["correct"] is True, line["checked"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"serve_img_per_s", "setup_s"}
    assert set(line["checked"]) == set(TRAFFIC["limits"])
    for c in line["checked"].values():
        assert c["value"] <= c["limit"]


def test_a_token_altered_where_it_is_produced_is_not_correct(root):
    def altered(cell):
        real = cell.engine._fetch

        def fetch(out, b):
            host = real(out, b)
            return host._replace(token=(host.token + 1)
                                 % cell.ctx.config["vocab"])
        cell.engine._fetch = fetch
    line = _cell(root, sabotage=altered)
    assert line["correct"] is False
    c = line["checked"]["logit_gap"]
    assert c["value"] > c["limit"], line["checked"]


def test_a_traced_run_reports_the_metric_that_was_appended_last(root):
    line = _cell(root, trace=1)
    assert line["correct"] is True, line["checked"]
    tokens = line["metrics"][METRIC["name"]]
    assert tokens["unit"] == "tokens/s" and tokens["value"] > 0
    assert line["metrics"]["engine_batch_fill.bulk"]["value"] > 0
    assert line["metrics"]["toy_attempts_per_s"]["value"] > 0


def test_underscored_modules_are_not_drivers(later_tree):
    manifest = checks.manifest(later_tree)
    manifest["workloads"].append(dict(manifest["workloads"][-1],
                                      name="underscored", traffic="shared"))
    with open(os.path.join(later_tree, "benchmark", "workloads",
                           "shared.json"), "w") as f:
        json.dump(dict(TRAFFIC, driver="_serve"), f)
    with pytest.raises(SystemExit, match="names driver '_serve'"):
        bench_run.resolve_cell(later_tree, manifest, "underscored")
