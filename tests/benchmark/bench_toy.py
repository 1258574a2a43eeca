"""A throw-away benchmark root at toy size, made of NEW files only: the proof
that a configuration, a cell and a per-layer metric are added by adding files
and BENCHMARK.json entries, with no edit to a file of benchmark/.

The toy configurations keep every field of the real ones but the width (16),
the resolution (64) and bfloat16 (off: the CPU suite compares float32 with
float32); the traffic keeps every field but the sizes."""

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TOY_METRIC = '''"""A metric a later PR might add: steps or requests attempted per second."""


def read(rec):
    return rec.window["attempted"] / rec.window["window_s"]
'''

SIZES = {
    "train-b32": dict(batch=4, pool_batches=2, fetch_every=2),
    "serve-bulk": dict(pool_frames=8, sample=4, lead_in_requests=8,
                       stats_frames=4),
    "serve-bulk-soft": dict(pool_frames=8, sample=4, lead_in_requests=8,
                            stats_frames=4),
    "serve-live": dict(pool_frames=8, sample=4, stats_frames=4,
                       rate_per_s=20.0, burst=[1, 3]),
}


LIVE = "quality-serve-live"  # the cell PERF.md section 7 lists first: its
# traffic file, driver and readers are in benchmark/; only entries are missing


def add_live_cell(manifest: dict) -> None:
    """What a later PR writes into BENCHMARK.json to add the open-loop cell."""
    manifest["workloads"].append({
        "name": LIVE, "config": "quality-s2-w128", "traffic": "serve-live",
        "chips": 1, "why": "open loop below the knee, bursts of 1-16 frames"})
    manifest["end_to_end"].append({
        "name": "serve_p95_ms", "unit": "ms", "better": "lower",
        "bound": 0.1, "source": "host_clock", "workloads": [LIVE]})
    for name, unit, source in (
            ("engine_queue_wait_p50_ms.live", "ms", "program_span"),
            ("engine_batch_fill.live", "%", "program_counter"),
            ("serve_p50_ms.live", "ms", "host_clock"),
            ("generator_late_p95_ms.live", "ms", "host_clock"),
            ("predict_mfu.live", "%", "host_clock")):
        manifest["per_layer"].append({
            "name": name, "unit": unit, "source": source, "layer": "engine",
            "better": "higher" if unit == "%" else "lower",
            "moves": "serve_p95_ms", "workloads": [LIVE]})


def make_root(tmp: str) -> str:
    """Copy nothing but data: the manifest (plus one new cell with its
    metrics, plus one new metric with its reader), toy copies of the
    configurations and mixes, and the metric readers."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    add_live_cell(manifest)
    bench = os.path.join(tmp, "benchmark")
    os.makedirs(os.path.join(bench, "configs"))
    os.makedirs(os.path.join(bench, "workloads"))
    shutil.copytree(os.path.join(REPO, "benchmark", "layer_metrics"),
                    os.path.join(bench, "layer_metrics"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    for conf in manifest["configs"]:
        with open(os.path.join(REPO, conf["file"])) as f:
            config = json.load(f)
        config["fields"].update(hourglass_inch=16, imsize=64, amp=False)
        with open(os.path.join(tmp, conf["file"]), "w") as f:
            json.dump(config, f)
    for cell in manifest["workloads"]:
        name = cell["traffic"]
        with open(os.path.join(REPO, "benchmark", "workloads",
                               name + ".json")) as f:
            mix = json.load(f)
        mix.update(SIZES[name])
        if "engine" in mix:
            mix["engine"].update(buckets=[2, 4], queue=16)
        with open(os.path.join(bench, "workloads", name + ".json"), "w") as f:
            json.dump(mix, f)
    with open(os.path.join(bench, "layer_metrics", "toy_attempts_per_s.py"),
              "w") as f:
        f.write(TOY_METRIC)
    manifest["per_layer"].append({
        "name": "toy_attempts_per_s", "unit": "1/s", "better": "higher",
        "source": "host_clock", "layer": "engine",
        "moves": "setup_s"})
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return tmp
