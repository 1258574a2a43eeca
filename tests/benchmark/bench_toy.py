"""A throw-away benchmark root at toy size, made of NEW files only: the proof
that a configuration, a cell and a per-layer metric are added by adding files
and BENCHMARK.json entries, with no edit to a file of benchmark/.

What "toy size" means is each data file's own business: a configuration's
`toy` block overrides its `fields`, a traffic mix's `toy` block its
parameters (a nested group such as `engine` key by key). `make_root` knows no
field, family or traffic name, so a file a later PR adds is shrunk by the
block it brings; a file without one is an error that names the file. (The one
cell named here, `add_live_cell`, is the entries of a cell whose files are in
benchmark/ and whose entries are not in BENCHMARK.json yet.)"""

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DATA_DIRS = ("configs", "workloads", "layer_metrics", "sources")

TOY_METRIC = '''"""A metric a later PR might add: steps or requests attempted per second."""


def read(rec):
    return rec.window["attempted"] / rec.window["window_s"]
'''


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


def copy_data(src: str, dst: str) -> dict:
    """`src`'s manifest and data files (no code) copied to `dst` as they are;
    returns the manifest. What a later PR's tree starts from."""
    with open(os.path.join(src, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    for name in DATA_DIRS:
        shutil.copytree(os.path.join(src, "benchmark", name),
                        os.path.join(dst, "benchmark", name),
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(src, "BENCHMARK.json"), dst)
    return manifest


def _overlay(base: dict, over: dict) -> dict:
    out = dict(base)
    for key, value in over.items():
        both = isinstance(value, dict) and isinstance(base.get(key), dict)
        out[key] = _overlay(base[key], value) if both else value
    return out


def _toy(path: str) -> tuple:
    with open(path) as f:
        data = json.load(f)
    if "toy" not in data:
        raise ValueError('%s has no "toy" block: the sizes a CPU test runs '
                         "it at belong in the file itself" % path)
    return data, data.pop("toy")


def make_root(tmp: str, src: str = REPO) -> str:
    """Copy nothing but data from `src`: the manifest (plus one cell added by
    entries alone with metrics of its own, plus one new metric with its
    reader), each configuration and mix under its own `toy` block, and the
    metric readers."""
    with open(os.path.join(src, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    if not any(c["name"] == LIVE for c in manifest["workloads"]):
        add_live_cell(manifest)  # a tree that has the cell keeps its own
    bench = os.path.join(tmp, "benchmark")
    os.makedirs(os.path.join(bench, "configs"))
    os.makedirs(os.path.join(bench, "workloads"))
    shutil.copytree(os.path.join(src, "benchmark", "layer_metrics"),
                    os.path.join(bench, "layer_metrics"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    for conf in manifest["configs"]:
        config, toy = _toy(os.path.join(src, conf["file"]))
        config["fields"] = _overlay(config["fields"], toy)
        with open(os.path.join(tmp, conf["file"]), "w") as f:
            json.dump(config, f)
    for name in sorted({cell["traffic"] for cell in manifest["workloads"]}):
        mix, toy = _toy(os.path.join(src, "benchmark", "workloads",
                                     name + ".json"))
        with open(os.path.join(bench, "workloads", name + ".json"), "w") as f:
            json.dump(_overlay(mix, toy), f)
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


def toy_fields(config: str) -> dict:
    """The `fields` of one of the repo's configurations under its `toy`
    block."""
    data, toy = _toy(os.path.join(REPO, "benchmark", "configs",
                                  config + ".json"))
    return _overlay(data["fields"], toy)
